"""Serving bench — scheduler saturation vs offered load + no-stall proof,
plus the SPMD mesh-scaling sweep.

Measurements on the reduced smollm config (CPU-sized, CI-friendly):

  1. **Load sweep**: submit increasing request counts against a fixed slot
     pool and record tok/s, TTFT/ITL percentiles and slot occupancy per
     offered load — the saturation curve the paper's 3,700 img/s number is
     an operating point of.
  2. **Chunked-admission stall check**: while a long prompt is being
     admitted chunk-by-chunk, an already-running request must keep
     producing decode tokens.  We count decode tokens generated between
     the long prompt's admission start and its first token, for chunked
     vs whole-prompt admission.  Chunked must be > 0 (the acceptance
     criterion); whole-prompt admission is the stalling baseline.
  3. **Mesh sweep** (``--mesh dp,mp ...``): the paper scales throughput by
     replicating precision-specific PEs onto a bigger device (§V, Arria 10
     -> Stratix 10); our analogue is weak-scaling the continuous batcher
     over the device mesh — per-device decode slots held constant, tok/s of
     the batched-decode phase recorded per mesh shape.  Needs dp*mp visible
     devices (CI: XLA_FLAGS=--xla_force_host_platform_device_count=8).
     Results go to ``--out`` (CI uploads ``BENCH_serving_spmd.json``).
  4. **Host-gap profile**: the StepProfiler brackets every dispatch with
     ``block_until_ready``, so per decode/prefill-chunk step we record
     measured device-time vs host-gap (scheduler bookkeeping between
     syncs) — the fused-decode planning input, not a guess.
  5. **Tracing overhead spike**: decode-phase tok/s with the flight
     recorder on vs off (alternating best-of-N); asserts <3% overhead.
     ``--trace-out`` saves the Perfetto timeline itself.
  6. **Fused-decode sweep** (``--fused-decode``): paged decode tok/s and
     host-gap for the fused single-dispatch kernel vs the legacy
     two-dispatch composition, and for ragged live-slot vs always-padded
     dispatch at partial occupancy (CI uploads
     ``BENCH_decode_fused.json``).

Results print as ``name,value,derived`` CSV lines and are recorded to
``--out`` (CI uploads ``BENCH_serving.json`` with the other artifacts).
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import time

import jax
import numpy as np

from repro.configs import get_config
from repro.models import build_model, reduce_for_smoke
from repro.runtime.serving import (ContinuousBatcher, Request,
                                   RequestOptions, ServingConfig)


def _setup():
    cfg = dataclasses.replace(reduce_for_smoke(get_config("smollm-135m")),
                              dtype="float32")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _setup_spmd():
    """Mesh-sweep model: big enough that a decode step is weight-streaming
    bound (the regime where sharding the batch pays), small enough for CI.
    The smoke config is dispatch-overhead bound — sharding overhead would
    swamp the signal."""
    from repro.models.config import ModelConfig
    cfg = ModelConfig(name="spmd-bench", n_layers=4, d_model=512, n_heads=8,
                      n_kv_heads=8, head_dim=64, d_ff=2048, vocab=2048,
                      dtype="float32", layer_pattern=("attn",),
                      ffn_pattern=("dense",))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _setup_spmd_quant():
    """Quantized-act variant of the mesh-sweep model (2xT serving form,
    ternary weights x 2-bit acts): per-row act scales let the pure-DP
    shard_map dispatch invoke the tuned Pallas path per shard, so the
    weak-scaling sweep now has a quantized-act row set next to fp32."""
    from repro.models import to_serving
    from repro.models.config import ModelConfig
    cfg = ModelConfig(name="spmd-bench-2xT", n_layers=4, d_model=512,
                      n_heads=8, n_kv_heads=8, head_dim=64, d_ff=2048,
                      vocab=2048, dtype="float32", layer_pattern=("attn",),
                      ffn_pattern=("dense",), precision="2xT")
    model = build_model(cfg)
    params = to_serving(model.init(jax.random.PRNGKey(0)), cfg)
    return cfg, model, params


def _mk_requests(cfg, n, rng, *, lo=6, hi=20, max_new=8):
    return [Request(rid=i, tokens=rng.integers(0, cfg.vocab,
                                        (1, int(rng.integers(lo, hi + 1)))
                                        ).astype(np.int32),
        options=RequestOptions(max_new=max_new))
            for i in range(n)]


def load_sweep(cfg, model, params, loads=(2, 4, 8), n_slots=4):
    rows = []
    for n_req in loads:
        batcher = ContinuousBatcher(model, params,
        ServingConfig(n_slots=n_slots, s_max=32, chunk_size=8))
        rng = np.random.default_rng(n_req)
        t0 = time.time()
        for r in _mk_requests(cfg, n_req, rng):
            batcher.submit(r)
        done = batcher.run()
        wall = time.time() - t0
        assert len(done) == n_req, (len(done), n_req)
        s = batcher.metrics.summary()
        row = {
            "offered_requests": n_req,
            "n_slots": n_slots,
            "wall_s": wall,
            "tok_per_s": s["throughput"]["tok_per_s"],
            "ttft_ms": s["ttft_ms"],
            "itl_ms": s["itl_ms"],
            "queue_ms": s["queue_ms"],
            "slot_occupancy": s["scheduler"]["slot_occupancy"],
        }
        rows.append(row)
        print(f"serving_load_{n_req},{row['tok_per_s']:.1f},"
              f"ttft_p50={row['ttft_ms']['p50']:.1f}ms "
              f"occupancy={row['slot_occupancy']:.2f}")
    return rows


def stall_check(cfg, model, params, chunk_size):
    """Decode tokens produced by a running request while a long prompt is
    admitted.  Returns (decode_tokens_during_admission, admission_steps)."""
    batcher = ContinuousBatcher(model, params,
        ServingConfig(n_slots=2, s_max=48, chunk_size=chunk_size))
    rng = np.random.default_rng(0)
    short = Request(rid=0, tokens=rng.integers(0, cfg.vocab, (1, 4))
                    .astype(np.int32),
        options=RequestOptions(max_new=40))
    batcher.submit(short)
    while len(short.output) < 2:           # short request decoding steadily
        batcher.step()

    long_req = Request(rid=1, tokens=rng.integers(0, cfg.vocab, (1, 32))
                       .astype(np.int32),
        options=RequestOptions(max_new=2))
    before = len(short.output)
    batcher.submit(long_req)
    steps = 0
    while not long_req.output:             # until the long prompt's TTFT
        batcher.step()
        steps += 1
    return len(short.output) - before, steps


def _decode_phase(cfg, model, params, *, trace=None, n_slots=4,
                  decode_iters=24, chunk=8, seed=7):
    """Fill every slot, then time ``decode_iters`` fully-occupied decode
    steps (admission and its compiles excluded).  Returns (decode tok/s,
    batcher) — the batcher so callers can read its tracer/profiler."""
    max_new = n_slots + decode_iters + 8   # nobody finishes mid-window
    batcher = ContinuousBatcher(model, params,
        ServingConfig(n_slots=n_slots, s_max=chunk + max_new + 1,
                      chunk_size=chunk, trace=trace))
    rng = np.random.default_rng(seed)
    for r in _mk_requests(cfg, n_slots, rng, lo=4, hi=chunk,
                          max_new=max_new):
        batcher.submit(r)
    steps = 0
    while (batcher.queue or batcher._adm is not None) and steps < 10_000:
        batcher.step()                     # admission phase (+ compiles)
        steps += 1
    batcher.step()                         # one warm full-batch decode step
    before = batcher.metrics.decode_slot_tokens
    t0 = time.perf_counter()
    for _ in range(decode_iters):
        batcher.step()
    decode_s = time.perf_counter() - t0
    toks = batcher.metrics.decode_slot_tokens - before
    batcher.run()                          # drain
    return toks / max(decode_s, 1e-9), batcher


def _decode_phase_paged(cfg, model, params, *, fused, ragged, n_slots=4,
                        n_live=None, decode_iters=24, chunk=8, seed=7,
                        profile=False):
    """Paged twin of :func:`_decode_phase`: fill ``n_live`` slots (default
    all), then time ``decode_iters`` steady-state decode steps.  ``fused``
    and ``ragged`` select the single-dispatch kernel path and the live-slot
    occupancy-bucket dispatch respectively."""
    from repro.runtime.kvcache import PagedBatcher
    from repro.runtime.tracing import TraceConfig
    max_new = n_slots + decode_iters + 8   # nobody finishes mid-window
    batcher = PagedBatcher(model, params, ServingConfig(
        n_slots=n_slots, s_max=chunk + max_new + 1, chunk_size=chunk,
        kv_bits=8, block_size=4, fused_decode=fused, ragged_decode=ragged,
        trace=TraceConfig(profile=True) if profile else None))
    rng = np.random.default_rng(seed)
    for r in _mk_requests(cfg, n_live or n_slots, rng, lo=4, hi=chunk,
                          max_new=max_new):
        batcher.submit(r)
    steps = 0
    while (batcher.queue or batcher._adm is not None) and steps < 10_000:
        batcher.step()                     # admission phase (+ compiles)
        steps += 1
    batcher.step()                         # one warm steady-state step
    before = batcher.metrics.decode_slot_tokens
    t0 = time.perf_counter()
    for _ in range(decode_iters):
        batcher.step()
    decode_s = time.perf_counter() - t0
    toks = batcher.metrics.decode_slot_tokens - before
    batcher.run()                          # drain
    return toks / max(decode_s, 1e-9), batcher


def fused_decode_sweep(cfg, model, params, *, decode_iters=24):
    """The ISSUE-10 acceptance sweep: fused single-dispatch vs the legacy
    two-dispatch composition on the paged decode phase, plus ragged
    live-slot vs always-padded dispatch at partial occupancy.  Every row
    carries the profiler's host-gap numbers — the before/after evidence for
    the host-loop de-bugging (device-resident buffers, one jitted select,
    one sync per step)."""
    rows = []
    for fused, ragged, n_slots, n_live in (
            (True, True, 4, None),         # the default path
            (False, True, 4, None),        # unfused composition
            (True, True, 8, 2),            # ragged: 2 live of 8 slots
            (True, False, 8, 2)):          # padded: same load, full grid
        rate, b = _decode_phase_paged(
            cfg, model, params, fused=fused, ragged=ragged,
            n_slots=n_slots, n_live=n_live, decode_iters=decode_iters,
            profile=True)
        prof = b.profiler.summary()["decode"]
        row = {"fused": fused, "ragged": ragged, "n_slots": n_slots,
               "n_live": n_live or n_slots,
               "decode_tok_per_s": rate,
               "host_ms_p50": prof["host_ms"]["p50"],
               "device_ms_p50": prof["device_ms"]["p50"],
               "host_frac": prof["host_frac"]}
        rows.append(row)
        tag = (f"decode_fused_{'on' if fused else 'off'}"
               f"_{'ragged' if ragged else 'padded'}"
               f"_{row['n_live']}of{n_slots}")
        print(f"{tag},{rate:.1f},host_frac={row['host_frac']:.3f} "
              f"host_p50={row['host_ms_p50']:.3f}ms")
    by = {(r["fused"], r["ragged"], r["n_live"]): r for r in rows}
    speedups = {
        "fused_vs_unfused_full":
            by[(True, True, 4)]["decode_tok_per_s"] /
            max(by[(False, True, 4)]["decode_tok_per_s"], 1e-9),
        "ragged_vs_padded_2of8":
            by[(True, True, 2)]["decode_tok_per_s"] /
            max(by[(True, False, 2)]["decode_tok_per_s"], 1e-9),
    }
    for name, v in speedups.items():
        print(f"decode_fused_speedup_{name},{v:.2f},steady_state")
    return {"rows": rows, "speedups": speedups}


def host_gap_profile(cfg, model, params):
    """Measure (not guess) device-time vs host-gap per step phase: the
    StepProfiler brackets every dispatch with block_until_ready, so
    ``device_ms`` is the synchronous device wait and ``host_ms`` the
    scheduler bookkeeping gap before it — the fused-decode input the
    roadmap asks for."""
    from repro.runtime.tracing import TraceConfig
    _, batcher = _decode_phase(cfg, model, params,
                               trace=TraceConfig(profile=True))
    prof = batcher.profiler.summary()
    for label, s in sorted(prof.items()):
        print(f"serving_host_gap_{label},{s['host_ms']['p50']:.3f},"
              f"device_p50={s['device_ms']['p50']:.3f}ms "
              f"host_frac={s['host_frac']:.3f}")
    return prof


def tracing_overhead(cfg, model, params, *, rounds=3, max_overhead=0.03,
                     trace_out=None):
    """Spike bench: decode-phase tok/s with the flight recorder on vs off,
    ``rounds`` adjacent on/off pairs.  The reported overhead is the MIN
    over per-pair estimates: container scheduling noise only ever slows a
    run down, so every pair overstates the deterministic per-step tracer
    cost and the least-noisy pair bounds it tightest.  Asserts the tracer
    costs <3% tok/s."""
    from repro.runtime.tracing import TraceConfig
    traced, untraced = [], []
    doc = None
    for i in range(rounds):
        arms = [(True, traced), (False, untraced)]
        if i % 2:                          # alternate so drift cancels
            arms.reverse()
        for on, acc in arms:
            rate, b = _decode_phase(
                cfg, model, params, decode_iters=96,
                trace=TraceConfig(enabled=True) if on else None)
            acc.append(rate)
            if on:
                doc = b.tracer.to_perfetto(trace_out)
    pair_overheads = [1.0 - t / max(u, 1e-9)
                      for t, u in zip(traced, untraced)]
    overhead = min(pair_overheads)
    print(f"serving_tracing_overhead,{overhead:.4f},"
          f"pairs={[f'{o:.3f}' for o in pair_overheads]},"
          f"events={len(doc['traceEvents'])}")
    assert overhead < max_overhead, \
        f"tracing costs {overhead:.1%} tok/s (budget {max_overhead:.0%})"
    best = pair_overheads.index(overhead)
    return {"overhead_frac": overhead,
            "pair_overheads": pair_overheads,
            "traced_tok_per_s": traced[best],
            "untraced_tok_per_s": untraced[best],
            "trace_events": len(doc["traceEvents"])}


def _run_one_mesh(cfg, model, params, mesh, *, n_slots, decode_iters=16,
                  chunk=8):
    """Fill every slot, then time ``decode_iters`` fully-occupied batched
    decode steps (the phase the dp speedup claim is about).  Admission —
    which includes the per-slot compiles — happens before the window."""
    max_new = n_slots + decode_iters + 8   # nobody finishes mid-window
    batcher = ContinuousBatcher(model, params,
        ServingConfig(n_slots=n_slots, s_max=chunk + max_new + 1, chunk_size=chunk, mesh=mesh))
    rng = np.random.default_rng(7)
    t_start = time.perf_counter()
    for r in _mk_requests(cfg, n_slots, rng, lo=4, hi=chunk, max_new=max_new):
        batcher.submit(r)
    steps = 0
    while (batcher.queue or batcher._adm is not None) and steps < 10_000:
        batcher.step()                     # admission phase (+ compiles)
        steps += 1
    batcher.step()                         # one warm full-batch decode step

    before = batcher.metrics.decode_slot_tokens
    t0 = time.perf_counter()
    for _ in range(decode_iters):
        batcher.step()
    decode_s = time.perf_counter() - t0
    decode_toks = batcher.metrics.decode_slot_tokens - before

    done = batcher.run()                   # drain
    wall = time.perf_counter() - t_start
    assert len(done) == n_slots, (len(done), n_slots)
    s = batcher.metrics.summary()
    return {
        "n_slots": n_slots,
        "requests": n_slots,
        "wall_s": wall,
        "tok_per_s": s["throughput"]["tok_per_s"],
        "decode_tok_per_s": decode_toks / max(decode_s, 1e-9),
        "decode_tokens": decode_toks,
        "decode_phase_s": decode_s,
        "slot_occupancy": s["scheduler"]["slot_occupancy"],
    }


def mesh_sweep(cfg, model, params, mesh_specs, *, slots_per_dev=4,
               tag="serving_spmd", precision="fp32"):
    """Weak-scaling sweep: per-device slots constant, mesh shapes vary."""
    from repro.launch.mesh import parse_mesh
    rows = []
    for spec in mesh_specs:
        mesh = parse_mesh(spec)
        dp, mp = mesh.shape["data"], mesh.shape["model"]
        n_slots = slots_per_dev * dp * mp
        row = {"mesh": spec, "dp": dp, "mp": mp, "devices": dp * mp,
               "precision": precision}
        row.update(_run_one_mesh(cfg, model, params, mesh, n_slots=n_slots))
        rows.append(row)
        print(f"{tag}_{spec.replace(',', 'x')},"
              f"{row['decode_tok_per_s']:.1f},"
              f"total={row['tok_per_s']:.1f}tok/s slots={n_slots}")
    by_mesh = {r["mesh"]: r for r in rows}
    speedups = {}
    if "1,1" in by_mesh:
        base = by_mesh["1,1"]["decode_tok_per_s"]
        for spec, r in by_mesh.items():
            if spec != "1,1":
                speedups[f"decode_x_{spec.replace(',', 'x')}_vs_1x1"] = \
                    r["decode_tok_per_s"] / max(base, 1e-9)
    for name, v in speedups.items():
        print(f"{tag}_speedup_{name},{v:.2f},weak_scaling")
    return {"slots_per_device": slots_per_dev, "precision": precision,
            "rows": rows, "speedups": speedups}


def main(out=None, loads=(2, 4, 8), trace_out=None):
    cfg, model, params = _setup()
    rows = load_sweep(cfg, model, params, loads=tuple(loads))

    chunked_tokens, chunked_steps = stall_check(cfg, model, params, 8)
    stalled_tokens, stalled_steps = stall_check(cfg, model, params, 0)
    print(f"serving_admission_chunked,{chunked_tokens},"
          f"decode_tokens_during_{chunked_steps}_step_admission")
    print(f"serving_admission_whole_prompt,{stalled_tokens},"
          f"decode_tokens_during_{stalled_steps}_step_admission")
    # the tentpole claim: decode continues while a long prompt is admitted
    assert chunked_tokens > 0, \
        "chunked admission stalled decode (no tokens during admission)"

    result = {
        "load_sweep": rows,
        "admission": {
            "chunked": {"decode_tokens_during_admission": chunked_tokens,
                        "admission_steps": chunked_steps},
            "whole_prompt": {"decode_tokens_during_admission": stalled_tokens,
                             "admission_steps": stalled_steps},
        },
        # measured device-time vs host-gap per step phase (decode,
        # prefill_chunk) — block_until_ready-bracketed, not guessed
        "host_gap": host_gap_profile(cfg, model, params),
        "tracing": tracing_overhead(cfg, model, params,
                                    trace_out=trace_out),
    }
    if out:
        with open(out, "w") as f:
            json.dump(result, f, indent=1)
        print(f"wrote {out}")
    return result


def main_fused(out=None, decode_iters=24):
    cfg, model, params = _setup()
    result = {"fused_decode": fused_decode_sweep(cfg, model, params,
                                                 decode_iters=decode_iters)}
    if out:
        with open(out, "w") as f:
            json.dump(result, f, indent=1)
        print(f"wrote {out}")
    return result


def main_spmd(mesh_specs, out=None, slots_per_dev=4):
    cfg, model, params = _setup_spmd()
    if "1,1" not in mesh_specs:
        mesh_specs = ["1,1"] + list(mesh_specs)    # scaling baseline
    result = {"mesh_sweep": mesh_sweep(cfg, model, params, mesh_specs,
                                       slots_per_dev=slots_per_dev)}
    # quantized-act rows: the shard_map-dispatched Pallas path on the same
    # weak-scaling schedule (per-row act scales make it mesh-invariant)
    qcfg, qmodel, qparams = _setup_spmd_quant()
    result["mesh_sweep_quant_2xT"] = mesh_sweep(
        qcfg, qmodel, qparams, mesh_specs, slots_per_dev=slots_per_dev,
        tag="serving_spmd_2xT", precision="2xT")
    if out:
        with open(out, "w") as f:
            json.dump(result, f, indent=1)
        print(f"wrote {out}")
    return result


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=None, help="write BENCH_serving.json "
                    "(or BENCH_serving_spmd.json with --mesh) here")
    ap.add_argument("--loads", type=int, nargs="*", default=[2, 4, 8])
    ap.add_argument("--mesh", nargs="*", default=None, metavar="DP,MP",
                    help="run the SPMD mesh-scaling sweep instead of the "
                         "load sweep; '--mesh' alone sweeps 1,1 2,1 8,1 "
                         "(needs XLA_FLAGS=--xla_force_host_platform_"
                         "device_count=8 on CPU)")
    ap.add_argument("--slots-per-dev", type=int, default=4)
    ap.add_argument("--fused-decode", action="store_true",
                    help="run the fused-vs-unfused paged decode sweep "
                         "(ISSUE 10) instead of the load sweep; --out "
                         "writes BENCH_decode_fused.json")
    ap.add_argument("--trace-out", default=None, metavar="OUT.json",
                    help="also write the spike bench's Perfetto trace here "
                         "(CI uploads it with the other artifacts)")
    a = ap.parse_args()
    if a.fused_decode:
        main_fused(out=a.out)
    elif a.mesh is not None:
        specs = a.mesh or ["1,1", "2,1", "8,1"]
        main_spmd(specs, out=a.out, slots_per_dev=a.slots_per_dev)
    else:
        main(out=a.out, loads=a.loads, trace_out=a.trace_out)
