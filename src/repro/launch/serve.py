"""Serving launcher — quantized weights + chunked-prefill continuous batching.

    PYTHONPATH=src python -m repro.launch.serve --arch smollm-135m \
        --precision 2xT --kv-bits 8 --reduced --requests 4 --gen 16

Deployment flow (the paper's §III framework, LM-shaped):
  1. init/load params -> ``to_serving`` packs weights to k-bit HBM form
     (Table II config via --precision), folding alpha/dequant scales
     (BNS, eqs. 1/2);
  2. the continuous batcher admits prompts in fixed-size prefill chunks
     (bucketed shapes -> bounded jit compiles, warm tuning cache) while the
     integer-dot decode loop keeps serving every active slot;
  3. per-slot sampling (greedy, or --temperature/--top-k with a per-slot
     PRNG key) with optional per-token streaming (--stream);
  4. TTFT / ITL / queue-time percentiles and tok/s printed at the end
     (and dumped with --metrics-json).

Token LMs route through :class:`repro.runtime.serving.ContinuousBatcher`;
stub-frontend (embeds) and enc-dec archs keep a plain batched prefill+decode
loop (their inputs are not token streams the scheduler can chunk).
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCH_IDS, get_config
from repro.models import build_model, make_batch, reduce_for_smoke, to_serving
from repro.models.config import ShapeConfig
from repro.models.convert import serving_param_bytes
from repro.runtime.serving import (ContinuousBatcher, Request,
                                   RequestOptions, ServingConfig)


def _legacy_loop(model, params, cfg, args):
    """Batched prefill + greedy decode for embeds/enc-dec archs."""
    if args.autotune:
        from repro.core.precision import get_precision, signed
        from repro.kernels import engine, tuning
        entries = engine.tune_model_shapes(
            cfg, signed(get_precision(args.precision)),
            m_rows=(args.requests, args.requests * args.prompt_len))
        print(f"autotune: {len(entries)} shape classes -> "
              f"{tuning.cache_path()} (sweeps this run: "
              f"{tuning.stats()['sweeps']})")
    s_max = args.prompt_len + args.gen
    shape = ShapeConfig("serve", args.prompt_len, args.requests, "prefill")
    batch = make_batch(cfg, shape, key=jax.random.PRNGKey(1))

    prefill = jax.jit(lambda p, b: model.prefill(p, b, s_max))
    decode = jax.jit(lambda p, t, c, i: model.decode_step(p, t, c, i))

    t0 = time.time()
    logits, cache = prefill(params, batch)
    logits.block_until_ready()
    t_prefill = time.time() - t0

    tok = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
    generated = [np.asarray(tok)]
    t0 = time.time()
    for i in range(args.gen - 1):
        if cfg.frontend == "embeds":
            step_in = jnp.zeros((args.requests, 1, cfg.d_model), jnp.float32)
        else:
            step_in = tok
        logits, cache = decode(params, step_in, cache,
                               jnp.int32(args.prompt_len + i))
        tok = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
        generated.append(np.asarray(tok))
    jax.block_until_ready(logits)
    t_decode = time.time() - t0
    toks = np.concatenate(generated, axis=1)
    tps = args.requests * (args.gen - 1) / max(t_decode, 1e-9)
    print(f"prefill: {args.requests} reqs x {args.prompt_len} tok in "
          f"{t_prefill*1e3:.0f} ms; decode: {tps:.1f} tok/s "
          f"({t_decode/max(args.gen-1,1)*1e3:.1f} ms/step)")
    print(f"sample generations (first 8 tokens/request):\n{toks[:, :8]}")
    assert np.all(np.isfinite(np.asarray(logits)))
    return toks


def _trace_config(args):
    """``--trace/--profile/--metrics-interval`` -> a TraceConfig (or None).

    The flight recorder is also armed when only profiling or snapshot
    streaming is requested — both ride on the tracer — but the Perfetto
    file is written only when --trace names one.
    """
    if not (args.trace or args.profile or args.metrics_interval):
        return None
    from repro.runtime.tracing import TraceConfig
    snapshot_path = None
    if args.metrics_interval:
        if not args.metrics_json:
            raise SystemExit("--metrics-interval needs --metrics-json "
                             "(snapshot stream path is derived from it)")
        base = args.metrics_json
        base = base[:-5] if base.endswith(".json") else base
        snapshot_path = base + ".snapshots.jsonl"
    return TraceConfig(
        enabled=True, buffer=args.trace_buffer, path=args.trace,
        snapshot_path=snapshot_path,
        snapshot_interval=args.metrics_interval,
        profile=args.profile)


def _report_trace(batcher, args):
    """Post-run flight-recorder export: Perfetto file, snapshot stream
    tail, per-phase device/host profile summary."""
    tracer = getattr(batcher, "tracer", None)
    if tracer is None or not tracer.enabled:
        return
    if args.trace:
        doc = tracer.to_perfetto(args.trace)
        print(f"trace -> {args.trace} ({len(doc['traceEvents'])} events, "
              f"{tracer.dropped} dropped)")
    if tracer.snapshotter is not None:
        tracer.snapshotter.final(batcher.metrics)
        print(f"metrics snapshots -> {tracer.snapshotter.path} "
              f"({tracer.snapshotter.lines_written} lines)")
    profilers = [p for p in [getattr(batcher, "profiler", None)] if p]
    for lane in getattr(batcher, "lanes", []):        # AdaptiveServer
        if lane.profiler is not None:
            profilers.append(lane.profiler)
    for prof in profilers:
        for label, s in sorted(prof.summary().items()):
            print(f"profile[{label}]: {s['steps']} steps, device "
                  f"{s['device_ms']['p50']:.2f} ms p50, host gap "
                  f"{s['host_ms']['p50']:.2f} ms p50 "
                  f"(host_frac {s['host_frac']:.1%})")


def synthetic_prompts(cfg, args) -> list[np.ndarray]:
    """The launcher's ``--requests`` prompts, (1, L) int32 each, drawn from
    a fixed seed.  Lengths are ragged (``--prompt-len`` minus 0, 1 or 2) to
    exercise the shape buckets."""
    rng = np.random.default_rng(1)
    return [rng.integers(0, cfg.vocab, (1, max(1, args.prompt_len - rid % 3)))
            .astype(np.int32) for rid in range(args.requests)]


def batcher_loop(model, params, cfg, args, mesh=None):
    """Continuous batching through the scheduler v2 (SPMD when --mesh).
    Returns every request's generated token stream, in request order."""
    s_max = args.prompt_len + args.gen
    sc = ServingConfig(
        n_slots=args.slots or args.requests, s_max=s_max,
        prompt_len=args.prompt_len, chunk_size=args.chunk_size,
        autotune=args.autotune, mesh=mesh,
        kv_bits=args.kv_bits, block_size=args.kv_block_size,
        pool_bytes=args.pool_bytes or None,
        prefix_cache=args.prefix_cache,
        reserve=args.reserve, preemption=args.preemption,
        brownout=args.brownout, speculative=args.speculative,
        draft_precision=args.draft_precision, draft_k=args.draft_k,
        trace=_trace_config(args))
    adaptive = args.brownout
    if args.paged or adaptive or args.speculative:
        from repro.runtime.kvcache import PagedBatcher, paged_block_bytes
        if not sc.block_size:
            from repro.kernels import engine
            attn_shape = dict(
                b=sc.n_slots, kv=cfg.n_kv_heads,
                g=max(cfg.n_heads // max(cfg.n_kv_heads, 1), 1), dh=cfg.dh,
                s_max=s_max, kv_bits=args.kv_bits)
            if args.autotune:
                # sweep candidate pool block sizes (the paged kernel's
                # sequence tile) so the lookup below returns a measured
                # recommendation instead of the cold-cache default — for
                # both dispatch shapes the decode loop can take: the
                # two-dispatch paged-attention layer and the fused
                # attention+projection kernel (its tile preference can
                # differ, and the sweep records it under attn_fused_decode)
                engine.autotune_kv_block_size(**attn_shape)
                engine.autotune_fused_block_size(d=cfg.d_model, **attn_shape)
            sc = dataclasses.replace(
                sc, block_size=engine.preferred_kv_block_size(**attn_shape))
            print(f"--kv-block-size 0 -> {sc.block_size} "
                  f"({'tuned' if args.autotune else 'tuning-cache'} pick)")
        if adaptive:
            from repro.runtime.adaptive import AdaptiveServer
            batcher = AdaptiveServer(model, params, sc)
            print(f"adaptive serving: {len(batcher.lanes)} precision lanes "
                  f"(rung 0 {'speculative, ' if sc.speculative else ''}"
                  f"kv ladder 16/8/4"
                  + (f", rung 3 = {sc.draft_precision} weights"
                     if len(batcher.lanes) > 3 else "")
                  + f"); SLO classes: {sorted(batcher.classes)}")
        else:
            batcher = PagedBatcher(model, params, sc)
            print(f"paged KV cache: {batcher.num_blocks - 1} blocks x "
                  f"{batcher.block_size} positions at kv_bits={args.kv_bits} "
                  f"({paged_block_bytes(cfg, batcher.block_size, args.kv_bits)} "
                  f"B/block), prefix cache "
                  f"{'on' if args.prefix_cache else 'off'}, "
                  f"reserve={args.reserve}, preemption={args.preemption}")
            if sc.speculative:
                print(f"self-speculative decoding: {sc.draft_precision} "
                      f"draft, k={sc.draft_k}, fp-verified (lossless)")
    else:
        batcher = ContinuousBatcher(model, params, sc)
    if mesh is not None:
        from repro.parallel.sharding import serving_shard_factors
        dp, tp = serving_shard_factors(cfg, mesh, batcher.n_slots)
        print(f"SPMD serving on mesh data={mesh.shape['data']} "
              f"model={mesh.shape['model']}: decode batch sharded {dp}-way, "
              f"tensor-parallel {tp}-way "
              f"({'pure-DP (params replicated)' if tp == 1 else 'TP'})")
    chunk = getattr(batcher, "chunk_size", None)
    if chunk is None and adaptive:
        chunk = batcher.lanes[0].chunk_size
    if chunk:
        print(f"chunked prefill: chunk={chunk}, prompt buckets "
              f"= multiples of {chunk} (1 compiled chunk shape)")
    else:
        print("whole-prompt admission (chunked prefill disabled/unsupported)")

    slo_cycle = (["premium", "standard", "batch"] if args.slo == "mixed"
                 else [args.slo])

    def stream_cb(req, tok, finished):
        mark = "<eos>" if finished else ""
        print(f"  [rid {req.rid}] tok {tok}{mark}", flush=True)

    for rid, tokens in enumerate(synthetic_prompts(cfg, args)):
        batcher.submit(Request(
            rid=rid,
            tokens=tokens,
            options=RequestOptions(
                max_new=args.gen,
                temperature=args.temperature,
                top_k=args.top_k,
                seed=args.seed,
                slo=slo_cycle[rid % len(slo_cycle)],
                on_token=stream_cb if args.stream else None)))
    done = batcher.run()
    assert len(done) == args.requests, (len(done), args.requests)

    print(batcher.metrics.format())
    streams = [list(r.output) for r in sorted(done, key=lambda r: r.rid)]
    print("sample generations (first 8 tokens/request):\n"
          f"{np.array([s[:8] for s in streams])}")
    if args.metrics_json:
        with open(args.metrics_json, "w") as f:
            json.dump(batcher.metrics.summary(), f, indent=1)
        print(f"metrics -> {args.metrics_json}")
    _report_trace(batcher, args)
    return streams


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, default="smollm-135m")
    ap.add_argument("--precision", default="2xT")
    ap.add_argument("--kv-bits", type=int, default=8,
                    help="KV-cache storage width.  Dense batcher: 0 = model "
                         "dtype, 8/4 = quantized in-cache.  --paged: 16 = "
                         "raw blocks, 8/4 = quantized blocks")
    ap.add_argument("--paged", action="store_true",
                    help="serve through the paged KV cache (block pool + "
                         "radix prefix sharing, runtime.kvcache)")
    ap.add_argument("--kv-block-size", type=int, default=16,
                    help="positions per paged KV block (0 -> tuned pick "
                         "from the autotune cache)")
    ap.add_argument("--prefix-cache", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="radix prefix sharing across requests (--paged)")
    ap.add_argument("--reserve", choices=["prompt", "budget"],
                    default="prompt",
                    help="--paged admission policy: 'prompt' reserves only "
                         "the prompt's blocks (decode allocates on demand, "
                         "admits aggressively), 'budget' reserves the whole "
                         "generation budget up front (never preempts)")
    ap.add_argument("--preemption", choices=["recompute", "off"],
                    default="recompute",
                    help="--paged pool-exhaustion policy: 'recompute' "
                         "preempts the latest-admitted request and replays "
                         "it via chunked prefill (radix suffix hits make "
                         "that cheap); 'off' stalls starved slots until "
                         "blocks free up")
    ap.add_argument("--pool-bytes", type=int, default=0,
                    help="--paged pool byte budget (0 -> size the pool to "
                         "n_slots+1 full sequences); lets you overcommit "
                         "the pool below the workload's aggregate budget")
    ap.add_argument("--slo", default="standard",
                    choices=["premium", "standard", "batch", "mixed"],
                    help="SLO class tagged on the synthetic requests "
                         "('mixed' cycles premium/standard/batch).  With "
                         "--brownout the class picks the request's "
                         "latency targets and how deep down the precision "
                         "ladder it may be degraded; plain batchers ignore "
                         "it")
    ap.add_argument("--brownout", action="store_true",
                    help="serve through the AdaptiveServer: SLO-routed "
                         "multi-precision lanes (kv 16/8/4 rungs, then the "
                         "--draft-precision weight variant) that degrade "
                         "NEW admissions under pressure instead of "
                         "queueing; active slots keep their exact streams. "
                         "Needs a float --precision primary (the low-bit "
                         "variants are packed from it at startup)")
    ap.add_argument("--speculative", action="store_true",
                    help="self-speculative decoding: the --draft-precision "
                         "variant drafts --draft-k tokens per slot, the "
                         "full-precision weights verify them in ONE "
                         "windowed decode step; output is bit-identical "
                         "to fp-greedy (lossless).  Implies the paged "
                         "cache; needs a float --precision primary")
    ap.add_argument("--draft-precision", default="2xT",
                    help="PAPER_CONFIGS precision of the low-bit weight "
                         "variant (speculative drafts + brownout rung 3)")
    ap.add_argument("--draft-k", type=int, default=3,
                    help="draft tokens per speculative round")
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--slots", type=int, default=0,
                    help="decode slots (0 -> one per request)")
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--chunk-size", type=int, default=None,
                    help="prefill chunk (None -> auto; 0 -> whole-prompt)")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="0 = greedy; > 0 samples with a per-slot PRNG key")
    ap.add_argument("--top-k", type=int, default=0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--stream", action="store_true",
                    help="print tokens as they are generated")
    ap.add_argument("--metrics-json", default=None,
                    help="dump the serving metrics summary to this file")
    ap.add_argument("--trace", default=None, metavar="OUT.json",
                    help="record the serving flight recorder and export a "
                         "Perfetto/chrome://tracing timeline to this file "
                         "(scheduler steps, admissions, prefill chunks, "
                         "decode dispatches, per-request flow arrows)")
    ap.add_argument("--trace-buffer", type=int, default=65536,
                    help="flight-recorder ring capacity in events "
                         "(drop-oldest beyond this; drops are counted)")
    ap.add_argument("--profile", action="store_true",
                    help="bracket each device dispatch with "
                         "block_until_ready and measure device-time vs "
                         "host-gap per step (adds sync overhead; implies "
                         "the flight recorder)")
    ap.add_argument("--metrics-interval", type=int, default=0,
                    help="stream a Metrics.summary() snapshot (+numeric "
                         "delta) every N scheduler steps to "
                         "<metrics-json stem>.snapshots.jsonl "
                         "(needs --metrics-json)")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--autotune", action="store_true",
                    help="pre-tune Pallas tiles for the scheduler's shape "
                         "buckets (persists to the tuning cache; serving "
                         "then never re-tunes)")
    ap.add_argument("--mesh", default=None, metavar="DP,MP",
                    help="serve SPMD over a (data, model) device mesh, e.g. "
                         "'2,4' (token-LM batcher path only; needs dp*mp "
                         "visible devices)")
    return ap


def build(args):
    """Validate ``args`` and build what serving needs: the model, its
    serving-form (packed) params, its config and the mesh (or None)."""
    from repro.launch.mesh import parse_mesh
    mesh = parse_mesh(args.mesh)

    paged = args.paged or args.brownout or args.speculative
    if (args.brownout or args.speculative):
        from repro.core.precision import (A_FLOAT, W_FLOAT, get_precision,
                                          signed)
        p = signed(get_precision(args.precision))
        if p.w_mode != W_FLOAT or p.a_mode != A_FLOAT:
            raise SystemExit(
                f"--precision {args.precision}: --brownout/--speculative "
                "need a float primary — the low-bit lanes and the draft "
                "variant are packed down from the float weights at startup "
                "(try --precision fp32)")
    if paged and args.kv_bits == 0:
        args.kv_bits = 16                  # dense spelling of "unquantized"
    if not paged and args.kv_bits not in (0, 4, 8):
        raise SystemExit(
            f"--kv-bits {args.kv_bits}: the dense cache stores int8/int4 "
            "codes (or model dtype with 0); 16 is a --paged storage width")
    # paged serving owns KV quantization in the block pool; the in-model
    # dense-cache quantizer stays off
    cfg = get_config(args.arch, precision=args.precision,
                     kv_bits=0 if paged else args.kv_bits)
    if args.reduced:
        cfg = reduce_for_smoke(cfg)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    base_bytes = serving_param_bytes(params)
    # pack under per-shard K alignment only when TP will actually shard the
    # params: pure-DP models replicate (tp=1 keeps the laxer global
    # alignment -> packed words, not the int8-codes fallback), and the
    # legacy embeds/enc-dec loop serves single-device regardless of --mesh
    pack_tp = 1
    if mesh is not None and cfg.kind == "lm" and cfg.frontend != "embeds":
        from repro.parallel.sharding import pure_dp
        pack_tp = 1 if pure_dp(cfg, mesh) else mesh.shape["model"]
    params = to_serving(params, cfg, tp=pack_tp)
    packed_bytes = serving_param_bytes(params)
    print(f"weights: {base_bytes/1e6:.1f} MB bf16-form -> "
          f"{packed_bytes/1e6:.1f} MB {args.precision} serving form "
          f"({base_bytes/packed_bytes:.2f}x smaller)")
    return model, params, cfg, mesh


def main(argv=None):
    args = build_parser().parse_args(argv)
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    model, params, cfg, mesh = build(args)
    if cfg.kind != "lm" or cfg.frontend == "embeds":
        if mesh is not None:
            print("--mesh: legacy (embeds/enc-dec) loop is single-device; "
                  "ignoring the mesh")
        return _legacy_loop(model, params, cfg, args)
    return batcher_loop(model, params, cfg, args, mesh=mesh)


if __name__ == "__main__":
    main()
