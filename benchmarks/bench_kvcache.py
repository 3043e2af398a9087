"""Paged KV-cache bench — dense vs paged vs paged-quantized on a
shared-prefix workload.

Three claims, all recorded to ``BENCH_kvcache.json`` (CI artifact):

  1. **Parity**: the paged batcher at kv_bits=16 reproduces the dense
     batcher's greedy streams bit-for-bit on the workload (asserted), and
     prefix-cache hits never change them (asserted).
  2. **Effective capacity**: at a fixed pool byte budget, quantized blocks
     multiply the number of concurrently resident sequences — the paper's
     low-precision storage saving applied to the cache that bounds
     concurrency.  kv_bits=8 must fit >= 2x the sequences of kv_bits=16
     (asserted; kv_bits=4 recorded).
  3. **Prefix TTFT win**: on a workload of request groups sharing prompt
     prefixes, the radix cache skips the shared prefill chunks — strictly
     fewer chunk dispatches (asserted, deterministic) and a lower mean TTFT
     (asserted, wall-clock) than the same paged batcher with the prefix
     cache disabled.
  4. **Overcommit**: at a pool byte budget ~35% of the workload's
     full-budget reservation, dynamic allocation + preemption/recompute
     completes the workload with strictly higher admitted concurrency and
     strictly more decode tokens per dispatch than budget reservation at
     the same bytes (asserted) — and at ~20% it still completes a workload
     budget reservation cannot even admit (asserted).

Results print as ``name,value,derived`` CSV lines.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.models import build_model, reduce_for_smoke
from repro.runtime.kvcache import (PagedBatcher, paged_block_bytes,
                                   paged_capacity_blocks)
from repro.runtime.serving import (ContinuousBatcher, Request,
                                   RequestOptions, ServingConfig)

S_MAX = 32
CHUNK = 8
BLOCK = 8
PREFIX_LEN = 16
GROUPS = 3
PER_GROUP = 3
MAX_NEW = 6


def _setup():
    cfg = dataclasses.replace(reduce_for_smoke(get_config("smollm-135m")),
                              dtype="float32")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _shared_prefix_requests(cfg, rng):
    """GROUPS prompt groups; within a group every request shares a
    PREFIX_LEN-token prefix and differs in a short suffix."""
    reqs = []
    rid = 0
    for _ in range(GROUPS):
        prefix = rng.integers(0, cfg.vocab, (PREFIX_LEN,))
        for _ in range(PER_GROUP):
            suffix = rng.integers(0, cfg.vocab, (int(rng.integers(3, 8)),))
            toks = np.concatenate([prefix, suffix])[None].astype(np.int32)
            reqs.append(Request(rid=rid, tokens=toks,
        options=RequestOptions(max_new=MAX_NEW)))
            rid += 1
    return reqs


def _run_workload(batcher, cfg, *, warmup=True):
    """Warm the compiled shapes with a throwaway wave, then serve the
    shared-prefix workload and report outputs + metrics."""
    rng = np.random.default_rng(7)
    if warmup:
        w = Request(rid=10_000, tokens=rng.integers(
            0, cfg.vocab, (1, PREFIX_LEN + 3)).astype(np.int32),
        options=RequestOptions(max_new=MAX_NEW))
        batcher.submit(w)
        batcher.run()
    m0_chunks = batcher.metrics.prefill_chunks
    reqs = _shared_prefix_requests(cfg, np.random.default_rng(11))
    for r in reqs:
        batcher.submit(r)
    done = batcher.run()
    assert len(done) == len(reqs), (len(done), len(reqs))
    s = batcher.metrics.summary()
    return ({r.rid: r.output for r in done}, {
        "ttft_ms": s["ttft_ms"],
        "itl_ms": s["itl_ms"],
        "tok_per_s": s["throughput"]["tok_per_s"],
        "prefill_chunks": batcher.metrics.prefill_chunks - m0_chunks,
        "prefix_hit_tokens": batcher.metrics.prefix_hit_tokens,
        "prefix_hit_rate": s["kv_cache"]["prefix"]["hit_rate"],
        "peak_blocks": s["kv_cache"]["blocks"]["peak_in_use"],
        "evicted_blocks": s["kv_cache"]["evicted_blocks"],
    })


def overcommit_bench(cfg, model, params):
    """Dynamic allocation + preemption vs budget reservation at the SAME
    pool byte budget, sized to ~35% of the workload's full-budget
    reservation.  Asserted claims:

      * both policies complete the workload bit-identically, but dynamic
        allocation sustains strictly higher admitted concurrency
        (budget reservation serializes);
      * dynamic allocation's decode phase produces strictly more tokens
        per decode dispatch (the dispatch has a fixed compiled shape, so
        tokens/step IS decode-phase throughput) and higher wall tok/s;
      * at an even smaller budget (~20%), budget reservation cannot even
        admit — the pool no longer holds one full reservation and the
        batcher refuses to build — while dynamic allocation still
        completes the same workload via preemption/recompute.
    """
    n_slots, n_req, max_new = 4, 8, 20
    footprint = -(-min(6 + max_new - 1, S_MAX - 1) // BLOCK)
    full_reserve = n_slots * footprint                       # 16 blocks
    bb = paged_block_bytes(cfg, BLOCK, 16)
    pool_bytes = 7 * bb                                      # 6 allocatable
    frac = 6 / full_reserve

    def workload(mn=max_new):
        rng = np.random.default_rng(23)
        return [Request(rid=i, tokens=rng.integers(
            0, cfg.vocab, (1, 6)).astype(np.int32),
        options=RequestOptions(max_new=mn))
            for i in range(n_req)]

    def serve(reserve, pb, mn=max_new, preemption="recompute"):
        b = PagedBatcher(model, params,
        ServingConfig(n_slots=n_slots, s_max=S_MAX, chunk_size=CHUNK, kv_bits=16, block_size=BLOCK, pool_bytes=pb, reserve=reserve, preemption=preemption))
        warm = workload(mn)[:2]                              # compile shapes
        for r in warm:
            b.submit(r)
        b.run(max_steps=100_000)
        m0_tokens, m0_steps = b.metrics.decode_slot_tokens, b.metrics.decode_steps
        t0 = time.time()
        reqs = workload(mn)
        for r in reqs:
            b.submit(r)
        done = b.run(max_steps=100_000)
        wall = time.time() - t0
        assert len(done) == n_req, (reserve, len(done))
        m = b.metrics
        return {r.rid: r.output for r in done}, {
            "pool_blocks": b.num_blocks - 1,
            "decode_tok_per_step": (m.decode_slot_tokens - m0_tokens)
            / max(m.decode_steps - m0_steps, 1),
            "tok_per_s": sum(len(r.output) for r in done) / max(wall, 1e-9),
            "active_peak": m.requests_active_peak,
            "preemptions": m.preemptions,
            "recomputed_tokens": m.recomputed_tokens,
            "suffix_hit_tokens": m.suffix_hit_tokens,
            "evicted_blocks": m.blocks_evicted,
        }

    dyn_out, dyn = serve("prompt", pool_bytes)
    bud_out, bud = serve("budget", pool_bytes)
    assert dyn_out == bud_out, "preemption timing changed streams"
    assert dyn["active_peak"] > bud["active_peak"], \
        "dynamic allocation admitted no more concurrently than budget"
    assert dyn["preemptions"] > 0, "overcommit never preempted"
    assert dyn["decode_tok_per_step"] > bud["decode_tok_per_step"], \
        "dynamic allocation won no decode-phase throughput"
    print(f"kvcache_overcommit_dynamic,{dyn['tok_per_s']:.1f},"
          f"tok_step={dyn['decode_tok_per_step']:.2f} "
          f"peak_concurrent={dyn['active_peak']} "
          f"preempt={dyn['preemptions']} pool={dyn['pool_blocks']}blk"
          f"({frac:.0%} of full reservation)")
    print(f"kvcache_overcommit_budget,{bud['tok_per_s']:.1f},"
          f"tok_step={bud['decode_tok_per_step']:.2f} "
          f"peak_concurrent={bud['active_peak']} pool={bud['pool_blocks']}blk")
    print(f"kvcache_overcommit_speedup,"
          f"{dyn['decode_tok_per_step']/max(bud['decode_tok_per_step'],1e-9):.2f},"
          f"decode_tok_per_step dynamic/budget")

    # ~20% budget: budget reservation cannot even admit (pool < one full
    # reservation -> constructor refuses); dynamic+preemption completes the
    # same workload trimmed to 3-block lifetime footprints (max_new=14)
    tiny_bytes = 4 * bb                                      # 3 allocatable
    tiny_new = 3 * BLOCK - 6 + 1                             # footprint = 3
    try:
        serve("budget", tiny_bytes, mn=tiny_new)
        raise AssertionError("budget reserve accepted an unservable pool")
    except ValueError:
        pass
    tiny_out, tiny = serve("prompt", tiny_bytes, mn=tiny_new)
    ref_out, _ = serve("budget", pool_bytes, mn=tiny_new)    # uncontended ref
    assert tiny_out == ref_out, "tiny-pool preemption changed streams"
    print(f"kvcache_overcommit_tiny,{tiny['tok_per_s']:.1f},"
          f"dynamic completes on {tiny['pool_blocks']} blocks "
          f"(budget reserve cannot admit at all), "
          f"preempt={tiny['preemptions']}")
    return {
        "workload": {"n_slots": n_slots, "requests": n_req,
                     "prompt_len": 6, "max_new": max_new},
        "pool_bytes": pool_bytes,
        "fraction_of_full_reservation": frac,
        "dynamic": dyn, "budget": bud,
        "tiny_pool": {"pool_bytes": tiny_bytes,
                      "budget_admits": False, **tiny},
        "decode_tok_per_step_speedup":
            dyn["decode_tok_per_step"] / max(bud["decode_tok_per_step"], 1e-9),
    }


def dead_block_guard_bench():
    """The paged-attention kernel's ``pl.when`` dead-block guard at long
    page tables.  With a short live prefix most of the (B, KV, n_blocks)
    grid is dead; the guard skips dequant + both dots per dead block.

    Asserted: outputs with a long dead tail are BIT-identical to the
    truncated just-live table (the guard is the identity on dead blocks).
    Recorded: interpret-mode wall-clock at short vs full occupancy on the
    same long table — the per-step cost now tracks *live* blocks, not the
    padded table length.
    """
    from repro.kernels import kv_pool
    from repro.kernels.paged_attention import paged_attention
    rng = np.random.default_rng(3)
    b, kv, g, dh, bs, nblk, live = 2, 2, 4, 64, 16, 48, 3
    nb_pool = b * nblk + 2
    q = jnp.asarray(rng.normal(size=(b, kv, g, dh)).astype(np.float32))
    kp = jnp.asarray(rng.integers(-127, 128, (nb_pool, bs, kv, dh)).astype(np.int8))
    vp = jnp.asarray(rng.integers(-127, 128, (nb_pool, bs, kv, dh)).astype(np.int8))
    ks = jnp.asarray(rng.uniform(1e-3, 1e-1, (nb_pool, bs, kv, 1)).astype(np.float32))
    vs = jnp.asarray(rng.uniform(1e-3, 1e-1, (nb_pool, bs, kv, 1)).astype(np.float32))
    ids = rng.permutation(nb_pool - 1)[: b * nblk] + 1
    pt = jnp.asarray(ids.reshape(b, nblk).astype(np.int32))
    pos_short = jnp.asarray([live * bs - 1, live * bs - 5], np.int32)
    pos_full = jnp.asarray([nblk * bs - 1, nblk * bs - 1], np.int32)

    pool = kv_pool.from_heads(kp[None], ks[None], vp[None], vs[None])
    run = lambda table, pos: paged_attention(
        q, pool[kv_pool.CODES], pool[kv_pool.SCALES], table, pos, 0,
        kv_bits=8, interpret=True)
    out_long = run(pt, pos_short)
    out_live = run(pt[:, :live], pos_short)
    np.testing.assert_array_equal(np.asarray(out_long), np.asarray(out_live))

    def clock(pos, iters=3):
        jax.block_until_ready(run(pt, pos))                  # compile
        t0 = time.time()
        for _ in range(iters):
            jax.block_until_ready(run(pt, pos))
        return (time.time() - t0) / iters * 1e3

    ms_short, ms_full = clock(pos_short), clock(pos_full)
    speedup = ms_full / max(ms_short, 1e-9)
    print(f"kvcache_dead_block_guard,{speedup:.2f},"
          f"full_pos/short_pos wall at n_blocks={nblk} "
          f"(live={live}; {ms_full:.1f}ms vs {ms_short:.1f}ms, interpret)")
    return {"n_blocks": nblk, "live_blocks": live, "block_size": bs,
            "bit_identical_to_truncated_table": True,
            "ms_short_pos": ms_short, "ms_full_pos": ms_full,
            "full_over_short_speedup": speedup}


def capacity_sweep(cfg):
    """Max concurrently resident sequences at a fixed pool byte budget."""
    blocks_per_seq = -(-S_MAX // BLOCK)
    budget = 48 * paged_block_bytes(cfg, BLOCK, 16)   # 16 fp sequences
    rows = {}
    for kv_bits in (16, 8, 4):
        blocks = paged_capacity_blocks(cfg, budget, BLOCK, kv_bits)
        rows[kv_bits] = {
            "block_bytes": paged_block_bytes(cfg, BLOCK, kv_bits),
            "pool_blocks": blocks,
            "max_concurrent_seqs": blocks // blocks_per_seq,
        }
        print(f"kvcache_capacity_kv{kv_bits},{rows[kv_bits]['max_concurrent_seqs']},"
              f"blocks={blocks} at {budget} B")
    ratio8 = rows[8]["max_concurrent_seqs"] / max(rows[16]["max_concurrent_seqs"], 1)
    print(f"kvcache_capacity_ratio_8_vs_16,{ratio8:.2f},fixed_memory")
    assert ratio8 >= 2.0, f"kv_bits=8 capacity ratio {ratio8} < 2x"
    return {"pool_bytes": budget, "blocks_per_seq": blocks_per_seq,
            "by_kv_bits": rows, "ratio_8_vs_16": ratio8}


def main(out=None):
    cfg, model, params = _setup()
    mk_dense = lambda: ContinuousBatcher(model, params,
        ServingConfig(n_slots=4, s_max=S_MAX, chunk_size=CHUNK))
    mk_paged = lambda kv_bits, prefix: PagedBatcher(model, params,
        ServingConfig(n_slots=4, s_max=S_MAX, chunk_size=CHUNK, kv_bits=kv_bits, block_size=BLOCK, prefix_cache=prefix))

    dense_out, dense_m = _run_workload(mk_dense(), cfg)
    print(f"kvcache_dense,{dense_m['tok_per_s']:.1f},"
          f"ttft_p50={dense_m['ttft_ms']['p50']:.1f}ms "
          f"chunks={dense_m['prefill_chunks']}")

    p16_out, p16_m = _run_workload(mk_paged(16, False), cfg)
    assert p16_out == dense_out, "paged kv16 diverged from the dense batcher"
    print(f"kvcache_paged16,{p16_m['tok_per_s']:.1f},"
          f"ttft_p50={p16_m['ttft_ms']['p50']:.1f}ms "
          f"chunks={p16_m['prefill_chunks']}")

    pfx_out, pfx_m = _run_workload(mk_paged(16, True), cfg)
    assert pfx_out == dense_out, "prefix-cache hit changed outputs"
    assert pfx_m["prefill_chunks"] < p16_m["prefill_chunks"], \
        "prefix cache skipped no prefill chunks"
    assert pfx_m["ttft_ms"]["mean"] < p16_m["ttft_ms"]["mean"], \
        "prefix cache produced no TTFT win"
    ttft_win = p16_m["ttft_ms"]["mean"] / max(pfx_m["ttft_ms"]["mean"], 1e-9)
    print(f"kvcache_paged16_prefix,{pfx_m['tok_per_s']:.1f},"
          f"ttft_p50={pfx_m['ttft_ms']['p50']:.1f}ms "
          f"chunks={pfx_m['prefill_chunks']} "
          f"hit_rate={pfx_m['prefix_hit_rate']:.2f}")
    print(f"kvcache_prefix_ttft_win,{ttft_win:.2f},"
          f"mean_ttft_noprefix/prefix "
          f"(chunks {p16_m['prefill_chunks']}->{pfx_m['prefill_chunks']})")

    q8_out, q8_m = _run_workload(mk_paged(8, True), cfg)
    assert sorted(q8_out) == sorted(dense_out)     # served, quantized stream
    print(f"kvcache_paged8_prefix,{q8_m['tok_per_s']:.1f},"
          f"ttft_p50={q8_m['ttft_ms']['p50']:.1f}ms "
          f"chunks={q8_m['prefill_chunks']}")

    capacity = capacity_sweep(cfg)
    guard = dead_block_guard_bench()
    overcommit = overcommit_bench(cfg, model, params)

    result = {
        "workload": {"groups": GROUPS, "per_group": PER_GROUP,
                     "prefix_len": PREFIX_LEN, "max_new": MAX_NEW,
                     "s_max": S_MAX, "chunk": CHUNK, "block_size": BLOCK},
        "parity": {"paged16_equals_dense": True,
                   "prefix_hits_preserve_outputs": True},
        "modes": {"dense": dense_m, "paged16": p16_m,
                  "paged16_prefix": pfx_m, "paged8_prefix": q8_m},
        "prefix": {"ttft_win": ttft_win,
                   "chunks_skipped": p16_m["prefill_chunks"]
                   - pfx_m["prefill_chunks"],
                   "hit_rate": pfx_m["prefix_hit_rate"]},
        "capacity": capacity,
        "dead_block_guard": guard,
        "overcommit": overcommit,
    }
    if out:
        with open(out, "w") as f:
            json.dump(result, f, indent=1)
        print(f"wrote {out}")
    return result


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=None, help="write BENCH_kvcache.json here")
    a = ap.parse_args()
    main(out=a.out)
