"""Paged KV-cache subsystem (runtime.kvcache): block pool + radix prefix
cache unit tests, and the acceptance properties — the paged batcher at
kv_bits=16 is BIT-IDENTICAL to the dense batcher over random arrivals x
lengths x chunk sizes x block sizes, quantized paged batchers match their
dense-quantized counterparts, prefix-cache hits never change outputs, and
eviction under pool pressure keeps streams exact.
"""
import dataclasses

import jax
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs import get_config
from repro.models import build_model, reduce_for_smoke
from repro.runtime.kvcache import (BlockPool, PagedBatcher, RadixPrefixCache,
                                   paged_block_bytes, paged_capacity_blocks)
from repro.runtime.serving import (ContinuousBatcher, Request,
                                   RequestOptions, ServingConfig)

S_MAX = 24
_STATE = {}


def _setup(kv_bits=0):
    key = f"m{kv_bits}"
    if "cfg" not in _STATE:
        cfg = dataclasses.replace(reduce_for_smoke(get_config("smollm-135m")),
                                  dtype="float32")
        _STATE["cfg"] = cfg
        _STATE["params"] = build_model(cfg).init(jax.random.PRNGKey(0))
        _STATE["memo"] = {}
    if key not in _STATE:
        cfg = dataclasses.replace(_STATE["cfg"], kv_bits=kv_bits)
        _STATE[key] = build_model(cfg)
    return _STATE[key].cfg, _STATE[key], _STATE["params"]


def _prompt(length, salt, vocab):
    rng = np.random.default_rng(1009 * length + salt)
    return rng.integers(0, vocab, (1, length)).astype(np.int32)


def _run(batcher, prompts, max_new=5, eos=None):
    for i, p in enumerate(prompts):
        batcher.submit(Request(rid=i, tokens=p,
        options=RequestOptions(max_new=max_new, eos_id=eos)))
    done = batcher.run()
    assert sorted(r.rid for r in done) == list(range(len(prompts)))
    return {r.rid: r.output for r in done}


def _dense_memo(kv_bits, prompts, max_new, n_slots, chunk):
    """Dense-batcher outputs, memoized per config (the comparison oracle)."""
    key = (kv_bits, tuple(p.tobytes() for p in prompts), max_new, n_slots,
           chunk)
    memo = _STATE["memo"]
    if key not in memo:
        cfg, model, params = _setup(kv_bits)
        b = ContinuousBatcher(model, params,
        ServingConfig(n_slots=n_slots, s_max=S_MAX, chunk_size=chunk))
        memo[key] = _run(b, prompts, max_new=max_new)
    return memo[key]


# ---------------------------------------------------------------------------
# BlockPool unit tests
# ---------------------------------------------------------------------------
def test_pool_alloc_release_refcount():
    p = BlockPool(6)
    assert p.free_blocks == 5 and p.used_blocks == 0
    a = p.alloc(3)
    assert len(set(a)) == 3 and 0 not in a
    assert p.used_blocks == 3 and all(p.refcount(b) == 1 for b in a)
    p.acquire(a[0])
    assert not p.release(a[0])             # still referenced
    assert p.release(a[0])                 # last ref -> freed
    assert p.free_blocks == 3
    assert p.alloc(4) is None              # all-or-nothing
    assert p.free_blocks == 3              # failed alloc takes nothing
    b = p.alloc(3)
    assert set(b) | set(a[1:]) <= set(range(1, 6))


def test_pool_guards():
    p = BlockPool(4)
    with pytest.raises(ValueError):
        p.release(1)                       # not allocated
    with pytest.raises(ValueError):
        p.acquire(0)                       # null block is pinned/private
    with pytest.raises(ValueError):
        BlockPool(1)


# ---------------------------------------------------------------------------
# RadixPrefixCache unit tests
# ---------------------------------------------------------------------------
def test_radix_match_insert_block_granular():
    pool = BlockPool(10)
    r = RadixPrefixCache(pool, block_size=4)
    toks = np.arange(10, dtype=np.int32)           # 2 full blocks + tail
    blocks = pool.alloc(2)
    assert r.match(toks) == []
    assert r.insert(toks, blocks) == 2
    assert len(r) == 2
    assert [pool.refcount(b) for b in blocks] == [2, 2]   # owner + tree
    assert r.match(toks) == blocks                 # full match
    assert r.match(toks[:7]) == blocks[:1]         # partial: 1 full block
    other = np.concatenate([toks[:4], toks[:4]])   # diverges at block 2
    assert r.match(other) == blocks[:1]
    # conflicting insert keeps existing nodes (no double-count)
    dup = pool.alloc(2)
    assert r.insert(toks, dup) == 0
    assert [pool.refcount(b) for b in dup] == [1, 1]


def test_radix_evict_lru_leaves_first():
    pool = BlockPool(10)
    r = RadixPrefixCache(pool, block_size=2)
    a = pool.alloc(2)
    b = pool.alloc(2)
    cold = np.array([1, 2, 3, 4], np.int32)
    hot = np.array([1, 2, 9, 9], np.int32)
    r.insert(cold, a)
    r.insert(hot, b)                               # shares block a[0]'s node?
    # paths: [1,2]->a0 shared prefix node; children [3,4]->a1, [9,9]->b1
    assert r.match(cold) == [a[0], a[1]]
    r.match(hot)                                   # hot path most recent
    # release owners: only the tree references remain
    for blk in a + b:
        pool.release(blk)
    assert pool.used_blocks == 3                   # a0 (shared), a1, b1
    freed = r.evict(1)                             # LRU leaf = cold's a1
    assert freed == 1
    assert r.match(cold) == [a[0]]                 # cold tail gone
    assert r.match(hot) == [a[0], b[1]]            # hot path intact
    # eviction frees the block for real (no other refs)
    assert pool.refcount(a[1]) == 0


def test_radix_evict_cascades_to_parents():
    pool = BlockPool(10)
    r = RadixPrefixCache(pool, block_size=2)
    blocks = pool.alloc(3)
    r.insert(np.arange(6, dtype=np.int32), blocks)
    for blk in blocks:
        pool.release(blk)
    assert r.evict(3) == 3                         # leaf, then exposed parents
    assert len(r) == 0 and pool.used_blocks == 0


# ---------------------------------------------------------------------------
# capacity math (the kv_bits -> effective-capacity claim)
# ---------------------------------------------------------------------------
def test_quantized_blocks_at_least_double_capacity():
    from repro.kernels import kv_pool
    from repro.models import transformer as tfm
    cfg, _, _ = _setup()
    budget = 1 << 20
    cap16 = paged_capacity_blocks(cfg, budget, 16, 16)
    cap8 = paged_capacity_blocks(cfg, budget, 16, 8)
    cap4 = paged_capacity_blocks(cfg, budget, 16, 4)
    # smoke cfg serves fp32 -> int8 codes (+ scale overhead) give >= 2x
    assert cap8 >= 2 * cap16, (cap8, cap16)
    assert cap4 > cap8, (cap4, cap8)
    # and at published widths, where smollm-135m's kv4 rows (192 lanes)
    # pack two positions a stored row
    wide = dataclasses.replace(get_config("smollm-135m"), dtype="float32")
    assert paged_capacity_blocks(wide, budget, 16, 4) > \
        paged_capacity_blocks(wide, budget, 16, 8)
    # block-bytes math agrees with the pool as the TPU lays it out, and the
    # pool a budget buys fits that budget
    laid_out = lambda pool: sum(kv_pool.laid_out_bytes(leaf.shape, leaf.dtype)
                                for leaf in jax.tree_util.tree_leaves(pool))
    tile = kv_pool.scale_tile_blocks(16, cfg.n_kv_heads)
    for bits, cap in ((16, cap16), (8, cap8), (4, cap4)):
        pool = tfm.make_pool(cfg, tile, 16, bits)
        assert laid_out(pool) == tile * paged_block_bytes(cfg, 16, bits), bits
        bought = jax.eval_shape(lambda: tfm.make_pool(cfg, cap + 1, 16, bits))
        more = jax.eval_shape(lambda: tfm.make_pool(cfg, cap + 2, 16, bits))
        assert laid_out(bought) <= budget < laid_out(more), bits


def test_pool_bytes_constructor_sizes_the_pool():
    cfg, model, params = _setup()
    budget = 64 * paged_block_bytes(cfg, 8, 16)
    b = PagedBatcher(model, params,
        ServingConfig(n_slots=2, s_max=S_MAX, chunk_size=4, kv_bits=16, block_size=8, pool_bytes=budget))
    assert b.num_blocks == 64
    b8 = PagedBatcher(model, params,
        ServingConfig(n_slots=2, s_max=S_MAX, chunk_size=4, kv_bits=8, block_size=8, pool_bytes=budget))
    assert b8.num_blocks - 1 >= 2 * (b.num_blocks - 1)


# ---------------------------------------------------------------------------
# acceptance: paged == dense, bit-identical
# ---------------------------------------------------------------------------
@settings(max_examples=4, deadline=None, derandomize=True)
@given(lengths=st.lists(st.integers(2, 10), min_size=1, max_size=4),
       max_new=st.integers(1, 6),
       chunk=st.sampled_from([4, 8]),
       block_size=st.sampled_from([4, 8]),
       n_slots=st.integers(1, 3))
def test_property_paged16_bit_identical_to_dense(lengths, max_new, chunk,
                                                 block_size, n_slots):
    """kv_bits=16 paged streams == dense batcher streams, bitwise, over
    random arrivals x lengths x budgets x chunk sizes x block sizes."""
    cfg, model, params = _setup()
    prompts = [_prompt(ln, i, cfg.vocab) for i, ln in enumerate(lengths)]
    want = _dense_memo(0, prompts, max_new, n_slots, chunk)
    paged = PagedBatcher(model, params,
        ServingConfig(n_slots=n_slots, s_max=S_MAX, chunk_size=chunk, kv_bits=16, block_size=block_size))
    got = _run(paged, prompts, max_new=max_new)
    assert got == want, (lengths, max_new, chunk, block_size, n_slots)
    # every slot drained, all blocks released (radix may keep cached refs)
    assert paged.idle and all(s is None for s in paged.slots)
    assert all(bl is None for bl in paged._slot_blocks)


@pytest.mark.parametrize("kv_bits,block_size", [(8, 8), (8, 4), (4, 8)])
def test_paged_quantized_matches_dense_quantized(kv_bits, block_size):
    """Paged kv_bits=8/4 blocks hold exactly what the dense quantized cache
    holds (same per-position quantizer) -> identical greedy streams."""
    cfg, model, params = _setup()
    prompts = [_prompt(5 + i, i, cfg.vocab) for i in range(4)]
    want = _dense_memo(kv_bits, prompts, 5, 2, 4)
    paged = PagedBatcher(model, params,
        ServingConfig(n_slots=2, s_max=S_MAX, chunk_size=4, kv_bits=kv_bits, block_size=block_size))
    got = _run(paged, prompts, max_new=5)
    assert got == want


@pytest.mark.parametrize("fused", [True, False])
@pytest.mark.parametrize("kv_bits", [16, 8, 4])
def test_paged_matches_dense_every_kv_width(kv_bits, fused):
    """Over the pool as the kernels store it (keys beside values, a scale
    row per block, carried whole through the layer loop), paged streams at
    every KV width equal the dense batcher's, bitwise, with the fused decode
    dispatch and without it."""
    cfg, model, params = _setup()
    prompts = [_prompt(3 + 2 * i, 7 + i, cfg.vocab) for i in range(3)]
    want = _dense_memo(0 if kv_bits == 16 else kv_bits, prompts, 4, 2, 4)
    paged = PagedBatcher(model, params, ServingConfig(
        n_slots=2, s_max=S_MAX, chunk_size=4, kv_bits=kv_bits, block_size=4,
        fused_decode=fused))
    assert _run(paged, prompts, max_new=4) == want


def test_prefix_hits_never_change_outputs():
    """Second wave of identical prompts: radix hits skip prefill chunks but
    the streams stay bit-identical; a prefix-cache-off batcher agrees."""
    cfg, model, params = _setup()
    prompts = [_prompt(9 + i, i, cfg.vocab) for i in range(3)]
    want = _dense_memo(0, prompts, 5, 2, 4)

    paged = PagedBatcher(model, params,
        ServingConfig(n_slots=2, s_max=S_MAX, chunk_size=4, kv_bits=16, block_size=4))
    first = _run(paged, prompts, max_new=5)
    chunks_cold = paged.metrics.prefill_chunks
    for i, p in enumerate(prompts):
        paged.submit(Request(rid=i, tokens=p,
        options=RequestOptions(max_new=5)))
    second = {r.rid: r.output for r in paged.run()}
    chunks_warm = paged.metrics.prefill_chunks - chunks_cold
    assert first == second == want
    assert paged.metrics.prefix_hit_tokens > 0
    assert paged.metrics.prefix_hits == 3
    assert chunks_warm < chunks_cold            # prefill actually skipped

    off = PagedBatcher(model, params,
        ServingConfig(n_slots=2, s_max=S_MAX, chunk_size=4, kv_bits=16, block_size=4, prefix_cache=False))
    assert _run(off, prompts, max_new=5) == want
    assert off.metrics.prefix_lookups == 0


def test_generated_suffix_shared_with_followup_turns():
    """Agent-style reuse: a finished request registers its generated
    blocks, so a follow-up turn whose prompt extends (old prompt + old
    generation) radix-hits past the original prompt — and the stream stays
    bit-identical to a cold dense run of the same turn-2 prompt."""
    cfg, model, params = _setup()
    p = _prompt(8, 3, cfg.vocab)
    paged = PagedBatcher(model, params,
        ServingConfig(n_slots=2, s_max=S_MAX, chunk_size=4, kv_bits=16, block_size=4))
    r0 = Request(rid=0, tokens=p,
        options=RequestOptions(max_new=8))
    paged.submit(r0)
    paged.run()
    turn2 = np.concatenate([p, np.asarray(r0.output, np.int32)[None]], axis=1)
    want = _dense_memo(0, [turn2], 4, 1, 4)
    r1 = Request(rid=1, tokens=turn2,
        options=RequestOptions(max_new=4))
    paged.submit(r1)
    paged.run()
    assert r1.output == want[0]
    assert paged.metrics.suffix_hit_tokens > 0      # generated KV reused
    assert paged.metrics.prefix_hit_tokens >= 8     # ...plus the old prompt


def test_quantized_act_configs_register_generated_suffixes():
    """The old ROADMAP gate is GONE: per-row dynamic act scales make decode
    KV a per-position function of the token stream, so quantized-act configs
    register generated-suffix radix nodes like every other precision — and a
    follow-up turn that radix-hits those decode-written blocks streams
    bit-identically to a cold run of the same prompt."""
    from repro.models import to_serving
    cfg = dataclasses.replace(reduce_for_smoke(get_config("smollm-135m")),
                              dtype="float32", precision="2xT")
    model = build_model(cfg)
    params = to_serving(model.init(jax.random.PRNGKey(0)), cfg)
    mk = lambda **kw: PagedBatcher(model, params, ServingConfig(
        n_slots=1, s_max=S_MAX, chunk_size=4, kv_bits=16, block_size=4, **kw))
    paged = mk()
    assert paged._share_suffix
    p = _prompt(8, 9, cfg.vocab)
    r0 = Request(rid=0, tokens=p, options=RequestOptions(max_new=8))
    paged.submit(r0)
    paged.run()
    # 8-token prompt -> 2 prompt blocks, plus decode-written suffix block(s)
    assert len(paged.radix) > 2

    turn2 = np.concatenate([p, np.asarray(r0.output, np.int32)[None]], axis=1)
    r1 = Request(rid=1, tokens=turn2, options=RequestOptions(max_new=4))
    paged.submit(r1)
    paged.run()
    assert paged.metrics.suffix_hit_tokens > 0      # generated KV reused
    cold = mk(prefix_cache=False)
    assert _run(cold, [turn2], max_new=4) == {0: r1.output}


def test_prefix_sharing_between_concurrent_requests():
    """A prompt registered at admission is hit by a same-prompt request that
    arrives while the first is still decoding."""
    cfg, model, params = _setup()
    p = _prompt(8, 3, cfg.vocab)
    want = _dense_memo(0, [p, p], 8, 2, 4)
    paged = PagedBatcher(model, params,
        ServingConfig(n_slots=2, s_max=S_MAX, chunk_size=4, kv_bits=16, block_size=4))
    r0 = Request(rid=0, tokens=p,
        options=RequestOptions(max_new=8))
    paged.submit(r0)
    while not r0.output:                        # r0 active, still decoding
        paged.step()
    r1 = Request(rid=1, tokens=p,
        options=RequestOptions(max_new=8))
    paged.submit(r1)
    done = {r0.rid: r0, r1.rid: r1}
    paged.run()
    assert {i: done[i].output for i in done} == want
    assert paged.metrics.prefix_hit_tokens > 0   # hit r0's live blocks


def test_eviction_under_pool_pressure_keeps_streams_exact():
    """A pool sized for ~1.5 sequences forces the radix cache to evict
    between requests; outputs still match the dense batcher and the
    eviction counter moves."""
    cfg, model, params = _setup()
    prompts = [_prompt(7 + i, 20 + i, cfg.vocab) for i in range(5)]
    want = _dense_memo(0, prompts, 4, 1, 4)
    blocks_per_seq = -(-S_MAX // 4)
    paged = PagedBatcher(model, params,
        ServingConfig(n_slots=1, s_max=S_MAX, chunk_size=4, kv_bits=16, block_size=4, num_blocks=1 + blocks_per_seq + 2))
    got = _run(paged, prompts, max_new=4)
    assert got == want
    assert paged.metrics.blocks_evicted > 0
    assert paged.metrics.kv_blocks_peak <= blocks_per_seq + 2


def test_pool_exhaustion_queues_instead_of_deadlocking():
    """With a pool holding exactly one sequence, every request still
    finishes under both reserve policies: budget reservation serializes
    admissions through the queue; prompt reservation over-admits and
    preempts, and each preemption costs exactly one extra admission (and
    radix lookup) — never a deadlock either way."""
    cfg, model, params = _setup()
    blocks_per_seq = -(-S_MAX // 8)
    prompts = [_prompt(6, 40 + i, cfg.vocab) for i in range(3)]

    budget = PagedBatcher(model, params,
        ServingConfig(n_slots=4, s_max=S_MAX, chunk_size=4, kv_bits=16, block_size=8, reserve="budget", num_blocks=1 + blocks_per_seq))
    got = _run(budget, prompts, max_new=10)
    assert all(len(v) == 10 for v in got.values())
    # the 3-block pool fits one 2-block request at a time plus no slack:
    # admissions must have serialized, never deadlocked
    assert budget.metrics.kv_blocks_peak <= 3
    assert budget.metrics.preemptions == 0
    # retried (pool-exhausted) admissions must not inflate the prefix
    # counters: exactly one lookup per ADMITTED request, and the token-level
    # hit rate stays a rate
    assert budget.metrics.prefix_lookups == len(prompts)
    s = budget.metrics.summary()["kv_cache"]["prefix"]
    assert 0.0 <= s["hit_rate"] <= 1.0

    paged = PagedBatcher(model, params,
        ServingConfig(n_slots=4, s_max=S_MAX, chunk_size=4, kv_bits=16, block_size=8, num_blocks=1 + blocks_per_seq))
    got2 = _run(paged, prompts, max_new=10)
    assert got2 == got                    # preemption timing never changes streams
    assert paged.metrics.kv_blocks_peak <= 3
    # dynamic allocation admits all 3 up front (1 prompt block each) and
    # preempts when decode outgrows the pool; every preemption re-admits
    # once, so lookups track admissions exactly — waiting retries still
    # don't inflate the counters
    assert paged.metrics.preemptions > 0
    # dynamic allocation sustains strictly more admitted concurrency than
    # budget reservation on the same pool (which serialized: peak 1)
    assert paged.metrics.requests_active_peak >= 2 \
        > budget.metrics.requests_active_peak
    assert paged.metrics.prefix_lookups == \
        len(prompts) + paged.metrics.preemptions
    s = paged.metrics.summary()["kv_cache"]["prefix"]
    assert 0.0 <= s["hit_rate"] <= 1.0


def test_paged_submit_validation():
    cfg, model, params = _setup()
    # budget reservation: a pool smaller than one full sequence could never
    # admit anything — rejected at construction
    with pytest.raises(ValueError, match="blocks"):
        PagedBatcher(model, params,
        ServingConfig(n_slots=1, s_max=S_MAX, chunk_size=4, kv_bits=16, block_size=8, num_blocks=3, reserve="budget"))
    paged = PagedBatcher(model, params,
        ServingConfig(n_slots=1, s_max=S_MAX, chunk_size=4, kv_bits=16, block_size=8))
    with pytest.raises(ValueError, match="max_new"):
        paged.submit(Request(rid=1, tokens=_prompt(4, 0, cfg.vocab),
        options=RequestOptions(max_new=0)))
    with pytest.raises(ValueError, match="budget"):
        paged.submit(Request(rid=2, tokens=_prompt(S_MAX, 0, cfg.vocab)))
    # prompt reservation accepts the small pool and serves any request
    # whose LIFETIME footprint fits; one that could never hold all its
    # blocks at once is still rejected up front (it could never finish)
    small = PagedBatcher(model, params,
        ServingConfig(n_slots=1, s_max=S_MAX, chunk_size=4, kv_bits=16, block_size=8, num_blocks=3))
    with pytest.raises(ValueError, match="KV blocks"):
        small.submit(Request(rid=3, tokens=_prompt(6, 0, cfg.vocab),
        options=RequestOptions(max_new=S_MAX)))
    got = _run(small, [_prompt(6, 77, cfg.vocab)], max_new=4)
    assert len(got[0]) == 4


def test_submit_capacity_check_counts_writable_positions():
    """Regression for the _blocks_needed cap.  Decode-chain writes stop at
    position s_max-2 (the finish check retires a slot at pos s_max-1), so
    a budget-heavy request's footprint is min(L+max_new-1, s_max-1)
    positions — with s_max ≡ 1 (mod block_size) the old min(..., s_max)
    cap charged a phantom block and made submit reject requests the pool
    could in fact serve.  BUT activation never caps the FIRST decode
    write: a fresh prompt of exactly s_max-1 tokens still writes position
    s_max-1, so the cap is max(L+1, s_max-1), not a flat s_max-1."""
    cfg, model, params = _setup()
    s_max, bs = 25, 8                     # s_max % bs == 1: the phantom case
    blocks = -(-(s_max - 1) // bs)        # 3 blocks suffice for small L
    paged = PagedBatcher(model, params,
        ServingConfig(n_slots=1, s_max=s_max, chunk_size=4, kv_bits=16, block_size=bs, num_blocks=1 + blocks))
    assert paged._blocks_needed(4, s_max) == blocks          # phantom fixed
    assert paged._blocks_needed(s_max - 1, 2) == blocks + 1  # edge kept
    # lifetime footprint 3 blocks == pool: admits and finishes
    req = Request(rid=0, tokens=_prompt(4, 5, cfg.vocab),
        options=RequestOptions(max_new=s_max))
    paged.submit(req)
    done = paged.run()
    assert len(done) == 1
    # budget truncates at the cache cap: pos finishes at s_max-1
    assert len(req.output) == s_max - 1 - 4 + 1
    # the s_max-1-token prompt needs the 4th block this pool lacks
    with pytest.raises(ValueError, match="KV blocks"):
        paged.submit(Request(rid=1, tokens=_prompt(s_max - 1, 5, cfg.vocab),
        options=RequestOptions(max_new=2)))


def test_full_length_prompt_writes_last_position_exactly():
    """The edge the footprint cap must cover: a fresh prompt of s_max-1
    tokens activates at pos = s_max-1 and its one decode step writes that
    very position — under BOTH reserve policies the paged streams must
    match the dense batcher (a short footprint would deflect the write to
    the null block and silently corrupt the final token)."""
    cfg, model, params = _setup()
    s_max, bs = 25, 8
    p = _prompt(s_max - 1, 13, cfg.vocab)
    dense = ContinuousBatcher(model, params,
        ServingConfig(n_slots=1, s_max=s_max, chunk_size=4))
    d = Request(rid=0, tokens=p,
        options=RequestOptions(max_new=4))
    dense.submit(d)
    dense.run()
    assert len(d.output) == 2             # pos cap truncates after one step
    for reserve in ("prompt", "budget"):
        paged = PagedBatcher(model, params,
        ServingConfig(n_slots=1, s_max=s_max, chunk_size=4, kv_bits=16, block_size=bs, num_blocks=1 + 4, reserve=reserve))
        r = Request(rid=0, tokens=p,
        options=RequestOptions(max_new=4))
        paged.submit(r)
        paged.run()
        assert r.output == d.output, reserve


def test_paged_rejects_unsupported_stacks():
    cfg = dataclasses.replace(reduce_for_smoke(get_config("falcon-mamba-7b")),
                              dtype="float32")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    assert model.decode_step_paged is None
    with pytest.raises(ValueError, match="attention-only"):
        PagedBatcher(model, params,
        ServingConfig(n_slots=1, s_max=16))
    cfg8, model8, params8 = _setup(8)
    with pytest.raises(ValueError, match="kv_bits"):
        PagedBatcher(model8, params8,
        ServingConfig(n_slots=1, s_max=16, chunk_size=4))


_PAGED_TP_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, numpy as np
from repro.models import build_model, to_serving
from repro.models.config import ModelConfig
from repro.runtime.kvcache import PagedBatcher
from repro.runtime.serving import Request, RequestOptions, ServingConfig
from repro.launch.mesh import make_mesh

cfg = ModelConfig(name="tp-paged", n_layers=2, d_model=1024, n_heads=8,
                  n_kv_heads=8, head_dim=128, d_ff=2048, vocab=512,
                  dtype="float32", layer_pattern=("attn",),
                  ffn_pattern=("dense",), precision="2xT")
model = build_model(cfg)
params = to_serving(model.init(jax.random.PRNGKey(1)), cfg, tp=8)

def serve(mesh):
    rng = np.random.default_rng(1)
    b = PagedBatcher(model, params,
        ServingConfig(n_slots=2, s_max=16, chunk_size=4, kv_bits=8, block_size=4, mesh=mesh))
    for i in range(2):
        b.submit(Request(rid=i, tokens=rng.integers(
            0, cfg.vocab, (1, 5 + i)).astype(np.int32),
        options=RequestOptions(max_new=3)))
    return b, {r.rid: r.output for r in b.run()}

_, base = serve(None)
b_mp, got = serve(make_mesh(1, 8))
assert got == base, (got, base)
# the pool really is KV-head sharded over the model axis
spec = tuple(b_mp.pool["layer_0"]["kv"].sharding.spec)
assert "model" in spec, spec
print("PAGED_TP_GOLDEN_OK")
"""


def test_paged_tp_mesh_golden_8dev():
    """TP-sharded paged serving (pool KV heads over 'model' via pool_specs)
    reproduces single-device streams; block/position dims stay local."""
    import os
    import subprocess
    import sys
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(repo, "src")
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "-c", _PAGED_TP_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, (out.stdout[-2000:], out.stderr[-4000:])
    assert "PAGED_TP_GOLDEN_OK" in out.stdout


def test_paged_metrics_surface():
    cfg, model, params = _setup()
    paged = PagedBatcher(model, params,
        ServingConfig(n_slots=2, s_max=S_MAX, chunk_size=4, kv_bits=8, block_size=8))
    _run(paged, [_prompt(6, 60, cfg.vocab)], max_new=3)
    s = paged.metrics.summary()["kv_cache"]
    assert s["blocks"]["total"] == paged.num_blocks - 1
    assert s["blocks"]["peak_in_use"] >= 1
    assert 0 < s["blocks"]["peak_utilization"] <= 1
    assert s["prefix"]["lookups"] == 1
    assert paged.metrics.format()
