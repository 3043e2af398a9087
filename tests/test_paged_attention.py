"""Paged-attention kernel vs oracle (page-table gather, quantized blocks,
null-block deflection) and the engine's attention-kernel registry: dispatch,
xla fallback, serving-path bit-exactness, and the block-size autotune."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import engine, kv_pool, tuning
from repro.kernels.decode_attention import (decode_attention,
                                            decode_attention_serving_ref)
from repro.kernels.paged_attention import paged_attention, paged_attention_ref

RNG = np.random.default_rng(0)


def _pool(nb, bs, kv, dh, kv_bits):
    if kv_bits == 16:
        mk = lambda: jnp.asarray(
            RNG.normal(size=(nb, bs, kv, dh)).astype(np.float32))
        return mk(), None, mk(), None
    qmax = (1 << (min(kv_bits, 8) - 1)) - 1
    dh_store = dh // 2 if kv_bits == 4 else dh
    mk = lambda: jnp.asarray(RNG.integers(
        -qmax, qmax + 1, (nb, bs, kv, dh_store)).astype(np.int8))
    ms = lambda: jnp.asarray(RNG.uniform(
        1e-3, 1e-1, (nb, bs, kv, 1)).astype(np.float32))
    return mk(), ms(), mk(), ms()


def _stored(kp, ks, vp, vs, layers=False):
    """Per-head (NB, bs, KV, Dh') leaves -> the pool's stored (codes,
    scales) leaves (scales None at kv_bits 16), stacked over one layer; with
    ``layers`` the leaves are stacked already, (L, NB, bs, KV, Dh')."""
    lift = (lambda x: x) if layers else \
        (lambda x: None if x is None else x[None])
    pool = kv_pool.from_heads(lift(kp), lift(ks), lift(vp), lift(vs))
    return pool[kv_pool.CODES], pool.get(kv_pool.SCALES)


def _page_table(b, n_blocks, nb_pool):
    """Distinct physical blocks per (b, j) drawn from [1, nb_pool)."""
    ids = RNG.permutation(nb_pool - 1)[: b * n_blocks] + 1
    return jnp.asarray(ids.reshape(b, n_blocks).astype(np.int32))


@pytest.mark.parametrize("b,kv,g,dh,bs,nblk,kv_bits", [
    (2, 2, 4, 64, 16, 8, 8),
    (1, 4, 1, 128, 32, 4, 8),      # MQA-style grouping 1
    (3, 1, 8, 64, 16, 4, 16),      # float blocks
    (2, 2, 2, 64, 16, 8, 4),       # nibble-packed blocks
    # stored rows of several positions (kv_pool.positions_per_row)
    (2, 3, 3, 64, 16, 4, 4),       # smollm-135m's kv4 rows: 2 a row
    (2, 2, 2, 16, 16, 4, 8),       # 2 a row
    (2, 1, 2, 16, 16, 4, 16),      # float rows: 4 a row
])
def test_paged_attention_kernel_matches_ref(b, kv, g, dh, bs, nblk, kv_bits):
    nb_pool = b * nblk + 3
    q = jnp.asarray(RNG.normal(size=(b, kv, g, dh)).astype(np.float32))
    kp, ks, vp, vs = _pool(nb_pool, bs, kv, dh, kv_bits)
    pt = _page_table(b, nblk, nb_pool)
    pos = jnp.asarray(RNG.integers(1, nblk * bs, (b,)).astype(np.int32))
    got = paged_attention(q, *_stored(kp, ks, vp, vs), pt, pos, 0,
                          kv_bits=kv_bits, interpret=True)
    want = paged_attention_ref(q, *_stored(kp, ks, vp, vs), pt, pos, 0,
                               kv_bits=kv_bits)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def _bf16_model_attention(q, kp, ks, vp, vs, pt, pos):
    """What a bf16 model's decode attention computes: K/V dequantized and
    rounded to bf16 (``layers._kv_dequant``), then f32 softmax attention."""
    gather = lambda leaf: leaf[pt].reshape(pt.shape[0], -1, *leaf.shape[2:])
    deq = lambda c, sc: (gather(c).astype(jnp.float32)
                         * gather(sc)).astype(jnp.bfloat16).astype(jnp.float32)
    k, v = deq(kp, ks), deq(vp, vs)                     # (B, S, KV, Dh)
    dh = q.shape[-1]
    s = jnp.einsum("bkgd,bskd->bkgs", q.astype(jnp.float32), k) / dh ** 0.5
    mask = jnp.arange(k.shape[1])[None, :] <= pos[:, None]
    p = jax.nn.softmax(jnp.where(mask[:, None, None], s, -1e30), axis=-1)
    return jnp.einsum("bkgs,bskd->bkgd", p, v)


@pytest.mark.parametrize("kernel", ["paged", "fused"])
def test_paged_kernels_round_kv_to_bf16_model_dtype(kernel):
    """With bf16 queries (a bf16 model), both paged kernels attend over K/V
    rounded to bf16, and the fused kernel projects bf16-rounded attention,
    as the model's reference path does; f32 K/V shift every score by up to
    2^-9 relative, which 2-bit activation codes downstream amplify."""
    from repro.kernels.decode_fused import fused_decode
    b, kv, g, dh, bs, nblk, d = 2, 3, 3, 64, 16, 4, 96
    nb_pool = b * nblk + 1
    q = jnp.asarray(RNG.normal(size=(b, kv, g, dh))).astype(jnp.bfloat16)
    kp, ks, vp, vs = _pool(nb_pool, bs, kv, dh, 8)
    pt = _page_table(b, nblk, nb_pool)
    pos = jnp.asarray([nblk * bs - 1, 21], np.int32)
    want = _bf16_model_attention(q, kp, ks, vp, vs, pt, pos)
    if kernel == "paged":
        got = paged_attention(q, *_stored(kp, ks, vp, vs), pt, pos, 0,
                              interpret=True)
    else:
        wo = jnp.asarray(RNG.normal(size=(kv * g * dh, d))
                         ).astype(jnp.bfloat16)
        got = fused_decode(q, *_stored(kp, ks, vp, vs), pt, pos,
                           jnp.arange(b), wo, 0, interpret=True)
        want = jnp.dot(want.astype(jnp.bfloat16).astype(jnp.float32)
                       .reshape(b, -1), wo.astype(jnp.float32))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_paged_attention_unreferenced_blocks_are_invisible():
    """Poisoning pool blocks no page table references (other requests' data,
    the null block) must not change any output."""
    b, kv, g, dh, bs, nblk = 2, 2, 2, 64, 16, 4
    nb_pool = b * nblk + 4
    q = jnp.asarray(RNG.normal(size=(b, kv, g, dh)).astype(np.float32))
    kp, ks, vp, vs = _pool(nb_pool, bs, kv, dh, 8)
    pt = _page_table(b, nblk, nb_pool)
    pos = jnp.asarray([nblk * bs - 1, 7], np.int32)
    out1 = paged_attention(q, *_stored(kp, ks, vp, vs), pt, pos, 0,
                           interpret=True)
    unref = sorted(set(range(nb_pool)) - set(np.asarray(pt).ravel().tolist()))
    kp2 = jnp.asarray(np.asarray(kp)).at[jnp.asarray(unref)].set(127)
    vp2 = jnp.asarray(np.asarray(vp)).at[jnp.asarray(unref)].set(127)
    ks2 = ks.at[jnp.asarray(unref)].set(1.0)
    out2 = paged_attention(q, *_stored(kp2, ks2, vp2, vs), pt, pos, 0,
                           interpret=True)
    np.testing.assert_array_equal(np.asarray(out1), np.asarray(out2))


def test_paged_attention_masks_past_pos():
    """Blocks wholly beyond pos contribute nothing even with garbage."""
    b, kv, g, dh, bs, nblk = 1, 2, 2, 64, 16, 4
    q = jnp.asarray(RNG.normal(size=(b, kv, g, dh)).astype(np.float32))
    kp, ks, vp, vs = _pool(nblk + 1, bs, kv, dh, 8)
    pt = jnp.asarray([[1, 2, 3, 4]], np.int32)
    pos = jnp.int32(bs - 1)                       # only block 1 visible
    out1 = paged_attention(q, *_stored(kp, ks, vp, vs), pt, pos, 0,
                           interpret=True)
    kp2 = jnp.asarray(np.asarray(kp)).at[2:].set(127)
    out2 = paged_attention(q, *_stored(kp2, ks, vp, vs), pt, pos, 0,
                           interpret=True)
    np.testing.assert_array_equal(np.asarray(out1), np.asarray(out2))


def test_paged_attention_dead_block_guard_is_identity():
    """The ``pl.when`` dead-block guard: extending the page table with dead
    tail blocks (wholly beyond pos) must leave outputs BIT-identical to the
    truncated just-live table — the guard skips the update entirely, so the
    tail can neither perturb the online-softmax scratch nor the output."""
    b, kv, g, dh, bs = 2, 2, 2, 64, 16
    live_blocks, long_blocks = 3, 24
    nb_pool = b * long_blocks + 2
    q = jnp.asarray(RNG.normal(size=(b, kv, g, dh)).astype(np.float32))
    for kv_bits in (16, 8, 4):
        kp, ks, vp, vs = _pool(nb_pool, bs, kv, dh, kv_bits)
        pt_long = _page_table(b, long_blocks, nb_pool)
        pt_live = pt_long[:, :live_blocks]
        pos = jnp.asarray([live_blocks * bs - 1, 5], np.int32)
        pool = _stored(kp, ks, vp, vs)
        out_long = paged_attention(q, *pool, pt_long, pos, 0,
                                   kv_bits=kv_bits, interpret=True)
        out_live = paged_attention(q, *pool, pt_live, pos, 0,
                                   kv_bits=kv_bits, interpret=True)
        np.testing.assert_array_equal(np.asarray(out_long),
                                      np.asarray(out_live))


def test_paged_ref_equals_dense_gather():
    """The paged oracle over a page table == dense decode attention over the
    gathered cache (same codes, same scales)."""
    b, kv, g, dh, bs, nblk = 2, 2, 4, 32, 8, 3
    nb_pool = b * nblk + 1
    q = jnp.asarray(RNG.normal(size=(b, kv, g, dh)).astype(np.float32))
    kp, ks, vp, vs = _pool(nb_pool, bs, kv, dh, 8)
    pt = _page_table(b, nblk, nb_pool)
    pos = jnp.asarray([13, 20], np.int32)
    got = paged_attention_ref(q, *_stored(kp, ks, vp, vs), pt, pos, 0)
    gather = lambda leaf: leaf[pt].reshape(b, nblk * bs, *leaf.shape[2:])
    want = decode_attention_serving_ref(q, gather(kp), gather(ks),
                                        gather(vp), gather(vs), pos)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# engine attention registry
# ---------------------------------------------------------------------------
def _layers(n_layers, nb, bs, kv, dh, kv_bits):
    """``n_layers`` random per-head pools, stacked leaf by leaf."""
    pools = [_pool(nb, bs, kv, dh, kv_bits) for _ in range(n_layers)]
    return [None if leaves[0] is None else jnp.stack(leaves)
            for leaves in zip(*pools)]


@pytest.mark.parametrize("kv_bits", [16, 8, 4])
def test_pool_write_matches_per_head_scatter(kv_bits):
    """``kv_pool.write`` puts each update's codes at (layer, block, offset)
    and its scales at the position's lanes of the block's scale row — as a
    scatter into per-head leaves does — with several updates of one block
    and of one stored row (a prefill chunk), the null block among them, and
    every other layer, block and position left as it was; at one position a
    stored row and at several (``bs`` 16 packs 4 a row at these widths)."""
    n_layers, nb, kv, dh, layer = 3, 6, 2, 8, 1
    for bs in (4, 16):
        heads = _layers(n_layers, nb, bs, kv, dh, kv_bits)
        phys = jnp.asarray([2, 2, 2, 5, 1, 0, 2], jnp.int32)
        off = jnp.asarray([0, 1, bs - 1, 2, 0, 3, 2], jnp.int32)
        rows = [leaf[0, :, 0][:phys.shape[0]] if leaf is not None else None
                for leaf in _layers(1, 8, 1, kv, dh, kv_bits)]  # (N, KV, Dh')
        got = kv_pool.write(kv_pool.from_heads(*heads), jnp.int32(layer),
                            phys, off, *rows)
        want = kv_pool.from_heads(*(
            None if leaf is None else leaf.at[layer, phys, off].set(row)
            for leaf, row in zip(heads, rows)))
        assert got.keys() == want.keys()
        for name in want:
            assert got[name].shape == want[name].shape
            np.testing.assert_array_equal(np.asarray(got[name]),
                                          np.asarray(want[name]),
                                          err_msg=f"{name} bs={bs}")
    assert kv_pool.leaf_specs(nb, 16, kv, dh, 8, jnp.float32)[
        kv_pool.CODES].shape == (nb, 4, 4 * 2 * kv * dh)


@pytest.mark.parametrize("kv_bits", [8, 4])
def test_pool_write_temp_is_linear_in_updates(kv_bits):
    """A prefill chunk's row writes hold temporaries linear in its length:
    at smollm-135m's widths the compiled write of 2048 positions holds at
    most 5x the temp bytes of 512 (4x is linear, a merge over every pair
    of updates' lanes would be 16x) and well under a GiB."""
    kv, dh = 3, 64
    dh_s = kv_pool.store_dh(dh, kv_bits)
    pool = kv_pool.leaf_specs(1000, 16, kv, dh, kv_bits, jnp.bfloat16,
                              stacked=4)
    write = jax.jit(lambda pool, *a: kv_pool.write(pool, 1, *a),
                    donate_argnums=0)

    def temp(n):
        i32 = jax.ShapeDtypeStruct((n,), jnp.int32)
        codes = jax.ShapeDtypeStruct((n, kv, dh_s), jnp.int8)
        scales = jax.ShapeDtypeStruct((n, kv, 1), jnp.float32)
        compiled = write.lower(pool, i32, i32, codes, scales, codes,
                               scales).compile()
        return compiled.memory_analysis().temp_size_in_bytes
    small, large = temp(512), temp(2048)
    assert large <= 5 * small and large < 64 << 20, (small, large)


@pytest.mark.parametrize("kv_bits", [16, 8, 4])
def test_fused_kernel_matches_ref_on_packed_rows(kv_bits):
    """The fused decode kernel over stored rows of several positions each
    (``kv_pool.positions_per_row`` packs 2 or 4 at these widths) agrees
    with its reference composition."""
    from repro.kernels.decode_fused import fused_decode, fused_decode_ref
    b, kv, g, dh, bs, nblk, d = 3, 2, 2, 16, 16, 3, 24
    nb_pool = b * nblk + 1
    kp, ks, vp, vs = _pool(nb_pool, bs, kv, dh, kv_bits)
    pool = _stored(kp, ks, vp, vs)
    assert pool[0].shape[-2] < bs
    q = jnp.asarray(RNG.normal(size=(b, kv, g, dh)).astype(np.float32))
    wo = jnp.asarray(RNG.normal(size=(kv * g * dh, d)).astype(np.float32))
    pt = _page_table(b, nblk, nb_pool)
    pos = jnp.asarray([nblk * bs - 1, 17, 3], np.int32)
    sm = jnp.asarray([2, 0], jnp.int32)
    got = fused_decode(q, *pool, pt, pos, sm, wo, 0, kv_bits=kv_bits,
                       interpret=True)
    want = fused_decode_ref(q, *pool, pt, pos, sm, wo, 0, kv_bits=kv_bits)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("kernel", ["paged", "fused"])
@pytest.mark.parametrize("kv_bits", [16, 8, 4])
def test_stacked_pool_layer_reads_equal_one_layer(kernel, kv_bits):
    """The kernels and the reference read layer ``l`` of a stacked pool,
    through the layer index, exactly as they read that layer's pool
    alone."""
    from repro.kernels.decode_fused import fused_decode
    n_layers, b, kv, g, dh, bs, nblk = 3, 2, 2, 2, 16, 8, 3
    nb_pool = b * nblk + 1
    heads = _layers(n_layers, nb_pool, bs, kv, dh, kv_bits)
    stacked = _stored(*heads, layers=True)
    q = jnp.asarray(RNG.normal(size=(b, kv, g, dh)).astype(np.float32))
    wo = jnp.asarray(RNG.normal(size=(kv * g * dh, 8)).astype(np.float32))
    pt = _page_table(b, nblk, nb_pool)
    pos = jnp.asarray([nblk * bs - 1, 10], np.int32)
    sm = jnp.arange(b, dtype=jnp.int32)

    def read(pool, layer):
        if kernel == "paged":
            return paged_attention(q, *pool, pt, pos, layer, kv_bits=kv_bits,
                                   interpret=True)
        return fused_decode(q, *pool, pt, pos, sm, wo, layer,
                            kv_bits=kv_bits, interpret=True)
    for layer in range(n_layers):
        one = _stored(*(None if leaf is None else leaf[layer]
                        for leaf in heads))
        np.testing.assert_array_equal(
            np.asarray(read(stacked, jnp.int32(layer))),
            np.asarray(read(one, 0)), err_msg=f"layer {layer}")
        np.testing.assert_array_equal(
            np.asarray(paged_attention_ref(q, *stacked, pt, pos, layer,
                                           kv_bits=kv_bits)),
            np.asarray(paged_attention_ref(q, *one, pt, pos, 0,
                                           kv_bits=kv_bits)))


def test_attention_registry_resolution_and_fallback():
    ks = engine.available_attention_kernels()
    assert (engine.ATTN_DECODE, 8, engine.BACKEND_PALLAS) in ks
    assert (engine.ATTN_PAGED, 16, engine.BACKEND_PALLAS) in ks
    # 4-bit dense decode has no Pallas kernel -> xla fallback
    fn = engine.resolve_attention(engine.ATTN_DECODE, 4, engine.BACKEND_PALLAS)
    assert fn is engine.resolve_attention(engine.ATTN_DECODE, 4,
                                          engine.BACKEND_XLA)
    with pytest.raises(KeyError):
        engine.resolve_attention("nope", 8, engine.BACKEND_XLA)


def test_engine_decode_attention_backends_agree():
    """engine.decode_attention: pallas(interpret) vs xla reference across
    cache widths — the serving decode path dispatches through this."""
    b, s, kv, g, dh = 3, 64, 2, 4, 32
    q = jnp.asarray(RNG.normal(size=(b, kv, g, dh)).astype(np.float32))
    for kv_bits in (8, 4):
        qmax = (1 << (kv_bits - 1)) - 1
        dh_store = dh // 2 if kv_bits == 4 else dh
        mk = lambda: jnp.asarray(RNG.integers(
            -qmax, qmax + 1, (b, s, kv, dh_store)).astype(np.int8))
        ms = lambda: jnp.asarray(RNG.uniform(
            1e-3, 1e-1, (b, s, kv, 1)).astype(np.float32))
        kc, ksc, vc, vsc = mk(), ms(), mk(), ms()
        pos = jnp.asarray([5, 30, 63], np.int32)
        xla = engine.decode_attention(q, kc, ksc, vc, vsc, pos,
                                      kv_bits=kv_bits, backend="xla")
        pal = engine.decode_attention(q, kc, ksc, vc, vsc, pos,
                                      kv_bits=kv_bits, backend="pallas",
                                      interpret=True)
        np.testing.assert_allclose(np.asarray(xla), np.asarray(pal),
                                   rtol=2e-5, atol=2e-5)


def test_engine_paged_attention_backends_agree():
    b, kv, g, dh, bs, nblk = 2, 2, 2, 64, 16, 4
    nb_pool = b * nblk + 1
    q = jnp.asarray(RNG.normal(size=(b, kv, g, dh)).astype(np.float32))
    for kv_bits in (16, 8):
        kp, ks, vp, vs = _pool(nb_pool, bs, kv, dh, kv_bits)
        pt = _page_table(b, nblk, nb_pool)
        pos = jnp.asarray([20, 40], np.int32)
        pool = _stored(kp, ks, vp, vs)
        xla = engine.paged_attention(q, *pool, pt, pos, layer=0,
                                     kv_bits=kv_bits, backend="xla")
        pal = engine.paged_attention(q, *pool, pt, pos, layer=0,
                                     kv_bits=kv_bits, backend="pallas",
                                     interpret=True)
        np.testing.assert_allclose(np.asarray(xla), np.asarray(pal),
                                   rtol=2e-5, atol=2e-5)


def test_decode_attention_per_slot_positions():
    """The dense kernel's pos operand accepts per-slot (B,) vectors: each
    row masks at its own position (continuous batching)."""
    b, s, kv, g, dh = 2, 64, 2, 2, 32
    q = jnp.asarray(RNG.normal(size=(b, kv, g, dh)).astype(np.float32))
    qmax = 127
    kc = jnp.asarray(RNG.integers(-qmax, qmax + 1, (b, s, kv, dh)).astype(np.int8))
    vc = jnp.asarray(RNG.integers(-qmax, qmax + 1, (b, s, kv, dh)).astype(np.int8))
    ks = jnp.asarray(RNG.uniform(1e-3, 1e-1, (b, s, kv, 1)).astype(np.float32))
    vs = jnp.asarray(RNG.uniform(1e-3, 1e-1, (b, s, kv, 1)).astype(np.float32))
    pos = jnp.asarray([7, 45], np.int32)
    got = decode_attention(q, kc, ks, vc, vs, pos, chunk=16, interpret=True)
    for i in range(b):
        want = decode_attention(q[i:i + 1], kc[i:i + 1], ks[i:i + 1],
                                vc[i:i + 1], vs[i:i + 1], jnp.int32(pos[i]),
                                chunk=16, interpret=True)
        np.testing.assert_allclose(np.asarray(got[i]), np.asarray(want[0]),
                                   rtol=1e-6, atol=1e-6)


def test_serving_decode_dispatch_bit_exact_vs_inline_math(tmp_path,
                                                          monkeypatch):
    """The engine-dispatched decode path (xla impl) is BIT-identical to the
    pre-dispatch inline formulation (dequant + layers._attend) — wiring the
    registry into models.layers changed nothing numerically off-TPU."""
    from repro.models import layers as L
    from repro.models.config import ModelConfig
    b, s, kv, h, dh = 3, 32, 2, 4, 16
    g = h // kv
    cfg = ModelConfig(name="t", n_layers=1, d_model=h * dh, n_heads=h,
                      n_kv_heads=kv, kv_bits=8)
    qmax = 127
    q = jnp.asarray(RNG.normal(size=(b, 1, h, dh)).astype(np.float32))
    kc = jnp.asarray(RNG.integers(-qmax, qmax + 1, (b, s, kv, dh)).astype(np.int8))
    vc = jnp.asarray(RNG.integers(-qmax, qmax + 1, (b, s, kv, dh)).astype(np.int8))
    ks = jnp.asarray(RNG.uniform(1e-3, 1e-1, (b, s, kv, 1)).astype(np.float32))
    vs = jnp.asarray(RNG.uniform(1e-3, 1e-1, (b, s, kv, 1)).astype(np.float32))
    pos_b = jnp.asarray([3, 17, 31], np.int32)

    kk = L._kv_dequant(kc, ks, jnp.float32)
    vv = L._kv_dequant(vc, vs, jnp.float32)
    mask = (jnp.arange(s)[None, :] <= pos_b[:, None])[:, None, None]
    inline = L._attend(q, kk, vv, mask, cfg)                 # (B, 1, H*Dh)

    q4 = q[:, 0].reshape(b, kv, g, dh)
    ref = decode_attention_serving_ref(q4, kc, ks, vc, vs, pos_b)
    np.testing.assert_array_equal(np.asarray(inline),
                                  np.asarray(ref.reshape(b, 1, h * dh)))


def test_autotune_attention_persists_and_short_circuits(tmp_path,
                                                        monkeypatch):
    monkeypatch.setenv("REPRO_TUNING_CACHE", str(tmp_path / "tuning.json"))
    tuning.reset()
    e1 = engine.autotune_decode_attention(b=2, s=256, kv=2, g=2, dh=32,
                                          iters=1)
    assert e1["block"][2] in (128, 256)
    sweeps = tuning.stats()["sweeps"]
    e2 = engine.autotune_decode_attention(b=2, s=256, kv=2, g=2, dh=32,
                                          iters=1)
    assert tuning.stats()["sweeps"] == sweeps        # cache hit, no re-sweep
    assert e2["block"] == e1["block"]

    e3 = engine.autotune_kv_block_size(b=2, kv=2, g=2, dh=32, s_max=64,
                                       candidates=(16, 32), iters=1)
    # candidates plus the clipped default (one whole-sequence block)
    assert e3["block"][2] in (16, 32, 64)
    assert engine.preferred_kv_block_size(b=2, kv=2, g=2, dh=32, s_max=64,
                                          kv_bits=8) == e3["block"][2]
    # cold cache (different shape class) -> default, never a sweep
    assert engine.preferred_kv_block_size(b=2, kv=2, g=2, dh=32, s_max=128,
                                          kv_bits=8, default=16) == 16
    tuning.reset()
