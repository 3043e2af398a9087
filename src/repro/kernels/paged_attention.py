"""Paged flash-decode attention — gather K/V through a page table.

The KV cache lives in a global pool of fixed-size blocks (``runtime.kvcache``)
instead of one dense (B, S_max) slab per slot: each request's blocks are named
by a per-request page table, so physical HBM is allocated per *block* and can
be shared between requests (radix prefix cache).  The paper's low-precision
storage argument applies per block: codes stay int8/int4 in HBM and are
dequantized in VMEM, so a kv_bits=8 pool holds ~2x the tokens of a bf16 pool
at fixed memory.

This kernel generalizes :mod:`repro.kernels.decode_attention` from contiguous
chunks to page-table indirection: one new token's query per sequence attends
over that sequence's blocks, with the physical block id resolved by the
scalar-prefetched page table in the BlockSpec index map (the canonical Pallas
pattern for paged attention — the DMA for block j of sequence b reads pool
row ``page_table[b, j]``).

Layout (per device, post-sharding):
  q          : (B, KV, G, Dh)    f32/bf16 (current token's queries, grouped)
  k_pool     : (NB, bs, KV, Dh)  int8 codes (kv_bits<=8) or float (kv_bits=16)
  k_scale    : (NB, bs, KV, 1)   f32 per-(position, head) scales (None for 16)
  v_pool     : (NB, bs, KV, Dh)  like k_pool
  v_scale    : (NB, bs, KV, 1)   like k_scale
  page_table : (B, n_blocks)     int32 physical block ids (scalar prefetch)
  pos        : (B,)              int32 per-sequence positions (mask: s <= pos)
  out        : (B, KV, G, Dh)    f32

Grid: (B, n_blocks), blocks innermost; one grid step moves one pool block
for ALL KV heads.  The kernel sees the pool through a free reshape,
``(NB, bs, KV*Dh')`` codes and ``(NB, bs, KV)`` scales, so every block's
last two dims span whole array dims — Mosaic tiles them in (8, 128) units
and refuses a block of one head out of KV (a unit second-minor dim).  Each
head is a static lane slice of the block.  Scratch m/l/acc, (KV, G, ·),
is carried across a sequence's blocks (online softmax).  Blocks wholly
beyond ``pos`` still DMA (their page-table entries point at the reserved
null block 0) but skip the dot/softmax update entirely
(``pl.when(j * bs <= pos)``) — bit-identical to masking, since a
fully-masked block's update is the identity.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.packing import unpack_nibbles



def heads_view(leaf):
    """``(NB, bs, KV, Dh')`` pool leaf -> ``(NB, bs, KV*Dh')`` (a free
    reshape): the layout the paged kernels block over."""
    return leaf.reshape(*leaf.shape[:2], -1)


def init_softmax_scratch(m_ref, l_ref, acc_ref):
    m_ref[...] = jnp.full_like(m_ref, -1e30)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)


def attend_block(q_ref, kp_ref, ks_ref, vp_ref, vs_ref, m_ref, l_ref,
                 acc_ref, *, j, pos, bs: int, kv: int, dh: int,
                 kv_bits: int):
    """Online-softmax update of every KV head's scratch with pool block
    ``j`` (positions ``j*bs ..``, masked past ``pos``).  ``q_ref[0, h]`` is
    head group ``h``'s (G, Dh) queries; the pool refs hold one block of all
    heads, head ``h`` in lanes ``[h*Dh', (h+1)*Dh')``.

    Dequantized K/V are rounded to the queries' (the model's) dtype, as the
    model's own dequant does (``layers._kv_dequant``): with a bf16 model the
    reference attends over bf16 K/V, and skipping that rounding shifts every
    score by up to 2^-9 relative."""
    dh_store = kp_ref.shape[-1] // kv

    def dequant(codes_ref, scale_ref, h):
        c = codes_ref[0, :, h * dh_store:(h + 1) * dh_store]  # (bs, Dh')
        if kv_bits == 4:
            c = unpack_nibbles(c)
        x = c.astype(jnp.float32)
        if scale_ref is not None:
            x = (x * scale_ref[0, :, h:h + 1]).astype(q_ref.dtype)
        return x.astype(jnp.float32)                         # (bs, Dh)

    idx = j * bs + jax.lax.broadcasted_iota(jnp.int32, (1, bs), 1)
    mask = idx <= pos                                        # (1, bs)
    for h in range(kv):
        q = q_ref[0, h].astype(jnp.float32)                  # (G, Dh)
        k = dequant(kp_ref, ks_ref, h)
        s = jnp.dot(q, k.T) / (dh ** 0.5)                    # (G, bs)
        s_masked = jnp.where(mask, s, -1e30)

        m_prev = m_ref[h]                                    # (G, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s_masked, axis=1, keepdims=True))
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)         # (G, bs)
        corr = jnp.exp(m_prev - m_new)                       # (G, 1)
        v = dequant(vp_ref, vs_ref, h)
        l_ref[h] = l_ref[h] * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[h] = acc_ref[h] * corr + jnp.dot(p, v)
        m_ref[h] = m_new


def _kernel(pt_ref, pos_ref, q_ref, kp_ref, ks_ref, vp_ref, vs_ref, out_ref,
            m_ref, l_ref, acc_ref, *, bs: int, n_blocks: int, kv: int,
            dh: int, kv_bits: int):
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        init_softmax_scratch(m_ref, l_ref, acc_ref)

    # Blocks whose first position is already past ``pos`` contribute exact
    # zeros through the mask (p=0, m_new=m_prev, corr=1), so skipping the
    # dot/softmax update entirely is bit-identical — dead tail blocks cost
    # only their (null-block) DMA, not dequant + two dots per block.
    @pl.when(j * bs <= pos_ref[b])
    def _live_block():
        attend_block(q_ref, kp_ref, ks_ref, vp_ref, vs_ref, m_ref, l_ref,
                     acc_ref, j=j, pos=pos_ref[b], bs=bs, kv=kv, dh=dh,
                     kv_bits=kv_bits)

    @pl.when(j == n_blocks - 1)
    def _done():
        out_ref[0] = (acc_ref[...] /
                      jnp.maximum(l_ref[...], 1e-30)).astype(out_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("kv_bits", "interpret"))
def paged_attention(q, k_pool, k_scale, v_pool, v_scale, page_table, pos, *,
                    kv_bits: int = 8, interpret: bool = False):
    """One decode step of attention through a page table.

    ``k_scale``/``v_scale`` must be None iff ``kv_bits == 16`` (raw storage).
    ``pos`` is scalar or (B,) per-sequence current positions.
    """
    b, kv, g, dh = q.shape
    bs = k_pool.shape[1]
    n_blocks = page_table.shape[1]
    has_scale = k_scale is not None
    assert has_scale == (kv_bits < 16), (kv_bits, has_scale)
    pt = page_table.astype(jnp.int32)
    pos_b = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (b,))

    kern = functools.partial(_kernel, bs=bs, n_blocks=n_blocks, kv=kv, dh=dh,
                             kv_bits=kv_bits)
    if not has_scale:
        # kv_bits=16: no scale operands; close the kernel over None refs
        def kern_ns(pt_ref, pos_ref, q_ref, kp_ref, vp_ref, out_ref,
                    m_ref, l_ref, acc_ref):
            return _kernel(pt_ref, pos_ref, q_ref, kp_ref, None, vp_ref, None,
                           out_ref, m_ref, l_ref, acc_ref, bs=bs,
                           n_blocks=n_blocks, kv=kv, dh=dh, kv_bits=kv_bits)
        kern = kern_ns

    block_map = lambda bi, j, pt, pos: (pt[bi, j], 0, 0)
    pool_spec = pl.BlockSpec((1, bs, kv * k_pool.shape[-1]), block_map)
    scale_spec = pl.BlockSpec((1, bs, kv), block_map)
    q_spec = pl.BlockSpec((1, kv, g, dh), lambda bi, j, pt, pos: (bi, 0, 0, 0))
    in_specs = [q_spec, pool_spec, scale_spec, pool_spec, scale_spec] \
        if has_scale else [q_spec, pool_spec, pool_spec]
    operands = (q, k_pool, k_scale, v_pool, v_scale) if has_scale \
        else (q, k_pool, v_pool)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, n_blocks),
        in_specs=in_specs,
        out_specs=q_spec,
        scratch_shapes=[pltpu.VMEM((kv, g, 1), jnp.float32),
                        pltpu.VMEM((kv, g, 1), jnp.float32),
                        pltpu.VMEM((kv, g, dh), jnp.float32)],
    )
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        name="paged_attention",
        out_shape=jax.ShapeDtypeStruct((b, kv, g, dh), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(pt, pos_b, q, *(heads_view(x) for x in operands[1:]))


def gather_pool(pool_leaf, page_table):
    """Dense (B, n_blocks*bs, ...) view of a pooled leaf (NB, bs, ...) through
    ``page_table`` (B, n_blocks) — the jnp-reference gather (XLA fuses it; on
    TPU the Pallas kernel's index map performs the same indirection without
    materializing the view)."""
    g = pool_leaf[page_table]                    # (B, n_blocks, bs, ...)
    return g.reshape(g.shape[0], g.shape[1] * g.shape[2], *g.shape[3:])


def paged_attention_ref(q, k_pool, k_scale, v_pool, v_scale, page_table,
                        pos, *, kv_bits: int = 8, out_dtype=jnp.float32):
    """Pure-jnp oracle: gather blocks dense, then the serving model's dense
    decode attention (``decode_attention_serving_ref``) over the view.

    Reusing the dense reference op-for-op is what makes the engine's
    ``xla``-backend paged dispatch BIT-identical to the model's inline
    dequant + ``layers._attend`` formulation — the paged batcher's
    kv_bits=16 streams stay bit-identical to the dense batcher's.
    """
    from .decode_attention import decode_attention_serving_ref
    gather = lambda leaf: None if leaf is None else \
        gather_pool(leaf, page_table)
    return decode_attention_serving_ref(
        q, gather(k_pool), gather(k_scale), gather(v_pool), gather(v_scale),
        pos, kv_bits=kv_bits, dtype=out_dtype)
