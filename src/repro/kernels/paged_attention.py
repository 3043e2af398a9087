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

Layout (per device, post-sharding; the pool as :mod:`repro.kernels.kv_pool`
stores it):
  q          : (B, KV, G, Dh)           f32/bf16 (current token's queries)
  kv_pool    : (L, NB, bs/P, P*2*KV*Dh') int8 codes (kv_bits<=8) or float
                                        (kv_bits=16), keys beside values,
                                        P positions a row
  kv_scale   : (L, NBR, R*2*KV*bs)      f32 per-(position, head) scales, R
                                        blocks a row (None for 16)
  layer      : ()                       int32 layer of the stacked pool
  page_table : (B, n_blocks)            int32 physical block ids
  pos        : (B,)                     int32 per-sequence positions (mask:
                                        s <= pos)
  out        : (B, KV, G, Dh)           f32

Grid: (B, n_blocks), blocks innermost; one grid step moves one pool block of
one layer for ALL KV heads: its ``(bs/P, P*2*KV*Dh')`` tile of codes and
the ``(8, R*2*KV*bs)`` tile of scale rows that holds its row, each spanning
whole dims of its array, as Mosaic's (8, 128) tiling requires.  Layer, page
table and positions are scalar-prefetched, so the block's index map reads
pool row ``[layer, page_table[b, j]]`` of the stacked pool in place: no
layer of the pool is sliced or relaid before the call.  Each head's keys
and values of each of the ``P`` interleaved position sets are static lane
slices of the block.  Scratch m/l/acc, (KV, G, ·), is carried
across a sequence's blocks (online softmax).  Blocks wholly beyond ``pos``
still DMA (their page-table entries point at the reserved null block 0) but
skip the dot/softmax update entirely (``pl.when(j * bs <= pos)``) —
bit-identical to masking, since a fully-masked block's update is the
identity.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.packing import unpack_nibbles

from . import kv_pool as kv_pool_lib


def layer_operand(layer):
    """``layer`` as the (1,) int32 scalar-prefetch operand."""
    return jnp.asarray(layer, jnp.int32).reshape(1)


def scale_column(row, first, per: int, rows: int):
    """(1, n) -> (rows, 1), exactly: output row ``r`` is lane
    ``first + r*per`` of ``row`` (summed with zeros).  The kernels hold a
    head's per-position scales as a row of lanes and scale the codes of
    every ``per``-th position by rows; ``first`` may be traced."""
    shape = (rows, row.shape[-1])
    at = (jax.lax.broadcasted_iota(jnp.int32, shape, 1)
          == jax.lax.broadcasted_iota(jnp.int32, shape, 0) * per + first)
    return jnp.sum(jnp.where(at, row, 0.0), axis=1, keepdims=True)


def init_softmax_scratch(m_ref, l_ref, acc_ref):
    m_ref[...] = jnp.full_like(m_ref, -1e30)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)


def attend_block(q_ref, kv_ref, sc_ref, m_ref, l_ref, acc_ref, *, j, pos,
                 phys, bs: int, kv: int, dh: int, kv_bits: int):
    """Online-softmax update of every KV head's scratch with pool block
    ``j`` (positions ``j*bs ..``, masked past ``pos``).  ``q_ref[0, h]`` is
    head group ``h``'s (G, Dh) queries; ``kv_ref[0]`` is one block of all
    heads, ``bs/P`` stored rows of ``P`` positions each, head ``h``'s keys
    of position ``r*P + p`` in lanes ``[(2h*P + p)*Dh', .. + Dh')`` of row
    ``r`` and its values ``P*Dh'`` lanes on; row ``(phys // R) % 8`` of
    ``sc_ref`` holds physical block ``phys``'s scales, ``bs`` lanes per head
    and side at sub-block ``phys % R`` of each side's ``R*bs`` lanes
    (``repro.kernels.kv_pool``).  Each of the ``P`` interleaved position
    sets is a (bs/P, Dh') slice of lanes, scored on its own: the softmax
    over a block does not depend on the order of its positions.

    Dequantized K/V are rounded to the queries' (the model's) dtype, as the
    model's own dequant does (``layers._kv_dequant``): with a bf16 model the
    reference attends over bf16 K/V, and skipping that rounding shifts every
    score by up to 2^-9 relative."""
    rows = kv_ref.shape[-2]
    per = bs // rows
    dh_store = kv_ref.shape[-1] // (2 * kv * per)
    if sc_ref is not None:
        tile = sc_ref[...]                                   # (8, R*2*KV*bs)
        per_row = tile.shape[-1] // (2 * kv * bs)
        sel = jax.lax.broadcasted_iota(jnp.int32, tile.shape, 0)
        srow = jnp.sum(jnp.where(sel == (phys // per_row) % tile.shape[0],
                                 tile, 0.0),
                       axis=0, keepdims=True)                # exact select
        sub = (phys % per_row) * bs

    def dequant(h, side, p):                                 # side 0: K, 1: V
        lane = ((2 * h + side) * per + p) * dh_store
        c = kv_ref[0, :, lane:lane + dh_store]               # (bs/P, Dh')
        if kv_bits == 4:
            c = unpack_nibbles(c)
        x = c.astype(jnp.float32)
        if sc_ref is not None:
            at = (2 * h + side) * per_row * bs
            x = (x * scale_column(srow[:, at:at + per_row * bs], sub + p,
                                  per, rows)).astype(q_ref.dtype)
        return x.astype(jnp.float32)                         # (bs/P, Dh)

    row_pos = j * bs + jax.lax.broadcasted_iota(jnp.int32, (1, rows), 1) * per
    masks = [row_pos + p <= pos for p in range(per)]         # (1, bs/P) each
    for h in range(kv):
        q = q_ref[0, h].astype(jnp.float32)                  # (G, Dh)
        ss = [jnp.dot(q, dequant(h, 0, p).T) / (dh ** 0.5)   # (G, bs/P)
              for p in range(per)]
        m_prev = m_ref[h]                                    # (G, 1)
        m_new = m_prev
        for s, mask in zip(ss, masks):
            m_new = jnp.maximum(m_new, jnp.max(jnp.where(mask, s, -1e30),
                                               axis=1, keepdims=True))
        corr = jnp.exp(m_prev - m_new)                       # (G, 1)
        l_new, acc_new = l_ref[h] * corr, acc_ref[h] * corr
        for p, (s, mask) in enumerate(zip(ss, masks)):
            pr = jnp.where(mask, jnp.exp(s - m_new), 0.0)    # (G, bs/P)
            l_new = l_new + jnp.sum(pr, axis=1, keepdims=True)
            acc_new = acc_new + jnp.dot(pr, dequant(h, 1, p))
        l_ref[h], acc_ref[h], m_ref[h] = l_new, acc_new, m_new


def _kernel(lyr_ref, pt_ref, pos_ref, q_ref, kv_ref, sc_ref, out_ref, m_ref,
            l_ref, acc_ref, *, bs: int, n_blocks: int, kv: int, dh: int,
            kv_bits: int):
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
        attend_block(q_ref, kv_ref, sc_ref, m_ref, l_ref, acc_ref, j=j,
                     pos=pos_ref[b], phys=pt_ref[b, j], bs=bs, kv=kv, dh=dh,
                     kv_bits=kv_bits)

    @pl.when(j == n_blocks - 1)
    def _done():
        out_ref[0] = (acc_ref[...] /
                      jnp.maximum(l_ref[...], 1e-30)).astype(out_ref.dtype)


def pool_block_specs(codes, scales, block_map, *, kv: int, bs: int):
    """BlockSpecs of one pool block of one layer (``block_map`` gives
    ``(layer, physical block)``): its codes tile, and the tile of
    ``SCALE_ROWS`` scale rows that holds its row."""
    rows = kv_pool_lib.SCALE_ROWS

    def scale_index(*ids):
        layer, phys = block_map(*ids)
        per_row = scales.shape[-1] // (2 * kv * bs)
        return layer, phys // (rows * per_row), 0
    code_spec = pl.BlockSpec((None, 1) + codes.shape[2:],
                             lambda *ids: (*block_map(*ids), 0, 0))
    if scales is None:
        return [code_spec]
    return [code_spec, pl.BlockSpec((None, rows, scales.shape[-1]),
                                    scale_index)]


@functools.partial(jax.jit,
                   static_argnames=("kv_bits", "interpret"))
def paged_attention(q, kv_pool, kv_scale, page_table, pos, layer, *,
                    kv_bits: int = 8, interpret: bool = False):
    """One decode step of attention through a page table, over layer
    ``layer`` of the stacked pool.

    ``kv_scale`` must be None iff ``kv_bits == 16`` (raw storage).  ``pos``
    is scalar or (B,) per-sequence current positions.
    """
    b, kv, g, dh = q.shape
    has_scale = kv_scale is not None
    assert has_scale == (kv_bits < 16), (kv_bits, has_scale)
    bs = kv_pool_lib.block_positions(kv_pool, kv,
                                     kv_pool_lib.store_dh(dh, kv_bits))
    n_blocks = page_table.shape[1]
    pt = page_table.astype(jnp.int32)
    pos_b = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (b,))

    kern = functools.partial(_kernel, bs=bs, n_blocks=n_blocks, kv=kv, dh=dh,
                             kv_bits=kv_bits)
    if not has_scale:
        # kv_bits=16: no scale operand; close the kernel over a None ref
        def kern_ns(lyr_ref, pt_ref, pos_ref, q_ref, kv_ref, out_ref, m_ref,
                    l_ref, acc_ref):
            return _kernel(lyr_ref, pt_ref, pos_ref, q_ref, kv_ref, None,
                           out_ref, m_ref, l_ref, acc_ref, bs=bs,
                           n_blocks=n_blocks, kv=kv, dh=dh, kv_bits=kv_bits)
        kern = kern_ns

    q_spec = pl.BlockSpec((1, kv, g, dh),
                          lambda bi, j, lyr, pt, pos: (bi, 0, 0, 0))
    in_specs = [q_spec] + pool_block_specs(
        kv_pool, kv_scale, lambda bi, j, lyr, pt, pos: (lyr[0], pt[bi, j]),
        kv=kv, bs=bs)
    operands = (q, kv_pool) + ((kv_scale,) if has_scale else ())

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
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
    )(layer_operand(layer), pt, pos_b, *operands)


def paged_attention_ref(q, kv_pool, kv_scale, page_table, pos, layer, *,
                        kv_bits: int = 8, out_dtype=jnp.float32):
    """Pure-jnp oracle: gather blocks dense, then the serving model's dense
    decode attention (``decode_attention_serving_ref``) over the view.

    Reusing the dense reference op-for-op is what makes the engine's
    ``xla``-backend paged dispatch BIT-identical to the model's inline
    dequant + ``layers._attend`` formulation — the paged batcher's
    kv_bits=16 streams stay bit-identical to the dense batcher's.
    """
    from .decode_attention import decode_attention_serving_ref
    k, ks, v, vs = kv_pool_lib.gather(
        kv_pool, kv_scale, page_table, layer, q.shape[1],
        kv_pool_lib.store_dh(q.shape[-1], kv_bits))
    return decode_attention_serving_ref(q, k, ks, v, vs, pos,
                                        kv_bits=kv_bits, dtype=out_dtype)
