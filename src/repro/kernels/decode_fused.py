"""Fused ragged decode: page-table gather + KV dequant + flash-decode +
output projection in ONE Pallas dispatch per layer, gridded over live slots.

The paper's thesis is that narrow datapaths only pay off when the
*computation* is organized around them (Colangelo et al., 1806.11547):
per-layer fused dataflow, not op-by-op dispatch.  This kernel is that shape
for the serving decode step.  The unfused path issues two dispatches per
layer (paged attention, then the ``wo`` projection matmul) over a batch
padded to ``(n_slots, 1)`` regardless of occupancy; here one ``pallas_call``
covers both, and the grid's slot dimension runs over **live slots only**:

  * ``slot_map`` (L,) int32 — the live-slot index map, scalar-prefetched so
    every BlockSpec index map routes block DMAs through it: the q row,
    page-table row, and position of grid step ``l`` are those of slot
    ``slot_map[l]``.  Dead slots are simply absent from the grid instead of
    computing masked garbage.
  * the innermost grid dimension walks the slot's KV blocks with the online-
    softmax scratch carried across iterations — sequence-parallel partial
    accumulation (the split-K of flash decode), with the per-block
    ``pl.when(j * bs <= pos)`` live guard so blocks wholly beyond ``pos``
    skip dequant and both dots.  Each step moves one pool block of one
    layer for all KV heads, in place from the stacked pool, as in
    :mod:`repro.kernels.paged_attention`.
  * the output projection is folded into the final block step: attention is
    linear in the value heads, so each query head ``h`` contributes
    ``attn_h @ wo[h·Dh : (h+1)·Dh]`` to the slot's (1, D) output.  ``wo``'s
    block index never changes, so it is fetched once per call, not once per
    slot.

The kernel computes the float-weight projection (``wo`` upcast to f32 in
VMEM) — the quantized-``wo`` epilogue (per-row activation requantization)
stays in the engine's composition fallback so its numerics never fork from
``qmatmul``.

Layout (per device, post-sharding; the pool as :mod:`repro.kernels.kv_pool`
stores it):
  q          : (B, KV, G, Dh)    padded batch of current-token queries
  kv_pool    : (L, NB, bs/P, P*2*KV*Dh') int8 codes (kv_bits<=8) or float
  kv_scale   : (L, NBR, R*2*KV*bs)   f32 per-(position, head) (None for 16)
  layer      : ()                int32 (scalar prefetch)
  page_table : (B, n_blocks)     int32 (scalar prefetch)
  pos        : (B,)              int32 (scalar prefetch)
  slot_map   : (L,)              int32 live slot ids (scalar prefetch)
  wo         : (KV*G*Dh, D)      float output-projection weight
  out        : (L, D)            f32, compact over live slots (the kernel
                                 writes (L, 1, D): a (1, D) block over L
                                 rows would have a unit second-minor dim)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import kv_pool as kv_pool_lib
from .paged_attention import (attend_block, init_softmax_scratch,
                              layer_operand, pool_block_specs)


def fused_decode_kernel(lyr_ref, sm_ref, pt_ref, pos_ref, q_ref, kv_ref,
                        sc_ref, wo_ref, out_ref, m_ref, l_ref, acc_ref, *,
                        bs: int, n_blocks: int, kv: int, dh: int,
                        kv_bits: int):
    li = pl.program_id(0)
    j = pl.program_id(1)
    slot = sm_ref[li]

    @pl.when(j == 0)
    def _init():
        init_softmax_scratch(m_ref, l_ref, acc_ref)

    # per-block live guard: a fully-dead block's online-softmax update is
    # the identity, so skipping it is bit-identical (see paged_attention)
    @pl.when(j * bs <= pos_ref[slot])
    def _live_block():
        attend_block(q_ref, kv_ref, sc_ref, m_ref, l_ref, acc_ref, j=j,
                     pos=pos_ref[slot], phys=pt_ref[slot, j], bs=bs, kv=kv,
                     dh=dh, kv_bits=kv_bits)

    # epilogue: project every query head's attention output, rounded to
    # the model dtype as the unfused layer rounds it, through its wo row
    # block into the slot's (1, D) output
    @pl.when(j == n_blocks - 1)
    def _project():
        out = jnp.zeros(out_ref.shape[1:], jnp.float32)
        g = acc_ref.shape[1]
        for h in range(kv):
            attn = (acc_ref[h] / jnp.maximum(l_ref[h], 1e-30)).astype(
                q_ref.dtype).astype(jnp.float32)             # (G, Dh)
            for gi in range(g):
                row = (h * g + gi) * dh
                w = wo_ref[row:row + dh, :].astype(jnp.float32)  # (Dh, D)
                out = out + jnp.dot(attn[gi:gi + 1], w)
        out_ref[0] = out.astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("kv_bits", "interpret"))
def fused_decode(q, kv_pool, kv_scale, page_table, pos, slot_map, wo,
                 layer, *, kv_bits: int = 8, interpret: bool = False):
    """One fused decode step: live-slot paged attention + output projection.

    ``slot_map`` (L,) selects the live rows of ``q``/``page_table``/``pos``;
    the result is compact (L, D) f32 — callers scatter it back to the padded
    batch (``jnp.zeros((B, D)).at[slot_map].set(out)``).  ``wo`` is the dense
    float (KV*G*Dh, D) projection weight; ``layer`` picks the layer of the
    stacked pool.
    """
    b, kv, g, dh = q.shape
    has_scale = kv_scale is not None
    assert has_scale == (kv_bits < 16), (kv_bits, has_scale)
    assert wo.shape[0] == kv * g * dh, (wo.shape, (kv, g, dh))
    bs = kv_pool_lib.block_positions(kv_pool, kv,
                                     kv_pool_lib.store_dh(dh, kv_bits))
    n_blocks = page_table.shape[1]
    n_live = slot_map.shape[0]
    d_out = wo.shape[1]
    pt = page_table.astype(jnp.int32)
    pos_b = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (b,))
    sm = slot_map.astype(jnp.int32)

    kern = functools.partial(fused_decode_kernel, bs=bs, n_blocks=n_blocks,
                             kv=kv, dh=dh, kv_bits=kv_bits)
    if not has_scale:
        # kv_bits=16: no scale operand; close the kernel over a None ref
        def kern_ns(lyr_ref, sm_ref, pt_ref, pos_ref, q_ref, kv_ref, wo_ref,
                    out_ref, m_ref, l_ref, acc_ref):
            return fused_decode_kernel(
                lyr_ref, sm_ref, pt_ref, pos_ref, q_ref, kv_ref, None,
                wo_ref, out_ref, m_ref, l_ref, acc_ref, bs=bs,
                n_blocks=n_blocks, kv=kv, dh=dh, kv_bits=kv_bits)
        kern = kern_ns

    q_spec = pl.BlockSpec(
        (1, kv, g, dh), lambda li, j, lyr, sm, pt, pos: (sm[li], 0, 0, 0))
    wo_spec = pl.BlockSpec(
        (kv * g * dh, d_out), lambda li, j, lyr, sm, pt, pos: (0, 0))
    in_specs = [q_spec] + pool_block_specs(
        kv_pool, kv_scale,
        lambda li, j, lyr, sm, pt, pos: (lyr[0], pt[sm[li], j]),
        kv=kv, bs=bs) + [wo_spec]
    operands = (q, kv_pool) + ((kv_scale,) if has_scale else ()) + (wo,)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(n_live, n_blocks),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, d_out),
                               lambda li, j, lyr, sm, pt, pos: (li, 0, 0)),
        scratch_shapes=[pltpu.VMEM((kv, g, 1), jnp.float32),
                        pltpu.VMEM((kv, g, 1), jnp.float32),
                        pltpu.VMEM((kv, g, dh), jnp.float32)],
    )
    out = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        # the fused_decode_single_dispatch audit rule finds the dispatch in
        # the jaxpr by this name
        name="fused_decode_kernel",
        out_shape=jax.ShapeDtypeStruct((n_live, 1, d_out), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(layer_operand(layer), sm, pt, pos_b, *operands)
    return out[:, 0]


def fused_decode_ref(q, kv_pool, kv_scale, page_table, pos, slot_map, wo,
                     layer, *, kv_bits: int = 8,
                     out_dtype=jnp.float32):
    """jnp oracle: gather the live rows, run the paged-attention reference,
    project through ``wo``, scatter back compactly (L, D)."""
    from .paged_attention import paged_attention_ref
    ql = q[slot_map]
    attn = paged_attention_ref(ql, kv_pool, kv_scale, page_table[slot_map],
                               jnp.asarray(pos)[slot_map], layer,
                               kv_bits=kv_bits, out_dtype=jnp.float32)
    flat = attn.reshape(ql.shape[0], -1)
    return jnp.dot(flat, wo.astype(jnp.float32)).astype(out_dtype)
