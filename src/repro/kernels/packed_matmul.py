"""Packed k-bit weight matmul — the TPU-native analogue of the paper's low-bit PEs.

Weights live in HBM bit-packed (k in {1,2,4,8} -> 32/k codes per int32 word),
cutting HBM traffic by 16/k vs bf16 — the paper's bandwidth/memory saving
(§II.A) mapped to the TPU memory hierarchy.  Inside the kernel each weight
block is unpacked HBM->VMEM once per (m-tile) reuse, decoded to int8, and fed
to the MXU (int8 x int8 -> int32, which on v5e runs at 2x bf16 peak), then a
fused per-channel scale-shift epilogue applies the BNS parameters
(paper eqs. 1/2) — exactly one multiply-add per output feature.

Layout:
  x         : (M, K)   int8 codes (quantized activations) or float (weight-only quant)
  wt_packed : (N, KW)  int32, KW = K * bits / 32 — W^T packed along K
  scale     : (1, N)   float32 fused gamma (weight scale x act scale x BN fold)
  bias      : (1, N)   float32 fused beta (optional)
  out       : (M, N)   float32/bf16

Grid: (M/bm, N/bn, K/bk) with K innermost; int32 (or f32) VMEM scratch
accumulator; tiles legal for Mosaic's (8, 128) block tiling
(``tuning._valid_block``: bm a multiple of 8, bn a multiple of 128 or all of
N, bk all of K or a multiple of 128 pack words).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _unpack_block(words, bits: int):
    """int32 words (bn, bkw) -> int8 codes (bkw * 32/bits, bn), sign-extended
    and TRANSPOSED (K on sublanes): the fields of a word expand along a
    sublane axis, which merges into K without the 8-16x lane padding of a
    (bn, bkw, 32/bits) expansion."""
    n = 32 // bits
    mask = (1 << bits) - 1
    w = words.T.astype(jnp.uint32)                                    # (bkw, bn)
    shifts = jax.lax.broadcasted_iota(jnp.uint32, (1, n, 1), 1) * bits
    fields = ((w[:, None, :] >> shifts) & mask).astype(jnp.int32)     # (bkw, n, bn)
    if bits > 1:
        sign_bit = 1 << (bits - 1)
        fields = jnp.where(fields >= sign_bit, fields - (1 << bits), fields)
    return fields.reshape(-1, words.shape[0]).astype(jnp.int8)


def _kernel(x_ref, w_ref, scale_ref, bias_ref, out_ref, acc_ref, *,
            bits: int, n_k: int, int_path: bool):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    w = _unpack_block(w_ref[...], bits)                               # (bk, bn) int8
    if int_path:
        # integer dots have one precision: pin it, or a global
        # jax_default_matmul_precision="highest" asks Mosaic for an fp32
        # contraction of int8 operands, which it refuses
        acc_ref[...] += jnp.dot(x_ref[...], w,
                                precision=jax.lax.Precision.DEFAULT,
                                preferred_element_type=jnp.int32)
    else:
        acc_ref[...] += jnp.dot(x_ref[...].astype(jnp.float32),
                                w.astype(jnp.float32),
                                preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _epilogue():
        out = acc_ref[...].astype(jnp.float32) * scale_ref[...]
        if bias_ref is not None:
            out = out + bias_ref[...]
        out_ref[...] = out.astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bits", "bm", "bn", "bk",
                                             "out_dtype", "interpret"))
def packed_matmul(x, wt_packed, scale, bias=None, *, bits: int,
                  bm: int = 128, bn: int = 128, bk: int = 512,
                  out_dtype=jnp.float32, interpret: bool = False):
    """See module docstring.  Shapes must already be multiples of the tiles
    (use ops.packed_linear for the padded convenience wrapper)."""
    m, k = x.shape
    n, kw = wt_packed.shape
    codes_per_word = 32 // bits
    assert kw * codes_per_word == k, (kw, codes_per_word, k)
    bk = min(bk, k)
    assert bk % codes_per_word == 0
    bkw = bk // codes_per_word
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, (m, n, k, bm, bn, bk)
    n_k = k // bk
    int_path = jnp.issubdtype(x.dtype, jnp.integer)
    acc_dtype = jnp.int32 if int_path else jnp.float32

    scale2 = scale.reshape(1, n).astype(jnp.float32)
    args = [x, wt_packed, scale2]
    in_specs = [
        pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
        pl.BlockSpec((bn, bkw), lambda i, j, kk: (j, kk)),
        pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),
    ]
    if bias is not None:
        args.append(bias.reshape(1, n).astype(jnp.float32))
        in_specs.append(pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)))
        kernel = functools.partial(_kernel, bits=bits, n_k=n_k, int_path=int_path)
    else:
        kernel = functools.partial(
            lambda xr, wr, sr, o, a, **kw2: _kernel(xr, wr, sr, None, o, a, **kw2),
            bits=bits, n_k=n_k, int_path=int_path)

    return pl.pallas_call(
        kernel,
        grid=(m // bm, n // bn, n_k),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), acc_dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(*args)
