"""Ternary-weight matmul — the paper's sign-flip + mux PE (Fig. 1 left).

Weights are {-1, 0, +1} stored as 2-bit signed fields, 16 per int32 word.
The FPGA PE replaces the multiplier with a sign-flip and a mux; the TPU
mapping decodes the 2-bit field to int8 in VMEM (a select, not a multiply)
and feeds the MXU — on TPU the "mux" is the decode and the MXU provides the
adder tree.  HBM weight traffic drops 8x vs bf16, which is where the ternary
win lives on this memory hierarchy (decode/serving is bandwidth-bound).

Epilogue: per-feature alpha (TWN scale) + optional fused beta — the BNS
scale-shift of paper eqs. (1)/(2).

Implementation note: decode here uses the arithmetic identity
    code = lo - 2*(hi AND lo_complement...)  -- instead we sign-extend the
2-bit two's-complement field exactly as the generic packed path, but the
kernel is kept separate because (a) it mirrors the paper's per-config PE
structure, (b) its epilogue is the alpha-scale form, (c) it pins bits=2 so
Mosaic can constant-fold the shift table.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _decode_ternary(words):
    """(bn, bkw) int32 -> (bkw*16, bn) int8 in {-1, 0, +1}: the decoded
    block TRANSPOSED, K on sublanes.  The 16 fields of a word then expand
    along a sublane axis, (bkw, 16, bn), which merges into K for free; the
    untransposed expansion (bn, bkw, 16) pads 16 lanes to 128 in VMEM and
    does not fit at serving widths.

    2-bit two's complement: 00 -> 0, 01 -> +1, 11 -> -1 (10 unused/-2 guarded
    upstream by the quantizer)."""
    w = words.T.astype(jnp.uint32)                             # (bkw, bn)
    shifts = jax.lax.broadcasted_iota(jnp.uint32, (1, 16, 1), 1) * 2
    f = ((w[:, None, :] >> shifts) & 0x3).astype(jnp.int32)    # (bkw, 16, bn)
    f = jnp.where(f >= 2, f - 4, f)                            # sign-extend
    return f.reshape(-1, words.shape[0]).astype(jnp.int8)


def _kernel(x_ref, w_ref, alpha_ref, bias_ref, out_ref, acc_ref, *,
            n_k: int, int_path: bool):
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    w = _decode_ternary(w_ref[...])                            # (bk, bn) int8
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

    @pl.when(kk == n_k - 1)
    def _epilogue():
        out = acc_ref[...].astype(jnp.float32) * alpha_ref[...]
        if bias_ref is not None:
            out = out + bias_ref[...]
        out_ref[...] = out.astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "out_dtype",
                                             "interpret"))
def ternary_matmul(x, wt_packed, alpha, bias=None, *,
                   bm: int = 128, bn: int = 128, bk: int = 512,
                   out_dtype=jnp.float32, interpret: bool = False):
    m, k = x.shape
    n, kw = wt_packed.shape
    assert kw * 16 == k
    bk = min(bk, k)
    assert bk % 16 == 0
    bkw = bk // 16
    assert m % bm == 0 and n % bn == 0 and k % bk == 0
    n_k = k // bk
    int_path = jnp.issubdtype(x.dtype, jnp.integer)
    acc_dtype = jnp.int32 if int_path else jnp.float32

    args = [x, wt_packed, alpha.reshape(1, n).astype(jnp.float32)]
    in_specs = [
        pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
        pl.BlockSpec((bn, bkw), lambda i, j, kk: (j, kk)),
        pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),
    ]
    if bias is not None:
        args.append(bias.reshape(1, n).astype(jnp.float32))
        in_specs.append(pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)))
        kernel = functools.partial(_kernel, n_k=n_k, int_path=int_path)
    else:
        kernel = functools.partial(
            lambda xr, wr, ar, o, acc, **kw2: _kernel(xr, wr, ar, None, o, acc, **kw2),
            n_k=n_k, int_path=int_path)

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
