"""Binary (1x1) matmul — XNOR + popcount, the paper's Fig. 1 PE on the TPU VPU.

Both operands are +/-1 vectors stored as {1,0} bit fields, 32 per int32 word
(paper: "-1 or 1 represented in hardware as either 0 or 1").  The FPGA PE is
an XNOR gate + popcount tree; the TPU analogue is vector XOR +
``lax.population_count`` + integer reduce — 32 MACs per word-op, the only
path on TPU whose *compute* density keeps growing below 8 bits (DESIGN.md §2).

    out[m, n] = sum_k a[m,k] * w[n,k]        (a, w in {-1,+1})
              = K - 2 * popcount(a_bits XOR w_bits)

Grid: (M/bm, N/bn, KW/bkw), KW = K/32, innermost K-accumulation of mismatch
counts in an int32 VMEM scratch; epilogue K - 2*mismatch, optional per-feature
alpha (XNOR-net scale).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(a_ref, w_ref, alpha_ref, out_ref, acc_ref, *, k_total: int, n_k: int):
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    a = a_ref[...]                                    # (bm, bkw) int32
    wt = w_ref[...].T                                 # (bkw, bn) int32
    # one word column at a time: (bm, 1) XOR (1, bn) is a lane-dense
    # (bm, bn) tile, where a whole (bm, bn, bkw) XOR tensor pads bkw to 128
    # lanes in VMEM and compiles ~20x slower at serving widths
    mismatch = jnp.zeros(acc_ref.shape, jnp.int32)
    for i in range(a.shape[1]):
        mismatch += jax.lax.population_count(
            jax.lax.bitwise_xor(a[:, i:i + 1], wt[i:i + 1, :]))
    acc_ref[...] += mismatch

    @pl.when(kk == n_k - 1)
    def _epilogue():
        dot = (k_total - 2 * acc_ref[...]).astype(jnp.float32)
        if alpha_ref is not None:
            dot = dot * alpha_ref[...]
        out_ref[...] = dot.astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("k", "bm", "bn", "bkw",
                                             "out_dtype", "interpret"))
def binary_matmul(a_packed, wt_packed, alpha=None, *, k: int,
                  bm: int = 128, bn: int = 128, bkw: int = 128,
                  out_dtype=jnp.float32, interpret: bool = False):
    m, kw = a_packed.shape
    n, kw2 = wt_packed.shape
    assert kw == kw2 and kw * 32 == k
    bkw = min(bkw, kw)
    assert m % bm == 0 and n % bn == 0 and kw % bkw == 0
    n_k = kw // bkw

    args = [a_packed, wt_packed]
    in_specs = [
        pl.BlockSpec((bm, bkw), lambda i, j, kk: (i, kk)),
        pl.BlockSpec((bn, bkw), lambda i, j, kk: (j, kk)),
    ]
    if alpha is not None:
        args.append(alpha.reshape(1, n).astype(jnp.float32))
        in_specs.append(pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)))
        kernel = functools.partial(_kernel, k_total=k, n_k=n_k)
    else:
        kernel = functools.partial(
            lambda ar, wr, o, acc, **kw2_: _kernel(ar, wr, None, o, acc, **kw2_),
            k_total=k, n_k=n_k)

    return pl.pallas_call(
        kernel,
        grid=(m // bm, n // bn, n_k),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(*args)
