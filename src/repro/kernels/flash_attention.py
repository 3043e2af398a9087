"""Flash attention (prefill/training forward) — fused online-softmax kernel.

The §Roofline tables show every prefill/train cell memory-bound on attention
score traffic: the pure-jnp blockwise path writes (…, Sq, chunk) fp32 scores
to HBM once per fusion boundary.  This kernel keeps scores/probabilities in
VMEM for a whole (q-block x kv-block) tile — the structural fix recorded in
EXPERIMENTS.md §Perf.

Supports causal and sliding-window (local) masking via position arithmetic,
GQA grouping, and bf16 inputs with fp32 softmax statistics.

Layout (per device, post-sharding):
  q   : (B, Sq, KV, G, Dh)
  k,v : (B, Sk, KV, Dh)
  out : (B, Sq, KV, G, Dh) f32

Grid: (B, KV, Sq/bq, Sk/bk), KV-blocks innermost; m/l/acc scratch carried
across the KV dimension.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(q_ref, k_ref, v_ref, out_ref, m_ref, l_ref, acc_ref, *,
            bq: int, bk: int, g: int, dh: int, n_k: int,
            causal: bool, window: int, softcap: float):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -1e30)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, :, 0].astype(jnp.float32).reshape(bq * g, dh)   # (bq*G, Dh)
    k = k_ref[0, :, 0].astype(jnp.float32)                       # (bk, Dh)
    s = jnp.dot(q, k.T) * (dh ** -0.5)                           # (bq*G, bk)
    if softcap > 0:
        s = softcap * jnp.tanh(s / softcap)

    q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, g), 0)
    q_pos = q_pos.reshape(bq * g, 1)
    k_pos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
    mask = jnp.ones((bq * g, bk), bool)
    if causal:
        mask &= k_pos <= q_pos
    if window > 0:
        mask &= k_pos > q_pos - window

    s_for_max = jnp.where(mask, s, -1e30)
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s_for_max, axis=1, keepdims=True))
    p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
    corr = jnp.exp(m_prev - m_new)
    v = v_ref[0, :, 0].astype(jnp.float32)                       # (bk, Dh)
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jnp.dot(p, v)
    m_ref[...] = m_new

    @pl.when(ki == n_k - 1)
    def _done():
        out = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        out_ref[0, :, 0] = out.reshape(bq, g, dh).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "window", "softcap",
                                             "bq", "bk", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    softcap: float = 0.0, bq: int = 256, bk: int = 256,
                    interpret: bool = False):
    b, sq, kv, g, dh = q.shape
    sk = k.shape[1]
    bq = min(bq, sq)
    bk = min(bk, sk)
    assert sq % bq == 0 and sk % bk == 0
    n_k = sk // bk

    return pl.pallas_call(
        functools.partial(_kernel, bq=bq, bk=bk, g=g, dh=dh, n_k=n_k,
                          causal=causal, window=window, softcap=softcap),
        grid=(b, kv, sq // bq, n_k),
        in_specs=[
            pl.BlockSpec((1, bq, 1, g, dh), lambda bi, ki, qi, kk: (bi, qi, ki, 0, 0)),
            pl.BlockSpec((1, bk, 1, dh), lambda bi, ki, qi, kk: (bi, kk, ki, 0)),
            pl.BlockSpec((1, bk, 1, dh), lambda bi, ki, qi, kk: (bi, kk, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, 1, g, dh),
                               lambda bi, ki, qi, kk: (bi, qi, ki, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, sq, kv, g, dh), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bq * g, 1), jnp.float32),
                        pltpu.VMEM((bq * g, 1), jnp.float32),
                        pltpu.VMEM((bq * g, dh), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v)


def flash_attention_ref(q, k, v, *, causal: bool = True, window: int = 0,
                        softcap: float = 0.0):
    """Pure-jnp oracle (full-materialization softmax)."""
    b, sq, kv, g, dh = q.shape
    sk = k.shape[1]
    s = jnp.einsum("bqkgd,bskd->bkgqs", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * (dh ** -0.5)
    if softcap > 0:
        s = softcap * jnp.tanh(s / softcap)
    q_pos = jnp.arange(sq)[:, None]
    k_pos = jnp.arange(sk)[None, :]
    mask = jnp.ones((sq, sk), bool)
    if causal:
        mask &= k_pos <= q_pos
    if window > 0:
        mask &= k_pos > q_pos - window
    s = jnp.where(mask[None, None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(jnp.any(mask, -1)[None, None, None, :, None], p, 0.0)
    out = jnp.einsum("bkgqs,bskd->bkgqd", p, v.astype(jnp.float32))
    return out.transpose(0, 3, 1, 2, 4)
