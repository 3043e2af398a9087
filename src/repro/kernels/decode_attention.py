"""Flash-decode attention over an int8-quantized KV cache — the serving
hot-spot kernel.

One new token's query attends to a seq_len cache.  HBM traffic is the cache
itself, so the cache stays int8 (per-token, per-head scales — the paper's
storage saving applied to KV, DESIGN.md §4) and is dequantized in VMEM.
Online-softmax accumulation over KV chunks; GQA: G = H/KV query heads share
each KV head.

Layout (per device, post-sharding):
  q        : (B, KV, G, Dh)   bf16/f32 (current token's queries, grouped)
  k_codes  : (B, S, KV, Dh)   int8
  k_scale  : (B, S, KV, 1)    f32
  v_codes  : (B, S, KV, Dh)   int8
  v_scale  : (B, S, KV, 1)    f32
  pos      : int32 scalar or (B,) per-slot positions (mask: s <= pos[b])
  out      : (B, KV, G, Dh)   f32

Grid: (B, KV, S/chunk), S innermost; scratch m/l/acc carried across chunks.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(pos_ref, q_ref, kc_ref, ks_ref, vc_ref, vs_ref, out_ref,
            m_ref, l_ref, acc_ref, *, chunk: int, n_chunks: int, dh: int):
    # pos_ref block is this batch row's (1, 1) position (per-slot positions
    # for continuous batching — slots join at different times)
    c = pl.program_id(2)

    @pl.when(c == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -1e30)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)                      # (G, Dh)
    k = kc_ref[0, :, 0].astype(jnp.float32) * ks_ref[0, :, 0]  # (chunk, Dh)
    s = jnp.dot(q, k.T) * (dh ** -0.5)                       # (G, chunk)
    idx = c * chunk + jax.lax.broadcasted_iota(jnp.int32, (1, chunk), 1)
    mask = idx <= pos_ref[0]                                 # (1, chunk)
    s_masked = jnp.where(mask, s, -1e30)

    m_prev = m_ref[...]                                      # (G, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s_masked, axis=1, keepdims=True))
    p = jnp.where(mask, jnp.exp(s - m_new), 0.0)             # (G, chunk)
    corr = jnp.exp(m_prev - m_new)                           # (G, 1)
    v = vc_ref[0, :, 0].astype(jnp.float32) * vs_ref[0, :, 0]  # (chunk, Dh)
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jnp.dot(p, v)
    m_ref[...] = m_new

    @pl.when(c == n_chunks - 1)
    def _done():
        out_ref[0, 0] = (acc_ref[...] /
                         jnp.maximum(l_ref[...], 1e-30)).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def decode_attention(q, k_codes, k_scale, v_codes, v_scale, pos, *,
                     chunk: int = 512, interpret: bool = False):
    b, kv, g, dh = q.shape
    s = k_codes.shape[1]
    chunk = min(chunk, s)
    assert s % chunk == 0
    n_chunks = s // chunk
    pos2 = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1, 1), (b, 1))

    return pl.pallas_call(
        functools.partial(_kernel, chunk=chunk, n_chunks=n_chunks, dh=dh),
        grid=(b, kv, n_chunks),
        in_specs=[
            pl.BlockSpec((1, 1), lambda bi, ki, ci: (bi, 0)),
            pl.BlockSpec((1, 1, g, dh), lambda bi, ki, ci: (bi, ki, 0, 0)),
            pl.BlockSpec((1, chunk, 1, dh), lambda bi, ki, ci: (bi, ci, ki, 0)),
            pl.BlockSpec((1, chunk, 1, 1), lambda bi, ki, ci: (bi, ci, ki, 0)),
            pl.BlockSpec((1, chunk, 1, dh), lambda bi, ki, ci: (bi, ci, ki, 0)),
            pl.BlockSpec((1, chunk, 1, 1), lambda bi, ki, ci: (bi, ci, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, g, dh), lambda bi, ki, ci: (bi, ki, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, kv, g, dh), jnp.float32),
        scratch_shapes=[pltpu.VMEM((g, 1), jnp.float32),
                        pltpu.VMEM((g, 1), jnp.float32),
                        pltpu.VMEM((g, dh), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(pos2, q, k_codes, k_scale, v_codes, v_scale)


def decode_attention_ref(q, k_codes, k_scale, v_codes, v_scale, pos):
    """Pure-jnp oracle: dequant + masked softmax + weighted sum."""
    b, kv, g, dh = q.shape
    s = k_codes.shape[1]
    pos_b = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (b,))
    k = k_codes.astype(jnp.float32) * k_scale                # (B,S,KV,Dh)
    v = v_codes.astype(jnp.float32) * v_scale
    scores = jnp.einsum("bkgd,bskd->bkgs", q.astype(jnp.float32), k) \
        * (dh ** -0.5)
    mask = jnp.arange(s)[None, None, None, :] <= pos_b[:, None, None, None]
    scores = jnp.where(mask, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bkgs,bskd->bkgd", probs, v)


def decode_attention_serving_ref(q, k_codes, k_scale, v_codes, v_scale,
                                 pos, *, kv_bits: int = 8,
                                 dtype=jnp.float32):
    """The serving model's dense one-step decode attention, op-for-op.

    This is the ``xla``-backend implementation the engine dispatches the
    serving decode path to: it reproduces ``models.layers`` BIT-EXACTLY
    (dequant to the model dtype, the same grouped einsum contraction, the
    same ``/ sqrt(dh)`` scaling, -1e30 mask fill, fp32 softmax), so wiring
    the engine dispatch into the decode path changes nothing on the XLA
    backend — only the TPU backend swaps in the Pallas kernel above.

    q: (B, KV, G, Dh); codes (B, S, KV, Dh'), scales (B, S, KV, 1);
    pos scalar or (B,).  kv_bits=4 nibble-unpacks the codes; scales must be
    None iff kv_bits=16 (raw model-dtype storage).  Returns (B, KV, G, Dh)
    in ``dtype``.
    """
    from repro.core.packing import unpack_nibbles
    b, kv, g, dh = q.shape
    if kv_bits == 4:
        k_codes, v_codes = unpack_nibbles(k_codes), unpack_nibbles(v_codes)
    if k_scale is None:
        kk, vv = k_codes.astype(dtype), v_codes.astype(dtype)
    else:
        kk = (k_codes.astype(jnp.float32) * k_scale).astype(dtype)
        vv = (v_codes.astype(jnp.float32) * v_scale).astype(dtype)
    s = kk.shape[1]
    pos_b = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (b,))
    # identical op sequence to layers._attend with Sq == 1 and a (B,1,1,S)
    # mask (broadcast to (B,1,1,1,S) over the kv/group axes)
    qg = q.reshape(b, 1, kv, g, dh)
    scores = jnp.einsum("bqkgd,bskd->bkgqs", qg.astype(jnp.float32),
                        kk.astype(jnp.float32)) / (dh ** 0.5)
    mask = (jnp.arange(s)[None, :] <= pos_b[:, None])[:, None, None, None, :]
    scores = jnp.where(mask, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgqs,bskd->bqkgd", probs, vv.astype(jnp.float32))
    return out[:, 0].astype(dtype)
