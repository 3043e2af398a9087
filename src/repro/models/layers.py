"""Layer library — every projection is quantization-aware (the paper's knob).

Params are plain nested dicts.  A quantized linear ("qlinear") has two
on-disk forms:

  train/QAT : {"qw": (K, N) float}          — fake-quant STE forward
  serving   : {"wt_packed": (N, KW) int32   — bit-packed W^T (or int8 codes
               "scale": (N,) f32}             when K doesn't pack), produced
                                              by convert.to_serving()

The serving matmul follows the kernel semantics in repro.kernels.ref — packed
weights are unpacked on the fly (HBM->VMEM bandwidth win) and the per-channel
scale is the fused BNS epilogue of paper eqs. (1)/(2).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.precision import (
    A_FLOAT,
    PrecisionConfig,
    W_FLOAT,
    get_precision,
    signed,
)
from repro.core.packing import pack_nibbles, unpack_nibbles
from repro.core.quantize import act_fake_quant, weight_fake_quant
from repro.kernels import engine, kv_pool

from .config import ModelConfig


def pdtype(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


# ---------------------------------------------------------------------------
# qlinear
# ---------------------------------------------------------------------------
def qlinear_init(key, k: int, n: int, cfg: ModelConfig, scale: float = None):
    s = scale if scale is not None else k ** -0.5
    w = jax.random.normal(key, (k, n), jnp.float32) * s
    return {"qw": w.astype(pdtype(cfg))}


def _serve_matmul(p, x, pcfg: PrecisionConfig):
    """Quantized-serving matmul via the precision-dispatch engine: the
    registry picks the kernel (jnp reference semantics on CPU, Pallas with
    autotuned tiles on TPU) and handles the dynamic symmetric per-row
    activation quantization for the integer MXU path (row-independent
    numerics, so the same call is shard_map-safe on local batches)."""
    pw = engine.as_packed_weight(p, pcfg)
    return engine.qmatmul(x, pw, pcfg)


def qlinear_apply(p, x, cfg: ModelConfig, quantize_acts: bool = True):
    """x @ W under the model's PrecisionConfig.  Dispatches on param form."""
    pcfg = signed(get_precision(cfg.precision))
    if "wt_packed" in p:
        return _serve_matmul(p, x, pcfg).astype(pdtype(cfg))
    w = p["qw"]
    if pcfg.w_mode == W_FLOAT:
        return jnp.dot(x, w.astype(x.dtype))
    if quantize_acts and pcfg.a_mode != A_FLOAT:
        x = act_fake_quant(x.astype(jnp.float32), pcfg).astype(x.dtype)
    return engine.fake_quant_dot(x, w, pcfg, axis=0)


# ---------------------------------------------------------------------------
# norms / rope / activations
# ---------------------------------------------------------------------------
def rmsnorm_init(d: int):
    return {"g": jnp.ones((d,), jnp.float32)}


def rmsnorm(p, x, eps: float = 1e-6):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * p["g"]).astype(x.dtype)


def _act(x, kind: str):
    if kind == "silu":
        return jax.nn.silu(x)
    if kind == "gelu":
        return jax.nn.gelu(x)
    raise ValueError(kind)


def rope(x, positions, theta: float):
    """x: (B, S, H, Dh); positions: (B, S) int32."""
    dh = x.shape[-1]
    half = dh // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    angles = positions[..., None].astype(jnp.float32) * freqs      # (B,S,half)
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def _softcap(x, cap: float):
    if cap <= 0:
        return x
    return cap * jnp.tanh(x / cap)


# ---------------------------------------------------------------------------
# attention (GQA + RoPE + sliding window + softcap + quantized KV cache)
# ---------------------------------------------------------------------------
def attn_init(key, cfg: ModelConfig, post_norms: bool = False):
    ks = jax.random.split(key, 4)
    d, h, kv, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.dh
    p = {
        "norm": rmsnorm_init(d),
        "wq": qlinear_init(ks[0], d, h * dh, cfg),
        "wk": qlinear_init(ks[1], d, kv * dh, cfg),
        "wv": qlinear_init(ks[2], d, kv * dh, cfg),
        "wo": qlinear_init(ks[3], h * dh, d, cfg),
    }
    if post_norms:
        p["post_norm"] = rmsnorm_init(d)
    return p


# nibble packing lives in core.packing (shared with the paged-attention
# kernel's in-VMEM decode); kept under the old names for local callers
_pack_nibbles = pack_nibbles
_unpack_nibbles = unpack_nibbles


def _kv_quantize(k, v, bits: int):
    """Symmetric per-(token, head) KV quantization — the paper's bandwidth
    saving applied to the decode-dominant tensor (beyond-paper, same
    mechanism).  Scales are per position so appends never re-scale history.
    bits=4 additionally nibble-packs along Dh (2 codes/byte)."""
    qmax = (1 << (bits - 1)) - 1
    def q(t):
        s = jnp.maximum(jnp.max(jnp.abs(t), axis=3, keepdims=True), 1e-6) / qmax
        codes = jnp.clip(jnp.round(t / s), -qmax, qmax).astype(jnp.int8)
        if bits == 4:
            codes = _pack_nibbles(codes)
        return codes, s.astype(jnp.float32)
    kq, ks = q(k.astype(jnp.float32))
    vq, vs = q(v.astype(jnp.float32))
    return kq, ks, vq, vs


def _kv_dequant(codes, s, dtype, bits: int = 8):
    if bits == 4:
        codes = _unpack_nibbles(codes)
    return (codes.astype(jnp.float32) * s).astype(dtype)


def _attend(q, k, v, mask, cfg: ModelConfig):
    """q: (B,Sq,H,Dh) k/v: (B,Sk,KV,Dh); mask: (B,1,Sq,Sk) or broadcastable."""
    b, sq, h, dh = q.shape
    kv = k.shape[2]
    groups = h // kv
    qg = q.reshape(b, sq, kv, groups, dh)
    scores = jnp.einsum("bqkgd,bskd->bkgqs", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) / (dh ** 0.5)
    scores = _softcap(scores, cfg.attn_softcap)
    scores = jnp.where(mask[:, :, None, :, :] if mask.ndim == 4 else mask,
                       scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgqs,bskd->bqkgd", probs, v.astype(jnp.float32))
    return out.reshape(b, sq, h * dh).astype(q.dtype)


ATTN_KV_CHUNK = 1024      # flash-style blocking threshold & block size


def _attend_flash(q, k, v, pos_q, pos_k, cfg: ModelConfig, *, causal: bool,
                  local: bool, kv_chunk: int = ATTN_KV_CHUNK):
    """Blockwise (FlashAttention-semantics) attention in pure JAX.

    Never materializes (Sq, Sk) — scans KV in chunks carrying running
    (max, denom, weighted-acc).  Used whenever Sk > kv_chunk; memory per step
    is O(Sq * kv_chunk).  Exact same math as _attend (fp32 softmax).

    q: (B,Sq,H,Dh); k/v: (B,Sk,KV,Dh); pos_q: (B,Sq); pos_k: (B,Sk).
    """
    b, sq, h, dh = q.shape
    kv = k.shape[2]
    g = h // kv
    sk = k.shape[1]
    n_chunks = sk // kv_chunk
    assert n_chunks * kv_chunk == sk, (sk, kv_chunk)
    qg = q.reshape(b, sq, kv, g, dh).astype(jnp.float32)
    scale = dh ** -0.5

    kc = k.reshape(b, n_chunks, kv_chunk, kv, dh).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(b, n_chunks, kv_chunk, kv, dh).transpose(1, 0, 2, 3, 4)
    pc = pos_k.reshape(b, n_chunks, kv_chunk).transpose(1, 0, 2)

    def step(carry, xs):
        m, l, acc = carry
        k_c, v_c, p_c = xs
        s = jnp.einsum("bqkgd,bskd->bkgqs", qg, k_c.astype(jnp.float32)) * scale
        s = _softcap(s, cfg.attn_softcap)
        mask = jnp.ones((b, 1, 1, sq, kv_chunk), bool)
        if causal:
            mask &= (p_c[:, None, None, None, :] <=
                     pos_q[:, None, None, :, None])
        if local:
            mask &= (p_c[:, None, None, None, :] >
                     pos_q[:, None, None, :, None] - cfg.window)
        s_for_max = jnp.where(mask, s, -1e30)
        m_new = jnp.maximum(m, jnp.max(s_for_max, axis=-1))
        p = jnp.where(mask, jnp.exp(s - m_new[..., None]), 0.0)
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        if cfg.attn_probs_bf16:
            # FA2-style: probabilities in [0,1] tolerate bf16; halves the
            # dominant (…,Sq,chunk) read of the P·V matmul (§Perf)
            pv = jnp.einsum("bkgqs,bskd->bkgqd", p.astype(jnp.bfloat16),
                            v_c.astype(jnp.bfloat16)).astype(jnp.float32)
        else:
            pv = jnp.einsum("bkgqs,bskd->bkgqd", p, v_c.astype(jnp.float32))
        acc_new = acc * corr[..., None] + pv
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, kv, g, sq), -1e30, jnp.float32)
    l0 = jnp.zeros((b, kv, g, sq), jnp.float32)
    acc0 = jnp.zeros((b, kv, g, sq, dh), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(jax.checkpoint(step), (m0, l0, acc0),
                                  (kc, vc, pc))
    out = acc / jnp.maximum(l[..., None], 1e-30)
    out = out.transpose(0, 3, 1, 2, 4).reshape(b, sq, h * dh)
    return out.astype(q.dtype)


def attn_apply(p, x, cfg: ModelConfig, positions, *, local: bool,
               cache=None, cache_pos=None, return_kv: bool = False):
    """Full-sequence (train/prefill) when cache is None, else cached decode.

    cache: dict {"k","v"[, "ks","vs"]} with k/v (B, S_max, KV, Dh) (int8 codes
    + scales when cfg.kv_bits); cache_pos: scalar current position (or (B,)
    per-slot positions for one-step decode).  With a cache and Sq > 1 this is
    the chunked-prefill append path: the whole chunk's KV is written at
    [cache_pos, cache_pos + Sq) and queries attend causally over the cache.
    Returns (out, new_cache_or_kv).
    """
    b = x.shape[0]
    h, kvh, dh = cfg.n_heads, cfg.n_kv_heads, cfg.dh
    xn = rmsnorm(p["norm"], x, cfg.norm_eps)
    q = qlinear_apply(p["wq"], xn, cfg).reshape(b, -1, h, dh)
    k = qlinear_apply(p["wk"], xn, cfg).reshape(b, -1, kvh, dh)
    v = qlinear_apply(p["wv"], xn, cfg).reshape(b, -1, kvh, dh)
    q = rope(q, positions, cfg.rope_theta)
    k = rope(k, positions, cfg.rope_theta)

    if cache is None:
        sq = x.shape[1]
        if sq > ATTN_KV_CHUNK and sq % ATTN_KV_CHUNK == 0:
            out = _attend_flash(q, k, v, positions, positions, cfg,
                                causal=True, local=local)
        else:
            i = positions[:, :, None]                   # (B,Sq,1) query pos
            j = positions[:, None, :]                   # (B,1,Sk) key pos
            mask = j <= i
            if local:
                mask &= j > i - cfg.window
            out = _attend(q, k, v, mask[:, None], cfg)
        new = (k, v) if return_kv else None
    elif x.shape[1] > 1:
        # chunk append (chunked prefill): scalar start position, all batch
        # rows advance together.  KV for the whole chunk lands in the cache
        # and queries attend over the cache with a causal position mask, so
        # interleaved decode steps never wait for a full-prompt prefill.
        s_max = cache["k"].shape[1]
        start = jnp.asarray(cache_pos, jnp.int32).reshape(())

        def write(buf, upd):
            return jax.lax.dynamic_update_slice(
                buf, upd.astype(buf.dtype), (0, start, 0, 0))

        if cfg.kv_bits:
            kq, ks, vq, vs = _kv_quantize(k, v, cfg.kv_bits)
            ck, cv = write(cache["k"], kq), write(cache["v"], vq)
            nks, nvs = write(cache["ks"], ks), write(cache["vs"], vs)
            new = {"k": ck, "v": cv, "ks": nks, "vs": nvs}
            kk = _kv_dequant(ck, nks, x.dtype, cfg.kv_bits)
            vv = _kv_dequant(cv, nvs, x.dtype, cfg.kv_bits)
        else:
            ck, cv = write(cache["k"], k), write(cache["v"], v)
            new = {"k": ck, "v": cv}
            kk, vv = ck, cv
        j = jnp.arange(s_max)[None, None, :]            # (1,1,S)
        qpos = positions[:, :, None]                    # (B,Sq,1)
        mask = (j <= qpos)[:, None]                     # (B,1,Sq,S)
        if local:
            mask &= (j > qpos - cfg.window)[:, None]
        out = _attend(q, kk, vv, mask, cfg)
    else:
        s_max = cache["k"].shape[1]
        # cache_pos: scalar OR per-batch (B,) vector (continuous batching —
        # slots join at different times, runtime/serving.py)
        pos_b = jnp.broadcast_to(jnp.asarray(cache_pos, jnp.int32), (b,))
        bidx = jnp.arange(b)

        def write(buf, upd):
            return buf.at[bidx, pos_b].set(upd[:, 0].astype(buf.dtype))

        if cfg.kv_bits:
            kq, ks, vq, vs = _kv_quantize(k, v, cfg.kv_bits)
            ck, cv = write(cache["k"], kq), write(cache["v"], vq)
            nks, nvs = write(cache["ks"], ks), write(cache["vs"], vs)
            new = {"k": ck, "v": cv, "ks": nks, "vs": nvs}
        else:
            ck, cv = write(cache["k"], k), write(cache["v"], v)
            new = {"k": ck, "v": cv}
        if cfg.kv_bits and not local and cfg.attn_softcap <= 0:
            # the serving hot path: engine-dispatched flash-decode over the
            # quantized cache (Pallas kernel on TPU; the xla registration is
            # the bit-exact jnp reference of the inline math below)
            q4 = q[:, 0].reshape(b, kvh, h // kvh, dh)
            out = engine.decode_attention(
                q4, new["k"], new["ks"], new["v"], new["vs"], pos_b,
                kv_bits=cfg.kv_bits, dtype=x.dtype)
            out = out.reshape(b, 1, h * dh)
        else:
            if cfg.kv_bits:
                kk = _kv_dequant(ck, nks, x.dtype, cfg.kv_bits)
                vv = _kv_dequant(cv, nvs, x.dtype, cfg.kv_bits)
            else:
                kk, vv = ck, cv
            j = jnp.arange(s_max)[None, :]                  # (1,S)
            mask = (j <= pos_b[:, None])[:, None, None]     # (B,1,1,S)
            if local:
                mask &= (j > pos_b[:, None] - cfg.window)[:, None, None]
            out = _attend(q, kk, vv, mask, cfg)

    out = qlinear_apply(p["wo"], out, cfg)
    if "post_norm" in p:
        out = rmsnorm(p["post_norm"], out, cfg.norm_eps)
    return out, new


def attn_apply_paged(p, x, cfg: ModelConfig, positions, *, local: bool,
                     pool, page_table, kv_bits: int, layer,
                     slot_map=None, fused: bool = True):
    """Attention over a block-paged KV pool (runtime.kvcache) instead of a
    per-slot dense cache.

    pool: this layer position's block storage as ``repro.kernels.kv_pool``
    lays it out, stacked over periods (``layer`` picks the period) —
    physical blocks shared by every request; block 0 is the reserved
    null/scratch block.  page_table: (B, n_blocks) int32
    mapping each sequence's logical block j to its physical block.
    positions: (B, Sq) query positions — Sq > 1 is a B=1 prefill-chunk
    append, Sq == 1 the batched decode step; both write the chunk/token KV
    rows into the owning blocks (``positions // bs`` -> page-table row ->
    physical block) in place, and attend over the (B, n_blocks*bs) page-table
    view with the causal position mask, so the math — and, for kv_bits=16,
    the bits — match the dense cache path exactly.

    Out-of-range positions (bucket padding past the pool view) and retired
    slots (their page-table rows are zeroed) deflect writes to the null
    block.  Returns (out, new_pool).

    Decode steps (Sq == 1, global, no softcap) take the **fused** path by
    default: one engine dispatch covering paged attention *and* the ``wo``
    projection, gridded over ``slot_map`` (live slots only; None = all
    slots).  ``fused=False`` keeps the two-dispatch legacy path for
    differential tests and benches.
    """
    b = x.shape[0]
    h, kvh, dh = cfg.n_heads, cfg.n_kv_heads, cfg.dh
    dh_s = kv_pool.store_dh(dh, kv_bits)
    nb = page_table.shape[1]
    bs = kv_pool.block_positions(pool[kv_pool.CODES], kvh, dh_s)
    s_pad = nb * bs
    xn = rmsnorm(p["norm"], x, cfg.norm_eps)
    q = qlinear_apply(p["wq"], xn, cfg).reshape(b, -1, h, dh)
    k = qlinear_apply(p["wk"], xn, cfg).reshape(b, -1, kvh, dh)
    v = qlinear_apply(p["wv"], xn, cfg).reshape(b, -1, kvh, dh)
    q = rope(q, positions, cfg.rope_theta)
    k = rope(k, positions, cfg.rope_theta)
    sq = x.shape[1]

    # ---- block writes: (b, sq) positions -> (physical block, offset) ------
    pos = jnp.asarray(positions, jnp.int32)                    # (B, Sq)
    lb = jnp.clip(pos // bs, 0, nb - 1)
    phys = jnp.take_along_axis(page_table.astype(jnp.int32), lb, axis=1)
    phys = jnp.where(pos < s_pad, phys, 0)                     # OOB -> null
    off = pos % bs
    flat = lambda t: None if t is None else t.reshape(b * sq, *t.shape[2:])
    if kv_bits < 16:
        kq, ks, vq, vs = _kv_quantize(k, v, kv_bits)
    else:
        kq, ks, vq, vs = k, None, v, None
    new = kv_pool.write(pool, layer, phys.reshape(-1), off.reshape(-1),
                        flat(kq), flat(ks), flat(vq), flat(vs))
    codes, scales = new[kv_pool.CODES], new.get(kv_pool.SCALES)

    if sq == 1 and not local and cfg.attn_softcap <= 0:
        # batched decode: engine-dispatched paged attention (page-table
        # prefetch Pallas kernel on TPU; the xla registration gathers the
        # dense view and reproduces the chunk path's _attend bit-exactly)
        q4 = q[:, 0].reshape(b, kvh, h // kvh, dh)
        if fused:
            # fused ragged decode: attention + wo projection in one engine
            # dispatch over the live slots; dead rows come back as zeros
            # (their residual stream is never emitted)
            pcfg = signed(get_precision(cfg.precision))
            out = engine.fused_paged_decode(
                q4, codes, scales, page_table.astype(jnp.int32), pos[:, 0],
                slot_map, p["wo"], pcfg, layer=layer, kv_bits=kv_bits,
                dtype=x.dtype)
            if "post_norm" in p:
                out = rmsnorm(p["post_norm"], out, cfg.norm_eps)
            return out, new
        out = engine.paged_attention(
            q4, codes, scales, page_table.astype(jnp.int32), pos[:, 0],
            layer=layer, kv_bits=kv_bits, dtype=x.dtype)
        out = out.reshape(b, 1, h * dh)
    else:
        # prefill-chunk append (or local/softcap attention): attend over the
        # gathered dense (B, s_pad) page-table view
        kg, ksg, vg, vsg = kv_pool.gather(codes, scales, page_table, layer,
                                          kvh, dh_s)
        if kv_bits < 16:
            kk = _kv_dequant(kg, ksg, x.dtype, kv_bits)
            vv = _kv_dequant(vg, vsg, x.dtype, kv_bits)
        else:
            kk, vv = kg, vg
        j = jnp.arange(s_pad)[None, None, :]                   # (1,1,S)
        qpos = pos[:, :, None]                                 # (B,Sq,1)
        mask = (j <= qpos)[:, None]                            # (B,1,Sq,S)
        if local:
            mask &= (j > qpos - cfg.window)[:, None]
        out = _attend(q, kk, vv, mask, cfg)

    out = qlinear_apply(p["wo"], out, cfg)
    if "post_norm" in p:
        out = rmsnorm(p["post_norm"], out, cfg.norm_eps)
    return out, new


def make_kv_pool(cfg: ModelConfig, num_blocks: int, block_size: int,
                 kv_bits: int, stacked: int = None):
    """Block-pool pytree for one attention layer (or stacked leading dim):
    ``num_blocks`` physical blocks of ``block_size`` positions each, laid out
    as ``repro.kernels.kv_pool`` defines.  Block 0 is reserved as the
    null/scratch block (never allocated)."""
    return kv_pool.make(num_blocks, block_size, cfg.n_kv_heads, cfg.dh,
                        kv_bits, cfg.dtype, stacked)


def make_kv_cache(cfg: ModelConfig, b: int, s_max: int, stacked: int = None):
    """Cache pytree for one layer (or stacked leading dim)."""
    kvh, dh = cfg.n_kv_heads, cfg.dh
    lead = (stacked,) if stacked else ()
    if cfg.kv_bits:
        dh_store = dh // 2 if cfg.kv_bits == 4 else dh
        return {
            "k": jnp.zeros(lead + (b, s_max, kvh, dh_store), jnp.int8),
            "v": jnp.zeros(lead + (b, s_max, kvh, dh_store), jnp.int8),
            "ks": jnp.full(lead + (b, s_max, kvh, 1), 1e-6, jnp.float32),
            "vs": jnp.full(lead + (b, s_max, kvh, 1), 1e-6, jnp.float32),
        }
    dt = jnp.dtype(cfg.dtype)
    return {
        "k": jnp.zeros(lead + (b, s_max, kvh, dh), dt),
        "v": jnp.zeros(lead + (b, s_max, kvh, dh), dt),
    }


# ---------------------------------------------------------------------------
# dense FFN (gated SwiGLU or plain 2-matrix)
# ---------------------------------------------------------------------------
def ffn_init(key, cfg: ModelConfig, gated: bool = True, post_norms: bool = False):
    ks = jax.random.split(key, 3)
    d, f = cfg.d_model, cfg.d_ff
    p = {"norm": rmsnorm_init(d),
         "w_up": qlinear_init(ks[1], d, f, cfg),
         "w_down": qlinear_init(ks[2], f, d, cfg)}
    if gated:
        p["w_gate"] = qlinear_init(ks[0], d, f, cfg)
    if post_norms:
        p["post_norm"] = rmsnorm_init(d)
    return p


def ffn_apply(p, x, cfg: ModelConfig):
    xn = rmsnorm(p["norm"], x, cfg.norm_eps)
    up = qlinear_apply(p["w_up"], xn, cfg)
    if "w_gate" in p:
        up = _act(qlinear_apply(p["w_gate"], xn, cfg), cfg.act_fn) * up
    else:
        up = _act(up, cfg.act_fn)
    out = qlinear_apply(p["w_down"], up, cfg)
    if "post_norm" in p:
        out = rmsnorm(p["post_norm"], out, cfg.norm_eps)
    return out


# ---------------------------------------------------------------------------
# MoE (token-choice top-k, capacity + gather dispatch — SPMD-safe)
# ---------------------------------------------------------------------------
def moe_init(key, cfg: ModelConfig):
    ks = jax.random.split(key, 4)
    d, f, e = cfg.d_model, cfg.moe_d_ff, cfg.n_experts
    dt = pdtype(cfg)
    return {
        "norm": rmsnorm_init(d),
        "w_router": jax.random.normal(ks[0], (d, e), jnp.float32) * d ** -0.5,
        "w_gate": (jax.random.normal(ks[1], (e, d, f), jnp.float32) * d ** -0.5).astype(dt),
        "w_up": (jax.random.normal(ks[2], (e, d, f), jnp.float32) * d ** -0.5).astype(dt),
        "w_down": (jax.random.normal(ks[3], (e, f, d), jnp.float32) * f ** -0.5).astype(dt),
    }


def _expert_matmul(w, x, cfg: ModelConfig):
    """x: (E, C, K) @ w: (E, K, N) with fake-quant under the model precision
    (expert weights are the paper's biggest storage win — see DESIGN §4)."""
    pcfg = signed(get_precision(cfg.precision))
    if isinstance(w, dict):                            # serving: packed per expert
        return engine.qmatmul_experts(x, w, pcfg)
    if pcfg.w_mode != W_FLOAT:
        w = weight_fake_quant(w.astype(jnp.float32), pcfg, axis=1).astype(x.dtype)
    return jnp.einsum("eck,ekn->ecn", x, w.astype(x.dtype))


def moe_apply(p, x, cfg: ModelConfig):
    """Token-choice top-k MoE with capacity, slot-map dispatch.

    SPMD-aware formulation: build an (E, cap) slot->token index map, then
      dispatch = x[tok_map]          (gather FROM token-sharded x)
      combine  = zeros(T).at[tok_map].add(y * gate_map)
                                     (scatter-add FROM expert-sharded y)
    Under pjit this moves O(T*D) per model shard instead of all-gathering the
    O(E*cap*D) expert buffer (the baseline's dominant collective —
    EXPERIMENTS.md §Perf kimi iteration 1).  Dropped (over-capacity) slots
    point at a dummy row T which is sliced off.
    """
    if cfg.moe_impl == "shard_map":
        from jax._src.mesh import thread_resources
        mesh = thread_resources.env.physical_mesh
        if not mesh.empty and "model" in mesh.axis_names:
            from repro.parallel.moe_shard_map import moe_apply_shard_map
            return moe_apply_shard_map(p, x, cfg, mesh)
        # no mesh context (smoke tests) -> fall through to the pjit path

    b, s, d = x.shape
    t = b * s
    e, k = cfg.n_experts, cfg.top_k
    cap = int(t * k / e * cfg.capacity_factor) or 1

    xin = rmsnorm(p["norm"], x, cfg.norm_eps).reshape(t, d)
    logits = jnp.dot(xin.astype(jnp.float32), p["w_router"])
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_i = jax.lax.top_k(probs, k)                    # (T, k)
    top_p = top_p / jnp.maximum(jnp.sum(top_p, -1, keepdims=True), 1e-9)

    flat_e = top_i.reshape(-1)                                # (T*k,)
    onehot = jax.nn.one_hot(flat_e, e, dtype=jnp.int32)       # (T*k, E)
    pos = jnp.take_along_axis(jnp.cumsum(onehot, axis=0) - 1,
                              flat_e[:, None], axis=1)[:, 0]  # (T*k,)
    keep = pos < cap
    tok = jnp.repeat(jnp.arange(t), k)

    # slot maps: (E, cap) token index (T = dummy) and gate weight.
    # over-capacity entries are routed to the OOB expert index e so that
    # mode="drop" discards them (writing a dummy at [e, 0] would clobber a
    # legitimate slot-0 token)
    e_idx = jnp.where(keep, flat_e, e)
    tok_map = jnp.full((e, cap), t, jnp.int32)
    tok_map = tok_map.at[e_idx, pos].set(tok, mode="drop")
    gate_map = jnp.zeros((e, cap), jnp.float32)
    gate_map = gate_map.at[e_idx, pos].set(top_p.reshape(-1), mode="drop")

    x_pad = jnp.concatenate([xin, jnp.zeros((1, d), xin.dtype)], axis=0)
    buf = x_pad[tok_map]                                      # (E, cap, D)
    if cfg.moe_ep_constraints:
        # Pin the dispatch buffers to an expert-parallel layout.  "ep_fsdp"
        # additionally shards the CONTRACTION dim over 'data' to match the
        # FSDP-sharded expert weights — the einsum then runs as partial sums
        # + all-reduce of the small (E,cap,N) output instead of all-gathering
        # the K-sharded weights every microbatch (EXPERIMENTS.md §Perf kimi
        # iterations 4-5; iteration 4's output-only pin was refuted).
        from jax.sharding import PartitionSpec as _P
        kshard = "data" if cfg.moe_ep_constraints == "ep_fsdp" else None
        buf = jax.lax.with_sharding_constraint(buf, _P("model", None, kshard))

    h = _act(_expert_matmul(p["w_gate"], buf, cfg), cfg.act_fn) * \
        _expert_matmul(p["w_up"], buf, cfg)
    if cfg.moe_ep_constraints == "ep_fsdp":
        h = jax.lax.with_sharding_constraint(h, _P("model", None, "data"))
    y = _expert_matmul(p["w_down"], h, cfg)                   # (E, cap, D)
    if cfg.moe_ep_constraints:
        y = jax.lax.with_sharding_constraint(y, _P("model", None, None))

    out_pad = jnp.zeros((t + 1, d), jnp.float32)
    out_pad = out_pad.at[tok_map.reshape(-1)].add(
        (y.astype(jnp.float32) * gate_map[..., None]).reshape(e * cap, d))
    out = out_pad[:t]
    # aux load-balance loss (Switch): stored for the training loop
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(jax.nn.one_hot(top_i[:, 0], e, dtype=jnp.float32), axis=0)
    aux = e * jnp.sum(me * ce)
    return out.reshape(b, s, d).astype(x.dtype), aux


# ---------------------------------------------------------------------------
# Mamba-1 (chunked selective scan; O(1) decode state)
# ---------------------------------------------------------------------------
def mamba_init(key, cfg: ModelConfig):
    ks = jax.random.split(key, 6)
    d, di, r, n = cfg.d_model, cfg.d_inner, cfg.dt_rank_, cfg.ssm_state
    dt = pdtype(cfg)
    return {
        "norm": rmsnorm_init(d),
        "w_in": qlinear_init(ks[0], d, 2 * di, cfg),
        "conv_w": (jax.random.normal(ks[1], (cfg.ssm_conv, di), jnp.float32) * 0.2).astype(dt),
        "conv_b": jnp.zeros((di,), jnp.float32),
        "w_x": qlinear_init(ks[2], di, r + 2 * n, cfg),
        "w_dt": qlinear_init(ks[3], r, di, cfg, scale=r ** -0.5),
        "dt_bias": jnp.log(jnp.expm1(
            jnp.exp(jax.random.uniform(ks[4], (di,), jnp.float32,
                                       jnp.log(1e-3), jnp.log(1e-1))))),
        "A_log": jnp.log(jnp.tile(jnp.arange(1, n + 1, dtype=jnp.float32), (di, 1))),
        "D": jnp.ones((di,), jnp.float32),
        "w_out": qlinear_init(ks[5], di, d, cfg),
    }


def _causal_conv(x, w, b, state=None):
    """Depthwise causal conv over seq.  x: (B,S,Di), w: (K,Di).  If ``state``
    ((B, K-1, Di)) is given, continues from it: one-step decode for S == 1,
    chunk continuation (chunked prefill) for S > 1; returns the new state."""
    kk = w.shape[0]
    if state is None:
        xp = jnp.pad(x, ((0, 0), (kk - 1, 0), (0, 0)))
        out = sum(xp[:, i:i + x.shape[1], :] * w[i] for i in range(kk))
        return out + b, xp[:, -(kk - 1):, :] if kk > 1 else None
    if x.shape[1] > 1:                                        # chunk append
        xp = jnp.concatenate([state.astype(x.dtype), x], axis=1)
        out = sum(xp[:, i:i + x.shape[1], :] * w[i] for i in range(kk))
        return out + b, xp[:, -(kk - 1):, :] if kk > 1 else state
    xs = jnp.concatenate([state, x], axis=1)                  # (B, K, Di)
    out = jnp.einsum("bkd,kd->bd", xs.astype(jnp.float32),
                     w.astype(jnp.float32))[:, None, :].astype(x.dtype)
    return out + b, xs[:, 1:, :]


def _ssm_scan_chunked(dt, xs, bmat, cmat, a_mat, h0, chunk: int):
    """Selective scan h_t = exp(dt_t A) h_{t-1} + dt_t B_t x_t ; y_t = C_t.h_t.

    dt, xs: (B,S,Di); bmat, cmat: (B,S,N); a_mat: (Di,N); h0: (B,Di,N).
    The (B, chunk, Di, N) decay/drive tensors are formed PER CHUNK inside the
    scan (never for the full sequence) — peak intermediate is O(B*chunk*Di*N),
    which is what makes 64-layer mamba trainable at 4k (DESIGN.md §Perf).
    Returns (y (B,S,Di) fp32, h_last (B,Di,N))."""
    b, s, di = dt.shape
    n = a_mat.shape[1]
    nc = max(s // chunk, 1)
    lc = s // nc
    reshape_c = lambda t: t.reshape(b, nc, lc, *t.shape[2:]).swapaxes(0, 1)
    dt_c, xs_c, b_c, c_c = map(reshape_c, (dt, xs, bmat, cmat))

    def combine(l, r):
        return (r[0] * l[0], r[0] * l[1] + r[1])

    def step(h, inputs):
        dtk, xsk, bk, ck = inputs                        # (B, lc, ...)
        decay = jnp.exp(dtk[..., None] * a_mat[None, None])       # (B,lc,Di,N)
        drive = (dtk * xsk)[..., None] * bk[:, :, None, :]
        aa, bb = jax.lax.associative_scan(combine, (decay, drive), axis=1)
        h_all = aa * h[:, None] + bb                     # (B,lc,Di,N)
        y = jnp.einsum("bldn,bln->bld", h_all, ck)
        return h_all[:, -1], y

    h_last, y_c = jax.lax.scan(jax.checkpoint(step), h0,
                               (dt_c, xs_c, b_c, c_c))
    y = y_c.swapaxes(0, 1).reshape(b, s, di)
    return y, h_last


def mamba_apply(p, x, cfg: ModelConfig, state=None):
    """state: None (train/prefill) or {"conv": (B,K-1,Di), "ssm": (B,Di,N)}.
    Returns (out, new_state) — new_state is None for train, final state for
    prefill/decode."""
    b = x.shape[0]
    di, n, r = cfg.d_inner, cfg.ssm_state, cfg.dt_rank_
    xn = rmsnorm(p["norm"], x, cfg.norm_eps)
    xz = qlinear_apply(p["w_in"], xn, cfg)
    xs, z = jnp.split(xz, 2, axis=-1)                          # (B,S,Di) each

    conv_state = state["conv"] if state is not None else None
    xs, new_conv = _causal_conv(xs, p["conv_w"].astype(jnp.float32),
                                p["conv_b"], conv_state)
    xs = jax.nn.silu(xs.astype(jnp.float32)).astype(x.dtype)

    dbc = qlinear_apply(p["w_x"], xs, cfg)
    dt_r, b_, c_ = jnp.split(dbc, [r, r + n], axis=-1)
    dt = jax.nn.softplus(qlinear_apply(p["w_dt"], dt_r, cfg).astype(jnp.float32)
                         + p["dt_bias"])                       # (B,S,Di)
    a_mat = -jnp.exp(p["A_log"])                               # (Di,N)

    if state is None or xs.shape[1] > 1:
        h0 = state["ssm"] if state is not None else jnp.zeros((b, di, n), jnp.float32)
        y, h_last = _ssm_scan_chunked(dt, xs.astype(jnp.float32),
                                      b_.astype(jnp.float32),
                                      c_.astype(jnp.float32), a_mat, h0,
                                      cfg.ssm_chunk)
    else:                                                       # one-step decode
        decay = jnp.exp(dt[:, 0, :, None] * a_mat[None])        # (B,Di,N)
        drive = (dt[:, 0] * xs[:, 0].astype(jnp.float32))[..., None] * \
            b_[:, 0].astype(jnp.float32)[:, None, :]
        h_last = decay * state["ssm"] + drive
        y = jnp.einsum("bdn,bn->bd", h_last,
                       c_[:, 0].astype(jnp.float32))[:, None]   # (B,1,Di)

    y = y + p["D"] * xs.astype(jnp.float32)
    y = (y * jax.nn.silu(z.astype(jnp.float32))).astype(x.dtype)
    out = qlinear_apply(p["w_out"], y, cfg)
    new_state = None
    if state is not None or xs.shape[1] > 1:
        new_state = {"conv": new_conv if new_conv is not None else
                     jnp.zeros((b, cfg.ssm_conv - 1, di), x.dtype),
                     "ssm": h_last}
    return out, new_state


def make_ssm_state(cfg: ModelConfig, b: int, stacked: int = None):
    lead = (stacked,) if stacked else ()
    return {"conv": jnp.zeros(lead + (b, cfg.ssm_conv - 1, cfg.d_inner),
                              jnp.dtype(cfg.dtype)),
            "ssm": jnp.zeros(lead + (b, cfg.d_inner, cfg.ssm_state), jnp.float32)}
