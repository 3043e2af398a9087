"""Plain reference for a dense decoder with grouped-query attention and a
gated SiLU FFN, under the paper's ``<A>xT`` precisions: every projection
has ternary weights (TWN: threshold 0.7 * mean|w| per output channel, scale
the mean of the kept |w|) and signed ``A``-bit activations (codes in
[-qmax, qmax], qmax = 2**(A-1) - 1, scale the row's absmax over qmax);
keys and values are stored as int8 with a scale per position and head; the
residual stream and every stored activation are bfloat16; norms, softmax
and accumulation are float32.  The LM head is a bfloat16 matmul (the paper
keeps the last layer wide).

It imports nothing of the program and takes nothing the program made.
Weights come from ``bench.weights`` (the benchmark's own generator, the
same draws the program was given), made again here one layer at a time
inside the layer scan.  It runs each whole sequence (prompt and served
tokens) at once with a causal mask, over its own keys and values.

``a_bits`` below the configuration's is the control: the same reference
one precision lower (int4 activation codes for int8).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bench import weights as W

F32 = jnp.float32
BF16 = jnp.bfloat16
HI = jax.lax.Precision.HIGHEST


def act_bits(precision: str) -> int:
    """"8xT" -> 8: the activation bits of a ternary-weight precision."""
    a, w = precision.split("x")
    if w != "T":
        raise ValueError(f"this reference has ternary weights, not {w!r}")
    return int(a)


def ternarize(w):
    """(K, N) float -> ternary codes (K, N) float32 and scales (N,)."""
    a = jnp.abs(w.astype(F32))
    delta = 0.7 * jnp.mean(a, axis=0, keepdims=True)
    keep = a > delta
    codes = jnp.where(keep, jnp.sign(w.astype(F32)), 0.0)
    alpha = jnp.sum(a * keep, axis=0) / jnp.maximum(jnp.sum(keep, axis=0), 1)
    return codes, alpha


def _rmsnorm(x, g, eps):
    xf = x.astype(F32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * g).astype(BF16)


def _qlinear(x, w, a_bits):
    """Signed per-row activation codes times ternary weights."""
    codes, alpha = w
    qmax = (1 << (a_bits - 1)) - 1
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=-1, keepdims=True),
                    jnp.asarray(1e-8, x.dtype)) / qmax
    q = jnp.clip(jnp.round(x / s), -qmax, qmax)
    acc = jnp.einsum("...k,kn->...n", q.astype(F32), codes, precision=HI)
    return (acc * alpha * s.astype(F32)).astype(BF16)


def _rope(x, theta):
    s, half = x.shape[1], x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=F32) / half)
    ang = jnp.arange(s, dtype=F32)[:, None] * freqs            # (S, half)
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = jnp.split(x.astype(F32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                           axis=-1).astype(BF16)


def _kv8(t):
    """int8 codes and a float32 scale per position and head, dequantized
    to the model's bfloat16."""
    tf = t.astype(F32)
    s = jnp.maximum(jnp.max(jnp.abs(tf), axis=-1, keepdims=True), 1e-6) / 127
    c = jnp.clip(jnp.round(tf / s), -127, 127).astype(jnp.int8)
    return (c.astype(F32) * s).astype(BF16)


def _layer(m, a_bits, x, lw):
    b, s, _ = x.shape
    h, kv, dh = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    t = {k: ternarize(v) for k, v in lw.items() if k.endswith(".qw")}
    xn = _rmsnorm(x, lw["layer_0.attn.norm.g"], m["norm_eps"])
    q = _qlinear(xn, t["layer_0.attn.wq.qw"], a_bits).reshape(b, s, h, dh)
    k = _qlinear(xn, t["layer_0.attn.wk.qw"], a_bits).reshape(b, s, kv, dh)
    v = _qlinear(xn, t["layer_0.attn.wv.qw"], a_bits).reshape(b, s, kv, dh)
    q, k = _rope(q, m["rope_theta"]), _rope(k, m["rope_theta"])
    k, v = _kv8(k), _kv8(v)
    qg = q.reshape(b, s, kv, h // kv, dh).astype(F32)
    sc = jnp.einsum("bqkgd,bskd->bkgqs", qg, k.astype(F32),
                    precision=HI) / (dh ** 0.5)
    causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    p = jax.nn.softmax(jnp.where(causal, sc, -1e30), axis=-1)
    o = jnp.einsum("bkgqs,bskd->bqkgd", p, v.astype(F32), precision=HI)
    o = o.reshape(b, s, h * dh).astype(BF16)
    x = x + _qlinear(o, t["layer_0.attn.wo.qw"], a_bits)
    xn = _rmsnorm(x, lw["layer_0.ffn.norm.g"], m["norm_eps"])
    up = _qlinear(xn, t["layer_0.ffn.w_up.qw"], a_bits)
    gate = _qlinear(xn, t["layer_0.ffn.w_gate.qw"], a_bits)
    hid = jax.nn.silu(gate) * up
    return x + _qlinear(hid, t["layer_0.ffn.w_down.qw"], a_bits)


@functools.partial(jax.jit, static_argnums=(1, 2))
def hidden(key, m_items, a_bits, tokens):
    """For token rows (B, S): the final-norm output (B, S, D)."""
    m = dict(m_items)
    g = W.global_weights(key, m)
    x = g["embed.w"][tokens].astype(BF16)

    def body(x, layer):
        return _layer(m, a_bits, x, W.layer_weights(key, m, layer)), None

    x, _ = jax.lax.scan(body, x, jnp.arange(m["n_layers"], dtype=jnp.int32))
    return _rmsnorm(x, g["final_norm.g"], m["norm_eps"])


@functools.partial(jax.jit, static_argnums=(1,))
def logits_at(key, m_items, h, ids):
    """For hidden rows (R, D), with logits rounded as the head's bfloat16
    output: the best logit, the logit of ``ids`` (R,), and the argmax."""
    m = dict(m_items)
    g = W.global_weights(key, m)
    head = g["embed.w"].T if m["tie_embeddings"] else g["lm_head.qw"]
    out = jnp.dot(h.astype(F32), head.astype(F32), precision=HI)
    lg = out.astype(BF16).astype(F32)[:, :m["vocab"]]
    return (lg.max(axis=1), jnp.take_along_axis(lg, ids[:, None], 1)[:, 0],
            jnp.argmax(lg, axis=1))
