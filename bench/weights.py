"""Float weights made from the seed, one layer at a time.

The benchmark owns the weights: both the system under test (which packs
them with its own ``to_serving``) and the plain reference (which quantizes
them itself) draw them from here, so the reference takes nothing the
program made.  Every leaf is addressed by its name inside one layer period
and by the layer index, so any slice of layers can be made on its own and
the reference can remake one layer while it runs.

Leaf names follow the program's parameter tree for a dense GQA decoder
(``layer_0.attn.wq.qw`` and so on); matrices and the embedding are stored
in the model's dtype, norm gains in float32, as the program stores them.
"""
from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed: int):
    """A PRNG key for any whole number up to 64 bits."""
    seed = int(seed)
    key = jax.random.PRNGKey(0)
    for word in (seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF):
        key = jax.random.fold_in(key, np.uint32(word))
    return key


def _name_key(key, name: str):
    return jax.random.fold_in(key, np.uint32(zlib.crc32(name.encode())))


def layer_leaves(m: dict) -> dict:
    """name -> (shape, init) for one layer period; init is "ones" or the
    standard deviation of a normal draw."""
    d, h, kv, dh, f = (m["d_model"], m["n_heads"], m["n_kv_heads"],
                       m["head_dim"], m["d_ff"])
    return {
        "layer_0.attn.norm.g": ((d,), "ones"),
        "layer_0.attn.wq.qw": ((d, h * dh), d ** -0.5),
        "layer_0.attn.wk.qw": ((d, kv * dh), d ** -0.5),
        "layer_0.attn.wv.qw": ((d, kv * dh), d ** -0.5),
        "layer_0.attn.wo.qw": ((h * dh, d), (h * dh) ** -0.5),
        "layer_0.ffn.norm.g": ((d,), "ones"),
        "layer_0.ffn.w_gate.qw": ((d, f), d ** -0.5),
        "layer_0.ffn.w_up.qw": ((d, f), d ** -0.5),
        "layer_0.ffn.w_down.qw": ((f, d), f ** -0.5),
    }


def global_leaves(m: dict) -> dict:
    d, v = m["d_model"], -(-m["vocab"] // 512) * 512
    out = {"embed.w": ((v, d), 0.02), "final_norm.g": ((d,), "ones")}
    if not m["tie_embeddings"]:
        out["lm_head.qw"] = ((d, v), d ** -0.5)
    return out


def _make(key, name, shape, init, dtype, ternary=False):
    """A normal draw, or — for a ``ternary`` projection matrix — its
    ternary image: sign(z) where |z| > 0.6745 (half the entries), times
    1.2 x the standard deviation.  Such weights are what a model trained
    for ternary weights holds, and their conversion to codes and scales is
    exact whatever order its sums run in."""
    if init == "ones":
        return jnp.ones(shape, jnp.float32)
    z = jax.random.normal(_name_key(key, name), shape, jnp.float32)
    if ternary:
        z = jnp.where(jnp.abs(z) > 0.6745, jnp.sign(z), 0.0) * 1.2
    return (z * init).astype(dtype)


def layer_weights(key, m: dict, layer) -> dict:
    """One layer's float weights (``layer`` may be traced); its projection
    matrices are ternary images."""
    key = jax.random.fold_in(key, layer)
    dt = jnp.dtype(m["dtype"])
    return {n: _make(key, n, s, i, dt, ternary=True)
            for n, (s, i) in layer_leaves(m).items()}


def global_weights(key, m: dict) -> dict:
    dt = jnp.dtype(m["dtype"])
    return {n: _make(key, n, s, i, dt)
            for n, (s, i) in global_leaves(m).items()}


def nest(flat: dict) -> dict:
    """{"a.b.c": x} -> {"a": {"b": {"c": x}}}."""
    out: dict = {}
    for name, leaf in flat.items():
        node = out
        *head, last = name.split(".")
        for part in head:
            node = node.setdefault(part, {})
        node[last] = leaf
    return out
