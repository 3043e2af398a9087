"""shard_map MoE — explicit local dispatch, one psum as the only collective.

The pjit slot-map MoE (models.layers.moe_apply) lets the SPMD partitioner
choose the communication; §Perf shows it settles on (T,D)-scale gathers both
ways.  This module is the structural alternative identified in the kimi
iteration log: under ``jax.shard_map`` each (data i, model j) device

  1. already holds its token shard x_i (replicated over model) AND its
     expert shard E_j (replicated over data) — so DISPATCH IS LOCAL:
     device (i,j) fills slots for experts in E_j from tokens in x_i with
     per-group capacity (GShard-style: capacity budgeted per data shard);
  2. computes its experts on its slots — no communication;
  3. scatter-adds its partial (T_loc, D) output and ``psum``s over the
     model axis — the ONLY collective, ~D*T_loc bytes per layer.

Semantics: identical routing to moe_apply except capacity is per
(data-shard, expert) instead of global — the standard GShard grouping
(tokens compete for capacity within their shard).  Requires expert weights
replicated over 'data' (non-FSDP); the FSDP variant would add a partial-K
psum and is future work (EXPERIMENTS.md §Perf).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.models.config import ModelConfig
from repro.models.layers import _act, _expert_matmul, rmsnorm


def _local_moe(p, x, cfg: ModelConfig, *, data_axis: str, model_axis: str):
    """Per-device body (inside shard_map).  x: (B_loc, S, D) local tokens;
    p['w_gate'] etc: (E_loc, D, F) local experts."""
    b, s, d = x.shape
    t = b * s
    e = cfg.n_experts
    k = cfg.top_k
    n_model = jax.lax.axis_size(model_axis)
    e_loc = e // n_model
    j = jax.lax.axis_index(model_axis)
    cap = int(t * k / e * cfg.capacity_factor) or 1     # per-group capacity

    xin = rmsnorm(p["norm"], x, cfg.norm_eps).reshape(t, d)
    logits = jnp.dot(xin.astype(jnp.float32), p["w_router"])
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_i = jax.lax.top_k(probs, k)
    top_p = top_p / jnp.maximum(jnp.sum(top_p, -1, keepdims=True), 1e-9)

    flat_e = top_i.reshape(-1)                           # (T*k,) global ids
    onehot = jax.nn.one_hot(flat_e, e, dtype=jnp.int32)
    pos = jnp.take_along_axis(jnp.cumsum(onehot, axis=0) - 1,
                              flat_e[:, None], axis=1)[:, 0]
    # keep only slots routed to MY experts, under MY capacity
    local_e = flat_e - j * e_loc
    mine = (local_e >= 0) & (local_e < e_loc) & (pos < cap)
    tok = jnp.repeat(jnp.arange(t), k)

    # foreign/over-capacity slots -> OOB expert index, dropped by the scatter
    e_idx = jnp.where(mine, local_e, e_loc)
    tok_map = jnp.full((e_loc, cap), t, jnp.int32)
    tok_map = tok_map.at[e_idx, pos].set(tok, mode="drop")
    gate_map = jnp.zeros((e_loc, cap), jnp.float32)
    gate_map = gate_map.at[e_idx, pos].set(top_p.reshape(-1), mode="drop")

    x_pad = jnp.concatenate([xin, jnp.zeros((1, d), xin.dtype)], axis=0)
    buf = x_pad[tok_map]                                  # (E_loc, cap, D)

    h = _act(_expert_matmul(p["w_gate"], buf, cfg), cfg.act_fn) * \
        _expert_matmul(p["w_up"], buf, cfg)
    y = _expert_matmul(p["w_down"], h, cfg)               # (E_loc, cap, D)

    out_pad = jnp.zeros((t + 1, d), jnp.float32)
    out_pad = out_pad.at[tok_map.reshape(-1)].add(
        (y.astype(jnp.float32) * gate_map[..., None]).reshape(e_loc * cap, d))
    out = jax.lax.psum(out_pad[:t], model_axis)           # the ONLY collective

    # load-balance stats averaged over the data axis (global token means)
    me = jax.lax.pmean(jnp.mean(probs, axis=0), data_axis)
    ce = jax.lax.pmean(
        jnp.mean(jax.nn.one_hot(top_i[:, 0], e, dtype=jnp.float32), axis=0),
        data_axis)
    aux = e * jnp.sum(me * ce)
    return out.reshape(b, s, d).astype(x.dtype), aux


def moe_apply_shard_map(p, x, cfg: ModelConfig, mesh, *,
                        data_axis: str = "data", model_axis: str = "model"):
    """Drop-in for layers.moe_apply under an explicit mesh.

    p: MoE params with experts stacked (E, ...) (un-period-stacked — call
    inside the period loop); x: (B, S, D) global.
    """
    espec = P(model_axis)
    pspecs = {
        "norm": jax.tree_util.tree_map(lambda _: P(), p["norm"]),
        "w_router": P(),
        "w_gate": espec, "w_up": espec, "w_down": espec,
    }
    fn = functools.partial(_local_moe, cfg=cfg, data_axis=data_axis,
                           model_axis=model_axis)
    return shard_map(
        fn, mesh=mesh,
        in_specs=(pspecs, P(data_axis, None, None)),
        out_specs=(P(data_axis, None, None), P()),
        check_vma=False,
    )(p, x)
