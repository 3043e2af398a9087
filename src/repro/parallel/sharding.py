"""Sharding rules: param/activation/cache PartitionSpecs over the production mesh.

Policy (DESIGN.md §5):
  * TP over 'model': attention heads, FFN hidden, MoE experts, mamba d_inner,
    vocab — each sharded ONLY when divisible by the axis size (smollm's 9
    heads, whisper's 8 heads fall back to replicated attention).
  * DP over 'data' (+ 'pod' outer): batch; FSDP option shards the K dim of
    expert weights over 'data' (required for kimi-k2 training).
  * SP: when the batch doesn't cover the data axes (long_500k B=1) the KV
    cache / SSM state shards its SEQUENCE dim over 'data' instead — softmax
    over a sharded KV length lowers to partial-max/sum collectives.

Rules are name-based over the param pytree (works for both train-form "qw"
and serving-form "wt_packed"/"scale" leaves).
"""
from __future__ import annotations

from typing import Any

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.models.config import ModelConfig

# names whose OUTPUT (N) dim is model-sharded
_N_SHARDED = ("wq", "wk", "wv", "w_gate", "w_up", "w_in", "w_dt", "lm_head")
# names whose K (contraction) dim is model-sharded
_K_SHARDED = ("wo", "w_down", "w_out", "w_x")
# mamba per-channel (d_inner) vectors/tensors
_DI_SHARDED = ("conv_w", "conv_b", "dt_bias", "A_log", "D")


def _axis(mesh: Mesh, name: str) -> int:
    return mesh.shape[name]


def _div(dim: int, n: int) -> bool:
    return n > 0 and dim % n == 0


def _model_if(dim: int, mesh) -> Any:
    return "model" if _div(dim, _axis(mesh, "model")) else None


def pure_dp(cfg: ModelConfig, mesh: Mesh) -> bool:
    """Small models don't amortize TP: replicate params, shard batch over
    every axis (smollm d=576, whisper d=512 — DESIGN.md §5).
    ``force_pure_dp`` opts a config in explicitly (granite decode, §Perf)."""
    return cfg.force_pure_dp or cfg.d_model < 1024


def _dx(cfg: ModelConfig, mesh: Mesh):
    """Axes available for batch sharding."""
    base = ("pod", "data") if "pod" in mesh.axis_names else ("data",)
    if pure_dp(cfg, mesh):
        return base + ("model",)
    return base


def _batch_axes(cfg, mesh, b: int):
    """Largest prefix-product of data axes that divides the batch."""
    dx = _dx(cfg, mesh)
    # try full set, then drop trailing axes
    for cut in range(len(dx), 0, -1):
        axes = dx[:cut]
        total = 1
        for a in axes:
            total *= _axis(mesh, a)
        if _div(b, total):
            return axes
    return None


def param_specs(params, cfg: ModelConfig, mesh: Mesh, fsdp: bool = False):
    """Pytree of PartitionSpec matching ``params`` (shapes or arrays)."""
    tp = _axis(mesh, "model")
    dp = _axis(mesh, "data")
    if pure_dp(cfg, mesh):
        return jax.tree_util.tree_map(
            lambda leaf: P(*(None,) * len(leaf.shape)), params)
    heads_ok = _div(cfg.n_heads, tp) if cfg.n_heads else False
    kv_ok = _div(cfg.n_kv_heads, tp) if cfg.n_kv_heads else False

    def leaf_spec(path, leaf):
        keys = [k.key for k in path if hasattr(k, "key")]
        shape = leaf.shape
        rank = len(shape)
        name = next((k for k in reversed(keys)
                     if k not in ("qw", "wt_packed", "scale", "w", "g", "b")), "")
        leafname = keys[-1] if keys else ""
        in_expert = "moe" in keys and name in ("w_gate", "w_up", "w_down")

        # ---- embeddings ----
        if keys[-2:] == ["embed", "w"]:
            return P(_model_if(shape[0], mesh), None)
        if "lm_head" in keys:
            if leafname == "qw":
                return P(None, _model_if(shape[-1], mesh))
            if leafname == "wt_packed":   # (V, KW) — vocab sharded
                return P(_model_if(shape[0], mesh), None)
            if leafname == "scale":
                return P(_model_if(shape[0], mesh))
            return P(*(None,) * rank)

        # ---- MoE experts: (..., E, K, N) / packed (..., E, N, KW) ----
        if in_expert:
            e_axis = rank - 3 if leafname != "scale" else rank - 2
            spec = [None] * rank
            if _div(cfg.n_experts, tp):
                spec[e_axis] = "model"
            if fsdp and leafname == name and _div(shape[-2], dp):
                spec[-2] = "data"       # FSDP: K dim over data (kimi training)
            return P(*spec)
        if "w_router" in keys:
            return P(*(None,) * rank)

        # ---- attention / ffn / mamba projections ----
        is_attn = name in ("wq", "wk", "wv", "wo")
        if is_attn:
            ok = heads_ok if name in ("wq", "wo") else kv_ok
            if not ok:
                return P(*(None,) * rank)
        if name in _N_SHARDED:
            if leafname in ("qw",) or leafname == name:       # (..., K, N)
                return P(*(None,) * (rank - 1), _model_if(shape[-1], mesh))
            if leafname == "wt_packed":                        # (..., N, KW)
                return P(*(None,) * (rank - 2), _model_if(shape[-2], mesh), None)
            if leafname == "scale":                            # (..., N)
                return P(*(None,) * (rank - 1), _model_if(shape[-1], mesh))
        if name in _K_SHARDED:
            if leafname in ("qw",) or leafname == name:       # (..., K, N)
                return P(*(None,) * (rank - 2), _model_if(shape[-2], mesh), None)
            if leafname == "wt_packed":                        # (..., N, KW)
                return P(*(None,) * (rank - 1), _model_if(shape[-1], mesh))
            if leafname == "scale":
                return P(*(None,) * rank)
        if name in _DI_SHARDED or leafname in _DI_SHARDED:
            # last dim = d_inner for conv_w; first-nonperiod dim otherwise
            spec = [None] * rank
            for ax in range(rank - 1, -1, -1):
                if _div(shape[ax], tp) and shape[ax] % cfg.d_inner == 0:
                    spec[ax] = "model"
                    break
            return P(*spec)
        # norms, biases, scalars
        return P(*(None,) * rank)

    return jax.tree_util.tree_map_with_path(leaf_spec, params)


def batch_specs(batch, cfg: ModelConfig, mesh: Mesh):
    """Input batch specs: batch dim over the largest dividing data-axis set."""
    def spec(path, leaf):
        axes = _batch_axes(cfg, mesh, leaf.shape[0])
        return P(axes, *(None,) * (len(leaf.shape) - 1))

    return jax.tree_util.tree_map_with_path(spec, batch)


def cache_specs(cache, cfg: ModelConfig, mesh: Mesh, batch: int,
                kv_seq_shard: bool = False, allow_sp: bool = True):
    """KV/SSM cache specs.  Batch over data axes when divisible; otherwise
    sequence-parallel: shard the cache length (long_500k, B=1).

    ``kv_seq_shard``: when the KV heads don't divide the model axis (glm4
    kv=2, starcoder2 kv=4, ... vs tp=16) the baseline replicates the cache
    16x.  This option shards the cache SEQUENCE over the otherwise-idle
    'model' axis instead — attention over a sharded KV length lowers to
    partial-softmax reductions (EXPERIMENTS.md §Perf glm4 iteration).

    ``allow_sp=False`` disables the sequence-parallel fallback entirely: the
    continuous batcher appends KV rows at dynamic positions
    (dynamic_update_slice over the sequence dim), which must stay local to
    one shard — its admission cache (batch=1) replicates instead."""
    tp = _axis(mesh, "model")
    baxes = _batch_axes(cfg, mesh, batch)
    # SP fallback axes for the sequence dim (never includes 'model' when the
    # model axis carries TP)
    sp_axes = _dx(cfg, mesh) if allow_sp else ()
    kv_ok = (not pure_dp(cfg, mesh)) and \
        (_div(cfg.n_kv_heads, tp) if cfg.n_kv_heads else False)

    def spec(path, leaf):
        keys = [k.key for k in path if hasattr(k, "key")]
        shape = leaf.shape
        rank = len(shape)
        leafname = keys[-1] if keys else ""
        if leafname in ("k", "v", "ks", "vs", "cross_k", "cross_v"):
            # (P?, B, S, KV, Dh) — periods lead when stacked
            lead = rank - 4
            bspec = baxes
            sspec = None
            if baxes is None:
                # sequence-parallel long-context decode
                sspec = tuple(a for a in sp_axes
                              if _div(shape[lead + 1], _axis(mesh, a)))
                sspec = sspec or None
            kvspec = "model" if kv_ok and _div(shape[lead + 2], tp) else None
            if kvspec is None and kv_seq_shard and not pure_dp(cfg, mesh) \
                    and _div(shape[lead + 1], tp) and sspec is None:
                sspec = "model"
            return P(*(None,) * lead, bspec, sspec, kvspec, None)
        if leafname == "conv":                                 # (P?, B, K-1, Di)
            lead = rank - 3
            return P(*(None,) * lead, baxes, None,
                     None if pure_dp(cfg, mesh) else _model_if(shape[-1], mesh))
        if leafname == "ssm":                                  # (P?, B, Di, N)
            lead = rank - 3
            return P(*(None,) * lead, baxes,
                     None if pure_dp(cfg, mesh) else _model_if(shape[-2], mesh),
                     None)
        return P(*(None,) * rank)

    return jax.tree_util.tree_map_with_path(spec, cache)


def pool_specs(pool, cfg: ModelConfig, mesh: Mesh):
    """Paged KV block-pool specs (runtime.kvcache), for the layout
    ``repro.kernels.kv_pool`` defines: leaves (P?, NB, rows, lanes) whose
    lanes hold whole KV heads one after another (keys beside values, scales
    alike), so the lane dim shards over 'model' in whole heads when the KV
    heads divide and TP applies.  The block (NB) and in-block position (bs)
    dims ALWAYS stay local to a shard.  Appends scatter KV rows at
    dynamically computed (block, offset) coordinates, so — like the dense
    serving cache's sequence dim (``allow_sp=False``) — the paged dims must
    never be partitioned; sharding the pool over data requires per-shard
    pools and page tables (open item)."""
    from repro.kernels import kv_pool
    tp = _axis(mesh, "model")
    kv_ok = (not pure_dp(cfg, mesh)) and \
        (_div(cfg.n_kv_heads, tp) if cfg.n_kv_heads else False)

    def spec(path, leaf):
        keys = [k.key for k in path if hasattr(k, "key")]
        spec = [None] * len(leaf.shape)
        if kv_ok and keys and keys[-1] in (kv_pool.CODES, kv_pool.SCALES):
            spec[kv_pool.LANE_DIM] = "model"
        return P(*spec)

    return jax.tree_util.tree_map_with_path(spec, pool)


def act_scale_specs(cfg: ModelConfig, mesh: Mesh, batch: int):
    """Spec for fine-grained activation-scale tensors of shape (B, G) /
    (B*T, G): the scale rows partition over the SAME data axes as the
    activations they dequantize (kernels/act_quant grouped variants,
    engine._prep_activations).  Per-row act scales are batch-shaped but not
    batch-coupled, so they shard row-wise alongside their tensor instead of
    forcing a replicated per-tensor scalar — the representation that lets
    quantized-act step functions run under shard_map."""
    return P(_batch_axes(cfg, mesh, batch), None)


def logits_spec(cfg: ModelConfig, mesh: Mesh, batch: int):
    vspec = None if pure_dp(cfg, mesh) else _model_if(cfg.padded_vocab, mesh)
    return P(_batch_axes(cfg, mesh, batch), None, vspec)


def named_shardings(mesh: Mesh, specs):
    """PartitionSpec pytree -> NamedSharding pytree (jit in/out_shardings,
    device_put targets)."""
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), specs,
        is_leaf=lambda x: isinstance(x, P))


def serving_shard_factors(cfg: ModelConfig, mesh: Mesh, n_slots: int):
    """(dp, tp) the continuous batcher actually achieves on ``mesh``:

    ``dp`` — how many ways the ``n_slots`` decode batch is sharded (product
    of the dividing batch axes; for pure-DP models that includes the 'model'
    axis).  ``tp`` — the model-axis size when TP applies (1 for pure-DP
    models, whose params replicate).  The engine's serving pre-tune uses
    these to key the tuning cache on PER-DEVICE shapes: local decode rows
    M = n_slots/dp and local layer dims N or K divided by tp."""
    baxes = _batch_axes(cfg, mesh, n_slots)
    dp = 1
    for a in (baxes or ()):
        dp *= _axis(mesh, a)
    tp = 1 if pure_dp(cfg, mesh) else _axis(mesh, "model")
    return dp, tp
