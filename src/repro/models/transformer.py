"""Period-scan decoder LM — covers 9 of the 10 assigned architectures.

The layer stack is ``n_periods`` repetitions of ``cfg.layer_pattern`` /
``cfg.ffn_pattern``; parameters are stacked along a leading period axis and
the stack is applied with ``lax.scan`` (HLO size O(period), compile time
O(period) — essential for the 61/80-layer archs).

Three entry points (all pure functions over (params, inputs)):
  forward(params, tokens_or_embeds)            -> logits            (train)
  prefill(params, tokens, s_max)               -> logits, cache     (serving)
  decode_step(params, token, cache, pos)       -> logits, cache     (serving)

Cache pytree = {"kv": stacked KV (attn layers), "ssm": stacked SSM states
(mamba layers)} — stacked over periods, scanned in lock-step with params.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .config import ModelConfig
from . import layers as L


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------
def _init_period(key, cfg: ModelConfig):
    """One period's params: dict layer_i -> {mixer, ffn} by pattern."""
    p = {}
    keys = jax.random.split(key, cfg.period * 2)
    post = cfg.name.startswith("gemma2")
    for i, (mixer, ffn) in enumerate(zip(cfg.layer_pattern, cfg.ffn_pattern)):
        lp = {}
        if mixer.startswith("attn"):
            lp["attn"] = L.attn_init(keys[2 * i], cfg, post_norms=post)
        elif mixer == "mamba":
            lp["mamba"] = L.mamba_init(keys[2 * i], cfg)
        else:
            raise ValueError(mixer)
        if ffn == "dense":
            lp["ffn"] = L.ffn_init(keys[2 * i + 1], cfg,
                                   gated=cfg.ffn_gated, post_norms=post)
        elif ffn == "moe":
            lp["moe"] = L.moe_init(keys[2 * i + 1], cfg)
        elif ffn != "none":
            raise ValueError(ffn)
        p[f"layer_{i}"] = lp
    return p


def init_params(key, cfg: ModelConfig):
    k_embed, k_blocks, k_head = jax.random.split(key, 3)
    dt = L.pdtype(cfg)
    v, d = cfg.padded_vocab, cfg.d_model
    params = {
        "embed": {"w": (jax.random.normal(k_embed, (v, d), jnp.float32) * 0.02).astype(dt)},
        "blocks": jax.vmap(lambda k: _init_period(k, cfg))(
            jax.random.split(k_blocks, cfg.n_periods)),
        "final_norm": L.rmsnorm_init(d),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = {"qw": (jax.random.normal(k_head, (d, v), jnp.float32)
                                    * d ** -0.5).astype(dt)}
    return params


# ---------------------------------------------------------------------------
# period application
# ---------------------------------------------------------------------------
def _apply_period(pp, x, cfg: ModelConfig, positions, *, caches=None,
                  cache_pos=None, collect_state: bool = False):
    """Apply one period.  caches: {"kv": per-attn-layer dict list, "ssm": ...}
    stacked per *period-position* (dict keyed layer_i).  Returns
    (x, new_caches, aux_loss)."""
    new_caches = {} if caches is not None or collect_state else None
    aux_total = jnp.zeros((), jnp.float32)
    for i, (mixer, ffn) in enumerate(zip(cfg.layer_pattern, cfg.ffn_pattern)):
        lp = pp[f"layer_{i}"]
        if mixer.startswith("attn"):
            cache_i = caches[f"layer_{i}"] if caches is not None else None
            out, new_kv = L.attn_apply(
                lp["attn"], x, cfg, positions, local=(mixer == "attn_local"),
                cache=cache_i, cache_pos=cache_pos)
            x = x + out
            if new_caches is not None:
                new_caches[f"layer_{i}"] = new_kv if cache_i is not None else None
        elif mixer == "mamba":
            state_i = caches[f"layer_{i}"] if caches is not None else None
            out, new_state = L.mamba_apply(lp["mamba"], x, cfg, state=state_i)
            x = x + out
            if new_caches is not None:
                new_caches[f"layer_{i}"] = new_state
        if ffn == "dense":
            x = x + L.ffn_apply(lp["ffn"], x, cfg)
        elif ffn == "moe":
            out, aux = L.moe_apply(lp["moe"], x, cfg)
            x = x + out
            aux_total = aux_total + aux
    return x, new_caches, aux_total


def _embed(params, inputs, cfg: ModelConfig):
    if cfg.frontend == "embeds":
        x = inputs.astype(L.pdtype(cfg))      # stub frontend supplies embeddings
    else:
        x = params["embed"]["w"][inputs]
    if cfg.name.startswith("gemma2"):
        x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
    return x


def _logits(params, x, cfg: ModelConfig):
    xn = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    if cfg.tie_embeddings:
        logits = jnp.dot(xn, params["embed"]["w"].T.astype(xn.dtype))
    elif cfg.quantize_lm_head:
        logits = L.qlinear_apply(params["lm_head"], xn, cfg)
    else:
        # paper/WRPN convention: the classifier stays at full precision
        logits = jnp.dot(xn, params["lm_head"]["qw"].astype(xn.dtype)) \
            if "qw" in params["lm_head"] else \
            L.qlinear_apply(params["lm_head"], xn, cfg)
    logits = logits.astype(jnp.float32)
    if cfg.final_softcap > 0:
        logits = cfg.final_softcap * jnp.tanh(logits / cfg.final_softcap)
    return logits


# ---------------------------------------------------------------------------
# entry points
# ---------------------------------------------------------------------------
def forward(params, inputs, cfg: ModelConfig, remat: bool = True):
    """Training forward: logits (B, S, V) + aux losses."""
    b = inputs.shape[0]
    s = inputs.shape[1]
    x = _embed(params, inputs, cfg)
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))

    def body(x, pp):
        y, _, aux = _apply_period(pp, x, cfg, positions)
        return y, aux

    if remat:
        body = jax.checkpoint(body)
    x, auxes = jax.lax.scan(body, x, params["blocks"])
    return _logits(params, x, cfg), jnp.sum(auxes)


def make_cache(cfg: ModelConfig, b: int, s_max: int, mesh=None):
    """Stacked per-period cache pytree (periods as leading axis).

    With ``mesh``, leaves are created directly under the serving cache
    shardings (batch over the data axes, KV heads over 'model' when they
    divide) — no replicated host allocation followed by a reshard.  The
    sequence-parallel fallback is disabled: serving appends KV at dynamic
    positions, so the sequence dim must stay local to one shard."""
    per = {}
    for i, mixer in enumerate(cfg.layer_pattern):
        if mixer.startswith("attn"):
            per[f"layer_{i}"] = L.make_kv_cache(cfg, b, s_max, stacked=cfg.n_periods)
        elif mixer == "mamba":
            per[f"layer_{i}"] = L.make_ssm_state(cfg, b, stacked=cfg.n_periods)
    if mesh is not None:
        from repro.parallel.sharding import cache_specs, named_shardings
        per = jax.device_put(per, named_shardings(
            mesh, cache_specs(per, cfg, mesh, b, allow_sp=False)))
    return per


def make_pool(cfg: ModelConfig, num_blocks: int, block_size: int,
              kv_bits: int, mesh=None):
    """Stacked per-period block-pool pytree for the paged KV cache
    (runtime.kvcache): every attention layer gets ``num_blocks`` physical
    blocks of ``block_size`` positions (block 0 reserved as null), laid out
    as ``repro.kernels.kv_pool`` defines.  Requires an attention-only stack —
    SSM state has no sequence dim to page.

    The leaves are made on the device in the pinned row-major layout the
    paged kernels read (:func:`pool_formats`), so no program relays them.
    With ``mesh``, they are placed under ``parallel.sharding.pool_specs``:
    KV heads shard over 'model' when they divide; the block and in-block
    position dims always stay local to a shard (appends are scatters at
    dynamic positions — the shard-local append rule)."""
    assert all(m.startswith("attn") for m in cfg.layer_pattern), \
        f"{cfg.name}: paged KV cache needs an attention-only stack"
    make = lambda: {f"layer_{i}": L.make_kv_pool(cfg, num_blocks, block_size,
                                                 kv_bits,
                                                 stacked=cfg.n_periods)
                    for i in range(cfg.period)}
    return jax.jit(make, out_shardings=pool_formats(
        jax.eval_shape(make), cfg, mesh))()


def pool_formats(pool, cfg: ModelConfig, mesh=None):
    """The pool's pinned layout and sharding, leaf by leaf: what
    :func:`make_pool` places it in, and what every program that takes the
    pool declares for its argument and result.  ``pool`` may be shapes."""
    from repro.kernels import kv_pool
    if mesh is None:
        from jax.sharding import SingleDeviceSharding
        return kv_pool.formats(pool, SingleDeviceSharding(jax.devices()[0]))
    from repro.parallel.sharding import named_shardings, pool_specs
    return kv_pool.formats(pool, named_shardings(mesh,
                                                 pool_specs(pool, cfg, mesh)))


def _paged_scan(params, x, cfg: ModelConfig, positions, pool, page_table,
                kv_bits: int, slot_map=None, fused: bool = False):
    """The layer loop over a paged pool.  The pool rides in the carry, whole:
    each layer writes its new rows into the stacked leaves in place and the
    kernels read its blocks through the layer index, so no layer of the pool
    is sliced out or written back."""
    def body(carry, scanned):
        x, pool = carry
        pp, layer = scanned
        pool = dict(pool)
        for i, (mixer, ffn) in enumerate(zip(cfg.layer_pattern, cfg.ffn_pattern)):
            lp = pp[f"layer_{i}"]
            out, pool[f"layer_{i}"] = L.attn_apply_paged(
                lp["attn"], x, cfg, positions, local=(mixer == "attn_local"),
                pool=pool[f"layer_{i}"], page_table=page_table,
                kv_bits=kv_bits, layer=layer, slot_map=slot_map, fused=fused)
            x = x + out
            if ffn == "dense":
                x = x + L.ffn_apply(lp["ffn"], x, cfg)
            elif ffn == "moe":
                out, _ = L.moe_apply(lp["moe"], x, cfg)
                x = x + out
        return (x, pool), None

    layers = jnp.arange(cfg.n_periods, dtype=jnp.int32)
    (x, pool), _ = jax.lax.scan(body, (x, pool), (params["blocks"], layers))
    return x, pool


def prefill_chunk_paged(params, tokens, pool, page_table, pos,
                        cfg: ModelConfig, kv_bits: int):
    """Paged counterpart of :func:`prefill_chunk`: the chunk's KV is written
    into the pool blocks named by ``page_table`` (B=1 row) at positions
    [pos, pos + C), and queries attend through the page table.  Unlike the
    dense path, ``pos`` may start past 0 — admission skips the portion of
    the prompt covered by a radix prefix-cache hit.  Returns (logits, pool)."""
    b, c = tokens.shape[0], tokens.shape[1]
    x = _embed(params, tokens, cfg)
    pos = jnp.asarray(pos, jnp.int32).reshape(())
    positions = jnp.broadcast_to(pos + jnp.arange(c, dtype=jnp.int32)[None],
                                 (b, c))
    x, new_pool = _paged_scan(params, x, cfg, positions, pool, page_table,
                              kv_bits)
    return _logits(params, x, cfg), new_pool


def decode_step_paged(params, token, pool, page_table, pos,
                      cfg: ModelConfig, kv_bits: int, slot_map=None,
                      fused: bool = True):
    """Paged counterpart of :func:`decode_step`: per-slot page tables
    (B, n_blocks) resolve each slot's blocks; the new token's KV row lands in
    the slot's current block (retired slots' zeroed rows deflect to the null
    block).

    ``fused=True`` (default) runs each layer's attention + wo projection as
    one fused engine dispatch over ``slot_map`` (live slots only; None = the
    full padded batch); ``fused=False`` keeps the legacy two-dispatch layer.
    Returns (logits, pool)."""
    b = token.shape[0]
    x = _embed(params, token, cfg)
    positions = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))[:, None]
    x, new_pool = _paged_scan(params, x, cfg, positions, pool, page_table,
                              kv_bits, slot_map=slot_map, fused=fused)
    return _logits(params, x, cfg), new_pool


def decode_window_paged(params, tokens, pool, page_table, pos,
                        cfg: ModelConfig, kv_bits: int):
    """Batched multi-token decode window with PER-SLOT start positions —
    the verify step of self-speculative decoding (runtime.kvcache).

    tokens: (B, W) — each slot's last accepted token followed by its W-1
    draft tokens; pos: (B,) per-slot window starts.  Row j of slot i runs at
    position ``pos[i] + j``: its KV is (re)written into the slot's blocks —
    overwriting the draft model's approximate KV at the same positions
    *before* any query in the window attends them (the paged write path
    appends, then attends, per layer) — and its logits are the exact
    full-precision next-token distribution given the window prefix.  The
    scheduler accepts the longest draft prefix matching these logits'
    greedy tokens, which makes speculative streams bit-identical to the
    sequential fp-greedy stream.

    Unlike :func:`prefill_chunk_paged` (scalar start, B=1 admission) the
    position grid differs per batch row; the paged attention path handles
    the general (B, W) grid natively.  Returns (logits (B, W, V), pool)."""
    b, w = tokens.shape[0], tokens.shape[1]
    x = _embed(params, tokens, cfg)
    positions = (jnp.asarray(pos, jnp.int32).reshape(b, 1)
                 + jnp.arange(w, dtype=jnp.int32)[None, :])
    x, new_pool = _paged_scan(params, x, cfg, positions, pool, page_table,
                              kv_bits)
    return _logits(params, x, cfg), new_pool


def prefill(params, inputs, cfg: ModelConfig, s_max: int):
    """Process a prompt, build the cache, return last-position logits."""
    b, s = inputs.shape[0], inputs.shape[1]
    x = _embed(params, inputs, cfg)
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
    cache = make_cache(cfg, b, s_max)
    x, cache = _prefill_scan(params, x, cfg, positions, cache, s_max)
    return _logits(params, x[:, -1:, :], cfg), cache


def _prefill_scan(params, x, cfg, positions, cache, s_max):
    def body(x, scanned):
        pp, cache_p = scanned
        new_cache_p = {}
        for i, (mixer, ffn) in enumerate(zip(cfg.layer_pattern, cfg.ffn_pattern)):
            lp = pp[f"layer_{i}"]
            key = f"layer_{i}"
            if mixer.startswith("attn"):
                out, kv = L.attn_apply(lp["attn"], x, cfg, positions,
                                       local=(mixer == "attn_local"),
                                       return_kv=True)
                x = x + out
                k, v = kv
                pad = s_max - k.shape[1]
                if cfg.kv_bits:
                    kq, ks, vq, vs = L._kv_quantize(k, v, cfg.kv_bits)
                    new_cache_p[key] = {
                        "k": jnp.pad(kq, ((0, 0), (0, pad), (0, 0), (0, 0))),
                        "v": jnp.pad(vq, ((0, 0), (0, pad), (0, 0), (0, 0))),
                        "ks": jnp.pad(ks, ((0, 0), (0, pad), (0, 0), (0, 0)),
                                      constant_values=1e-6),
                        "vs": jnp.pad(vs, ((0, 0), (0, pad), (0, 0), (0, 0)),
                                      constant_values=1e-6),
                    }
                else:
                    new_cache_p[key] = {
                        "k": jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0))),
                        "v": jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0))),
                    }
            elif mixer == "mamba":
                out, st = L.mamba_apply(lp["mamba"], x, cfg, state=None)
                x = x + out
                new_cache_p[key] = st
            if ffn == "dense":
                x = x + L.ffn_apply(lp["ffn"], x, cfg)
            elif ffn == "moe":
                out, _ = L.moe_apply(lp["moe"], x, cfg)
                x = x + out
        return x, new_cache_p

    x, new_cache = jax.lax.scan(body, x, (params["blocks"], cache))
    return x, new_cache


def prefill_chunk(params, tokens, cache, pos, cfg: ModelConfig):
    """Process one prompt chunk against an existing cache (chunked prefill).

    tokens: (B, C) int32 (or (B, C, D) embeds); cache: a ``make_cache``
    pytree; pos: SCALAR int32 start position — the chunk's KV is appended at
    cache positions [pos, pos + C) and its queries attend causally over the
    cache, so long prompts can be admitted C tokens at a time, interleaved
    with decode steps for already-running requests (bounded TTFT impact).

    For attention-only stacks a prompt processed in aligned chunks produces
    logits bit-identical to :func:`prefill` of the whole prompt (same fp32
    softmax; appended cache rows beyond the mask contribute exact zeros).
    SSM layers thread their conv/ssm state through chunks exactly as long as
    no padding tokens are interleaved (the serving scheduler therefore only
    chunk-admits attention-only models).

    Returns (logits (B, C, V), new cache).
    """
    b, c = tokens.shape[0], tokens.shape[1]
    x = _embed(params, tokens, cfg)
    pos = jnp.asarray(pos, jnp.int32).reshape(())
    positions = jnp.broadcast_to(pos + jnp.arange(c, dtype=jnp.int32)[None],
                                 (b, c))

    def body(x, scanned):
        pp, cache_p = scanned
        x, new_cache_p, _ = _apply_period(pp, x, cfg, positions,
                                          caches=cache_p, cache_pos=pos)
        return x, new_cache_p

    x, new_cache = jax.lax.scan(body, x, (params["blocks"], cache))
    return _logits(params, x, cfg), new_cache


def decode_step(params, token, cache, pos, cfg: ModelConfig):
    """One decoding step.  token: (B, 1) int32 (or (B,1,D) embeds);
    pos: scalar int32 OR (B,) per-slot positions (continuous batching).
    Returns (logits (B,1,V), new cache)."""
    b = token.shape[0]
    x = _embed(params, token, cfg)
    positions = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))[:, None]

    def body(x, scanned):
        pp, cache_p = scanned
        x, new_cache_p, _ = _apply_period(pp, x, cfg, positions,
                                          caches=cache_p, cache_pos=pos)
        return x, new_cache_p

    x, new_cache = jax.lax.scan(body, x, (params["blocks"], cache))
    return _logits(params, x, cfg), new_cache
