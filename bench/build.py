"""Build the system under test for one configuration: the program's model,
its serving-form weights made on the device from the seed, and the paged
batcher with the deployment's settings.

The float weights come from :mod:`bench.weights`; the program packs them
with its own ``to_serving``.  Layers are made in slices of
``weight_slice_layers`` by one compiled program called once per slice and
written into the joined stack in place, so a model whose float form does
not fit the chip still gets its packed form.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bench import weights as W


def model_config(cfg_json: dict):
    from repro.models.config import ModelConfig
    return ModelConfig(**cfg_json["model"])


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _packed_slice(key, mcfg, m_items, n, l0):
    from repro.models import to_serving
    m = dict(m_items)
    layers = jax.vmap(lambda l: W.layer_weights(key, m, l))(
        l0 + jnp.arange(n, dtype=jnp.int32))
    tree = W.nest(layers)
    return to_serving({"blocks": tree}, mcfg, tp=1)["blocks"]


@functools.partial(jax.jit, static_argnums=(1, 2))
def _packed_globals(key, mcfg, m_items):
    from repro.models import to_serving
    return to_serving(W.nest(W.global_weights(key, dict(m_items))), mcfg,
                      tp=1)


@functools.partial(jax.jit, donate_argnums=(0,))
def _write_slice(stack, part, l0):
    return jax.tree_util.tree_map(
        lambda s, p: jax.lax.dynamic_update_slice_in_dim(s, p, l0, 0),
        stack, part)


def serving_params(cfg_json: dict, seed: int):
    """The program's serving-form parameter tree, made from the seed."""
    m = cfg_json["model"]
    mcfg = model_config(cfg_json)
    items = tuple(sorted(m.items()))
    key = W.seed_key(seed)
    n_layers = m["n_layers"]
    n = min(cfg_json.get("weight_slice_layers", n_layers), n_layers)
    if n_layers % n:
        raise ValueError(f"{n_layers} layers do not split into slices of {n}")
    if n == n_layers:
        blocks = _packed_slice(key, mcfg, items, n, jnp.int32(0))
    else:
        shapes = jax.eval_shape(
            lambda: _packed_slice(key, mcfg, items, n_layers, jnp.int32(0)))
        blocks = jax.jit(lambda: jax.tree_util.tree_map(
            lambda s: jnp.zeros(s.shape, s.dtype), shapes))()
        for l0 in range(0, n_layers, n):
            part = _packed_slice(key, mcfg, items, n, jnp.int32(l0))
            blocks = _write_slice(blocks, part, jnp.int32(l0))
            del part
    params = dict(_packed_globals(key, mcfg, items))
    params["blocks"] = blocks
    return params


def batcher(cfg_json: dict, params):
    """The program's paged batcher with the deployment's settings; what the
    program derives itself (chunk size, block size, pool size) is left to
    it."""
    from repro.models import build_model
    from repro.runtime.kvcache import PagedBatcher
    from repro.runtime.serving import ServingConfig
    s = cfg_json["serving"]
    model = build_model(model_config(cfg_json))
    return PagedBatcher(model, params, ServingConfig(
        n_slots=s["n_slots"], s_max=s["s_max"], kv_bits=s["kv_bits"],
        prefix_cache=s["prefix_cache"]))
