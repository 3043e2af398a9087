"""Pipeline parallelism — scan-based GPipe over a mesh axis (opt-in).

For deeper multi-pod meshes the 'pod' axis can carry pipeline STAGES instead
of plain DP (DESIGN.md §5).  The period-scan transformer splits naturally:
stage s owns periods [s*P/S, (s+1)*P/S); parameters are stage-sharded along
the period axis, activations flow stage-to-stage via ``lax.ppermute`` inside
``jax.shard_map``, and microbatches are pumped through the classic GPipe
schedule (n_micro + n_stages - 1 ticks; bubble fraction (S-1)/(M+S-1)).

This module pipelines the BLOCK STACK (embedding and the LM head stay with
the caller — they are data-parallel).  Exact: the 2-stage pipeline equals the
sequential forward bit-for-bit in fp32 (tests/test_pipeline.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.models.config import ModelConfig
from repro.models.transformer import _apply_period


def _stage_params(blocks, n_stages: int):
    """Reshape period-stacked block params (P, ...) -> (S, P/S, ...)."""
    def reshape(x):
        p = x.shape[0]
        assert p % n_stages == 0, (p, n_stages)
        return x.reshape(n_stages, p // n_stages, *x.shape[1:])
    return jax.tree_util.tree_map(reshape, blocks)


def pipeline_blocks(blocks, x, cfg: ModelConfig, mesh, *, axis: str = "pod",
                    n_micro: int = None):
    """Run the block stack as a GPipe pipeline over ``axis``.

    blocks: period-stacked params (n_periods, ...); x: (B, S, D) activations
    (batch divisible by n_micro).  Returns y: (B, S, D).
    """
    n_stages = mesh.shape[axis]
    n_micro = n_micro or n_stages
    b = x.shape[0]
    assert b % n_micro == 0
    mb = b // n_micro
    staged = _stage_params(blocks, n_stages)
    positions = jnp.broadcast_to(
        jnp.arange(x.shape[1], dtype=jnp.int32)[None], (mb, x.shape[1]))

    # microbatch queue: (n_micro, mb, S, D)
    micro = x.reshape(n_micro, mb, *x.shape[1:])
    n_ticks = n_micro + n_stages - 1

    def stage_fn(stage_blocks, micro_in):
        """Runs on ONE stage (inside shard_map).  stage_blocks has leading
        (1, P/S, ...); micro_in is the full queue (replicated)."""
        sp = jax.tree_util.tree_map(lambda t: t[0], stage_blocks)
        stage_idx = jax.lax.axis_index(axis)

        def apply_stage(h):
            def body(h, pp):
                y, _, _ = _apply_period(pp, h, cfg, positions)
                return y, None
            h, _ = jax.lax.scan(body, h, sp)
            return h

        def tick(carry, t):
            h_prev = carry                       # activation leaving this stage
            # shift stage s -> s+1 (stage 0 receives garbage, replaced below)
            h_in = jax.lax.ppermute(
                h_prev, axis,
                [(i, i + 1) for i in range(n_stages - 1)])
            mb_idx = jnp.clip(t, 0, n_micro - 1)
            fresh = jax.lax.dynamic_index_in_dim(micro_in, mb_idx, 0,
                                                 keepdims=False)
            h_in = jnp.where(stage_idx == 0, fresh, h_in)
            active = (t - stage_idx >= 0) & (t - stage_idx < n_micro)
            h_out = jnp.where(active, apply_stage(h_in), h_in)
            # last stage emits its finished microbatch at ticks >= S-1
            return h_out, h_out

        _, outs = jax.lax.scan(tick, jnp.zeros((mb,) + x.shape[1:], x.dtype),
                               jnp.arange(n_ticks))
        # outs: (n_ticks, mb, S, D); only the last stage's outputs at ticks
        # [n_stages-1, n_ticks) are the real results — select them
        result = jax.lax.dynamic_slice_in_dim(outs, n_stages - 1, n_micro, 0)
        return result                             # (n_micro, mb, S, D)

    spec_blocks = jax.tree_util.tree_map(
        lambda _: P(axis), staged,
        is_leaf=lambda v: hasattr(v, "shape"))
    out = shard_map(
        stage_fn, mesh=mesh,
        in_specs=(spec_blocks, P()),
        out_specs=P(axis),                        # each stage returns a copy;
        check_vma=False,
    )(staged, micro)
    # out is (n_stages*n_micro, mb, S, D) stacked over stages; the LAST
    # stage's slice holds the real outputs
    out = out.reshape(n_stages, n_micro, mb, *x.shape[1:])[-1]
    return out.reshape(b, *x.shape[1:])


def bubble_fraction(n_stages: int, n_micro: int) -> float:
    """GPipe bubble overhead: (S-1)/(M+S-1)."""
    return (n_stages - 1) / (n_micro + n_stages - 1)
