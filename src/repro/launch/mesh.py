"""Production mesh construction.

Defined as FUNCTIONS (not module-level constants) so importing this module
never touches jax device state — the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before first init;
tests and benches see the real single device.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def auto_mesh(shape, axes):
    """``jax.make_mesh`` with every axis ``Auto``: the serving and training
    steps place data with ``NamedSharding``/``shard_map`` and leave the
    partitioning of everything else to the compiler.  (``make_mesh`` builds
    ``Explicit`` axes by default, under which ``vmap``, ``dynamic_slice`` and
    ``scan`` over sharded operands are refused.)"""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 v5e pod (256 chips) or 2x16x16 (512 chips, 2 pods).

    Axes: 'data' carries DP/FSDP + sequence-parallel long-context KV;
    'model' carries TP/EP; 'pod' (multi-pod) carries pure DP — gradient
    all-reduce on the inter-pod DCI link, everything else intra-pod ICI
    (DESIGN.md §5)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def make_mesh(n_data: int, n_model: int, n_pod: int = 1):
    """Arbitrary mesh for elastic restarts / smaller slices."""
    if n_pod > 1:
        return auto_mesh((n_pod, n_data, n_model), ("pod", "data", "model"))
    return auto_mesh((n_data, n_model), ("data", "model"))


def data_axes(mesh) -> tuple:
    """Axes that shard the batch (pod joins data when present)."""
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def parse_mesh(spec):
    """``--mesh dp,mp`` -> a ('data', 'model') Mesh (e.g. "2,4"; "1,1" is a
    single-device mesh, the sharded batcher's exactness baseline).  ``None``
    or empty returns None (single-device, unsharded serving path)."""
    if spec in (None, "", "none"):
        return None
    try:
        dp, mp = (int(v) for v in str(spec).split(","))
    except ValueError:
        raise ValueError(
            f"--mesh expects 'dp,mp' (e.g. '2,4'), got {spec!r}") from None
    if dp < 1 or mp < 1:
        raise ValueError(f"--mesh axes must be >= 1, got {spec!r}")
    have = len(jax.devices())
    if dp * mp > have:
        raise ValueError(
            f"--mesh {spec} needs {dp * mp} devices but only {have} are "
            "visible (set XLA_FLAGS=--xla_force_host_platform_device_count=N "
            "for a virtual CPU mesh)")
    return make_mesh(dp, mp)
