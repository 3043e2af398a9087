"""Compile a configuration's programs for a described TPU v5e, without a
chip, and print what each needs of the chip's memory.

    JAX_PLATFORMS=cpu python3 bench/rehearse.py <config> [<config> ...]

For each configuration (``bench/configs/<config>.json``) it compiles the
weight construction (one slice of layers, the embedding and head) and the
serving programs the benchmark's window runs: the prefill chunk and the
decode step at every occupancy bucket, with the engine's Pallas kernels
(the engine picks them from the backend it sees, which here is made to
read "tpu").  Nothing runs; a program the TPU compiler refuses, or one that
does not fit, fails here instead of on the chip.
"""
from __future__ import annotations

import json
import os
import sys
from pathlib import Path
from unittest import mock

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


def _gb(n):
    return f"{n / 1e9:.3f} GB"


def report(name, compiled):
    ma = compiled.memory_analysis()
    print(f"  {name}: arguments {_gb(ma.argument_size_in_bytes)}, output "
          f"{_gb(ma.output_size_in_bytes)}, temporaries "
          f"{_gb(ma.temp_size_in_bytes)}, code "
          f"{_gb(ma.generated_code_size_in_bytes)}", flush=True)
    return ma


def rehearse(config: str, chip):
    import jax
    import jax.numpy as jnp
    from bench import build
    from bench import weights as W
    from repro.models import build_model, transformer as tfm
    from repro.runtime.serving import bucket_length

    cfg_json = json.loads((BENCH / "configs" / f"{config}.json").read_text())
    m, s = cfg_json["model"], cfg_json["serving"]
    mcfg = build.model_config(cfg_json)
    items = tuple(sorted(m.items()))
    n = cfg_json.get("weight_slice_layers", m["n_layers"])

    def spec(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip),
            tree)

    key = spec(jax.eval_shape(lambda: W.seed_key(0)))
    print(f"{config}: {m['n_layers']} layers, slices of {n}")
    sl = jax.jit(build._packed_slice, static_argnums=(1, 2, 3)).lower(
        key, mcfg, items, n, spec(jnp.int32(0))).compile()
    report(f"weights, {n} layers", sl)
    gl = jax.jit(build._packed_globals, static_argnums=(1, 2)).lower(
        key, mcfg, items).compile()
    report("weights, embedding and head", gl)

    params = spec(dict(
        jax.eval_shape(lambda: build._packed_globals(W.seed_key(0), mcfg,
                                                     items)),
        blocks=jax.eval_shape(lambda: build._packed_slice(
            W.seed_key(0), mcfg, items, m["n_layers"], jnp.int32(0)))))
    weight_bytes = sum(a.size * a.dtype.itemsize
                       for a in jax.tree_util.tree_leaves(params))
    print(f"  serving weights: {_gb(weight_bytes)}")

    bs, chunk, kv_bits = 16, min(32, s["s_max"]), s["kv_bits"]
    per_seq = bucket_length(s["s_max"], bs) // bs
    nb = 1 + (s["n_slots"] + 1) * per_seq
    pool = spec(jax.eval_shape(lambda: tfm.make_pool(mcfg, nb, bs, kv_bits)))
    pool_bytes = sum(a.size * a.dtype.itemsize
                     for a in jax.tree_util.tree_leaves(pool))
    print(f"  KV pool: {nb} blocks of {bs}, {_gb(pool_bytes)}")
    model = build_model(mcfg)
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32,  # noqa: E731
                                              sharding=chip)
    prefill = jax.jit(lambda p, t, pool, pt, pos: model.prefill_chunk_paged(
        p, t, pool, pt, pos, kv_bits), donate_argnums=(2,))
    report(f"prefill chunk ({chunk} tokens)",
           prefill.lower(params, i32(1, chunk), pool, i32(1, per_seq),
                         i32()).compile())
    b, n_slots = 1, s["n_slots"]
    while True:
        def decode(p, t, pool, pt, pos, sm):
            lg, new = model.decode_step_paged(p, t[sm], pool, pt[sm], pos[sm],
                                              kv_bits, fused=True)
            return lg, jnp.argmax(lg[:, 0], axis=-1), new
        report(f"decode, {b} live slots", jax.jit(
            decode, donate_argnums=(2,)).lower(
                params, i32(n_slots, 1), pool, i32(n_slots, per_seq),
                i32(n_slots), i32(b)).compile())
        if b >= n_slots:
            break
        b = min(2 * b, n_slots)


def main(argv=None) -> int:
    configs = (argv if argv is not None else sys.argv[1:]) or \
        [p.stem for p in sorted((BENCH / "configs").glob("*.json"))]
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ["REPRO_TUNING_CACHE"] = str(ROOT / ".bench_tuning.json")
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    # the engine picks Pallas kernels (not their interpret mode) from the
    # backend it sees
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        for c in configs:
            rehearse(c, chip)
    return 0


if __name__ == "__main__":
    sys.exit(main())
