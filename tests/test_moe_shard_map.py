"""shard_map MoE == pjit slot-map MoE (8 virtual devices, subprocess)."""
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import dataclasses
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get_config
from repro.models import reduce_for_smoke
from repro.models import layers as L
from repro.parallel.moe_shard_map import moe_apply_shard_map

cfg = reduce_for_smoke(get_config("granite-moe-1b-a400m"))
cfg = dataclasses.replace(cfg, n_experts=8, top_k=2, capacity_factor=64.0,
                          dtype="float32")   # high cap -> no drops either way
from repro.launch.mesh import make_mesh
mesh = make_mesh(2, 4)

key = jax.random.PRNGKey(0)
p = L.moe_init(key, cfg)
x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, cfg.d_model), jnp.float32)

want, aux_want = L.moe_apply(p, x, cfg)                       # pjit slot-map

with mesh:
    got, aux_got = jax.jit(
        lambda p_, x_: moe_apply_shard_map(p_, x_, cfg, mesh))(p, x)

np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                           rtol=5e-4, atol=5e-4)
np.testing.assert_allclose(float(aux_got), float(aux_want), rtol=1e-4)
print("SHARDMAP_MOE_OK")

# and with drops: per-group capacity drops a SUBSET of what global capacity
# drops — both must stay finite and close in norm
cfg2 = dataclasses.replace(cfg, capacity_factor=1.0)
want2, _ = L.moe_apply(p, x, cfg2)
with mesh:
    got2, _ = jax.jit(lambda p_, x_: moe_apply_shard_map(p_, x_, cfg2, mesh))(p, x)
assert np.all(np.isfinite(np.asarray(got2)))
rel = np.linalg.norm(np.asarray(got2) - np.asarray(want2)) / \
    np.linalg.norm(np.asarray(want2))
assert rel < 0.5, rel
print("SHARDMAP_MOE_CAP_OK", rel)
"""


@pytest.mark.slow
def test_shard_map_moe_matches_pjit_8dev():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "SHARDMAP_MOE_OK" in out.stdout
    assert "SHARDMAP_MOE_CAP_OK" in out.stdout
