"""GPipe pipeline == sequential forward, exactly (subprocess, 8 devices)."""
import os
import subprocess
import sys

import pytest

from repro.parallel.pipeline import bubble_fraction

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get_config
from repro.models import build_model, reduce_for_smoke
from repro.models.transformer import _apply_period
from repro.parallel.pipeline import pipeline_blocks

cfg = reduce_for_smoke(get_config("glm4-9b"))
import dataclasses
cfg = dataclasses.replace(cfg, n_layers=4, dtype="float32")  # 4 periods -> 2/stage
model = build_model(cfg)
params = model.init(jax.random.PRNGKey(0))

b, s, d = 8, 16, cfg.d_model
x = jax.random.normal(jax.random.PRNGKey(1), (b, s, d), jnp.float32)

# sequential reference over the period stack
positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (2, s))
def seq_blocks(blocks, x):
    def body(h, pp):
        y, _, _ = _apply_period(pp, h, cfg, positions[:1].repeat(x.shape[0], 0))
        return y, None
    h, _ = jax.lax.scan(body, x, blocks)
    return h
want = seq_blocks(params["blocks"], x)

from repro.launch.mesh import auto_mesh
mesh = auto_mesh((2, 4), ("pod", "data"))
got = jax.jit(lambda bl, xx: pipeline_blocks(bl, xx, cfg, mesh, axis="pod",
                                             n_micro=4))(params["blocks"], x)
np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                           rtol=1e-5, atol=1e-5)
print("PIPELINE_OK")
"""


@pytest.mark.slow
def test_gpipe_matches_sequential_8dev():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "PIPELINE_OK" in out.stdout


def test_bubble_fraction():
    assert bubble_fraction(1, 8) == 0
    assert bubble_fraction(4, 4) == pytest.approx(3 / 7)
    assert bubble_fraction(2, 16) == pytest.approx(1 / 17)
