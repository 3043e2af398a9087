"""SPMD integration: run REAL sharded train/decode steps on 8 virtual CPU
devices (subprocess — jax locks device count at first init, so the 8-device
world must be a fresh interpreter)."""
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs import get_config
from repro.models import build_model, make_batch, reduce_for_smoke, to_serving
from repro.models.config import ShapeConfig
from repro.models import transformer as tfm
from repro.optim import make_optimizer
from repro.parallel.sharding import batch_specs, cache_specs, param_specs
from repro.launch.steps import make_train_step

assert len(jax.devices()) == 8
from repro.launch.mesh import make_mesh
mesh = make_mesh(2, 4)
sh = lambda specs: jax.tree_util.tree_map(
    lambda s: NamedSharding(mesh, s), specs, is_leaf=lambda x: isinstance(x, P))

# --- sharded training: granite reduced (MoE + EP over 4-way model axis) ---
cfg = reduce_for_smoke(get_config("granite-moe-1b-a400m"))
model = build_model(cfg)
params = model.init(jax.random.PRNGKey(0))
opt = make_optimizer("adamw", lr=1e-3)
opt_state = opt.init(params)
batch = make_batch(cfg, ShapeConfig("t", 32, 4, "train"))
pspecs = param_specs(params, cfg, mesh)
ospecs = opt.state_specs(pspecs, params)
bspecs = batch_specs(batch, cfg, mesh)
step = jax.jit(make_train_step(model, opt, accum_steps=2),
               in_shardings=(sh(pspecs), sh(ospecs), sh(bspecs)),
               donate_argnums=(0, 1))
with mesh:
    p, o, m = step(jax.device_put(params, sh(pspecs)),
                   jax.device_put(opt_state, sh(ospecs)),
                   jax.device_put(batch, sh(bspecs)))
    l1 = float(m["loss"])
    for _ in range(3):
        p, o, m = step(p, o, jax.device_put(batch, sh(bspecs)))
assert np.isfinite(l1) and np.isfinite(float(m["loss"]))
assert float(m["loss"]) < l1  # same batch 4x -> loss must drop
print("TRAIN_OK", l1, float(m["loss"]))

# --- sharded quantized decode: glm4 reduced, 2xT + int8 KV ---
cfg = reduce_for_smoke(get_config("glm4-9b", precision="2xT", kv_bits=8))
model = build_model(cfg)
params = to_serving(model.init(jax.random.PRNGKey(0)), cfg, tp=4)
pspecs = param_specs(params, cfg, mesh)
prompt = make_batch(cfg, ShapeConfig("p", 8, 4, "prefill"))
with mesh:
    sparams = jax.device_put(params, sh(pspecs))
    logits, cache = jax.jit(lambda p, b: model.prefill(p, b, 16))(sparams, prompt)
    cspecs = cache_specs(cache, cfg, mesh, 4)
    cache = jax.device_put(cache, sh(cspecs))
    tok = jnp.argmax(logits[:, -1:], -1).astype(jnp.int32)
    dec = jax.jit(lambda p, t, c, i: model.decode_step(p, t, c, i))
    for i in range(3):
        logits, cache = dec(sparams, tok, cache, jnp.int32(8 + i))
assert np.all(np.isfinite(np.asarray(logits)))
print("DECODE_OK")
"""


@pytest.mark.slow
def test_spmd_train_and_decode_8dev():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "TRAIN_OK" in out.stdout and "DECODE_OK" in out.stdout
