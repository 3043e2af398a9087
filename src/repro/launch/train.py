"""Training launcher — mesh + sharded step + checkpoint + fault tolerance.

    PYTHONPATH=src python -m repro.launch.train --arch smollm-135m \
        --steps 100 --batch 8 --seq 128 --precision 2xT --reduced

``--reduced`` swaps in the smoke-scale config so the loop runs on CPU; the
full configs train the same way on a real pod (the dry-run proves they
lower/compile on the production mesh).  The loop is the ElasticTrainer:
preemption-safe, checkpointed, straggler-monitored.
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.checkpoint import Checkpointer
from repro.configs import ARCH_IDS, get_config
from repro.data import SyntheticLM
from repro.launch.mesh import make_mesh
from repro.launch.steps import make_train_step
from repro.models import build_model, reduce_for_smoke
from repro.optim import make_optimizer
from repro.parallel.sharding import batch_specs, param_specs
from repro.runtime import ElasticTrainer, StragglerMonitor


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, default="smollm-135m")
    ap.add_argument("--precision", default="fp32")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--optimizer", default="adamw",
                    choices=["adamw", "adafactor", "adam8bit"])
    ap.add_argument("--accum-steps", type=int, default=1)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--save-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args(argv)

    cfg = get_config(args.arch, precision=args.precision)
    if args.reduced:
        cfg = reduce_for_smoke(cfg)
    model = build_model(cfg)
    opt = make_optimizer(args.optimizer, lr=args.lr)

    n_dev = len(jax.devices())
    mesh = make_mesh(n_dev, 1)

    def build(n_data, n_model):
        params = model.init(jax.random.PRNGKey(0))
        opt_state = opt.init(params)
        pspecs = param_specs(params, cfg, mesh)
        step = make_train_step(model, opt, accum_steps=args.accum_steps)
        psh = jax.tree_util.tree_map(lambda s: NamedSharding(mesh, s), pspecs,
                                     is_leaf=lambda x: isinstance(x, P))
        jitted = jax.jit(step, donate_argnums=(0, 1))

        def step_fn(state, batch):
            batch = {k: jnp.asarray(v) for k, v in batch.items()}
            p, o, metrics = jitted(state["params"], state["opt"], batch)
            return {"params": p, "opt": o}, {k: float(v)
                                             for k, v in metrics.items()}

        state = {"params": jax.device_put(params, psh), "opt": opt_state}
        return mesh, state, None, step_fn

    data = SyntheticLM(vocab=cfg.vocab, seq_len=args.seq,
                       global_batch=args.batch)
    ckpt = Checkpointer(args.ckpt_dir)
    monitor = StragglerMonitor()
    trainer = ElasticTrainer(ckpt, build, save_every=args.save_every)

    t0 = time.time()
    state, metrics, status = trainer.run(args.steps, n_dev, 1, data,
                                         monitor=monitor)
    wall = time.time() - t0
    losses = [m["loss"] for m in metrics]
    if losses:
        print(f"status={status} steps={len(losses)} wall={wall:.1f}s "
              f"first_loss={losses[0]:.4f} last_loss={losses[-1]:.4f} "
              f"stragglers={len(monitor.events)}")
    return losses


if __name__ == "__main__":
    main()
