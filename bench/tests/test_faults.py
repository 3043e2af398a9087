"""A whole run at a test size on the CPU, with the timed path broken
underneath: ``correct`` must come out false for every fault a serving cell
can have.  (Every cell runs on one chip, so there is no exchange between
chips to leave out.)  The control — the reference computed one precision
below the configuration's, in the program's place — must fail too."""
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from bench import control, run

CFG = json.loads((Path(__file__).resolve().parents[1] / "configs"
                  / "smollm-135m-8xT.json").read_text())
CFG["model"].update(n_layers=2, d_model=128, n_heads=4, n_kv_heads=2,
                    head_dim=32, d_ff=256, vocab=512)
CFG["serving"].update(n_slots=4, s_max=128)
# at this size the sound program reads a gap of 0 on the CPU and the
# control 0.240-0.293 (three seeds): the limit lies between
CFG["limits"]["gap_max"] = 0.05
MIX = {"loop": "closed", "clients": 4,
       "prompt": {"dist": "uniform", "min": 20, "max": 60},
       "output": {"dist": "uniform", "min": 12, "max": 20},
       "shared_prefix": 24}
CELL = "smollm-135m-8xT.shared-doc"    # a closed-loop cell, run at test size


def _run(seed=2**33 + 11, keep=None):
    return run.run_cell(CELL, seed, 2.0, False, require_tpu=False,
                        config_override=CFG, mix_override=MIX,
                        spec_override=run.load_spec(CELL), keep=keep)


def test_sound_run_is_correct():
    res = _run()
    assert res["correct"], res["checked"]
    assert res["checked"]["compared_tokens"]["value"] > 50


def _unchanged_state(monkeypatch):
    from repro.runtime.kvcache.batcher import PagedBatcher
    orig = PagedBatcher._dispatch_decode

    def step_keeps_pool(self):
        pool = jax.tree_util.tree_map(jnp.copy, self.pool)
        out = orig(self)
        self.pool = pool          # the decode step's KV writes are lost
        return out
    monkeypatch.setattr(PagedBatcher, "_dispatch_decode", step_keeps_pool)


def _half_batch(monkeypatch):
    from repro.runtime.kvcache.batcher import PagedBatcher
    orig = PagedBatcher._dispatch_decode

    def half(self):
        sm = self._dev["slot_map"]
        n = sm.shape[0]
        if n > 1:                 # the second half recomputes the first row
            self._dev["slot_map"] = sm.at[n // 2:].set(sm[0])
        try:
            return orig(self)
        finally:
            self._dev["slot_map"] = sm
    monkeypatch.setattr(PagedBatcher, "_dispatch_decode", half)


def _altered_token(monkeypatch):
    from repro.runtime.kvcache.batcher import PagedBatcher
    orig = PagedBatcher._decode_call

    def altered(self, live):
        nxt = orig(self, live).copy()
        nxt[live[0]] = (nxt[live[0]] + 1) % 512
        return nxt
    monkeypatch.setattr(PagedBatcher, "_decode_call", altered)


@pytest.mark.parametrize("fault", [_unchanged_state, _half_batch,
                                   _altered_token])
def test_fault_is_not_correct(monkeypatch, fault):
    fault(monkeypatch)
    res = _run()
    assert res["correct"] is False, res["checked"]


def test_control_is_not_correct():
    keep = {}
    _run(keep=keep)
    got = control.control_numbers(keep, 2**33 + 11)
    limits = CFG["limits"]
    assert any(got[k] > limits[k] for k in got if k in limits), got
