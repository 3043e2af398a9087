"""How ``correct`` is decided: the tokens the timed path served to a sample
of requests, compared with the plain reference.

The sample is drawn from the seed before the window opens (always with the
longest request in it), from the requests due in the window (open loop) or
the clients' first requests (closed loop); every sampled request is
waited for after the window.  The reference then runs once over each
prompt with its served tokens (greedy decoding: a correct program serves
the reference's best token at every position, up to rounding) and gives:

- ``gap_max``: the widest gap by which a served token's reference logit
  lies below the reference's best logit at that position;
- ``gap_share_pct``: the share of served tokens with any gap (reported,
  not limited: rounding alone moves near-ties).
"""
from __future__ import annotations

import importlib

import jax.numpy as jnp
import numpy as np

from bench import weights as W

SAMPLE = 8                 # requests compared per run
ROW_BLOCK = 256            # logits rows per reference call


def choose(candidates, seed: int, n: int = SAMPLE):
    """Up to ``n`` requests drawn from the seed, always with the longest
    (prompt + output budget) among them."""
    if not candidates:
        return []
    longest = max(candidates, key=lambda r: (r.prompt_len + r.max_new, r.rid))
    rest = [r for r in candidates if r is not longest]
    rng = np.random.default_rng([int(seed), 7])
    pick = rng.choice(len(rest), size=min(n - 1, len(rest)), replace=False)
    return [longest] + [rest[i] for i in sorted(pick)]


def _rows(cfg_json: dict, reqs):
    """Token rows (SAMPLE, S) of prompt + served tokens (the last served
    token is never an input); for each served token its (row, position)
    and id."""
    s = -(-cfg_json["serving"]["s_max"] // 128) * 128
    toks = np.zeros((SAMPLE, s), np.int32)
    where, ids = [], []
    for i, r in enumerate(reqs):
        seq = np.concatenate([r.tokens, np.asarray(r.output[:-1], np.int32)])
        toks[i, :len(seq)] = seq
        for j, t in enumerate(r.output):
            where.append((i, r.prompt_len - 1 + j))
            ids.append(t)
    return (toks, np.asarray(where, np.int64).reshape(-1, 2),
            np.asarray(ids, np.int32))


def reference(cfg_json: dict, seed: int, reqs, ids=None,
              a_bits: int | None = None) -> dict:
    """One reference pass over ``reqs``: per served position the gap of its
    served token (or of ``ids``) below the reference's best, and the
    reference's argmax.  ``a_bits`` below the configuration's reads the
    control."""
    ref = importlib.import_module(f"bench.references.{cfg_json['reference']}")
    m = cfg_json["model"]
    items = tuple(sorted(m.items()))
    key = W.seed_key(seed)
    toks, where, served = _rows(cfg_json, reqs)
    ids = served if ids is None else np.asarray(ids, np.int32)
    bits = a_bits or ref.act_bits(m["precision"])
    h = ref.hidden(key, items, bits, jnp.asarray(toks))
    rows = h[where[:, 0], where[:, 1]]
    n = rows.shape[0]
    pad = -n % ROW_BLOCK
    rows = jnp.pad(rows, ((0, pad), (0, 0)))
    pids = jnp.pad(jnp.asarray(ids), (0, pad))
    best, at, top = [], [], []
    for a in range(0, n + pad, ROW_BLOCK):
        b, t, mx = ref.logits_at(key, items, rows[a:a + ROW_BLOCK],
                                 pids[a:a + ROW_BLOCK])
        best.append(np.asarray(b)), at.append(np.asarray(t))
        top.append(np.asarray(mx))
    return {"gap": (np.concatenate(best) - np.concatenate(at))[:n],
            "argmax": np.concatenate(top)[:n]}


def numbers(gap) -> dict:
    return {
        "compared_tokens": int(gap.size),
        "gap_share_pct": float(100.0 * np.mean(gap > 0)) if gap.size else
        float("nan"),
        "gap_max": float(gap.max()) if gap.size else float("inf"),
    }


def compared(cfg_json: dict, seed: int, reqs, due, picked):
    """The numbers ``correct`` compares, each with its value, and the
    served tokens' gaps."""
    done = [r for r in picked if r.done]
    out = {"unfinished": sum(1 for r in due if not r.done)
           + len(picked) - len(done),
           "wrong_length": sum(1 for r in reqs
                               if r.done and len(r.output) != r.max_new)}
    gap = reference(cfg_json, seed, done)["gap"] if done else np.zeros(0)
    out.update(numbers(gap))
    return out, gap
