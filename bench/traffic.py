"""The one traffic generator: a mix file of parameters -> requests.

A mix (``bench/traffic/<name>.json``) states the loop (``open`` with a
fixed ``rate_per_s``, or ``closed`` with a number of ``clients``), the
prompt and output length distributions, and how many leading prompt tokens
every request shares (``shared_prefix``).

Every seed gets the same set of sizes and arrival gaps — stratified
quantiles of their distributions — in an order the seed draws, and its own
tokens: the seed changes which request is which, not how much work there
is.  (Lengths drawn afresh by the seed moved a closed loop's tokens per
second by up to 9% from seed to seed on the chip.)
"""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path
from statistics import NormalDist

import numpy as np

HERE = Path(__file__).resolve().parent


@dataclasses.dataclass
class Req:
    rid: int
    tokens: np.ndarray               # (L,) int32 prompt
    max_new: int
    due: float = 0.0                 # open loop: seconds after window start
    submit_t: float | None = None
    token_t: list = dataclasses.field(default_factory=list)
    output: list = dataclasses.field(default_factory=list)
    done: bool = False
    handle: object = None            # the program's request object

    @property
    def prompt_len(self) -> int:
        return int(self.tokens.shape[0])


def load_mix(name: str) -> dict:
    return json.loads((HERE / "traffic" / f"{name}.json").read_text())


def quantiles(spec: dict, n: int) -> np.ndarray:
    """n stratified draws of a length distribution, ascending."""
    p = (np.arange(n) + 0.5) / n
    lo, hi = spec["min"], spec["max"]
    if spec["dist"] == "uniform":
        x = lo + np.floor(p * (hi - lo + 1))
    elif spec["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(q) for q in p])
        x = np.round(spec["median"] * np.exp(spec["sigma"] * z))
    else:
        raise ValueError(f"unknown distribution {spec['dist']!r}")
    return np.clip(x, lo, hi).astype(np.int64)


class Traffic:
    """Requests for one run, made on demand so a closed loop can take as
    many as its window needs.

    Open loop: the ``round(rate * span)`` requests due in the window, and
    those due after it (which keep the load on while the window's requests
    finish), each set with the stratified gaps of a Poisson stream scaled
    to span it, and one stratified set of lengths.  Closed loop: requests
    come in rounds of ``clients``, each round one stratified set of
    lengths.  The seed orders every set.
    """

    def __init__(self, mix: dict, vocab: int, seed: int):
        self.mix, self.vocab, self.seed = mix, vocab, int(seed)
        shared = int(mix.get("shared_prefix", 0))
        self.prefix = (np.random.default_rng([self.seed, 1]).integers(
            0, vocab, shared).astype(np.int32) if shared else
            np.zeros(0, np.int32))

    def _order(self, *tag: int):
        return np.random.default_rng([self.seed, 3, *tag]).permutation

    def _lengths(self, n: int, *tag: int):
        perm = self._order(*tag)
        return (perm(quantiles(self.mix["prompt"], n)),
                perm(quantiles(self.mix["output"], n)))

    def _make(self, rid: int, prompt_len: int, max_new: int,
              due: float = 0.0) -> Req:
        rng = np.random.default_rng([self.seed, 2, rid])
        own = rng.integers(0, self.vocab, int(prompt_len)).astype(np.int32)
        return Req(rid, np.concatenate([self.prefix, own]), int(max_new),
                   due)

    def closed(self, i: int) -> Req:
        """The i-th request of a closed loop."""
        n = int(self.mix["clients"])
        prompts, outs = self._lengths(n, 0, i // n)
        return self._make(i, prompts[i % n], outs[i % n])

    def open_schedule(self, window_s: float, after_s: float) -> list[Req]:
        """Requests due in the window, then those due up to ``after_s``
        past its close, in order of due time."""
        rate = float(self.mix["rate_per_s"])
        out = []
        for tag, (t0, span) in enumerate(((0.0, window_s),
                                          (window_s, after_s))):
            n = round(rate * span)
            if n == 0:
                continue
            prompts, outs = self._lengths(n, 1, tag)
            gaps = self._order(2, tag)(-np.log1p(-(np.arange(n) + 0.5) / n))
            due = t0 + span * np.concatenate([[0.0], np.cumsum(gaps)[:-1]]) \
                / gaps.sum()
            out += [self._make(len(out), prompts[j], outs[j], float(due[j]))
                    for j in range(n)]
        return out
