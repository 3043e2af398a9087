"""The load drivers and the percentile arithmetic, with a fake server on a
fake clock."""
import math

import numpy as np
import pytest

from bench import drivers, run, stats
from bench.traffic import Req, Traffic, quantiles


class Clock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t

    def sleep(self, dt):
        self.t += dt


class FakeServer:
    """Admits one request per step; every admitted request emits one token
    per step; each step takes ``dt`` seconds of the fake clock."""

    def __init__(self, clock, dt=0.01, slots=4):
        self.clock, self.dt, self.slots = clock, dt, slots
        self.queue, self.live = [], []

    def submit(self, req, on_token):
        self.queue.append((req, on_token))

    def step(self):
        self.clock.t += self.dt
        if self.queue and len(self.live) < self.slots:
            self.live.append(self.queue.pop(0) + ([0],))
        for item in list(self.live):
            req, cb, n = item
            n[0] += 1
            done = n[0] >= req.max_new
            cb(None, 7, done)
            if done:
                self.live.remove(item)

    @property
    def idle(self):
        return not self.queue and not self.live


def _req(i, due, max_new=3):
    return Req(i, np.zeros(5, np.int32), max_new, due)


def test_open_loop_times_from_due_not_submit():
    clock = Clock()
    server = FakeServer(clock, dt=0.05, slots=1)
    # three requests due at once: the third waits for the first two
    reqs = [_req(i, 0.0) for i in range(3)]
    win = drivers.open_loop(server, reqs, 1.0, 5.0, clock=clock,
                            sleep=clock.sleep)
    ttft = [r.token_t[0] - r.due for r in reqs]
    assert ttft[0] == pytest.approx(0.05)
    # the last one waited for two 3-token requests: 6 steps, then its own
    assert ttft[2] == pytest.approx(0.35)
    assert all(r.submit_t == pytest.approx(win.t0) for r in reqs)


def test_open_loop_sleeps_until_due_and_drains():
    clock = Clock()
    server = FakeServer(clock, dt=0.01)
    reqs = [_req(0, 0.5), _req(1, 2.5)]
    win = drivers.open_loop(server, reqs, 1.0, 10.0, clock=clock,
                            sleep=clock.sleep)
    assert reqs[0].submit_t - win.t0 == pytest.approx(0.5)
    assert reqs[0].done
    # due after the window, and the window's requests were done: not sent
    assert reqs[1].submit_t is None


def test_unfinished_requests_count_as_missing():
    clock = Clock()
    server = FakeServer(clock, dt=0.5, slots=1)
    reqs = [_req(i, 0.0, max_new=4) for i in range(3)]
    win = drivers.open_loop(server, reqs, 1.0, 2.0, clock=clock,
                            sleep=clock.sleep)
    due = reqs
    assert sum(1 for r in due if not r.done) == 2
    e2e = run.end_to_end(reqs, due, win, 1.0, "open")
    assert math.isinf(e2e["ttft_p95_ms"])


def test_closed_loop_ramps_before_the_window():
    clock = Clock()
    server = FakeServer(clock, dt=0.01, slots=2)
    made = []

    def nxt():
        made.append(_req(len(made), 0.0, max_new=5))
        return made[-1]
    win = drivers.closed_loop(server, nxt, 2, 0.2, clock=clock)
    first = made[:2]
    assert all(r.token_t[0] <= win.t0 for r in first)
    # clients keep sending: more requests than clients were made
    assert len(made) > 2
    e2e = run.end_to_end(made, [], win, 1.0, "closed")
    assert e2e["output_tok_s"] == pytest.approx(2 / 0.01, rel=0.06)


def test_percentile_is_nearest_rank():
    xs = list(range(1, 101))
    assert stats.percentile(xs, 95) == 95
    assert stats.percentile(xs, 99) == 99
    assert stats.percentile([5.0, 1.0, 3.0, 2.0], 50) == 2.0
    assert stats.percentile([1.0] * 19 + [math.inf], 95) == 1.0
    assert math.isinf(stats.percentile([1.0] * 18 + [math.inf] * 2, 95))
    assert stats.spread([1.0, 2.0, 3.0, 4.0, 5.0]) == pytest.approx(
        (4.5 - 1.5) / 3.0)


def test_every_seed_gets_the_same_work_in_its_own_order():
    mix = {"loop": "open", "rate_per_s": 5.0,
           "prompt": {"dist": "lognormal", "median": 256, "sigma": 0.8,
                      "min": 16, "max": 1536},
           "output": {"dist": "uniform", "min": 8, "max": 256}}
    a = Traffic(mix, 1000, 2**33 + 1).open_schedule(20.0, 5.0)
    again = Traffic(mix, 1000, 2**33 + 1).open_schedule(20.0, 5.0)
    b = Traffic(mix, 1000, 1).open_schedule(20.0, 5.0)
    assert [(r.due, r.prompt_len, r.max_new) for r in a] == \
        [(r.due, r.prompt_len, r.max_new) for r in again]
    assert len(a) == len(b) == 125
    win_a = [r for r in a if r.due < 20.0]
    win_b = [r for r in b if r.due < 20.0]
    assert sorted(r.prompt_len for r in win_a) == \
        sorted(r.prompt_len for r in win_b)
    assert [r.prompt_len for r in win_a] != [r.prompt_len for r in win_b]
    # the same Poisson gaps in another order
    assert len(win_a) == len(win_b) == 100
    assert [r.due for r in win_a] != [r.due for r in win_b]
    assert not np.array_equal(win_a[0].tokens, win_b[0].tokens)
    assert len(set(r.prompt_len for r in win_a)) > 50
    assert quantiles(mix["output"], 4).tolist() == [39, 101, 163, 225]
    closed = dict(mix, loop="closed", clients=8)
    ca, cb = Traffic(closed, 1000, 7), Traffic(closed, 1000, 2**31 + 5)
    assert sorted(ca.closed(i).max_new for i in range(8, 16)) == \
        sorted(cb.closed(i).max_new for i in range(8, 16))
