"""Regression tests for the serving metrics accounting.

Two bugfix anchors:
  * the throughput wall-clock starts at the first ADMISSION, not the first
    submit — requests queued into an idle scheduler must not deflate tok/s
    (the legacy submit-anchored window is still reported for bench history);
  * ``_pcts`` uses the canonical nearest-rank percentile (inverted CDF),
    cross-checked against ``numpy.percentile(..., method="inverted_cdf")``.
"""
import time
import types

import numpy as np

from repro.runtime.metrics import Metrics, _pcts


def _req(submitted_at=0.0, started_at=0.0, prompt_len=4,
         last_token_at=None):
    return types.SimpleNamespace(
        submitted_at=submitted_at, started_at=started_at,
        last_token_at=last_token_at, finished_at=0.0,
        tokens=np.zeros((1, prompt_len), np.int32))


# ---------------------------------------------------------------------------
# percentile math
# ---------------------------------------------------------------------------
def test_pcts_matches_numpy_inverted_cdf():
    rng = np.random.default_rng(0)
    for n in (1, 2, 3, 4, 5, 10, 99, 100, 101, 200, 1000):
        xs = rng.normal(size=n).tolist()
        got = _pcts(xs)
        for p in (50, 90, 99):
            want = float(np.percentile(xs, p, method="inverted_cdf"))
            assert got[f"p{p}"] == want, (n, p, got[f"p{p}"], want)
        assert got["n"] == n
        assert abs(got["mean"] - float(np.mean(xs))) < 1e-12


def test_pcts_nearest_rank_regression_cases():
    # p50 of 4 samples: canonical rank ceil(0.5*4)=2 -> 2nd smallest.  The
    # old round(p/100*(n-1)) picked index 2 (the 3rd smallest).
    assert _pcts([1.0, 2.0, 3.0, 4.0])["p50"] == 2.0
    # p99 of 100 samples: rank ceil(99)=99 -> the 99th smallest
    xs = [float(i) for i in range(1, 101)]
    assert _pcts(xs)["p99"] == 99.0
    assert _pcts(xs)["p50"] == 50.0
    assert _pcts([7.0])["p99"] == 7.0
    empty = _pcts([])
    assert empty == {"p50": 0.0, "p90": 0.0, "p99": 0.0, "mean": 0.0, "n": 0}


# ---------------------------------------------------------------------------
# throughput window
# ---------------------------------------------------------------------------
def test_throughput_window_starts_at_first_admission():
    m = Metrics(n_slots=2)
    r = _req(submitted_at=time.perf_counter())
    m.on_submit(r)
    time.sleep(0.08)                       # pure queue-idle: no compute yet
    r.started_at = time.perf_counter()
    m.on_admit(r)
    for _ in range(4):
        now = time.perf_counter()
        m.on_token(r, first=(_ == 0), now=now)
        r.last_token_at = now
        time.sleep(0.002)
    r.finished_at = time.perf_counter()
    m.on_finish(r)

    s = m.summary()
    th = s["throughput"]
    # admission window excludes the idle wait; submit window includes it
    assert th["window"] == "admission"
    assert m.wall_since_submit_s >= m.wall_s + 0.05
    # tail symmetry: a submit into an idle scheduler (no compute after it)
    # must not extend either window's END
    wall_before = m.wall_s
    time.sleep(0.03)
    m.on_submit(_req(submitted_at=time.perf_counter()))
    assert m.wall_s == wall_before
    assert th["since_submit"]["wall_s"] == m.wall_since_submit_s
    assert th["tok_per_s"] > th["since_submit"]["tok_per_s"]
    assert abs(th["tok_per_s"] * max(m.wall_s, 1e-9)
               - s["tokens"]["generated"]) < 1e-6


# ---------------------------------------------------------------------------
# ITL guard: identity check, not truthiness
# ---------------------------------------------------------------------------
def test_itl_records_sample_when_last_token_at_is_epoch_zero():
    """Regression: ``elif req.last_token_at:`` silently dropped the ITL
    sample whenever the previous token's timestamp was exactly 0.0 (falsy
    float) — real under monkeypatched clocks.  The guard must be
    ``is not None``."""
    m = Metrics(n_slots=1)
    r = _req()
    m.on_submit(r)
    m.on_admit(r)
    m.on_token(r, first=True, now=time.perf_counter())
    r.last_token_at = 0.0                  # epoch-zero: a REAL timestamp
    m.on_token(r, first=False, now=time.perf_counter())
    assert len(m.itl_ms) == 1 and m.itl_ms[0] > 0.0


def test_itl_skips_sample_when_no_previous_token():
    m = Metrics(n_slots=1)
    r = _req()                             # last_token_at=None: no history
    m.on_submit(r)
    m.on_admit(r)
    m.on_token(r, first=False, now=time.perf_counter())  # defensive path
    assert m.itl_ms == []


# ---------------------------------------------------------------------------
# speculative acceptance rates
# ---------------------------------------------------------------------------
def test_draft_accept_rate_is_unit_consistent():
    """``draft_accept_rate`` = accepted / drafted (a true fraction);
    ``accept_rate`` keeps the legacy blended denominator (drafted tokens +
    verify dispatches) verbatim for bench-history continuity."""
    m = Metrics(n_slots=1)
    m.on_spec_round(drafted=3, accepted=3)   # perfect round
    m.on_spec_round(drafted=3, accepted=1)
    s = m.summary()["speculative"]
    assert s["draft_accept_rate"] == 4 / 6
    assert s["accept_rate"] == 4 / (6 + 2)   # legacy: mixes in verify steps
    assert s["accepted_per_verify"] == 2.0
    # a flawless run reads 1.0 on the new rate (the legacy one cannot)
    m2 = Metrics(n_slots=1)
    m2.on_spec_round(drafted=4, accepted=4)
    assert m2.summary()["speculative"]["draft_accept_rate"] == 1.0
    assert m2.summary()["speculative"]["accept_rate"] < 1.0


# ---------------------------------------------------------------------------
# paged-KV counters (prefix hit rate / cache utilization / evictions)
# ---------------------------------------------------------------------------
def test_kv_cache_counters_default_zero_and_absent_from_format():
    m = Metrics(n_slots=2)
    s = m.summary()["kv_cache"]
    assert s["prefix"] == {"lookups": 0, "hits": 0, "hit_tokens": 0,
                           "hit_rate": 0.0}
    assert s["blocks"]["total"] == 0 and s["blocks"]["utilization"] == 0.0
    assert s["evicted_blocks"] == 0
    assert "kv blocks" not in m.format()       # dense batcher: no noise


def test_kv_cache_prefix_and_eviction_accounting():
    m = Metrics(n_slots=2)
    r = _req(prompt_len=10)
    m.on_submit(r)
    m.on_admit(r)                              # prompt_tokens += 10
    m.on_prefix_lookup(8, 10)                  # 8 of 10 tokens from cache
    m.on_prefix_lookup(0, 6)                   # miss
    m.on_evictions(3)
    m.on_kv_blocks(5, 20)
    m.on_kv_blocks(12, 20)
    m.on_kv_blocks(4, 20)
    s = m.summary()["kv_cache"]
    assert s["prefix"]["lookups"] == 2 and s["prefix"]["hits"] == 1
    assert s["prefix"]["hit_tokens"] == 8
    assert s["prefix"]["hit_rate"] == 8 / 10   # over admitted prompt tokens
    assert s["blocks"] == {"total": 20, "in_use": 4, "peak_in_use": 12,
                           "utilization": 4 / 20,
                           "peak_utilization": 12 / 20}
    assert s["evicted_blocks"] == 3
    assert "kv blocks 4/20" in m.format()
    assert "prefix hit rate 0.80" in m.format()


def test_preemption_and_suffix_hit_accounting():
    """The dynamic-allocation counters: preemptions / recomputed_tokens /
    generated-suffix hits split from prompt-prefix hits — and the
    admitted-concurrency gauge that the overcommit bench reads."""
    m = Metrics(n_slots=2)
    a, b = _req(prompt_len=6), _req(prompt_len=4)
    m.on_submit(a)
    m.on_submit(b)
    m.on_admit(a)
    m.on_admit(b)
    assert m.requests_active == 2 and m.requests_active_peak == 2

    m.on_preempt(b)                            # b back to the queue
    assert m.preemptions == 1 and m.requests_active == 1
    # b's re-admission: prompt' = 4 prompt + 5 generated tokens; the radix
    # served 4 prompt-kind and 4 suffix-kind tokens, 0 were re-prefilled
    # redundantly beyond the match
    m.on_admit(b, n_prompt_tokens=9, resumed=True)
    m.on_prefix_lookup(4, 9, suffix_tokens=4)
    m.on_recompute(0)
    assert m.requests_active == 2
    # resumed admissions never re-sample the queue wait
    assert len(m.queue_ms) == 2

    m.on_finish(a)
    m.on_finish(b)
    m.on_kv_blocks(3, 20)                      # enables the kv format() line
    s = m.summary()
    assert s["scheduler"]["preemptions"] == 1
    assert s["scheduler"]["recomputed_tokens"] == 0
    assert s["scheduler"]["active_peak"] == 2
    kc = s["kv_cache"]
    assert kc["prefix"]["hit_tokens"] == 4
    assert kc["suffix"] == {"hits": 1, "hit_tokens": 4,
                            "hit_rate": 4 / (6 + 4 + 9)}
    # prompt_tokens counted the resumed admission too: rates stay rates
    assert 0.0 <= kc["prefix"]["hit_rate"] <= 1.0
    out = m.format()
    assert "preemptions 1" in out and "suffix hits 4 tok" in out


def test_preemption_absent_from_format_when_zero():
    m = Metrics(n_slots=1)
    r = _req()
    m.on_submit(r)
    m.on_admit(r)
    m.on_token(r, first=True, now=time.perf_counter())
    m.on_finish(r)
    assert "preemptions" not in m.format()      # dense batcher: no noise
    assert m.summary()["scheduler"]["preemptions"] == 0


def test_throughput_windows_coincide_under_immediate_admission():
    """No queueing: both windows agree (continuity for old bench numbers)."""
    m = Metrics(n_slots=1)
    r = _req(submitted_at=time.perf_counter())
    m.on_submit(r)
    r.started_at = time.perf_counter()
    m.on_admit(r)
    m.on_token(r, first=True, now=time.perf_counter())
    r.finished_at = time.perf_counter()
    m.on_finish(r)
    s = m.summary()["throughput"]
    assert abs(s["wall_s"] - s["since_submit"]["wall_s"]) < 0.05
    assert m.format()                      # renders without error
