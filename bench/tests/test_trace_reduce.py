"""The trace reduction, on a hand-made trace whose numbers are worked out
below, and on a trace recorded on a TPU v5e."""
from pathlib import Path

import jax
import pytest

from bench import trace_reduce

DATA = Path(__file__).resolve().parents[1] / "testdata"


def test_synthetic_trace():
    pd = jax.profiler.ProfileData.from_text_proto(
        (DATA / "synthetic.xplane.pbtxt").read_text())
    r = trace_reduce.reduce_planes(pd.planes)
    # window: bench.trace_window, 1000..11000 ns
    assert r.window_s == pytest.approx(10e-6)
    # ops at 1000..2000 and 3000..4000 ns
    assert r.busy_s == pytest.approx(2e-6)
    assert r.n_chips == 1
    assert r.module_time(r"_decode_fn") == (pytest.approx(4e-6), 1)
    assert r.op_time(r"ternary") == (pytest.approx(1e-6), 1)
    # idle 2000..3000 lies in bench.step; 4000..11000 in no span
    assert r.idle_by_span == {"bench.step": pytest.approx(1e-6),
                              "bench.none": pytest.approx(7e-6)}
    b = r.breakdown()
    assert b["idle_gaps"][0] == ["bench.none", pytest.approx(7e-6)]
    assert len(b["device_ops"]) == 2


def test_trace_without_window_is_refused():
    pd = jax.profiler.ProfileData.from_text_proto(
        (DATA / "synthetic.xplane.pbtxt").read_text().replace(
            "bench.trace_window", "other"))
    with pytest.raises(ValueError):
        trace_reduce.reduce_planes(pd.planes)


def test_recorded_trace():
    """A 1.1 s slice of glm4-9b-2xT.decode-heavy on a TPU v5e (64 live
    slots): eight decode steps and one prefill chunk."""
    r = trace_reduce.reduce_file(DATA / "glm4-decode.xplane.pb")
    assert r.n_chips == 1
    assert 0 < r.busy_s <= r.window_s
    secs, n = r.module_time(r"_decode_fn")
    assert n == 8 and 0.5 < secs < r.window_s
    assert r.module_time(r"lambda")[1] == 1
    assert r.op_time(r"^ternary_matmul$")[1] > 0
    assert r.op_time(r"^paged_attention$")[1] > 0
    # loops are not operations of their own
    assert "while" not in r.ops
    # every idle gap lies in a benchmark span or none
    assert all(k.startswith("bench.") for k in r.idle_by_span)
    assert sum(r.idle_by_span.values()) == pytest.approx(
        r.window_s - r.busy_s, rel=1e-6)
    b = r.breakdown()
    assert b["device_ops"][0][0] == "paged_attention"
    assert len(b["device_ops"]) == 10
