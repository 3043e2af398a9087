"""Serving flight-recorder tests: ring semantics, Perfetto export schema,
snapshot/delta stream, profiler sanity, crash dumps, the ``repro.*`` spans
on a ``jax.profiler`` capture — and the load-bearing invariant that
tracing OBSERVES the scheduler without perturbing it (greedy token streams
bit-identical with the recorder on vs off).
"""
import dataclasses
import json

import numpy as np
import pytest

import jax

from repro.configs import get_config
from repro.models import build_model, reduce_for_smoke
from repro.runtime.kvcache import PagedBatcher
from repro.runtime.metrics import Metrics
from repro.runtime.profile import StepProfiler
from repro.runtime.serving import (ContinuousBatcher, Request,
                                   RequestOptions, ServingConfig)
from repro.runtime.tracing import (NULL_TRACER, MetricsSnapshotter,
                                   TraceConfig, Tracer, _numeric_delta)

_STATE = {}


def _setup():
    if "cfg" not in _STATE:
        cfg = dataclasses.replace(reduce_for_smoke(get_config("smollm-135m")),
                                  dtype="float32")
        _STATE["cfg"] = cfg
        _STATE["params"] = build_model(cfg).init(jax.random.PRNGKey(0))
        _STATE["model"] = build_model(cfg)
    return _STATE["cfg"], _STATE["model"], _STATE["params"]


def _requests(cfg, n=4, max_new=6, seed=3):
    rng = np.random.default_rng(seed)
    return [Request(rid=i,
                    tokens=rng.integers(0, cfg.vocab,
                                        (1, int(rng.integers(4, 10)))
                                        ).astype(np.int32),
                    options=RequestOptions(max_new=max_new))
            for i in range(n)]


def _validate_perfetto(doc):
    """Chrome-trace consistency: per-track B/E stacks balance (every B has
    an E, no E without a B), flow t/f edges only for ids that started, X
    events carry ts+dur."""
    stacks = {}
    flow_started = set()
    for e in doc["traceEvents"]:
        ph = e["ph"]
        if ph == "M":
            continue
        assert isinstance(e["ts"], float) and e["pid"] == 1
        if ph == "B":
            stacks.setdefault(e["tid"], []).append(e["name"])
        elif ph == "E":
            st = stacks.get(e["tid"])
            assert st, f"E without B: {e}"
            st.pop()
        elif ph == "X":
            assert e["dur"] >= 0.0
        elif ph == "s":
            flow_started.add(e["id"])
        elif ph in ("t", "f"):
            assert e["id"] in flow_started, f"flow edge before start: {e}"
            if ph == "f":
                assert e["bp"] == "e"
        elif ph == "i":
            assert e["s"] == "t"
    for tid, st in stacks.items():
        assert st == [], f"unclosed spans on tid {tid}: {st}"


# ---------------------------------------------------------------------------
# ring buffer
# ---------------------------------------------------------------------------
def test_ring_drops_oldest_and_counts():
    tr = Tracer(capacity=16)
    for i in range(40):
        tr.instant(f"e{i}", "test")
    assert len(tr.events) == 16
    assert tr.dropped == 24
    names = [e["name"] for e in tr.events]
    assert names == [f"e{i}" for i in range(24, 40)]   # oldest gone
    assert tr.to_perfetto()["otherData"]["dropped_events"] == 24


def test_capacity_floor():
    assert Tracer(capacity=1).capacity == 16


def test_disabled_tracer_records_nothing():
    tr = Tracer(enabled=False)
    tr.begin("a", "t")
    tr.end("a", "t")
    tr.instant("b", "t")
    tr.counter("c", "t", v=1)
    tr.complete("d", "t", 0.0, 1.0)
    tr.flow("s", 0)
    tr.maybe_tuning_counter()
    assert list(tr.events) == [] and tr.dropped == 0
    assert list(NULL_TRACER.events) == []              # shared singleton


def test_from_config_dispatch():
    assert Tracer.from_config(None) is NULL_TRACER
    existing = Tracer()
    assert Tracer.from_config(existing) is existing    # lane sharing
    t = Tracer.from_config(TraceConfig(enabled=True, buffer=64))
    assert t.enabled and t.capacity == 64
    t.detach_engine()                                  # don't leak the hook
    off = Tracer.from_config(TraceConfig(enabled=False))
    assert not off.enabled


# ---------------------------------------------------------------------------
# export sanitization
# ---------------------------------------------------------------------------
def test_orphan_end_pruned_after_overflow():
    tr = Tracer(capacity=16)
    tr.begin("span", "t")                  # its B will fall off the ring
    for i in range(20):
        tr.instant(f"e{i}", "test")
    tr.end("span", "t")                    # orphan E
    doc = tr.to_perfetto()
    _validate_perfetto(doc)
    assert not any(e["ph"] == "E" for e in doc["traceEvents"])


def test_unclosed_begin_gets_synthetic_close():
    tr = Tracer(capacity=64)
    tr.begin("outer", "t")
    tr.begin("inner", "t")
    tr.instant("mark", "test")
    doc = tr.to_perfetto()
    _validate_perfetto(doc)
    closes = [e for e in doc["traceEvents"]
              if e["ph"] == "E" and e["args"].get("synthetic_close")]
    assert [e["name"] for e in closes] == ["inner", "outer"]  # LIFO order


def test_orphan_flow_edges_pruned():
    tr = Tracer(capacity=16)
    tr.flow("s", 7)                        # will fall off the ring
    for i in range(20):
        tr.instant(f"e{i}", "test")
    tr.flow("t", 7)                        # start dropped -> pruned
    tr.flow("s", 9)
    tr.flow("f", 9)                        # intact chain survives
    doc = tr.to_perfetto()
    _validate_perfetto(doc)
    ids = [(e["ph"], e["id"]) for e in doc["traceEvents"]
           if e.get("cat") == "flow"]
    assert ids == [("s", 9), ("f", 9)]


def test_dump_jsonl_header_and_tail(tmp_path):
    tr = Tracer(capacity=64)
    for i in range(10):
        tr.instant(f"e{i}", "test")
    p = tmp_path / "dump.jsonl"
    assert tr.dump_jsonl(str(p), last=4) == 4
    lines = [json.loads(x) for x in p.read_text().splitlines()]
    assert lines[0]["flight_recorder"] is True
    assert [x["name"] for x in lines[1:]] == ["e6", "e7", "e8", "e9"]


# ---------------------------------------------------------------------------
# spans
# ---------------------------------------------------------------------------
def _untimed(events):
    return [{k: v for k, v in e.items() if k != "ts"} for e in events]


def test_null_tracer_span_records_nothing():
    with NULL_TRACER.span("step", "scheduler", queue_depth=3):
        with NULL_TRACER.span("decode", "scheduler"):
            pass
    assert list(NULL_TRACER.events) == [] and NULL_TRACER.dropped == 0


def test_span_leaves_the_begin_end_pair():
    old, new = Tracer(capacity=64), Tracer(capacity=64)
    old.begin("prefill_chunk", "scheduler", track="lane", rid=4, pos=8)
    old.end("prefill_chunk", "scheduler", track="lane")
    with new.span("prefill_chunk", "scheduler", track="lane", rid=4, pos=8):
        pass
    assert _untimed(new.events) == _untimed(old.events)
    assert [e["ph"] for e in new.events] == ["B", "E"]
    assert new.events[0]["args"] == {"rid": 4, "pos": 8}


def test_span_closes_on_an_exception():
    class Boom(RuntimeError):
        pass

    tr = Tracer(capacity=64)
    with pytest.raises(Boom):
        with tr.span("step", "scheduler"):
            with tr.span("decode", "scheduler"):
                raise Boom
    assert [(e["ph"], e["name"]) for e in tr.events] == [
        ("B", "step"), ("B", "decode"), ("E", "decode"), ("E", "step")]
    _validate_perfetto(tr.to_perfetto())


# ---------------------------------------------------------------------------
# metrics snapshotter
# ---------------------------------------------------------------------------
def test_numeric_delta():
    prev = {"a": 1, "b": {"c": 2.0, "s": "x"}, "gone": 5}
    cur = {"a": 4, "b": {"c": 2.5, "s": "y", "new": 3}, "flag": True}
    d = _numeric_delta(prev, cur)
    assert d == {"a": 3, "b": {"c": 0.5, "new": 3}}    # strings/bools dropped
    assert _numeric_delta(None, {"a": 2}) == {"a": 2}  # first snapshot: vs 0


def test_snapshotter_interval_and_final(tmp_path):
    p = tmp_path / "snaps.jsonl"
    snap = MetricsSnapshotter(str(p), interval=3)
    m = Metrics(n_slots=2)
    for _ in range(7):
        m.decode_steps += 1
        snap.tick(m)
    assert snap.lines_written == 2                     # steps 3 and 6
    snap.final(m)
    lines = [json.loads(x) for x in p.read_text().splitlines()]
    assert len(lines) == 3
    assert all("summary" in x and "t_wall" in x for x in lines)
    # deltas are per-interval: 3 + 3 + 1 decode steps
    deltas = [x["delta"]["scheduler"]["decode_steps"] for x in lines]
    assert deltas == [3, 3, 1]


# ---------------------------------------------------------------------------
# profiler
# ---------------------------------------------------------------------------
def test_profiler_summary_and_trace_spans():
    tr = Tracer(capacity=256)
    prof = StepProfiler(tr)
    for _ in range(4):
        with prof.step("decode"):
            sum(range(2000))               # stand-in device work
    s = prof.summary()
    assert s["decode"]["steps"] == 4
    assert s["decode"]["device_ms"]["p50"] >= 0.0
    assert 0.0 <= s["decode"]["host_frac"] <= 1.0
    doc = tr.to_perfetto()
    _validate_perfetto(doc)
    dev = [e for e in doc["traceEvents"] if e.get("name") == "device:decode"]
    assert len(dev) == 4 and all(e["ph"] == "X" for e in dev)


# ---------------------------------------------------------------------------
# traced serving: schema, coverage, and non-perturbation
# ---------------------------------------------------------------------------
@pytest.mark.slow
def test_traced_run_schema_coverage_and_identical_streams(tmp_path):
    """One PagedBatcher workload run twice — recorder on vs off.  The traced
    run must export a schema-valid Perfetto doc in which every step span
    the scheduler opened was closed, and every greedy stream must be
    bit-identical to the untraced run (observability must not touch
    scheduling or numerics)."""
    cfg, model, params = _setup()
    sc = ServingConfig(n_slots=3, s_max=24, chunk_size=4, kv_bits=16,
                       block_size=4)

    def run(trace):
        b = PagedBatcher(model, params,
                         dataclasses.replace(sc, trace=trace))
        for r in _requests(cfg):
            b.submit(r)
        done = b.run()
        return b, {r.rid: list(r.output) for r in done}

    tcfg = TraceConfig(enabled=True, path=str(tmp_path / "t.json"))
    traced_b, traced_out = run(tcfg)
    traced_b.tracer.detach_engine()
    _, plain_out = run(None)

    assert traced_out == plain_out         # bit-identical streams
    doc = traced_b.tracer.to_perfetto(str(tmp_path / "t.json"))
    _validate_perfetto(doc)
    steps = [e for e in doc["traceEvents"] if e.get("name") == "step"]
    n_steps = traced_b.metrics.scheduler_steps
    assert n_steps > 0
    assert [e["ph"] for e in steps] == ["B", "E"] * n_steps
    assert not any(e.get("args", {}).get("synthetic_close") for e in steps)
    names = {e.get("name") for e in doc["traceEvents"]}
    for expected in ("step", "decode", "prefill_chunk", "admit", "finish",
                     "first_token", "req", "kv_blocks"):
        assert expected in names, expected
    # the file written is valid JSON and identical to the returned doc
    assert json.loads((tmp_path / "t.json").read_text()) == doc


def _profiled_spans(trace_dir):
    """The ``repro.*`` host spans of a ``jax.profiler`` capture as
    ``(start, end, name, children)``, nested by containment per thread."""
    [pb] = trace_dir.glob("plugins/profile/*/*.xplane.pb")
    pd = jax.profiler.ProfileData.from_file(str(pb))
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            evs = sorted((e.start_ns, -(e.start_ns + e.duration_ns), e.name)
                         for e in line.events
                         if e.name.startswith("repro."))
            stack = []
            for a, nb, name in evs:
                span = (a, -nb, name[len("repro."):], [])
                while stack and span[1] > stack[-1][1]:
                    stack.pop()
                if stack:
                    stack[-1][3].append(span)
                stack.append(span)
                out.append(span)
    return out


def _within(span, name):
    found = []
    for c in span[3]:
        if c[2] == name:
            found.append(c)
        found += _within(c, name)
    return found


def test_profiler_capture_nests_the_scheduler_phases(tmp_path):
    """With no trace config (the shared disabled tracer) and a profiler
    session running, every scheduler step lands on the profiler's host
    plane with its phases nested inside it, and the served tokens are
    those of the same run without the session."""
    cfg, model, params = _setup()
    sc = ServingConfig(n_slots=3, s_max=24, chunk_size=4, kv_bits=16,
                       block_size=4)

    def run():
        b = PagedBatcher(model, params, sc)
        assert b.tracer is NULL_TRACER
        for r in _requests(cfg):
            b.submit(r)
        done = b.run()
        return b, {r.rid: list(r.output) for r in done}

    _, plain_out = run()
    with jax.profiler.trace(str(tmp_path)):
        b, traced_out = run()
    assert traced_out == plain_out
    assert list(NULL_TRACER.events) == []

    spans = _profiled_spans(tmp_path)
    steps = [s for s in spans if s[2] == "step"]
    assert len(steps) == b.metrics.scheduler_steps
    decode_steps = [s for s in steps if _within(s, "decode")]
    assert len(decode_steps) == b.metrics.decode_steps > 0
    for s in decode_steps:
        assert len(_within(s, "decode")) == 1
        assert len(_within(s, "decode_fetch")) == 1
        assert len(_within(s, "emit")) == 1
    assert all(not _within(s, "decode_fetch") for s in steps
               if s not in decode_steps)
    # every prompt finishes in a step of its own, which holds its first
    # token's wait inside the step's admission
    first = [s for s in steps if _within(s, "first_token")]
    assert len(first) == len(traced_out) == 4
    for s in first:
        [admit] = [c for c in s[3] if c[2] == "admit"]
        assert len(_within(admit, "first_token")) == 1
    # every prefill chunk is dispatched inside the step's admission
    chunks = [c for s in spans if s[2] == "admit"
              for c in _within(s, "prefill_chunk")]
    assert len(chunks) == b.metrics.prefill_chunks
    assert len([s for s in spans if s[2] == "prefill_chunk"]) == len(chunks)


@pytest.mark.slow
def test_crash_dumps_flight_recorder(tmp_path):
    """An exception unwinding run() writes the JSONL flight recorder next
    to the crash, then re-raises untouched."""
    cfg, model, params = _setup()
    crash = tmp_path / "boom.crash.jsonl"
    sc = ServingConfig(n_slots=2, s_max=24, chunk_size=4,
                       trace=TraceConfig(enabled=True,
                                         crash_dump=str(crash)))
    b = ContinuousBatcher(model, params, sc)

    class Boom(RuntimeError):
        pass

    def explode(req, tok, finished):
        raise Boom("third token")

    reqs = _requests(cfg, n=2)
    reqs[0].options = RequestOptions(max_new=6, on_token=explode)
    for r in reqs:
        b.submit(r)
    with pytest.raises(Boom):
        b.run()
    b.tracer.detach_engine()
    lines = [json.loads(x) for x in crash.read_text().splitlines()]
    assert lines[0]["flight_recorder"] is True
    assert any(e.get("name") == "step" for e in lines[1:])
    # idempotent: a second unwind through a shared tracer doesn't rewrite
    crash.unlink()
    b.tracer.on_crash()
    assert not crash.exists()
