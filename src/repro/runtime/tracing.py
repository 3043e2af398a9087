"""Serving flight recorder — structured event tracing for the scheduler.

The paper's sustained-throughput claim only holds while the scheduler keeps
the compute saturated; :mod:`repro.runtime.metrics` reports *aggregates*
(percentiles, counters) but cannot answer "where did step 412 spend its
time" or "what did the brownout controller see the tick it raised".  This
module records the event stream those questions need:

  * **spans**, opened only through :meth:`Tracer.span`: the scheduler step
    and its phases (admission, prefill chunk, first-token wait, block
    allocation, loop-state staging, decode dispatch, next-token fetch,
    emission), speculative draft/verify rounds;
  * **instants**: admission, first token, finish, preemption, stall,
    pool-eviction waves, brownout level transitions (with the
    ``controller_signals()`` snapshot that caused them), engine kernel
    dispatches (via :func:`repro.kernels.engine.set_dispatch_listener`);
  * **counters**: KV-pool occupancy, tuning-cache hits/misses;
  * **flow events** linking one request's admission → chunks → first token
    → finish (→ re-admission after preemption) across slots and lanes.

Every span also enters ``jax.profiler.TraceAnnotation("repro.<name>")``,
whether or not the ring records: under a running ``jax.profiler`` session
the scheduler's phases land on the profiler's host plane, on the same
clock as the device timeline; with no session running the annotation costs
well under a microsecond.  The profiler session is the only switch for
these spans.

Events land in a bounded ring buffer (``collections.deque(maxlen=...)``,
drop-oldest; the drop count is exposed and exported).  The hot-path cost is
one dict construction + deque append per event when enabled and a single
attribute check when disabled.  Tracer calls NEVER appear inside
jit-compiled step functions (the ``tracing-in-jit`` astlint rule enforces
this: a tracer call traced into a jaxpr would either crash lowering or
silently record once at compile time).

Exporters:
  * :meth:`Tracer.to_perfetto` — chrome://tracing / Perfetto JSON.  Ring
    overflow can orphan an ``E`` (its ``B`` was dropped) or strand a ``B``
    (export mid-span); the exporter prunes the former and synthesizes a
    closing ``E`` for the latter so every exported ``B`` has an ``E``.
  * :meth:`Tracer.dump_jsonl` / :meth:`Tracer.on_crash` — flight-recorder
    dump, one event per line; ``run()`` calls ``on_crash`` on any exception
    so the last N events land next to the stack trace.
  * :class:`MetricsSnapshotter` — periodic ``Metrics.summary()`` snapshots
    (plus numeric-leaf deltas vs the previous snapshot) to JSONL, for
    load-over-time plots; ``launch/serve.py --metrics-interval`` rides it.

Ring timestamps are ``time.perf_counter`` microseconds relative to the
tracer's construction (the chrome-trace unit); snapshot lines also carry
wall time.
"""
from __future__ import annotations

import contextlib
import dataclasses
import json
import time
from collections import deque
from typing import Any

import jax

# well-known track (chrome "thread") names; batchers may add their own
# (the adaptive server names one track per lane)
TRACK_SCHEDULER = "scheduler"
TRACK_DEVICE = "device"
TRACK_ENGINE = "engine"

_PID = 1                       # single-process scheduler: one trace "process"


@dataclasses.dataclass
class TraceConfig:
    """The ``ServingConfig.trace`` payload: what to record and where it goes.

    ``enabled=False`` with a ``snapshot_interval`` still ticks the metrics
    snapshotter (``--metrics-interval`` without ``--trace``); ``profile``
    turns on the per-step device-sync boundary timing
    (:class:`repro.runtime.profile.StepProfiler`) independently of event
    recording."""
    enabled: bool = True
    buffer: int = 65536                 # ring capacity (events)
    path: str | None = None             # Perfetto JSON export target
    crash_dump: str | None = None       # JSONL on exception (default:
                                        # "<path>.crash.jsonl", or
                                        # "flight_recorder_crash.jsonl")
    snapshot_path: str | None = None    # metrics-snapshot JSONL
    snapshot_interval: int = 0          # scheduler steps between snapshots
    profile: bool = False               # device-time vs host-gap per step


class Tracer:
    """Bounded-ring structured event recorder (chrome-trace event dicts).

    Every recording method is a no-op behind one ``self.enabled`` check —
    call sites guard with ``if tr.enabled:`` where they would otherwise
    build kwargs.  :meth:`span` is the one exception: it always enters its
    profiler annotation, and records into the ring only when enabled."""

    def __init__(self, capacity: int = 65536, *, enabled: bool = True):
        self.enabled = bool(enabled)
        self.capacity = max(int(capacity), 16)
        self.events: deque[dict] = deque(maxlen=self.capacity)
        self.dropped = 0
        self.config: TraceConfig | None = None
        self.snapshotter: MetricsSnapshotter | None = None
        self._t0 = time.perf_counter()
        self._wall_t0 = time.time()
        self._tracks: dict[str, int] = {}
        self._last_tuning: dict | None = None
        self._engine_attached = False
        self._crash_dumped = False

    # ------------------------------------------------------------ factory
    @classmethod
    def from_config(cls, trace) -> "Tracer":
        """Build the tracer a batcher runs on from ``ServingConfig.trace``:
        ``None`` → the shared disabled singleton; an existing ``Tracer`` is
        passed through (the adaptive server shares one across lanes)."""
        if trace is None:
            return NULL_TRACER
        if isinstance(trace, Tracer):
            return trace
        t = cls(capacity=trace.buffer, enabled=trace.enabled)
        t.config = trace
        if trace.snapshot_interval and trace.snapshot_path:
            t.snapshotter = MetricsSnapshotter(
                trace.snapshot_path, trace.snapshot_interval)
        if t.enabled:
            t.attach_engine()
        return t

    # ---------------------------------------------------------- recording
    def _now_us(self) -> float:
        return (time.perf_counter() - self._t0) * 1e6

    def _append(self, ev: dict) -> None:
        if len(self.events) == self.capacity:
            self.dropped += 1
        self.events.append(ev)

    def track(self, name: str) -> int:
        """Stable tid for a named track (chrome "thread"); registers the
        thread_name metadata lazily at export."""
        tid = self._tracks.get(name)
        if tid is None:
            tid = len(self._tracks) + 1
            self._tracks[name] = tid
        return tid

    def begin(self, name: str, cat: str, track: str = TRACK_SCHEDULER,
              **args) -> None:
        if not self.enabled:
            return
        self._append({"ph": "B", "name": name, "cat": cat,
                      "ts": self._now_us(), "pid": _PID,
                      "tid": self.track(track), "args": args})

    def end(self, name: str, cat: str, track: str = TRACK_SCHEDULER,
            **args) -> None:
        if not self.enabled:
            return
        self._append({"ph": "E", "name": name, "cat": cat,
                      "ts": self._now_us(), "pid": _PID,
                      "tid": self.track(track), "args": args})

    @contextlib.contextmanager
    def span(self, name: str, cat: str, track: str = TRACK_SCHEDULER,
             **args):
        """Open a span: ``repro.<name>`` on the profiler's host plane always,
        and the ring's ``B``/``E`` pair under ``name`` when enabled (closed
        on an exception too).  Host code only — never inside jitted code."""
        with jax.profiler.TraceAnnotation(f"repro.{name}"):
            if not self.enabled:
                yield
                return
            self.begin(name, cat, track, **args)
            try:
                yield
            finally:
                self.end(name, cat, track)

    def instant(self, name: str, cat: str, track: str = TRACK_SCHEDULER,
                **args) -> None:
        if not self.enabled:
            return
        self._append({"ph": "i", "s": "t", "name": name, "cat": cat,
                      "ts": self._now_us(), "pid": _PID,
                      "tid": self.track(track), "args": args})

    def counter(self, name: str, cat: str, track: str = TRACK_SCHEDULER,
                **values) -> None:
        if not self.enabled:
            return
        self._append({"ph": "C", "name": name, "cat": cat,
                      "ts": self._now_us(), "pid": _PID,
                      "tid": self.track(track), "args": values})

    def complete(self, name: str, cat: str, ts_us: float, dur_us: float,
                 track: str = TRACK_SCHEDULER, **args) -> None:
        """Retro-emitted complete ("X") span with explicit start/duration —
        the profiler's shape: timing is measured first, recorded after."""
        if not self.enabled:
            return
        self._append({"ph": "X", "name": name, "cat": cat, "ts": ts_us,
                      "dur": dur_us, "pid": _PID, "tid": self.track(track),
                      "args": args})

    def flow(self, phase: str, fid: int, track: str = TRACK_SCHEDULER,
             name: str = "req") -> None:
        """Per-request flow edge: ``phase`` is "s" (start, at admission),
        "t" (through: chunks/tokens/re-admission), or "f" (finish).  The
        flow id is the request id, so Perfetto draws one arrow chain per
        request across slots and lanes."""
        if not self.enabled:
            return
        ev = {"ph": phase, "name": name, "cat": "flow", "id": int(fid),
              "ts": self._now_us(), "pid": _PID, "tid": self.track(track)}
        if phase == "f":
            ev["bp"] = "e"                 # bind to the enclosing slice end
        self._append(ev)

    # --------------------------------------------------- engine timeline
    def attach_engine(self) -> None:
        """Put kernel dispatches on this trace's timeline: install a
        persistent listener on the engine's dispatch-trace hook.  Dispatches
        fire at jit TRACE time (first call / recompile), so these instants
        mark compiles, not per-step runtime work — which is exactly the
        honest placement: a dispatch instant mid-serving means a shape
        bucket recompiled mid-serving."""
        if not self.enabled or self._engine_attached:
            return
        from repro.kernels import engine

        def _on_dispatch(ev) -> None:
            args = {k: (list(v) if isinstance(v, tuple) else v)
                    for k, v in ev._asdict().items() if k != "op"}
            self.instant(f"dispatch:{ev.op}", "engine",
                         track=TRACK_ENGINE, **args)

        engine.set_dispatch_listener(_on_dispatch)
        self._engine_attached = True

    def detach_engine(self) -> None:
        if self._engine_attached:
            from repro.kernels import engine
            engine.set_dispatch_listener(None)
            self._engine_attached = False

    def maybe_tuning_counter(self) -> None:
        """Emit a tuning-cache counter sample when the stats moved since the
        last emission (hits/misses/sweeps live in one process-wide dict, so
        per-step unconditional sampling would just repeat values)."""
        if not self.enabled:
            return
        from repro.kernels import tuning
        s = tuning.stats()
        if s != self._last_tuning:
            self._last_tuning = dict(s)
            self.counter("tuning_cache", "engine", track=TRACK_ENGINE, **s)

    # ----------------------------------------------------- snapshot tick
    def tick_snapshot(self, metrics) -> None:
        if self.snapshotter is not None:
            self.snapshotter.tick(metrics)

    # ----------------------------------------------------------- export
    def _sanitized(self) -> list[dict]:
        """Ring contents made chrome-trace-consistent: orphaned ``E`` events
        (their ``B`` fell off the ring) are pruned, unclosed ``B`` events get
        a synthetic closing ``E`` at the last timestamp, and flow ``t``/``f``
        edges whose ``s`` was dropped are pruned too."""
        body: list[dict] = []
        stacks: dict[int, list[dict]] = {}
        flow_starts: set[int] = set()
        last_ts = 0.0
        for ev in self.events:
            last_ts = max(last_ts, ev["ts"] + ev.get("dur", 0.0))
            ph = ev["ph"]
            if ph == "B":
                stacks.setdefault(ev["tid"], []).append(ev)
            elif ph == "E":
                st = stacks.get(ev["tid"])
                if not st:
                    continue               # orphan: its B was dropped
                st.pop()
            elif ph == "s":
                flow_starts.add(ev["id"])
            elif ph in ("t", "f") and ev["id"] not in flow_starts:
                continue                   # orphan flow edge
            body.append(ev)
        for st in stacks.values():
            for b in reversed(st):
                body.append({"ph": "E", "name": b["name"], "cat": b["cat"],
                             "ts": last_ts, "pid": _PID, "tid": b["tid"],
                             "args": {"synthetic_close": True}})
        return body

    def to_perfetto(self, path: str | None = None) -> dict:
        """Export the ring as a chrome://tracing / Perfetto JSON object
        (and write it to ``path`` when given)."""
        meta = [{"ph": "M", "name": "process_name", "pid": _PID,
                 "args": {"name": "repro-serving"}}]
        for name, tid in self._tracks.items():
            meta.append({"ph": "M", "name": "thread_name", "pid": _PID,
                         "tid": tid, "args": {"name": name}})
            meta.append({"ph": "M", "name": "thread_sort_index", "pid": _PID,
                         "tid": tid, "args": {"sort_index": tid}})
        obj = {
            "traceEvents": meta + self._sanitized(),
            "displayTimeUnit": "ms",
            "otherData": {
                "dropped_events": self.dropped,
                "buffer_capacity": self.capacity,
                "wall_t0": self._wall_t0,
            },
        }
        if path:
            with open(path, "w") as f:
                json.dump(obj, f)
        return obj

    def dump_jsonl(self, path: str, last: int | None = None) -> int:
        """Flight-recorder dump: the last ``last`` ring events (all when
        None), one JSON object per line.  Returns the line count."""
        evs = list(self.events)
        if last is not None:
            evs = evs[-int(last):]
        with open(path, "w") as f:
            f.write(json.dumps({"flight_recorder": True,
                                "dropped": self.dropped,
                                "wall_t0": self._wall_t0}) + "\n")
            for ev in evs:
                f.write(json.dumps(ev) + "\n")
        return len(evs)

    def crash_path(self) -> str:
        cfg = self.config
        if cfg is not None and cfg.crash_dump:
            return cfg.crash_dump
        if cfg is not None and cfg.path:
            return cfg.path + ".crash.jsonl"
        return "flight_recorder_crash.jsonl"

    def on_crash(self) -> None:
        """Exception hook for ``run()``: dump the ring next to the crash.
        Idempotent — the adaptive server and its lanes share one tracer, and
        only the outermost unwind should write."""
        if not self.enabled or self._crash_dumped:
            return
        self._crash_dumped = True
        try:
            self.dump_jsonl(self.crash_path())
        except OSError:                    # never mask the real exception
            pass


# Shared disabled singleton: batchers constructed without a trace config all
# point here, so the hot path pays one attribute read, zero allocation.
NULL_TRACER = Tracer(capacity=16, enabled=False)


class MetricsSnapshotter:
    """Periodic ``Metrics.summary()`` snapshots to JSONL.

    Every line carries the step counter, wall time, the full summary, and
    ``delta`` — the numeric leaves of the summary minus the previous
    snapshot's (counters become per-interval rates for load-over-time
    plots).  Lines are appended and flushed per write so a crash loses at
    most the current interval."""

    def __init__(self, path: str, interval: int = 32):
        self.path = path
        self.interval = max(int(interval), 1)
        self.lines_written = 0
        self._since = 0
        self._prev: dict | None = None
        with open(path, "w"):              # truncate: one file per run
            pass

    def tick(self, metrics) -> None:
        self._since += 1
        if self._since >= self.interval:
            self._since = 0
            self.write(metrics)

    def write(self, metrics) -> None:
        s = metrics.summary()
        line = {
            "step": metrics.scheduler_steps,
            "t_wall": time.time(),
            "summary": s,
            "delta": _numeric_delta(self._prev, s),
        }
        self._prev = s
        with open(self.path, "a") as f:
            f.write(json.dumps(line) + "\n")
        self.lines_written += 1

    def final(self, metrics) -> None:
        """One last snapshot regardless of interval phase (end of run)."""
        self.write(metrics)


def _numeric_delta(prev: Any, cur: Any) -> Any:
    """Numeric leaves of ``cur`` minus the matching leaves of ``prev``
    (missing/previously-absent leaves delta against 0); non-numeric leaves
    are dropped."""
    if isinstance(cur, dict):
        out = {}
        for k, v in cur.items():
            d = _numeric_delta(prev.get(k) if isinstance(prev, dict)
                               else None, v)
            if d is not None:
                out[k] = d
        return out or None
    if isinstance(cur, bool):
        return None
    if isinstance(cur, (int, float)):
        base = prev if isinstance(prev, (int, float)) \
            and not isinstance(prev, bool) else 0
        return cur - base
    return None

