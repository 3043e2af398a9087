"""The program's own host spans in a traced run.

The batcher opens each phase of a scheduler step through its tracer's
``span``, which writes ``repro.<phase>`` on the profiler's host plane, on
the device trace's clock: ``repro.step`` holding ``repro.admit`` (with
``repro.prefill_chunk`` and ``repro.first_token``), ``repro.pre_decode``,
``repro.stage``, ``repro.decode``, ``repro.decode_fetch`` and
``repro.emit``.  This module reads them from the traced run's profile
(the newest ``.xplane.pb`` under ``.bench_trace``, as ``bench/run.py``
writes it), keeps those that lie wholly inside the ``bench.trace_window``
span, and nests them by containment on their thread's line.  A program
that opens no such spans gives an empty list, and the readers built on it
return None.

    python3 bench/program_spans.py [<trace.xplane.pb>]

prints the traced window's device idle time split by the innermost
``repro.*`` span over each gap, with the span counts behind the readers.
"""
from __future__ import annotations

import dataclasses
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
TRACE_DIR = BENCH.parent / ".bench_trace"
WINDOW_SPAN = "bench.trace_window"
PREFIX = "repro."
# phases in which the host waits on the device, not on its own work
WAITS = ("decode_fetch", "first_token")


@dataclasses.dataclass
class Span:
    start_ns: float
    end_ns: float
    name: str                                  # without the prefix
    children: list = dataclasses.field(default_factory=list)

    @property
    def ms(self) -> float:
        return (self.end_ns - self.start_ns) * 1e-6

    def descendants(self, names):
        for c in self.children:
            if c.name in names:
                yield c
            yield from c.descendants(names)


def _host_lines(planes):
    return [line for p in planes if p.name.startswith("/host:")
            for line in p.lines]


def _window(lines):
    w = [(e.start_ns, e.start_ns + e.duration_ns) for line in lines
         for e in line.events if e.name == WINDOW_SPAN]
    return w[0] if w else None


def from_planes(planes) -> list[Span]:
    """Every ``repro.*`` span wholly inside the trace window, in start
    order, each holding the spans it contains on its thread's line."""
    lines = _host_lines(planes)
    window = _window(lines)
    if window is None:
        return []
    lo, hi = window
    out = []
    for line in lines:
        spans = sorted(
            (e.start_ns, -(e.start_ns + e.duration_ns), e.name)
            for e in line.events if e.name.startswith(PREFIX))
        stack: list[Span] = []
        for a, neg_b, name in spans:
            s = Span(a, -neg_b, name[len(PREFIX):])
            while stack and s.end_ns > stack[-1].end_ns:
                stack.pop()
            if stack:
                stack[-1].children.append(s)
            stack.append(s)
            if lo <= s.start_ns and s.end_ns <= hi:
                out.append(s)
    return sorted(out, key=lambda s: s.start_ns)


def newest_trace(trace_dir: Path) -> Path | None:
    pb = sorted(trace_dir.glob("plugins/profile/*/*.xplane.pb"))
    return pb[-1] if pb else None


def _load(path) -> list[Span]:
    import jax
    return from_planes(jax.profiler.ProfileData.from_file(str(path)).planes)


def of_run(run) -> list[Span] | None:
    """The run's spans, parsed once per run; None when the run has no
    device trace."""
    if run.trace is None:
        return None
    cached = getattr(run, "_program_spans", None)
    if cached is None:
        path = newest_trace(TRACE_DIR)
        cached = _load(path) if path is not None else []
        run._program_spans = cached
    return cached


def covered_ns(span: Span, names) -> float:
    """Time inside ``span`` covered by its descendants named ``names``."""
    merged: list[list[float]] = []
    for d in sorted(span.descendants(names), key=lambda d: d.start_ns):
        if merged and d.start_ns <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], d.end_ns)
        else:
            merged.append([d.start_ns, d.end_ns])
    return sum(b - a for a, b in merged)


def mean(xs):
    return sum(xs) / len(xs) if xs else None


def steps(spans) -> list[Span]:
    return [s for s in spans if s.name == "step"]


def chunk_steps(spans) -> list[Span]:
    return [s for s in steps(spans)
            if any(s.descendants(("prefill_chunk",)))]


def first_tokens(spans) -> list[Span]:
    return [s for s in spans if s.name == "first_token"]


def host_self_ms(spans):
    return mean([s.ms - covered_ns(s, WAITS) * 1e-6 for s in steps(spans)])


def chunk_step_ms(spans):
    return mean([s.ms for s in chunk_steps(spans)])


def first_token_wait_ms(spans):
    return mean([s.ms for s in first_tokens(spans)])


def idle_by_phase(planes) -> dict:
    """Device idle seconds in the trace window, by the innermost
    ``repro.*`` span that covers the middle of each gap (``none`` outside
    every span), averaged over the chips."""
    from bench import trace_reduce as tr
    planes = list(planes)
    window = _window(_host_lines(planes))
    if window is None:
        return {}
    lo, hi = window
    spans = from_planes(planes)
    idle: dict = {}
    n_chips = 0
    for p in planes:
        if not tr._DEVICE.match(p.name):
            continue
        n_chips += 1
        busy = [tr._clip(e.start_ns, e.start_ns + e.duration_ns, lo, hi)
                for line in p.lines if line.name == tr.OPS_LINE
                for e in line.events]
        merged = tr._merge([(a, b) for a, b in busy if b > a])
        edges = [lo] + [x for ab in merged for x in ab] + [hi]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                k = _innermost(spans, (a + b) / 2)
                idle[k] = idle.get(k, 0.0) + (b - a) * 1e-9
    return {k: v / n_chips for k, v in idle.items()}


def _innermost(spans, t) -> str:
    best = None
    for s in spans:
        if s.start_ns <= t <= s.end_ns and (
                best is None or s.end_ns - s.start_ns
                < best.end_ns - best.start_ns):
            best = s
    return best.name if best is not None else "none"


def summary(planes) -> dict:
    planes = list(planes)
    spans = from_planes(planes)
    return {"idle_s_by_phase": idle_by_phase(planes),
            "steps": len(steps(spans)),
            "chunk_steps": len(chunk_steps(spans)),
            "first_tokens": len(first_tokens(spans)),
            "step_ms": mean([s.ms for s in steps(spans)]),
            "host_self_ms": host_self_ms(spans),
            "chunk_step_ms": chunk_step_ms(spans),
            "first_token_wait_ms": first_token_wait_ms(spans)}


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    path = Path(argv[0]) if argv else newest_trace(TRACE_DIR)
    if path is None:
        print("no trace found", file=sys.stderr)
        return 1
    import jax
    planes = jax.profiler.ProfileData.from_file(str(path)).planes
    print(json.dumps(summary(planes)))
    return 0


if __name__ == "__main__":
    for p in (str(BENCH.parent), str(BENCH.parent / "src")):
        if p not in sys.path:
            sys.path.insert(0, p)
    sys.exit(main())
