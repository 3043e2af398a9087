"""Reduce a ``jax.profiler`` trace (``.xplane.pb``) to what the per-layer
metrics read.

- Device busy time: the union of the intervals in which an operation ran
  on each TPU (the "XLA Ops" line of a ``/device:TPU:<n>`` plane), inside
  the traced window, averaged over the chips.
- Device time per compiled program ("XLA Modules" line) and per operation
  ("XLA Ops" line), by the operation's HLO name without its ``.<n>``
  suffix (``ternary_matmul``, ``paged_attention``, ``copy``, ``fusion``).
  Loops and calls (``while``, ``conditional``, ``call``) span the
  operations inside them and are left out of the per-operation table.
- Idle gaps: the stretches of the window with no operation running,
  attributed to the innermost host span of the benchmark (``bench.*``
  ``TraceAnnotation``s) that covers the middle of the gap.

The window is the host span named ``bench.trace_window``.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import re

WINDOW_SPAN = "bench.trace_window"
HOST_PREFIX = "bench."
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
_DEVICE = re.compile(r"^/device:TPU:\d+$")
_CONTAINERS = {"while", "conditional", "call"}


def op_name(event_name: str) -> str:
    """``%copy.130 = s8[...] copy(...)`` -> ``copy``."""
    head = event_name.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"\.\d+$", "", head)


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float                                  # mean over chips
    n_chips: int
    modules: dict                                  # name -> (seconds, count)
    ops: dict                                      # name -> (seconds, count)
    idle_by_span: dict                             # host span -> seconds

    def module_time(self, pattern: str) -> tuple[float, int]:
        """Summed device seconds and executions of programs whose name
        matches ``pattern`` (a regular expression), per chip."""
        rx = re.compile(pattern)
        hits = [v for k, v in self.modules.items() if rx.search(k)]
        return (sum(s for s, _ in hits) / self.n_chips,
                sum(n for _, n in hits) // self.n_chips)

    def op_time(self, pattern: str) -> tuple[float, int]:
        rx = re.compile(pattern)
        hits = [v for k, v in self.ops.items() if rx.search(k)]
        return (sum(s for s, _ in hits) / self.n_chips,
                sum(n for _, n in hits) // self.n_chips)

    def breakdown(self, n: int = 10) -> dict:
        top_ops = sorted(self.ops.items(), key=lambda kv: -kv[1][0])[:n]
        gaps = sorted(self.idle_by_span.items(), key=lambda kv: -kv[1])[:n]
        return {"device_ops": [[k, v[0] / self.n_chips] for k, v in top_ops],
                "idle_gaps": [[k, v] for k, v in gaps]}


def _merge(intervals):
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def _clip(a, b, lo, hi):
    return max(a, lo), min(b, hi)


def _host_spans(planes):
    spans = []
    for p in planes:
        if not p.name.startswith("/host:"):
            continue
        for line in p.lines:
            for e in line.events:
                if e.name.startswith(HOST_PREFIX):
                    spans.append((e.start_ns, e.start_ns + e.duration_ns,
                                  e.name))
    return spans


def reduce_planes(planes) -> Reduced:
    planes = list(planes)
    spans = _host_spans(planes)
    win = [s for s in spans if s[2] == WINDOW_SPAN]
    if not win:
        raise ValueError(f"trace holds no {WINDOW_SPAN!r} span")
    lo, hi = win[0][0], win[0][1]
    index = _SpanIndex([s for s in spans if s[2] != WINDOW_SPAN])
    modules: dict = collections.defaultdict(lambda: [0.0, 0])
    ops: dict = collections.defaultdict(lambda: [0.0, 0])
    idle: dict = collections.defaultdict(float)
    busy_total, n_chips = 0.0, 0
    for p in planes:
        if not _DEVICE.match(p.name):
            continue
        n_chips += 1
        busy = []
        for line in p.lines:
            if line.name not in (OPS_LINE, MODULES_LINE):
                continue
            table = ops if line.name == OPS_LINE else modules
            for e in line.events:
                a, b = _clip(e.start_ns, e.start_ns + e.duration_ns, lo, hi)
                if b <= a:
                    continue
                name = e.name
                if line.name == OPS_LINE:
                    busy.append((a, b))
                    name = op_name(name)
                    if name in _CONTAINERS:
                        continue
                table[name][0] += (b - a) * 1e-9
                table[name][1] += 1
        merged = _merge(busy)
        busy_total += sum(b - a for a, b in merged) * 1e-9
        edges = [lo] + [x for ab in merged for x in ab] + [hi]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                idle[index.at((a + b) / 2)] += (b - a) * 1e-9
    if n_chips == 0:
        raise ValueError("trace holds no TPU device plane")
    for k in idle:
        idle[k] /= n_chips
    return Reduced(window_s=(hi - lo) * 1e-9, busy_s=busy_total / n_chips,
                   n_chips=n_chips,
                   modules={k: tuple(v) for k, v in modules.items()},
                   ops={k: tuple(v) for k, v in ops.items()},
                   idle_by_span=dict(idle))


class _SpanIndex:
    """Innermost benchmark span covering a time: spans of one name never
    overlap each other, so each name is one sorted list searched by
    bisection, and the shortest hit among the names is the innermost."""

    def __init__(self, spans):
        by_name: dict = collections.defaultdict(list)
        for a, b, name in spans:
            by_name[name].append((a, b))
        self.names = {n: sorted(v) for n, v in by_name.items()}
        self.starts = {n: [a for a, _ in v] for n, v in self.names.items()}

    def at(self, t) -> str:
        best, name = None, "bench.none"
        for n, iv in self.names.items():
            i = bisect.bisect_right(self.starts[n], t) - 1
            if i >= 0 and iv[i][1] >= t:
                if best is None or iv[i][1] - iv[i][0] < best:
                    best, name = iv[i][1] - iv[i][0], n
        return name


def reduce_file(path) -> Reduced:
    import jax
    return reduce_planes(jax.profiler.ProfileData.from_file(str(path)).planes)
