#!/usr/bin/env python3
"""Run one benchmark cell once on the chip.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything a cell is made of is data found by name: the cell in
``BENCHMARK.json`` names a configuration (``bench/configs/<name>.json``:
the model's sizes, precision and deployment, and the plain reference it is
checked against) and a traffic mix (``bench/traffic/<name>.json``); each
per-layer metric is read by ``bench/layer_metrics/<name>.py``.

A run builds the weights on the device from the seed, warms every program
the window can run (the prefill chunk and every decode occupancy bucket),
drives the program's ``PagedBatcher`` for ``--seconds`` with the mix's
open or closed loop, then checks the served tokens of a sample of
finished requests against the reference.  With ``--trace 0`` it reports
the cell's end-to-end metrics; with ``--trace 1`` it records a profiler
trace of a slice of the window and reports the per-layer metrics.  The
last line of standard output is one JSON object; without a TPU, or with
fewer chips than the cell asks for, the run fails and prints none.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = ROOT / ".bench_trace"
TRACE_SECONDS = 3.0          # traced slice, in the middle of the window


class NoChip(RuntimeError):
    pass


# --------------------------------------------------------------------- spec
def load_spec(workload: str) -> dict:
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; known: "
                         f"{sorted(cells)}")
    cell = cells[workload]
    conf = next(c for c in spec["configs"] if c["name"] == cell["config"])

    def mine(ms):
        return [x for x in ms if workload in x.get("workloads", [workload])]
    return {"cell": cell, "config_file": ROOT / conf["file"],
            "end_to_end": mine(spec["end_to_end"]),
            "per_layer": mine(spec["per_layer"])}


# ------------------------------------------------------------------- device
def devices(chips: int, require_tpu: bool = True):
    import jax
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoChip(f"JAX sees no TPU (platform {devs[0].platform!r})")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    return devs[:chips]


def peak_bytes(devs) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devs)


def peaks_for(device_kind: str) -> dict:
    """The chip's published peaks; a device missing from the table is an
    error, never a default."""
    table = json.loads((BENCH / "peaks.json").read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json (known: {sorted(table)})")
    return table[device_kind]


def use_compile_cache():
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


class CompileCount:
    """Backend compilations so far (programs loaded from the persistent
    cache do not count)."""

    def __init__(self):
        import jax
        self.n = 0

        def listen(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.n += 1
        jax.monitoring.register_event_duration_secs_listener(listen)


# ------------------------------------------------------------------ warm-up
def warm(server, n_slots: int, vocab: int, seed: int):
    """Serve ``n_slots`` one-chunk requests, long enough that all are live
    at once: admission and decode then run every occupancy bucket up to
    ``n_slots``."""
    import numpy as np
    from bench.traffic import Req
    rng = np.random.default_rng([int(seed), 9])
    c = server.chunk_size
    reqs = [Req(-1 - i, rng.integers(0, vocab, c - 1).astype(np.int32),
                n_slots + 8) for i in range(n_slots)]
    for r in reqs:
        server.submit(r, lambda h, t, f, r=r: _mark(r, f))
    while not server.idle:
        server.step()


def _mark(r, finished):
    r.done = r.done or finished


# ------------------------------------------------------------------ the run
class RunData:
    """What the per-layer readers see of one run."""

    def __init__(self, **kw):
        self.__dict__.update(kw)

    def clock_of_wall(self, wall: float) -> float:
        return wall - self.wall0 + self.clock0

    def counter_delta(self, which: str) -> dict:
        a, b = self.counters[which]
        return {k: b[k] - a[k] for k in a}

    def _in_trace(self, t):
        return self.trace_lo <= t <= self.trace_hi

    def decode_contexts(self) -> list[int]:
        """Context length of every decode token emitted in the traced
        slice: output token j >= 1 attended over prompt + j positions."""
        return [r.prompt_len + j for r in self.reqs
                for j, t in enumerate(r.token_t) if j and self._in_trace(t)]

    def tokens_in_trace(self) -> int:
        return sum(1 for r in self.reqs for t in r.token_t
                   if self._in_trace(t))

    def roofline_share(self, work: dict, seconds: float) -> float:
        least = max(work["ops"] / self.peaks[work["peak"]],
                    work["bytes"] / self.peaks["hbm_bytes_per_s"])
        return 100.0 * least / seconds


def end_to_end(reqs, due, win, setup_s, loop: str) -> dict:
    from bench.stats import percentile
    t0, t1 = win.t0, win.t1
    out = {"setup_s": setup_s}
    gaps = [(b - a) * 1e3 for r in reqs
            for a, b in zip(r.token_t, r.token_t[1:]) if t0 <= b <= t1]
    out["itl_p95_ms"] = percentile(gaps, 95)
    if loop == "open":
        out["ttft_p95_ms"] = percentile(
            [(r.token_t[0] - r.due) * 1e3 if r.token_t else math.inf
             for r in due], 95)
    n_tok = sum(1 for r in reqs for t in r.token_t if t0 <= t <= t1)
    out["output_tok_s"] = n_tok / (t1 - t0)
    return out


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             require_tpu: bool = True, config_override=None,
             mix_override=None, spec_override=None, keep=None):
    """One run of one cell; returns the result line's object.  ``keep``
    (a dict) receives the run's requests."""
    os.environ["REPRO_TUNING_CACHE"] = str(ROOT / ".bench_tuning.json")
    spec = spec_override or load_spec(workload)
    cfg_json = config_override or json.loads(spec["config_file"].read_text())
    import jax
    cell = spec["cell"]
    devs = devices(cell["chips"], require_tpu)
    use_compile_cache()
    compiles = CompileCount()

    from bench import build, check, drivers, traffic
    from bench.server import PagedServer
    mix = mix_override or traffic.load_mix(cell["traffic"])
    m, serving = cfg_json["model"], cfg_json["serving"]

    params = build.serving_params(cfg_json, seed)
    jax.block_until_ready(params)
    gen = traffic.Traffic(mix, m["vocab"], seed)
    if mix["loop"] == "open":
        reqs = gen.open_schedule(seconds, float(mix.get("drain_s", 60)))
        due = [r for r in reqs if r.due < seconds]
        live = serving["n_slots"]
    else:
        reqs = [gen.closed(i) for i in range(int(mix["clients"]))]
        due, live = [], min(serving["n_slots"], int(mix["clients"]))
    server = PagedServer(build.batcher(cfg_json, params))
    warm(server, live, m["vocab"], seed)

    span = jax.profiler.TraceAnnotation
    counters, tstate = {}, {"lo": None, "hi": None, "ann": None}
    clock = time.perf_counter
    wall_pair = (time.time(), clock())

    mid = (seconds - min(seconds, TRACE_SECONDS)) / 2

    def tick(now, t0):
        """Counter snapshots at the window's edges; the traced slice."""
        if "window" not in counters:
            counters["window"] = [server.counters(), None]
        if not trace:
            return
        if tstate["lo"] is None and now >= t0 + mid:
            import shutil
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            jax.profiler.start_trace(str(TRACE_DIR))
            tstate["ann"] = span("bench.trace_window")
            tstate["ann"].__enter__()
            tstate["lo"] = clock()
            counters["trace"] = [server.counters(), None]
        elif (tstate["lo"] is not None and tstate["hi"] is None
              and now >= tstate["lo"] + min(seconds, TRACE_SECONDS)):
            stop_trace()

    def stop_trace():
        tstate["hi"] = clock()
        counters["trace"][1] = server.counters()
        tstate["ann"].__exit__(None, None, None)
        jax.profiler.stop_trace()

    n0 = compiles.n
    if mix["loop"] == "open":
        win = drivers.open_loop(server, reqs, seconds,
                                float(mix.get("drain_s", 60)), clock=clock,
                                sleep=time.sleep, span=span, tick=tick)
    else:
        sent = [0]

        def next_req():
            if sent[0] == len(reqs):
                reqs.append(gen.closed(len(reqs)))
            sent[0] += 1
            return reqs[sent[0] - 1]
        # the shared document, prefilled once into the prefix cache
        doc = gen.closed(10**9)
        doc.max_new = 8
        warm_reqs = [doc] if mix.get("shared_prefix") else []
        win = drivers.closed_loop(server, next_req, int(mix["clients"]),
                                  seconds, clock=clock, span=span,
                                  warm=warm_reqs, tick=tick)
    if trace and tstate["hi"] is None and tstate["lo"] is not None:
        stop_trace()
    counters["window"][1] = server.counters()
    in_window = compiles.n - n0
    # the sample: requests due in the window (all waited for), or those a
    # closed loop finished by the window's close
    picked = check.choose(due if due else [r for r in reqs if r.done], seed)
    print(f"compiles inside the window: {in_window}", flush=True)

    setup_s = win.t0 - (T_START - time.monotonic() + clock())
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": peak_bytes(devs)}
    chunk_size, programs = server.chunk_size, server.programs()

    # the program's state goes before the reference runs
    del server, params
    gc.collect()

    attempted = len(due) if mix["loop"] == "open" else sum(
        1 for r in reqs if r.token_t and r.token_t[0] <= win.t1)
    failed = sum(1 for r in due if not r.done)
    result = {"correct": None, "attempted": attempted, "failed": failed,
              "metrics": {}, "device": device}

    if trace:
        from bench import trace_reduce
        pb = sorted(TRACE_DIR.glob("plugins/profile/*/*.xplane.pb"))
        try:
            red = trace_reduce.reduce_file(pb[-1]) if pb else None
        except ValueError:          # no device plane: only off the chip
            if require_tpu:
                raise
            red = None
        peaks = peaks_for(devs[0].device_kind) if require_tpu else {}
        rd = RunData(m=m, reqs=reqs, due=due, win=win, trace=red,
                     counters=counters, trace_lo=tstate["lo"] or 0.0,
                     trace_hi=tstate["hi"] or 0.0, chunk_size=chunk_size,
                     wall0=wall_pair[0], clock0=wall_pair[1],
                     peaks=peaks,
                     programs=programs)
        for mt in spec["per_layer"]:
            reader = importlib.import_module(
                f"bench.layer_metrics.{mt['name']}")
            v = reader.read(rd)
            if v is not None and math.isfinite(v):
                result["metrics"][mt["name"]] = {"value": v,
                                                 "unit": mt["unit"]}
        if red is not None:
            device["busy_s"] = red.busy_s
            device["window_s"] = red.window_s
            result["breakdown"] = red.breakdown()
    else:
        e2e = end_to_end(reqs, due, win, setup_s, mix["loop"])
        for mt in spec["end_to_end"]:
            result["metrics"][mt["name"]] = {"value": e2e[mt["name"]],
                                             "unit": mt["unit"]}

    numbers, gap = check.compared(cfg_json, seed, reqs, due, picked)
    if keep is not None:
        keep.update(reqs=reqs, config=cfg_json, picked=picked, gap=gap)
    numbers["compiles_in_window"] = in_window
    limits = dict(cfg_json["limits"])
    limits.setdefault("compiles_in_window", 0)
    ok = all(k in numbers and numbers[k] <= lim for k, lim in limits.items())
    result["correct"] = bool(ok)
    result["checked"] = {k: {"value": numbers[k], "limit": limits.get(k)}
                         for k in numbers}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = ap.parse_args(argv)
    try:
        result = run_cell(a.workload, a.seed, a.seconds, bool(a.trace))
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    for k, v in result["checked"].items():
        print(f"check {k}: {v['value']!r} (limit {v['limit']!r})",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
