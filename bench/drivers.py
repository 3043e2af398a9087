"""Load drivers: an open loop (requests arrive when due, whatever the
server does) and a closed loop (each client sends its next request when
the last one finishes).

A driver talks to a server through ``submit(req, on_token)``, ``step()``
and ``idle``, and reads time from ``clock`` (seconds, monotonic), so the
tests can drive it with a fake server and a fake clock.  Every token is
stamped with the clock when the server hands it over.  Host spans
(``span(name)``) mark submitting, stepping, token emission and waiting for
the next arrival, so the trace can say what the host did in a device gap.
"""
from __future__ import annotations

import contextlib
import dataclasses


def _no_span(name):
    return contextlib.nullcontext()


@dataclasses.dataclass
class Window:
    t0: float                  # window start, driver clock
    t1: float                  # window close


class _Stamp:
    """on_token callback: records the token and its time on the request."""

    def __init__(self, req, clock, span):
        self.req, self.clock, self.span = req, clock, span

    def __call__(self, _handle, tok, finished):
        with self.span("bench.emit"):
            self.req.token_t.append(self.clock())
            self.req.output.append(int(tok))
            if finished:
                self.req.done = True


def _submit(server, req, clock, span):
    with span("bench.submit"):
        req.submit_t = clock()
        server.submit(req, _Stamp(req, clock, span))


def _step(server, span):
    with span("bench.step"):
        server.step()


def _no_tick(now, t0):
    pass


def open_loop(server, reqs, window_s: float, drain_s: float, *, clock,
              sleep, span=_no_span, tick=_no_tick) -> Window:
    """Submit each request when it is due (``req.due`` seconds after the
    window opens) and step the server in between.  After the window closes
    the arrivals go on, so the load stays, until every request due in the
    window has finished or ``drain_s`` has passed.  ``tick(now, t0)`` runs
    once per turn of the loop."""
    t0 = clock()
    for r in reqs:
        r.due += t0
    due_in_window = [r for r in reqs if r.due < t0 + window_s]
    i = 0
    while True:
        now = clock()
        tick(now, t0)
        if now >= t0 + window_s and all(r.done for r in due_in_window):
            break
        if now >= t0 + window_s + drain_s:
            break
        while i < len(reqs) and reqs[i].due <= now:
            _submit(server, reqs[i], clock, span)
            i += 1
        if not server.idle:
            _step(server, span)
        elif i < len(reqs):
            wake = reqs[i].due if now >= t0 + window_s else min(
                reqs[i].due, t0 + window_s)
            with span("bench.wait_arrival"):
                sleep(max(0.0, wake - now))
        else:
            break
    return Window(t0, t0 + window_s)


def closed_loop(server, next_req, clients: int, window_s: float, *, clock,
                span=_no_span, warm=(), tick=_no_tick) -> Window:
    """``clients`` clients, each sending its next request (``next_req()``)
    as soon as its last one finishes.  The ramp — every client's first
    request admitted and producing tokens — comes before the window opens.
    ``warm`` requests are served to completion first (a shared document,
    say)."""
    for r in warm:
        _submit(server, r, clock, span)
        while not r.done:
            _step(server, span)
    live = []
    for _ in range(clients):
        r = next_req()
        _submit(server, r, clock, span)
        live.append(r)
    while not all(r.token_t for r in live):
        _step(server, span)
        live = _refill(server, live, next_req, clock, span)
    t0 = clock()
    while (now := clock()) < t0 + window_s:
        tick(now, t0)
        _step(server, span)
        live = _refill(server, live, next_req, clock, span)
    return Window(t0, clock())


def _refill(server, live, next_req, clock, span):
    out = []
    for r in live:
        if r.done:
            r = next_req()
            _submit(server, r, clock, span)
        out.append(r)
    return out
