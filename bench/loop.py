"""Drives the front door through one measured window, and the arithmetic
of what the window measured.

The window is timed on the host's clock.  Each request carries the time
it was due: in a closed loop the moment the harness hands it over, in an
open loop its place on the schedule.  Latency runs from that due time to
the moment its logits reached the host, so a step that blocks the loop
shows in the latency of every request that waited behind it, not only in
its own.  A rate counts the rows finished inside the window over the
whole window.

Host spans: the window and every call into the front door run under
``jax.profiler.TraceAnnotation``, so a device trace can say what the host
was doing while the device sat idle — ``bench.window``,
``frontend.submit``, ``frontend.step`` and ``loadgen.wait``.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time

import numpy as np

SPANS = ("frontend.submit", "frontend.step", "loadgen.wait")


@dataclasses.dataclass
class Record:
    """One request of the run: rows ``[off, off + n)`` of the pool, due at
    ``due`` on the host clock; ``req`` is the front door's request."""
    off: int
    n: int
    due: float
    req: object
    submitted: float | None = None

    @property
    def done(self) -> bool:
        return bool(self.req.done) and self.req.t_done is not None


@dataclasses.dataclass
class Window:
    t0: float
    t_end: float
    records: list
    pending: list                  # planned but not yet submitted
    compiles: int = 0
    drain_end: float | None = None


def null_span(name):
    return contextlib.nullcontext()


class _Submitter:
    def __init__(self, fe, pool, new_request, clock, span):
        self.fe, self.pool = fe, pool
        self.new_request = new_request
        self.clock, self.span = clock, span
        self.records = []

    def submit(self, planned, due):
        req = self.new_request(len(self.records),
                               self.pool[planned.off:planned.off + planned.n])
        rec = Record(planned.off, planned.n, due, req)
        with self.span("frontend.submit"):
            self.fe.submit(req)
        rec.submitted = self.clock()
        self.records.append(rec)

    def step(self) -> bool:
        with self.span("frontend.step"):
            return self.fe.step()


def closed_window(fe, mix, stream, pool, seconds, *, new_request,
                  clock=time.perf_counter, span=null_span) -> Window:
    """Keep ``backlog_requests`` requests waiting at the front door and
    step it until ``seconds`` have passed."""
    d = _Submitter(fe, pool, new_request, clock, span)
    backlog = mix["backlog_requests"]
    with span("bench.window"):
        t0 = clock()
        t_end = t0 + seconds
        while clock() < t_end:
            while len(fe.queue) < backlog:
                d.submit(next(stream), clock())
            d.step()
    return Window(t0, t_end, d.records, [])


def open_window(fe, plan, pool, seconds, *, new_request,
                clock=time.perf_counter, sleep=time.sleep,
                span=null_span) -> Window:
    """Submit each planned request once its due time has come, step the
    front door while it has work, and sleep while it has none."""
    d = _Submitter(fe, pool, new_request, clock, span)
    i = 0
    with span("bench.window"):
        t0 = clock()
        t_end = t0 + seconds
        while True:
            now = clock()
            if now >= t_end:
                break
            while i < len(plan) and t0 + plan[i].due <= now:
                d.submit(plan[i], t0 + plan[i].due)
                i += 1
            if d.step():
                continue
            nxt = t0 + plan[i].due if i < len(plan) else t_end
            wait = min(nxt, t_end) - clock()
            if wait > 0:
                with span("loadgen.wait"):
                    sleep(wait)
    return Window(t0, t_end, d.records, [(p, t0 + p.due) for p in plan[i:]])


def drain(fe, win: Window, pool, *, new_request, limit_s: float,
          clock=time.perf_counter):
    """After the window: submit what was due in it and not yet handed
    over, and step until every request is answered or ``limit_s`` has
    passed.  Sets ``win.drain_end``."""
    d = _Submitter(fe, pool, new_request, clock, null_span)
    d.records = win.records
    for planned, due in win.pending:
        d.submit(planned, due)
    win.pending = []
    deadline = clock() + limit_s
    while not all(r.done for r in win.records) and clock() < deadline:
        if not fe.step():
            break
    win.drain_end = clock()


# -- arithmetic ---------------------------------------------------------------

def percentile(xs, q: float) -> float:
    """Linear interpolation between closest ranks (numpy's default)."""
    return float(np.percentile(np.asarray(xs, dtype=float), q))


def latencies_s(win: Window) -> list:
    """Due time to logits on the host, for every request of the window; a
    request never answered counts until the end of the drain."""
    end = win.drain_end if win.drain_end is not None else win.t_end
    return [(r.req.t_done if r.done else end) - r.due for r in win.records]


def queue_waits_s(win: Window) -> list:
    """Due time to the first dispatch of the request's rows."""
    end = win.drain_end if win.drain_end is not None else win.t_end
    out = []
    for r in win.records:
        t = getattr(r.req, "t_first_dispatch", None)
        out.append((t if t is not None else end) - r.due)
    return out


def rows_per_s(win: Window) -> float:
    """Rows answered inside the window over the window's length."""
    rows = sum(r.n for r in win.records
               if r.done and r.req.t_done <= win.t_end)
    return rows / (win.t_end - win.t0)


def lateness_s(win: Window) -> list:
    """How late the generator handed each request over."""
    return [r.submitted - r.due for r in win.records
            if r.submitted is not None]


class CompileCounter:
    """Counts JAX tracing and compilation events while it is active."""

    KEYS = ("/jax/core/compile/jaxpr_trace_duration",
            "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax.monitoring
        self.count = 0
        self.active = False
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, key, _secs, **_kw):
        if self.active and key in self.KEYS:
            self.count += 1
