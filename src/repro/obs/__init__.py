"""``repro.obs`` — zero-dependency telemetry for the serving stack.

Three pillars (DESIGN.md §11):

* ``trace``   — request + stage-tick span tracing, Chrome trace-event
  export (Perfetto-loadable), schema validator; and the always-on
  ``host_span`` profiler spans of the serving host step.
* ``metrics`` — Counter/Gauge/Reservoir registry with a
  wave/life scope split; component ``stats()`` dicts are thin views
  over ``MetricsRegistry.snapshot()``.
* ``sparsity``— post-ReLU activation zero-fraction profiling fed by the
  conv lowerings' epilogues.

``Telemetry`` is the bundle the serving stack threads through
(frontend → engine → pipeline → kernels).  It is **off by default**
(``telemetry=None`` everywhere): the instrumented code guards every
hook behind one ``is None`` check, so the off path costs a branch.
The ``host_span`` spans and their nanosecond counters are not part of
it: they cost a few microseconds a span and show only in a profiler
session.
"""
from __future__ import annotations

import time

from repro.obs.metrics import (Counter, Gauge, HighWater, MetricsRegistry,
                               Reservoir, percentile)
from repro.obs.sparsity import SparsityProfiler
from repro.obs.trace import Trace, validate_chrome_trace

__all__ = [
    "Counter", "Gauge", "HighWater", "MetricsRegistry",
    "Reservoir", "percentile", "SparsityProfiler", "Trace",
    "validate_chrome_trace", "Telemetry",
]


class Telemetry:
    """What a serving component receives when observability is on.

    ``trace``: ``True`` (fresh buffer), a ``Trace`` instance, or
    ``None``.  ``sparsity_groups``: ``coarse_in`` lane-group size to
    profile activation sparsity at (``None`` = profiling off — the
    model compiles its unprofiled stage programs).  ``clock`` must be
    the same callable the frontend schedules with, so spans and SLO
    arithmetic share a time axis.
    """

    def __init__(self, trace=None, sparsity_groups=None,
                 clock=time.perf_counter, trace_capacity=200_000):
        if trace is True:
            trace = Trace(capacity=trace_capacity, clock=clock)
        assert trace is None or isinstance(trace, Trace), trace
        self.trace = trace
        self.sparsity = (None if sparsity_groups is None
                         else SparsityProfiler(groups=sparsity_groups))
        self.clock = clock

    @property
    def profiled(self) -> bool:
        """True when stage programs must emit sparsity aux."""
        return self.sparsity is not None
