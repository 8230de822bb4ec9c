"""Reduces a profiler trace to what the per-layer metrics read.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote, with
nothing but JAX: the operations on each device (the ``XLA Ops`` line of
each ``/device:TPU:<n>`` plane), the programs they ran in (``XLA
Modules``), and the harness's own host spans (``loop.SPANS`` and
``bench.window``).  Host and device events share one clock there.

The rest works on plain ``Event`` lists, so it is tested on synthetic
traces:

* busy time is the union of the operations' intervals inside the window;
  the idle share is one minus busy over the window;
* a kernel family is told apart by the name the trace gives each Pallas
  operation, that of its jitted wrapper
  (``%conv2d_implicit_pallas.53 = ... custom-call(...)``, ``FAMILIES``);
* each idle gap on the device is put down to what the host was doing:
  the TPU runtime's host-to-device copy (``RUNTIME``) where its events
  cover the gap, else the harness span that covers most of it.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os

# kernel family -> the jitted wrapper's name, which the trace gives each
# of the family's Pallas operations
FAMILIES = {
    "conv_implicit": "conv2d_implicit_pallas",
    "conv_depthwise": "conv2d_dw_pallas",
    "conv_sparse": "conv2d_sparse_pallas",
}


@dataclasses.dataclass
class Event:
    name: str
    start: int              # ns
    end: int                # ns
    device: int = 0

    @property
    def dur(self) -> int:
        return self.end - self.start


# host events of the TPU runtime that make up a host-to-device copy: the
# host-side change into the device's layout, and the start of the transfer
RUNTIME = ("XlaLinearize", "tpu::System::TransferToDevice")
H2D = "h2d.copy"


@dataclasses.dataclass
class Trace:
    ops: list               # device operations, every device
    modules: list           # device program executions
    spans: list             # harness host spans
    window: tuple           # (start, end) ns of bench.window
    n_devices: int
    runtime: list = dataclasses.field(default_factory=list)  # H2D events


def family(ev: Event) -> str | None:
    for fam, key in FAMILIES.items():
        if key in ev.name:
            return fam
    return None


def find_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {log_dir}, "
                                f"found {paths}")
    return paths[0]


def load(path: str, span_names) -> Trace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ops, modules, spans, runtime = [], [], [], []
    window = None
    devices = 0
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:") and \
                plane.name[len("/device:TPU:"):].isdigit():
            dev = int(plane.name[len("/device:TPU:"):])
            devices += 1
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops.extend(Event(e.name, int(e.start_ns), int(e.end_ns),
                                     dev)
                               for e in line.events)
                elif line.name == "XLA Modules":
                    modules.extend(Event(e.name, int(e.start_ns),
                                         int(e.end_ns), dev)
                                   for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == "bench.window":
                        window = (int(e.start_ns), int(e.end_ns))
                    elif e.name in span_names:
                        spans.append(Event(e.name, int(e.start_ns),
                                           int(e.end_ns)))
                    elif e.name in RUNTIME:
                        runtime.append(Event(H2D, int(e.start_ns),
                                             int(e.end_ns)))
    if window is None:
        raise ValueError(f"{path}: no bench.window span")
    return Trace(ops, modules, spans, window, devices, runtime)


def clip(events, lo: int, hi: int) -> list:
    return [(max(e.start, lo), min(e.end, hi)) for e in events
            if e.end > lo and e.start < hi]


def union(intervals) -> list:
    """Merged, sorted, disjoint intervals."""
    out = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(iv) for iv in out]


def covered(intervals) -> int:
    return sum(e - s for s, e in union(intervals))


def gaps(busy: list, lo: int, hi: int) -> list:
    """Idle intervals of ``[lo, hi)`` between merged busy intervals."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


def busy_ns(tr: Trace) -> float:
    """Busy time inside the window, averaged over the devices traced."""
    lo, hi = tr.window
    per_dev = {}
    for e in tr.ops:
        per_dev.setdefault(e.device, []).append(e)
    total = sum(covered(clip(evs, lo, hi)) for evs in per_dev.values())
    return total / max(tr.n_devices, 1)


def _overlap(ivs: list, starts: list, a: int, b: int) -> int:
    """Length of [a, b) covered by merged, sorted intervals ``ivs``."""
    tot = 0
    i = max(bisect.bisect_right(starts, a) - 1, 0)
    while i < len(ivs) and ivs[i][0] < b:
        tot += max(0, min(b, ivs[i][1]) - max(a, ivs[i][0]))
        i += 1
    return tot


def attribute_gaps(tr: Trace, device: int = 0) -> dict:
    """Idle nanoseconds of one device inside the window, by what the host
    was doing: the part of each gap that the runtime's host-to-device
    copy covers goes to ``h2d.copy``, the rest to the harness span that
    overlaps the gap most (``host.other`` where none does)."""
    lo, hi = tr.window
    busy = union(clip([e for e in tr.ops if e.device == device], lo, hi))
    h2d = union(clip(tr.runtime, lo, hi))
    h2d_starts = [s for s, _ in h2d]
    spans = sorted(tr.spans, key=lambda s: s.start)
    starts = [s.start for s in spans]
    longest = max((s.dur for s in spans), default=0)
    out: dict = {}
    for gs, ge in gaps(busy, lo, hi):
        part = _overlap(h2d, h2d_starts, gs, ge)
        if part:
            out[H2D] = out.get(H2D, 0) + part
        rest = ge - gs - part
        if rest <= 0:
            continue
        best, name = 0, "host.other"
        j = bisect.bisect_left(starts, ge) - 1
        while j >= 0 and starts[j] > gs - longest:
            s = spans[j]
            ov = min(ge, s.end) - max(gs, s.start)
            if ov > best:
                best, name = ov, s.name
            j -= 1
        out[name] = out.get(name, 0) + rest
    return out


def op_label(ev: Event) -> str:
    """A readable, stable label for an operation: its kernel family, or
    the HLO instruction's name without ``%`` and its instance number
    (``%copy.409 = s8[...] copy(...)`` -> ``copy``)."""
    fam = family(ev)
    if fam is not None:
        return fam
    name = ev.name.split(" = ")[0].lstrip("%")
    base, _, num = name.rpartition(".")
    return base if base and (num.isdigit() or num == "clone") else name


def top_ops(tr: Trace, n: int = 10) -> list:
    """[[label, seconds]] of the device time inside the window, by label,
    largest first."""
    lo, hi = tr.window
    tot: dict = {}
    for e in tr.ops:
        s, t = max(e.start, lo), min(e.end, hi)
        if t > s:
            lab = op_label(e)
            tot[lab] = tot.get(lab, 0) + (t - s)
    return [[k, v / 1e9] for k, v in
            sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def family_ns(tr: Trace, modules=None) -> dict:
    """Device nanoseconds per kernel family inside the window (``other``
    for every operation of no family).  With ``modules``, only operations
    inside those program executions count."""
    lo, hi = tr.window
    ops = tr.ops
    if modules is not None:
        by_dev = _by_device(modules)
        ops = [e for e in ops if _inside(e, by_dev)]
    out: dict = {}
    for e in ops:
        s, t = max(e.start, lo), min(e.end, hi)
        if t > s:
            k = family(e) or "other"
            out[k] = out.get(k, 0) + (t - s)
    return out


def _by_device(modules) -> dict:
    out: dict = {}
    for m in sorted(modules, key=lambda m: m.start):
        starts, ends = out.setdefault(m.device, ([], []))
        starts.append(m.start)
        ends.append(m.end)
    return out


def _inside(ev: Event, by_dev: dict) -> bool:
    if ev.device not in by_dev:
        return False
    starts, ends = by_dev[ev.device]
    i = bisect.bisect_right(starts, ev.start) - 1
    return i >= 0 and ev.end <= ends[i]


def modules_inside(tr: Trace, needle: str) -> list:
    """Program executions whose name holds ``needle`` and that lie wholly
    inside the window."""
    lo, hi = tr.window
    return [m for m in tr.modules
            if needle in m.name and m.start >= lo and m.end <= hi]


def idle_percent(tr: Trace | None) -> float | None:
    """100 x (1 - busy / window), or None without a trace."""
    if tr is None:
        return None
    lo, hi = tr.window
    return 100.0 * (1.0 - busy_ns(tr) / (hi - lo))
