"""A kernel family's share of its roofline, from a device trace.

The least time of the family's layers on one microbatch (``opcount.py``
against ``peaks.py``), times the stage programs that ran wholly inside
the traced window, over the device time of the family's kernels inside
those programs.  The least time is of the batch the kernels saw only
where every microbatch of the window was full, so the share is read
there alone; elsewhere, and where the trace holds none of the family's
kernels, there is nothing to read.
"""
from __future__ import annotations

import devtrace
import opcount
import peaks

STAGE_PROGRAM = "stage_fn"      # the engine's jitted per-stage program


def share(ctx, op: str, fam: str) -> float | None:
    tr = ctx.trace
    layers = [l for l in ctx.layers if l["op"] == op]
    if tr is None or not layers:
        return None
    mbs = ctx.counters.get("engine.mb_injected", 0)
    if not mbs or ctx.counters.get("engine.rows_injected") != \
            mbs * ctx.microbatch:
        return None
    mods = devtrace.modules_inside(tr, STAGE_PROGRAM)
    kernel_ns = devtrace.family_ns(tr, mods).get(fam, 0)
    if not mods or kernel_ns <= 0:
        return None
    pk = peaks.peak(ctx.device_kind)
    least = len(mods) * sum(opcount.least_seconds(l, ctx.microbatch, pk)
                            for l in layers)
    return 100.0 * least / (kernel_ns / 1e9)
