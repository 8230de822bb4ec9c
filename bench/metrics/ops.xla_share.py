"""ops.xla_share: share of the device's busy time, inside the traced
window, in operations that are no Pallas conv kernel: the pad and phase
copies, requantization, pooling, the classifier (device trace)."""
import devtrace


def read(ctx):
    tr = ctx.trace
    if tr is None:
        return None
    lo, hi = tr.window
    busy = devtrace.covered(devtrace.clip(tr.ops, lo, hi))
    if busy <= 0:
        return None
    conv = devtrace.covered(devtrace.clip(
        [e for e in tr.ops if devtrace.family(e)], lo, hi))
    return 100.0 * (busy - conv) / busy
