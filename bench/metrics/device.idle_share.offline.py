"""device.idle_share.offline: share of the traced window in which no
operation ran on the device, averaged over the chips (device trace)."""
import devtrace


def read(ctx):
    return devtrace.idle_percent(ctx.trace)
