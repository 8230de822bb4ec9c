"""engine.mb_fill: rows injected over microbatch slots offered, from the
engine's registry counters over the window (``engine.rows_injected`` /
(``engine.mb_injected`` x microbatch)), in percent."""


def read(ctx):
    mbs = ctx.counters.get("engine.mb_injected", 0)
    if not mbs:
        return None
    rows = ctx.counters["engine.rows_injected"]
    return 100.0 * rows / (mbs * ctx.microbatch)
