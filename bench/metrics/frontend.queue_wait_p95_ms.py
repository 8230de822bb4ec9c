"""frontend.queue_wait_p95_ms: 95th percentile, over every request due in
the window, of the time from its due time to the front door's first
dispatch of its rows (``FrontendRequest.t_first_dispatch``)."""
import loop


def read(ctx):
    w = loop.queue_waits_s(ctx.window)
    return 1e3 * loop.percentile(w, 95) if w else None
