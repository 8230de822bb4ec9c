"""latency_p95_ms: 95th percentile, over every request due in the window,
of the time from its due time to its logits on the host; a request never
answered counts until the end of the drain (host clock)."""
import loop


def read(ctx):
    lat = loop.latencies_s(ctx.window)
    return 1e3 * loop.percentile(lat, 95) if lat else None
