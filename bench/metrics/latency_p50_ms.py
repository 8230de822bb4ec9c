"""latency_p50_ms: median of the latencies that latency_p95_ms reads."""
import loop


def read(ctx):
    lat = loop.latencies_s(ctx.window)
    return 1e3 * loop.percentile(lat, 50) if lat else None
