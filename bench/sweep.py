#!/usr/bin/env python3
"""Finds the highest rate an open-loop cell sustains, once, on the chip.

    python3 bench/sweep.py --workload resnet50.server --seed 3 \\
        --seconds 10 --rates 200 300 400 500

Builds the cell's served model once, then offers its traffic mix at each
rate in turn for ``--seconds``, drains, and prints one JSON line per
rate: latency p50 and p95 from the due time, the share of requests not
answered when the window closed, and the median latency of the window's
last quarter of requests against its first quarter — a backlog that
grows all through the window shows as a ratio well above 1.  The knee
is the highest rate whose p95 stays within the latency limit and whose
backlog does not grow; the cell's traffic file fixes its rate below it.
The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys

import loop
import run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    cell = run.cell_from_benchmark(args.workload)
    if cell.mix["loop"] != "open":
        sys.exit("sweep: the cell's traffic is not an open loop")
    devices = run.require_chip(cell.chips)
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    b = run.build(cell, args.seed, devices)
    for rate in args.rates:
        mix = dict(cell.mix, rate_rps=rate)
        win = run.window(b, mix, args.seconds)
        open_at_close = sum(not r.done for r in win.records)
        loop.drain(b.fe, win, b.pool, new_request=run.new_request,
                   limit_s=run.DRAIN_LIMIT_S)
        lat = loop.latencies_s(win)
        q = max(len(lat) // 4, 1)
        print(json.dumps({
            "rate_rps": rate, "requests": len(lat),
            "p50_ms": 1e3 * loop.percentile(lat, 50),
            "p95_ms": 1e3 * loop.percentile(lat, 95),
            "open_at_close": open_at_close / max(len(lat), 1),
            "last_vs_first_quarter": (loop.percentile(lat[-q:], 50)
                                      / loop.percentile(lat[:q], 50)),
            "rows_per_s": loop.rows_per_s(win)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
