#!/usr/bin/env python3
"""Readings that the limits of the correctness check are set from.

    python3 bench/control.py --workload resnet50.offline --seeds 1 2 3 ...

For each seed, in one process on the chip: the program's readings —
the cell's served model (``run.build``, warm-up included) answering
every pool image once, through the front door, in requests of the
cell's microbatch — and the control's — the plain reference put in the
program's place and computed in int4, the precision below the int8 that
the configuration states (``forward(..., bits=4)``).  Both are compared
with the float32 reference by ``verdict.compare``, as a run compares its
answers, and printed one JSON line per seed and side.

The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import types

import numpy as np

import run
import verdict

CONTROL_BITS = 4


def _records(logits: np.ndarray) -> list:
    """One answered record per pool image."""
    return [run.loop.Record(i, 1, 0.0, types.SimpleNamespace(
        done=True, t_done=0.0, logits=logits[i:i + 1]))
        for i in range(len(logits))]


def readings(cell, seed: int, devices) -> dict:
    import jax
    b = run.build(cell, seed, devices)
    mb = cell.mix["microbatch"]
    reqs = [run.new_request(i, b.pool[o:o + mb])
            for i, o in enumerate(range(0, len(b.pool), mb))]
    b.fe.run(reqs)
    served = np.concatenate([np.asarray(r.logits) for r in reqs])
    weights, pool = b.weights, b.pool
    del b
    gc.unfreeze()
    gc.collect()
    with jax.default_device(devices[0]):
        ref = run.reference_logits(cell, weights, pool)
        fwd = jax.jit(lambda p, x: cell.ref.forward(p, x, cell.cfg,
                                                    bits=CONTROL_BITS))
        low = np.concatenate([np.asarray(fwd(weights, pool[i:i + 32]))
                              for i in range(0, len(pool), 32)])
    return {"program": verdict.compare(_records(served), ref),
            "control_int4": verdict.compare(_records(low), ref)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    cell = run.cell_from_benchmark(args.workload)
    devices = run.require_chip(cell.chips)
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    for seed in args.seeds:
        for side, nums in readings(cell, seed, devices).items():
            print(json.dumps({"workload": args.workload, "seed": seed,
                              "side": side, **nums}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
