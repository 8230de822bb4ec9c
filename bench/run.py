#!/usr/bin/env python3
"""Runs one cell of the benchmark once, on the chip it is started on.

    python3 bench/run.py --workload resnet50.offline --seed 7 \\
        --seconds 10 --trace 0

A cell is one entry of ``workloads`` in ``BENCHMARK.json``: a
configuration (``bench/configs/<config>.json`` and its plain reference
``<config>.py``) under a traffic mix (``bench/traffic/<mix>.json``).  In
one process the run

1. refuses to go on unless JAX's first device is a TPU, the conv kernels
   lower to Mosaic, and there are as many chips as the cell asks for;
2. keeps JAX's persistent compilation cache at the checkout's
   ``.jax_cache`` (or where ``JAX_COMPILATION_CACHE_DIR`` says), every
   program in it;
3. makes float32 weights from ``--seed`` on the device in one jitted
   call, compiles them to the served form (the program's own
   ``compile_params``, jitted) and builds the front door,
   ``ResNetFrontend`` with the traffic file's ``replicas`` x ``stages``
   (1 x 1 unless it says otherwise) over the cell's chips;
4. draws the image pool from the seed and warms up exactly the
   microbatch shapes the traffic uses — all of that is ``setup_s``;
5. drives the front door for ``--seconds``, counting compilations in the
   window, with the profiler on under ``--trace 1``;
6. drains the window's requests, reads the device's peak memory, frees
   the served model, runs the plain reference over the pool and compares
   every answer with it (``verdict.py``);
7. prints the numbers compared with their limits as its last lines on
   standard error, and one JSON line as its last line on standard
   output: ``correct``, ``attempted``, ``failed``, ``metrics`` (the
   cell's ``end_to_end`` metrics, or with ``--trace 1`` its
   ``per_layer`` ones, each read by ``bench/metrics/<name>.py``),
   ``device``, with ``--trace 1`` ``breakdown``, and last ``check``.
"""
from __future__ import annotations

import os
import time

T_START = time.perf_counter()
# the TPU runtime would otherwise log under a fixed path in /tmp
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import argparse          # noqa: E402
import dataclasses       # noqa: E402
import gc                # noqa: E402
import json              # noqa: E402
import shutil            # noqa: E402
import sys               # noqa: E402
import tempfile          # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import numpy as np       # noqa: E402

import devtrace          # noqa: E402
import loop              # noqa: E402
import spec              # noqa: E402
import traffic           # noqa: E402
import verdict           # noqa: E402

DRAIN_LIMIT_S = 60.0
REF_BLOCK = 32           # images per call of the reference


@dataclasses.dataclass
class Cell:
    name: str
    cfg: dict
    ref: object              # the configuration's plain reference module
    mix: dict
    chips: int
    end_to_end: list
    per_layer: list


def cell_from_benchmark(name: str, bm: dict | None = None) -> Cell:
    bm = spec.benchmark() if bm is None else bm
    wl = spec.workload(bm, name)
    return Cell(name, spec.config(wl["config"]), spec.reference(wl["config"]),
                spec.traffic(wl["traffic"]), wl["chips"],
                spec.metrics_of(bm, "end_to_end", name),
                spec.metrics_of(bm, "per_layer", name))


@dataclasses.dataclass
class Context:
    """What a metric reader (``bench/metrics/<name>.py``) may read."""
    cell: Cell
    setup_s: float
    window: loop.Window
    counters: dict           # engine counter increments over the window
    microbatch: int
    layers: list             # the reference's layers (opcount.py)
    device_kind: str
    n_chips: int
    trace: devtrace.Trace | None = None


def require_chip(chips: int) -> list:
    """The devices to run on, or exit non-zero with no result."""
    import jax
    from repro.kernels import ops
    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"bench: no TPU: JAX's devices are "
                 f"{devices[0].platform!r}; the benchmark runs only on a "
                 f"TPU")
    if ops._mode() != "tpu":
        sys.exit(f"bench: REPRO_PALLAS={os.environ.get('REPRO_PALLAS')!r} "
                 f"would not run the Mosaic kernels; unset it")
    if len(devices) < chips:
        sys.exit(f"bench: the cell needs {chips} chips, JAX has "
                 f"{len(devices)}")
    return devices[:chips]


def seed_keys(seed: int):
    """(weights key, pool key, numpy generator of the traffic), all from
    every bit of ``seed``."""
    import jax
    if seed < 0:
        raise ValueError(f"--seed must be >= 0, got {seed}")
    ss = np.random.SeedSequence(seed)
    w = ss.spawn(3)
    keys = [jax.random.wrap_key_data(
        np.asarray(s.generate_state(2, dtype=np.uint32)),
        impl="threefry2x32") for s in w[:2]]
    return keys[0], keys[1], np.random.default_rng(w[2])


def program_config(cfg: dict):
    import importlib
    p = cfg["program"]
    return getattr(importlib.import_module(p["module"]), p["class"])(
        **p["kwargs"])


def served_params(prog_cfg, weights, mode: str, prepare: str | None = None):
    """The program's parameter tree holding the benchmark's weights, in
    the served form: the program's ``compile_params``, in one jitted
    call, after the program config's method ``prepare`` where the
    configuration names one (a branch fusion, say).  The trees must
    agree leaf for leaf."""
    import jax
    from repro import nn
    from repro.core.compiled_linear import compile_params
    abstract = jax.eval_shape(prog_cfg.init, jax.random.PRNGKey(0))
    want = [(l.shape, l.dtype) for l in jax.tree.leaves(abstract)]
    got = [(l.shape, l.dtype) for l in jax.tree.leaves(weights)]
    if (jax.tree.structure(nn.unbox(abstract)) != jax.tree.structure(weights)
            or want != got):
        raise ValueError("the reference's weights do not match the "
                         "program's parameter tree")
    boxed = jax.tree.unflatten(jax.tree.structure(abstract),
                               jax.tree.leaves(weights))
    def serve_form(p):
        if prepare:
            p = getattr(prog_cfg, prepare)(p)
        return nn.unbox(compile_params(p, mode))

    return jax.jit(serve_form)(boxed)


def warm_sizes(mix: dict) -> list:
    """Microbatch row counts the window can inject: a closed loop of
    whole microbatches only ever injects full ones."""
    mb = mix["microbatch"]
    rows = {r for r, _ in mix["size_mix"]}
    if mix["loop"] == "closed" and all(r % mb == 0 for r in rows):
        return [mb]
    return list(range(1, mb + 1))


def reference_logits(cell: Cell, weights, pool: np.ndarray) -> np.ndarray:
    import jax
    fwd = jax.jit(lambda p, x: cell.ref.forward(p, x, cell.cfg))
    return np.concatenate([np.asarray(fwd(weights, pool[i:i + REF_BLOCK]))
                           for i in range(0, len(pool), REF_BLOCK)])


def _counters(fe) -> dict:
    """The engines' counters, summed over the replicas."""
    out: dict = {}
    for eng in fe.replicas:
        for k, v in eng.snapshot().items():
            if isinstance(v, (int, float)):
                out[k] = out.get(k, 0) + v
    return out


def new_request(rid, images):
    from repro.serving.frontend import FrontendRequest
    return FrontendRequest(rid=rid, images=images)


@dataclasses.dataclass
class Built:
    """The served model and the run's inputs, made from the seed."""
    fe: object               # the program's ResNetFrontend
    weights: object          # float32 weights, on the device
    pool: np.ndarray         # the image pool, host memory
    rng: np.random.Generator  # the traffic's generator


def build(cell: Cell, seed: int, devices) -> Built:
    """Weights, served form and front door, image pool, and the warm-up
    of every microbatch shape the traffic uses."""
    import jax
    from repro.serving.frontend import ResNetFrontend
    mix, cfg = cell.mix, cell.cfg
    k_w, k_pool, rng = seed_keys(seed)
    with jax.default_device(devices[0]):
        weights = jax.block_until_ready(
            jax.jit(lambda k: cell.ref.init(k, cfg))(k_w))
        prog_cfg = program_config(cfg)
        params = served_params(prog_cfg, weights, cfg["serve_mode"],
                               cfg["program"].get("prepare"))
        fe = ResNetFrontend(prog_cfg, params, mode=cfg["serve_mode"],
                            n_replicas=mix.get("replicas", 1),
                            n_stages=mix.get("stages", 1),
                            microbatch=mix["microbatch"],
                            devices=list(devices))
        pool = traffic.image_pool(k_pool, mix, cfg["in_hw"])
    for i, n in enumerate(warm_sizes(mix)):
        fe.run([new_request(-1 - i, pool[:n])])
    # what set-up leaves alive stays alive: collect its garbage now and
    # keep the survivors out of every later collection, so that the
    # window's collections scan the window's objects only
    gc.collect()
    gc.freeze()
    return Built(fe, weights, pool, rng)


def window(b: Built, mix: dict, seconds: float, *, span=loop.null_span,
           clock=time.perf_counter) -> loop.Window:
    """One measured window of the mix's loop."""
    if mix["loop"] == "closed":
        return loop.closed_window(b.fe, mix, traffic.closed_stream(mix, b.rng),
                                  b.pool, seconds, new_request=new_request,
                                  clock=clock, span=span)
    return loop.open_window(b.fe, traffic.open_plan(mix, seconds, b.rng),
                            b.pool, seconds, new_request=new_request,
                            clock=clock, span=span)


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, devices,
             t_start: float = T_START) -> dict:
    import jax

    clock = time.perf_counter
    cfg = cell.cfg
    b = build(cell, seed, devices)
    fe, pool = b.fe, b.pool
    counter = loop.CompileCounter()
    span = jax.profiler.TraceAnnotation if trace else loop.null_span
    log_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    before = _counters(fe)
    setup_s = clock() - t_start
    try:
        if trace:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0     # host spans, no Python calls
            jax.profiler.start_trace(log_dir, profiler_options=opts)
        counter.active = True
        win = window(b, cell.mix, seconds, span=span, clock=clock)
        counter.active = False
        win.compiles = counter.count
        if trace:
            jax.profiler.stop_trace()
        after = _counters(fe)
        loop.drain(fe, win, pool, new_request=new_request,
                   limit_s=DRAIN_LIMIT_S, clock=clock)
        tr = None
        if trace:
            tr = devtrace.load(devtrace.find_xplane(log_dir),
                               set(loop.SPANS))
    finally:
        if log_dir is not None:
            shutil.rmtree(log_dir, ignore_errors=True)
    mem_peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in devices)
    weights = b.weights
    del fe, b
    gc.unfreeze()
    gc.collect()
    with jax.default_device(devices[0]):
        ref = reference_logits(cell, weights, pool)
    numbers = verdict.compare(win.records, ref)
    correct, checked = verdict.judge(numbers, cfg["check"])

    kind = devices[0].device_kind
    ctx = Context(cell=cell, setup_s=setup_s, window=win,
                  counters={k: after.get(k, 0) - before.get(k, 0)
                            for k in after},
                  microbatch=cell.mix["microbatch"], layers=cell.ref.layers(cfg),
                  device_kind=kind, n_chips=len(devices), trace=tr)
    entries = cell.per_layer if trace else cell.end_to_end
    metrics = {}
    for m in entries:
        v = spec.metric_reader(m["name"])(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": int(mem_peak)}
    result = {"correct": correct, "attempted": len(win.records),
              "failed": numbers["unanswered"], "metrics": metrics,
              "device": device}
    late = loop.lateness_s(win)
    notes = {"compiles_in_window": win.compiles,
             "requests": len(win.records),
             "generator_late_p99_ms": (1e3 * loop.percentile(late, 99)
                                       if late else None),
             "setup_s": setup_s}
    if tr is not None:
        device["busy_s"] = devtrace.busy_ns(tr) / 1e9
        device["window_s"] = (tr.window[1] - tr.window[0]) / 1e9
        gaps = devtrace.attribute_gaps(tr)
        result["breakdown"] = {
            "device_ops": devtrace.top_ops(tr),
            "idle_gaps": [[k, v / 1e9] for k, v in sorted(
                gaps.items(), key=lambda kv: -kv[1])[:10]]}
    result["check"] = checked
    return {"result": result, "notes": notes}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = cell_from_benchmark(args.workload)
    devices = require_chip(cell.chips)

    import jax
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    out = run_cell(cell, args.seed, args.seconds, bool(args.trace), devices)
    res, notes = out["result"], out["notes"]
    print(f"bench: {args.workload} seed {args.seed}: " + ", ".join(
        f"{k} {v}" for k, v in notes.items()), file=sys.stderr)
    for name, c in res["check"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
