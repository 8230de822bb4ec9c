#!/usr/bin/env python3
"""Bring-up check on the chip: the paper's ResNet50 served end to end.

    python3 chip_smoke.py [--seed 0]         # one TPU chip
    python3 chip_smoke.py --four-chips       # four chips, pipeline + fleet

Builds ResNet50 at full size (``configs/resnet50_compiled``: width 1.0,
224x224 input, 1000 classes) with random weights from ``--seed``, and
serves it in ``int8`` through the normal stack — ``ResNetFrontend`` ->
``PipelineEngine`` -> ``ConvPipeline`` -> ``ops.conv2d`` -> the Pallas
conv kernels compiled by Mosaic.  One warm-up wave, then 4 requests of
2 images each at microbatch 2, the paper's batch.

The served logits must be finite, bit-identical to
``serving.pipeline.reference_logits`` on the same chip (the numerics
contract, DESIGN.md §8-9), and within a relative L2 error of 0.15 of a
plain float32 forward of the uncompiled weights (int7 weights and
per-row int8 activations; the bound of tests/test_conv.py).
``sparse_cfmm`` cannot be compiled for the chip yet; the script checks
that it is refused with ``ops.UnsupportedOnTPU`` rather than served on
another path.

``--four-chips`` runs only the multi-chip phase: the same model as a
4-stage layer pipeline (1 replica x 4 stages) and as a 2 replicas x 2
stages fleet, each stage on its own chip, both compared exactly with
``reference_logits`` on chip 0.

The script refuses to run (non-zero exit, no result) unless JAX's first
device is a TPU and the kernels lower to Mosaic (``REPRO_PALLAS`` unset,
``auto`` or ``tpu``).  Its last line of output is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""
from __future__ import annotations

import argparse
import contextlib
import importlib.metadata
import json
import os
import sys
import time

T_START = time.perf_counter()

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

REL_ERR_BOUND = 0.15        # vs the float32 forward (tests/test_conv.py)
MICROBATCH = 2              # the paper's batch
N_REQUESTS, ROWS = 4, 2     # the measured wave: 4 requests x 2 images


def check(ok: bool, what: str):
    """A failed phase exits non-zero (and not under ``python -O`` too,
    unlike ``assert``)."""
    if not ok:
        sys.exit(f"chip_smoke: FAILED: {what}")


def require_tpu(n_devices: int = 1):
    """Exit non-zero unless JAX sees ``n_devices`` TPUs and the conv
    kernels lower to Mosaic.  Returns the device list."""
    import jax
    from repro.kernels import ops
    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke: no TPU found — JAX's devices are "
                 f"{devices[0].platform!r}; this check runs only on a TPU")
    if ops._mode() != "tpu":
        sys.exit(f"chip_smoke: REPRO_PALLAS={os.environ['REPRO_PALLAS']!r} "
                 f"would not run the Mosaic kernels; unset it")
    if len(devices) < n_devices:
        sys.exit(f"chip_smoke: needs {n_devices} TPU chips, found "
                 f"{len(devices)}")
    return devices


@contextlib.contextmanager
def timed(what: str):
    """Print the host-clock seconds of one phase (set-up, not a metric)."""
    t0 = time.perf_counter()
    try:
        yield
    finally:
        print(f"[time] {what}: {time.perf_counter() - t0:.3f} s host clock")


def _cache_state(path: str) -> str:
    files = [os.path.join(d, f) for d, _, fs in os.walk(path) for f in fs]
    size = sum(os.path.getsize(f) for f in files)
    return f"{len(files)} files, {size / 2**20:.1f} MiB"


def _version(pkg: str) -> str:
    try:
        return importlib.metadata.version(pkg)
    except importlib.metadata.PackageNotFoundError:
        return "not installed"


def _wave(rng, cfg, rid0: int):
    from repro.serving.frontend import FrontendRequest
    return [FrontendRequest(rid=rid0 + i, images=rng.standard_normal(
        (ROWS, cfg.in_hw, cfg.in_hw, 3)).astype("float32"))
        for i in range(N_REQUESTS)]


def serve(fe, cfg, rng, label: str):
    """Warm up, then serve one measured wave; returns (images, logits)."""
    import numpy as np
    t0 = time.perf_counter()
    fe.run(_wave(rng, cfg, rid0=1000))
    t_warm = time.perf_counter() - t0
    reqs = _wave(rng, cfg, rid0=0)
    t0 = time.perf_counter()
    fe.run(reqs)
    t_wave = time.perf_counter() - t0
    check(all(r.done for r in reqs), f"{label}: requests left undone")
    print(f"[{label}] warm-up wave (compiles included) {t_warm:.3f} s; "
          f"served {N_REQUESTS} requests x {ROWS} images in {t_wave:.6f} s "
          f"host wall time (not a metric)")
    return (np.concatenate([r.images for r in reqs]),
            np.concatenate([r.logits for r in reqs]))


def check_exact(label: str, got, want):
    import numpy as np
    check(np.isfinite(got).all(), f"{label}: non-finite logits")
    n_diff = int(np.sum(got != want))
    print(f"[{label}] bit-identical to reference_logits: {n_diff == 0} "
          f"({n_diff} of {got.size} logits differ)")
    check(n_diff == 0, f"{label}: logits differ from reference_logits")


def float32_forward(params, cfg, x):
    import jax
    import jax.numpy as jnp
    from repro import nn
    from repro.models import resnet
    fn = jax.jit(lambda p, v: resnet.apply(p, v, cfg))
    with jax.default_matmul_precision("highest"):
        return jnp.asarray(fn(nn.unbox(params), x))


def compare_float32(label: str, got, want):
    import numpy as np
    rel = float(np.linalg.norm(got - want) / np.linalg.norm(want))
    top1 = float(np.mean(np.argmax(got, -1) == np.argmax(want, -1)))
    print(f"[{label}] vs float32 forward: rel L2 error {rel:.6f} "
          f"(bound {REL_ERR_BOUND}), top-1 agreement {top1:.3f}")
    return rel


def one_chip(cfg, params, seed: int):
    import jax
    import numpy as np
    from repro.kernels import ops
    from repro.serving.frontend import ResNetFrontend
    from repro.serving.pipeline import reference_logits

    rng = np.random.default_rng(seed)
    t0 = time.perf_counter()
    fe = ResNetFrontend(cfg, params, mode="int8", n_replicas=1, n_stages=1,
                        microbatch=MICROBATCH)
    print(f"[int8] weights compiled and placed in "
          f"{time.perf_counter() - t0:.3f} s; kernels lower as "
          f"{ops._mode()!r}")
    x, served = serve(fe, cfg, rng, "int8")
    t0 = time.perf_counter()
    ref = np.asarray(reference_logits(fe.params, cfg, jax.numpy.asarray(x),
                                      MICROBATCH))
    print(f"[int8] reference_logits (compiles included) "
          f"{time.perf_counter() - t0:.3f} s")
    check_exact("int8", served, ref)
    with timed("float32 forward (compile included)"):
        want = np.asarray(float32_forward(params, cfg, x))
    rel = compare_float32("int8", served, want)
    check(rel <= REL_ERR_BOUND, f"int8: rel L2 error {rel} > bound")

    try:
        with timed("sparse_cfmm up to its refusal"):
            ResNetFrontend(cfg, params, mode="sparse_cfmm", n_replicas=1,
                           n_stages=1, microbatch=MICROBATCH
                           ).run_batch(x[:2])
    except ops.UnsupportedOnTPU as e:
        print(f"[sparse_cfmm] not run: refused on the chip as expected "
              f"({e})")
    else:
        check(False, "sparse_cfmm served on a TPU: it must be refused "
                     "until its bitmap expand compiles")


def four_chips(cfg, params, seed: int, devices):
    import jax
    import numpy as np
    from repro.serving.frontend import ResNetFrontend
    from repro.serving.pipeline import reference_logits

    rng = np.random.default_rng(seed)
    for label, n_rep, n_st in (("4-stage pipeline", 1, 4),
                               ("2x2 fleet", 2, 2)):
        fe = ResNetFrontend(cfg, params, mode="int8", n_replicas=n_rep,
                            n_stages=n_st, microbatch=MICROBATCH)
        placed = [str(st.device) for eng in fe.replicas
                  for st in eng.pipe.stages]
        print(f"[{label}] stages on {placed}")
        check(len(set(placed)) == 4, f"{label}: stages share a device")
        x, served = serve(fe, cfg, rng, label)
        with jax.default_device(devices[0]):
            ref = np.asarray(reference_logits(
                fe.params, cfg, jax.device_put(x, devices[0]), MICROBATCH))
        check_exact(label, served, ref)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and images")
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip pipeline and fleet phase")
    args = ap.parse_args(argv)
    n_dev = 4 if args.four_chips else 1
    devices = require_tpu(n_dev)
    print(f"[time] process start to devices found: "
          f"{time.perf_counter() - T_START:.3f} s host clock")

    import jax
    from repro.configs.resnet50_compiled import CONFIG as cfg
    from repro.launch.compile_cache import enable_compile_cache
    from repro.models import resnet

    cache = enable_compile_cache()
    print(f"[device] {devices[0].device_kind} x {len(devices)} "
          f"({devices[0].platform}); jax {jax.__version__}, libtpu "
          f"{_version('libtpu')}")
    print(f"[cache] compile cache {cache}: {_cache_state(cache)} at start")
    print(f"[model] ResNet50 width {cfg.width_mult}, {cfg.in_hw}x"
          f"{cfg.in_hw} input, {cfg.num_classes} classes, seed {args.seed}")
    with timed("resnet.init"):
        params = jax.block_until_ready(
            resnet.init(jax.random.PRNGKey(args.seed), cfg))
    if args.four_chips:
        four_chips(cfg, params, args.seed, devices)
    else:
        one_chip(cfg, params, args.seed)
    print(f"[time] whole script: {time.perf_counter() - T_START:.3f} s "
          f"host clock; compile cache {_cache_state(cache)} at the end")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
