"""Pipeline-parallel ResNet serving driver (the executable Fig 7).

  PYTHONPATH=src python -m repro.launch.serve_pipeline \
      --stages 4 --microbatch 2 --mode sparse_cfmm --width 0.25 --hw 32

Plans stages (MAC-balanced, or from the Fig 7 chip packing with
--from-partition), places each stage's constant weights on its own local
device (fan a CPU host out with
XLA_FLAGS=--xla_force_host_platform_device_count=N), and streams
microbatched requests through the rotating schedule.
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.core import partition
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import pipeline_stage_devices
from repro.models import resnet
from repro.serving.pipeline import PipelineEngine, PipelineRequest


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--width", type=float, default=0.25)
    ap.add_argument("--hw", type=int, default=32)
    ap.add_argument("--mode", default="int8",
                    choices=("int8", "cfmm", "sparse_cfmm", "bitserial"))
    ap.add_argument("--sparsity", type=float, default=0.8)
    ap.add_argument("--stages", type=int, default=2)
    ap.add_argument("--microbatch", type=int, default=2)
    ap.add_argument("--images", type=int, default=16)
    ap.add_argument("--from-partition", action="store_true",
                    help="stage map from the Fig 7 chip packing "
                         "(re-balanced to --stages) instead of MACs")
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = resnet.ResNetConfig(width_mult=args.width, num_classes=100,
                              in_hw=args.hw)
    params = resnet.init(jax.random.PRNGKey(0), cfg)
    plan = None
    if args.from_partition:
        blocks = resnet.conv_blocks_for(cfg)
        plan = partition.solve_max_throughput(blocks).stage_plans(
            blocks, args.stages)
    devices = pipeline_stage_devices(args.stages)
    engine = PipelineEngine(cfg, params, mode=args.mode,
                            sparsity=args.sparsity, n_stages=args.stages,
                            plan=plan, microbatch=args.microbatch,
                            devices=devices)
    rng = np.random.RandomState(0)
    reqs = [PipelineRequest(rid=i, images=rng.randn(
        args.images // 2, args.hw, args.hw, 3).astype(np.float32))
            for i in range(2)]
    engine.run(reqs)                       # warmup (compiles every stage)
    for r in reqs:
        engine.submit(r)
    t0 = time.time()
    while engine.step():
        pass
    dt = time.time() - t0
    st = engine.stats()
    n_img = sum(len(r.images) for r in reqs)
    print(f"[pipeline] {st['n_stages']} stages on "
          f"{len(set(st['stage_devices']))} devices, microbatch "
          f"{st['microbatch']}: {n_img} images in {dt:.2f}s "
          f"({n_img / dt:.1f} im/s wall), bubble "
          f"{st['bubble_fraction']:.2f}")
    for s, blocks_ in enumerate(st["stage_blocks"]):
        w = st["stage_weight_bytes"][s]
        print(f"  stage {s}: blocks {blocks_[0]}..{blocks_[-1]} "
              f"({w / 1e3:.0f} kB resident) on {st['stage_devices'][s]}")
    for e, b in enumerate(st["edge_bytes"]):
        print(f"  edge {e}->{e + 1}: {b['int8_bytes']} B int8 / microbatch "
              f"(+{b['meta_bytes']} B scale), planned "
              f"{st['planned_link_bytes'][e] * st['microbatch']} B")
    return engine


if __name__ == "__main__":
    main()
