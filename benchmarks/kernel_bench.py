"""Kernel micro-benchmarks: the compiled-matmul dataflows.

CPU wall-times are sanity signals only (this container has one core); the
meaningful numbers are the analytic TPU-side effective-bandwidth /
effective-TOPs models, which mirror the paper's "effective TOPs"
accounting (sparsity credited as useful work).
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import compiled_linear as cl
from repro.core.quantize import quantize_int7
from repro.kernels import ops
from repro.roofline.analysis import HBM_BW, PEAK_BF16, PEAK_INT8


def _time(fn, *args, iters=5):
    # one warmup call (jax.block_until_ready handles tuples/pytrees; the
    # old tuple special-case re-ran fn a second time and skewed jit-cache
    # warmup)
    jax.block_until_ready(fn(*args))
    t0 = time.time()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.time() - t0) / iters


# ---------------------------------------------------------------------------
# Convolution: fused implicit-GEMM vs the materialized-im2col baseline
# ---------------------------------------------------------------------------

def _conv_baseline(x, codes, w_scale, gamma, beta, sc, k, stride):
    """The pre-refactor conv chain: materialize f32 im2col patches in HBM,
    dynamic-quantize them, matmul, then separate Collector ops."""
    patches = jax.lax.conv_general_dilated_patches(
        x, (k, k), (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    q, s_x = cl.act_quant(patches)
    acc = jax.lax.dot_general(q, codes,
                              dimension_numbers=(((3,), (0,)), ((), ())),
                              preferred_element_type=jnp.int32)
    y = acc.astype(jnp.float32) * (s_x * w_scale.reshape(1, -1))
    y = y * gamma + beta + sc
    return jax.nn.relu(y)


def conv_traffic_bytes(hw, c_in, c_out, k, stride, fused, quant_out=False):
    """Analytic per-image HBM *activation* traffic (weights excluded — both
    paths stream the same constant codes).

    Baseline: f32 input read, f32 patch tensor write+read (the k*k-inflated
    im2col buffer), int8 requant write+read, f32 accumulator write, and one
    fused elementwise Collector pass (read y + shortcut, write y).
    Fused:    int8 input read, shortcut read, one f32 (or int8 with the
    quantization-domain pass) output write.
    """
    ho = wo = -(-hw // stride)
    m, patch = ho * wo, c_in * k * k
    out_f32, out_int8 = 4 * m * c_out, m * c_out
    if fused:
        read = hw * hw * c_in + out_f32          # int8 input + shortcut
        write = out_int8 if quant_out else out_f32
        return read + write
    read = (4 * hw * hw * c_in        # f32 input
            + 4 * m * patch           # patches back in for act_quant
            + m * patch               # int8 patches into the matmul
            + out_f32 + out_f32)      # y + shortcut into Collector ops
    write = (4 * m * patch            # materialized f32 patch tensor
             + m * patch              # int8 requantized patches
             + out_f32                # matmul accumulator
             + out_f32)               # Collector output
    return read + write


def _tiled_conv_report(full=False):
    """Row-strip tiling: per-grid-cell VMEM working set (analytic, from
    the strip planner at the real ResNet50 geometries — the 224x224 stem
    and conv2_x) and measured tiled-vs-untiled wall time.

    The VMEM numbers are exact bookkeeping (kernels/tiling.py), not
    timing: whole-image residency = the pre-tiling kernel's cell (padded
    image + weight tile + full-image acc/y rows) vs the planned strip's
    cell.  Wall time compares the strip-looped lowering against the
    untiled one on the conv2_x-shaped jnp path.
    """
    from repro.kernels import ref as kref
    from repro.kernels import tiling

    # (name, hw, c_in, c_out, k, stride) — Table I geometries
    geoms = [("stem_224_k7s2", 224, 3, 64, 7, 2),
             ("conv2_x_56_k3s1", 56, 256, 256, 3, 1)]
    report = {"vmem_budget_bytes": tiling.DEFAULT_VMEM_BUDGET, "layers": {}}
    print(" row-strip tiled conv: per-grid-cell VMEM working set "
          f"(budget {tiling.DEFAULT_VMEM_BUDGET >> 10} kB):")
    for name, hw, c_in, c_out, k, stride in geoms:
        _, _, h_out = kref.same_pads(hw, k, stride)
        bn, _ = ops._tile_pad(c_out, 128)  # the tile the kernel launches
        weight_bytes = tiling.vmem_bytes((k * k, c_in, bn), 1)
        kw = dict(k=k, stride=stride, h_out=h_out, w_out=h_out,
                  c_in=c_in, bn=bn, weight_bytes=weight_bytes)
        tiled = tiling.plan_strips(**kw)
        whole = tiling.plan_strips(**kw, strip_h=h_out)
        row = {
            "strip_h": tiled.strip_h, "n_strips": tiled.n_strips,
            "slab_h": tiled.slab_h,
            "x_vmem_bytes": {"whole_image": whole.x_bytes,
                             "strip": tiled.x_bytes},
            "cell_vmem_bytes": {"whole_image": whole.cell_bytes,
                                "strip": tiled.cell_bytes},
            "x_vmem_ratio": whole.x_bytes / tiled.x_bytes,
            "cell_vmem_ratio": whole.cell_bytes / tiled.cell_bytes,
        }
        report["layers"][name] = row
        print(f"   {name:16s} strip_h={tiled.strip_h:3d} "
              f"({tiled.n_strips} strips): x slab "
              f"{whole.x_bytes / 1e3:7.1f} -> {tiled.x_bytes / 1e3:7.1f} kB "
              f"({row['x_vmem_ratio']:.1f}x), cell "
              f"{whole.cell_bytes / 1e6:5.2f} -> "
              f"{tiled.cell_bytes / 1e6:5.2f} MB "
              f"({row['cell_vmem_ratio']:.1f}x)")
    stem = report["layers"]["stem_224_k7s2"]
    assert stem["x_vmem_ratio"] >= 4 and stem["cell_vmem_ratio"] >= 4, stem

    # wall time: tiled vs untiled on a conv2_x-shaped layer
    N, hw, c, k = (2, 56, 256, 3) if full else (1, 28, 128, 3)
    key = jax.random.PRNGKey(1)
    x = jax.random.randint(key, (N, hw, hw, c), -127, 128, jnp.int8)
    qt = quantize_int7(
        jax.random.normal(jax.random.fold_in(key, 1), (c * k * k, c)) * 0.05)
    kw = dict(x_scale=0.02, w_scale=qt.scale.reshape(-1), relu=True)
    strip_h = max(1, hw // 4)
    mk_untiled = lambda: jax.jit(lambda a: ops.conv2d(a, qt.values, k, 1,
                                                      **kw))
    mk_tiled = lambda: jax.jit(lambda a: ops.conv2d(a, qt.values, k, 1,
                                                    strip_h=strip_h, **kw))
    np.testing.assert_array_equal(np.asarray(mk_untiled()(x)),
                                  np.asarray(mk_tiled()(x)))
    # best-of over two FRESH jit instances each: on this single-core
    # container the first executable instance after other bench sections
    # measures up to ~2x slow (allocator warmup), while re-jits of the
    # identical program are steady — min over fresh instances reports the
    # steady state
    t_u = min(_time(mk_untiled(), x), _time(mk_untiled(), x))
    t_t = min(_time(mk_tiled(), x), _time(mk_tiled(), x))
    report["walltime"] = {
        "layer": f"{hw}x{hw}x{c} k{k}s1 (batch {N})", "strip_h": strip_h,
        "cpu_ms": {"untiled": t_u * 1e3, "tiled": t_t * 1e3},
        "tiled_over_untiled": t_t / t_u,
    }
    print(f"   conv2_x-shaped walltime ({hw}x{hw}x{c}, strip_h={strip_h}): "
          f"untiled {t_u * 1e3:.2f} ms vs tiled {t_t * 1e3:.2f} ms "
          f"({t_t / t_u:.2f}x); bit-identical outputs")
    return report


def run_conv(full=False):
    """Fused implicit-GEMM conv vs materialized im2col + separate epilogue:
    CPU wall-time (jnp lowerings of both), the analytic HBM activation-
    traffic model, and the row-strip tiling VMEM/walltime report.
    Persisted by benchmarks/run.py to BENCH_conv.json."""
    N, hw, c, k = (2, 56, 256, 3) if full else (1, 28, 128, 3)
    stride = 1
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (N, hw, hw, c)) * 0.5
    w = jax.random.normal(jax.random.fold_in(key, 1), (c * k * k, c)) * 0.05
    qt = quantize_int7(w)
    gamma = jax.random.normal(jax.random.fold_in(key, 2), (c,))
    beta = jax.random.normal(jax.random.fold_in(key, 3), (c,))
    sc = jax.random.normal(jax.random.fold_in(key, 4), (N, hw, hw, c))

    baseline = jax.jit(lambda a, s: _conv_baseline(
        a, qt.values, qt.scale.reshape(-1), gamma, beta, s, k, stride))

    def _fused(a, s):
        q, s_x = cl.act_quant(a)
        return ops.conv2d(q, qt.values, k, stride, x_scale=s_x,
                          w_scale=qt.scale.reshape(-1), gamma=gamma,
                          beta=beta, shortcut=s, relu=True)

    fused = jax.jit(_fused)
    t_base = _time(baseline, x, sc)
    t_fused = _time(fused, x, sc)
    layer = f"{hw}x{hw}x{c} k{k}s{stride}"
    print(f" conv {layer} (batch {N}) CPU-lowering walltime:")
    print(f"   im2col + separate epilogue {t_base * 1e3:8.2f} ms")
    print(f"   fused implicit-GEMM        {t_fused * 1e3:8.2f} ms "
          f"({t_base / t_fused:.2f}x)")

    traffic = {}
    for kk in (1, 3, 7):
        b = conv_traffic_bytes(hw, c, c, kk, stride, fused=False)
        f = conv_traffic_bytes(hw, c, c, kk, stride, fused=True)
        fq = conv_traffic_bytes(hw, c, c, kk, stride, fused=True,
                                quant_out=True)
        traffic[f"k{kk}"] = {"baseline": b, "fused_f32": f,
                             "fused_int8": fq, "ratio_f32": b / f,
                             "ratio_int8": b / fq}
        print(f"   k={kk} HBM activation traffic/image: baseline "
              f"{b / 1e6:6.2f} MB vs fused {f / 1e6:6.2f} MB "
              f"({b / f:5.1f}x; {b / fq:5.1f}x with int8 quant-domain out)")
    assert traffic["k3"]["ratio_f32"] >= 5.0, traffic["k3"]
    return {
        "layer": layer, "batch": N,
        "cpu_ms": {"im2col_baseline": t_base * 1e3,
                   "fused_implicit_gemm": t_fused * 1e3},
        "cpu_speedup": t_base / t_fused,
        "hbm_activation_traffic": traffic,
        "tiled": _tiled_conv_report(full),
    }


def run_sparse_conv(full=False):
    """Bitmap-native sparse conv vs the dense-codes implicit-GEMM conv:
    CPU wall-time (jnp lowerings), bit-identity, and the analytic HBM
    *weight* traffic — the (1-s)*8 + 1 bits/param win carried into the
    path that dominates ResNet50.  Persisted to BENCH_sparse_conv.json."""
    from repro import nn
    s = 0.8
    # (layer, c_in, c_out, k, hw): ResNet50 geometries incl. the K=147 stem
    layers = ([("conv2_x_b 3x3", 256, 256, 3, 56), ("stem 7x7", 3, 64, 7, 56)]
              if full else
              [("conv2_x_b 3x3", 128, 128, 3, 28), ("stem 7x7", 3, 64, 7, 28)])
    key = jax.random.PRNGKey(0)
    out = {"sparsity": s, "layers": {}}
    print(f" sparse conv weight traffic at s={s} "
          f"(packed (1-s)*8+1 = {(1 - s) * 8 + 1:.1f} bits/param):")
    for name, c_in, c_out, k, hw in layers:
        p = {"w": nn.conv_param(key, c_in, c_out, k, 1,
                                ("conv_in", "conv_out"))}
        w = nn.unbox(cl.compile_params(p, mode="sparse_cfmm",
                                       sparsity=s))["w"]
        codes = cl.packed_codes(w)
        x = jax.random.randint(jax.random.fold_in(key, 1),
                               (1, hw, hw, c_in), -127, 128, jnp.int8)
        kw = dict(x_scale=0.02, w_scale=w["scale"].reshape(-1), relu=True)
        packed_fn = jax.jit(lambda a: ops.conv2d(
            a, (w["bitmap"], w["values"]), k, 1, **kw))
        dense_fn = jax.jit(lambda a: ops.conv2d(a, codes, k, 1, **kw))
        np.testing.assert_array_equal(np.asarray(packed_fn(x)),
                                      np.asarray(dense_fn(x)))
        t_packed, t_dense = _time(packed_fn, x), _time(dense_fn, x)
        bytes_dense = codes.size                     # int8 codes, 1 B/param
        bytes_packed = int(w["bitmap"].size + w["values"].size)
        ratio = bytes_packed / bytes_dense
        out["layers"][name] = {
            "geometry": f"{c_in}->{c_out} k{k} {hw}x{hw}",
            "weight_bytes_dense_codes": int(bytes_dense),
            "weight_bytes_packed": bytes_packed,
            "ratio_packed_vs_dense": ratio,
            "bits_per_param": bytes_packed * 8 / (c_in * k * k * c_out),
            "cpu_ms": {"dense_codes": t_dense * 1e3,
                       "bitmap_native": t_packed * 1e3},
        }
        print(f"   {name:14s} weights {bytes_dense / 1e3:7.1f} kB dense -> "
              f"{bytes_packed / 1e3:7.1f} kB packed ({ratio:.3f}x, "
              f"{out['layers'][name]['bits_per_param']:.2f} b/param); "
              f"bit-identical outputs")
    r3 = out["layers"][layers[0][0]]["ratio_packed_vs_dense"]
    assert r3 <= 0.35, out    # the 2.6/8 = 0.325 target + keep_k rounding
    return out


def run(full=False):
    K, N = (4096, 4096) if full else (2048, 1024)
    M_decode = 8
    key = jax.random.PRNGKey(0)
    w = jax.random.normal(key, (K, N)) * 0.05
    qt = quantize_int7(w)
    keep = K // 5 // 8 * 8
    codes = cl.balanced_prune_codes(w, keep).values
    bitmap, values = cl.bitmap_pack(codes, keep)
    x = jax.random.randint(key, (M_decode, K), -127, 128, jnp.int8)
    xf = jax.random.normal(key, (M_decode, K), jnp.bfloat16)

    dense = jax.jit(lambda a, b: a @ b)
    int8mm = jax.jit(lambda a, b: ops.cfmm_matmul(a, b))
    sparse = jax.jit(lambda a, b, v: ops.sparse_cfmm_matmul(a, b, v))

    t_dense = _time(dense, xf, w.astype(jnp.bfloat16))
    t_int8 = _time(int8mm, x, qt.values)
    t_sparse = _time(sparse, x, bitmap, values)
    print(f" decode matvec (M={M_decode}, {K}x{N}) CPU-lowering walltime:")
    print(f"   dense bf16     {t_dense * 1e3:8.2f} ms")
    print(f"   int7 (cfmm)    {t_int8 * 1e3:8.2f} ms")
    print(f"   sparse bitmap  {t_sparse * 1e3:8.2f} ms")

    # analytic TPU model: weight-bound decode (per the paper's effective-ops
    # accounting, zero weights count as useful work)
    bytes_dense = K * N * 2
    bytes_int8 = K * N * 1
    bytes_sparse = bitmap.size + values.size
    t_mem = {m: b / HBM_BW for m, b in [("dense bf16", bytes_dense),
                                        ("int7", bytes_int8),
                                        ("sparse int7", bytes_sparse)]}
    flops = 2 * M_decode * K * N
    print(f"\n TPU v5e analytic decode step ({K}x{N}, batch {M_decode}):")
    for mode, b in [("dense bf16", bytes_dense), ("int7", bytes_int8),
                    ("sparse int7", bytes_sparse)]:
        peak = PEAK_BF16 if mode == "dense bf16" else PEAK_INT8
        t_c = flops / peak
        t_m = b / HBM_BW
        eff_tops = flops / max(t_c, t_m) / 1e12
        print(f"   {mode:12s} weights {b / 1e6:7.2f} MB -> bound "
              f"{max(t_c, t_m) * 1e6:7.2f} us  effective {eff_tops:6.1f} TOP/s "
              f"({'memory' if t_m > t_c else 'compute'}-bound)")
    speedup = bytes_dense / bytes_sparse
    print(f"   sparse-vs-dense effective decode speedup (weight-bound): "
          f"{speedup:.1f}x  — the paper's zero-overhead sparsity, as "
          f"bandwidth")
    return {
        "cpu_ms": {"dense": t_dense * 1e3, "int8": t_int8 * 1e3,
                   "sparse": t_sparse * 1e3},
        "weight_bytes": {"dense": bytes_dense, "int8": bytes_int8,
                         "sparse": int(bytes_sparse)},
        "weight_bound_speedup": float(speedup),
    }
