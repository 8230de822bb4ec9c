"""Backend dispatch for the Pallas kernels.

``REPRO_PALLAS`` picks the lowering: ``tpu`` (the Pallas kernels,
compiled by Mosaic), ``interpret`` (the same kernels through the Pallas
interpreter — the kernel test-suite on CPU), ``jnp`` (the
mathematically identical jnp references), or ``auto`` / unset: ``tpu``
on a TPU backend, ``jnp`` everywhere else.  Any other value raises.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ref, tiling


MODES = ("auto", "tpu", "interpret", "jnp")


class UnsupportedOnTPU(NotImplementedError):
    """A serve mode whose kernel Mosaic cannot compile yet.  Raised
    instead of silently lowering that op to a jnp reference on the
    chip."""


def _mode() -> str:
    env = os.environ.get("REPRO_PALLAS", "auto")
    if env not in MODES:
        raise ValueError(f"REPRO_PALLAS={env!r}: expected one of {MODES}")
    if env != "auto":
        return env
    return "tpu" if jax.default_backend() == "tpu" else "jnp"


def _refuse_bitmap_on_tpu(mode: str):
    """The bitmap expand (kernels/bitmap.py) needs a cumsum and a per-lane
    gather, which Mosaic does not lower: bitmap-packed weights cannot be
    served on the chip yet, and must not quietly take another path."""
    if mode == "tpu":
        raise UnsupportedOnTPU(
            "sparse_cfmm: the on-chip bitmap expand does not compile with "
            "Mosaic yet — serve int8 or cfmm on a TPU")


def _pad_to(x: jax.Array, axis: int, mult: int):
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x, size
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), size


def cfmm_matmul(x_q: jax.Array, codes: jax.Array,
                scale: jax.Array | None = None) -> jax.Array:
    """int8 (M,K) @ int8 (K,N) -> int32 (or f32 with scale fused)."""
    mode = _mode()
    if mode == "jnp":
        if scale is None:
            return ref.int8_matmul_ref(x_q, codes)
        return ref.cfmm_matmul_ref(x_q, codes, scale)
    from repro.kernels.cfmm_matmul import cfmm_matmul_pallas
    interpret = mode == "interpret"
    M, K = x_q.shape
    N = codes.shape[1]
    bm = 128 if M >= 128 else max(8, 1 << (M - 1).bit_length())
    bk, k_pad = _tile_pad(K, 512)
    bn, n_pad = _tile_pad(N, 128)
    xp, _ = _pad_to(x_q, 0, bm)
    s = scale if scale is not None else jnp.ones((1, N), jnp.float32)
    if k_pad > K:                  # zero rows/cols: exact under int8 matmul
        xp = jnp.pad(xp, ((0, 0), (0, k_pad - K)))
        codes = jnp.pad(codes, ((0, k_pad - K), (0, 0)))
    if n_pad > N:
        codes = jnp.pad(codes, ((0, 0), (0, n_pad - N)))
        s = jnp.pad(s, ((0, 0), (0, n_pad - N)))
    out = cfmm_matmul_pallas(xp, codes, s, bm=bm, bn=bn, bk=bk,
                             interpret=interpret)[:M, :N]
    if scale is None:
        return out.astype(jnp.int32)
    return out


def _largest_tile(dim: int, cap: int) -> int:
    for t in range(min(cap, dim), 0, -1):
        if dim % t == 0:
            return t
    return 1


def _tile_pad(dim: int, cap: int) -> tuple[int, int]:
    """(tile, padded_dim) for a lane-tiled axis: one tile when the axis
    fits the cap, else the largest divisor that is a whole number of
    128-lane vregs — Mosaic takes a lane block only as a 128 multiple or
    the whole axis.  An axis with no such divisor (a prime, 8*prime, or
    192 under a 128 cap) is padded to the next cap multiple and the
    caller slices the result; zero pad rows/columns are exact under int8
    matmul.  ``cap`` is a multiple of 128."""
    if dim <= cap:
        return dim, dim
    for t in range(cap, 0, -128):
        if dim % t == 0:
            return t, dim
    return cap, -(-dim // cap) * cap


def _conv_lane_tile(n: int, plan_for) -> tuple[int, int]:
    """(bn, n_pad) for a conv's channel grid axis.  ``_tile_pad``'s
    128-lane tile where the axis has one; an axis without (mobilenet_v2's
    144, 576, 960) is one whole-axis block — the other lane block Mosaic
    takes — unless that cell overruns the VMEM budget even at one-row
    strips, and only then padded to 128-lane tiles.  ``plan_for(bn)`` is
    the caller's strip plan for a channel tile of ``bn``."""
    bn, n_pad = _tile_pad(n, 128)
    if (n_pad > n and plan_for(n).cell_bytes
            <= tiling.DEFAULT_VMEM_BUDGET):
        return n, n
    return bn, n_pad


def sparse_cfmm_matmul(x_q: jax.Array, bitmap: jax.Array,
                       values: jax.Array,
                       scale: jax.Array | None = None) -> jax.Array:
    """Bitmap-packed sparse matmul; int32 out (or f32 with scale fused)."""
    mode = _mode()
    _refuse_bitmap_on_tpu(mode)
    if bitmap.shape[0] * 8 != x_q.shape[1]:
        # K padded to a multiple of 8 at compile time (masked tail rows);
        # zero int8 activations are exact, so pad x to match
        assert bitmap.shape[0] * 8 == -(-x_q.shape[1] // 8) * 8, (
            bitmap.shape, x_q.shape)
        x_q, _ = _pad_to(x_q, 1, 8)
    if mode == "jnp":
        acc = ref.sparse_matvec_ref(x_q, bitmap, values)
        if scale is None:
            return acc
        return acc.astype(jnp.float32) * scale
    from repro.kernels.sparse_matvec import sparse_matvec_pallas
    interpret = mode == "interpret"
    M, K = x_q.shape
    N = bitmap.shape[1]
    bn, n_pad = _tile_pad(N, 128)
    k_chunk = _largest_tile(K, 1024)
    if k_chunk % 8 != 0:
        k_chunk = K  # single chunk fallback
    s = scale if scale is not None else jnp.ones((1, N), jnp.float32)
    if n_pad > N:                  # zero bitmap bytes expand to zero codes
        bitmap = jnp.pad(bitmap, ((0, 0), (0, n_pad - N)))
        values = jnp.pad(values, ((0, 0), (0, n_pad - N)))
        s = jnp.pad(s, ((0, 0), (0, n_pad - N)))
    out = sparse_matvec_pallas(x_q, bitmap, values, s, bn=bn,
                               k_chunk=k_chunk, interpret=interpret)[:, :N]
    if scale is None:
        return out.astype(jnp.int32)
    return out


def block_sparse_matmul(x: jax.Array, w: jax.Array,
                        block_kn: tuple = (128, 128)) -> jax.Array:
    """x (M,K) @ w (K,N) skipping all-zero constant blocks.

    w must be a *concrete* array (constant parameters) — the block mask and
    active-block plan are built at trace time, so zero blocks are dropped
    from the grid entirely (the paper's dropped MACs).
    """
    from repro.core.sparsity import block_mask
    from repro.kernels.block_sparse import (block_sparse_matmul_pallas,
                                            plan_blocks)
    assert not isinstance(w, jax.core.Tracer), (
        "block_sparse_matmul requires constant weights")
    bk, bn = block_kn
    K, N = w.shape
    assert K % bk == 0 and N % bn == 0, ((K, N), block_kn)
    mask = block_mask(w, (bk, bn))
    wnp = np.asarray(w)
    blocks = []
    for nb in range(mask.shape[1]):
        for kb in np.nonzero(mask[:, nb])[0]:
            blocks.append(wnp[kb * bk:(kb + 1) * bk, nb * bn:(nb + 1) * bn])
    meta = plan_blocks(mask)
    mode = _mode()
    if mode == "jnp" or meta.shape[1] == 0:
        w_dense = jnp.asarray(np.where(
            np.kron(mask, np.ones((bk, bn), bool)), wnp, 0))
        return (x @ w_dense.astype(x.dtype))
    w_blocks = jnp.asarray(np.stack(blocks))
    M = x.shape[0]
    bm = min(128, M)
    xp, _ = _pad_to(x, 0, bm)
    out = block_sparse_matmul_pallas(
        xp, w_blocks.astype(x.dtype), jnp.asarray(meta), (bk, bn),
        N // bn, interpret=(mode == "interpret"))[:M]
    col_has_work = np.repeat(mask.any(axis=0), bn)
    return jnp.where(jnp.asarray(col_has_work)[None, :], out, 0)


def _strip_blocked(sc_flat: jax.Array, plan, n_pad: int) -> jax.Array:
    """(N, m_out, n_out) f32 -> the tiled kernels' strip-blocked layout
    (N, n_strips*ms_pad, n_pad): each strip's ms rows padded to the
    sublane multiple (and channels to the lane tile) with zeros."""
    N, m_out, n_out = sc_flat.shape
    sc = jnp.pad(sc_flat, ((0, 0), (0, plan.n_strips * plan.ms - m_out),
                           (0, n_pad - n_out)))
    sc = sc.reshape(N, plan.n_strips, plan.ms, n_pad)
    sc = jnp.pad(sc, ((0, 0), (0, 0), (0, plan.ms_pad - plan.ms), (0, 0)))
    return sc.reshape(N, plan.n_strips * plan.ms_pad, n_pad)


def conv2d(x_q: jax.Array, codes: jax.Array, k: int, stride: int, *,
           x_scale, w_scale: jax.Array, gamma: jax.Array | None = None,
           beta: jax.Array | None = None, shortcut: jax.Array | None = None,
           relu: bool = True, quant_out: bool = False,
           w_layout: str = "channel", strip_h: int | None = None,
           zero_count: int | None = None):
    """Fused row-strip-tiled implicit-GEMM int8 SAME conv + Collector.

    x_q:     (N, H, W, c_in) int8 activations; x_scale their scale —
             a scalar (per-tensor quantization domain) or an ``(N,)``
             per-row vector (one domain per image, DESIGN.md §9).  The
             domain shape propagates: with a per-row x_scale, quant_out
             emits a per-row y_scale, so a chain of convs stays per-row
             end to end and a row's results never depend on its batch
             neighbours
    codes:   (c_in*k*k, c_out) int8 constant weight codes — in im2col
             patch (channel-major) order by default, or the compiled
             spatial-major tap order with ``w_layout="spatial"`` (what
             ``compile_params`` stores for every dense conv leaf, so the
             serving path pays zero call-time layout shuffles) — OR a
             packed ``(bitmap, values)`` pair in the spatial-major
             bitmap-native layout (kernels/conv_sparse.py): the
             sparse_cfmm fast path, where packed bytes reach the kernel
             and the dense weight never exists outside VMEM
    w_scale: per-output-channel dequant scale, broadcastable to (c_out,)
    gamma/beta: folded-BN scale and bias (the Non-Kernel Collector ops)
    shortcut:   optional f32 (N, h_out, w_out, c_out) residual to add
    quant_out:  round the output back to int8 (paper: "saturated and
                rounded to 8 bits") -> returns (y_q int8, y_scale);
                otherwise returns f32 (N, h_out, w_out, c_out).
    strip_h: row-strip override (tests/benchmarks force awkward strip
             boundaries); None lets kernels/tiling.py pick the largest
             strip whose VMEM working set fits the budget.  Tiled and
             untiled outputs are bit-identical; the jnp lowering only
             loops strips when strip_h is forced.
    zero_count: opt-in activation-sparsity profiling (DESIGN.md §11) —
             the coarse_in group size to count zeros at.  Appends the
             profiler aux dict (kernels/ref.zero_counts_ref keys) to the
             return: ``(y, zc)`` or ``(y_q, y_scale, zc)``.  jnp lowers
             to the exact recount on ``y``; the Pallas kernels emit a
             cheap per-strip zero-count output alongside the amax (host
             recount fallback when channel padding misaligns the
             groups).  Observation-only — y/y_q bits are unchanged.

    Lowering follows REPRO_PALLAS like every op here: the jnp reference on
    CPU, the Pallas implicit-GEMM kernel on TPU / in interpret mode.
    """
    mode = _mode()
    N, H, W, C = x_q.shape
    packed = isinstance(codes, (tuple, list))
    if packed:
        _refuse_bitmap_on_tpu(mode)
        bitmap, values = codes
        n_out = bitmap.shape[1]
        assert bitmap.shape[0] * 8 == -(-C * k * k // 8) * 8, (
            bitmap.shape, C, k)
    else:
        n_out = codes.shape[1]
        assert codes.shape[0] == C * k * k, (codes.shape, C, k)
    one = jnp.ones((n_out,), jnp.float32)
    x_s = jnp.asarray(x_scale, jnp.float32)
    per_row = x_s.ndim >= 1          # (N,) per-row domains vs scalar
    col_scale = (w_scale.reshape(-1).astype(jnp.float32)
                 * (one if gamma is None else gamma.astype(jnp.float32)))
    # (R, n_out), R = N for per-row domains, 1 for the per-tensor scalar
    eff_scale = x_s.reshape(-1, 1) * col_scale.reshape(1, -1)
    eff_bias = (jnp.zeros((n_out,), jnp.float32) if beta is None
                else beta.astype(jnp.float32))
    profile_fast = False          # in-kernel zero counts (Pallas only)
    if mode == "jnp":
        # (R, 1, 1, n_out) broadcasts against NHWC accumulators in the
        # oracles' shared _collector, per-row and per-tensor alike
        eff4 = eff_scale.reshape(eff_scale.shape[0], 1, 1, n_out)
        if strip_h is not None:
            y = ref.conv2d_collector_strips_ref(
                x_q, codes, k, stride, strip_h, eff4, eff_bias,
                shortcut, relu, layout=w_layout)
        elif packed:
            y = ref.conv2d_sparse_collector_ref(
                x_q, bitmap, values, k, stride, eff4, eff_bias,
                shortcut, relu)
        else:
            y = ref.conv2d_collector_ref(x_q, codes, k, stride, eff4,
                                         eff_bias, shortcut, relu,
                                         layout=w_layout)
        amax_of = (lambda: jnp.max(jnp.abs(y), axis=(1, 2, 3))) if per_row \
            else (lambda: jnp.max(jnp.abs(y)))
    else:
        _, _, h_out = ref.same_pads(H, k, stride)
        _, _, w_out = ref.same_pads(W, k, stride)
        m_out = h_out * w_out

        def plan_for(bn, strip_h=None):
            if packed:             # per-cell weight slab for the planner:
                weight_bytes = (tiling.vmem_bytes((bitmap.shape[0], bn), 1)
                                + tiling.vmem_bytes((values.shape[0], bn),
                                                    1))
                if C % 8 != 0:     # + the one-shot expanded slab (stem)
                    weight_bytes += tiling.vmem_bytes(
                        (bitmap.shape[0] * 8, bn), 1)
            else:
                weight_bytes = tiling.vmem_bytes((k * k, C, bn), 1)
            return tiling.plan_strips(
                k=k, stride=stride, h_out=h_out, w_out=w_out, c_in=C,
                bn=bn, weight_bytes=weight_bytes,
                has_shortcut=shortcut is not None, strip_h=strip_h)

        bn, n_pad = _conv_lane_tile(n_out, plan_for)
        if n_pad > n_out:          # awkward channel count: zero-pad + slice
            if packed:
                bitmap = jnp.pad(bitmap, ((0, 0), (0, n_pad - n_out)))
                values = jnp.pad(values, ((0, 0), (0, n_pad - n_out)))
            else:
                codes = jnp.pad(codes, ((0, 0), (0, n_pad - n_out)))
            eff_scale = jnp.pad(eff_scale, ((0, 0), (0, n_pad - n_out)))
            eff_bias = jnp.pad(eff_bias, (0, n_pad - n_out))
        plan = plan_for(bn, strip_h)
        sc = None
        if shortcut is not None:
            sc = _strip_blocked(
                shortcut.astype(jnp.float32).reshape(N, m_out, n_out),
                plan, n_pad)
        # profiling rides the kernel launch (a per-strip zero-count
        # output next to the amax) when the padded channel axis keeps
        # coarse_in groups aligned; otherwise fall back to an exact
        # host-side recount on y below (padded channels are all-zero and
        # would inflate the counts)
        profile_fast = (zero_count is not None and n_pad == n_out
                        and n_out % zero_count == 0
                        and bn % zero_count == 0)
        kw = dict(k=k, stride=stride, bn=bn, strip_h=plan.strip_h,
                  relu=relu, interpret=(mode == "interpret"),
                  profile_g=zero_count if profile_fast else None)
        # the kernels index eff_scale per image (grid axis n) so per-row
        # domains ride the same launch; a per-tensor scalar broadcasts
        eff_rows = jnp.broadcast_to(eff_scale, (N, n_pad))
        if packed:
            from repro.kernels.conv_sparse import conv2d_sparse_pallas
            outs = conv2d_sparse_pallas(
                x_q, bitmap, values, eff_rows,
                eff_bias.reshape(1, n_pad), sc, **kw)
        else:
            from repro.kernels.conv_implicit import conv2d_implicit_pallas
            if w_layout == "channel":  # pre-compile codes pay the permute
                codes = ref.to_spatial_major(codes, k, C)
            outs = conv2d_implicit_pallas(
                x_q, codes, eff_rows,
                eff_bias.reshape(1, n_pad), sc, **kw)
        y_flat, _amax = outs[0], outs[1]
        y = y_flat.reshape(N, plan.n_strips, plan.ms_pad, n_pad)[
            :, :, :plan.ms, :n_out]
        y = y.reshape(N, plan.n_strips * plan.ms, n_out)[:, :m_out]
        y = y.reshape(N, h_out, w_out, n_out)
        # reduced on-chip in the epilogue: (N, n_strips, 1, n_pad) ->
        # whole-tensor max, or max over strips/channels only (keep N)
        # per-row
        amax_of = (lambda: jnp.max(_amax, axis=(1, 2, 3))) if per_row \
            else (lambda: jnp.max(_amax))
    zc = None
    if zero_count is not None:
        if profile_fast:
            # kernel outputs: (N, n_strips, n_j, 1, groups/tile) valid-row
            # zero counts; flatten (tile, in-tile group) -> the global
            # channel-group axis and reduce on the right axes
            m_out = y.shape[1] * y.shape[2]
            zg = outs[2].reshape(N, -1, n_out // zero_count)
            za = outs[3].reshape(N, -1, n_out // zero_count)
            zc = {"row_zeros": jnp.sum(zg, axis=(1, 2)),
                  "group_zeros": jnp.sum(zg, axis=(0, 1)),
                  "group_allzero": jnp.sum(za, axis=(0, 1)),
                  "elems_per_row": jnp.float32(m_out * n_out),
                  "cells": jnp.float32(N * m_out)}
        else:
            zc = ref.zero_counts_ref(y, zero_count)
    if not quant_out:
        return (y, zc) if zero_count is not None else y
    # quantization-domain pass: activations go straight back to int8 so
    # the next conv consumes codes without an f32 HBM round-trip; under
    # per-row domains s_y is (N,) — one independent scale per image
    s_y = (jnp.maximum(amax_of(), 1e-12) / 127.0).astype(jnp.float32)
    s_b = s_y.reshape(-1, 1, 1, 1) if per_row else s_y
    y_q = jnp.clip(jnp.round(y / s_b), -127, 127).astype(jnp.int8)
    if zero_count is not None:
        return y_q, s_y, zc
    return y_q, s_y


def conv2d_dw(x_q: jax.Array, values: jax.Array, k: int, stride: int, *,
              x_scale, w_scale: jax.Array, gamma: jax.Array | None = None,
              beta: jax.Array | None = None,
              shortcut: jax.Array | None = None, relu: bool = True,
              quant_out: bool = False, strip_h: int | None = None,
              zero_count: int | None = None):
    """Fused row-strip-tiled depthwise int8 SAME conv + Collector.

    The depthwise sibling of ``conv2d`` (same Collector semantics, same
    quantization-domain contract: per-row ``x_scale`` propagates to a
    per-row ``y_scale`` under ``quant_out``).  ``values`` is the
    compile-time tap-major ``(k*k, C)`` int8 weight — one weight row per
    receptive-field tap — consumed by the VPU tap-MAC kernel
    (kernels/conv_depthwise.py); implicit-GEMM would burn a (C, C)
    matmul per tap for a diagonal's worth of useful work.  jnp lowering
    and Pallas kernel are bit-identical across strip tilings (the jnp
    path loops strips only when ``strip_h`` is forced, like ``conv2d``).
    """
    mode = _mode()
    N, H, W, C = x_q.shape
    assert values.shape == (k * k, C), (values.shape, k, C)
    one = jnp.ones((C,), jnp.float32)
    x_s = jnp.asarray(x_scale, jnp.float32)
    per_row = x_s.ndim >= 1          # (N,) per-row domains vs scalar
    col_scale = (w_scale.reshape(-1).astype(jnp.float32)
                 * (one if gamma is None else gamma.astype(jnp.float32)))
    eff_scale = x_s.reshape(-1, 1) * col_scale.reshape(1, -1)
    eff_bias = (jnp.zeros((C,), jnp.float32) if beta is None
                else beta.astype(jnp.float32))
    profile_fast = False
    if mode == "jnp":
        eff4 = eff_scale.reshape(eff_scale.shape[0], 1, 1, C)
        if strip_h is not None:
            y = ref.conv2d_dw_collector_strips_ref(
                x_q, values, k, stride, strip_h, eff4, eff_bias,
                shortcut, relu)
        else:
            y = ref.conv2d_dw_collector_ref(x_q, values, k, stride, eff4,
                                            eff_bias, shortcut, relu)
        amax_of = (lambda: jnp.max(jnp.abs(y), axis=(1, 2, 3))) if per_row \
            else (lambda: jnp.max(jnp.abs(y)))
    else:
        _, _, h_out = ref.same_pads(H, k, stride)
        _, _, w_out = ref.same_pads(W, k, stride)
        m_out = h_out * w_out

        def plan_for(bn, strip_h=None):
            # the slab is channel-tiled (bn channels per cell), so the
            # planner's activation term scales with bn, not C
            return tiling.plan_strips(
                k=k, stride=stride, h_out=h_out, w_out=w_out, c_in=bn,
                bn=bn, weight_bytes=tiling.vmem_bytes((k * k, bn), 1),
                has_shortcut=shortcut is not None, strip_h=strip_h)

        bn, n_pad = _conv_lane_tile(C, plan_for)
        x_c = x_q
        if n_pad > C:              # awkward channel count: zero-pad + slice
            # zero input channels x zero weight channels -> zero outputs,
            # exact under int8 MACs; the pad is sliced off below
            x_c = jnp.pad(x_q, ((0, 0), (0, 0), (0, 0), (0, n_pad - C)))
            values = jnp.pad(values, ((0, 0), (0, n_pad - C)))
            eff_scale = jnp.pad(eff_scale, ((0, 0), (0, n_pad - C)))
            eff_bias = jnp.pad(eff_bias, (0, n_pad - C))
        plan = plan_for(bn, strip_h)
        sc = None
        if shortcut is not None:
            sc = _strip_blocked(
                shortcut.astype(jnp.float32).reshape(N, m_out, C),
                plan, n_pad)
        profile_fast = (zero_count is not None and n_pad == C
                        and C % zero_count == 0
                        and bn % zero_count == 0)
        eff_rows = jnp.broadcast_to(eff_scale, (N, n_pad))
        from repro.kernels.conv_depthwise import conv2d_dw_pallas
        outs = conv2d_dw_pallas(
            x_c, values, eff_rows, eff_bias.reshape(1, n_pad), sc,
            k=k, stride=stride, bn=bn, strip_h=plan.strip_h, relu=relu,
            interpret=(mode == "interpret"),
            profile_g=zero_count if profile_fast else None)
        y_flat, _amax = outs[0], outs[1]
        y = y_flat.reshape(N, plan.n_strips, plan.ms_pad, n_pad)[
            :, :, :plan.ms, :C]
        y = y.reshape(N, plan.n_strips * plan.ms, C)[:, :m_out]
        y = y.reshape(N, h_out, w_out, C)
        amax_of = (lambda: jnp.max(_amax, axis=(1, 2, 3))) if per_row \
            else (lambda: jnp.max(_amax))
    zc = None
    if zero_count is not None:
        if profile_fast:
            m_out = y.shape[1] * y.shape[2]
            zg = outs[2].reshape(N, -1, C // zero_count)
            za = outs[3].reshape(N, -1, C // zero_count)
            zc = {"row_zeros": jnp.sum(zg, axis=(1, 2)),
                  "group_zeros": jnp.sum(zg, axis=(0, 1)),
                  "group_allzero": jnp.sum(za, axis=(0, 1)),
                  "elems_per_row": jnp.float32(m_out * C),
                  "cells": jnp.float32(N * m_out)}
        else:
            zc = ref.zero_counts_ref(y, zero_count)
    if not quant_out:
        return (y, zc) if zero_count is not None else y
    s_y = (jnp.maximum(amax_of(), 1e-12) / 127.0).astype(jnp.float32)
    s_b = s_y.reshape(-1, 1, 1, 1) if per_row else s_y
    y_q = jnp.clip(jnp.round(y / s_b), -127, 127).astype(jnp.int8)
    if zero_count is not None:
        return y_q, s_y, zc
    return y_q, s_y


def flash_attention(q, k, v, causal=True, window=None):
    """GQA-native flash attention: Pallas on TPU, jnp chunked elsewhere.

    q: (B, KVH, G, Tq, D); k: (B, KVH, Tk, D); v: (B, KVH, Tk, Dv).
    """
    mode = _mode()
    if mode == "jnp":
        from repro.models.attention import flash_attention as jnp_flash
        return jnp_flash(q, k, v, causal=causal, window=window)
    from repro.kernels.flash_attention import flash_attention_pallas
    B, KVH, G, Tq, D = q.shape
    Tk = k.shape[2]
    bq = _largest_tile(Tq, 128)
    bk = _largest_tile(Tk, 128)
    return flash_attention_pallas(q, k, v, causal=causal, window=window,
                                  bq=bq, bk=bk,
                                  interpret=(mode == "interpret"))
