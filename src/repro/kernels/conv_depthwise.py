"""Depthwise fused convolution — Pallas TPU kernel (DESIGN.md §12).

Implicit-GEMM degenerates at groups == C: each output channel reads ONE
input channel, so the per-tap (c_in, bn) matmul slab collapses to a
diagonal and the MXU would burn c_in x multiplies per useful MAC.  This
kernel keeps the same grid, strip tiling, and fused Collector epilogue as
kernels/conv_implicit.py but replaces the tap matmul with a VPU
elementwise tap-MAC:

    acc[m, c] += x[oh*s + dy, ow*s + dx, c] * w[dy*k + dx, c]

Weights arrive tap-major (k*k, C) int8 — stored that way at compile time
(nn.dwconv_param already initializes in this layout, so compilation does
zero shuffles) — and each grid cell holds a CHANNEL-TILED halo'd phase
slab (P, strip_h + halo, w_out + halo, bn): unlike the dense kernel,
whose every output tile needs all input channels, a depthwise output
tile touches exactly its own bn input channels, so the slab read shrinks
with the channel grid axis.

Grid: (N, n_strips, C/bn).  Strided convs read the same stride² input
phases as conv_implicit (``phase_split``), so every tap is a stride-1
window.  Outputs match conv_implicit's contract — strip-blocked f32 y
plus the per-(image, strip) column amax (and the optional zero-count
pair) — so ops.conv2d_dw reuses the same unblocking
and requantization tail as ops.conv2d.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.conv_implicit import launch_conv, out_hw
from repro.kernels.tiling import strip_geometry, tap_phases


def dw_tap_macs(x_ref, w_tap, k, stride, h_out, w_out):
    """Depthwise tap-MAC loop: one stride-1 VMEM window of the tap's
    input phase + VPU elementwise multiply-accumulate per tap, the k*k
    loop unrolled at trace time.

    x_ref: (P, h_out + halo, w_out + halo, bn) int8 phase slab
    (conv_implicit.phase_split); w_tap: (k*k, bn) int8 -> (m_out, bn)
    int32, m_out = h_out * w_out.
    """
    bn = x_ref.shape[-1]
    m_out = h_out * w_out
    phase = {p: i for i, p in enumerate(tap_phases(k, stride))}
    acc = jnp.zeros((m_out, bn), jnp.int32)
    for dy in range(k):
        for dx in range(k):
            p = phase[dy % stride, dx % stride]
            # widened before the reshape: an int32 (h, w, bn) stack
            # collapses for any w (see conv_implicit.window_rows)
            sl = x_ref[p, pl.ds(dy // stride, h_out),
                       pl.ds(dx // stride, w_out), :].astype(jnp.int32)
            tap = dy * k + dx
            acc += sl.reshape(m_out, bn) * w_tap[tap:tap + 1].astype(
                jnp.int32)
    return acc


@functools.partial(jax.jit, static_argnames=(
    "k", "stride", "bn", "strip_h", "relu", "interpret", "profile_g"))
def conv2d_dw_pallas(x: jax.Array, w_tap: jax.Array,
                     eff_scale: jax.Array, eff_bias: jax.Array,
                     shortcut: jax.Array | None = None, *,
                     k: int, stride: int, bn: int = 128,
                     strip_h: int | None = None,
                     relu: bool = True, interpret: bool = False,
                     profile_g: int | None = None):
    """Fused row-strip-tiled depthwise conv.

    x:         (N, H, W, C) int8, unpadded, channels padded to the bn
               tile; the launch SAME-pads and phase-splits it
    w_tap:     (k*k, C) int8, tap-major (the compile-time storage layout)
    eff_scale: (N, C) f32 = s_x[row] * w_scale[channel] * bn_scale
    eff_bias:  (1, C) f32
    shortcut:  optional (N, n_strips*ms_pad, C) f32, strip-blocked
    Returns (y, amax) — strip-blocked f32 y (N, n_strips*ms_pad, C) and
    per-(image, strip, channel) max|y| over valid rows — or
    (y, amax, zg, za) with ``profile_g`` (same contract as the dense
    implicit-GEMM kernel, shared unblocking in ops.conv2d_dw).
    """
    N, _, _, C = x.shape
    KK, n_out = w_tap.shape
    assert KK == k * k and n_out == C and C % bn == 0, \
        ((KK, k), (n_out, C, bn))
    assert eff_scale.shape == (N, C), (eff_scale.shape, N, C)
    h_out, w_out = out_hw(x, stride)
    g = strip_geometry(k=k, stride=stride, h_out=h_out, w_out=w_out,
                       strip_h=strip_h if strip_h is not None else h_out)

    def macs(x_ref, w_ref):
        return dw_tap_macs(x_ref, w_ref[...], k, stride, g.strip_h, w_out)

    # channel-tiled: a depthwise output tile reads only its own bn input
    # channels
    weights = [(w_tap, pl.BlockSpec((KK, bn), lambda n, s, j: (0, j)))]
    return launch_conv(macs, x, weights, eff_scale, eff_bias, shortcut,
                       k=k, stride=stride, h_out=h_out, g=g, bn=bn,
                       relu=relu, profile_g=profile_g, interpret=interpret,
                       c_block=bn)
