"""Bitmap-native implicit-GEMM sparse convolution — Pallas TPU kernel.

The paper's headline numbers come from *sparse constant parameters*: at
s=0.8 the bitmap format stores (1-s)*8 + 1 = 2.6 bits/param instead of
the 8 bits of dense int8 codes.  `conv_implicit.py` already keeps the
im2col patch tensor out of HBM; this kernel carries the packed-weight win
into the same launch — HBM only ever sees `(bitmap, values)` bytes, and
the dense tap slabs exist solely in VMEM.

Format (core.compiled_linear.compile_params, conv leaves, sparse_cfmm):
  weights are *spatial-major* (k*k*c_in, c_out) — row = tap*c_in + c —
  with K padded up to a multiple of 8 by all-zero masked tap rows, then
  bitmap-packed column-wise:
    bitmap (K_pad/8, c_out) uint8, values (keep_k, c_out) int8.

Kernel: grid (N, n_strips, c_out/bn), identical to conv_implicit — the
input streams as halo'd row strips (kernels/tiling.py) while the packed
weight slab is re-read per cell and expands via the shared
`kernels.bitmap.expand_bitmap_tile`:

* c_in % 8 == 0 — expand *per k-tap tile*, fused with the MAC: each tap's
  (c_in, bn) slab is expanded and immediately fed to the MXU, carrying the
  running nonzero count tap to tap; the full dense weight never exists.
* otherwise (e.g. the c_in=3 stem) — byte rows straddle tap boundaries,
  so the whole (K_pad, bn) slab expands in one tile, then the tap loop
  slices it; still VMEM-only.

The MAC loop and the Collector epilogue (dequant * folded-BN scale, bias,
shortcut, ReLU, on-chip per-strip amax for the quantization-domain pass)
are *shared code* with `conv_implicit.py` (`conv_tap_macs` /
`collector_epilogue`) — only the tap-weight sourcing differs — so sparse
and dense conv outputs are bit-identical for identical (expanded) codes
by construction, tiled or not.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.bitmap import expand_bitmap_tile
from repro.kernels.conv_implicit import conv_tap_macs, launch_conv, out_hw
from repro.kernels.tiling import strip_geometry


@functools.partial(jax.jit, static_argnames=(
    "k", "stride", "bn", "strip_h", "relu", "interpret", "profile_g"))
def conv2d_sparse_pallas(x: jax.Array, bitmap: jax.Array,
                         values: jax.Array, eff_scale: jax.Array,
                         eff_bias: jax.Array,
                         shortcut: jax.Array | None = None, *,
                         k: int, stride: int, bn: int = 128,
                         strip_h: int | None = None,
                         relu: bool = True, interpret: bool = False,
                         profile_g: int | None = None):
    """Fused bitmap-native row-strip-tiled implicit-GEMM sparse conv.

    x:         (N, H, W, C) int8, unpadded; the launch SAME-pads and
               phase-splits it
    bitmap:    (K_pad/8, n_out) uint8, spatial-major taps, K_pad =
               k*k*C rounded up to a multiple of 8 (zero-masked tail)
    values:    (keep_k, n_out) int8 nonzero codes, ascending-row order
    eff_scale: (N, n_out) f32 = s_x[row] * w_scale * bn_scale, one row
               per image (per-row quantization domains; a per-tensor
               domain broadcasts one row); eff_bias (1, n_out) f32
    shortcut:  optional (N, n_strips*ms_pad, n_out) f32, strip-blocked
    strip_h:   output rows per strip; None = one whole-image strip
    profile_g: opt-in sparsity profiling group size (see
               conv2d_implicit_pallas — identical outputs/semantics)
    Returns (y, amax) exactly as conv2d_implicit_pallas
    ((y, amax, zg, za) with ``profile_g``).
    """
    N, _, _, C = x.shape
    Kb8, n_out = bitmap.shape
    keep_k = values.shape[0]
    assert Kb8 * 8 == -(-k * k * C // 8) * 8, (Kb8, k, C)
    assert n_out % bn == 0 and values.shape[1] == n_out, (n_out, bn)
    assert eff_scale.shape == (N, n_out), (eff_scale.shape, N, n_out)
    h_out, w_out = out_hw(x, stride)
    g = strip_geometry(k=k, stride=stride, h_out=h_out, w_out=w_out,
                       strip_h=strip_h if strip_h is not None else h_out)

    def macs(x_ref, bm_ref, val_ref):
        # the MAC loop and Collector are conv_implicit's own (shared
        # code, so sparse == dense bit-identity holds by construction);
        # only the tap weight sourcing differs — packed bytes expand on
        # the fly in VMEM
        vals = val_ref[...]
        if C % 8 == 0:                             # tap rows byte-aligned:
            def tap_weights(tap, base):            # expand fused per tap,
                bm8 = bm_ref[tap * C // 8:(tap + 1) * C // 8, :]
                return expand_bitmap_tile(bm8, vals, base, keep_k)
            carry = jnp.zeros((1, bn), jnp.int32)  # running nonzero count
        else:                                      # taps straddle bytes
            w_dense, _ = expand_bitmap_tile(       # (stem): one-shot slab
                bm_ref[...], vals, jnp.zeros((1, bn), jnp.int32), keep_k)

            def tap_weights(tap, carry):
                return jax.lax.slice(w_dense, (tap * C, 0),
                                     ((tap + 1) * C, bn)), carry
            carry = None
        return conv_tap_macs(x_ref, k, stride, g.strip_h, w_out, bn,
                             tap_weights, carry)

    weights = [(bitmap, pl.BlockSpec((Kb8, bn), lambda n, s, j: (0, j))),
               (values, pl.BlockSpec((keep_k, bn), lambda n, s, j: (0, j)))]
    return launch_conv(macs, x, weights, eff_scale, eff_bias, shortcut,
                       k=k, stride=stride, h_out=h_out, g=g, bn=bn,
                       relu=relu, profile_g=profile_g, interpret=interpret)
