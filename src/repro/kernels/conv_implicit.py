"""Implicit-GEMM fused convolution — Pallas TPU kernel (DESIGN.md §3).

The paper's Fig 1 Kernel + Non-Kernel decomposition as ONE kernel launch
per conv layer: the Kernel is the int8 x int7 MACs, the Non-Kernel
(Collector) — per-channel dequant, folded-BN scale, bias, shortcut add,
ReLU, and the output-amax needed to round activations back to 8 bits — is
fused into the epilogue.  The im2col patch tensor is never materialized
in HBM: each grid cell holds a row strip of the (padded) input image in
VMEM and forms the k*k receptive-field taps *implicitly* as strided
slices, issuing one MXU matmul per tap:

    out[oh, ow, :] += x[oh*s + dy, ow*s + dx, :] @ w[dy, dx, :, :]

so HBM activation traffic is 1 byte/input-pixel instead of the 4*k*k
bytes/pixel of a materialized f32 patch tensor + separate-epilogue chain.

Grid: (N, n_strips, C_out/bn) — the paper's persistent line-buffer
streaming as row-strip tiling (kernels/tiling.py).  The launch pads
every conv's input once (SAME padding plus the rows and columns the strip
plan reads past it) and, when strided, splits it into its stride²
phases (``phase_split``), so tap (dy, dx) is the stride-1 window of phase
(dy%s, dx%s) at offset (dy//s, dx//s) — Mosaic has no in-register
strided slice.  Each cell holds one (strip_h + halo, w_out + halo, C)
int8 slab per phase, read at an element (``pl.Element``) row offset so
consecutive strips overlap by their halo rows; the per-cell VMEM working
set is bounded by the strip planner instead of growing with image height
(7x7 maps degenerate to one strip — exactly the pre-tiling kernel).
Weights arrive in spatial-major layout (k*k*c_in, c_out), stored that
way at compile time and viewed as (k*k, c_in, c_out), so each tap's
(c_in, bn) slab is a leading-axis index with no call-time permute.

Outputs: f32 (N, n_strips*ms_pad, C_out) strip-blocked conv result plus a
per-(image, strip) column amax (N, n_strips, 1, C_out) — max|y| over the
strip's valid rows, reduced on-chip so the caller can requantize to int8
without re-reading the f32 output (the quantization-domain pass); the
caller max-reduces over strips and channels, which equals the
whole-image amax exactly.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.ref import same_pads
from repro.kernels.tiling import StripPlan, strip_geometry, tap_phases


def out_hw(x: jax.Array, stride: int) -> tuple[int, int]:
    """SAME conv output rows and columns of an (N, H, W, C) input."""
    return -(-x.shape[1] // stride), -(-x.shape[2] // stride)


def phase_split(x: jax.Array, k: int, stride: int,
                g: StripPlan) -> jax.Array:
    """(N, H, W, C) int8 input -> (N*P, g.ph_rows, g.w_out + g.halo, C):
    image n's phase p = (ry, rx) of ``tap_phases`` at row n*P + p,
    holding SAME-padded pixel (i*stride + ry, j*stride + rx).  One pad
    adds the SAME border and the zero rows and columns the strip plan
    reads past it (exact for int8 MACs; they only feed the last strip's
    surplus rows, which the kernel masks and the caller slices off).
    Stride 1 is one phase: the padded input itself."""
    N, H, W, C = x.shape
    lo_h, _, _ = same_pads(H, k, stride)
    lo_w, _, _ = same_pads(W, k, stride)
    cols = g.w_out + g.halo
    x = jnp.pad(x, ((0, 0), (lo_h, g.ph_rows * stride - lo_h - H),
                    (lo_w, cols * stride - lo_w - W), (0, 0)))
    if stride == 1:
        return x
    x = x.reshape(N, g.ph_rows, stride, cols, stride, C)
    ph = jnp.stack([x[:, :, ry, :, rx] for ry, rx in tap_phases(k, stride)],
                   axis=1)
    return ph.reshape(-1, g.ph_rows, cols, C)


def slab_spec(n_ph: int, g: StripPlan, c: int, c_block=None):
    """BlockSpec of one cell's halo'd slab over ``phase_split``'s output:
    every phase of image n, phase rows [s*strip_h, s*strip_h + strip_h +
    halo).  Strips overlap by the halo, so the block is addressed in
    elements (all dims ``pl.Element`` — Mosaic refuses a mix).
    ``c_block`` channel-tiles the slab (depthwise: tile j reads channels
    [j*c_block, (j+1)*c_block))."""
    cb = c if c_block is None else c_block
    return pl.BlockSpec(
        (pl.Element(n_ph), pl.Element(g.strip_h + g.halo),
         pl.Element(g.w_out + g.halo), pl.Element(cb)),
        lambda n, s, j: (n * n_ph, s * g.strip_h, 0,
                         0 if cb == c else j * cb))


def window_rows(window: jax.Array) -> jax.Array:
    """(h, w, C) int8 tap window -> (h*w, C) matmul rows.  Mosaic
    collapses an int8 (w, C) stack only when w % 4 == 0 or C % 128 == 0
    (four int8 rows pack into one 32-bit sublane); other windows (14x14
    and 7x7 maps with C off the lane multiple) widen through int32,
    which is exact."""
    h, w, C = window.shape
    if w % 4 == 0 or C % 128 == 0:
        return window.reshape(h * w, C)
    return window.astype(jnp.int32).reshape(h * w, C).astype(jnp.int8)


def conv_tap_macs(x_ref, k, stride, h_out, w_out, n_cols, tap_weights,
                  carry=None):
    """Implicit-im2col MAC loop shared by the dense and bitmap-native
    sparse conv kernels: one stride-1 VMEM window + MXU matmul per tap,
    the k*k loop unrolled at trace time (taps are static).  ``x_ref`` is
    one cell's (P, h_out + halo, w_out + halo, C) phase slab
    (``phase_split``/``slab_spec``).

    ``tap_weights(tap, carry) -> ((C, n_cols) int8 slab, carry)`` supplies
    each tap's weight slab — a dense VMEM slice, or an on-chip bitmap
    expand threading its running nonzero count through ``carry``.
    """
    C = x_ref.shape[-1]
    m_out = h_out * w_out
    phase = {p: i for i, p in enumerate(tap_phases(k, stride))}
    acc = jnp.zeros((m_out, n_cols), jnp.int32)
    for dy in range(k):
        for dx in range(k):
            p = phase[dy % stride, dx % stride]
            sl = window_rows(x_ref[p, pl.ds(dy // stride, h_out),
                                   pl.ds(dx // stride, w_out), :])
            w_tap, carry = tap_weights(dy * k + dx, carry)
            acc += jax.lax.dot_general(
                sl, w_tap, dimension_numbers=(((1,), (0,)), ((), ())),
                preferred_element_type=jnp.int32)
    return acc


def collector_epilogue(acc, scale, bias, sc_ref, out_ref, amax_ref, *,
                       m_out, relu, valid_rows=None,
                       zero_refs=None, group_size=None):
    """Fused Collector: dequant * BN-scale (one (1, bn) vector), bias,
    shortcut, ReLU, on-chip amax.  One implementation shared by every
    conv kernel, so sparse and dense conv outputs are bit-identical by
    construction.

    ``valid_rows`` masks the amax to the strip's real output rows: the
    last strip of a tiled launch computes surplus rows from zero-padded
    input (sliced off by the caller) whose bias/ReLU values must not leak
    into the quantization scale.  The amax is stored per channel column
    (a lane-dense (1, bn) row); the caller reduces it.

    ``zero_refs`` (opt-in sparsity profiling, DESIGN.md §11) is a
    ``(zg_ref, za_ref)`` pair of per-cell output refs: the epilogue also
    counts this strip-tile's zero elements per ``group_size``-channel
    ``coarse_in`` group and its all-zero-group (row) cells — masked to
    the same valid rows as the amax, so surplus strip rows never count.
    Per-row group counts are one matmul against the (bn, groups) 0/1
    membership matrix (exact: small integers in f32).  Observation-only:
    ``y`` itself is untouched, so profiled and unprofiled launches stay
    bit-identical (tested).
    """
    y = acc.astype(jnp.float32) * scale + bias
    if sc_ref is not None:
        y = y + sc_ref[0, :m_out, :]
    if relu:
        y = jnp.maximum(y, 0.0)
    ay = jnp.abs(y)
    rows = (None if valid_rows is None else
            jax.lax.broadcasted_iota(jnp.int32, ay.shape, 0))
    if rows is not None:
        ay = jnp.where(rows < valid_rows, ay, 0.0)
    amax_ref[0, 0] = jnp.max(ay, axis=0, keepdims=True)
    if zero_refs is not None:
        zg_ref, za_ref = zero_refs
        zm = y == 0.0
        if rows is not None:
            zm = zm & (rows < valid_rows)
        bn = y.shape[1]
        member = (jax.lax.broadcasted_iota(jnp.int32, (bn, bn // group_size),
                                           0) // group_size
                  == jax.lax.broadcasted_iota(
                      jnp.int32, (bn, bn // group_size), 1))
        per_row = jnp.dot(zm.astype(jnp.float32),
                          member.astype(jnp.float32),
                          preferred_element_type=jnp.float32)
        zg_ref[0, 0, 0] = jnp.sum(per_row, axis=0, keepdims=True)
        za_ref[0, 0, 0] = jnp.sum((per_row == group_size).astype(
            jnp.float32), axis=0, keepdims=True)
    out_ref[0, :m_out, :] = y


def launch_conv(macs, x, weights, eff_scale, eff_bias, shortcut, *,
                k, stride, h_out, g: StripPlan, bn, relu, profile_g,
                interpret, c_block=None):
    """The launch every conv kernel shares.  Grid (N, n_strips, n_out/bn)
    over: the phase-split input slab (``phase_split``/``slab_spec``,
    channel-tiled by ``c_block``), the kernel's own weight blocks
    ``weights`` — (array, BlockSpec) pairs — the per-image eff_scale row
    (per-row quantization domains, DESIGN.md §9), the bias row and the
    optional strip-blocked shortcut.  The body runs ``macs(x_ref,
    *weight_refs) -> (strip_h*w_out, bn) int32`` and the shared Collector
    epilogue.

    Outputs: the strip-blocked f32 y (N, n_strips*ms_pad, n_out), the
    lane-dense per-(image, strip) column amax (N, n_strips, 1, n_out)
    and — with ``profile_g`` — the two per-(image, strip, channel-tile)
    zero-count rows (N, n_strips, n_out/bn, 1, bn/profile_g).  Every
    block's trailing dims are lane multiples or whole axes, the shapes
    Mosaic accepts.
    """
    N, _, _, C = x.shape
    n_out = eff_scale.shape[1]
    n_w = len(weights)
    w_out = g.w_out
    m_out = g.ms

    def kernel(*refs):
        x_ref, w_refs = refs[0], refs[1:1 + n_w]
        s_ref, b_ref, *rest = refs[1 + n_w:]
        sc_ref = rest.pop(0) if shortcut is not None else None
        acc = macs(x_ref, *w_refs)
        valid = jnp.minimum(g.strip_h,
                            h_out - pl.program_id(1) * g.strip_h) * w_out
        collector_epilogue(acc, s_ref[0], b_ref[...], sc_ref, rest[0],
                           rest[1], m_out=m_out, relu=relu,
                           valid_rows=valid,
                           zero_refs=rest[2:4] if profile_g else None,
                           group_size=profile_g)

    in_specs = ([slab_spec(len(tap_phases(k, stride)), g, C, c_block)]
                + [spec for _, spec in weights]
                + [pl.BlockSpec((1, 1, bn), lambda n, s, j: (n, 0, j)),
                   pl.BlockSpec((1, bn), lambda n, s, j: (0, j))])
    args = ([phase_split(x, k, stride, g)]
            + [a for a, _ in weights]
            + [eff_scale.reshape(N, 1, n_out), eff_bias])
    if shortcut is not None:
        assert shortcut.shape == (N, g.n_strips * g.ms_pad, n_out), \
            (shortcut.shape, g)
        in_specs.append(
            pl.BlockSpec((1, g.ms_pad, bn), lambda n, s, j: (n, s, j)))
        args.append(shortcut.astype(jnp.float32))
    out_specs = [pl.BlockSpec((1, g.ms_pad, bn), lambda n, s, j: (n, s, j)),
                 pl.BlockSpec((1, 1, 1, bn), lambda n, s, j: (n, s, 0, j))]
    out_shape = [jax.ShapeDtypeStruct((N, g.n_strips * g.ms_pad, n_out),
                                      jnp.float32),
                 jax.ShapeDtypeStruct((N, g.n_strips, 1, n_out),
                                      jnp.float32)]
    if profile_g:
        assert bn % profile_g == 0, (bn, profile_g)
        gpb = bn // profile_g
        out_specs += [pl.BlockSpec((1, 1, 1, 1, gpb),
                                   lambda n, s, j: (n, s, j, 0, 0))] * 2
        out_shape += [jax.ShapeDtypeStruct(
            (N, g.n_strips, n_out // bn, 1, gpb), jnp.float32)] * 2
    return tuple(pl.pallas_call(
        kernel, grid=(N, g.n_strips, n_out // bn), in_specs=in_specs,
        out_specs=out_specs, out_shape=out_shape,
        interpret=interpret)(*args))


@functools.partial(jax.jit, static_argnames=(
    "k", "stride", "bn", "strip_h", "relu", "interpret", "profile_g"))
def conv2d_implicit_pallas(x: jax.Array, w_sp: jax.Array,
                           eff_scale: jax.Array, eff_bias: jax.Array,
                           shortcut: jax.Array | None = None, *,
                           k: int, stride: int, bn: int = 128,
                           strip_h: int | None = None,
                           relu: bool = True, interpret: bool = False,
                           profile_g: int | None = None):
    """Fused row-strip-tiled implicit-GEMM conv.

    x:         (N, H, W, C) int8, unpadded; the launch SAME-pads and
               phase-splits it (``phase_split``)
    w_sp:      (k*k*C, n_out) int8, spatial-major tap layout (the
               compile-time storage layout — no call-time permute)
    eff_scale: (N, n_out) f32 = s_x[row] * w_scale * bn_scale (whole
               dequant+BN), one row per image: per-row quantization
               domains index it on the grid's image axis (a per-tensor
               scalar domain broadcasts the same row N times)
    eff_bias:  (1, n_out) f32
    shortcut:  optional (N, n_strips*ms_pad, n_out) f32, strip-blocked
               (each strip's strip_h*w_out rows padded to ms_pad)
    strip_h:   output rows per strip; None = one whole-image strip
    profile_g: opt-in sparsity profiling — coarse_in group size (must
               divide bn); appends two per-(image, strip, channel-tile,
               group) f32 zero-count outputs (elements / all-zero row
               cells over valid rows) to the return, observation-only
    Returns (y, amax) or (y, amax, zg, za), as ``launch_conv`` lays them
    out: amax is the per-(image, strip, channel) max|y| over valid rows
    for the int8 requantization pass.
    """
    N, _, _, C = x.shape
    KK, n_out = w_sp.shape
    assert KK == k * k * C and n_out % bn == 0, ((KK, k, C), (n_out, bn))
    assert eff_scale.shape == (N, n_out), (eff_scale.shape, N, n_out)
    h_out, w_out = out_hw(x, stride)
    g = strip_geometry(k=k, stride=stride, h_out=h_out, w_out=w_out,
                       strip_h=strip_h if strip_h is not None else h_out)

    def macs(x_ref, w_ref):
        return conv_tap_macs(x_ref, k, stride, g.strip_h, w_out, bn,
                             lambda tap, carry: (w_ref[tap], carry))

    weights = [(w_sp.reshape(k * k, C, n_out),
                pl.BlockSpec((k * k, C, bn), lambda n, s, j: (0, 0, j)))]
    return launch_conv(macs, x, weights, eff_scale, eff_bias, shortcut,
                       k=k, stride=stride, h_out=h_out, g=g, bn=bn,
                       relu=relu, profile_g=profile_g, interpret=interpret)
