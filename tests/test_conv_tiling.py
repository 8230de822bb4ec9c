"""Row-strip tiled conv: strip-boundary bit-identity property sweep
(tiled vs untiled, jnp and interpret lowerings, dense and bitmap-packed),
the strip planner's budget/halo arithmetic, and the quantization-domain
scale from the strip-reduced amax."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import nn
from repro.core import compiled_linear as cl
from repro.core.quantize import quantize_int7
from repro.kernels import ops, ref
from repro.kernels.tiling import (DEFAULT_VMEM_BUDGET, plan_strips,
                                  strip_geometry)

# the acceptance grid: k x stride x odd/even H x strip_h that does not
# divide h_out (plus dividing ones), covering halo rows, stride-2
# subsampled strips, and the k=7 s=2 stem corner
KS = [(1, 1), (1, 2), (3, 1), (3, 2), (7, 1), (7, 2)]
HS = [8, 9]
STRIP_HS = [1, 3, 4]


def _conv_case(k, H, W, C, n_out=16, seed=0):
    key = jax.random.PRNGKey(seed + 17 * k + H + C)
    x = jax.random.randint(key, (2, H, W, C), -127, 128, jnp.int8)
    w = jax.random.normal(jax.random.fold_in(key, 1),
                          (C * k * k, n_out)) * 0.1
    return x, quantize_int7(w)


@pytest.mark.parametrize("k,stride", KS)
@pytest.mark.parametrize("H", HS)
@pytest.mark.parametrize("strip_h", STRIP_HS)
def test_tiled_bit_identical_dense(k, stride, H, strip_h, monkeypatch):
    """Dense codes: the strip-tiled conv equals the untiled conv bit for
    bit in BOTH lowerings, and the interpret kernel equals the jnp
    oracle exactly (unit scales keep the f32 epilogue integer-exact)."""
    x, qt = _conv_case(k, H, 7, C=8)
    n_out = qt.values.shape[1]
    kw = dict(x_scale=1.0, w_scale=jnp.ones((n_out,)), relu=False)
    outs = {}
    for mode in ("jnp", "interpret"):
        monkeypatch.setenv("REPRO_PALLAS", mode)
        outs[mode, "untiled"] = ops.conv2d(x, qt.values, k, stride, **kw)
        outs[mode, "tiled"] = ops.conv2d(x, qt.values, k, stride,
                                         strip_h=strip_h, **kw)
    want = np.asarray(outs["jnp", "untiled"])
    for key_ in outs:
        np.testing.assert_array_equal(np.asarray(outs[key_]), want)


@pytest.mark.parametrize("k,stride", [(3, 1), (3, 2), (7, 2)])
@pytest.mark.parametrize("strip_h", [1, 3])
def test_tiled_bit_identical_bitmap_packed(k, stride, strip_h, monkeypatch):
    """Bitmap-packed weights ride the same strip decomposition: tiled ==
    untiled bit-for-bit in both lowerings, for byte-aligned (c_in=8) and
    tap-straddling (c_in=3, the stem) packings."""
    for C in (8, 3):
        key = jax.random.PRNGKey(5 * k + C)
        p = {"w": nn.conv_param(key, C, 16, k, stride,
                                ("conv_in", "conv_out"))}
        w = nn.unbox(cl.compile_params(p, mode="sparse_cfmm",
                                       sparsity=0.8))["w"]
        x = jax.random.randint(jax.random.fold_in(key, 1), (2, 9, 7, C),
                               -127, 128, jnp.int8)
        codes = (w["bitmap"], w["values"])
        kw = dict(x_scale=0.02, w_scale=w["scale"].reshape(-1), relu=False)
        outs = {}
        for mode in ("jnp", "interpret"):
            monkeypatch.setenv("REPRO_PALLAS", mode)
            outs[mode, "u"] = ops.conv2d(x, codes, k, stride, **kw)
            outs[mode, "t"] = ops.conv2d(x, codes, k, stride,
                                         strip_h=strip_h, **kw)
        want = np.asarray(outs["jnp", "u"])
        for key_ in outs:
            np.testing.assert_array_equal(np.asarray(outs[key_]), want)


def test_stem_geometry_tiled(monkeypatch):
    """The 224x224-class stem corner at test scale: k=7 s=2 c_in=3 with a
    strip_h that does not divide h_out — tiled == untiled in both
    lowerings, including the quant_out scale from the strip-reduced
    amax (the last strip's surplus rows must not leak into it)."""
    k, stride, C, n_out = 7, 2, 3, 16
    key = jax.random.PRNGKey(0)
    x = jax.random.randint(key, (1, 20, 20, C), -127, 128, jnp.int8)
    w = jax.random.normal(jax.random.fold_in(key, 1),
                          (C * k * k, n_out)) * 0.1
    qt = quantize_int7(w)
    kw = dict(x_scale=0.02, w_scale=qt.scale.reshape(-1),
              gamma=jnp.ones((n_out,)), beta=jnp.full((n_out,), 0.3),
              relu=True, quant_out=True)
    outs = {}
    for mode in ("jnp", "interpret"):
        monkeypatch.setenv("REPRO_PALLAS", mode)
        outs[mode, "u"] = ops.conv2d(x, qt.values, k, stride, **kw)
        outs[mode, "t"] = ops.conv2d(x, qt.values, k, stride, strip_h=3,
                                     **kw)  # h_out=10, 4 strips, last short
    yu, su = outs["jnp", "u"]
    for key_ in outs:
        y, s = outs[key_]
        np.testing.assert_array_equal(np.asarray(y), np.asarray(yu))
        np.testing.assert_array_equal(np.asarray(s), np.asarray(su))


def test_tiled_shortcut_and_collector(monkeypatch):
    """Shortcut adds land in the right strip rows (the strip-blocked
    re-layout) and the fused Collector matches the untiled epilogue."""
    k, stride, n_out = 3, 1, 16
    x, qt = _conv_case(k, 9, 7, C=8)
    key = jax.random.PRNGKey(7)
    sc = jax.random.normal(key, (2, 9, 7, n_out))
    gamma = jax.random.normal(jax.random.fold_in(key, 1), (n_out,))
    beta = jax.random.normal(jax.random.fold_in(key, 2), (n_out,))
    kw = dict(x_scale=0.03, w_scale=qt.scale.reshape(-1), gamma=gamma,
              beta=beta, shortcut=sc, relu=True)
    for mode in ("jnp", "interpret"):
        monkeypatch.setenv("REPRO_PALLAS", mode)
        y_u = ops.conv2d(x, qt.values, k, stride, **kw)
        y_t = ops.conv2d(x, qt.values, k, stride, strip_h=4, **kw)
        np.testing.assert_array_equal(np.asarray(y_t), np.asarray(y_u))


# ---------------------------------------------------------------------------
# Strip planner
# ---------------------------------------------------------------------------

def test_strip_geometry_halo_math():
    """slab_h = (strip_h-1)*stride + k, strips advance strip_h*stride
    input rows, and x_rows covers the last strip's slab."""
    g = strip_geometry(k=3, stride=1, h_out=10, w_out=10, strip_h=4)
    assert (g.n_strips, g.slab_h, g.row_step) == (3, 6, 4)
    assert g.x_rows == 2 * 4 + 6                       # last slab in bounds
    assert (g.ms, g.ms_pad) == (40, 40)
    g = strip_geometry(k=7, stride=2, h_out=112, w_out=112, strip_h=5)
    assert (g.slab_h, g.row_step) == (4 * 2 + 7, 10)   # k-stride halo = 5
    assert g.n_strips == -(-112 // 5)
    # degenerate: one strip == the untiled kernel's whole-image residency
    g1 = strip_geometry(k=3, stride=1, h_out=7, w_out=7, strip_h=7)
    assert (g1.n_strips, g1.slab_h) == (1, 9)


def test_plan_strips_budget():
    """The planner maximizes strip_h under the budget, degenerates to one
    strip for small maps, and floors at single-row strips."""
    small = plan_strips(k=3, stride=1, h_out=7, w_out=7, c_in=512,
                        bn=128, weight_bytes=9 * 512 * 128)
    assert small.n_strips == 1                         # conv5_x fits whole
    big = plan_strips(k=7, stride=2, h_out=112, w_out=112, c_in=3,
                      bn=64, weight_bytes=7 * 7 * 3 * 64)
    assert big.n_strips > 1 and big.cell_bytes <= DEFAULT_VMEM_BUDGET
    bigger = plan_strips(k=7, stride=2, h_out=112, w_out=112,
                         c_in=3, bn=64, weight_bytes=7 * 7 * 3 * 64,
                         budget=big.cell_bytes + (1 << 16))
    assert bigger.strip_h >= big.strip_h               # monotone in budget
    floor = plan_strips(k=3, stride=1, h_out=64, w_out=64, c_in=64,
                        bn=128, weight_bytes=9 * 64 * 128, budget=1)
    assert floor.strip_h == 1
    forced = plan_strips(k=3, stride=1, h_out=10, w_out=10, c_in=8,
                         bn=16, weight_bytes=9 * 8 * 16, strip_h=4)
    assert (forced.strip_h, forced.n_strips) == (4, 3)


def test_planner_default_untouched_for_resnet50_geoms(monkeypatch):
    """With no override, ResNet50-sized test geometries plan a single
    strip under the default budget, so the serving path is byte-for-byte
    the pre-tiling launch (and the compiled ResNet keeps matching the
    dense path end to end, see test_conv.py)."""
    p = plan_strips(k=3, stride=1, h_out=14, w_out=14, c_in=64,
                    bn=128, weight_bytes=9 * 64 * 128)
    assert p.n_strips == 1


# ---------------------------------------------------------------------------
# Channel lane tiles
# ---------------------------------------------------------------------------

def test_conv_lane_tile_whole_axis():
    """A channel axis with no 128-lane divisor is one whole-axis block
    when its cell fits the VMEM budget, and is padded to 128-lane tiles
    only when it does not; axes with a 128-lane divisor keep it."""
    fits = lambda bn: plan_strips(k=1, stride=1, h_out=7, w_out=7, c_in=8,
                                  bn=bn, weight_bytes=0)
    too_big = lambda bn: dataclasses.replace(
        fits(bn), cell_bytes=DEFAULT_VMEM_BUDGET + 1)
    assert ops._conv_lane_tile(144, fits) == (144, 144)
    assert ops._conv_lane_tile(960, fits) == (960, 960)
    assert ops._conv_lane_tile(144, too_big) == (128, 256)
    assert ops._conv_lane_tile(256, fits) == (128, 256)
    assert ops._conv_lane_tile(96, too_big) == (96, 96)


@pytest.mark.parametrize("stride", [1, 2])
def test_whole_axis_lane_tile_bit_identical(stride, monkeypatch):
    """144 channels (mobilenet_v2's expand width) launch as one
    whole-axis block, unpadded: dense and depthwise kernels in interpret
    mode equal the jnp oracles bit for bit, with and without strips."""
    C = 144
    x, qt = _conv_case(3, 9, 7, C=8, n_out=C)
    key = jax.random.PRNGKey(5)
    xd = jax.random.randint(key, (2, 9, 7, C), -127, 128, jnp.int8)
    wd = jax.random.randint(jax.random.fold_in(key, 1), (9, C), -63, 64,
                            jnp.int8)
    kw = dict(x_scale=1.0, w_scale=jnp.ones((C,)), relu=False)
    outs = {}
    for mode in ("jnp", "interpret"):
        monkeypatch.setenv("REPRO_PALLAS", mode)
        for strip_h in (None, 2):
            outs[mode, strip_h] = (
                ops.conv2d(x, qt.values, 3, stride, strip_h=strip_h, **kw),
                ops.conv2d_dw(xd, wd, 3, stride, strip_h=strip_h, **kw))
    want = outs["jnp", None]
    for got in outs.values():
        for g_, w_ in zip(got, want):
            np.testing.assert_array_equal(np.asarray(g_), np.asarray(w_))
