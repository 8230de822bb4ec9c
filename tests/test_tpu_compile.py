"""Mosaic compiles of the conv kernels at published shapes, for a v5e chip
that is described, not attached.

Interpret mode accepts code that Mosaic refuses (block shapes off the
(8, 128) rule, in-register strided slices, unlowered primitives, scoped
VMEM overruns), so these cases compile each kernel exactly as
``ops.conv2d`` / ``ops.conv2d_dw`` launch it on the chip: the serving
path's own wrapper under ``REPRO_PALLAS=tpu``, shapes only, no arrays.
Nothing runs, so nothing here says anything about results or times.

The TPU library may be loaded by one process at a time, so the topology
is described inside a module fixture (never at import) and every case
lives in this one file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops, ref, tiling


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    # the TPU compiler writes its logs under /tmp unless told otherwise
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:        # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a described chip's compiles cannot be read back from the
    # persistent cache without the chip: keep them out of it
    cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", cache)
    compilation_cache.reset_cache()


@pytest.fixture
def tpu_lowering(monkeypatch):
    monkeypatch.setenv("REPRO_PALLAS", "tpu")


# (hw, c_in, c_out, k, stride, shortcut) — ResNet50 at width 1.0 and
# 224x224 with a microbatch of 2; "3x3_s2" is the strided 3x3 of the
# v1.5 bottleneck (56x56x128 -> 28x28), the shape repvgg/mobilenet-style
# strided 3x3s take on the same kernel
CONV_CASES = {
    "stem_7x7_s2": (224, 3, 64, 7, 2, False),
    "conv3_x_3x3_s1_whole_image": (28, 128, 128, 3, 1, False),
    "conv2_x_3x3_s1_strip_tiled": (56, 64, 64, 3, 1, False),
    "3x3_s2": (56, 128, 128, 3, 2, False),
    "conv3_x_1x1_s2_projection": (56, 256, 512, 1, 2, False),
    "conv5_x_3x3_on_7x7": (7, 512, 512, 3, 1, False),
    "conv4_x_1x1_with_shortcut": (14, 256, 1024, 1, 1, True),
}


def _compile_conv(sharding, hw, c_in, c_out, k, stride, shortcut,
                  zero_count=None):
    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=sharding)
    _, _, h_out = ref.same_pads(hw, k, stride)
    args = [S((2, hw, hw, c_in), jnp.int8), S((k * k * c_in, c_out), jnp.int8),
            S((2,), jnp.float32), S((c_out,), jnp.float32),
            S((c_out,), jnp.float32), S((c_out,), jnp.float32)]
    if shortcut:
        args.append(S((2, h_out, h_out, c_out), jnp.float32))

    def f(x, w, xs, ws, g, b, sc=None):
        return ops.conv2d(x, w, k, stride, x_scale=xs, w_scale=ws, gamma=g,
                          beta=b, shortcut=sc, quant_out=not shortcut,
                          w_layout="spatial", zero_count=zero_count)

    compiled = jax.jit(f).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("case", list(CONV_CASES))
def test_conv_implicit_compiles_for_v5e(one_chip, tpu_lowering, case):
    hw, c_in, c_out, k, stride, shortcut = CONV_CASES[case]
    if case.endswith("strip_tiled"):      # the planner really tiles it
        _, _, h_out = ref.same_pads(hw, k, stride)
        plan = tiling.plan_strips(
            k=k, stride=stride, h_out=h_out, w_out=h_out, c_in=c_in,
            bn=c_out, weight_bytes=tiling.vmem_bytes((k * k, c_in, c_out), 1))
        assert plan.n_strips > 1, plan
    _compile_conv(one_chip, hw, c_in, c_out, k, stride, shortcut)


def test_conv_zero_count_profile_compiles_for_v5e(one_chip, tpu_lowering):
    """The opt-in sparsity-profiling outputs (DESIGN.md §11)."""
    _compile_conv(one_chip, 14, 256, 256, 3, 1, False, zero_count=8)


# mobilenet_v2's 1x1 expand convs whose width has no 128-lane divisor:
# one whole-axis channel block, unpadded
MBV2_EXPAND_CASES = {
    "mbv2_1x1_expand_144": (56, 24, 144, 1, 1, False),
    "mbv2_1x1_expand_960": (7, 160, 960, 1, 1, False),
}


@pytest.mark.parametrize("case", list(MBV2_EXPAND_CASES))
def test_conv_whole_axis_lanes_compile_for_v5e(one_chip, tpu_lowering,
                                               case):
    _compile_conv(one_chip, *MBV2_EXPAND_CASES[case])


@pytest.mark.parametrize("hw,c,stride", [(56, 144, 1), (56, 144, 2),
                                         (7, 960, 1)],
                         ids=["dw_3x3_s1", "dw_3x3_s2", "dw_3x3_960"])
def test_conv_depthwise_compiles_for_v5e(one_chip, tpu_lowering, hw, c,
                                         stride):
    """mobilenet_v2's depthwise 3x3s: 144 channels at 56x56, both
    strides, and 960 channels at 7x7, each one whole-axis block."""
    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    def f(x, w, xs, ws, g, b):
        return ops.conv2d_dw(x, w, 3, stride, x_scale=xs, w_scale=ws,
                             gamma=g, beta=b, quant_out=True)

    compiled = jax.jit(f).lower(
        S((2, hw, hw, c), jnp.int8), S((9, c), jnp.int8),
        S((2,), jnp.float32), S((c,), jnp.float32), S((c,), jnp.float32),
        S((c,), jnp.float32)).compile()
    assert "tpu_custom_call" in compiled.as_text()
