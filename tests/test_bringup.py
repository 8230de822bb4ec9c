"""No hidden fallbacks on the way to the chip: the smoke check's device
guard, the lowering switch, the device-layout helpers and the
sparse_cfmm refusal — all decided on the CPU, with the platform steered
inside the test."""
import importlib.util
import pathlib
import types

import jax
import jax.numpy as jnp
import pytest

from repro.kernels import ops
from repro.launch import compile_cache, mesh

ROOT = pathlib.Path(__file__).resolve().parents[1]


def _chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_chip_smoke_refuses_without_a_tpu():
    assert jax.devices()[0].platform == "cpu"
    with pytest.raises(SystemExit) as e:
        _chip_smoke().require_tpu()
    assert "no TPU found" in str(e.value.code)


@pytest.mark.parametrize("bad", ["interpet", "TPU", "pallas", ""])
def test_unknown_lowering_mode_raises(monkeypatch, bad):
    monkeypatch.setenv("REPRO_PALLAS", bad)
    with pytest.raises(ValueError, match="REPRO_PALLAS"):
        ops._mode()


@pytest.mark.parametrize("mode", ["tpu", "interpret", "jnp"])
def test_known_lowering_modes_pass_through(monkeypatch, mode):
    monkeypatch.setenv("REPRO_PALLAS", mode)
    assert ops._mode() == mode


def test_auto_lowering_is_jnp_on_cpu(monkeypatch):
    monkeypatch.delenv("REPRO_PALLAS", raising=False)
    assert ops._mode() == "jnp"
    monkeypatch.setenv("REPRO_PALLAS", "auto")
    assert ops._mode() == "jnp"


def _fake(platform, i):
    return types.SimpleNamespace(platform=platform, id=i)


def test_device_helpers_raise_on_short_accelerator():
    chips = [_fake("tpu", 0)]
    with pytest.raises(ValueError, match="needs 4 tpu devices"):
        mesh.pipeline_stage_devices(4, devices=chips)
    with pytest.raises(ValueError, match="needs 4 tpu devices"):
        mesh.replica_pipeline_devices(2, 2, devices=chips)
    four = [_fake("tpu", i) for i in range(4)]
    assert [d.id for d in mesh.pipeline_stage_devices(4, devices=four)] \
        == [0, 1, 2, 3]
    groups = mesh.replica_pipeline_devices(2, 2, devices=four)
    assert [[d.id for d in g] for g in groups] == [[0, 1], [2, 3]]


def test_device_helpers_wrap_only_on_cpu():
    cpu = [_fake("cpu", 0)]
    assert [d.id for d in mesh.pipeline_stage_devices(3, devices=cpu)] \
        == [0, 0, 0]
    assert [[d.id for d in g] for g in
            mesh.replica_pipeline_devices(2, 2, devices=cpu)] \
        == [[0, 0], [0, 0]]


def test_sparse_conv_is_refused_on_tpu(monkeypatch):
    """Bitmap-packed weights raise the typed error under the TPU lowering
    instead of reaching a dense or jnp path."""
    monkeypatch.setenv("REPRO_PALLAS", "tpu")
    x = jnp.zeros((1, 8, 8, 8), jnp.int8)
    bitmap = jnp.zeros((9, 16), jnp.uint8)
    values = jnp.zeros((4, 16), jnp.int8)
    with pytest.raises(ops.UnsupportedOnTPU):
        ops.conv2d(x, (bitmap, values), 3, 1, x_scale=1.0,
                   w_scale=jnp.ones((16,)))
    with pytest.raises(ops.UnsupportedOnTPU):
        ops.sparse_cfmm_matmul(jnp.zeros((2, 72), jnp.int8), bitmap, values)


def test_sparse_serving_is_refused_before_packing(monkeypatch):
    """The serving front door refuses sparse_cfmm under the TPU lowering
    before it prunes or packs a single leaf."""
    from repro.core import compiled_linear as cl
    from repro.models import resnet
    from repro.serving.frontend import ResNetFrontend
    cfg = resnet.ResNetConfig(width_mult=0.125, num_classes=10, in_hw=16)
    params = resnet.init(jax.random.PRNGKey(0), cfg)
    packed = []
    monkeypatch.setattr(cl, "compile_params",
                        lambda *a, **k: packed.append(a))
    monkeypatch.setenv("REPRO_PALLAS", "tpu")
    with pytest.raises(ops.UnsupportedOnTPU):
        ResNetFrontend(cfg, params, mode="sparse_cfmm")
    assert packed == []


def test_compile_cache_dir(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR wins untouched; otherwise one fixed,
    git-ignored path inside the checkout."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == "/elsewhere/cache"
    assert jax.config.jax_compilation_cache_dir == before
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    try:
        path = compile_cache.enable_compile_cache()
        assert path == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().split()
