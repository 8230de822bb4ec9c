"""Pallas kernels vs pure-jnp oracles (interpret mode), shape/dtype sweeps."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import compiled_linear as cl
from repro.core.quantize import quantize_int7
from repro.kernels import ops, ref


@pytest.mark.parametrize("M,K,N", [(8, 512, 128), (128, 1024, 256),
                                   (100, 960, 384), (1, 512, 128),
                                   (17, 2048, 128)])
def test_cfmm_matmul_kernel_exact(M, K, N):
    key = jax.random.PRNGKey(M * K + N)
    x = jax.random.randint(key, (M, K), -127, 128, jnp.int8)
    qt = quantize_int7(jax.random.normal(key, (K, N)))
    y = ops.cfmm_matmul(x, qt.values)
    np.testing.assert_array_equal(np.asarray(y),
                                  np.asarray(ref.int8_matmul_ref(x, qt.values)))


def test_tile_pad_prime_dims_not_degenerate():
    """Regression for the _largest_tile pathology: a prime axis above the
    tile cap used to degrade to tile size 1 (one grid cell per column);
    _tile_pad pads to the next cap multiple instead."""
    from repro.kernels.ops import _tile_pad
    assert _tile_pad(131, 128) == (128, 256)           # prime N
    assert _tile_pad(1031, 512) == (512, 1536)         # prime K
    assert _tile_pad(134, 128) == (128, 256)           # 2*67: tile 67 is
    assert _tile_pad(128, 128) == (128, 128)           # no sublane multiple
    assert _tile_pad(96, 128) == (96, 96)              # fits: single tile
    assert _tile_pad(256, 128) == (128, 256)
    assert _tile_pad(192, 128) == (128, 256)           # no 128-lane divisor
    assert _tile_pad(1536, 512) == (512, 1536)         # clean divisor kept
    assert _tile_pad(640, 512) == (128, 640)           # 128-lane divisor
    # 8*prime: the largest divisor is a sliver tile of 8 — pad instead
    assert _tile_pad(8 * 131, 128) == (128, 1152)
    assert _tile_pad(8 * 521, 512) == (512, 4608)


def test_cfmm_matmul_prime_dims_exact():
    """Prime K and N run the padded-tile path and stay exact (the zero
    pad rows/cols vanish under int8 matmul)."""
    key = jax.random.PRNGKey(4)
    M, K, N = 4, 1031, 131
    x = jax.random.randint(key, (M, K), -127, 128, jnp.int8)
    qt = quantize_int7(jax.random.normal(key, (K, N)))
    y = ops.cfmm_matmul(x, qt.values)
    np.testing.assert_array_equal(np.asarray(y),
                                  np.asarray(ref.int8_matmul_ref(x, qt.values)))
    ys = ops.cfmm_matmul(x, qt.values, qt.scale.reshape(1, N))
    np.testing.assert_allclose(
        np.asarray(ys),
        np.asarray(ref.int8_matmul_ref(x, qt.values), np.float32)
        * np.asarray(qt.scale.reshape(1, N)), rtol=1e-6)


def test_sparse_matvec_prime_n_exact():
    """Prime N through the bitmap kernel: padded zero bitmap columns
    expand to zero codes, sliced off after the launch."""
    key = jax.random.PRNGKey(6)
    K, N, keep = 512, 131, 104
    qt = cl.balanced_prune_codes(jax.random.normal(key, (K, N)), keep)
    bitmap, values = cl.bitmap_pack(qt.values, keep)
    x = jax.random.randint(key, (4, K), -127, 128, jnp.int8)
    y = ops.sparse_cfmm_matmul(x, bitmap, values)
    np.testing.assert_array_equal(
        np.asarray(y), np.asarray(ref.sparse_matvec_ref(x, bitmap, values)))


def test_conv_prime_n_out_exact():
    """Prime c_out > 128 through the conv kernel pads channels to the
    lane tile and slices back — bit-identical to the jnp oracle."""
    k, C, n_out = 3, 8, 131
    key = jax.random.PRNGKey(8)
    x = jax.random.randint(key, (1, 8, 8, C), -127, 128, jnp.int8)
    qt = quantize_int7(jax.random.normal(key, (C * k * k, n_out)) * 0.1)
    y = ops.conv2d(x, qt.values, k, 1, x_scale=1.0,
                   w_scale=jnp.ones((n_out,)), relu=False)
    acc = ref.conv2d_int8_ref(x, qt.values, k, 1)
    np.testing.assert_array_equal(np.asarray(y),
                                  np.asarray(acc).astype(np.float32))


@pytest.mark.parametrize("M,K,N", [(8, 512, 128), (4, 1024, 256)])
def test_cfmm_matmul_fused_scale(M, K, N):
    key = jax.random.PRNGKey(0)
    x = jax.random.randint(key, (M, K), -127, 128, jnp.int8)
    qt = quantize_int7(jax.random.normal(key, (K, N)))
    scale = qt.scale.reshape(1, N)
    y = ops.cfmm_matmul(x, qt.values, scale)
    expect = np.asarray(ref.int8_matmul_ref(x, qt.values), np.float32) * \
        np.asarray(scale)
    np.testing.assert_allclose(np.asarray(y), expect, rtol=1e-6)


@pytest.mark.parametrize("M,K,N,s", [(8, 1024, 128, 0.8), (4, 2048, 256, 0.9),
                                     (8, 960, 128, 0.8), (1, 512, 128, 0.5)])
def test_sparse_matvec_kernel_exact(M, K, N, s):
    key = jax.random.PRNGKey(K + N)
    w = jax.random.normal(key, (K, N))
    keep = max(8, int(K * (1 - s)) // 8 * 8)
    qt = cl.balanced_prune_codes(w, keep)
    bitmap, values = cl.bitmap_pack(qt.values, keep)
    x = jax.random.randint(key, (M, K), -127, 128, jnp.int8)
    y = ops.sparse_cfmm_matmul(x, bitmap, values)
    np.testing.assert_array_equal(
        np.asarray(y), np.asarray(ref.sparse_matvec_ref(x, bitmap, values)))


@pytest.mark.parametrize("M,K,N", [(64, 512, 256), (128, 256, 384),
                                   (8, 256, 128)])
def test_block_sparse_kernel(M, K, N):
    key = jax.random.PRNGKey(7)
    w = np.array(jax.random.normal(key, (K, N)))
    w[:128, :128] = 0.0             # whole-block zeros get dropped
    if K >= 512:
        w[256:384, :] = 0.0
    x = jax.random.normal(jax.random.fold_in(key, 1), (M, K))
    y = ops.block_sparse_matmul(x, jnp.asarray(w), (128, 128))
    ref_y = x @ jnp.asarray(w)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref_y),
                               rtol=2e-5, atol=2e-4)


def test_block_sparse_skips_zero_blocks():
    from repro.kernels.block_sparse import plan_blocks
    mask = np.zeros((4, 3), bool)
    mask[0, 0] = mask[2, 0] = mask[1, 2] = True
    meta = plan_blocks(mask)
    assert meta.shape == (4, 3)           # only 3 active of 12 blocks
    assert list(meta[1]) == [0, 0, 2]     # column-major n order
    assert list(meta[2]) == [1, 0, 1]     # first-of-column flags
    assert list(meta[3]) == [0, 1, 1]     # last-of-column flags


def test_bitmap_pack_storage_budget():
    w = jax.random.normal(jax.random.PRNGKey(0), (4096, 256))
    keep = 4096 // 5 // 8 * 8
    qt = cl.balanced_prune_codes(w, keep)
    bitmap, values = cl.bitmap_pack(qt.values, keep)
    bits_per_param = (bitmap.size + values.size) * 8 / (4096 * 256)
    assert bits_per_param < 2.7           # ~(1-s)*8 + 1 bits


@pytest.mark.parametrize("causal,window,G,Dv", [
    (True, None, 1, 32), (True, None, 4, 32), (False, None, 2, 32),
    (True, 64, 2, 32), (True, None, 2, 16)])
def test_flash_attention_kernel_vs_oracle(causal, window, G, Dv):
    import jax.numpy as jnp
    from repro.kernels.ops import flash_attention
    B, KVH, Tq, Tk, D = 1, 2, 128, 256, 32
    key = jax.random.PRNGKey(G * 7 + Dv)
    q = jax.random.normal(key, (B, KVH, G, Tq, D), jnp.float32)
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, KVH, Tk, D))
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, KVH, Tk, Dv))
    out = flash_attention(q, k, v, causal=causal, window=window)
    # oracle: naive softmax per (kv-head, group)
    qf = q.reshape(B, KVH * G, Tq, D)
    kf = jnp.repeat(k, G, axis=1).reshape(B, KVH * G, Tk, D)
    vf = jnp.repeat(v, G, axis=1).reshape(B, KVH * G, Tk, Dv)
    want = ref.flash_attention_ref(qf, kf, vf, causal=causal, window=window)
    np.testing.assert_allclose(
        np.asarray(out.reshape(B, KVH * G, Tq, Dv), np.float32),
        np.asarray(want, np.float32), rtol=2e-3, atol=2e-3)
