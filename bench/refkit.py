"""Pieces that the configurations' plain references share.  Plain
``jax.numpy``; nothing of the system under test."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


class _Draw:
    """Hands out consecutive slices of three flat draws — conv and
    classifier weights, BN scales, BN biases — so that a whole tree comes
    from three random calls.  Without ``bufs`` it only counts."""

    def __init__(self, bufs=None):
        self.bufs = bufs
        self.used = {"w": 0, "scale": 0, "bias": 0}

    def __call__(self, kind, *shape):
        n = math.prod(shape)
        o = self.used[kind]
        self.used[kind] += n
        return None if self.bufs is None else \
            self.bufs[kind][o:o + n].reshape(shape)

    def weight(self, fan_in, *shape):
        """A weight drawn normal, clipped at two standard deviations and
        scaled by 1/sqrt(fan-in)."""
        w = self("w", *shape)
        return None if w is None else w / math.sqrt(fan_in)


def draw_all(key, build):
    """``build(draw)`` twice: once to count, once with the draws — weights
    normal clipped at +-2, folded-BN scales uniform in [0.8, 1.2), biases
    normal with standard deviation 0.05."""
    count = _Draw()
    build(count)
    kw, ks, kb = jax.random.split(key, 3)
    n = count.used
    return build(_Draw({
        "w": jnp.clip(jax.random.normal(kw, (n["w"],)), -2.0, 2.0),
        "scale": jax.random.uniform(ks, (n["scale"],), minval=0.8,
                                    maxval=1.2),
        "bias": 0.05 * jax.random.normal(kb, (n["bias"],))}))


def fake_quant(x, bits: int, axes):
    """Symmetric ``bits``-bit quantization and back, one scale per slice
    that ``axes`` reduces over."""
    q = 2 ** (bits - 1) - 1
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=axes, keepdims=True),
                    1e-12) / q
    return jnp.clip(jnp.round(x / s), -q, q) * s
