"""Plain float32 reference of ResNet-50 (He et al. 2015, arXiv:1512.03385).

Written from the paper's Table 1 (50-layer column) in plain ``jax.numpy``
/ ``lax`` with every contraction at ``Precision.HIGHEST``: no kernels, no
quantization, no batching tricks.  It imports nothing of the system under
test and is given only float weights that the benchmark made from the
seed.

Architecture, as served (``resnet50.json``):

* stem: 7x7/2 conv, folded BatchNorm (per-channel scale and bias), ReLU,
  3x3/2 max pool, SAME padding throughout;
* 16 bottleneck blocks in four stages (3, 4, 6, 3): 1x1 -> 3x3 -> 1x1
  convs, each followed by folded BN, ReLU after the first two; the
  shortcut (a 1x1 projection with folded BN and no ReLU in the first
  block of a stage, the identity elsewhere) is added before the last
  ReLU.  The stage-entry stride sits on the first 1x1 conv and on the
  projection, as in the paper's original model;
* global average pool, then a bias-free 1000-way linear classifier.

Weights are stored as the benchmark makes them: a conv weight is a flat
``(c_in*k*k, c_out)`` matrix whose rows run channel-major over the k*k
taps (row ``c*k*k + dy*k + dx``); ``scale``/``bias`` are the folded BN.

``forward(..., bits=4)`` is the control of the correctness check: the
same forward with every conv and classifier input quantized per image,
and every weight per output channel, to symmetric ``bits``-bit integers.
"""
from __future__ import annotations

import jax.numpy as jnp
from jax import lax

import refkit

HI = lax.Precision.HIGHEST


def _stem_width(c: dict) -> int:
    return max(8, int(c["stem_channels"] * c["width_mult"]))


def _stages(c: dict) -> list:
    """[(name, blocks, mid, out)] at the config's width multiplier."""
    w = c["width_mult"]
    return [(f"conv{i + 2}_x", n, max(8, int(mid * w)),
             max(8, int(mid * c["expansion"] * w)))
            for i, (n, mid) in enumerate(c["stages"])]


def layers(c: dict) -> list:
    """Every conv and the classifier in forward order, with the input
    feature-map side ``hw_in`` it reads."""
    out = []
    hw = c["in_hw"]
    w0 = _stem_width(c)
    out.append(dict(name="stem", op="conv", k=7, stride=2, c_in=3,
                    c_out=w0, hw_in=hw))
    hw = -(-hw // 2)                  # stem conv
    hw = -(-hw // 2)                  # max pool
    c_in = w0
    for name, n_blocks, mid, c_out in _stages(c):
        for b in range(n_blocks):
            s = 2 if (b == 0 and name != "conv2_x") else 1
            u = f"{name}_{b + 1}"
            if b == 0:
                out.append(dict(name=f"{u}/sc", op="conv", k=1, stride=s,
                                c_in=c_in, c_out=c_out, hw_in=hw))
            out.append(dict(name=f"{u}/a", op="conv", k=1, stride=s,
                            c_in=c_in, c_out=mid, hw_in=hw))
            hw = -(-hw // s)
            out.append(dict(name=f"{u}/b", op="conv", k=3, stride=1,
                            c_in=mid, c_out=mid, hw_in=hw))
            out.append(dict(name=f"{u}/c", op="conv", k=1, stride=1,
                            c_in=mid, c_out=c_out, hw_in=hw))
            c_in = c_out
    out.append(dict(name="head", op="head", k=1, stride=1, c_in=c_in,
                    c_out=c["num_classes"], hw_in=hw))
    return out


def _conv_init(draw, c_in, c_out, k):
    return {"w": draw.weight(c_in * k * k, c_in * k * k, c_out),
            "scale": draw("scale", c_out), "bias": draw("bias", c_out)}


def init(key, c: dict) -> dict:
    """Float32 weights from ``key``, as ``refkit.draw_all`` draws them."""

    def build(draw):
        params = {"stem": _conv_init(draw, 3, _stem_width(c), 7)}
        c_in = _stem_width(c)
        for name, n_blocks, mid, c_out in _stages(c):
            blocks = []
            for b in range(n_blocks):
                blk = {"a": _conv_init(draw, c_in, mid, 1),
                       "b": _conv_init(draw, mid, mid, 3),
                       "c": _conv_init(draw, mid, c_out, 1)}
                if b == 0:
                    blk["sc"] = _conv_init(draw, c_in, c_out, 1)
                blocks.append(blk)
                c_in = c_out
            params[name] = blocks
        params["head"] = {"w": draw.weight(c_in, c_in, c["num_classes"])}
        return params

    return refkit.draw_all(key, build)


def _conv(p, x, k, stride, bits, relu=True, shortcut=None):
    c_in = x.shape[-1]
    w = p["w"]
    if bits:
        x = refkit.fake_quant(x, bits, (1, 2, 3))
        w = refkit.fake_quant(w, bits, (0,))
    hwio = w.reshape(c_in, k, k, -1).transpose(1, 2, 0, 3)
    y = lax.conv_general_dilated(x, hwio, (stride, stride), "SAME",
                                 dimension_numbers=("NHWC", "HWIO", "NHWC"),
                                 precision=HI)
    y = y * p["scale"] + p["bias"]
    if shortcut is not None:
        y = y + shortcut
    return jnp.maximum(y, 0.0) if relu else y


def forward(params: dict, x, c: dict, bits: int | None = None):
    """(N, H, W, 3) float32 images -> (N, num_classes) float32 logits."""
    h = _conv(params["stem"], x, 7, 2, bits)
    h = lax.reduce_window(h, -jnp.inf, lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
                          "SAME")
    for name, _, _, _ in _stages(c):
        for b, blk in enumerate(params[name]):
            s = 2 if (b == 0 and name != "conv2_x") else 1
            sc = _conv(blk["sc"], h, 1, s, bits, relu=False) \
                if "sc" in blk else h
            y = _conv(blk["a"], h, 1, s, bits)
            y = _conv(blk["b"], y, 3, 1, bits)
            h = _conv(blk["c"], y, 1, 1, bits, shortcut=sc)
    pooled = jnp.mean(h, axis=(1, 2))
    w = params["head"]["w"]
    if bits:
        pooled = refkit.fake_quant(pooled, bits, (1,))
        w = refkit.fake_quant(w, bits, (0,))
    return jnp.dot(pooled, w, precision=HI)
