"""Plain float32 reference of MobileNetV2 (Sandler et al. 2018,
arXiv:1801.04381).

Written from the paper's Table 2 in plain ``jax.numpy`` / ``lax`` with
every contraction at ``Precision.HIGHEST``: no kernels, no quantization.
It imports nothing of the system under test and is given only float
weights that the benchmark made from the seed.

Architecture, as served (``mobilenet_v2.json``):

* stem: 3x3/2 conv to 32 channels, folded BatchNorm, ReLU;
* 17 inverted-residual blocks (Table 2 rows t, c, n, s): a 1x1 expansion
  to t*c_in channels with folded BN and ReLU (left out where t = 1), a
  3x3 depthwise conv with the row's stride on its first block, folded BN
  and ReLU, then a linear 1x1 projection with folded BN and no
  activation; the identity shortcut is added after the projection where
  the stride is 1 and c_in == c_out;
* a 1x1 conv to 1280 channels with folded BN and ReLU, global average
  pool, and a bias-free 1000-way linear classifier.

Departure from the paper, which the served model shares: ReLU where the
paper uses ReLU6.

Weight storage: dense convs as in ``resnet50.py`` (flat channel-major
``(c_in*k*k, c_out)``), a depthwise weight as ``(k*k, C)``, one row per
tap (row ``dy*k + dx``).  ``forward(..., bits=4)`` is the control: every
conv and classifier input quantized per image, every weight per output
channel, to symmetric ``bits``-bit integers.
"""
from __future__ import annotations

import jax.numpy as jnp
from jax import lax

import refkit

HI = lax.Precision.HIGHEST


def _ch(n: int, w: float) -> int:
    return max(8, (int(n * w) // 8) * 8)


def blocks(c: dict) -> list:
    """[(t, c_in, c_mid, c_out, stride)] for every inverted residual."""
    out = []
    c_in = _ch(c["stem_channels"], c["width_mult"])
    for t, ch, n, s in c["blocks"]:
        for i in range(n):
            c_out = _ch(ch, c["width_mult"])
            out.append((t, c_in, t * c_in, c_out, s if i == 0 else 1))
            c_in = c_out
    return out


def layers(c: dict) -> list:
    """Every conv, depthwise conv and the classifier in forward order,
    with the input feature-map side ``hw_in`` it reads."""
    hw = c["in_hw"]
    stem = _ch(c["stem_channels"], c["width_mult"])
    out = [dict(name="stem", op="conv", k=3, stride=2, c_in=3, c_out=stem,
                hw_in=hw)]
    hw = -(-hw // 2)
    for j, (t, c_in, c_mid, c_out, s) in enumerate(blocks(c)):
        u = f"block{j + 1}"
        if t != 1:
            out.append(dict(name=f"{u}/ex", op="conv", k=1, stride=1,
                            c_in=c_in, c_out=c_mid, hw_in=hw))
        out.append(dict(name=f"{u}/dw", op="dwconv", k=3, stride=s,
                        c_in=c_mid, c_out=c_mid, hw_in=hw))
        hw = -(-hw // s)
        out.append(dict(name=f"{u}/pj", op="conv", k=1, stride=1,
                        c_in=c_mid, c_out=c_out, hw_in=hw))
    tail = _ch(c["tail_channels"], c["width_mult"])
    out.append(dict(name="tail", op="conv", k=1, stride=1,
                    c_in=blocks(c)[-1][3], c_out=tail, hw_in=hw))
    out.append(dict(name="head", op="head", k=1, stride=1, c_in=tail,
                    c_out=c["num_classes"], hw_in=hw))
    return out


def _conv_init(draw, c_in, c_out, k):
    return {"w": draw.weight(c_in * k * k, c_in * k * k, c_out),
            "scale": draw("scale", c_out), "bias": draw("bias", c_out)}


def _dw_init(draw, ch, k):
    return {"w": draw.weight(k * k, k * k, ch),
            "scale": draw("scale", ch), "bias": draw("bias", ch)}


def init(key, c: dict) -> dict:
    """Float32 weights from ``key``, as ``refkit.draw_all`` draws them."""
    spec = blocks(c)

    def build(draw):
        params = {"stem": _conv_init(
            draw, 3, _ch(c["stem_channels"], c["width_mult"]), 3)}
        blks = []
        for t, c_in, c_mid, c_out, _ in spec:
            blk = {}
            if t != 1:
                blk["ex"] = _conv_init(draw, c_in, c_mid, 1)
            blk["dw"] = _dw_init(draw, c_mid, 3)
            blk["pj"] = _conv_init(draw, c_mid, c_out, 1)
            blks.append(blk)
        params["blocks"] = blks
        tail = _ch(c["tail_channels"], c["width_mult"])
        params["tail"] = _conv_init(draw, spec[-1][3], tail, 1)
        params["head"] = {"w": draw.weight(tail, tail, c["num_classes"])}
        return params

    return refkit.draw_all(key, build)


def _conv(p, x, k, stride, bits, relu=True, depthwise=False):
    ch = x.shape[-1]
    w = p["w"]
    if bits:
        x = refkit.fake_quant(x, bits, (1, 2, 3))
        w = refkit.fake_quant(w, bits, (0,))
    if depthwise:
        hwio, groups = w.reshape(k, k, 1, ch), ch
    else:
        hwio, groups = w.reshape(ch, k, k, -1).transpose(1, 2, 0, 3), 1
    y = lax.conv_general_dilated(x, hwio, (stride, stride), "SAME",
                                 dimension_numbers=("NHWC", "HWIO", "NHWC"),
                                 feature_group_count=groups, precision=HI)
    y = y * p["scale"] + p["bias"]
    return jnp.maximum(y, 0.0) if relu else y


def forward(params: dict, x, c: dict, bits: int | None = None):
    """(N, H, W, 3) float32 images -> (N, num_classes) float32 logits."""
    h = _conv(params["stem"], x, 3, 2, bits)
    for p, (t, c_in, _, c_out, s) in zip(params["blocks"], blocks(c)):
        y = _conv(p["ex"], h, 1, 1, bits) if t != 1 else h
        y = _conv(p["dw"], y, 3, s, bits, depthwise=True)
        y = _conv(p["pj"], y, 1, 1, bits, relu=False)
        h = y + h if (s == 1 and c_in == c_out) else y
    h = _conv(params["tail"], h, 1, 1, bits)
    pooled = jnp.mean(h, axis=(1, 2))
    w = params["head"]["w"]
    if bits:
        pooled = refkit.fake_quant(pooled, bits, (1,))
        w = refkit.fake_quant(w, bits, (0,))
    return jnp.dot(pooled, w, precision=HI)
