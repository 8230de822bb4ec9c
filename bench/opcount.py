"""Operations and least bytes of each layer, from its shapes alone.

The count does not depend on how a kernel is written.  Operations are
2 x multiply-accumulates.  Bytes are what the layer has to move at the
least: its int8 input map, its int8 weights and its int8 output map, once
each per call (the classifier's output is float32 logits).  So a kernel
that writes float32 and reads it back, copies its input, or fetches a
weight twice is measured against this floor, and no change to a kernel
can make the count stale.

A layer is a dict as the configurations' references give it
(``layers(cfg)``): ``op`` in ``conv`` / ``dwconv`` / ``head``, ``k``,
``stride``, ``c_in``, ``c_out`` and the input side ``hw_in``.
"""
from __future__ import annotations


def out_hw(layer: dict) -> int:
    return -(-layer["hw_in"] // layer["stride"])


def macs_per_image(layer: dict) -> int:
    k2 = layer["k"] ** 2
    if layer["op"] == "conv":
        return out_hw(layer) ** 2 * layer["c_out"] * layer["c_in"] * k2
    if layer["op"] == "dwconv":
        return out_hw(layer) ** 2 * layer["c_out"] * k2
    if layer["op"] == "head":
        return layer["c_in"] * layer["c_out"]
    raise ValueError(f"{layer['name']}: unknown op {layer['op']!r}")


def weight_bytes(layer: dict) -> int:
    k2 = layer["k"] ** 2
    if layer["op"] == "dwconv":
        return k2 * layer["c_out"]
    return k2 * layer["c_in"] * layer["c_out"]


def call_cost(layer: dict, n: int) -> tuple[int, int]:
    """(operations, least bytes) of one call on a batch of ``n`` images."""
    in_b = layer["hw_in"] ** 2 * layer["c_in"]
    if layer["op"] == "head":
        out_b = 4 * layer["c_out"]
    else:
        out_b = out_hw(layer) ** 2 * layer["c_out"]
    return (2 * macs_per_image(layer) * n,
            n * (in_b + out_b) + weight_bytes(layer))


def least_seconds(layer: dict, n: int, pk: dict) -> float:
    """The least time one call can take on a chip with peaks ``pk``: the
    larger of its operations over the int8 peak and its bytes over the
    HBM bandwidth."""
    ops, nbytes = call_cost(layer, n)
    return max(ops / pk["int8_ops_per_s"], nbytes / pk["hbm_bytes_per_s"])


def model_ops_per_image(layers: list) -> int:
    return sum(2 * macs_per_image(l) for l in layers)
