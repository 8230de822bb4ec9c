"""Operation and byte counts from shapes (opcount.py) against hand counts,
and the references' layer lists against the program's own graph."""
import pytest

import opcount
import spec


def _layer(op, k, stride, c_in, c_out, hw_in):
    return dict(name="x", op=op, k=k, stride=stride, c_in=c_in,
                c_out=c_out, hw_in=hw_in)


def test_plain_conv_by_hand():
    # 3x3, 64 -> 64 on 56x56: 56*56 outputs x 64 x 64 x 9 MACs
    l = _layer("conv", 3, 1, 64, 64, 56)
    assert opcount.macs_per_image(l) == 56 * 56 * 64 * 64 * 9 == 115605504
    ops, nbytes = opcount.call_cost(l, 2)
    assert ops == 2 * 2 * 115605504
    # two images in and out (int8) and one copy of the weights
    assert nbytes == 2 * (56 * 56 * 64 * 2) + 9 * 64 * 64


def test_strided_conv_by_hand():
    # 1x1/2, 256 -> 512 on 56x56 reads 56x56 and writes 28x28
    l = _layer("conv", 1, 2, 256, 512, 56)
    assert opcount.out_hw(l) == 28
    assert opcount.macs_per_image(l) == 28 * 28 * 256 * 512
    _, nbytes = opcount.call_cost(l, 1)
    assert nbytes == 56 * 56 * 256 + 28 * 28 * 512 + 256 * 512
    # the stem: 7x7/2 on 224 with SAME padding -> 112
    stem = _layer("conv", 7, 2, 3, 64, 224)
    assert opcount.macs_per_image(stem) == 112 * 112 * 64 * 3 * 49


def test_depthwise_conv_by_hand():
    # 3x3/2 depthwise over 96 channels of 112x112 -> 56x56
    l = _layer("dwconv", 3, 2, 96, 96, 112)
    assert opcount.macs_per_image(l) == 56 * 56 * 96 * 9
    _, nbytes = opcount.call_cost(l, 1)
    assert nbytes == 112 * 112 * 96 + 56 * 56 * 96 + 9 * 96


def test_least_seconds_takes_the_larger_bound():
    pk = {"int8_ops_per_s": 1e12, "hbm_bytes_per_s": 1e9}
    l = _layer("conv", 1, 1, 8, 8, 4)
    ops, nbytes = opcount.call_cost(l, 1)
    assert opcount.least_seconds(l, 1, pk) == max(ops / 1e12, nbytes / 1e9)


@pytest.mark.parametrize("name,gmac,weight_mb,n_conv,n_dw", [
    ("resnet50", 3.86, 25.5, 53, 0),
    ("mobilenet_v2", 0.30, 3.5, 35, 17),
])
def test_whole_model_totals(name, gmac, weight_mb, n_conv, n_dw):
    layers = spec.reference(name).layers(spec.config(name))
    macs = sum(opcount.macs_per_image(l) for l in layers)
    wbytes = sum(opcount.weight_bytes(l) for l in layers)
    assert macs / 1e9 == pytest.approx(gmac, abs=0.005)
    assert wbytes / 1e6 == pytest.approx(weight_mb, abs=0.05)
    assert sum(l["op"] == "conv" for l in layers) == n_conv
    assert sum(l["op"] == "dwconv" for l in layers) == n_dw
    assert opcount.model_ops_per_image(layers) == 2 * macs


def test_mobilenet_depthwise_share():
    layers = spec.reference("mobilenet_v2").layers(
        spec.config("mobilenet_v2"))
    dw = sum(opcount.macs_per_image(l) for l in layers
             if l["op"] == "dwconv")
    assert dw / 1e9 == pytest.approx(0.021, abs=0.001)


@pytest.mark.parametrize("name", ["resnet50", "mobilenet_v2"])
def test_reference_layers_match_program_graph(name):
    """The reference's layers are the program's conv, depthwise and
    classifier nodes, in order, with the same geometry and input size."""
    import run
    cfg = spec.config(name)
    graph = run.program_config(cfg).graph()
    info = graph.shapes()
    nodes = [n for n in graph.topo_order()
             if n.op in ("conv", "dwconv", "head")]
    layers = spec.reference(name).layers(cfg)
    assert sorted(l["name"] for l in layers) == sorted(n.name for n in nodes)
    by_name = {l["name"]: l for l in layers}
    for n in nodes:
        l = by_name[n.name]
        src = info[n.inputs[0]]
        assert (l["op"], l["hw_in"], l["c_in"]) == (n.op, src.hw, src.ch)
        if n.op != "head":
            assert (l["k"], l["stride"], l["c_out"]) == (n.k, n.stride,
                                                         n.c_out)
