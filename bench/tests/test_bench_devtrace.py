"""The trace reduction (devtrace.py) and the readers built on it, on a
small synthetic trace whose answers are counted by hand."""
import types

import pytest

import devtrace
from devtrace import Event


def _trace():
    # window [100, 200) ns.  Device 0 ops: an implicit conv [100, 130),
    # a copy [125, 140) overlapping it, a depthwise conv [150, 170), and
    # an op that starts before the window [90, 105).  Busy: [100, 140)
    # and [150, 170) -> 60 of 100 ns.  Idle gaps: [140, 150), [170, 200).
    ops = [Event("%conv2d_implicit_pallas.1 = (f32[2]) custom-call()",
                 100, 130),
           Event("%copy.7 = s8[2] copy(s8[2] %x)", 125, 140),
           Event("%conv2d_dw_pallas.2 = (f32[2]) custom-call()", 150, 170),
           Event("%fusion.3 = f32[2] fusion()", 90, 105)]
    modules = [Event("jit_stage_fn(1)", 95, 145),
               Event("jit_stage_fn(1)", 148, 172),
               Event("jit_other(2)", 172, 180)]
    spans = [Event("frontend.step", 138, 152),
             Event("loadgen.wait", 171, 200)]
    return devtrace.Trace(ops, modules, spans, (100, 200), 1)


def test_busy_is_a_union_inside_the_window():
    tr = _trace()
    assert devtrace.busy_ns(tr) == 60
    assert devtrace.idle_percent(tr) == pytest.approx(40.0)


def test_union_and_gaps():
    assert devtrace.union([(5, 7), (1, 3), (2, 4), (7, 8)]) == [(1, 4), (5, 8)]
    assert devtrace.gaps([(1, 4), (5, 8)], 0, 10) == [(0, 1), (4, 5), (8, 10)]


def test_families_come_from_the_kernel_names():
    tr = _trace()
    assert [devtrace.family(e) for e in tr.ops] == [
        "conv_implicit", None, "conv_depthwise", None]
    # inside the window, by family
    assert devtrace.family_ns(tr) == {"conv_implicit": 30, "other": 20,
                                      "conv_depthwise": 20}
    # only inside programs that lie wholly in the window: the first
    # stage program starts at 95, so only the depthwise conv counts
    mods = devtrace.modules_inside(tr, "stage_fn")
    assert [(m.start, m.end) for m in mods] == [(148, 172)]
    assert devtrace.family_ns(tr, mods) == {"conv_depthwise": 20}


def test_gaps_go_to_the_covering_host_span():
    tr = _trace()
    assert devtrace.attribute_gaps(tr) == {"frontend.step": 10,
                                           "loadgen.wait": 30}
    # the runtime's host-to-device copy [135, 147) covers 7 ns of the
    # first gap [140, 150): those go to it, the rest to the span
    tr.runtime = [Event(devtrace.H2D, 135, 147)]
    assert devtrace.attribute_gaps(tr) == {"h2d.copy": 7,
                                           "frontend.step": 3,
                                           "loadgen.wait": 30}
    tr.spans, tr.runtime = [], []
    assert devtrace.attribute_gaps(tr) == {"host.other": 40}


def test_top_ops_by_label():
    tr = _trace()
    got = dict(devtrace.top_ops(tr))
    assert got == {"conv_implicit": 30e-9, "conv_depthwise": 20e-9,
                   "copy": 15e-9, "fusion": 5e-9}
    # the trace names an operation by its HLO text
    ev = Event("%convert_multiply_fusion.12 = (f32[32,3136,256]) fusion("
               "f32[32] %x.1), kind=kLoop", 0, 1)
    assert devtrace.op_label(ev) == "convert_multiply_fusion"
    assert devtrace.op_label(Event("%pad.18.clone = s8[2] pad()", 0, 1)) \
        == "pad.18"
    assert devtrace.op_label(Event(
        "%conv2d_implicit_pallas.53 = (f32[32]) custom-call()", 0, 1)) \
        == "conv_implicit"


def _ctx(tr, layers, mb=2, rows=None, mbs=1):
    return types.SimpleNamespace(
        trace=tr, layers=layers, microbatch=mb, device_kind="TPU v5 lite",
        counters={"engine.mb_injected": mbs,
                  "engine.rows_injected": mb * mbs if rows is None else rows})


def test_roofline_share_by_hand():
    import opcount
    import peaks
    import roofline
    tr = _trace()
    dw = dict(name="dw", op="dwconv", k=3, stride=1, c_in=8, c_out=8,
              hw_in=4)
    share = roofline.share(_ctx(tr, [dw]), "dwconv", "conv_depthwise")
    least = opcount.least_seconds(dw, 2, peaks.peak("TPU v5 lite"))
    assert share == pytest.approx(100 * least / 20e-9)
    # a partly filled microbatch in the window: nothing to read
    assert roofline.share(_ctx(tr, [dw], rows=1), "dwconv",
                          "conv_depthwise") is None
    # no layer of the family, or no trace: nothing to read
    assert roofline.share(_ctx(tr, []), "dwconv", "conv_depthwise") is None
    assert roofline.share(_ctx(None, [dw]), "dwconv",
                          "conv_depthwise") is None


def test_xla_share_reader():
    import spec
    read = spec.metric_reader("ops.xla_share")
    # busy 60 ns, conv kernels cover [100, 130) and [150, 170) = 50 ns
    assert read(_ctx(_trace(), [])) == pytest.approx(100 * 10 / 60)
    assert read(_ctx(None, [])) is None


def test_unknown_device_has_no_peaks():
    import peaks
    with pytest.raises(KeyError):
        peaks.peak("TPU v9 imaginary")
    assert peaks.peak("TPU v5 lite")["int8_ops_per_s"] == 393e12
