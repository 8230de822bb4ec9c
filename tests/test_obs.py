"""Unified telemetry (repro/obs/): metrics registry semantics, Chrome
trace-event export + validator, traced open-loop serving, stage-tick
bubble attribution, and activation-sparsity profiling exactness.

The load-bearing gates (DESIGN.md §11):

* a traced open-loop wave exports VALID Chrome trace JSON with the full
  admission → queue → dispatch → collect span chain for every completed
  request, plus per-stage tick spans on the replica tracks;
* per-stage idle-cause attribution sums EXACTLY to the pipeline's
  ``idle_stage_ticks`` and to ``bubble_fraction`` within float
  tolerance — the attribution is a partition of the bubble, not an
  estimate;
* sparsity histograms match an exact jnp recount of the same rows
  (``reference_profile``), and the profiler's reduction matches a plain
  numpy recount of synthetic aux;
* telemetry is observation-only: logits with profiling on are
  bit-identical to the unprofiled reference;
* the always-on host spans (``obs.trace.host_span``) reach a profiler
  trace with their args, nest inside the front door's step, and their
  phase counters account for no more than the engine step's time.
"""
import gc
import glob
import json
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro import nn
from repro.core import compiled_linear as cl
from repro.kernels import ops
from repro.models import resnet
from repro.obs import Telemetry
from repro.obs.metrics import (LIFE, WAVE, Counter, Gauge, HighWater,
                               MetricsRegistry, Reservoir, percentile)
from repro.obs.sparsity import SparsityProfiler
from repro.obs.trace import (HOST_SPANS, Trace, host_span, main as trace_main,
                             trace_gc, validate_chrome_trace)
from repro.serving.frontend import FrontendRequest, ResNetFrontend
from repro.serving.loadgen import poisson_plan, run_open_loop
from repro.serving.pipeline import reference_logits, reference_profile

CFG = resnet.ResNetConfig(width_mult=0.125, num_classes=4, in_hw=8)
MB = 2

_params_cache = {}


def _compiled():
    if "int8" not in _params_cache:
        params = resnet.init(jax.random.PRNGKey(0), CFG)
        _params_cache["int8"] = nn.unbox(
            cl.compile_params(params, mode="int8", sparsity=0.5))
    return _params_cache["int8"]


def _images(n, seed=0):
    return np.asarray(jax.random.normal(
        jax.random.PRNGKey(seed), (n, CFG.in_hw, CFG.in_hw, 3)))


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------

def test_metric_kinds():
    c = Counter("c")
    c.inc()
    c.inc(3)
    assert c.value == 4
    c.reset()
    assert c.value == 0

    g = Gauge("g", initial=1.5)
    g.set(9.0)
    assert g.value == 9.0
    g.reset()
    assert g.value == 1.5

    hw = HighWater("hw")
    for v in (3, 7, 2):
        hw.observe(v)
    assert hw.value == 7


def test_registry_scopes_and_reset_wave():
    m = MetricsRegistry()
    wave_c = m.counter("served")
    life_c = m.counter("odometer", scope=LIFE)
    life_g = m.gauge("row_time", scope=LIFE, initial=None)
    wave_c.inc(5)
    life_c.inc(7)
    life_g.set(0.25)
    assert m.counter("served") is wave_c          # get-or-create
    assert m.wave_names() == ["served"]
    assert set(m.names()) == {"served", "odometer", "row_time"}
    m.reset_wave()
    assert wave_c.value == 0                      # wave zeroed
    assert life_c.value == 7 and life_g.value == 0.25   # life survives
    snap = m.snapshot()
    assert snap == {"served": 0, "odometer": 7, "row_time": 0.25}
    assert "served" in m and "missing" not in m
    with pytest.raises(AssertionError):           # kind mismatch is a bug
        m.gauge("served")


def test_sparsity_profiler_matches_numpy_recount():
    """Feed synthetic post-ReLU activations through the profiler's aux
    contract and check every reduced number against a direct numpy
    recount of the same activations."""
    rng = np.random.RandomState(0)
    groups, n, hw, c = 4, 3, 2, 8
    prof = SparsityProfiler(groups=groups, hist_buckets=4)
    acts = []
    for _ in range(2):                            # two microbatches
        a = np.maximum(rng.randn(n, hw, hw, c), 0.0)
        acts.append(a)
        z = (a == 0.0)
        zg = z.reshape(n, hw, hw, c // groups, groups)
        prof.add({"layer0": {
            "row_zeros": z.reshape(n, -1).sum(1).astype(np.float32),
            "group_zeros": zg.sum((0, 1, 2, 4)).astype(np.float32),
            "group_allzero": zg.all(4).sum((0, 1, 2)).astype(np.float32),
            "elems_per_row": np.float32(hw * hw * c),
            "cells": np.float32(n * hw * hw),
        }})
    snap = prof.snapshot()
    assert snap["microbatches_profiled"] == 2
    lay = snap["layers"]["layer0"]
    allz = np.concatenate(acts)                   # (2n, hw, hw, c)
    zeros = float((allz == 0.0).sum())
    elems = allz.size
    assert lay["n_rows"] == 2 * n
    assert lay["zeros"] == zeros
    assert lay["zero_fraction"] == pytest.approx(zeros / elems)
    fr = (allz == 0.0).reshape(2 * n, -1).mean(1)
    ref_hist, _ = np.histogram(fr, bins=np.linspace(0, 1, 5))
    assert lay["row_fraction_hist"]["counts"] == [int(x) for x in ref_hist]
    zg = (allz == 0.0).reshape(2 * n, hw, hw, c // groups, groups)
    ref_group = zg.sum((0, 1, 2, 4)) / (elems / (c // groups))
    np.testing.assert_allclose(lay["group_zero_fraction"], ref_group)
    ref_cells = zg.all(4).sum((0, 1, 2)) / (2 * n * hw * hw)
    np.testing.assert_allclose(lay["group_allzero_cell_fraction"],
                               ref_cells)
    assert snap["overall_zero_fraction"] == pytest.approx(zeros / elems)


# ---------------------------------------------------------------------------
# trace export + validator
# ---------------------------------------------------------------------------

def _fake_clock(times):
    it = iter(times)
    last = [0.0]

    def clock():
        try:
            last[0] = next(it)
        except StopIteration:
            pass
        return last[0]
    return clock


def test_trace_export_nests_and_validates():
    tr = Trace(clock=_fake_clock([0.0]))
    tr.name_process(1, "replica0")
    tr.name_thread(1, 0, "stage0")
    tr.span("outer", "t", 1, 0, 0.001, 0.009)
    tr.span("inner", "t", 1, 0, 0.002, 0.005)     # nested inside outer
    tr.instant("edge", "t", 1, 0, t=0.004, bytes=128)
    obj = tr.to_chrome_trace()
    assert validate_chrome_trace(obj) == []
    phs = [(e["ph"], e["name"]) for e in obj["traceEvents"]]
    assert phs[:2] == [("M", "process_name"), ("M", "thread_name")]
    # stack discipline: outer B, inner B, inner E (or instant), outer E
    names = [e["name"] for e in obj["traceEvents"] if e["ph"] in "BE"]
    assert names == ["outer", "inner", "inner", "outer"]
    assert obj["otherData"]["dropped_events"] == 0


def test_trace_buffer_bounded_and_still_valid():
    tr = Trace(capacity=2, clock=_fake_clock([0.0]))
    for i in range(5):
        tr.span(f"s{i}", "t", 0, 0, i * 0.01, i * 0.01 + 0.005)
    assert len(tr.spans) == 2 and tr.dropped == 3
    obj = tr.to_chrome_trace()
    assert validate_chrome_trace(obj) == []       # whole spans dropped,
    assert obj["otherData"]["dropped_events"] == 3  # never orphaned B/E


def test_validator_rejects_broken_traces():
    assert validate_chrome_trace([]) != []
    assert validate_chrome_trace({"traceEvents": []}) != []
    ev = {"name": "a", "ph": "B", "ts": 1.0, "pid": 0, "tid": 0}
    assert any("missing keys" in e for e in validate_chrome_trace(
        {"traceEvents": [{"ph": "B"}]}))
    assert any("unclosed" in e for e in validate_chrome_trace(
        {"traceEvents": [ev]}))
    assert any("no open B" in e for e in validate_chrome_trace(
        {"traceEvents": [dict(ev, ph="E")]}))
    bad_order = [dict(ev, ts=5.0), dict(ev, ph="E", ts=6.0),
                 dict(ev, name="b", ts=1.0),
                 dict(ev, name="b", ph="E", ts=2.0)]
    assert any("not monotonic" in e for e in validate_chrome_trace(
        {"traceEvents": bad_order}))


def test_trace_cli_validates_files(tmp_path):
    tr = Trace(clock=_fake_clock([0.0]))
    tr.span("a", "t", 0, 0, 0.0, 0.001)
    good = tr.save(tmp_path / "good.json")
    assert trace_main([str(good)]) == 0
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps({"traceEvents": [
        {"name": "a", "ph": "E", "ts": 0.0, "pid": 0, "tid": 0}]}))
    assert trace_main([str(bad)]) == 1
    assert trace_main([]) == 2
    # and as the CLI CI actually runs
    r = subprocess.run([sys.executable, "-m", "repro.obs.trace",
                        str(good)], capture_output=True, text=True)
    assert r.returncode == 0, r.stdout + r.stderr


# ---------------------------------------------------------------------------
# traced serving: open-loop wave -> valid trace with the full span chain
# ---------------------------------------------------------------------------

def test_traced_open_loop_wave_full_span_chain(monkeypatch, tmp_path):
    monkeypatch.setenv("REPRO_PALLAS", "jnp")
    tel = Telemetry(trace=True)
    fe = ResNetFrontend(CFG, _compiled(), mode="int8", n_replicas=2,
                        n_stages=2, microbatch=MB, telemetry=tel)
    fe.run([FrontendRequest(rid=-1, images=_images(2))])     # warmup
    plan = poisson_plan(rate_rps=400.0, n_requests=6,
                        image_pool=_images(4, seed=1),
                        size_mix=((1, 2.0), (2, 1.0)), seed=0)
    res = run_open_loop(fe, plan, max_wall_s=60.0)
    done = [a.req for a in plan if a.req.done]
    assert res["admitted"] == len(plan) and len(done) == len(plan)

    path = tel.trace.save(tmp_path / "wave.json")
    obj = json.loads(open(path).read())
    assert validate_chrome_trace(obj) == []
    spans_by_rid, stage_spans, arrivals = {}, 0, set()
    for e in obj["traceEvents"]:
        if e["ph"] == "B" and e.get("cat") == "request":
            spans_by_rid.setdefault(e["tid"], set()).add(e["name"])
        if e["ph"] == "B" and e.get("cat") == "pipeline":
            stage_spans += 1
            assert e["name"].startswith("stage")
        if e["ph"] == "i" and e["name"] == "arrival":
            arrivals.add(e["args"]["rid"])
    chain = {"admission", "queue", "dispatch", "collect"}
    for req in done:
        assert spans_by_rid.get(req.rid) == chain, (req.rid, spans_by_rid)
    assert arrivals == {a.req.rid for a in plan}
    assert stage_spans > 0
    # replica process/thread names are in the metadata
    names = {e["args"]["name"] for e in obj["traceEvents"]
             if e["ph"] == "M" and e["name"] == "process_name"}
    assert "frontend" in names and any("replica" in n for n in names)


# ---------------------------------------------------------------------------
# bubble attribution
# ---------------------------------------------------------------------------

def test_bubble_attribution_partitions_bubble_fraction(monkeypatch):
    """Per-stage idle-cause counts are a PARTITION of the pipeline's
    idle stage-ticks: they sum to ``idle_stage_ticks`` exactly and to
    ``bubble_fraction * n_stages * ticks`` within float tolerance, on
    every replica, for a wave long enough to contain fill, drain, and
    host-gap ticks."""
    monkeypatch.setenv("REPRO_PALLAS", "jnp")
    fe = ResNetFrontend(CFG, _compiled(), mode="int8", n_replicas=2,
                        n_stages=2, microbatch=MB)
    reqs = [FrontendRequest(rid=i, images=_images(2, seed=i))
            for i in range(6)]
    fe.run(reqs)
    st = fe.stats()
    for rs in st["replicas"]:
        attr = rs["bubble_attribution"]
        assert sorted(attr) == ["drain", "fill", "host", "starved"]
        S = rs["n_stages"]
        assert all(len(v) == S for v in attr.values())
        total = sum(sum(v) for v in attr.values())
        assert total == rs["idle_stage_ticks"]
        launches = sum(rs["stage_launches"])
        assert total == S * rs["ticks"] - launches
        assert total == pytest.approx(
            rs["bubble_fraction"] * S * rs["ticks"])


# ---------------------------------------------------------------------------
# sparsity profiling through the fleet vs the jnp recount oracle
# ---------------------------------------------------------------------------

def test_fleet_sparsity_matches_reference_profile(monkeypatch):
    monkeypatch.setenv("REPRO_PALLAS", "jnp")
    groups = 4
    tel = Telemetry(trace=True, sparsity_groups=groups)
    fe = ResNetFrontend(CFG, _compiled(), mode="int8", n_replicas=1,
                        n_stages=2, microbatch=MB, telemetry=tel)
    x = _images(6, seed=3)
    reqs = [FrontendRequest(rid=i, images=x[i * MB:(i + 1) * MB])
            for i in range(3)]
    fe.run(reqs)
    # observation-only: logits bit-identical to the unprofiled reference
    got = np.concatenate([np.asarray(r.logits) for r in reqs])
    ref = np.asarray(reference_logits(_compiled(), CFG, x, MB))
    np.testing.assert_array_equal(got, ref)

    served = tel.sparsity.snapshot()
    _, oracle = reference_profile(_compiled(), CFG, x, MB, groups,
                                  lowering="jnp")
    assert served["microbatches_profiled"] == 3
    assert served["layers"].keys() == oracle["layers"].keys()
    exact = ops._mode() == "jnp"
    for name, a in served["layers"].items():
        b = oracle["layers"][name]
        assert a["n_rows"] == b["n_rows"] == 6
        if exact:
            assert a["zeros"] == b["zeros"], name
            assert (a["row_fraction_hist"]["counts"]
                    == b["row_fraction_hist"]["counts"]), name
            assert a["group_zero_fraction"] == b["group_zero_fraction"]
            assert (a["group_allzero_cell_fraction"]
                    == b["group_allzero_cell_fraction"]), name
        else:
            np.testing.assert_allclose(a["zero_fraction"],
                                       b["zero_fraction"], atol=1e-5)
    assert 0.0 < served["overall_zero_fraction"] < 1.0


# ---------------------------------------------------------------------------
# the registry behind the frontend: snapshot + structural reset audit
# ---------------------------------------------------------------------------

def test_frontend_snapshot_and_reset_wave_audit(monkeypatch):
    """The reset_stats audit, structurally: every wave-scoped metric in
    the door + engine registries zeroes on ``reset_stats`` and every
    life-scoped one survives — checked against the registry's own scope
    declarations rather than a hand-kept list of attributes."""
    monkeypatch.setenv("REPRO_PALLAS", "jnp")
    fe = ResNetFrontend(CFG, _compiled(), mode="int8", n_replicas=2,
                        n_stages=2, microbatch=MB)
    fe.run([FrontendRequest(rid=i, images=_images(2, seed=i))
            for i in range(4)])
    snap = fe.snapshot()
    assert set(snap) == {"door", "replicas"}
    assert snap["door"]["door.requests_done"] == 4
    assert sum(snap["door"][f"door.replica{r}.rows_dispatched"]
               for r in range(2)) == 8
    assert any(n.startswith("pipe.stage0.idle.")
               for n in snap["replicas"][0])

    life_before = {
        n: fe.metrics.get(n).snapshot()
        for n in fe.metrics.names() if fe.metrics.get(n).scope == LIFE}
    assert life_before["door.row_time_s"] is not None   # EWMA warmed
    fe.reset_stats()
    after = fe.snapshot()
    for name in fe.metrics.wave_names():
        m = fe.metrics.get(name)
        v = after["door"][name]
        if m.kind == "counter":
            assert v == 0, name
        elif m.kind == "reservoir":
            assert v["count"] == 0 and v["p50"] is None, name
    for eng_snap, eng in zip(after["replicas"], fe.replicas):
        for name in eng.metrics.wave_names():
            if eng.metrics.get(name).kind == "counter":
                assert eng_snap[name] == 0, name
    for name, v in life_before.items():                 # life survives
        assert after["door"][name] == v, name
    st = fe.stats()
    assert st["requests_done"] == 0 and st["latency_p50_s"] is None
    assert st["est_row_time_s"] is not None             # odometer kept


# ---------------------------------------------------------------------------
# host spans: profiler events on the device trace's clock + ns counters
# ---------------------------------------------------------------------------

PHASES = ("engine.pack_ns", "pipe.put_ns", "pipe.launch_ns",
          "engine.fetch_ns", "engine.scatter_ns")


def _profiled(tmp_path, fn):
    """Run ``fn`` under a profiler session; return its host-span events
    as ``(name, start_ns, end_ns, stats)``, every one of ``HOST_SPANS``
    on the host planes of the xplane it wrote."""
    from jax.profiler import ProfileData
    jax.profiler.start_trace(str(tmp_path))
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
            for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if e.name in HOST_SPANS]


def test_host_span_counts_and_emits_profiler_event(tmp_path):
    ctr = Counter("x_ns")

    def work():
        with host_span("engine.pack", ctr, mb=7, rows=3) as sp:
            sum(range(10_000))
        with host_span("door.step"):              # no counter: span only
            pass
        assert ctr.value == sp.t1_ns - sp.t0_ns > 0

    evs = _profiled(tmp_path, work)
    assert [(n, st) for n, _, _, st in evs] == [
        ("engine.pack", {"mb": 7, "rows": 3}), ("door.step", {})]
    (_, t0, t1, _), _ = evs
    assert t1 - t0 >= ctr.value * 0.5             # the event spans the block


def _fleet(n_stages=1):
    return ResNetFrontend(CFG, _compiled(), mode="int8", n_replicas=1,
                          n_stages=n_stages, microbatch=MB)


def test_traced_frontend_shows_every_host_span_nested(monkeypatch,
                                                       tmp_path):
    monkeypatch.setenv("REPRO_PALLAS", "jnp")
    fe = _fleet()
    fe.run([FrontendRequest(rid=-1, images=_images(MB))])      # warmup

    def serve():
        fe.run([FrontendRequest(rid=i, images=_images(MB, seed=i))
                for i in range(2)])
        gc.collect()                              # one host.gc span

    evs = _profiled(tmp_path, serve)
    assert {n for n, *_ in evs} == set(HOST_SPANS)
    steps = [(t0, t1) for n, t0, t1, _ in evs if n == "door.step"]
    inner = [e for e in evs if e[0].split(".")[0] in ("engine", "pipe")]
    for name, t0, t1, _ in inner:
        assert any(s0 <= t0 and t1 <= s1 for s0, s1 in steps), name
    # one microbatch's spans share its ordinal
    by_mb = {}
    for name, *_, st in inner:
        by_mb.setdefault(st["mb"], set()).add(name)
    assert len(by_mb) == 2
    assert all(v == {"engine.step", "engine.pack", "pipe.put", "pipe.launch",
                     "engine.fetch", "engine.scatter"}
               for v in by_mb.values())
    assert {st["rid"] for n, *_, st in evs if n == "door.submit"} == {0, 1}


@pytest.mark.parametrize("n_stages", [1, 2])
def test_phase_counters_within_engine_step(monkeypatch, n_stages):
    monkeypatch.setenv("REPRO_PALLAS", "jnp")
    fe = _fleet(n_stages)
    fe.run([FrontendRequest(rid=i, images=_images(MB, seed=i))
            for i in range(3)])
    snap = fe.replicas[0].snapshot()
    assert all(snap[k] > 0 for k in PHASES + ("engine.step_ns",))
    assert sum(snap[k] for k in PHASES) <= snap["engine.step_ns"]
    fe.reset_stats()                              # wave-scoped
    assert all(fe.replicas[0].snapshot()[k] == 0 for k in PHASES)


def test_idle_engine_step_opens_no_span(monkeypatch, tmp_path):
    monkeypatch.setenv("REPRO_PALLAS", "jnp")
    eng = _fleet().replicas[0]
    evs = _profiled(tmp_path, lambda: [eng.step() for _ in range(3)])
    assert evs == []
    assert eng.snapshot()["engine.step_ns"] == 0


def test_gc_span_is_registered_once(monkeypatch):
    monkeypatch.setenv("REPRO_PALLAS", "jnp")
    _fleet()
    _fleet()
    trace_gc()
    hooks = [cb for cb in gc.callbacks if type(cb).__name__ == "_GcSpan"]
    assert len(hooks) == 1


def test_chrome_stage_spans_are_the_launch_spans(monkeypatch):
    """The Chrome export still validates with its ``stage{s}`` spans, and
    they are stamped from the ``pipe.launch`` blocks: their durations sum
    to ``pipe.launch_ns``."""
    monkeypatch.setenv("REPRO_PALLAS", "jnp")
    tel = Telemetry(trace=True)
    fe = ResNetFrontend(CFG, _compiled(), mode="int8", n_replicas=1,
                        n_stages=2, microbatch=MB, telemetry=tel)
    fe.run([FrontendRequest(rid=i, images=_images(MB, seed=i))
            for i in range(3)])
    obj = tel.trace.to_chrome_trace()
    assert validate_chrome_trace(obj) == []
    stages = [s for s in tel.trace.spans if s.cat == "pipeline"]
    assert {s.name for s in stages} == {"stage0", "stage1"}
    assert len(stages) == 2 * 3
    launch_us = fe.replicas[0].snapshot()["pipe.launch_ns"] / 1e3
    assert sum(s.dur for s in stages) == pytest.approx(launch_us, rel=1e-6)


def test_graph_nodes_scope_their_ops():
    """Each graph node's ops carry the node's name in their op_name, so a
    device trace can tell the layers apart."""
    from repro.models.graph import compile_graph
    units = compile_graph(CFG.graph(), _compiled())
    u = units[1]
    x = jax.ShapeDtypeStruct((MB,) + CFG.graph().in_shape(), np.float32)
    carry = jax.eval_shape(units[0].fn, units[0].params, x)
    text = jax.jit(u.fn).lower(u.params, carry).as_text(debug_info=True)
    names = [n.name for n in CFG.graph().nodes if n.name in u.params]
    assert names and all(f"/{n}/" in text for n in names), names
