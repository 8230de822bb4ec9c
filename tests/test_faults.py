"""Failure x recovery matrix for the serving fleet (serving/faults.py,
the frontend watchdog + requeue, and SLO-aware admission).

The contract under test: a replica can die (fail-stop), wedge (hang), or
degrade (slow) at ANY point in a request's life — before its rows
dispatch, mid-pipeline, or on the last tick — and every affected request
still completes with logits BIT-IDENTICAL to
``serving.pipeline.reference_logits``, because per-row quantization
domains make re-execution exact (DESIGN.md §9/§10).  Plus: no orphaned
row spans anywhere (engine queues and inlets drained), failure/requeue
accounting, replica re-admission, the open-loop load generator, and the
typed shed path.

Hang-injection cells burn ``watchdog_ticks`` no-progress steps per cell,
so they carry the ``chaos`` marker (pytest.ini) and run in CI's slow
tier; the kill cells are the acceptance gate and stay in tier-1.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import nn
from repro.core import compiled_linear as cl
from repro.models import resnet
from repro.serving.faults import Fault, FaultInjector, ReplicaFailure
from repro.serving.frontend import (Admitted, FrontendRequest, Rejected,
                                    ResNetFrontend)
from repro.serving.loadgen import (offered_rows_per_s, poisson_plan,
                                   run_open_loop)
from repro.serving.pipeline import (PipelineEngine, PipelineRequest,
                                    reference_logits)

CFG = resnet.ResNetConfig(width_mult=0.125, num_classes=4, in_hw=8)
MB = 2

_params_cache = {}


def _compiled(mode="int8"):
    if mode not in _params_cache:
        params = resnet.init(jax.random.PRNGKey(0), CFG)
        _params_cache[mode] = nn.unbox(
            cl.compile_params(params, mode=mode, sparsity=0.5))
    return _params_cache[mode]


def _images(n, seed=1):
    return np.asarray(jax.random.normal(jax.random.PRNGKey(seed),
                                        (n, CFG.in_hw, CFG.in_hw, 3)))


_ref_cache = {}


def _reference(images, microbatch=MB):
    key = (microbatch, os.environ.get("REPRO_PALLAS"), images.tobytes())
    if key not in _ref_cache:
        _ref_cache[key] = np.asarray(reference_logits(
            _compiled(), CFG, jnp.asarray(images), microbatch))
    return _ref_cache[key]


def _check_refs(reqs, microbatch=MB):
    for r in reqs:
        assert r.done, r.rid
        np.testing.assert_array_equal(
            np.asarray(r.logits), _reference(r.images, microbatch))


def _assert_drained(fe):
    """No orphaned _RowSpans anywhere: every engine queue empty, every
    stage inlet empty, all row accounting at zero — on failed AND
    healthy replicas — and the door holds nothing."""
    for eng in fe.replicas:
        assert not eng.queue, eng.queue
        assert eng.pending_rows == 0 == eng._scan_pending_rows()
        assert not eng.pipe.busy
    assert not fe.queue and not fe._requeue and not fe._inflight
    assert fe._door_rows == fe._scan_door_rows() == 0


def _wave(base, n_reqs=4, rows=MB):
    """mb-aligned traffic (rows == microbatch) so every injected
    microbatch is full — requeue after a failure then never changes a
    microbatch SHAPE, keeping even the Pallas lowerings bit-exact."""
    x = _images(n_reqs * rows)
    return [FrontendRequest(rid=base + i, images=x[i * rows:(i + 1) * rows])
            for i in range(n_reqs)]


def _fleet(pack, n_stages, **kw):
    kw.setdefault("watchdog_ticks", 4)
    return ResNetFrontend(CFG, _compiled(), mode="int8", n_replicas=2,
                          n_stages=n_stages, microbatch=MB,
                          continuous=pack, **kw)


def _healthy_ticks(fe, base):
    """Drive one healthy wave and report replica 0's productive tick
    count — the faulted waves use the same deterministic traffic, so
    tick i of the twin run is step i of the fault schedule."""
    reqs = _wave(base)
    fe.run(reqs)
    _check_refs(reqs)
    return fe.replicas[0].pipe.ticks


def _run_fault_cell(fe, inj, fault, base):
    """Arm ``fault`` on replica 0, drive a fresh wave, assert the
    recovery contract, then heal the fleet for the next cell."""
    inj.arm(fe.replicas[0], fault)
    reqs = _wave(base)
    fe.reset_stats()
    fe.run(reqs)
    _check_refs(reqs)
    _assert_drained(fe)
    st = fe.stats()
    assert st["replicas_failed"] == 1 and st["failed"] == [True, False], st
    assert st["requeues"] >= 1 and st["rows_requeued"] >= 1, st
    assert st["rows_dispatched"][1] >= st["rows_requeued"], st
    inj.disarm(fe.replicas[0])
    fe.restart_replica(0)
    return st


# ---------------------------------------------------------------------------
# The failure matrix: kind x timing x packing x stages
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n_stages", (1, 2))
@pytest.mark.parametrize("pack", (True, False))
def test_kill_matrix(monkeypatch, pack, n_stages):
    """Fail-stop at {before dispatch, mid-pipeline, last tick}: every
    request completes bit-identical to the never-failed reference, no
    spans orphaned, requeue accounted."""
    monkeypatch.setenv("REPRO_PALLAS", "jnp")
    fe = _fleet(pack, n_stages)
    inj = FaultInjector()
    ticks = _healthy_ticks(fe, base=0)
    timings = {"before": 0, "mid": max(1, ticks // 2),
               "last": max(1, ticks - 1)}
    for i, (name, at) in enumerate(timings.items()):
        _run_fault_cell(fe, inj, Fault("kill", at_step=at),
                        base=100 * (i + 1))


@pytest.mark.chaos
@pytest.mark.parametrize("n_stages", (1, 2))
@pytest.mark.parametrize("pack", (True, False))
def test_hang_matrix(monkeypatch, pack, n_stages):
    """Wedge (no exception, no progress) at the same three timings: the
    progress watchdog fails the replica after ``watchdog_ticks`` stalled
    steps and the requeue contract holds identically."""
    monkeypatch.setenv("REPRO_PALLAS", "jnp")
    fe = _fleet(pack, n_stages)
    inj = FaultInjector()
    ticks = _healthy_ticks(fe, base=0)
    timings = {"before": 0, "mid": max(1, ticks // 2),
               "last": max(1, ticks - 1)}
    for i, (name, at) in enumerate(timings.items()):
        st = _run_fault_cell(fe, inj, Fault("hang", at_step=at),
                             base=100 * (i + 1))
        assert "watchdog" in st["failures"][0]["reason"], st["failures"]


def test_slow_replica_limps_to_completion(monkeypatch):
    """A replica degraded to 1/3 rate stays under the watchdog threshold:
    it is NOT failed, and its share of the work completes (slowly) with
    exact logits."""
    monkeypatch.setenv("REPRO_PALLAS", "jnp")
    fe = _fleet(True, 1, watchdog_ticks=8)
    inj = FaultInjector()
    inj.arm(fe.replicas[0], Fault("slow", at_step=0, slow_factor=3))
    reqs = _wave(0)
    fe.run(reqs)
    _check_refs(reqs)
    _assert_drained(fe)
    st = fe.stats()
    assert st["replicas_failed"] == 0 and st["requeues"] == 0, st


def test_slow_replica_past_watchdog_is_failed(monkeypatch):
    """A replica degraded past the watchdog threshold is
    indistinguishable from a wedge — failed, drained, requeued; the
    threshold is exactly the boundary between the two slow tests."""
    monkeypatch.setenv("REPRO_PALLAS", "jnp")
    fe = _fleet(True, 1, watchdog_ticks=4)
    inj = FaultInjector()
    inj.arm(fe.replicas[0], Fault("slow", at_step=0, slow_factor=50))
    reqs = _wave(0)
    fe.run(reqs)
    _check_refs(reqs)
    _assert_drained(fe)
    st = fe.stats()
    assert st["replicas_failed"] == 1 and st["rows_requeued"] >= 1, st


def test_kill_requeue_interpret(monkeypatch):
    """The recovery path through the Pallas kernels in interpret mode:
    microbatch=1 keeps every executable shape fixed, so requeued rows
    are bit-identical even across the failure (the kernel-tier CI cell;
    the jnp matrix above covers the schedule space)."""
    monkeypatch.setenv("REPRO_PALLAS", "interpret")
    fe = ResNetFrontend(CFG, _compiled(), mode="int8", n_replicas=2,
                        n_stages=1, microbatch=1, watchdog_ticks=4)
    inj = FaultInjector()
    inj.arm(fe.replicas[0], Fault("kill", at_step=1))
    reqs = [FrontendRequest(rid=i, images=_images(1, seed=i))
            for i in range(4)]
    fe.run(reqs)
    _check_refs(reqs, microbatch=1)
    _assert_drained(fe)
    st = fe.stats()
    assert st["replicas_failed"] == 1 and st["rows_requeued"] >= 1, st


# ---------------------------------------------------------------------------
# Re-admission, guards, accounting
# ---------------------------------------------------------------------------

def test_restart_replica_rejoins_the_fleet(monkeypatch):
    """After kill + restart, the replica serves again: fresh engine,
    fresh device placement, same shared host tree, rows routed to BOTH
    replicas on the next wave."""
    monkeypatch.setenv("REPRO_PALLAS", "jnp")
    fe = _fleet(True, 1)
    inj = FaultInjector()
    inj.arm(fe.replicas[0], Fault("kill", at_step=0))
    old_engine = fe.replicas[0]
    fe.run(_wave(0))
    assert fe.failed[0]
    fe.restart_replica(0)
    assert fe.replicas[0] is not old_engine
    assert fe.replicas[0].params is fe.params      # shared host tree
    assert not fe.failed[0]
    fe.reset_stats()
    reqs = _wave(100)
    fe.run(reqs)
    _check_refs(reqs)
    st = fe.stats()
    assert all(n > 0 for n in st["rows_dispatched"]), st
    assert st["replicas_failed"] == 0


def test_restart_live_replica_requeues_its_work(monkeypatch):
    """Restarting a HEALTHY mid-flight replica (e.g. a planned rolling
    update) drains and requeues what it holds — nothing is lost and the
    logits stay exact."""
    monkeypatch.setenv("REPRO_PALLAS", "jnp")
    fe = _fleet(True, 2)
    fe.run(_wave(0))                               # warm/compile
    reqs = _wave(100)
    for r in reqs:
        fe.submit(r)
    fe.step()                                      # rows now in flight
    assert any(eng.pending_rows for eng in fe.replicas)
    fe.restart_replica(0)
    while fe.step():
        pass
    _check_refs(reqs)
    _assert_drained(fe)


def test_all_replicas_failed_raises_diagnosable(monkeypatch):
    monkeypatch.setenv("REPRO_PALLAS", "jnp")
    fe = _fleet(True, 1)
    inj = FaultInjector()
    for eng in fe.replicas:
        inj.arm(eng, Fault("kill", at_step=0))
    with pytest.raises(RuntimeError, match="all 2 replicas failed") as ei:
        fe.run(_wave(0))
    assert ei.value.fleet_stats["replicas_failed"] == 2


def test_run_max_steps_timeout_attaches_stats(monkeypatch):
    """The last-resort escape: with the watchdog disabled, a wedged
    replica turns `while step()` into a diagnosable TimeoutError with
    the fleet stats attached — never an infinite loop."""
    monkeypatch.setenv("REPRO_PALLAS", "jnp")
    fe = ResNetFrontend(CFG, _compiled(), mode="int8", n_replicas=1,
                        n_stages=1, microbatch=MB, watchdog_ticks=None)
    inj = FaultInjector()
    inj.arm(fe.replicas[0], Fault("hang", at_step=0))
    with pytest.raises(TimeoutError, match="max_steps=25") as ei:
        fe.run(_wave(0), max_steps=25)
    st = ei.value.fleet_stats
    assert st["replicas_failed"] == 0 and st["door_rows"] >= 0
    assert st["watchdog_ticks"] is None


def test_watchdog_no_false_positive_at_threshold_one(monkeypatch):
    """A healthy busy replica changes its progress marker on EVERY step
    (the inlet occupancy pattern shifts even when row counts hold), so
    even watchdog_ticks=1 never fails a healthy fleet — the threshold
    buys hang detection, not flakiness."""
    monkeypatch.setenv("REPRO_PALLAS", "jnp")
    fe = _fleet(True, 2, watchdog_ticks=1)
    reqs = [FrontendRequest(rid=i, images=_images(1 + i % 3, seed=i))
            for i in range(6)]
    fe.run(reqs)
    assert fe.stats()["replicas_failed"] == 0, fe.stats()["failures"]
    for r in reqs:
        assert r.done


def test_extract_pending_returns_unfetched_rows(monkeypatch):
    """Rows whose logits are computed but still unfetched are owed: the
    engine returns them with ``rows_submitted`` rewound, and a fleet that
    kills a replica in that state still answers every request
    bit-identically."""
    monkeypatch.setenv("REPRO_PALLAS", "jnp")
    eng = PipelineEngine(CFG, _compiled(), mode="int8", n_stages=1,
                         microbatch=MB)
    req = PipelineRequest(rid=7, images=_images(MB))
    eng.submit(req)
    eng.door_rows = 3                       # work waits: the fetch defers
    assert eng.step() and len(eng._unfetched) == 1
    assert eng.extract_pending() == [(req, 0, MB)]
    assert req.rows_submitted == 0 and not req.done
    assert eng.pending_rows == 0 and not eng._unfetched
    assert eng.step() is False

    fe = _fleet(True, 1)
    fe.run(_wave(0))                               # warm
    reqs = _wave(100, n_reqs=8)
    for r in reqs:
        fe.submit(r)
    fe.step()
    assert fe.replicas[0]._unfetched               # logits left on device
    inj = FaultInjector()
    inj.arm(fe.replicas[0], Fault("kill", at_step=0))
    while fe.step():
        pass
    _check_refs(reqs)
    _assert_drained(fe)
    st = fe.stats()
    assert st["replicas_failed"] == 1 and st["rows_requeued"] >= 2 * MB, st


@pytest.mark.parametrize("admit_rows", (None, MB))
def test_watchdog_quiet_with_unfetched_microbatch(monkeypatch, admit_rows):
    """A healthy replica that holds an unfetched microbatch between steps
    still changes its progress marker on every step, so even
    watchdog_ticks=1 never fails it; with ``admit_rows`` at one
    microbatch the door cannot hand it more, and its next step only
    fetches."""
    monkeypatch.setenv("REPRO_PALLAS", "jnp")
    fe = _fleet(True, 1, watchdog_ticks=1, admit_rows=admit_rows)
    reqs = _wave(0, n_reqs=12)
    for r in reqs:
        fe.submit(r)
    held = 0
    while fe.step():
        held += sum(bool(eng._unfetched) for eng in fe.replicas)
    assert held >= 3
    assert fe.stats()["replicas_failed"] == 0, fe.stats()["failures"]
    _check_refs(reqs)
    _assert_drained(fe)


def test_door_rows_counter_matches_scan_through_failure(monkeypatch):
    """The O(1) door backlog counter the admission estimate reads must
    equal its linear-scan oracle at every step of a kill-recovery run
    (requeued spans flow through the same accounting)."""
    monkeypatch.setenv("REPRO_PALLAS", "jnp")
    fe = _fleet(True, 1, admit_rows=3)
    fe.run(_wave(0))                               # warm
    inj = FaultInjector()
    inj.arm(fe.replicas[0], Fault("kill", at_step=2))
    reqs = [FrontendRequest(rid=100 + i, images=_images(1 + i % 4, seed=i))
            for i in range(6)]
    for r in reqs:
        fe.submit(r)
        assert fe._door_rows == fe._scan_door_rows()
    while True:
        try:
            busy = fe.step()
        finally:
            assert fe._door_rows == fe._scan_door_rows()
        if not busy:
            break
    for r in reqs:
        assert r.done
    _assert_drained(fe)


def test_fault_injector_disarm_restores(monkeypatch):
    monkeypatch.setenv("REPRO_PALLAS", "jnp")
    fe = _fleet(True, 1)
    eng = fe.replicas[0]
    inj = FaultInjector()
    inj.arm(eng, Fault("kill", at_step=0))
    assert "step" in eng.__dict__                  # instance-level wrap
    with pytest.raises(ReplicaFailure):
        eng.step()
    inj.disarm(eng)
    assert "step" not in eng.__dict__              # class method restored
    assert eng.step() is False                     # idle engine, no raise
    inj.disarm(eng)                                # idempotent
    with pytest.raises(AssertionError):
        Fault("explode")
    with pytest.raises(AssertionError):
        Fault("slow", slow_factor=1)


# ---------------------------------------------------------------------------
# SLO-aware admission (deterministic: seeded service-rate estimate)
# ---------------------------------------------------------------------------

def test_slo_admission_sheds_typed_outcome(monkeypatch):
    """With a p95 budget set and a measured service rate, a request
    whose estimated wait (backlog x per-row time) exceeds the budget is
    shed with a typed ``Rejected`` — never queued — while requests under
    budget keep flowing; without a budget nothing is ever shed."""
    monkeypatch.setenv("REPRO_PALLAS", "jnp")
    fe = _fleet(True, 1, admit_rows=2)
    fe._row_time = 0.1                             # seeded calibration
    fe.slo_p95_s = 1.0
    x = _images(12)
    r0 = FrontendRequest(rid=0, images=x[:4])
    r1 = FrontendRequest(rid=1, images=x[4:8])
    shed = FrontendRequest(rid=2, images=x[8:12])
    out0 = fe.submit(r0)
    assert isinstance(out0, Admitted)
    assert out0.estimated_wait_s == pytest.approx(0.4)
    out1 = fe.submit(r1)
    assert isinstance(out1, Admitted)              # 0.8s, still under
    out2 = fe.submit(shed)                         # 1.2s > 1.0 budget
    assert isinstance(out2, Rejected)
    assert out2.estimated_wait_s == pytest.approx(1.2)
    assert out2.slo_p95_s == 1.0 and out2.reason == "p95-budget"
    assert shed.rejected and not shed.done
    assert shed.rid not in fe._live and len(fe.queue) == 2
    st = fe.stats()
    assert st["rejected"] == 1 and st["rejected_rows"] == 4
    # the admitted requests drain normally and exactly; the shed one can
    # be resubmitted once the backlog clears
    while fe.step():
        pass
    _check_refs([r0, r1])
    fe.slo_p95_s = None
    out3 = fe.submit(shed)
    assert isinstance(out3, Admitted) and not shed.rejected
    while fe.step():
        pass
    _check_refs([shed])


def test_slo_none_or_uncalibrated_always_admits(monkeypatch):
    monkeypatch.setenv("REPRO_PALLAS", "jnp")
    fe = _fleet(True, 1)
    assert fe._row_time is None
    fe.slo_p95_s = 1e-9                            # absurd budget, no data
    out = fe.submit(FrontendRequest(rid=0, images=_images(2)))
    assert isinstance(out, Admitted)               # cannot shed w/o evidence
    assert out.estimated_wait_s is None
    while fe.step():
        pass
    fe.slo_p95_s = None
    fe._row_time = 10.0                            # huge, but no budget set
    out = fe.submit(FrontendRequest(rid=1, images=_images(2)))
    assert isinstance(out, Admitted)
    while fe.step():
        pass


def test_reset_service_rate_and_survival_across_reset_stats(monkeypatch):
    monkeypatch.setenv("REPRO_PALLAS", "jnp")
    fe = _fleet(True, 1)
    fe.run(_wave(0))
    assert fe._row_time is not None
    fe.reset_stats()
    assert fe._row_time is not None                # calibration survives
    assert fe.stats()["est_row_time_s"] == fe._row_time
    fe.reset_service_rate()
    assert fe._row_time is None


# ---------------------------------------------------------------------------
# Open-loop load generation
# ---------------------------------------------------------------------------

def test_poisson_plan_deterministic_and_shaped():
    pool = _images(8)
    mix = ((1, 0.75), (2, 0.25))
    p1 = poisson_plan(rate_rps=50, n_requests=20, image_pool=pool,
                      size_mix=mix, seed=7)
    p2 = poisson_plan(rate_rps=50, n_requests=20, image_pool=pool,
                      size_mix=mix, seed=7)
    assert [a.t for a in p1] == [a.t for a in p2]
    for a, b in zip(p1, p2):
        np.testing.assert_array_equal(a.req.images, b.req.images)
    sizes = {len(a.req.images) for a in p1}
    assert sizes <= {1, 2} and 1 in sizes
    assert all(p1[i].t < p1[i + 1].t for i in range(len(p1) - 1))
    assert offered_rows_per_s(p1) > 0
    # rids unique and offset
    rids = [a.req.rid for a in poisson_plan(rate_rps=1, n_requests=3,
                                            image_pool=pool, seed=0,
                                            rid_base=100)]
    assert rids == [100, 101, 102]


def test_open_loop_conservation_and_exactness(monkeypatch):
    """Open-loop replay: every offered request is either admitted (and
    completes bit-identical to its reference) or typed-rejected —
    admitted + rejected == offered, nothing silently dropped."""
    monkeypatch.setenv("REPRO_PALLAS", "jnp")
    fe = _fleet(True, 1)
    pool = _images(8)
    # warm BOTH microbatch shapes (full and 1-row) so compile time never
    # lands inside the measured open-loop wave
    fe.run([FrontendRequest(rid=-2, images=pool[:MB]),
            FrontendRequest(rid=-1, images=pool[:1])])
    fe.reset_service_rate()
    fe.run([FrontendRequest(rid=-3, images=pool[:MB])])
    cap_rows_s = 1.0 / fe._row_time
    fe.reset_stats()
    plan = poisson_plan(rate_rps=0.5 * cap_rows_s / 1.25, n_requests=8,
                        image_pool=pool, size_mix=((1, 3), (2, 1)), seed=3)
    res = run_open_loop(fe, plan, max_wall_s=120)
    assert res["admitted"] + res["rejected"] == res["offered"] == 8
    assert res["rejected"] == 0                    # no SLO set
    assert res["latency_p95_s"] >= res["latency_p50_s"] > 0
    for r in res["admitted_requests"]:
        assert r.done
        np.testing.assert_array_equal(np.asarray(r.logits),
                                      _reference(r.images))


def test_open_loop_overload_sheds_under_slo(monkeypatch):
    """At a 16x-capacity open-loop burst with a tight p95 budget, the
    admission controller sheds (typed) rather than queueing without
    bound, and every admitted request still completes exactly."""
    monkeypatch.setenv("REPRO_PALLAS", "jnp")
    fe = _fleet(True, 1)
    pool = _images(8)
    fe.run([FrontendRequest(rid=-2, images=pool[:MB]),
            FrontendRequest(rid=-1, images=pool[:1])])
    fe.reset_service_rate()
    fe.run([FrontendRequest(rid=-3, images=pool[:MB])])
    cap_rows_s = 1.0 / fe._row_time
    fe.slo_p95_s = 10 * fe._row_time
    fe.reset_stats()
    plan = poisson_plan(rate_rps=16 * cap_rows_s / 1.25, n_requests=16,
                        image_pool=pool, size_mix=((1, 3), (2, 1)), seed=5)
    res = run_open_loop(fe, plan, max_wall_s=120)
    assert res["admitted"] + res["rejected"] == 16
    assert res["rejected"] > 0, res
    assert fe.stats()["rejected"] == res["rejected"]
    for r in res["admitted_requests"]:
        assert r.done
        np.testing.assert_array_equal(np.asarray(r.logits),
                                      _reference(r.images))
    for r in res["rejected_requests"]:
        assert r.rejected and r.logits is None
