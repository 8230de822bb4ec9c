"""Replicated-pipeline serving front-end (serving/frontend.py).

Conformance: every request's logits must be *bit-identical* to
``serving.pipeline.reference_logits`` at the engine's microbatch
granularity for every (n_replicas, n_stages, serve mode) cell, no matter
the arrival order or how requests interleave mid-flight — replicas never
share a quantization domain and neither do queue neighbours.  Plus: the
shared host-side compiled tree / per-group disjoint stage subtree spies,
least-loaded routing + admission backpressure, latency accounting, and a
forced-4-device subprocess harness (2 replicas x 2 stages on disjoint
device groups).
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import nn
from repro.core import compiled_linear as cl
from repro.launch.mesh import replica_pipeline_devices
from repro.models import resnet
from repro.obs.metrics import Reservoir
from repro.serving.frontend import (FrontendRequest, ResNetFrontend,
                                    _percentile)
from repro.serving.pipeline import reference_logits

CFG = resnet.ResNetConfig(width_mult=0.125, num_classes=4, in_hw=8)
MODES = ("int8", "sparse_cfmm")
MB = 2

_params_cache = {}


def _compiled(mode):
    if mode not in _params_cache:
        params = resnet.init(jax.random.PRNGKey(0), CFG)
        _params_cache[mode] = nn.unbox(
            cl.compile_params(params, mode=mode, sparsity=0.5))
    return _params_cache[mode]


def _images(n, seed=1):
    return np.asarray(jax.random.normal(jax.random.PRNGKey(seed),
                                        (n, CFG.in_hw, CFG.in_hw, 3)))


_ref_cache = {}


def _reference(mode, images, microbatch):
    """Per-request reference, cached by content so the matrix doesn't
    recompile the whole-model jit for every (cell, request) pair."""
    key = (mode, microbatch, os.environ.get("REPRO_PALLAS"),
           images.tobytes())
    if key not in _ref_cache:
        _ref_cache[key] = np.asarray(reference_logits(
            _compiled(mode), CFG, jnp.asarray(images), microbatch))
    return _ref_cache[key]


def _check_vs_reference(reqs, mode, microbatch=MB):
    for r in reqs:
        assert r.done
        np.testing.assert_array_equal(
            np.asarray(r.logits), _reference(mode, r.images, microbatch))


# ---------------------------------------------------------------------------
# Conformance matrix: replicas x stages x serve mode, arrival orders
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n_stages", (1, 2))
@pytest.mark.parametrize("n_replicas", (1, 2))
@pytest.mark.parametrize("mode", MODES)
def test_fleet_bit_identical_jnp(monkeypatch, mode, n_replicas, n_stages):
    """Every request equals its own per-microbatch reference — replica
    count, stage count, routing, and queue neighbours cannot change a
    single bit.  (Arrival order and mid-flight interleaving are swept in
    the dedicated tests below; microbatch-boundary odd sizes too.)"""
    monkeypatch.setenv("REPRO_PALLAS", "jnp")
    x = _images(8)
    fe = ResNetFrontend(CFG, _compiled(mode), mode=mode,
                        n_replicas=n_replicas, n_stages=n_stages,
                        microbatch=MB)
    reqs = [FrontendRequest(rid=i, images=x[a:b])
            for i, (a, b) in enumerate([(0, 4), (4, 6), (6, 8)])]
    fe.run(reqs)
    _check_vs_reference(reqs, mode)


@pytest.mark.slow
@pytest.mark.parametrize("n_replicas", (1, 2))
@pytest.mark.parametrize("mode", MODES)
def test_fleet_bit_identical_interpret(monkeypatch, mode, n_replicas):
    """The fleet through the Pallas kernels in interpret mode (single
    image/microbatch, 2 stages — interpret is slow; the full lowering
    matrix for the stage programs themselves lives in test_pipeline.py,
    and routing above them is lowering-independent)."""
    monkeypatch.setenv("REPRO_PALLAS", "interpret")
    fe = ResNetFrontend(CFG, _compiled(mode), mode=mode,
                        n_replicas=n_replicas, n_stages=2, microbatch=1)
    reqs = [FrontendRequest(rid=i, images=_images(1, seed=i))
            for i in range(2)]
    fe.run(reqs)
    _check_vs_reference(reqs, mode, microbatch=1)


def test_arrival_order_and_interleaving_do_not_change_bits(monkeypatch):
    """The same requests through opposite arrival orders AND a wave
    submitted mid-flight (odd sizes, so partial microbatches ride along):
    every request always matches its own reference."""
    monkeypatch.setenv("REPRO_PALLAS", "jnp")
    x = _images(10)
    sizes = [(0, 3), (3, 4), (4, 9), (9, 10)]
    outs = {}
    for order in (1, -1):
        fe = ResNetFrontend(CFG, _compiled("int8"), mode="int8",
                            n_replicas=2, n_stages=2, microbatch=MB)
        reqs = [FrontendRequest(rid=i, images=x[a:b])
                for i, (a, b) in enumerate(sizes)][::order]
        early, late = reqs[:2], reqs[2:]
        for r in early:
            fe.submit(r)
        for _ in range(3):                     # partially drain
            fe.step()
        for r in late:                         # interleave mid-flight
            fe.submit(r)
        while fe.step():
            pass
        _check_vs_reference(reqs, "int8")
        outs[order] = {r.rid: np.asarray(r.logits) for r in reqs}
    for rid in outs[1]:
        np.testing.assert_array_equal(outs[1][rid], outs[-1][rid])


def test_zero_row_request_completes(monkeypatch):
    monkeypatch.setenv("REPRO_PALLAS", "jnp")
    fe = ResNetFrontend(CFG, _compiled("int8"), mode="int8", n_replicas=2,
                        microbatch=MB)
    req = FrontendRequest(rid=0, images=_images(4)[:0])
    fe.run([req])
    assert req.done and req.logits.shape == (0, CFG.num_classes)
    assert req.latency_s is not None


# ---------------------------------------------------------------------------
# Shared host tree + disjoint per-group stage subtrees (spies)
# ---------------------------------------------------------------------------

def _leaf_bytes(tree):
    return sum(l.nbytes for l in jax.tree.leaves(tree))


@pytest.mark.parametrize("mode", MODES)
def test_replicas_share_host_tree_and_split_stage_subtrees(monkeypatch,
                                                           mode):
    """The fleet compiles ONE host-side param tree (every replica engine
    aliases it), and each replica's device group holds exactly its own
    stages' unit subtrees — the model is divided over a replica's stages
    and replicated only across replicas."""
    monkeypatch.setenv("REPRO_PALLAS", "jnp")
    params = _compiled(mode)
    fe = ResNetFrontend(CFG, params, mode=mode, n_replicas=2, n_stages=2,
                        microbatch=MB)
    units = resnet.compiled_units(params, CFG)
    unit_bytes = {u.name: _leaf_bytes(u.params) for u in units}
    for eng in fe.replicas:
        assert eng.params is fe.params         # one compiled tree, aliased
        seen = []
        for stage in eng.pipe.stages:
            seen.extend(stage.unit_names)
            assert stage.weight_bytes() == sum(
                unit_bytes[n] for n in stage.unit_names)
        assert sorted(seen) == sorted(unit_bytes)  # disjoint + complete
    # boxed params also compile exactly once, at the front door
    boxed = resnet.init(jax.random.PRNGKey(0), CFG)
    fe2 = ResNetFrontend(CFG, boxed, mode=mode, sparsity=0.5,
                         n_replicas=2, microbatch=MB)
    assert all(eng.params is fe2.params for eng in fe2.replicas)


def test_replica_device_carving():
    """replica_pipeline_devices carves contiguous disjoint groups when
    the devices exist and wraps round-robin when they don't (CPU devices
    only: an accelerator short of devices raises, tests/test_bringup.py)."""
    import types
    devs = [types.SimpleNamespace(platform="cpu", name=c) for c in "abcdefgh"]
    names = lambda gs: [[d.name for d in g] for g in gs]
    groups = replica_pipeline_devices(2, 3, devices=devs)
    assert names(groups) == [["a", "b", "c"], ["d", "e", "f"]]
    flat = [d.name for g in groups for d in g]
    assert len(set(flat)) == len(flat)         # disjoint
    wrapped = replica_pipeline_devices(3, 2, devices=devs[:4])
    assert names(wrapped) == [["a", "b"], ["c", "d"], ["a", "b"]]


# ---------------------------------------------------------------------------
# Routing, backpressure, accounting
# ---------------------------------------------------------------------------

def test_least_loaded_routing_spreads_requests(monkeypatch):
    """Two same-size requests land on different replicas (the second
    sees replica 0 loaded), and the dispatch tallies say so."""
    monkeypatch.setenv("REPRO_PALLAS", "jnp")
    fe = ResNetFrontend(CFG, _compiled("int8"), mode="int8", n_replicas=2,
                        microbatch=MB)
    reqs = [FrontendRequest(rid=i, images=_images(4, seed=i))
            for i in range(2)]
    fe.run(reqs)
    assert sorted(r.replica for r in reqs) == [0, 1]
    st = fe.stats()
    assert st["rows_dispatched"] == [4, 4]
    assert st["requests_dispatched"] == [1, 1]


def test_admission_backpressure_holds_queue(monkeypatch):
    """With more offered rows than the fleet can absorb, the front door
    holds requests in ITS queue (bounded replica inlets) and still
    drains everything correctly."""
    monkeypatch.setenv("REPRO_PALLAS", "jnp")
    fe = ResNetFrontend(CFG, _compiled("int8"), mode="int8", n_replicas=2,
                        n_stages=1, microbatch=MB, admit_rows=2)
    reqs = [FrontendRequest(rid=i, images=_images(2, seed=i))
            for i in range(6)]
    for r in reqs:
        fe.submit(r)
    assert len(fe.queue) == 6                  # nothing dispatched yet
    fe.step()
    assert len(fe.queue) > 0                   # held back, not dumped
    assert max(eng.pending_rows for eng in fe.replicas) <= 2 + MB
    while fe.step():
        pass
    _check_vs_reference(reqs, "int8")
    st = fe.stats()
    assert st["max_queue_depth"] == 6 and st["queue_depth"] == 0
    assert st["requests_done"] == 6


def test_admit_rows_validated_and_partial_mb_load_exact(monkeypatch):
    """admit_rows=0 would deadlock the front door (an idle replica could
    never be handed work) — rejected at construction; and pending_rows
    counts a partial microbatch at its REAL size, so routing sees true
    load under ragged request sizes."""
    monkeypatch.setenv("REPRO_PALLAS", "jnp")
    params = _compiled("int8")
    with pytest.raises(AssertionError, match="admit_rows"):
        ResNetFrontend(CFG, params, mode="int8", n_replicas=2,
                       microbatch=MB, admit_rows=0)
    fe = ResNetFrontend(CFG, params, mode="int8", n_replicas=1,
                        n_stages=2, microbatch=MB)
    eng = fe.replicas[0]
    eng.submit(FrontendRequest(rid=0, images=_images(1)))  # 1 row, mb=2
    assert eng.pending_rows == 1
    eng.step()                                 # now in flight, stage 0
    assert eng.pending_rows == 1               # exact, not rounded to mb
    while eng.step():
        pass
    assert eng.pending_rows == 0


def test_submit_validation_rejects_malformed(monkeypatch):
    """The front door rejects wrong-rank, wrong-geometry, non-castable,
    and non-finite image payloads with a clear ValueError — mirroring
    ServingEngine.submit's hardening — instead of shape-erroring deep
    inside a packed microbatch (where the crash would also take down the
    innocent requests sharing it).  Nothing malformed enters the queue."""
    monkeypatch.setenv("REPRO_PALLAS", "jnp")
    fe = ResNetFrontend(CFG, _compiled("int8"), mode="int8", n_replicas=1,
                        microbatch=MB)
    hw = CFG.in_hw
    bad = [
        (np.zeros((2, hw, hw), np.float32), "shape"),          # rank 3
        (np.zeros((2, hw, hw, 1), np.float32), "shape"),       # 1 channel
        (np.zeros((2, hw + 1, hw + 1, 3), np.float32), "shape"),
        (np.zeros((2, hw, hw, 3, 1), np.float32), "shape"),    # rank 5
        (np.asarray([["nope"]], dtype=object), "castable"),
        (np.full((1, hw, hw, 3), np.nan, np.float32), "NaN/Inf"),
        (np.full((1, hw, hw, 3), np.inf, np.float32), "NaN/Inf"),
    ]
    for images, match in bad:
        with pytest.raises(ValueError, match=match):
            fe.submit(FrontendRequest(rid=99, images=images))
    assert len(fe.queue) == 0 and not fe._inflight
    # a list-of-lists payload that IS castable to the right shape passes
    ok = FrontendRequest(rid=1, images=_images(1).tolist())
    fe.run([ok])
    assert ok.done and isinstance(ok.images, np.ndarray)
    np.testing.assert_array_equal(ok.logits, _reference("int8", ok.images,
                                                        MB))


def test_resubmit_live_request_and_duplicate_rid_rejected(monkeypatch):
    """Re-submitting a request object that is still queued/in-flight, or
    a second request reusing a live rid, used to silently reset the
    victim's dispatch accounting mid-flight — both now raise a clear
    ValueError and leave the fleet untouched.  Once the original request
    completes, both its object and its rid are reusable again."""
    monkeypatch.setenv("REPRO_PALLAS", "jnp")
    fe = ResNetFrontend(CFG, _compiled("int8"), mode="int8", n_replicas=1,
                        microbatch=MB)
    req = FrontendRequest(rid=7, images=_images(6))    # 3 microbatches
    fe.submit(req)                                 # queued, not yet run
    with pytest.raises(ValueError, match="already queued or in flight"):
        fe.submit(req)
    with pytest.raises(ValueError, match="duplicates a live request"):
        fe.submit(FrontendRequest(rid=7, images=_images(2, seed=9)))
    assert len(fe.queue) == 1                      # victim untouched
    fe.step()                                      # req now mid-flight
    assert not req.done and req.rows_done < len(req.images)
    with pytest.raises(ValueError, match="already queued or in flight"):
        fe.submit(req)
    while fe.step():
        pass
    assert req.done
    np.testing.assert_array_equal(req.logits, _reference("int8", req.images,
                                                         MB))
    # drained: the same object and the same rid are both legal again
    fe.run([req])
    assert req.done
    other = FrontendRequest(rid=7, images=_images(1, seed=3))
    fe.run([other])
    assert other.done


def test_percentile_edge_cases():
    """The stack's one percentile implementation: None on empty (a fleet
    that served nothing has no p95, not a p95 of 0), identity on a
    single sample, exact interpolation between two."""
    assert _percentile([], 50) is None
    assert _percentile([], 95) is None
    assert _percentile(iter(()), 99) is None       # any empty iterable
    for q in (0, 50, 95, 100):
        assert _percentile([0.25], q) == 0.25
    assert _percentile([1.0, 3.0], 50) == 2.0
    assert _percentile([1.0, 3.0], 0) == 1.0
    assert _percentile([1.0, 3.0], 100) == 3.0
    assert _percentile((3.0, 1.0, 2.0), 95) == pytest.approx(2.9)


def test_latency_reservoir_edge_cases():
    """The bounded latency store: empty -> no percentiles, window
    exactly full keeps everything in arrival order, overflow evicts the
    OLDEST sample first (sliding window, not a random reservoir)."""
    r = Reservoir("lat", window=3)
    assert len(r) == 0 and r.percentile(50) is None
    assert r.snapshot()["p95"] is None and r.observed == 0
    r.observe(5.0)                                 # single sample
    assert r.percentile(50) == 5.0 == r.percentile(95)
    r.append(1.0)                                  # deque-compatible alias
    r.observe(3.0)                                 # window exactly full
    assert len(r) == r.window == 3
    assert r.values() == [5.0, 1.0, 3.0]           # arrival order kept
    assert r.percentile(50) == 3.0
    r.observe(2.0)                                 # overflow: 5.0 evicted
    assert len(r) == 3 and r.observed == 4
    assert r.values() == [1.0, 3.0, 2.0]
    assert r.percentile(100) == 3.0                # max is of the window
    with pytest.raises(AssertionError):
        Reservoir("bad", window=0)


def test_reset_stats_audit_is_structural(monkeypatch):
    """Regression guard for the reset_stats surface: every wave-scoped
    metric the door registers must zero on reset (checked from the
    registry's own scope declarations, so a future counter added without
    a scope decision fails HERE, not in a stale hand-kept list)."""
    monkeypatch.setenv("REPRO_PALLAS", "jnp")
    fe = ResNetFrontend(CFG, _compiled("int8"), mode="int8",
                        n_replicas=2, microbatch=MB)
    fe.run([FrontendRequest(rid=i, images=_images(2, seed=i))
            for i in range(4)])
    assert fe.metrics.wave_names(), "door must register wave metrics"
    fe.reset_stats()
    snap = fe.snapshot()["door"]
    for name in fe.metrics.wave_names():
        kind = fe.metrics.get(name).kind
        if kind == "counter":
            assert snap[name] == 0, name
        elif kind == "reservoir":
            assert snap[name]["count"] == 0, name
        elif kind in ("gauge", "highwater"):
            # queue depth is re-observed on the (drained) queue
            assert snap[name] == 0, name
    # the life side survives: the EWMA row time keeps its calibration
    assert fe.stats()["est_row_time_s"] is not None


def test_latency_window_bounds_samples(monkeypatch):
    """The latency reservoir is a bounded deque: an open-loop serve that
    completes requests forever holds at most ``latency_window`` samples
    (stats() reports the bound and the current fill), and the p50/p95
    reflect only the most recent window."""
    monkeypatch.setenv("REPRO_PALLAS", "jnp")
    fe = ResNetFrontend(CFG, _compiled("int8"), mode="int8", n_replicas=1,
                        microbatch=MB, latency_window=4)
    for i in range(8):
        fe.run([FrontendRequest(rid=i, images=_images(1, seed=i))])
    st = fe.stats()
    assert st["requests_done"] == 8                # all completed...
    assert st["latency_samples"] == 4              # ...window kept 4
    assert st["latency_window"] == 4
    assert len(fe._latencies) == 4
    assert st["latency_p95_s"] >= st["latency_p50_s"] > 0
    with pytest.raises(AssertionError):
        ResNetFrontend(CFG, _compiled("int8"), mode="int8",
                       latency_window=0)


def test_two_small_requests_share_a_microbatch(monkeypatch):
    """The continuous-batching demonstrator: two 1-row requests on one
    replica ride in ONE shared microbatch (occupancy 1.0, one injection)
    and each still matches its own single-request reference bit for bit.
    The whole-request baseline (continuous=False) needs two half-empty
    microbatches for the same traffic."""
    monkeypatch.setenv("REPRO_PALLAS", "jnp")
    reqs = [FrontendRequest(rid=i, images=_images(1, seed=i))
            for i in range(2)]
    fe = ResNetFrontend(CFG, _compiled("int8"), mode="int8", n_replicas=1,
                        n_stages=1, microbatch=MB)
    fe.run(reqs)
    _check_vs_reference(reqs, "int8")
    st = fe.replicas[0].stats()
    assert st["mb_injected"] == 1 and st["rows_injected"] == 2
    assert st["microbatch_occupancy"] == 1.0
    base = ResNetFrontend(CFG, _compiled("int8"), mode="int8",
                          n_replicas=1, n_stages=1, microbatch=MB,
                          continuous=False)
    breqs = [FrontendRequest(rid=i, images=_images(1, seed=i))
             for i in range(2)]
    base.run(breqs)
    _check_vs_reference(breqs, "int8")
    stb = base.replicas[0].stats()
    assert stb["mb_injected"] == 2
    assert stb["microbatch_occupancy"] == 0.5


def test_row_granular_dispatch_splits_across_replicas(monkeypatch):
    """A request larger than one replica's admission room spills its
    remaining rows to the other replica instead of head-of-line blocking
    the queue — and the reassembled logits still match the reference."""
    monkeypatch.setenv("REPRO_PALLAS", "jnp")
    fe = ResNetFrontend(CFG, _compiled("int8"), mode="int8", n_replicas=2,
                        n_stages=1, microbatch=MB, admit_rows=2)
    req = FrontendRequest(rid=0, images=_images(6))
    fe.run([req])
    _check_vs_reference([req], "int8")
    assert req.replica == 0                    # first rows' replica
    st = fe.stats()
    assert sum(st["rows_dispatched"]) == 6
    assert all(n > 0 for n in st["rows_dispatched"])   # genuinely split


def test_dispatch_load_counters_match_scan(monkeypatch):
    """The O(1) incremental ``pending_rows`` the router reads must equal
    the linear-scan oracle on every replica at every step of a loaded
    mixed-size workload (the scan is what the incremental counters
    replaced to stop dispatch being O(requests²))."""
    monkeypatch.setenv("REPRO_PALLAS", "jnp")
    fe = ResNetFrontend(CFG, _compiled("int8"), mode="int8", n_replicas=2,
                        n_stages=2, microbatch=MB, admit_rows=3)
    reqs = [FrontendRequest(rid=i, images=_images(1 + i % 4, seed=i))
            for i in range(8)]
    for r in reqs:
        fe.submit(r)
    while True:
        busy = fe.step()
        for eng in fe.replicas:
            assert eng.pending_rows == eng._scan_pending_rows()
        if not busy:
            break
    _check_vs_reference(reqs, "int8")
    assert all(eng.pending_rows == 0 for eng in fe.replicas)


def test_stats_latency_and_replica_accounting(monkeypatch):
    monkeypatch.setenv("REPRO_PALLAS", "jnp")
    fe = ResNetFrontend(CFG, _compiled("int8"), mode="int8", n_replicas=2,
                        n_stages=2, microbatch=MB)
    reqs = [FrontendRequest(rid=i, images=_images(2, seed=i))
            for i in range(4)]
    fe.run(reqs)
    st = fe.stats()
    assert st["n_replicas"] == 2
    assert len(st["replica_bubble"]) == 2
    assert len(st["replicas"]) == 2
    assert [s["replica"] for s in st["replicas"]] == [0, 1]
    assert all(s["in_flight"] == 0 for s in st["replicas"])
    assert st["latency_p50_s"] is not None
    assert st["latency_p95_s"] >= st["latency_p50_s"] > 0
    assert all(r.latency_s > 0 for r in reqs)
    assert sum(st["rows_dispatched"]) == 8
    fe.reset_stats()
    assert fe.stats()["requests_done"] == 0
    assert fe.stats()["latency_p50_s"] is None


# ---------------------------------------------------------------------------
# Multi-device harness (forced 4-device CPU fan-out, subprocess)
# ---------------------------------------------------------------------------

_MULTIDEV_SCRIPT = r"""
import jax, numpy as np, jax.numpy as jnp
from repro import nn
from repro.core.compiled_linear import compile_params
from repro.models import resnet
from repro.serving.frontend import FrontendRequest, ResNetFrontend
from repro.serving.pipeline import reference_logits

assert len(jax.devices()) == 4, jax.devices()
cfg = resnet.ResNetConfig(width_mult=0.125, num_classes=4, in_hw=8)
params = nn.unbox(compile_params(resnet.init(jax.random.PRNGKey(0), cfg),
                                 mode="int8"))
fe = ResNetFrontend(cfg, params, mode="int8", n_replicas=2, n_stages=2,
                    microbatch=1)
groups = [[str(s.device) for s in eng.pipe.stages] for eng in fe.replicas]
flat = [d for g in groups for d in g]
assert len(set(flat)) == 4, groups            # disjoint device groups
for eng in fe.replicas:                       # weights live on-group
    for s in eng.pipe.stages:
        for leaf in jax.tree.leaves(s.params):
            assert list(leaf.devices())[0] == s.device, (s.index,
                                                         leaf.devices())
x = np.asarray(jax.random.normal(jax.random.PRNGKey(1), (4, 8, 8, 3)))
reqs = [FrontendRequest(rid=0, images=x[:1]),
        FrontendRequest(rid=1, images=x[1:4])]
fe.run(reqs)
for r in reqs:
    ref = reference_logits(params, cfg, jnp.asarray(r.images), 1)
    np.testing.assert_array_equal(np.asarray(r.logits), np.asarray(ref))
assert sorted(r.replica for r in reqs) == [0, 1]
print("FLEET_MULTIDEV_OK", groups)
"""


def test_fleet_on_four_forced_devices():
    """Real multi-device fleet: 2 replicas x 2 stages on 4 distinct CPU
    devices, stage params committed to their own group's devices, outputs
    bit-identical per request.  Subprocess because device count is fixed
    at backend init."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                        " --xla_force_host_platform_device_count=4")
    env["REPRO_PALLAS"] = "jnp"
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..", "src")] +
        env.get("PYTHONPATH", "").split(os.pathsep))
    proc = subprocess.run([sys.executable, "-c", _MULTIDEV_SCRIPT],
                          capture_output=True, text=True, timeout=300,
                          env=env)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "FLEET_MULTIDEV_OK" in proc.stdout
