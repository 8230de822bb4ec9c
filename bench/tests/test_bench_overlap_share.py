"""The readers of the engine's deferred-fetch counter, on synthetic
contexts, and a traced run of the harness on the CPU at a tiny size in
which the closed loop keeps work waiting, so the engine launches each
microbatch before it fetches the one before."""
import types

import pytest

import spec

NAMES = ("engine.overlap_share.offline", "engine.overlap_share.server")


def _ctx(counters):
    return types.SimpleNamespace(counters=counters, microbatch=32)


@pytest.mark.parametrize("name", NAMES)
@pytest.mark.parametrize("overlapped, injected, want", [
    (99, 100, 99.0), (0, 8, 0.0), (8, 8, 100.0), (1, 4, 25.0)])
def test_overlap_share_from_counters(name, overlapped, injected, want):
    read = spec.metric_reader(name)
    assert read(_ctx({"engine.mb_injected": injected,
                      "engine.mb_overlapped": overlapped})) == \
        pytest.approx(want)


@pytest.mark.parametrize("name", NAMES)
def test_overlap_share_none_without_the_counter(name):
    read = spec.metric_reader(name)
    # a program that keeps no engine.mb_overlapped: nothing to read
    assert read(_ctx({"engine.mb_injected": 4,
                      "engine.rows_injected": 128})) is None
    # no microbatch injected in the window
    assert read(_ctx({"engine.mb_injected": 0,
                      "engine.mb_overlapped": 0})) is None
    assert read(_ctx({})) is None


def test_traced_run_reads_the_overlap_share(monkeypatch):
    """A ``--trace 1`` run of a tiny closed-loop cell: the offline reader
    finds its counter, and the backlog makes nearly every microbatch
    launch before its predecessor is fetched."""
    import jax
    import run
    from test_bench_harness import _tiny_cell
    monkeypatch.setenv("REPRO_PALLAS", "jnp")
    cell = _tiny_cell()
    cell.per_layer = [m for m in spec.benchmark()["per_layer"]
                      if m["name"] == NAMES[0]]
    res = run.run_cell(cell, 2 ** 33 + 7, 0.5, True, jax.devices())["result"]
    assert res["correct"] is True
    assert set(res["metrics"]) == {NAMES[0]}
    assert 50.0 <= res["metrics"][NAMES[0]]["value"] <= 100.0
