"""The readers of the program's host-span counters, on synthetic
contexts, and a traced run of the harness on the CPU at a tiny size:
the program's span names stay apart from the harness's, so the
breakdown reads what it read before, and every new metric reads."""
import types

import pytest

import devtrace
import loop
import spec
from repro.obs.trace import HOST_SPANS

# metric -> (counters, value in ms)
READS = {
    "engine.pack_ms.offline": ({"engine.mb_injected": 4,
                                "engine.pack_ns": 60_000_000}, 15.0),
    "engine.pack_ms.server": ({"engine.mb_injected": 8,
                               "engine.pack_ns": 28_000_000}, 3.5),
    "engine.host_ms.offline": ({"engine.mb_injected": 4,
                                "engine.step_ns": 180_000_000,
                                "engine.fetch_ns": 80_000_000}, 25.0),
}


def test_host_span_names_are_not_harness_names():
    harness = set(loop.SPANS) | {"bench.window", devtrace.H2D}
    harness |= set(devtrace.RUNTIME)
    assert not set(HOST_SPANS) & harness
    assert len(set(HOST_SPANS)) == len(HOST_SPANS)


@pytest.mark.parametrize("name", list(READS))
def test_counter_readers(name):
    read = spec.metric_reader(name)
    counters, want = READS[name]
    ctx = lambda c: types.SimpleNamespace(counters=c, microbatch=32)
    assert read(ctx(counters)) == pytest.approx(want)
    # a program that keeps no such counters: nothing to read
    assert read(ctx({"engine.mb_injected": 4,
                     "engine.rows_injected": 128})) is None
    assert read(ctx(dict(counters, **{"engine.mb_injected": 0}))) is None


def test_traced_run_reads_the_new_metrics(monkeypatch):
    """A ``--trace 1`` run of a tiny cell: the three readers find their
    counters, and the idle gaps still name only harness spans."""
    import jax
    import run
    from test_bench_harness import _tiny_cell
    monkeypatch.setenv("REPRO_PALLAS", "jnp")
    cell = _tiny_cell()
    cell.per_layer = [m for m in spec.benchmark()["per_layer"]
                      if m["name"] in READS]
    res = run.run_cell(cell, 2 ** 33 + 5, 0.5, True, jax.devices())["result"]
    assert res["correct"] is True
    assert set(res["metrics"]) == set(READS)
    assert all(m["value"] > 0 for m in res["metrics"].values())
    gaps = {k for k, _ in res["breakdown"]["idle_gaps"]}
    assert gaps <= set(loop.SPANS) | {devtrace.H2D, "host.other"}
