"""A whole run of the harness on the CPU at a tiny size, with the chip
check skipped: a sound run comes out correct, and one whose timed path
alters an answer where it is produced comes out not correct.  The int4
control, the plain reference computed a precision below int8, fails the
limits.  Plus the harness's refusals and the pieces BENCHMARK.json
names."""
import copy
import json
import os
import re
import shutil
import subprocess
import sys

import numpy as np
import pytest

import run
import spec
import verdict

LIMIT = 0.1       # both rel-L2 numbers at this size: sound <= 0.05, int4 >= 0.17


def _tiny_cell(name="resnet50"):
    cfg = copy.deepcopy(spec.config(name))
    cfg.update(width_mult=0.125, in_hw=32, num_classes=10)
    cfg["program"]["kwargs"].update(width_mult=0.125, in_hw=32,
                                    num_classes=10)
    for k in ("worst_row_rel_l2", "rel_l2"):
        cfg["check"][k]["limit"] = LIMIT
    mix = {"loop": "closed", "microbatch": 4, "pool_images": 16,
           "size_mix": [[4, 1.0]], "backlog_requests": 2}
    bm = spec.benchmark()
    return run.Cell(name, cfg, spec.reference(name), mix, 1,
                    spec.metrics_of(bm, "end_to_end", f"{name}.offline"),
                    spec.metrics_of(bm, "per_layer", f"{name}.offline"))


@pytest.fixture
def jnp_lowering(monkeypatch):
    # the kernel suite's conftest asks for interpret mode; a whole model
    # through the interpreter would take minutes
    monkeypatch.setenv("REPRO_PALLAS", "jnp")


def _run(seed=2 ** 33 + 1):
    import jax
    return run.run_cell(_tiny_cell(), seed, 0.5, False, jax.devices())


def test_sound_run_is_correct(jnp_lowering):
    out = _run()
    res = out["result"]
    assert res["correct"] is True
    assert res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) == {"images_per_s", "setup_s"}
    assert res["metrics"]["images_per_s"]["unit"] == "images/s"
    assert res["device"]["platform"] == "cpu"
    assert list(res)[-1] == "check"
    assert res["check"]["rel_l2"]["value"] < LIMIT
    assert out["notes"]["compiles_in_window"] == 0


def test_answer_altered_where_produced_is_caught(jnp_lowering, monkeypatch):
    from repro.distributed.conv_pipeline import ConvPipeline
    orig = ConvPipeline.tick
    seen = {"n": 0}

    def tick(self, inject=None, tag=None):
        out = []
        for t, y in orig(self, inject, tag):
            seen["n"] += 1
            if seen["n"] == 3:            # one microbatch of the window:
                y = y.at[0].set(y[1])     # row 0 gets row 1's answer
            out.append((t, y))
        return out

    monkeypatch.setattr(ConvPipeline, "tick", tick)
    res = _run()["result"]
    assert seen["n"] > 3
    assert res["correct"] is False
    assert res["check"]["worst_row_rel_l2"]["value"] > LIMIT


@pytest.mark.parametrize("name", ["resnet50", "mobilenet_v2"])
def test_int4_control_fails_the_limits(name):
    import jax
    import control
    cell = _tiny_cell(name)
    k_w, k_pool, _ = run.seed_keys(5)
    w = jax.jit(lambda k: cell.ref.init(k, cell.cfg))(k_w)
    pool = np.asarray(jax.random.normal(k_pool, (16, 32, 32, 3)))
    ref = run.reference_logits(cell, w, pool)
    low = np.asarray(jax.jit(lambda p, x: cell.ref.forward(
        p, x, cell.cfg, bits=control.CONTROL_BITS))(w, pool))
    ok, checked = verdict.judge(verdict.compare(control._records(low), ref),
                                cell.cfg["check"])
    assert not ok
    assert checked["rel_l2"]["value"] > LIMIT


def test_served_params_runs_the_configs_prepare_step():
    """A configuration may name a method of the program's config that
    turns the drawn tree into the served one (repvgg's branch fusion)."""
    import jax
    from repro import nn
    from repro.models.repvgg import RepVGGConfig
    pc = RepVGGConfig(width_mult=0.125, num_classes=10, in_hw=32)
    w = nn.unbox(pc.init(jax.random.PRNGKey(0)))
    served = run.served_params(pc, w, "int8", "fuse")
    assert [set(b) for b in served["blocks"]] == \
        [{"w", "scale", "bias"}] * len(w["blocks"])
    assert served["blocks"][0]["w"]["values"].dtype == np.int8


def _bench_cmd(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("REPRO_PALLAS", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "resnet50.offline",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def test_refuses_without_a_tpu():
    p = _bench_cmd(spec.ROOT)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert not p.stdout.strip()


def test_refuses_with_only_its_own_files(tmp_path):
    shutil.copy(spec.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(spec.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _bench_cmd(tmp_path)
    assert p.returncode != 0
    assert not p.stdout.strip()


NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def test_every_named_piece_is_found():
    bm = spec.benchmark()
    assert bm["command"] == ["python3", "bench/run.py"]
    assert bm["paths"] == ["bench"]
    cfgs = {c["name"]: c for c in bm["configs"]}
    for c in bm["configs"]:
        assert NAME.match(c["name"]) and c["file"].startswith("bench/")
        assert spec.config(c["name"])["source"] == c["source"]
        assert callable(spec.reference(c["name"]).forward)
    metric_names = [m["name"] for m in bm["end_to_end"] + bm["per_layer"]]
    assert len(set(metric_names)) == len(metric_names)
    for m in bm["end_to_end"] + bm["per_layer"]:
        assert NAME.match(m["name"]) and callable(
            spec.metric_reader(m["name"]))
    for w in bm["workloads"]:
        assert NAME.match(w["name"]) and w["config"] in cfgs
        mix = spec.traffic(w["traffic"])
        assert mix["loop"] in ("open", "closed")
        cell = run.cell_from_benchmark(w["name"], bm)
        e2e = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in e2e and len(e2e) >= 2 and cell.per_layer
        for m in cell.per_layer:
            assert m["moves"] in e2e
    assert json.loads((spec.ROOT / "BENCHMARK.json").read_text()) == bm
