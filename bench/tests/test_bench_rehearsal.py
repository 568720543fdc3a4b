"""A CPU rehearsal of the benchmark: every cell resolves its files by
name, and the harness loop runs each cell at test size end to end."""
import json
import os
import shutil
import subprocess
import sys
import time

import pytest
from bench.tests.tiny import ROOT, tiny_cell

from bench import harness

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in SPEC["workloads"]]


def test_every_cell_resolves_its_files_by_name():
    used = set()
    for name in CELLS:
        cell = harness.resolve_cell(name)
        used.add(cell.config["name"])
        assert cell.config["limits"].keys() == {
            "loss_gap", "grad_gap", "change_gap", "replica_gap"}
        assert cell.end_to_end and cell.per_layer
        assert "setup_s" in {m["name"] for m in cell.end_to_end}
        for metric in cell.per_layer:
            assert callable(harness.load_reader(metric["name"]))
            if metric["name"].endswith("_roofline"):
                kernel = metric["name"][:-len("_roofline")]
                assert (ROOT / "bench" / "work" / f"{kernel}.py").is_file()
    assert used == {c["name"] for c in SPEC["configs"]}
    for c in SPEC["configs"]:
        cfg = harness.load_json(ROOT / c["file"])
        assert cfg["name"] == c["name"] and cfg["reduced"] == c["reduced"]


def test_every_configuration_names_its_own_files():
    """A configuration is its file and the reference, work model and
    test-sized copy that the file names."""
    for c in SPEC["configs"]:
        cfg = harness.load_json(ROOT / c["file"])
        for key in ("reference", "work", "test_copy"):
            assert (ROOT / cfg[key]).is_file(), (c["name"], key)
        assert callable(harness.config_module(cfg, "reference").sizes)
        assert callable(harness.config_module(cfg, "work").flops_per_token)


@pytest.mark.parametrize("name", CELLS)
def test_harness_loop_runs_each_cell_at_test_size(name):
    cell = tiny_cell(name)
    # long enough for the failover cell's first event (after 4 steps)
    # and the step after it, also on a CPU that other tests share
    res = harness.run_cell(cell, 2**33 + 17, 1.0, False, time.perf_counter())
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert set(res["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert list(res)[-1] == "checks"
    assert res["device"]["platform"] == "cpu"


def run_bench(cwd, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0], "--seed",
         "1", "--seconds", "1", *args], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=300)


def test_the_command_refuses_the_cpu():
    out = run_bench(ROOT)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "no TPU" in out.stderr


def test_the_command_refuses_a_tree_without_the_system(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    out = run_bench(tmp_path)
    assert out.returncode != 0
    assert out.stdout.strip() == ""


def synthetic_ctx(name):
    """The context a traced run hands the readers, over a hand-made
    trace of two steps and one failure of one chip: each step runs the
    grads program (with one flash and one SSD kernel call) and a bucket
    update; the failure's span is mostly idle."""
    from bench import trace
    from bench.trace import Event
    dev, host = "/device:TPU:0", "/host:CPU"
    ms = 1_000_000
    events = [Event(host, "python", "bench.window", 0, 1000 * ms),
              Event(host, "python", "bench.recover", 500 * ms, 200 * ms)]
    for t0 in (0, 700 * ms):
        events += [
            Event(host, "python", "bench.step", t0, 20 * ms),
            Event(host, "python", "bench.loss", t0 + 20 * ms, 280 * ms),
            Event(dev, trace.MODULES_LINE, "jit_grads_fn(1)", t0 + 10 * ms,
                  240 * ms),
            Event(dev, trace.OPS_LINE, "%jvp_jit_flash_attention_fwd__.1",
                  t0 + 20 * ms, 100 * ms),
            Event(dev, trace.OPS_LINE, "%jvp_jit_ssd_fwd__.2",
                  t0 + 130 * ms, 100 * ms),
            Event(dev, trace.MODULES_LINE, "jit_upd(2)", t0 + 260 * ms,
                  30 * ms),
            Event(dev, trace.OPS_LINE, "%fusion.2", t0 + 260 * ms, 30 * ms)]
    events.append(Event(dev, trace.OPS_LINE, "%copy.1", 550 * ms, 20 * ms))
    programs = {"grads": [{"module": "jit_grads_fn", "calls": {
                    "jvp_jit_flash_attention_fwd__.1": "flash_attention_fwd",
                    "jvp_jit_ssd_fwd__.2": "ssd_fwd"}}],
                "bupdate": [{"module": "jit_upd", "calls": {}}]}
    cell = harness.resolve_cell(name)
    window = {"steps": 2, "seconds": 1.0, "tokens": 2 * 8192,
              "window_compiles": 0,
              "events": [{"kind": "fail", "t_call": 0.5, "t_bound": 0.7,
                          "replan_s": 0.001, "copied_bytes": 1 << 30,
                          "first_step_end": 1.0}]}
    return harness.reader_context(cell, window,
                                  trace.Reduction(events, programs),
                                  "TPU v5 lite", 10_000_000_000)


@pytest.mark.parametrize("name", CELLS)
def test_every_per_layer_reader_reads_a_traced_run(name):
    ctx = synthetic_ctx(name)
    for metric in ctx["cell"].per_layer:
        value = harness.load_reader(metric["name"])(ctx)
        assert value is not None and value >= 0, metric["name"]
        if metric["unit"] == "%":
            assert value <= 100, metric["name"]
    read = lambda m: harness.load_reader(m)(ctx)  # noqa: E731
    if name == "gpt3_medium.failover":
        assert read("replan_ms") == pytest.approx(1.0)
        assert read("rebind_copy_ms") == pytest.approx(199.0)
        # 20 of the 200 ms inside bench.recover are busy
        assert read("recovery_idle_share") == pytest.approx(90.0)
        assert read("window_compiles") == 0
    else:
        assert read("grads_ms") == pytest.approx(240.0)
        assert read("sync_update_ms") == pytest.approx(30.0)
        # ops cover 2 x 230 ms and the copy 20 ms of the 1000 ms window
        assert read("idle_share") == pytest.approx(52.0)
        assert read("peak_hbm_gb") == pytest.approx(10.0)
