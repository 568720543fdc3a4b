"""A configuration comes as files of its own: the harness takes its sizes,
``ArchConfig`` groups and ``Model`` options from the configuration file,
and its reference, work model and test-sized copy from the files that
the configuration names."""
import dataclasses
import json
import shutil
import time

import jax.numpy as jnp
import pytest
from bench.tests.tiny import ROOT, tiny_cell

from bench import harness
from bench.work import model as work_model


def config(name):
    return harness.load_json(ROOT / "bench" / "configs" / f"{name}.json")


#: The sizes the dense and Mamba2 references read at the committed
#: configurations: the weights of every cell follow from these and the
#: seed alone.
SIZES = {
    "gpt3_medium": {
        "family": "dense", "num_layers": 8, "d_model": 1024,
        "vocab_size": 50257, "rms_norm_eps": 1e-06, "tie_embeddings": False,
        "num_heads": 16, "head_dim": 64, "d_ff": 4096, "rope_theta": 10000.0},
    "mamba2_780m": {
        "family": "ssm", "num_layers": 8, "d_model": 1536,
        "vocab_size": 50288, "rms_norm_eps": 1e-06, "tie_embeddings": True,
        "ssm": {"state_size": 128, "head_dim": 64, "expand": 2,
                "conv_width": 4, "chunk_size": 256, "n_groups": 1}},
}


@pytest.mark.parametrize("name", sorted(SIZES))
def test_the_reference_reads_the_committed_sizes(name):
    cfg = config(name)
    ref = harness.config_module(cfg, "reference")
    assert ref.sizes(cfg) == SIZES[name]


@pytest.mark.parametrize("name", sorted(SIZES))
def test_the_committed_configurations_build_the_same_system(name):
    from repro.configs import SSMConfig, get_arch
    cfg = config(name)
    given = {k: cfg[k] for k in (
        "name", "source", "family", "num_layers", "d_model", "num_heads",
        "num_kv_heads", "d_ff", "vocab_size", "rms_norm_eps",
        "tie_embeddings")}
    given.update({k: cfg[k] for k in ("head_dim", "mlp_variant",
                                      "rope_theta") if k in cfg})
    if "ssm" in cfg:
        given["ssm"] = SSMConfig(**cfg["ssm"])
    arch = harness.arch_config(cfg)
    assert arch == dataclasses.replace(get_arch(cfg["arch"]), **given)
    assert arch.moe is None
    dep = cfg["deployment"]
    assert harness.model_options(dep) == {
        "dtype": jnp.float32, "attn_impl": dep["attn_impl"],
        "ssd_impl": dep["ssd_impl"], "remat": False, "scan_layers": False}


def test_groups_and_options_follow_the_fields_they_name():
    from repro.configs import MoEConfig
    from repro.models import Model
    cfg = dict(config("gpt3_medium"),
               moe={"num_experts": 8, "top_k": 2, "num_shared_experts": 1})
    assert harness.arch_config(cfg).moe == MoEConfig(
        num_experts=8, top_k=2, num_shared_experts=1)
    dep = dict(cfg["deployment"], moe_impl="grouped",
               param_dtype="bfloat16", remat=True)
    opts = harness.model_options(dep)
    assert opts["moe_impl"] == "grouped"
    assert opts["param_dtype"] == jnp.bfloat16
    assert (opts["remat"], opts["scan_layers"]) == (False, False)
    assert "seq_len" not in opts and "nodes" not in opts
    model = Model(harness.arch_config(cfg), **opts)
    assert model.moe_impl == "grouped" and model.dtype == jnp.float32


def write(path, text):
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(text)


def test_a_configuration_added_as_new_files_runs_at_test_size(tmp_path):
    """A checkout to which a third configuration comes as new files only
    (its configuration file, reference, work model and test-sized copy)
    and a cell in BENCHMARK.json: the cell resolves, the harness loop
    runs it at test size through those files, and it is correct."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    own = {"reference": "bench/extra_reference.py",
           "work": "bench/work/extra_model.py",
           "test_copy": "bench/tests/data/tiny_extra.json"}
    write(tmp_path / own["reference"],
          '"""The dense reference, marking that it was used."""\n'
          "from pathlib import Path\n\n"
          "from bench import reference\n"
          "from bench.reference import *  # noqa: F401,F403\n\n\n"
          "def sizes(cfg):\n"
          '    Path(__file__).with_suffix(".used").write_text(cfg["name"])\n'
          "    return reference.sizes(cfg)\n")
    write(tmp_path / own["work"],
          '"""The dense FLOP count, marking that it was used."""\n'
          "from pathlib import Path\n\n"
          "from bench.work import model\n\n\n"
          "def flops_per_token(cfg):\n"
          '    Path(__file__).with_suffix(".used").write_text(cfg["name"])\n'
          "    return model.flops_per_token(cfg)\n")
    dense = config("gpt3_medium")
    write(tmp_path / "bench" / "configs" / "extra.json",
          json.dumps(dict(dense, name="extra", **own)))
    tiny = harness.load_json(ROOT / dense["test_copy"])
    tiny["name"] = "tiny_extra"
    write(tmp_path / own["test_copy"], json.dumps(tiny))

    spec = json.loads((tmp_path / "BENCHMARK.json").read_text())
    spec["configs"].append({
        "name": "extra", "source": "https://arxiv.org/abs/2005.14165",
        "file": "bench/configs/extra.json", "reduced": ["num_layers"],
        "why": "a configuration that comes as new files"})
    spec["workloads"].append({
        "name": "extra.steady", "config": "extra", "traffic": "steady",
        "chips": 1, "why": "as gpt3_medium.steady"})
    for metric in spec["end_to_end"] + spec["per_layer"]:
        if "gpt3_medium.steady" in metric.get("workloads", []):
            metric["workloads"].append("extra.steady")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    cell = tiny_cell("extra.steady", tmp_path)
    assert cell.config["name"] == "tiny_extra"
    res = harness.run_cell(cell, 2**33 + 29, 0.5, False, time.perf_counter())
    assert res["correct"], res["checks"]
    assert (tmp_path / "bench" / "extra_reference.used").read_text() == (
        "tiny_extra")
    assert set(res["metrics"]) == {"tokens_per_s", "setup_s"}

    w = res["window"]
    ctx = harness.reader_context(cell, w, None, "TPU v5 lite", 0)
    assert harness.load_reader("mfu", tmp_path)(ctx) == pytest.approx(
        100.0 * work_model.flops_per_token(cell.config) * w["tokens"]
        / w["seconds"] / 197e12)
    assert (tmp_path / "bench" / "work" / "extra_model.used").read_text() == (
        "tiny_extra")
