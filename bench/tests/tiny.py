"""Test-sized copies of the benchmark's configurations."""
import copy
import json
from pathlib import Path

from bench import harness

ROOT = Path(__file__).resolve().parents[2]
TINY = {"gpt3_medium": "tiny_dense", "mamba2_780m": "tiny_ssm"}


def tiny_spec():
    """BENCHMARK.json with each configuration swapped for its test-sized
    copy in ``bench/tests/data`` (same family, kernels and deployment)."""
    with open(ROOT / "BENCHMARK.json") as f:
        spec = copy.deepcopy(json.load(f))
    for c in spec["configs"]:
        c["file"] = f"bench/tests/data/{TINY[c['name']]}.json"
    return spec


def tiny_cell(name):
    """The cell at test size, held to the committed configuration's
    correctness limits."""
    cell = harness.resolve_cell(name, spec=tiny_spec())
    committed = harness.resolve_cell(name).config
    cell.config["limits"] = dict(committed["limits"])
    return cell
