"""Test-sized copies of the benchmark's configurations."""
import copy
import json
from pathlib import Path

from bench import harness

ROOT = Path(__file__).resolve().parents[2]


def tiny_spec(root=ROOT):
    """``BENCHMARK.json`` under ``root`` with each configuration swapped
    for the test-sized copy that its file names (``test_copy``: the same
    kernels and deployment)."""
    with open(root / "BENCHMARK.json") as f:
        spec = copy.deepcopy(json.load(f))
    for c in spec["configs"]:
        c["file"] = harness.load_json(root / c["file"])["test_copy"]
    return spec


def tiny_cell(name, root=ROOT, committed_limits=True):
    """The cell at test size, with the committed configuration's
    reference and work model and, unless ``committed_limits`` is false,
    its correctness limits (else the test-sized copy's own)."""
    cell = harness.resolve_cell(name, root, spec=tiny_spec(root))
    committed = harness.resolve_cell(name, root).config
    keys = ["reference", "work"] + ["limits"] * committed_limits
    cell.config.update({k: copy.deepcopy(committed[k]) for k in keys})
    return cell
