"""The correctness check fails where it should, at test size on the CPU
(bench/faults.py): each fault planted under the timed path fails the
committed configuration's limits; the control, the system computing in
bfloat16, fails the limits set for the test size (``tiny_*.json``) from
the same rule: sound runs there read about 1e-7, where a TPU's default
matmul precision reads about 1e-4 at the cells' sizes."""
import time

import pytest
from bench.tests.tiny import tiny_cell

from bench import faults, harness


@pytest.mark.parametrize("fault", faults.FAULTS)
def test_a_planted_fault_makes_the_run_incorrect(fault):
    cell = tiny_cell("gpt3_medium.failover")
    with faults.planted(fault):
        res = harness.run_cell(cell, 12345, 0.2, False, time.perf_counter())
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("name", ["gpt3_medium.steady",
                                  "mamba2_780m.steady"])
def test_the_bfloat16_control_is_incorrect(name):
    cell = tiny_cell(name, committed_limits=False)
    checks = faults.control(cell, 2**32 + 3, 0.2)
    assert not harness.is_correct(checks), checks
