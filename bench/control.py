#!/usr/bin/env python3
"""Readings that set the correctness limits, on the chip.

    python3 bench/control.py --workload gpt3_medium.steady \\
        --seeds 11,12,13 --faults half_batch,no_exchange --control

For each seed, in one process: a sound run of the cell (a short window),
a run with each named fault planted under the timed path, and the
control, the system computing in bfloat16 (``bench/faults.py``).  Prints one JSON line per reading with every number the
check compares.  The benchmark's own runs never run this.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--faults", default="")
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--sound", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=2.0)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from bench import faults, harness
    from bench.run import CACHE, open_chip

    open_chip()
    cell = harness.resolve_cell(args.workload)
    trace_dir = CACHE / "trace" / str(os.getpid())

    def emit(seed, what, checks):
        print(json.dumps({"workload": cell.name, "seed": seed, "what": what,
                          **faults.values(checks)}), flush=True)

    for seed in (int(s) for s in args.seeds.split(",")):
        if args.sound:
            res = harness.run_cell(cell, seed, args.seconds, False,
                                   time.perf_counter(), trace_dir)
            emit(seed, "sound", res["checks"])
        for fault in filter(None, args.faults.split(",")):
            with faults.planted(fault):
                res = harness.run_cell(cell, seed, args.seconds, False,
                                       time.perf_counter(), trace_dir)
            emit(seed, fault, res["checks"])
        if args.control:
            emit(seed, "control", faults.control(cell, seed, args.seconds))


if __name__ == "__main__":
    main()
