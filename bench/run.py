#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload gpt3_medium.steady --seed 7 \\
        --seconds 20 --trace 0

Run from the root of a checkout.  The cell, its configuration, traffic
and metrics come from ``BENCHMARK.json`` and the files it names (see
``bench/harness.py``).  With ``--trace 0`` the result line carries the
cell's end-to-end metrics; with ``--trace 1`` a profiler trace of the
window gives its per-layer metrics.  The numbers the correctness check
compares are printed last on standard error and under ``checks`` in the
result line.

The run refuses to measure without a TPU, or with fewer chips than the
cell asks for: it exits nonzero and prints no result.  JAX's persistent
compilation cache lives at ``<checkout>/.bench_cache/jax``, so only the
first run of a cell in a checkout compiles.
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
CACHE = ROOT / ".bench_cache"


def fail(msg: str) -> None:
    print(f"[bench] FAIL: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def enable_cache(path: Path) -> None:
    """Persistent compilation cache at a fixed path in the checkout, for
    every program however small or quick to compile."""
    import jax
    path.mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def open_chip():
    """JAX's devices, once it is certain that they are TPUs; the TPU
    runtime's logs and the compilation cache go under the checkout."""
    (CACHE / "tpu_logs").mkdir(parents=True, exist_ok=True)
    os.environ.setdefault("TPU_LOG_DIR", str(CACHE / "tpu_logs"))
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        fail(f"JAX finds no TPU (platform {devices[0].platform!r})")
    enable_cache(CACHE / "jax")
    return devices


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        fail(f"no src/repro under {ROOT}: run from a checkout")
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

    from bench import harness
    cell = harness.resolve_cell(args.workload)
    devices = open_chip()
    if len(devices) < cell.chips:
        fail(f"{args.workload} needs {cell.chips} chips, JAX sees "
             f"{len(devices)}")
    print(f"[bench] {devices[0].device_kind} x{len(devices)}",
          file=sys.stderr, flush=True)

    result = harness.run_cell(cell, args.seed, args.seconds,
                              bool(args.trace), T_START,
                              trace_dir=CACHE / "trace" / str(os.getpid()))
    for name, c in result["checks"].items():
        print(f"[check] {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
