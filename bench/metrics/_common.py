"""Helpers the per-layer readers share.  A reader is
``bench/metrics/<metric>.py`` with ``read(ctx) -> float | None``; the
context is built by ``bench.harness.reader_context`` after a traced window:

* ``config``: the configuration file; ``window``: steps, tokens,
  seconds, the events' host timings and the compile count;
* ``trace``: a ``bench.trace.Reduction`` of the window's trace;
* ``peaks``: the device kind's row of ``bench/peaks.json``;
* ``chips``, ``memory_peak_bytes``, ``root``;
* ``flops_per_token``: the model FLOPs per token by the work model that
  the configuration names (``work``).

``None`` means the reader found nothing to read in this run.
"""
from __future__ import annotations

from typing import Dict, List, Optional

#: ProgramCache key kinds of the sync data plane (runtime/sync_exec.py)
SYNC_KINDS = ("bpack", "bscale", "badd", "bsumsq", "bef", "bupdate",
              "update")


def mfu(ctx: Dict) -> Optional[float]:
    w = ctx["window"]
    if not w["steps"]:
        return None
    rate = w["tokens"] / w["seconds"]
    peak = ctx["peaks"]["bf16_flops"] * ctx["chips"]
    return 100.0 * ctx["flops_per_token"] * rate / peak


def kernel_functions(kernel: str) -> set:
    """The entry functions of the system's kernel module
    ``repro.kernels.<kernel>``, whose names the compiler gives the
    module's Pallas custom calls."""
    import importlib
    import inspect
    mod = importlib.import_module(f"repro.kernels.{kernel}")
    return {n for n, f in inspect.getmembers(mod, callable)
            if getattr(f, "__module__", None) == mod.__name__}


def roofline(ctx: Dict, kernel: str) -> Optional[float]:
    """Least time of the kernel's work in the window over its traced
    device time, in %.  The work of one sequence through one layer comes
    from ``bench/work/<kernel>.py``; every sequence of every step passes
    every layer once."""
    import importlib
    ns = ctx["trace"].kernel_ns(kernel_functions(kernel))
    if not ns:
        return None
    flops, nbytes = importlib.import_module(f"bench.work.{kernel}").work(
        ctx["config"])
    w, cfg = ctx["window"], ctx["config"]
    calls = w["steps"] * cfg["deployment"]["global_batch"] * cfg["num_layers"]
    row = ctx["peaks"]
    least = max(flops * calls / row["bf16_flops"],
                nbytes * calls / row["hbm_bytes_per_s"])
    return 100.0 * least / (ns / 1e9)


def per_step_ms(ctx: Dict, kinds) -> Optional[float]:
    w = ctx["window"]
    ns = ctx["trace"].program_ns(kinds)
    if not w["steps"] or not ns:
        return None
    return ns / 1e6 / w["steps"]


def failures(ctx: Dict) -> List[Dict]:
    return [e for e in ctx["window"]["events"] if e["kind"] == "fail"]
