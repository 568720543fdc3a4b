"""Reduction of a profiler trace of the window to device metrics.

The JAX profiler writes ``<dir>/plugins/profile/<time>/*.xplane.pb``.
``load_events`` flattens it to plain events (plane, line, name, start and
duration in ns); everything else here works on that list, so the
same code runs on hand-placed events in the tests.

* Device busy time is the union of the intervals in which an operation
  ran on a device (the ``XLA Ops`` lines of the ``/device:`` planes),
  clipped to the benchmark's ``bench.window`` host span and averaged
  over the chips in use.
* Program time is the summed duration of a program's executions (the
  ``XLA Modules`` lines), matched by HLO module name.
* Kernel time is the summed duration of the Pallas custom calls that a
  program's compiled HLO makes to a kernel module's entry functions.
* Idle gaps are the stretches of the window with no device operation,
  each named by the innermost host span open at its start: the
  benchmark's own ``bench.*`` spans or the program's ``oobleck.*``
  spans (``repro.utils.spans``).
"""
from __future__ import annotations

import bisect
import dataclasses
import re
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

DEVICE_PLANE = "/device:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "bench.window"
SPAN_PREFIX = ("bench.", "oobleck.")


@dataclasses.dataclass
class Event:
    plane: str
    line: str
    name: str           # a device op's HLO instruction, e.g. %fusion.3
    start: int          # ns
    dur: int            # ns

    @property
    def end(self) -> int:
        return self.start + self.dur


# ----------------------------------------------------------------------
# Loading
# ----------------------------------------------------------------------
def find_xplane(trace_dir: Path) -> Path:
    found = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load_events(path: Path) -> List[Event]:
    """Device events of every ``/device:`` plane, and the host spans of
    the benchmark and of the program (``SPAN_PREFIX``); other host
    events are dropped."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(str(path))
    out: List[Event] = []
    for plane in data.planes:
        device = plane.name.startswith(DEVICE_PLANE)
        for line in plane.lines:
            if device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            for ev in line.events:
                if device or ev.name.startswith(SPAN_PREFIX):
                    # an op's name is its instruction's whole HLO text:
                    # keep the instruction name
                    name = ev.name.split(" ", 1)[0]
                    out.append(Event(plane.name, line.name, name,
                                     int(ev.start_ns), int(ev.duration_ns)))
    return out


# ----------------------------------------------------------------------
# Interval arithmetic
# ----------------------------------------------------------------------
def union(intervals: Iterable[Tuple[int, int]]) -> List[Tuple[int, int]]:
    merged: List[List[int]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def clip(intervals: Iterable[Tuple[int, int]], lo: int, hi: int
         ) -> List[Tuple[int, int]]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


def covered(intervals: Iterable[Tuple[int, int]]) -> int:
    return sum(b - a for a, b in union(intervals))


# ----------------------------------------------------------------------
# Custom calls of a program's HLO
# ----------------------------------------------------------------------
_CUSTOM = re.compile(r"^\s*(?:ROOT\s+)?%([^\s=]+)\s*=.*"
                     r"custom_call_target=\"tpu_custom_call\"")
_WRAPPERS = re.compile(r"^(?:jvp_|transpose_|jit_|vmap_|remat_|"
                       r"checkpoint_)+")


def kernel_calls(hlo_text: str) -> Dict[str, str]:
    """HLO instruction name -> the kernel function it calls, for every
    Pallas custom call of a compiled program.  The compiler names the
    instruction after the jitted kernel entry, wrapped by the
    transformations (``transpose_jvp_jit_flash_attention_bwd___.3`` ->
    ``flash_attention_bwd``)."""
    out = {}
    for line in hlo_text.splitlines():
        m = _CUSTOM.match(line)
        if m:
            base = re.sub(r"\.\d+$", "", m.group(1))
            out[m.group(1)] = _WRAPPERS.sub("", base).strip("_")
    return out


def op_name(e: Event) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``."""
    return e.name.split(" ", 1)[0].lstrip("%")


# ----------------------------------------------------------------------
# The reduction
# ----------------------------------------------------------------------
class Reduction:
    def __init__(self, events: Sequence[Event],
                 programs: Optional[Dict[str, List[Dict]]] = None):
        self.events = list(events)
        spans = [e for e in self.events if not e.plane.startswith(
            DEVICE_PLANE)]
        win = [e for e in spans if e.name == WINDOW_SPAN]
        if not win:
            raise ValueError("the trace has no bench.window span")
        self.lo, self.hi = win[0].start, win[0].end
        self.spans = [e for e in spans if e.name != WINDOW_SPAN]
        self.devices = sorted({e.plane for e in self.events
                               if e.plane.startswith(DEVICE_PLANE)})
        self.ops = [e for e in self.events if e.line == OPS_LINE
                    and e.plane.startswith(DEVICE_PLANE)
                    and e.end > self.lo and e.start < self.hi]
        self.modules = [e for e in self.events if e.line == MODULES_LINE
                        and e.plane.startswith(DEVICE_PLANE)
                        and e.end > self.lo and e.start < self.hi]
        self.programs = programs or {}

    # -- device busy / idle ------------------------------------------------
    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) / 1e9

    def busy_ns(self, lo: Optional[int] = None, hi: Optional[int] = None
                ) -> float:
        """Union of op intervals in [lo, hi], averaged over devices."""
        lo = self.lo if lo is None else lo
        hi = self.hi if hi is None else hi
        if not self.devices:
            return 0.0
        per = [covered(clip([(e.start, e.end) for e in self.ops
                             if e.plane == d], lo, hi))
               for d in self.devices]
        return sum(per) / len(per)

    @property
    def busy_s(self) -> float:
        return self.busy_ns() / 1e9

    def idle_share(self, lo: Optional[int] = None, hi: Optional[int] = None
                   ) -> Optional[float]:
        lo = self.lo if lo is None else lo
        hi = self.hi if hi is None else hi
        if hi <= lo or not self.devices:
            return None
        return 1.0 - self.busy_ns(lo, hi) / (hi - lo)

    def span_intervals(self, name: str) -> List[Tuple[int, int]]:
        return [(e.start, e.end) for e in self.spans if e.name == name]

    def idle_gaps(self) -> List[Tuple[str, int]]:
        """(open host span, gap ns) for every idle stretch of the
        window on the first device, longest first."""
        if not self.devices:
            return []
        busy = union(clip([(e.start, e.end) for e in self.ops
                           if e.plane == self.devices[0]],
                          self.lo, self.hi))
        gaps, t = [], self.lo
        for a, b in busy + [(self.hi, self.hi)]:
            if a > t:
                gaps.append((t, a - t))
            t = max(t, b)
        # one sweep: the gaps come in time order
        spans = sorted(self.spans, key=lambda e: e.start)
        named, open_, i = [], [], 0
        for t, ns in gaps:
            while i < len(spans) and spans[i].start <= t:
                open_.append(spans[i])
                i += 1
            open_ = [e for e in open_ if e.end > t]
            named.append((innermost(open_), ns))
        return sorted(named, key=lambda g: -g[1])

    def open_span(self, t: int) -> str:
        """The innermost host span open at ``t``."""
        return innermost([e for e in self.spans if e.start <= t < e.end])

    # -- programs and kernels ----------------------------------------------
    def modules_of(self, kinds: Iterable[str]) -> set:
        return {p["module"] for k in kinds for p in self.programs.get(k, [])}

    def _first_device(self, events: Iterable[Event]) -> List[Event]:
        dev = self.devices[0] if self.devices else None
        return [e for e in events if e.plane == dev]

    def program_ns(self, kinds: Iterable[str]) -> int:
        """Device time of the executions of the programs of these cache
        key kinds, inside the window (first device)."""
        names = self.modules_of(kinds)
        return sum(min(e.end, self.hi) - max(e.start, self.lo)
                   for e in self._first_device(self.modules)
                   if module_name(e) in names)

    def module_at(self) -> Dict[int, str]:
        """id(op event) -> name of the program execution that holds it
        (first device)."""
        mods = sorted(self._first_device(self.modules),
                      key=lambda e: e.start)
        starts = [e.start for e in mods]
        out = {}
        for e in self._first_device(self.ops):
            i = bisect.bisect_right(starts, e.start) - 1
            if i >= 0 and e.end <= mods[i].end:
                out[id(e)] = module_name(mods[i])
        return out

    def kernel_ns(self, functions: Iterable[str],
                  kinds: Iterable[str] = ("grads",)) -> int:
        """Device time, on the first device, of the Pallas custom calls
        that the programs of these kinds make to any of ``functions``
        (the kernel entries of one kernel module).  0 when none runs."""
        functions = set(functions)
        calls = {(p["module"], op) for k in kinds
                 for p in self.programs.get(k, [])
                 for op, fn in p["calls"].items() if fn in functions}
        where = self.module_at()
        return sum(min(e.end, self.hi) - max(e.start, self.lo)
                   for e in self._first_device(self.ops)
                   if (where.get(id(e)), op_name(e)) in calls)

    # -- the breakdown the result line carries -------------------------------
    def self_times(self) -> Dict[str, int]:
        """Self time of each op (its duration less the ops nested in it,
        such as a while loop's body), summed by program and
        instruction, on the first device."""
        where = self.module_at()
        ops = sorted(self._first_device(self.ops),
                     key=lambda e: (e.start, -e.dur))
        own = {id(e): min(e.end, self.hi) - max(e.start, self.lo)
               for e in ops}
        stack: List[Event] = []
        for e in ops:
            while stack and stack[-1].end <= e.start:
                stack.pop()
            if stack:
                parent = stack[-1]
                own[id(parent)] -= (min(e.end, parent.end, self.hi)
                                    - max(e.start, self.lo))
            stack.append(e)
        by: Dict[str, int] = {}
        for e in ops:
            key = f"{where.get(id(e), '?')}/{op_name(e)}"
            by[key] = by.get(key, 0) + own[id(e)]
        return by

    def breakdown(self) -> Dict:
        top = sorted(self.self_times().items(), key=lambda kv: -kv[1])[:10]
        by: Dict[str, int] = {}
        for span, ns in self.idle_gaps():
            by[span] = by.get(span, 0) + ns
        gaps = sorted(by.items(), key=lambda kv: -kv[1])[:10]
        return {"device_ops": [[k, v / 1e9] for k, v in top],
                "idle_gaps": [[k, v / 1e9] for k, v in gaps]}


def innermost(spans: Sequence[Event]) -> str:
    """The name of the shortest of these open spans, or ``none``."""
    return min(spans, key=lambda e: e.dur).name if spans else "none"


def module_name(e: Event) -> str:
    """``jit_grads_fn(1770692291)`` -> ``jit_grads_fn``."""
    return re.sub(r"\(\d+\)$", "", e.name)


def reduce_dir(trace_dir: Path, programs: Dict[str, List[Dict]]
               ) -> Reduction:
    return Reduction(load_events(find_xplane(trace_dir)), programs)

