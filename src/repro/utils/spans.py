"""Named host spans on the profiler's clock.

``span(name)`` times a block with ``time.perf_counter`` and, while a JAX
profiler trace is being collected, also writes the block into that trace
as a ``TraceAnnotation`` — the same clock as the device planes, so a
reduction of the trace can say what the host was doing in each stretch
in which the device sat idle::

    with span("oobleck.recover.copy") as s:
        ...
    info["copy"] = s.seconds

With no trace being collected a span costs one flag check and two clock
reads; it never calls into the device or waits for it.  Names are
``oobleck.<layer>.<phase>``.
"""
from __future__ import annotations

import sys
import time

# jax.profiler.TraceAnnotation, imported by the first span after JAX has
# loaded: the planning layer (repro.core) times itself with spans, and a
# process that only plans never loads JAX
_TraceAnnotation = None


def _trace_annotation():
    global _TraceAnnotation
    if _TraceAnnotation is None and "jax" in sys.modules:
        from jax.profiler import TraceAnnotation
        _TraceAnnotation = TraceAnnotation
    return _TraceAnnotation


class span:
    """Context manager: ``seconds`` holds the block's wall time once the
    block has left."""

    __slots__ = ("name", "seconds", "_start", "_note")

    def __init__(self, name: str):
        self.name = name
        self.seconds = 0.0
        self._note = None

    def __enter__(self) -> "span":
        # without JAX loaded no profiler trace can be collecting
        note = _TraceAnnotation or _trace_annotation()
        if note is not None and note.is_enabled():
            self._note = note(self.name)
            self._note.__enter__()
        self._start = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        self.seconds = time.perf_counter() - self._start
        if self._note is not None:
            self._note.__exit__(*exc)
            self._note = None
        return False
