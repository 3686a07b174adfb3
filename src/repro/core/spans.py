"""Host spans for the platform's hot paths (internal; not part of the
``repro.core`` surface).

A :class:`span` does two things at once: it opens a profiler annotation,
so that in a traced run the span lies on the host plane of the same trace
as the device's operations and on the same clock; and it adds its
wall-clock duration to a counter in a dict the caller already keeps (a
fused unit's ``stats``, ``ServeEngine.metrics``, a sidecar's
``timings``), so the same time is read without a trace.  Spans that only
wait on the device carry ``wait`` in their name; the trace reduction never
names an idle gap after a wait.
"""
from __future__ import annotations

import time

try:
    from jax.profiler import TraceAnnotation
except Exception:  # the platform core runs without jax
    TraceAnnotation = None


class span:
    """``with span(name, counters, key, **args):`` — annotate the block as
    ``name`` (with ``args``, e.g. a message ``seq``) and add its duration
    in seconds to ``counters[key]``; with ``counters`` None it only
    annotates."""

    __slots__ = ("_ann", "_counters", "_key", "_t0")

    def __init__(self, name: str, counters: dict | None = None,
                 key: str | None = None, **args):
        self._ann = (TraceAnnotation(name, **args)
                     if TraceAnnotation is not None else None)
        self._counters = counters
        self._key = key

    def __enter__(self) -> None:
        if self._ann is not None:
            self._ann.__enter__()
        self._t0 = time.perf_counter()

    def __exit__(self, *exc) -> None:
        dt = time.perf_counter() - self._t0
        if self._ann is not None:
            self._ann.__exit__(*exc)
        if self._counters is not None:
            self._counters[self._key] += dt
