"""Named spans on the profiler's clock, and the host time of each stage.

``span(name, timings, key, **args)`` is a context manager that opens a
``jax.profiler.TraceAnnotation``: while a trace is recorded
(``jax.profiler.trace``) the span appears on the host plane, on the same
clock as the device's operations, so an idle gap on the device can be put
down to the stage the host was in. When ``timings`` is given, the host's
``perf_counter`` duration of the block also lands in ``timings[key]``: that
is the host's time in the stage, which for a stage that only dispatches
device work is the dispatch; the stage's device time is in the trace.

With no trace recorded a span costs one ``TraceAnnotation`` construction
and two ``perf_counter`` calls. Spans wrap stages, never per-chunk or
per-element work, so their number does not grow with the graph.
"""
from __future__ import annotations

import itertools
import time

from jax.profiler import TraceAnnotation

__all__ = ["span", "next_count_id"]

_COUNT_IDS = itertools.count(1)


def next_count_id() -> int:
    """A process-wide serial that the spans of one count share."""
    return next(_COUNT_IDS)


class span:
    """``with span("tc.orient", timings, "orient"): ...``"""

    __slots__ = ("_ann", "_timings", "_key", "_t0")

    def __init__(self, name: str, timings: dict | None = None,
                 key: str | None = None, **args):
        self._ann = TraceAnnotation(name, **args)
        self._timings = timings
        self._key = name if key is None else key

    def __enter__(self):
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        if self._timings is not None:
            self._timings[self._key] = time.perf_counter() - self._t0
        return self._ann.__exit__(*exc)
