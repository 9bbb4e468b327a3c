"""Per-layer metric readers, one module each, named by the ``reader`` key
of ``bench/metrics/<metric>.json``. Each exposes ``read(ctx, **args)``,
which returns the metric's value, or ``None`` when the run holds nothing
to read (the harness then leaves the metric out of the line). What a reader
puts in ``notes`` goes into the result line's notes under the metric's
name."""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class ReadContext:
    trace: object  # bench.trace.Trace of the window
    window_ns: float
    outcome: object  # bench.harness.Outcome
    peaks: dict  # bench/peaks.json entry of the device kind
    slice_bits: int
    notes: dict = dataclasses.field(default_factory=dict)
