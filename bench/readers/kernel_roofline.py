"""Share of the kernel's roofline (%): the least time the chip could take
for the bytes the counted graphs' slice pairs need (``bench.roofline``),
over the device time of the kernel's operations in the trace.

The kernel's operations are those whose name or stats match ``kernel``.
A window that counted graphs but holds no such operation is an error: the
kernel was renamed or left the path, and the metric would read something
else. The names matched and their number go into the notes.
"""
from __future__ import annotations

import collections

from bench import roofline
from bench import trace as trace_mod


def read(ctx, kernel: str):
    pairs = ctx.outcome.pairs
    if not pairs:
        return None
    events = trace_mod.matching(ctx.trace.ops, kernel, field="text")
    if not events:
        raise LookupError(f"no device operation of the window matches {kernel!r}")
    names = collections.Counter(trace_mod.module_name(e.name) for e in events)
    ctx.notes.update(matched=dict(names.most_common(5)), ops=len(events))
    least = roofline.least_time_s(sum(pairs), ctx.slice_bits, len(pairs),
                                  ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / (trace_mod.time_ns(events, ctx.window_ns) / 1e9)
