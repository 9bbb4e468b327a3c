"""Share of the per-vertex attribution's roofline (%): the least time the
chip could take for the bytes the queried graphs' slice pairs and
triangles need (``bench.vertex_bytes``), over the device time of the
attribution's modules in the trace.

The triangles per graph are the reference's (``reference_triangles`` in
the outcome's notes), the pairs the outcome's. A window that answered
queries but ran none of the modules is an error: they were renamed or
left the path. The names matched go into the notes.
"""
from __future__ import annotations

import collections

from bench import trace as trace_mod
from bench import vertex_bytes


def read(ctx, modules: str):
    pairs = ctx.outcome.pairs
    if not pairs:
        return None
    events = trace_mod.matching(ctx.trace.modules, modules)
    if not events:
        raise LookupError(f"no device module of the window matches {modules!r}")
    ctx.notes.update(matched=dict(collections.Counter(e.name for e in events)))
    triangles = int(ctx.outcome.notes["reference_triangles"]) * len(pairs)
    least = vertex_bytes.least_time_s(sum(pairs), triangles, ctx.slice_bits,
                                      ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / (trace_mod.time_ns(events, ctx.window_ns) / 1e9)
