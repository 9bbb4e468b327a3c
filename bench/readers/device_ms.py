"""Device time of the named modules, per graph counted in the window (ms).

A window that counted graphs but ran none of the modules is an error: they
were renamed or left the path. The names matched go into the notes."""
from __future__ import annotations

import collections

from bench import trace as trace_mod


def read(ctx, modules: str):
    if not ctx.outcome.graphs:
        return None
    events = trace_mod.matching(ctx.trace.modules, modules)
    if not events:
        raise LookupError(f"no device module of the window matches {modules!r}")
    ctx.notes.update(matched=dict(collections.Counter(e.name for e in events)))
    return trace_mod.time_ns(events, ctx.window_ns) / ctx.outcome.graphs / 1e6
