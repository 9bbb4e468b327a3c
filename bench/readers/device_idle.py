"""Share of the traced window in which no operation ran on the device (%)."""
from __future__ import annotations

from bench import trace as trace_mod


def read(ctx):
    if not ctx.trace.ops or ctx.window_ns <= 0:
        return None
    return 100.0 * (1.0 - trace_mod.busy_ns(ctx.trace, ctx.window_ns) / ctx.window_ns)
