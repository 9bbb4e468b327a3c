"""Device-idle time inside the program's counts, per graph counted (ms).

The time in which no operation ran on the first device, within the
window and inside the program's ``tc.count`` spans, over the graphs
counted in the window: the host side of a count, as far as it holds the
device back. The notes split it by the innermost ``tc.*`` span that covers
each idle interval (``tc.count`` itself where no stage span does), in ms
per graph, and give the share that falls under a stage span.

A window that counted graphs with a program that emits spans, but holds no
``tc.count`` span, is an error; a program that emits none reads nothing.
"""
from __future__ import annotations

import collections

from bench import spans as spans_mod
from bench import trace as trace_mod


def read(ctx):
    graphs = ctx.outcome.graphs
    if not graphs:
        return None
    marked = spans_mod.spans(ctx.trace.host)
    counts = [e for e in marked if e.name == spans_mod.COUNT]
    if not counts:
        if not spans_mod.instrumented():
            return None
        raise LookupError(f"no {spans_mod.COUNT!r} span in a window that "
                          "counted graphs")
    idle = _intersect(_idle(ctx.trace, ctx.window_ns),
                      trace_mod.union((e.start, e.end) for e in counts))
    by = collections.Counter()
    for s, t in idle:
        for a, b, name in _split(s, t, marked):
            by[name] += b - a
    total = sum(by.values())
    ctx.notes.update(
        by_span_ms={k: v / graphs / 1e6 for k, v in by.most_common()},
        stage_share=(total - by[spans_mod.COUNT]) / total if total else None,
        count_spans=len(counts))
    return total / graphs / 1e6


def _idle(trace, window_ns: float) -> list:
    """Idle intervals of the first device within [0, window_ns]."""
    devs = trace.devices
    busy = trace_mod.clip(trace_mod.union(
        (e.start, e.end) for e in trace.ops if not devs or e.device == devs[0]),
        0.0, window_ns)
    out, prev = [], 0.0
    for s, t in busy + [(window_ns, window_ns)]:
        if s > prev:
            out.append((prev, s))
        prev = max(prev, t)
    return out


def _intersect(a: list, b: list) -> list:
    """Intersection of two sorted lists of disjoint intervals."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, t = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if s < t:
            out.append((s, t))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def _split(s: float, t: float, marked: list):
    """(start, end, span) pieces of [s, t], each named by the shortest span
    that covers it."""
    over = [e for e in marked if e.start < t and e.end > s]
    cuts = sorted({s, t} | {x for e in over for x in (e.start, e.end) if s < x < t})
    for a, b in zip(cuts, cuts[1:]):
        cover = [e for e in over if e.start <= a and b <= e.end]
        if cover:
            yield a, b, min(cover, key=lambda e: e.dur).name
