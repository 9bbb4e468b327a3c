"""Closed loop of one-shot counts.

One client counts relabelled copies of one seeded graph back to back
through ``repro.core.tcim_count``: the next count starts when the last one
returns. Counts run until ``--seconds`` have passed; the count in flight
then finishes and is included. ``count_s`` is the whole window divided by
the counts completed.

Every count is a distinct graph (a fresh random vertex relabelling, made
on a helper thread while the previous count runs), so no content-keyed
cache of the program can hit; the triangle count is the base graph's,
which the reference computes once in a child process while the warm-up
count and the window run, and which is read once the window has closed.

Traffic key: ``expect``, the ``stats`` entries a count has to report, such
as the build and the execute implementation.
"""
from __future__ import annotations

import concurrent.futures
import time

import numpy as np

from bench import graphs
from bench.harness import Check, Harness, Outcome


def run(h: Harness) -> Outcome:
    g = h.config["graph"]
    n, m, gseed = int(g["n"]), int(g["m"]), int(g["seed"])
    expect = dict(h.traffic.get("expect", {}))
    ref_pool = h.pool(1)
    ref = ref_pool.apply_async(graphs.rmat_triangles, (n, m, gseed))
    base = graphs.rmat(n, m, gseed)
    stream = copies(base, n, h.seed)
    helper = concurrent.futures.ThreadPoolExecutor(1)
    try:
        return _run(h, n, expect, ref, stream, helper)
    finally:
        helper.shutdown(wait=True)


def copies(base: np.ndarray, n: int, seed: int):
    """The relabelled copies a run counts, in order, fixed by the seed."""
    rng = np.random.default_rng([seed, 0x0C0])
    while True:
        yield graphs.relabel(base, n, rng)


def _count(tcim_count, edges, n, expect):
    import jax

    with jax.profiler.TraceAnnotation("bench.tcim_count"):
        res = tcim_count(edges, n=n)
    off = {k: res.stats.get(k) for k, v in expect.items()
           if res.stats.get(k) != v}
    return int(res.triangles), off


def _run(h, n, expect, ref, stream, helper) -> Outcome:
    from repro.core import tcim_count

    nxt = helper.submit(next, stream)
    warm = nxt.result()
    nxt = helper.submit(next, stream)
    # Warm-up: the same buckets as every relabelled copy, so the window
    # compiles nothing.
    warm_count, warm_off = _count(tcim_count, warm, n, expect)
    counts, offs, kept = [], [], []
    t0 = h.open_window()
    while True:
        edges = nxt.result()
        nxt = helper.submit(next, stream)
        c, off = _count(tcim_count, edges, n, expect)
        counts.append(c)
        offs.append(off)
        if h.trace:
            kept.append(edges)
        if time.perf_counter() - t0 >= h.seconds:
            break
    h.close_window()
    nxt.cancel()
    want = int(ref.get(timeout=600))
    wrong = [c for c in counts if c != want]
    off_path = sum(1 for o in offs if o)
    failed = sum(1 for c, o in zip(counts, offs) if c != want or o)
    pairs = []
    if h.trace:
        pool = h.pool(min(len(kept), 4))
        pairs = pool.starmap(graphs.slice_pairs,
                             [(e, n, int(h.config.get("slice_bits", 64)))
                              for e in kept])
    return Outcome(
        attempted=len(counts),
        failed=failed,
        checks=[
            Check("wrong_counts", len(wrong), 0),
            Check("max_count_gap", max((abs(c - want) for c in counts), default=0), 0),
            Check("off_path_counts", off_path, 0),
        ],
        metrics={"count_s": h.window_s / len(counts)},
        graphs=len(counts),
        pairs=pairs,
        notes={
            "reference_triangles": want,
            "warmup": {"triangles": warm_count, "off_path": warm_off},
            "off_path": [o for o in offs if o][:3],
        },
    )
