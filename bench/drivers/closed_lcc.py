"""Closed loop of per-vertex LCC queries.

One client asks for the local clustering coefficient of every vertex
(LDBC Graphalytics LCC) of relabelled copies of one seeded graph, back to
back, through ``repro.core.tcim_vertex_counts``: the next query starts when
the last one returns. Queries run until ``--seconds`` have passed; the
query in flight then finishes and is included. ``count_s`` is the whole
window divided by the queries completed.

Every query is a distinct graph (a fresh random vertex relabelling, made
on a helper thread while the previous query runs), so no content-keyed
cache of the program can hit. The reference counts T of the base graph
(``bench.lcc_ref``) are computed once in a child process while the warm-up
and the window run; every query's T and LCC are compared, vertex by
vertex, with the reference mapped through that copy's permutation, once
the window has closed.

Traffic key: ``expect``, the ``stats`` entries a query has to report, such
as the build and what ran the attribution.
"""
from __future__ import annotations

import concurrent.futures
import time

import numpy as np

from bench import graphs, lcc_ref
from bench.harness import Check, Harness, Outcome


def run(h: Harness) -> Outcome:
    g = h.config["graph"]
    n, m, gseed = int(g["n"]), int(g["m"]), int(g["seed"])
    expect = dict(h.traffic.get("expect", {}))
    ref_pool = h.pool(1)
    ref = ref_pool.apply_async(lcc_ref.rmat_vertex_triangles, (n, m, gseed))
    base = graphs.rmat(n, m, gseed)
    stream = copies(base, n, h.seed)
    helper = concurrent.futures.ThreadPoolExecutor(1)
    try:
        return _run(h, base, n, expect, ref, stream, helper)
    finally:
        helper.shutdown(wait=True)


def copies(base: np.ndarray, n: int, seed: int):
    """(permutation, relabelled copy) pairs a run queries, in order, fixed
    by the seed: copy = the base graph with vertex x renamed perm[x]."""
    rng = np.random.default_rng([seed, 0x1CC])
    while True:
        perm = rng.permutation(n)
        e = perm[base]
        yield perm, np.stack([e.min(axis=1), e.max(axis=1)], axis=1)


def _query(tcim_vertex_counts, edges, n, expect):
    import jax

    with jax.profiler.TraceAnnotation("bench.tcim_vertex_counts"):
        res = tcim_vertex_counts(edges, n=n)
    off = {k: res.stats.get(k) for k, v in expect.items()
           if res.stats.get(k) != v}
    return res, off


def _run(h, base, n, expect, ref, stream, helper) -> Outcome:
    from repro.core import tcim_vertex_counts

    nxt = helper.submit(next, stream)
    _, warm = nxt.result()
    nxt = helper.submit(next, stream)
    # Warm-up: the same buckets as every relabelled copy, so the window
    # compiles nothing.
    warm_res, warm_off = _query(tcim_vertex_counts, warm, n, expect)
    answers, kept = [], []
    t0 = h.open_window()
    while True:
        perm, edges = nxt.result()
        nxt = helper.submit(next, stream)
        res, off = _query(tcim_vertex_counts, edges, n, expect)
        answers.append((perm, res.triangles, res.vertex_triangles, res.lcc, off))
        if h.trace:
            kept.append(edges)
        if time.perf_counter() - t0 >= h.seconds:
            break
    h.close_window()
    nxt.cancel()
    want_t = np.asarray(ref.get(timeout=900), dtype=np.int64)
    want_lcc = lcc_ref.local_clustering(base, n, want_t)
    want_total = int(want_t.sum()) // 3
    wrong, tgap, lgap, failed = 0, 0, 0.0, 0
    for perm, total, t, lcc, off in answers:
        wt, wl = np.empty_like(want_t), np.empty_like(want_lcc)
        wt[perm], wl[perm] = want_t, want_lcc
        t = np.asarray(t, dtype=np.int64)
        lcc = np.asarray(lcc, dtype=np.float64)
        bad = int(np.count_nonzero((t != wt) | (lcc != wl)))
        wrong += bad
        tgap = max(tgap, int(np.abs(t - wt).max()), abs(int(total) - want_total))
        lgap = max(lgap, float(np.abs(lcc - wl).max()))
        failed += bool(bad or off or int(total) != want_total)
    off_path = sum(1 for a in answers if a[-1])
    pairs = []
    if h.trace:
        pool = h.pool(min(len(kept), 4))
        pairs = pool.starmap(graphs.slice_pairs,
                             [(e, n, int(h.config.get("slice_bits", 64)))
                              for e in kept])
    return Outcome(
        attempted=len(answers),
        failed=failed,
        checks=[
            Check("wrong_vertices", wrong, 0),
            Check("max_triangle_gap", tgap, 0),
            Check("max_lcc_gap", lgap, 0),
            Check("off_path_counts", off_path, 0),
        ],
        metrics={"count_s": h.window_s / len(answers)},
        graphs=len(answers),
        pairs=pairs,
        notes={
            "reference_triangles": want_total,
            "reference_vertices_with_triangles": int(np.count_nonzero(want_t)),
            "warmup": {"triangles": warm_res.triangles, "off_path": warm_off,
                       "vertex_pairs": warm_res.stats.get("vertex_pairs"),
                       "vertex_nonzero_pairs": warm_res.stats.get("vertex_nonzero_pairs")},
            "off_path": [a[-1] for a in answers if a[-1]][:3],
        },
    )
