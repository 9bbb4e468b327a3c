"""The benchmark's own per-vertex reference: T(v) and LDBC Graphalytics LCC.

NumPy only; imports nothing of the program, so it runs in the reference's
child process. T(v) is the number of triangles through vertex v: the
degree-ordered graph's triangles u < v < w are found by the same sorted
merge as ``bench.graphs.triangles``, and each adds one to its three
vertices. LCC(v) = T(v) / (d(v) (d(v) - 1) / 2), the float64 quotient of
those exact integers, and 0 where d(v) < 2.
"""
from __future__ import annotations

import numpy as np

from bench import graphs


def degree_relabel(edges: np.ndarray, n: int) -> np.ndarray:
    """``new_id[v]``: v's id in non-decreasing degree order, ties by id."""
    deg = np.bincount(edges.reshape(-1), minlength=n)
    new_id = np.empty(n, dtype=np.int64)
    new_id[np.argsort(deg, kind="stable")] = np.arange(n, dtype=np.int64)
    return new_id


def vertex_triangles(edges: np.ndarray, n: int, block: int = 1 << 18) -> np.ndarray:
    """T(v) for every vertex (int64 [n]), in the edge list's own ids."""
    t = np.zeros(n, dtype=np.int64)
    if len(edges) == 0:
        return t
    new_id = degree_relabel(edges, n)
    e = new_id[edges]
    oriented = np.stack([e.min(axis=1), e.max(axis=1)], axis=1)
    oriented = oriented[np.lexsort((oriented[:, 1], oriented[:, 0]))]
    indptr = graphs._indptr(oriented[:, 0], n)
    indices = oriented[:, 1]
    for start in range(0, len(oriented), block):
        u, v = oriented[start:start + block].T
        du = indptr[u + 1] - indptr[u]
        first = np.repeat(np.cumsum(du) - du, du)
        ks = indices[np.repeat(indptr[u], du) + np.arange(du.sum()) - first]
        edge = np.repeat(np.arange(len(u)), du)
        vv = v[edge]
        lo, hi = indptr[vv], indptr[vv + 1]
        pos = graphs._window_searchsorted(indices, lo, hi, ks)
        hit = (pos < hi) & (indices[np.minimum(pos, len(indices) - 1)] == ks)
        for side in (u[edge[hit]], vv[hit], ks[hit]):
            t += np.bincount(side, minlength=n)
    return t[new_id]


def local_clustering(edges: np.ndarray, n: int, t: np.ndarray) -> np.ndarray:
    """LCC (float64 [n]) from the counts ``t`` of the same edge list."""
    deg = np.bincount(edges.reshape(-1), minlength=n).astype(np.int64)
    wedges = deg * (deg - 1) // 2
    out = np.zeros(n, dtype=np.float64)
    np.divide(t, wedges, out=out, where=wedges > 0)
    return out


def rmat_vertex_triangles(n: int, m: int, seed: int) -> np.ndarray:
    """The reference counts T of ``bench.graphs.rmat(n, m, seed)``."""
    return vertex_triangles(graphs.rmat(n, m, seed), n)
