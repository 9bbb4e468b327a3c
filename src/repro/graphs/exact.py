"""Exact triangle-counting references (oracles + the paper's baselines).

``triangles_bruteforce``   — O(n^3) dense; test oracle for tiny graphs.
``triangles_dense_trace``  — trace(A^3)/6, the paper's matmul-based family.
``triangles_intersection`` — per-edge sorted-adjacency intersection; this is
                             the paper's CPU baseline algorithm (run on
                             GraphX/E5430 in Table V). Vectorized merge-based
                             implementation so it is usable on millions of
                             edges from a single CPU core.
``vertex_triangles``       — the same merge, every triangle attributed to
                             its three vertices: T(v) per vertex.
``local_clustering``       — LDBC Graphalytics LCC from those counts.
"""
from __future__ import annotations

import numpy as np

from repro.graphs.csr import Graph

__all__ = [
    "triangles_bruteforce",
    "triangles_dense_trace",
    "triangles_intersection",
    "vertex_triangles",
    "local_clustering",
]


def triangles_bruteforce(g: Graph) -> int:
    """Enumerate all vertex triples on the dense matrix. Tiny graphs only."""
    a = g.dense()
    n = g.n
    count = 0
    for i in range(n):
        for j in range(i + 1, n):
            if not a[i, j]:
                continue
            count += int(np.sum(a[i, j + 1 :] & a[j, j + 1 :]))
    return count


def triangles_dense_trace(g: Graph) -> int:
    """trace(A^3) / 6 on the dense symmetric adjacency (float64 matmul)."""
    a = g.dense().astype(np.float64)
    a3 = a @ a @ a
    return int(round(np.trace(a3) / 6.0))


def triangles_intersection(g: Graph) -> int:
    """Oriented merge-based intersection count (exact, vectorized).

    For every oriented edge (u, v), count |N+(u) ∩ N+(v)| where N+ is the
    oriented (higher-id) adjacency. Implemented as a galloping-free sorted
    merge using searchsorted over the concatenated candidate lists.
    """
    return sum(int(len(u)) for u, _, _ in _triangle_blocks(g))


def vertex_triangles(g: Graph) -> np.ndarray:
    """T(v), the triangles through each vertex v (int64 [n], g's ids).

    Every triangle u < v < w found by the merge adds one to each of its
    three vertices, so ``T.sum() == 3 * triangles_intersection(g)``.
    """
    t = np.zeros(g.n, dtype=np.int64)
    for tri in _triangle_blocks(g):
        for side in tri:
            t += np.bincount(side, minlength=g.n)
    return t


def local_clustering(g: Graph) -> np.ndarray:
    """LDBC Graphalytics local clustering coefficient (float64 [n]).

    LCC(v) = T(v) / (d(v) (d(v) - 1) / 2), the float64 quotient of the two
    exact integers, and 0 where d(v) < 2.
    """
    deg = np.bincount(g.edges.reshape(-1), minlength=g.n).astype(np.int64)
    wedges = deg * (deg - 1) // 2
    out = np.zeros(g.n, dtype=np.float64)
    np.divide(vertex_triangles(g), wedges, out=out, where=wedges > 0)
    return out


def _triangle_blocks(g: Graph):
    """Yield (u, v, w) int64 arrays: the triangles u < v < w of the oriented
    graph, found edge block by edge block (N+(u) ∩ N+(v) per edge)."""
    indptr, indices = g.indptr, g.indices
    # Process edges in blocks to bound the temporary candidate arrays.
    m = len(g.edges)
    block = 1 << 18
    for start in range(0, m, block):
        e = g.edges[start : start + block]
        u, v = e[:, 0], e[:, 1]
        du = indptr[u + 1] - indptr[u]
        # Expand u's oriented neighbours for each edge: candidates k in N+(u).
        off = np.repeat(indptr[u], du)
        local = np.arange(du.sum(), dtype=np.int64) - np.repeat(
            np.concatenate([[0], np.cumsum(du)[:-1]]), du
        )
        ks = indices[off + local]
        edge_of = np.repeat(np.arange(len(e), dtype=np.int64), du)
        vv = v[edge_of]
        # Membership test: is k in N+(v)? indices per row are sorted, so run a
        # vectorized binary search within each row's [lo, hi) window.
        lo = indptr[vv]
        hi = indptr[vv + 1]
        pos = _window_searchsorted(indices, lo, hi, ks)
        hit = (pos < hi) & (indices[np.minimum(pos, len(indices) - 1)] == ks)
        hit &= pos < len(indices)
        edge = edge_of[hit]
        yield u[edge], v[edge], ks[hit]


def _window_searchsorted(
    sorted_concat: np.ndarray, lo: np.ndarray, hi: np.ndarray, keys: np.ndarray
) -> np.ndarray:
    """Vectorized searchsorted of keys[i] within sorted_concat[lo[i]:hi[i]].

    Binary search unrolled over the maximum window width (log2 of max degree).
    """
    lo = lo.copy()
    hi_w = hi.copy()
    # Classic vectorized binary search on [lo, hi) windows.
    while True:
        active = lo < hi_w
        if not active.any():
            break
        mid = (lo + hi_w) // 2
        midval = sorted_concat[np.minimum(mid, len(sorted_concat) - 1)]
        go_right = active & (midval < keys)
        go_left = active & ~go_right
        lo = np.where(go_right, mid + 1, lo)
        hi_w = np.where(go_left, mid, hi_w)
    return lo
