"""The benchmark's own graph code: generator, relabelling, reference counter
and slice-pair count.

Copied from the program (``graphs/generators.py`` ``rmat``,
``graphs/csr.py`` ``degree_order``, ``graphs/exact.py``
``triangles_intersection``) so that no later change to the program can move
the traffic or the reference. NumPy only: this module never imports JAX or
the program, so it runs in the reference's child processes.
"""
from __future__ import annotations

import numpy as np

WORD_BITS = 32


def canonicalize(src: np.ndarray, dst: np.ndarray) -> np.ndarray:
    """Dedup, drop self loops, enforce src < dst; returns [m, 2] int64."""
    src = src.astype(np.int64)
    dst = dst.astype(np.int64)
    keep = src != dst
    src, dst = src[keep], dst[keep]
    key = np.unique(np.minimum(src, dst) << np.int64(32) | np.maximum(src, dst))
    return np.stack([key >> np.int64(32), key & np.int64(0xFFFFFFFF)], axis=1)


def rmat(n: int, m: int, seed: int, a: float = 0.57, b: float = 0.19,
         c: float = 0.19) -> np.ndarray:
    """R-MAT power-law graph (Chakrabarti et al.) folded into [0, n);
    the same draws as the program's ``graphs.generators.rmat``."""
    rng = np.random.default_rng(seed)
    levels = max(1, int(np.ceil(np.log2(max(n, 2)))))
    cum = np.cumsum([a, b, c, 1.0 - a - b - c])
    m_try = int(m * 1.4)
    src = np.zeros(m_try, dtype=np.int64)
    dst = np.zeros(m_try, dtype=np.int64)
    for _ in range(levels):
        quad = np.searchsorted(cum, rng.random(m_try))
        src = (src << 1) | (quad >> 1)
        dst = (dst << 1) | (quad & 1)
    edges = canonicalize(src % n, dst % n)
    if len(edges) > m:
        edges = edges[np.sort(rng.choice(len(edges), size=m, replace=False))]
    return edges


def relabel(edges: np.ndarray, n: int, rng: np.random.Generator) -> np.ndarray:
    """A copy of the graph under a random vertex permutation.

    The triangle count is unchanged, and every content digest of the edge
    list (and of whatever is built from it) changes. The copy stays
    canonical (src < dst, no duplicates) but is not sorted.
    """
    perm = rng.permutation(n)
    e = perm[edges]
    return np.stack([e.min(axis=1), e.max(axis=1)], axis=1)


def degree_order(edges: np.ndarray, n: int) -> np.ndarray:
    """Relabel by non-decreasing degree (stable, ties by id), orient
    src < dst and sort by (src, dst): the program's ``degree_order``."""
    deg = np.bincount(edges.ravel(), minlength=n)
    new_id = np.empty(n, dtype=np.int64)
    new_id[np.argsort(deg, kind="stable")] = np.arange(n, dtype=np.int64)
    e = new_id[edges]
    out = np.stack([e.min(axis=1), e.max(axis=1)], axis=1)
    return out[np.lexsort((out[:, 1], out[:, 0]))]


def _indptr(src: np.ndarray, n: int) -> np.ndarray:
    ptr = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(np.bincount(src, minlength=n), out=ptr[1:])
    return ptr


def _window_searchsorted(sorted_concat, lo, hi, keys):
    """Lower bound of keys[i] within sorted_concat[lo[i]:hi[i]]."""
    lo = lo.copy()
    hi = hi.copy()
    last = len(sorted_concat) - 1
    while True:
        active = lo < hi
        if not active.any():
            return lo
        mid = (lo + hi) >> 1
        right = active & (sorted_concat[np.minimum(mid, last)] < keys)
        lo = np.where(right, mid + 1, lo)
        hi = np.where(active & ~right, mid, hi)


def triangles(edges: np.ndarray, n: int, block: int = 1 << 18) -> int:
    """Exact triangle count: for every oriented edge (u, v) of the
    degree-ordered graph, |N+(u) & N+(v)| by a vectorized sorted merge."""
    oriented = degree_order(edges, n)
    if len(oriented) == 0:
        return 0
    indptr = _indptr(oriented[:, 0], n)
    indices = oriented[:, 1]
    total = 0
    for start in range(0, len(oriented), block):
        u, v = oriented[start:start + block].T
        du = indptr[u + 1] - indptr[u]
        first = np.repeat(np.cumsum(du) - du, du)
        ks = indices[np.repeat(indptr[u], du) + np.arange(du.sum()) - first]
        vv = np.repeat(v, du)
        lo, hi = indptr[vv], indptr[vv + 1]
        pos = _window_searchsorted(indices, lo, hi, ks)
        hit = (pos < hi) & (indices[np.minimum(pos, len(indices) - 1)] == ks)
        total += int(np.count_nonzero(hit))
    return total


def rmat_triangles(n: int, m: int, seed: int) -> int:
    """The reference count of ``rmat(n, m, seed)``."""
    return triangles(rmat(n, m, seed), n)


def slice_pairs(edges: np.ndarray, n: int, slice_bits: int = 64,
                block: int = 1 << 18) -> int:
    """Valid slice pairs P of the degree-ordered graph.

    The count that defines the execute stage's work whatever implements
    it: for every oriented edge (u, v), the slices k in which row u and
    column v both hold a set bit (paper section IV-B).
    """
    oriented = degree_order(edges, n)
    if len(oriented) == 0:
        return 0
    ns = np.int64((n + slice_bits - 1) // slice_bits)
    u_all, v_all = oriented[:, 0], oriented[:, 1]
    row_keys = np.unique(u_all * ns + v_all // slice_bits)
    col_keys = np.unique(v_all * ns + u_all // slice_bits)
    row_owner = row_keys // ns
    row_ptr = _indptr(row_owner, n)
    row_slice = row_keys % ns
    total = 0
    for start in range(0, len(oriented), block):
        u = u_all[start:start + block]
        v = v_all[start:start + block]
        cnt = row_ptr[u + 1] - row_ptr[u]
        first = np.repeat(np.cumsum(cnt) - cnt, cnt)
        pos = np.repeat(row_ptr[u], cnt) + np.arange(cnt.sum()) - first
        want = np.repeat(v, cnt) * ns + row_slice[pos]
        at = np.minimum(np.searchsorted(col_keys, want), len(col_keys) - 1)
        total += int(np.count_nonzero(col_keys[at] == want))
    return total
