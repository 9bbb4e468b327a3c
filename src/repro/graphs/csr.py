"""CSR graph container + orientations (host and device).

The TCIM algorithm (paper §III) operates on the *upper-triangular* adjacency
matrix: a triangle {a<b<c} is counted exactly once at edge (a,c) through
intermediate b. The paper's Fig. 2 example stores 5 non-zeros for 5 undirected
edges, i.e. the oriented matrix.

``degree_order`` additionally relabels vertices by non-decreasing degree before
orienting. This is the standard fill-reducing trick for oriented TC (it bounds
per-row work by arboricity) and, for TCIM, concentrates the valid slices — we
measure its effect on valid-slice density in benchmarks/table4_valid_pct.py.

``device_orient`` is the jit-compiled mirror of ``build_graph``: one explicit
host->device transfer of the (pow2-bucket-padded) edge list, then degree
relabelling, orientation and the (src, dst) lexsort all run as dispatched
device work producing a ``DeviceGraph`` whose arrays never bounce back to the
host. It is the first stage of the device build pipeline (``core.build``);
results are bit-identical to ``build_graph`` (asserted in tests).
"""
from __future__ import annotations

import dataclasses
import hashlib

import numpy as np

__all__ = [
    "Graph",
    "DeviceGraph",
    "build_graph",
    "degree_order",
    "degree_relabel",
    "device_orient",
    "device_graph_trace_counts",
    "upper_triangular_edges",
]

# Positions, vertex ids and edge counts all live in int32 on device (x64 is
# off); the sentinel vertex id ``n`` must also fit.
_DEVICE_MAX = 2**31 - 2


def _pow2_ceil(x: int) -> int:
    # Local copy of core.plan.pow2_ceil: core.plan imports (via core.sbf)
    # this module, so importing it here would be circular.
    return 1 << max(0, (x - 1).bit_length())


@dataclasses.dataclass(frozen=True)
class Graph:
    """Undirected graph in canonical edge-list + CSR form.

    edges:    [m, 2] int64, src < dst, unique
    indptr:   [n+1]  CSR over the *oriented* (upper-triangular) adjacency
    indices:  [m]    column indices (all > row index)
    n:        vertex count
    """

    edges: np.ndarray
    indptr: np.ndarray
    indices: np.ndarray
    n: int

    @property
    def m(self) -> int:
        return int(len(self.edges))

    def dense(self) -> np.ndarray:
        """Dense symmetric adjacency (bool). Only for small graphs/tests."""
        a = np.zeros((self.n, self.n), dtype=bool)
        a[self.edges[:, 0], self.edges[:, 1]] = True
        a[self.edges[:, 1], self.edges[:, 0]] = True
        return a

    def dense_upper(self) -> np.ndarray:
        """Dense upper-triangular (oriented) adjacency (bool)."""
        a = np.zeros((self.n, self.n), dtype=bool)
        a[self.edges[:, 0], self.edges[:, 1]] = True
        return a


def upper_triangular_edges(edges: np.ndarray) -> np.ndarray:
    """Canonical edge list already satisfies src < dst; sort by (src, dst)."""
    order = np.lexsort((edges[:, 1], edges[:, 0]))
    return edges[order]


def degree_relabel(edges: np.ndarray, n: int) -> np.ndarray:
    """``new_id[v]``: vertex v's id in non-decreasing (undirected) degree
    order, ties by id. ``x[new_id]`` maps a per-vertex array of the
    relabelled graph back to the original ids."""
    deg = np.bincount(np.asarray(edges).reshape(-1), minlength=n)
    # Stable argsort => deterministic relabelling.
    perm = np.argsort(deg, kind="stable")  # old ids in degree order
    new_id = np.empty(n, dtype=np.int64)
    new_id[perm] = np.arange(n, dtype=np.int64)
    return new_id


def degree_order(edges: np.ndarray, n: int) -> np.ndarray:
    """Relabel vertices by non-decreasing (undirected) degree.

    Returns the relabelled canonical edge list (src < dst under new ids).
    """
    e = degree_relabel(edges, n)[edges]
    lo = np.minimum(e[:, 0], e[:, 1])
    hi = np.maximum(e[:, 0], e[:, 1])
    out = np.stack([lo, hi], axis=1)
    order = np.lexsort((out[:, 1], out[:, 0]))
    return out[order]


@dataclasses.dataclass(frozen=True)
class DeviceGraph:
    """Oriented CSR resident on device — the device build's edge container.

    ``src``/``dst`` are the oriented (src < dst), (src, dst)-lexsorted edge
    endpoints, zero-copy on device, padded to the pow2 ``bucket`` with the
    sentinel vertex id ``n`` (sentinels sort last, so the first ``m`` lanes
    are exactly the real edges). ``indptr`` is the oriented CSR offsets.
    ``m_dev`` is the real edge count as a device scalar so downstream jitted
    stages never need an implicit host->device scalar transfer; ``m`` is the
    same value on the host. ``content_key`` digests the *input* edge list, so
    executor pools can key device-built stores without reading them back.
    ``new_id`` is the degree relabel (``degree_relabel`` on device, int32
    [n]; ``None`` without one): per-vertex results of the relabelled graph
    map back to the caller's ids as ``x[new_id]``.
    """

    src: object  # jax int32 [bucket]
    dst: object  # jax int32 [bucket]
    indptr: object  # jax int32 [n+1]
    m_dev: object  # jax int32 scalar
    n: int
    m: int
    bucket: int
    content_key: str
    new_id: object = None  # jax int32 [n], or None when not relabelled

    def to_host(self) -> Graph:
        """Materialize the oriented CSR back on the host (sync)."""
        src = np.asarray(self.src)[: self.m].astype(np.int64)
        dst = np.asarray(self.dst)[: self.m].astype(np.int64)
        edges = np.stack([src, dst], axis=1)
        return Graph(
            edges=edges,
            indptr=np.asarray(self.indptr).astype(np.int64),
            indices=edges[:, 1].copy(),
            n=self.n,
        )


# kind -> jitted fn; built lazily so importing this module never pulls jax.
_DEVICE_JITS: dict = {}


def _orient_step():
    fn = _DEVICE_JITS.get("orient")
    if fn is None:
        import functools

        import jax
        import jax.numpy as jnp

        @functools.partial(jax.jit, static_argnums=(2, 3))
        def orient(edges, m, n, reorder):
            """Degree-relabel (optional), orient src<dst, lexsort (src, dst).

            Mirrors ``degree_order`` + ``upper_triangular_edges`` exactly:
            the relabel uses the same stable argsort of undirected degree,
            and the (src, dst) lexsort is two stable passes (dst then src).
            Sentinel lanes carry vertex id ``n`` (> every real id), so they
            sort to the tail and every downstream stage masks by ``m``.
            The relabel ``new_id`` comes back too (``None`` without one).
            """
            bucket = edges.shape[0]
            valid = jnp.arange(bucket, dtype=jnp.int32) < m
            src, dst = edges[:, 0], edges[:, 1]
            if reorder:
                one = valid.astype(jnp.int32)
                deg = (
                    jnp.zeros(n, jnp.int32)
                    .at[src].add(one, mode="drop")
                    .at[dst].add(one, mode="drop")
                )
                perm = jnp.argsort(deg, stable=True)
                new_id = jnp.zeros(n, jnp.int32).at[perm].set(
                    jnp.arange(n, dtype=jnp.int32)
                )
                s = jnp.where(valid, new_id[jnp.clip(src, 0, n - 1)], n)
                d = jnp.where(valid, new_id[jnp.clip(dst, 0, n - 1)], n)
                src, dst = jnp.minimum(s, d), jnp.maximum(s, d)
            else:
                new_id = None
            o1 = jnp.argsort(dst, stable=True)
            s1, d1 = src[o1], dst[o1]
            o2 = jnp.argsort(s1, stable=True)
            src_s, dst_s = s1[o2], d1[o2]
            counts = jnp.zeros(n, jnp.int32).at[src_s].add(
                valid.astype(jnp.int32), mode="drop"
            )
            indptr = jnp.concatenate(
                [jnp.zeros(1, jnp.int32), jnp.cumsum(counts)]
            )
            return src_s, dst_s, indptr, new_id

        fn = _DEVICE_JITS["orient"] = orient
    return fn


def device_graph_trace_counts() -> dict:
    """Jit-cache sizes of the device orient stage (retrace regressions)."""
    out = {}
    for kind, fn in _DEVICE_JITS.items():
        try:
            out[kind] = int(fn._cache_size())
        except Exception:
            out[kind] = -1
    return out


def device_orient(
    edges: np.ndarray, n: int | None = None, *, reorder: bool = True
) -> DeviceGraph:
    """``build_graph`` on device: one explicit upload, zero host bounces.

    Pads the canonical undirected edge list to its pow2 bucket (so repeated
    graph sizes reuse the orient trace), performs the single host->device
    transfer, and dispatches the jitted relabel+orient+sort. The returned
    ``DeviceGraph`` is bit-identical to ``build_graph(edges, n, reorder)``
    (``to_host()`` for the comparison). Raises on empty graphs — there is
    nothing to build; callers route those through the trivial host path.
    """
    import jax

    from repro.runtime.spans import span

    edges = np.asarray(edges)
    m = int(len(edges))
    if m == 0:
        raise ValueError("device_orient needs a non-empty edge list")
    if n is None:
        n = int(edges.max()) + 1
    n = int(n)
    if n < 1 or n > _DEVICE_MAX or m > _DEVICE_MAX:
        raise ValueError(
            f"device build needs 1 <= n <= {_DEVICE_MAX} and m <= "
            f"{_DEVICE_MAX} (int32 device indices), got n={n} m={m}"
        )
    with span("tc.orient.digest"):
        h = hashlib.blake2b(digest_size=16)
        h.update(repr((n, m, bool(reorder), "orient-v1")).encode())
        h.update(np.ascontiguousarray(edges).tobytes())
    bucket = _pow2_ceil(m)
    with span("tc.orient.upload"):
        padded = np.full((bucket, 2), n, dtype=np.int32)
        padded[:m] = edges
        ed, m_dev = jax.device_put((padded, np.int32(m)))
    src, dst, indptr, new_id = _orient_step()(ed, m_dev, n, bool(reorder))
    return DeviceGraph(
        src=src,
        dst=dst,
        indptr=indptr,
        m_dev=m_dev,
        n=n,
        m=m,
        bucket=bucket,
        content_key=h.hexdigest(),
        new_id=new_id,
    )


def build_graph(edges: np.ndarray, n: int | None = None, reorder: bool = False) -> Graph:
    """Build the oriented CSR Graph from a canonical undirected edge list."""
    if len(edges) == 0:
        n = int(n or 0)
        return Graph(
            edges=np.zeros((0, 2), dtype=np.int64),
            indptr=np.zeros(n + 1, dtype=np.int64),
            indices=np.zeros(0, dtype=np.int64),
            n=n,
        )
    if n is None:
        n = int(edges.max()) + 1
    if reorder:
        edges = degree_order(edges, n)
    edges = upper_triangular_edges(edges)
    counts = np.bincount(edges[:, 0], minlength=n).astype(np.int64)
    indptr = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(counts, out=indptr[1:])
    return Graph(edges=edges, indptr=indptr, indices=edges[:, 1].copy(), n=n)
