"""Device-resident build front end: orient -> SBF -> worklist, jit-compiled.

PRs 1-4 made the execute stage fast; the remaining serial host stage was the
NumPy build front end — ``build_graph``'s orientation sorts, ``build_sbf``'s
``np.bitwise_or.at`` scatter, and ``build_worklist``'s expand-and-binary-
search. This module ports all three onto device as jitted JAX, bit-identical
to the NumPy reference:

  * **Orient** — ``graphs.csr.device_orient``: one explicit host->device
    transfer of the pow2-bucket-padded edge list; degree relabel + lexsort
    on device.
  * **Compress** — ``_sbf_step``: per side, a two-pass stable sort by
    (owner, slice) replaces the combined int64 key (int32-safe), run-start
    flags + a cumsum replace ``np.unique``/``searchsorted``, and a
    scatter-add of one-hot bit words replaces ``np.bitwise_or.at`` (each
    edge contributes a distinct bit, so add == OR exactly).
  * **Schedule** — ``_worklist_step``: the row-slice expansion maps each
    candidate lane to its edge with one scatter of edge ids at their
    candidate offsets and a running max (a segment id, no search), the
    column membership test a branchless binary search (identical
    lower-bound semantics to ``sbf._window_searchsorted``) run for the
    bit length of the longest column window, a bound computed on the
    device, and the hit compaction a cumsum scatter. Pairs come back
    compacted in the same order as the host build, padded to a pow2 bucket
    with the executor's ``-1`` no-op sentinel.

Shape bucketing mirrors the executor's store buckets: edges pad to
``pow2_ceil(m)``, slice stores to ``pow2_ceil(nvs)``, candidate/pair arrays
to their own pow2 buckets — so a second graph in the same buckets adds
**zero** new traces (``device_build_trace_counts`` exposes the jit caches
for regression tests).

Host involvement between the upload and the execute stage is exactly two
scalar-sized device->host readbacks (valid-slice counts + candidate total,
then the pair count) used to pick static output buckets — the bulk arrays
never leave the device, which is the point: ``SlicedBitmap`` carries the jax
stores straight into ``core.executor.Executor``, and only indices ever
travel again. ``device_build_async`` defers even those readbacks, so a fleet
can dispatch graph i+1's (sort-dominated) SBF build while graph i executes.
"""
from __future__ import annotations

import dataclasses
import functools

import numpy as np

from repro.core import sbf as sbf_mod
from repro.core.plan import pow2_ceil
from repro.runtime.contracts import max_transfers, no_host_sync
from repro.runtime.spans import span
from repro.graphs.csr import (
    DeviceGraph,
    Graph,
    device_graph_trace_counts,
    device_orient,
)

__all__ = [
    "DeviceCapacityError",
    "DeviceBuild",
    "DeviceBuildFuture",
    "DeviceWorklist",
    "device_build",
    "device_build_async",
    "device_build_graph",
    "device_build_sbf",
    "device_build_worklist",
    "device_delta_worklist",
    "device_build_trace_counts",
]

_INT32_LIMIT = 2**31 - 1


class DeviceCapacityError(ValueError):
    """The device build refused a graph (or delta batch) whose candidate
    total reaches int32 device indexing. The only device-build failure that
    ``build='auto'`` answers by building on the host instead; compile and
    runtime errors of the device path are never caught that way."""

# kind -> jitted fn, built lazily (mirrors graphs.csr._DEVICE_JITS).
_JITS: dict = {}


def _get_jits() -> dict:
    if _JITS:
        return _JITS
    import jax
    import jax.numpy as jnp

    def _side(first, second, m, n, slice_bits, n_slices, wps):
        """One SBF side: valid-slice CSR from (owner, bit-position) pairs.

        Matches ``sbf._build_side`` record for record: stable (owner, slice)
        order, per-record OR of bit words, CSR offsets over owners.
        """
        bucket = first.shape[0]
        valid = jnp.arange(bucket, dtype=jnp.int32) < m
        k = jnp.where(valid, second // slice_bits, n_slices)
        o1 = jnp.argsort(k, stable=True)
        f1, s1, k1 = first[o1], second[o1], k[o1]
        o2 = jnp.argsort(f1, stable=True)
        f2, s2, k2 = f1[o2], s1[o2], k1[o2]
        v2 = jnp.arange(bucket, dtype=jnp.int32) < m  # sentinels sort last
        prev_f = jnp.concatenate([jnp.full(1, -1, jnp.int32), f2[:-1]])
        prev_k = jnp.concatenate([jnp.full(1, -1, jnp.int32), k2[:-1]])
        newrec = v2 & ((f2 != prev_f) | (k2 != prev_k))
        rec = jnp.cumsum(newrec.astype(jnp.int32)) - 1
        rec = jnp.where(v2, rec, bucket)  # sentinel lanes scatter-drop
        nvs = jnp.sum(newrec.astype(jnp.int32))
        bit = s2 % slice_bits
        word = bit // 32
        # Every edge owns a distinct bit of its record's word, so the
        # scatter-add of one-hot words is exactly the bitwise-OR scatter.
        data = jnp.zeros((bucket, wps), jnp.uint32).at[rec, word].add(
            jnp.uint32(1) << (bit % 32).astype(jnp.uint32), mode="drop"
        )
        slice_idx = jnp.zeros(bucket, jnp.int32).at[rec].set(k2, mode="drop")
        counts = jnp.zeros(n, jnp.int32).at[f2].add(
            newrec.astype(jnp.int32), mode="drop"
        )
        ptr = jnp.concatenate([jnp.zeros(1, jnp.int32), jnp.cumsum(counts)])
        return ptr, slice_idx, data, nvs

    @functools.partial(jax.jit, static_argnums=(3, 4))
    def sbf_step(src, dst, m, n, slice_bits):
        """Both SBF sides + the worklist's candidate total, one dispatch."""
        n_slices = (n + slice_bits - 1) // slice_bits
        wps = slice_bits // 32
        row = _side(src, dst, m, n, slice_bits, n_slices, wps)
        col = _side(dst, src, m, n, slice_bits, n_slices, wps)
        return row + col + _cand(src, m, row[0])

    def _cand(src, m, row_ptr):
        """(int32 candidate total, float32 shadow sum bitcast to int32).

        The int32 sum is the exact value the expansion needs — but with x64
        off it silently wraps past 2**31, so the float32 shadow (monotone,
        small relative error) is what the host-side overflow guard trusts:
        any true total near or past the int32 limit shows up there. The
        shadow travels bitcast to int32 so one stacked readback carries
        every sizing scalar (``np.float32`` view on the host recovers it).
        """
        bucket = src.shape[0]
        n = row_ptr.shape[0] - 1
        valid = jnp.arange(bucket, dtype=jnp.int32) < m
        u = jnp.clip(src, 0, n - 1)
        cnt = jnp.where(valid, row_ptr[u + 1] - row_ptr[u], 0)
        shadow = jnp.sum(cnt.astype(jnp.float32))
        return jnp.sum(cnt), jax.lax.bitcast_convert_type(shadow, jnp.int32)

    @jax.jit
    def cand_total(src, m, row_ptr):
        return _cand(src, m, row_ptr)

    @functools.partial(jax.jit, static_argnums=(7,))
    def worklist_step(src, dst, m, row_ptr, row_idx, col_ptr, col_idx, cb):
        """Expand row slices per edge, test column membership, compact hits.

        The three phases run under the named scopes ``tc_expand``,
        ``tc_search`` and ``tc_compact``, which every operation's metadata
        carries into the profiler's trace. ``cb`` is the static candidate
        bucket. Expansion finds each lane's edge without a loop: edge ids
        scattered at their candidate offsets, then ``cummax``. The binary
        search replicates ``_window_searchsorted``'s lower-bound loop
        branchlessly. A step leaves at most floor(s/2) of an active window
        of s, so the bit length of the longest column window converges
        every lane; that count is traced, not static, so graphs whose
        longest windows differ share one compiled step. Returns the padded
        pairs and the stacked scalars (pair count, search steps).
        """
        bucket = src.shape[0]
        n = row_ptr.shape[0] - 1
        col_cap = col_idx.shape[0]
        with jax.named_scope("tc_expand"):
            valid = jnp.arange(bucket, dtype=jnp.int32) < m
            u = jnp.clip(src, 0, n - 1)
            cnt = jnp.where(valid, row_ptr[u + 1] - row_ptr[u], 0)
            cum = jnp.cumsum(cnt)
            start = cum - cnt
            total = cum[-1]
            lane = jnp.arange(cb, dtype=jnp.int32)
            # Lane l belongs to the last edge whose start is <= l: scatter
            # each edge id at its start (a zero-candidate edge shares its
            # start with the next edge, which max keeps; starts at cb drop)
            # and carry it forward with a running max.
            seed = jnp.zeros(cb, jnp.int32).at[start].max(
                jnp.arange(bucket, dtype=jnp.int32),
                indices_are_sorted=True, mode="drop",
            )
            e = jax.lax.cummax(seed, axis=0)
            lane_valid = lane < total
            row_pos = row_ptr[u[e]] + (lane - start[e])
            ks = row_idx[jnp.clip(row_pos, 0, row_idx.shape[0] - 1)]

        def body(_, lh):
            lo_w, hi_w = lh
            active = lo_w < hi_w
            mid = (lo_w + hi_w) >> 1
            midval = col_idx[jnp.minimum(mid, col_cap - 1)]
            go_right = active & (midval < ks)
            lo_w = jnp.where(go_right, mid + 1, lo_w)
            hi_w = jnp.where(active & ~go_right, mid, hi_w)
            return lo_w, hi_w

        with jax.named_scope("tc_search"):
            v = jnp.clip(dst[e], 0, n - 1)
            lo, hi = col_ptr[v], col_ptr[v + 1]
            longest = jnp.max(col_ptr[1:] - col_ptr[:-1], initial=0)
            steps = 32 - jax.lax.clz(longest)
            pos, _ = jax.lax.fori_loop(0, steps, body, (lo, hi))
            hit = lane_valid & (pos < hi) & (
                col_idx[jnp.minimum(pos, col_cap - 1)] == ks
            )
        with jax.named_scope("tc_compact"):
            out = jnp.cumsum(hit.astype(jnp.int32)) - 1
            tgt = jnp.where(hit, out, cb)  # misses scatter-drop
            pe = jnp.full(cb, -1, jnp.int32).at[tgt].set(e, mode="drop")
            pr = jnp.full(cb, -1, jnp.int32).at[tgt].set(row_pos, mode="drop")
            pc = jnp.full(cb, -1, jnp.int32).at[tgt].set(pos, mode="drop")
            return pe, pr, pc, jnp.stack([jnp.sum(hit.astype(jnp.int32)), steps])

    @functools.partial(jax.jit, static_argnums=(1,))
    def prefix(a, k):
        """Static prefix slice on device (eager ``a[:k]`` would stage its
        start index through an implicit host->device transfer)."""
        return jax.lax.slice_in_dim(a, 0, k)

    _JITS["sbf"] = sbf_step
    _JITS["cand_total"] = cand_total
    _JITS["worklist"] = worklist_step
    _JITS["prefix"] = prefix
    return _JITS


def device_build_trace_counts() -> dict:
    """Jit-cache sizes of every device-build stage (orient included) —
    regression tests assert a same-bucket rebuild adds zero to these."""
    out = dict(device_graph_trace_counts())
    for kind, fn in _JITS.items():
        try:
            out[kind] = int(fn._cache_size())
        except Exception:
            out[kind] = -1
    return out


@dataclasses.dataclass(frozen=True)
class DeviceWorklist:
    """Device-resident work list: pow2-padded pair indices, ``-1`` no-ops.

    The executor consumes the padded arrays directly (its fused step treats
    negative indices as exact no-ops), so the pairs never bounce through the
    host. ``num_pairs`` is the real (non-sentinel) pair count and
    ``search_steps`` the binary search's trip count (the longest column
    window's bit length) — both synced in the one readback that sizes the
    pair bucket, so reading them is free.
    """

    pair_edge: object  # jax int32 [PB]
    pair_row_pos: object  # jax int32 [PB]
    pair_col_pos: object  # jax int32 [PB]
    num_pairs: int
    num_candidates: int
    m_edges: int
    n_slices: int
    search_steps: int

    def compute_reduction(self) -> float:
        naive = self.m_edges * self.n_slices
        return 1.0 - (self.num_pairs / naive) if naive else 0.0

    def to_host(self) -> sbf_mod.Worklist:
        """Materialize as the exact host ``Worklist`` (sync)."""
        p = self.num_pairs
        return sbf_mod.Worklist(
            pair_edge=np.asarray(self.pair_edge)[:p].astype(np.int64),
            pair_row_pos=np.asarray(self.pair_row_pos)[:p].astype(np.int64),
            pair_col_pos=np.asarray(self.pair_col_pos)[:p].astype(np.int64),
            m_edges=self.m_edges,
            n_slices=self.n_slices,
        )


@dataclasses.dataclass(frozen=True)
class DeviceBuild:
    """A fully-built device pipeline input: graph + SBF + worklist."""

    graph: DeviceGraph
    sbf: sbf_mod.SlicedBitmap
    worklist: DeviceWorklist
    timings_s: dict

    def to_host(self) -> tuple[sbf_mod.SlicedBitmap, sbf_mod.Worklist]:
        """Materialize (sbf, worklist) on host — the sharded-path escape
        hatch (those executors re-pack stores per shard on the host)."""
        return self.sbf.to_host(), self.worklist.to_host()


def _finalize_sbf(
    dg: DeviceGraph, slice_bits: int, raw, row_nvs: int, col_nvs: int
) -> sbf_mod.SlicedBitmap:
    """Trim the raw full-bucket SBF pieces to pow2(nvs) store buckets.

    The trimmed rows beyond ``nvs`` are all-zero scatter targets, so the
    resulting stores match the host executor's zero-padded pow2 layout.
    """
    jits = _get_jits()
    rp, ri, rd = raw[0:3]
    cp, ci, cd = raw[4:7]
    sb_row = pow2_ceil(max(row_nvs, 1))
    sb_col = pow2_ceil(max(col_nvs, 1))
    n_slices = (dg.n + slice_bits - 1) // slice_bits
    return sbf_mod.SlicedBitmap(
        slice_bits=slice_bits,
        n=dg.n,
        n_slices=n_slices,
        row_ptr=rp,
        row_slice_idx=jits["prefix"](ri, sb_row),
        row_slice_data=jits["prefix"](rd, sb_row),
        col_ptr=cp,
        col_slice_idx=jits["prefix"](ci, sb_col),
        col_slice_data=jits["prefix"](cd, sb_col),
        row_valid=row_nvs,
        col_valid=col_nvs,
        content_key=f"device:{dg.content_key}:{slice_bits}",
    )


# The candidate total is summed in int32 on device (it wraps silently past
# 2**31), so the overflow guard reads the float32 shadow sum instead; the
# margin absorbs the float32 summation error near the limit.
_CAND_GUARD = float(_INT32_LIMIT - (1 << 16))


def _make_worklist(
    dg: DeviceGraph,
    sb: sbf_mod.SlicedBitmap,
    cand_total: int,
    cand_shadow: float,
) -> DeviceWorklist:
    """Dispatch the expansion/search/compaction; trim pairs to their bucket."""
    jits = _get_jits()
    if cand_shadow >= _CAND_GUARD:
        raise DeviceCapacityError(
            f"candidate total ~{cand_shadow:.3g} is at or past int32 device "
            "indexing; build this graph on the host (build='host')"
        )
    cb = pow2_ceil(max(cand_total, 1))
    pe, pr, pc, scalars = jits["worklist"](
        dg.src, dg.dst, dg.m_dev,
        sb.row_ptr, sb.row_slice_idx, sb.col_ptr, sb.col_slice_idx, cb,
    )
    with span("tc.schedule.pair_wait"):
        # one scalar-sized readback sizes the pair bucket
        num_pairs, search_steps = (int(x) for x in np.asarray(scalars))
    pb = pow2_ceil(max(num_pairs, 1))
    return DeviceWorklist(
        pair_edge=jits["prefix"](pe, pb),
        pair_row_pos=jits["prefix"](pr, pb),
        pair_col_pos=jits["prefix"](pc, pb),
        num_pairs=num_pairs,
        num_candidates=cand_total,
        m_edges=dg.m,
        n_slices=sb.n_slices,
        search_steps=search_steps,
    )


class DeviceBuildFuture:
    """An SBF build already dispatched; sizing syncs deferred to ``result``.

    Construction enqueues the (sort-dominated) orient + SBF device work and
    returns immediately, so a fleet can overlap graph i+1's build with graph
    i's execute — the async analogue of ``Executor.count_async``.
    ``result()`` performs the two scalar readbacks that size the static
    output buckets (valid-slice counts + candidate total, then the pair
    count), dispatches the worklist stage, and returns the ``DeviceBuild``.
    Idempotent.
    """

    def __init__(self, dg: DeviceGraph, slice_bits: int, raw, timings: dict):
        self._dg = dg
        self._slice_bits = slice_bits
        self._raw = raw
        self.timings_s = timings
        self._build: DeviceBuild | None = None

    def result(self) -> DeviceBuild:
        if self._build is None:
            import jax.numpy as jnp

            raw = self._raw
            with span("tc.schedule", self.timings_s, "schedule"):
                with span("tc.schedule.size_wait"):
                    # tclint: sync-ok(the build's one sizing readback, at future close)
                    sizes = np.asarray(jnp.stack([raw[3], raw[7], raw[8], raw[9]]))
                row_nvs, col_nvs, cand = (int(x) for x in sizes[:3])
                cand_shadow = float(sizes[3:].view(np.float32)[0])
                sb = _finalize_sbf(self._dg, self._slice_bits, raw, row_nvs, col_nvs)
                wl = _make_worklist(self._dg, sb, cand, cand_shadow)
            self._build = DeviceBuild(
                graph=self._dg, sbf=sb, worklist=wl, timings_s=self.timings_s
            )
            self._raw = None
        return self._build


def _dispatch_sbf(dg: DeviceGraph, slice_bits: int, timings: dict) -> DeviceBuildFuture:
    if slice_bits % 32 != 0:
        raise ValueError("slice_bits must be a multiple of 32")
    with span("tc.compress", timings, "compress"):
        raw = _get_jits()["sbf"](dg.src, dg.dst, dg.m_dev, dg.n, slice_bits)
    return DeviceBuildFuture(dg, slice_bits, raw, timings)


@max_transfers(1)
@no_host_sync()
def device_build_async(
    edges: np.ndarray,
    n: int | None = None,
    *,
    slice_bits: int = 64,
    reorder: bool = True,
) -> DeviceBuildFuture:
    """Dispatch the full device build (orient -> SBF) from a raw edge list.

    Contract (``TCIM_CONTRACTS=1``): exactly one explicit host->device
    transfer (``device_orient``'s edge upload) and no host syncs — the
    sizing readback happens in ``DeviceBuildFuture.result()``.
    """
    timings: dict = {}
    with span("tc.orient", timings, "orient"):
        dg = device_orient(edges, n, reorder=reorder)
    return _dispatch_sbf(dg, slice_bits, timings)


def device_build(
    edges: np.ndarray,
    n: int | None = None,
    *,
    slice_bits: int = 64,
    reorder: bool = True,
) -> DeviceBuild:
    """Blocking ``device_build_async`` (identical results)."""
    return device_build_async(edges, n, slice_bits=slice_bits, reorder=reorder).result()


@max_transfers(1)
@no_host_sync()
def device_build_graph_async(g: Graph, slice_bits: int = 64) -> DeviceBuildFuture:
    """Device build from a prebuilt (already oriented) host ``Graph``.

    Uploads ``g.edges`` once; the device re-sort of the already-sorted list
    is an identity, so results match ``device_build(g.edges, reorder=False)``
    and the host ``build_sbf``/``build_worklist`` bit for bit.
    """
    timings: dict = {}
    with span("tc.orient", timings, "orient"):
        dg = device_orient(g.edges, n=g.n, reorder=False)
    return _dispatch_sbf(dg, slice_bits, timings)


def device_build_graph(g: Graph, slice_bits: int = 64) -> DeviceBuild:
    """Blocking ``device_build_graph_async``."""
    return device_build_graph_async(g, slice_bits).result()


def device_build_sbf(dg: DeviceGraph, slice_bits: int = 64) -> sbf_mod.SlicedBitmap:
    """The granular SBF stage: jitted compression of one ``DeviceGraph``.

    Returns a device-resident ``SlicedBitmap`` (pow2-trimmed stores, valid
    counts synced). Prefer ``device_build*`` for the fused pipeline — this
    entry point syncs its sizing scalars immediately.
    """
    fut = _dispatch_sbf(dg, slice_bits, {})
    import jax.numpy as jnp

    raw = fut._raw
    # tclint: sync-ok(blocking build variant closes its sizing readback here)
    row_nvs, col_nvs = (int(x) for x in np.asarray(jnp.stack([raw[3], raw[7]])))
    return _finalize_sbf(dg, slice_bits, raw, row_nvs, col_nvs)


def device_build_worklist(
    dg: DeviceGraph, sb: sbf_mod.SlicedBitmap
) -> DeviceWorklist:
    """The granular worklist stage over a device SBF (bit-identical pairs)."""
    cand, shadow = _get_jits()["cand_total"](dg.src, dg.m_dev, sb.row_ptr)
    cand_shadow = float(np.asarray(shadow).reshape(1).view(np.float32)[0])
    return _make_worklist(dg, sb, int(cand), cand_shadow)


def _delta_index_arrays(sb: sbf_mod.SlicedBitmap):
    """Device int32 (row_ptr, row_idx, col_ptr, col_idx) for the delta step.

    Host-built SBFs (the streaming state's resident layout) upload their
    CSR index arrays pow2-row-bucketed, matching the executor's store
    buckets, so the delta worklist traces are keyed by the same pow2 shapes
    as everything else; device-built SBFs pass through as-is. The *stores*
    never travel — only the small index arrays do.
    """
    import jax
    import jax.numpy as jnp

    if sb.is_device:
        return sb.row_ptr, sb.row_slice_idx, sb.col_ptr, sb.col_slice_idx

    def idx(a):
        a = np.asarray(a, dtype=np.int32)
        bucket = pow2_ceil(max(len(a), 1))
        if bucket != len(a):
            a = np.concatenate([a, np.zeros(bucket - len(a), np.int32)])
        return jax.device_put(a)

    return (
        jax.device_put(jnp.asarray(np.asarray(sb.row_ptr, dtype=np.int32))),
        idx(sb.row_slice_idx),
        jax.device_put(jnp.asarray(np.asarray(sb.col_ptr, dtype=np.int32))),
        idx(sb.col_slice_idx),
    )


def device_delta_worklist(
    src: np.ndarray, dst: np.ndarray, sb: sbf_mod.SlicedBitmap
) -> DeviceWorklist:
    """Delta worklist: valid slice pairs for an arbitrary touched-edge subset.

    The streaming analogue of ``device_build_worklist``, reusing the same
    jitted ``worklist_step`` (scatter + running-max expansion, branchless
    binary search, cumsum compaction) over *just* the touched edges of a delta
    batch instead of the whole graph — pair positions come back in the
    SBF's global record coordinates, bit-identical to the host
    ``sbf.build_worklist_pairs`` on the same subset (parity-tested). Edges
    pad to a pow2 bucket and index arrays to pow2 row buckets, so repeated
    same-bucket delta batches add zero traces.
    """
    import jax

    src = np.asarray(src, dtype=np.int32)
    dst = np.asarray(dst, dtype=np.int32)
    m = len(src)
    bucket = pow2_ceil(max(m, 1))
    if bucket != m:
        pad = np.zeros(bucket - m, dtype=np.int32)
        src = np.concatenate([src, pad])
        dst = np.concatenate([dst, pad])
    src_d, dst_d = jax.device_put(src), jax.device_put(dst)
    row_ptr, row_idx, col_ptr, col_idx = _delta_index_arrays(sb)
    jits = _get_jits()
    cand, shadow = jits["cand_total"](src_d, m, row_ptr)
    cand_shadow = float(np.asarray(shadow).reshape(1).view(np.float32)[0])
    if cand_shadow >= _CAND_GUARD:
        raise DeviceCapacityError(
            f"delta candidate total ~{cand_shadow:.3g} is at or past int32 "
            "device indexing; split the batch or build on the host"
        )
    cb = pow2_ceil(max(int(cand), 1))
    pe, pr, pc, scalars = jits["worklist"](
        src_d, dst_d, m, row_ptr, row_idx, col_ptr, col_idx, cb
    )
    num_pairs, search_steps = (int(x) for x in np.asarray(scalars))
    pb = pow2_ceil(max(num_pairs, 1))
    return DeviceWorklist(
        pair_edge=jits["prefix"](pe, pb),
        pair_row_pos=jits["prefix"](pr, pb),
        pair_col_pos=jits["prefix"](pc, pb),
        num_pairs=num_pairs,
        num_candidates=int(cand),
        m_edges=m,
        n_slices=sb.n_slices,
        search_steps=search_steps,
    )
