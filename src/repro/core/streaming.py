"""Streaming incremental triangle counting — TCIM over an edge stream.

The one-shot pipeline (core.tcim) makes TC(G) a function of resident slice
stores; this module makes it a *running* function of an edge stream. A
:class:`StreamingTCState` holds the current oriented edge set, the host
``SlicedBitmap`` mirror, and a device-resident executor whose stores are
edited in place batch after batch. Each ``apply_batch(added, removed)``
costs O(touched pairs), not O(all pairs):

    1. **Touched set.** Let ``Vr`` be the sources and ``Vc`` the
       destinations of the batch's oriented edges. The *touched edges* are
       the current edges with ``src in Vr`` or ``dst in Vc`` (enumerated by
       binary search over the sorted edge-key arrays, both orientations).
       For every untouched edge ``(i, j)``, row record-set ``R_i`` and
       column record-set ``C_j`` are unchanged by the update (new or edited
       records only ever belong to owners in ``Vr``/``Vc``), so its
       popcount term is identical before and after and cancels in the
       difference.
    2. **Before count.** Build the delta worklist (valid slice pairs) for
       the touched edges of the OLD edge set against the OLD stores and
       dispatch it — asynchronously, against the executor's resident
       device stores.
    3. **Update.** ``core.sbf.update_sbf`` applies the batch to the host
       mirror and emits word-level :class:`~repro.core.sbf.UpdateLanes`;
       the executor scatters them into its resident stores
       (``update_stores`` — a pure scatter producing NEW device arrays, so
       the in-flight before-count keeps its buffers). Only when the batch
       creates new ``(vertex, slice)`` records do positions shift and the
       stores re-adopt wholesale (``grew`` — rare at streaming batch
       sizes). Cleared slices persist as all-zero records, so removals
       never shift positions and never grow anything.
    4. **After count.** Delta worklist for the touched edges of the NEW
       edge set against the NEW stores, dispatched the same way.
    5. ``triangles += after - before`` — exact, signed, bit-identical to a
       from-scratch count on the final edge set (property-tested; see
       ``verify()``).

Steady-state batches add **zero** jit traces: delta worklists and update
lanes pad to pow2 buckets, the scatter and chunk steps are module-level
cached jits, and the stores keep their pow2 row buckets across in-place
edits (``Executor.trace_count`` / ``executor.scatter_update_trace_count``
regression-tested).

Orientation is **stable**: edges orient by raw vertex id (``src < dst``),
never by degree, so a batch can never relabel the graph. Triangle counts
are orientation-invariant, so parity against the (degree-reordered)
one-shot ``tcim_count`` still holds.

With a 2-axis ``mesh`` the state runs a resident
:class:`~repro.distributed.tc.Sharded2DExecutor` instead: per batch, the
delta worklist is re-planned against the executor's FIXED range bounds
(``core.plan.plan_execution`` with pinned bounds — see
``core.plan.replan_fixed``) and the update lanes are remapped to
block-local rows (``Sharded2DExecutor.update_stores``); growth rebuilds
the sharded executor.
"""
from __future__ import annotations

import dataclasses

import jax
import numpy as np

from contextlib import nullcontext

from repro.core import build as build_mod
from repro.core import sbf as sbf_mod
from repro.core.executor import Executor
from repro.core.plan import pow2_ceil
from repro.graphs.csr import build_graph
from repro.runtime.contracts import max_retrace
from repro.runtime.spans import span

__all__ = [
    "DeltaResult",
    "StreamingTCState",
    "tcim_count_delta",
    "STREAM_BACKENDS",
]

# Streaming executes through the work-list Executor modes only (the dense
# bitgemm/mxu backends have no incremental story — no resident stores).
STREAM_BACKENDS = ("pallas_total", "pallas_unfused", "pallas_items", "jnp")

_STREAM_MODE = {
    "pallas_total": "fused",
    "pallas_unfused": "gather_then_kernel",
    "pallas_items": "pallas_items",
    "jnp": "jnp",
}

_STREAM_BUILDS = ("auto", "host", "device")


@dataclasses.dataclass(frozen=True)
class DeltaResult:
    """One applied batch: the new running count and what it cost."""

    triangles: int  # running count AFTER this batch
    delta: int  # signed correction this batch contributed
    added: int
    removed: int
    touched_edges: int  # touched edges of the post-update edge set
    pairs_before: int  # delta-worklist pairs counted against the old stores
    pairs_after: int  # ... against the new stores
    grew: bool  # batch created new (vertex, slice) records
    timings_s: dict


def _as_edge_array(edges) -> np.ndarray:
    if edges is None:
        return np.zeros((0, 2), dtype=np.int64)
    e = np.asarray(edges, dtype=np.int64)
    if e.size == 0:
        return np.zeros((0, 2), dtype=np.int64)
    return e.reshape(-1, 2)


def _orient_batch(edges: np.ndarray, n: int, noun: str) -> np.ndarray:
    """Canonicalize a batch: orient each pair by raw id, validate range."""
    if len(edges) == 0:
        return edges
    lo = np.minimum(edges[:, 0], edges[:, 1])
    hi = np.maximum(edges[:, 0], edges[:, 1])
    if (lo == hi).any():
        raise ValueError(f"{noun} contains a self-loop")
    if len(lo) and (int(lo.min()) < 0 or int(hi.max()) >= n):
        raise ValueError(
            f"{noun} references a vertex outside [0, {n}); the vertex "
            "universe is fixed at construction — pass n= with headroom "
            "for streams that introduce new vertices"
        )
    return np.stack([lo, hi], axis=1)


def _ranges_concat(arr: np.ndarray, lo: np.ndarray, hi: np.ndarray) -> np.ndarray:
    """Concatenate ``arr[lo[i]:hi[i]]`` for all i (vectorized)."""
    cnt = (hi - lo).astype(np.int64)
    total = int(cnt.sum())
    if total == 0:
        return arr[:0]
    base = np.repeat(lo.astype(np.int64), cnt)
    offs = np.arange(total, dtype=np.int64) - np.repeat(
        np.concatenate([[0], np.cumsum(cnt)[:-1]]), cnt
    )
    return arr[base + offs]


def _member(sorted_keys: np.ndarray, q: np.ndarray) -> np.ndarray:
    """Boolean membership of q in a sorted unique key array."""
    idx = np.searchsorted(sorted_keys, q)
    found = np.zeros(len(q), dtype=bool)
    ok = idx < len(sorted_keys)
    found[ok] = sorted_keys[idx[ok]] == q[ok]
    return found


class StreamingTCState:
    """A long-lived graph whose triangle count follows an edge stream.

    ``edges`` seeds the graph (any undirected pair list; oriented and
    deduplicated here); ``n`` fixes the vertex universe — pass headroom if
    the stream will introduce vertices beyond the seed's max id. Then
    ``apply_batch(added, removed)`` maintains ``triangles`` at O(touched
    pairs) per batch (module docstring has the protocol).

    ``backend`` picks the executor mode (``STREAM_BACKENDS``); ``build``
    picks the delta-worklist front end — ``'host'`` (NumPy
    ``build_worklist_pairs``), ``'device'`` (``core.build
    .device_delta_worklist``: the jitted expansion/search/compaction step over
    just the touched edges, bit-identical), or ``'auto'`` (device on
    accelerator backends). A 2-axis ``mesh`` streams against a resident
    ``Sharded2DExecutor`` (host build only — the planner needs host
    arrays).

    Durability / degradation hooks (used by ``launch.tc_serve``):

    * ``snapshot_tree()`` / ``from_snapshot()`` — the stream as a flat
      pytree of host arrays plus a metadata dict, round-trippable through
      ``checkpoint.store`` without re-running the seed count.
    * ``spill()`` / ``ensure_resident()`` — drop the device-resident
      executor (the host ``_sbf`` mirror stays authoritative) and rebuild
      it later, count-preserving, no recount.
    * ``compact()`` — rebuild the SBF from the live edge set, dropping the
      all-zero records removals leave behind (``zero_record_ratio``).

    Not thread-safe; one stream mutates one executor's stores.
    """

    _SNAP_LEAVES = (
        "keys", "row_ptr", "row_slice_idx", "row_slice_data",
        "col_ptr", "col_slice_idx", "col_slice_data",
    )

    def __init__(
        self,
        edges,
        *,
        n: int | None = None,
        slice_bits: int = 64,
        backend: str = "pallas_total",
        chunk_pairs: int = 1 << 20,
        mesh=None,
        schedule: str = "packed",
        build: str = "auto",
    ):
        if backend not in _STREAM_MODE:
            raise ValueError(f"backend {backend!r} not in {STREAM_BACKENDS}")
        if build not in _STREAM_BUILDS:
            raise ValueError(f"build {build!r} not in {_STREAM_BUILDS}")
        if mesh is not None and build == "device":
            raise ValueError(
                "build='device' is single-device only — the sharded path "
                "plans delta worklists on the host"
            )
        e = _as_edge_array(edges)
        if n is None:
            n = int(e.max()) + 1 if len(e) else 0
        self.n = int(n)
        self.slice_bits = int(slice_bits)
        self.backend = backend
        self._build = build
        self._chunk_pairs = chunk_pairs
        self._mesh = mesh
        self._schedule = schedule
        self._use_device_build = build == "device" or (
            build == "auto" and mesh is None and jax.default_backend() != "cpu"
        )
        e = _orient_batch(e, self.n, "initial edges")
        keys = np.unique(e[:, 0] * np.int64(self.n) + e[:, 1]) if len(e) else (
            np.zeros(0, dtype=np.int64)
        )
        self._keys = keys  # src-major sorted unique edge keys
        self._keys_t = np.sort(self._transpose_keys(keys))  # dst-major
        g = build_graph(self.current_edges(), n=self.n, reorder=False)
        self._sbf = sbf_mod.build_sbf(g, slice_bits)
        self.executor = self._make_executor(self._sbf)
        # Seed count: the full worklist, once — batches never recount it.
        self.triangles = int(self.executor.count(sbf_mod.build_worklist(g, self._sbf)))
        self.batches = 0
        # Dispatch signatures (pow2 scatter-lane / chunk buckets) this stream
        # has already run — re-running one is "steady state" and must hit the
        # compiled traces (max_retrace(0) under TCIM_CONTRACTS=1).
        self._steady_sigs: set[tuple] = set()

    # ------------------------------------------------------------ internals

    def _transpose_keys(self, keys: np.ndarray) -> np.ndarray:
        if self.n == 0:
            return keys.copy()
        return (keys % self.n) * np.int64(self.n) + keys // self.n

    def _make_sharded(self, sb: sbf_mod.SlicedBitmap):
        from repro.distributed.tc import Sharded2DExecutor

        return Sharded2DExecutor(
            sb,
            self._mesh,
            chunk_pairs=self._chunk_pairs,
            schedule=self._schedule,
        )

    def _make_executor(self, sb: sbf_mod.SlicedBitmap):
        if self._mesh is not None:
            return self._make_sharded(sb)
        return Executor(
            sb, mode=_STREAM_MODE[self.backend], chunk_pairs=self._chunk_pairs
        )

    def _touched(
        self, keys: np.ndarray, keys_t: np.ndarray, vr: np.ndarray, vc: np.ndarray
    ) -> tuple[np.ndarray, np.ndarray]:
        """Edges of the keyed edge set with src in vr or dst in vc."""
        n = np.int64(self.n)
        by_src = _ranges_concat(
            keys, np.searchsorted(keys, vr * n), np.searchsorted(keys, (vr + 1) * n)
        )
        by_dst = _ranges_concat(
            keys_t,
            np.searchsorted(keys_t, vc * n),
            np.searchsorted(keys_t, (vc + 1) * n),
        )
        k = np.unique(np.concatenate([by_src, self._transpose_keys(by_dst)]))
        return k // n, k % n

    def _delta_worklist(self, src: np.ndarray, dst: np.ndarray, sb):
        """Valid slice pairs for a touched-edge subset (host or device)."""
        if self._use_device_build and len(src):
            try:
                return build_mod.device_delta_worklist(src, dst, sb)
            except build_mod.DeviceCapacityError:
                if self._build == "device":
                    raise
                # auto: int32 capacity exceeded — fall back to the host.
        pe, pr, pc = sbf_mod.build_worklist_pairs(src, dst, sb)
        return sbf_mod.Worklist(
            pair_edge=pe,
            pair_row_pos=pr,
            pair_col_pos=pc,
            m_edges=len(src),
            n_slices=sb.n_slices,
        )

    def _store_sig(self) -> tuple:
        """Shapes of the resident device stores the jitted step closes over.
        They change on SBF growth (adopt_stores), so every steady-state
        signature must include them: a pair-bucket repeat across a growth
        event hits a cold cache legitimately."""
        return tuple(
            tuple(store.shape) if store is not None else ()
            for store in (
                getattr(self.executor, "row_data", None),
                getattr(self.executor, "col_data", None),
            )
        )

    def _count_sig(self, wl) -> tuple:
        """Shape-bucket signature of a count dispatch: the full-chunk count
        plus the pow2 bucket of the tail chunk and the current store shapes,
        which together determine the set of compiled step shapes the
        executor will hit."""
        npairs = int(wl.num_pairs)
        nfull, tail = divmod(npairs, int(self._chunk_pairs))
        return (
            "count",
            type(wl).__name__,
            nfull,
            pow2_ceil(tail) if tail else 0,
            self._store_sig(),
        )

    def _steady_guard(self, sig: tuple):
        """``max_retrace(0)`` when this signature already ran on this stream.

        First occurrences (growth, a new bucket) legitimately compile and
        just register the signature; repeats are the steady state the
        streaming path promises adds zero retraces. Sharded streams skip the
        contract — stripe-schedule shapes depend on the per-shard pair
        layout, which the signature does not capture.
        """
        if self._mesh is not None:
            return nullcontext()
        if sig in self._steady_sigs:
            return max_retrace(0)
        self._steady_sigs.add(sig)
        return nullcontext()

    def _validate(self, ka: np.ndarray, kr: np.ndarray) -> None:
        for k, noun in ((ka, "added"), (kr, "removed")):
            if len(np.unique(k)) != len(k):
                raise ValueError(f"duplicate edge in {noun} batch")
        if len(ka) and len(kr) and np.intersect1d(ka, kr).size:
            raise ValueError("an edge appears in both added and removed")
        if len(ka) and _member(self._keys, ka).any():
            raise ValueError("adding an edge that is already present")
        if len(kr) and not _member(self._keys, kr).all():
            raise ValueError("removing an edge that is not present")

    # --------------------------------------------------------------- public

    @property
    def num_edges(self) -> int:
        return int(len(self._keys))

    def current_edges(self) -> np.ndarray:
        """The current oriented edge set, [m, 2] int64 sorted by (src, dst)."""
        if self.n == 0 or len(self._keys) == 0:
            return np.zeros((0, 2), dtype=np.int64)
        n = np.int64(self.n)
        return np.stack([self._keys // n, self._keys % n], axis=1)

    # ------------------------------------------------- spill / re-admission

    @property
    def resident(self) -> bool:
        """Whether a device-resident executor currently backs this stream."""
        return self.executor is not None

    def spill(self) -> None:
        """Drop the device-resident executor; host state stays authoritative.

        The host mirror (``_sbf``), the sorted edge keys, and the running
        count fully determine the stream, so a spilled stream gives its
        device store bytes back to the serving budget and a later
        ``ensure_resident()`` rebuilds the executor without a recount.
        Deltas close synchronously (``apply_batch`` resolves both futures
        before returning), so there is never an in-flight future to strand.
        """
        self.executor = None

    def ensure_resident(self) -> bool:
        """Rebuild the executor after ``spill()``; True when it had to."""
        if self.executor is not None:
            return False
        self.executor = self._make_executor(self._sbf)
        return True

    # ------------------------------------------------------------ compaction

    def zero_record_ratio(self) -> float:
        """Fraction of stored slice records whose data words are all zero.

        Removals clear slice words in place (positions never shift), so a
        remove-heavy stream accumulates dead records that pad every delta
        worklist's pair bucket; this ratio is the compaction trigger.
        """
        # tclint: sync-ok(self._sbf is the authoritative host mirror - numpy, no device readback)
        row = np.asarray(self._sbf.row_slice_data)
        # tclint: sync-ok(host mirror, numpy already on host)
        col = np.asarray(self._sbf.col_slice_data)
        total = len(row) + len(col)
        if total == 0:
            return 0.0
        zeros = int((~row.any(axis=1)).sum()) + int((~col.any(axis=1)).sum())
        return zeros / total

    def compact(self) -> dict:
        """Rebuild the SBF from the live edge set, dropping zero records.

        The running count is a function of the live edge set only, so the
        rebuild is count-preserving by construction (property-tested); the
        resident stores re-adopt the compacted layout wholesale. Steady
        signatures are cleared — store shapes changed, so the next batch of
        each bucket legitimately compiles once.
        Returns ``{"records_before", "records_after"}``.
        """
        sb = self._sbf
        before = int(len(sb.row_slice_idx)) + int(len(sb.col_slice_idx))
        g = build_graph(self.current_edges(), n=self.n, reorder=False)
        self._sbf = sbf_mod.build_sbf(g, self.slice_bits)
        after = int(len(self._sbf.row_slice_idx)) + int(
            len(self._sbf.col_slice_idx)
        )
        if self.executor is not None:
            if self._mesh is not None:
                self.executor = self._make_sharded(self._sbf)
            else:
                self.executor.adopt_stores(self._sbf)
        self._steady_sigs.clear()
        return {"records_before": before, "records_after": after}

    # ---------------------------------------------------------- durability

    def snapshot_tree(self) -> tuple[dict, dict]:
        """The stream as ``(pytree, extra)`` for ``checkpoint.store``.

        The tree is flat host arrays (edge keys + the six SBF arrays);
        ``extra`` carries the scalars. ``from_snapshot`` round-trips both
        without re-running the seed count — ``triangles`` is trusted, which
        is safe because snapshots are only taken from a live state whose
        count the streaming protocol maintains exactly.
        """
        sb = self._sbf
        tree = {
            "keys": self._keys,
            "row_ptr": sb.row_ptr,
            "row_slice_idx": sb.row_slice_idx,
            "row_slice_data": sb.row_slice_data,
            "col_ptr": sb.col_ptr,
            "col_slice_idx": sb.col_slice_idx,
            "col_slice_data": sb.col_slice_data,
        }
        extra = {
            "n": int(self.n),
            "slice_bits": int(self.slice_bits),
            "n_slices": int(sb.n_slices),
            "backend": self.backend,
            "triangles": int(self.triangles),
            "batches": int(self.batches),
        }
        return tree, extra

    @classmethod
    def from_snapshot(
        cls,
        tree: dict,
        extra: dict,
        *,
        backend: str | None = None,
        chunk_pairs: int = 1 << 20,
        mesh=None,
        schedule: str = "packed",
        build: str = "auto",
    ) -> "StreamingTCState":
        """Rebuild a stream from ``snapshot_tree()`` output — no recount."""
        self = cls.__new__(cls)
        backend = backend or extra.get("backend", "pallas_total")
        if backend not in _STREAM_MODE:
            raise ValueError(f"backend {backend!r} not in {STREAM_BACKENDS}")
        self.n = int(extra["n"])
        self.slice_bits = int(extra["slice_bits"])
        self.backend = backend
        self._build = build
        self._chunk_pairs = chunk_pairs
        self._mesh = mesh
        self._schedule = schedule
        self._use_device_build = build == "device" or (
            build == "auto" and mesh is None and jax.default_backend() != "cpu"
        )
        self._keys = np.asarray(tree["keys"], dtype=np.int64)
        self._keys_t = np.sort(self._transpose_keys(self._keys))
        self._sbf = sbf_mod.SlicedBitmap(
            slice_bits=self.slice_bits,
            n=self.n,
            n_slices=int(extra["n_slices"]),
            row_ptr=np.asarray(tree["row_ptr"]),
            row_slice_idx=np.asarray(tree["row_slice_idx"]),
            row_slice_data=np.asarray(tree["row_slice_data"]),
            col_ptr=np.asarray(tree["col_ptr"]),
            col_slice_idx=np.asarray(tree["col_slice_idx"]),
            col_slice_data=np.asarray(tree["col_slice_data"]),
        )
        self.executor = self._make_executor(self._sbf)
        self.triangles = int(extra["triangles"])
        self.batches = int(extra["batches"])
        self._steady_sigs = set()
        return self

    def apply_batch(self, added=None, removed=None) -> DeltaResult:
        """Apply one edge batch; returns the updated running count.

        ``added``/``removed`` are undirected pair lists (any orientation;
        canonicalized here). Set semantics are enforced: adds must be
        absent, removes present, no edge in both, no self-loops, vertices
        within the fixed universe. Empty batches are free no-ops.
        ``timings_s`` holds the host's seconds in each step (the dispatch,
        for a step that only enqueues device work) and ``total``; each step
        runs under a ``tc.delta.<key>`` span inside one ``tc.delta`` span.
        """
        timings: dict[str, float] = {}
        with span("tc.delta", timings, "total"):
            return self._apply_batch(added, removed, timings)

    def _apply_batch(self, added, removed, timings: dict) -> DeltaResult:
        n = np.int64(self.n)
        a = _orient_batch(_as_edge_array(added), self.n, "added")
        r = _orient_batch(_as_edge_array(removed), self.n, "removed")
        if len(a) == 0 and len(r) == 0:
            self.batches += 1
            return DeltaResult(
                triangles=self.triangles, delta=0, added=0, removed=0,
                touched_edges=0, pairs_before=0, pairs_after=0, grew=False,
                timings_s=timings,
            )
        ka = a[:, 0] * n + a[:, 1]
        kr = r[:, 0] * n + r[:, 1]
        self._validate(ka, kr)
        # Transparent re-admission: a spilled stream rebuilds its executor
        # from the host mirror on the first non-empty batch that touches it.
        self.ensure_resident()
        vr = np.unique(np.concatenate([a[:, 0], r[:, 0]]))
        vc = np.unique(np.concatenate([a[:, 1], r[:, 1]]))

        # Before count: touched edges of the OLD edge set vs the OLD stores.
        with span("tc.delta.schedule_before", timings, "schedule_before"):
            src_b, dst_b = self._touched(self._keys, self._keys_t, vr, vc)
            wl_before = self._delta_worklist(src_b, dst_b, self._sbf)
        with span("tc.delta.dispatch_before", timings, "dispatch_before"):
            with self._steady_guard(self._count_sig(wl_before)):
                fut_before = self.executor.count_async(wl_before)

        # Update the host mirror and scatter/adopt the resident stores.
        with span("tc.delta.update", timings, "update"):
            upd = sbf_mod.update_sbf(self._sbf, a, r)
        with span("tc.delta.scatter", timings, "scatter"):
            self._scatter(upd)

        # Merge the sorted edge-key arrays (both orientations).
        with span("tc.delta.merge", timings, "merge"):
            keys = np.concatenate([self._keys, ka])
            keys.sort(kind="stable")
            if len(kr):
                keys = np.delete(keys, np.searchsorted(keys, kr))
            keys_t = np.concatenate([self._keys_t, self._transpose_keys(ka)])
            keys_t.sort(kind="stable")
            if len(kr):
                keys_t = np.delete(
                    keys_t, np.searchsorted(keys_t, self._transpose_keys(kr))
                )
            self._keys, self._keys_t = keys, keys_t

        # After count: touched edges of the NEW edge set vs the NEW stores
        # (same Vr/Vc — untouched terms cancel exactly in the difference).
        with span("tc.delta.schedule_after", timings, "schedule_after"):
            src_a, dst_a = self._touched(self._keys, self._keys_t, vr, vc)
            wl_after = self._delta_worklist(src_a, dst_a, self._sbf)
        with span("tc.delta.dispatch_after", timings, "dispatch_after"):
            with self._steady_guard(self._count_sig(wl_after)):
                fut_after = self.executor.count_async(wl_after)

        with span("tc.delta.close", timings, "close"):
            delta = int(fut_after.result()) - int(fut_before.result())
        self.triangles += delta
        self.batches += 1
        return DeltaResult(
            triangles=self.triangles,
            delta=delta,
            added=int(len(a)),
            removed=int(len(r)),
            touched_edges=int(len(src_a)),
            pairs_before=int(wl_before.num_pairs),
            pairs_after=int(wl_after.num_pairs),
            grew=bool(upd.grew),
            timings_s=timings,
        )

    def _scatter(self, upd) -> None:
        """Scatter (or adopt, on growth) the updated stores into the
        resident executor. The scatter never donates, so an in-flight
        count keeps its buffers; growth re-adopts (or rebuilds the sharded
        executor)."""
        if self._mesh is not None:
            if upd.grew:
                self.executor = self._make_sharded(upd.sbf)
            else:
                self.executor.update_stores(upd.sbf, upd.row_lanes, upd.col_lanes)
        elif upd.grew:
            self.executor.adopt_stores(upd.sbf)
        else:
            sig = tuple(
                pow2_ceil(max(int(lanes.num_lanes), 1)) if lanes is not None else 0
                for lanes in (upd.row_lanes, upd.col_lanes)
            )
            with self._steady_guard(("scatter",) + sig + self._store_sig()):
                self.executor.update_stores(upd.row_lanes, upd.col_lanes)
        self._sbf = upd.sbf

    def verify(self) -> int:
        """From-scratch oracle check: raises on any running-count drift."""
        from repro.core.tcim import tcim_count  # deferred: tcim imports us

        expect = tcim_count(
            self.current_edges(), n=self.n, slice_bits=self.slice_bits,
            collect_stats=False,
        ).triangles
        if expect != self.triangles:
            raise AssertionError(
                f"running count {self.triangles} != from-scratch {expect} "
                f"after {self.batches} batches"
            )
        return self.triangles


def tcim_count_delta(
    graph_state: StreamingTCState, edges_added=None, edges_removed=None
) -> DeltaResult:
    """Apply one edge batch to a streaming state; returns the running count.

    Functional alias for :meth:`StreamingTCState.apply_batch` — the
    entry point named by the streaming API: build the state once, then
    ``tcim_count_delta(state, adds, removes)`` per batch.

    Contract (``TCIM_CONTRACTS=1``): steady-state batches — a scatter /
    chunk-bucket signature the stream has dispatched before — run under
    ``max_retrace(0)``: re-hitting a known bucket must not compile.
    """
    return graph_state.apply_batch(edges_added, edges_removed)
