"""Executor — the schedulable execute-stage unit of the TCIM engine.

Replaces the old ``_execute_worklist`` loop, which had three hot-path sins:

  1. every chunk materialized gathered ``[P, W]`` operands in HBM (two HBM
     crossings per gathered word),
  2. every chunk blocked on a host ``int()`` sync before the next could be
     dispatched (no overlap, one round-trip per chunk),
  3. the ragged last chunk had a fresh shape, forcing an XLA retrace per
     distinct work-list size.

The Executor fixes all three:

  * **Fused execute.** Chunks run through ``ops.popcount_and_gather_total``
    (kernels/tc_gather_popcount.py): the slice stores are uploaded once and
    stay device-resident; only index arrays travel per chunk, and the gather
    happens inside the fused computation.
  * **Power-of-two chunk buckets.** Chunks are always a power-of-two number
    of pairs (ragged tails padded with the ``-1`` no-op sentinel), so an
    executor traces at most ``log2(chunk_pairs)`` distinct shapes over its
    lifetime — in the common case exactly two (full chunk + one tail
    bucket), and re-counts are pure cache hits. ``trace_count`` exposes the
    jit cache size for regression tests.
  * **Power-of-two store buckets.** The device-resident slice stores are
    zero-row-padded to the next power of two (zero slices are exact no-ops:
    nothing indexes them, and ``popcount(0 & x) == 0``), so the jitted chunk
    step's trace is keyed by the store's *bucket*, not its exact valid-slice
    count — two different graphs in the same bucket share every trace. Costs
    at most 2x transient store memory; ``pad_stores_pow2=False`` opts out
    for memory-bound single-graph deployments.
  * **Device-resident accumulation.** Each chunk adds into an int32 device
    accumulator carried across chunks; the only host transfer is the final
    scalar read. When the worst-case count ``num_pairs * slice_bits`` could
    overflow int32, the executor instead keeps the per-chunk totals on
    device and does one stacked transfer at the end, summing exactly in
    Python ints — still a single sync.
  * **Donated buffers.** On accelerator backends the per-chunk index buffers
    and the carried accumulator are donated to XLA (dead after each step);
    CPU does not support donation, so it is skipped there to avoid warnings.
  * **Async double-buffering.** By default the executor stages chunk i+1's
    index arrays (``jax.device_put``) one chunk ahead of dispatch, so at the
    moment chunk i's fused step is enqueued the next chunk's host->device
    staging has already been issued and its transfer can proceed while the
    kernel runs. On backends where dispatch is fully asynchronous the serial
    path converges to the same pipeline (nothing in either loop blocks —
    the one host sync stays at the end), so the flag mostly matters where
    ``device_put`` staging costs host time; ``double_buffer=False`` keeps
    the upload-on-demand path for comparison (benchmarks) and as the
    semantics reference (tests assert bit-identical counts).
  * **Async close.** ``count_async`` / ``execute_indices_async`` return a
    ``CountFuture`` with every chunk step already dispatched but the final
    host readback deferred to ``result()`` — fleet callers overlap graph
    i's close with graph i+1's stripe assembly and uploads. ``count`` is
    ``count_async(...).result()``, bit-identical.

``ExecutorPool`` sits above: a fleet serving many graphs gets one pooled
Executor per graph, grouped by the trace key ``(words_per_slice, chunk
bucket, mode)``, so counting a second graph with an equal key adds zero new
traces (the jitted chunk step is shared) and re-counting a recently-seen
graph reuses its device-resident stores outright.

Execution modes (the engine maps user-facing backends onto these):

    'fused'               gather inside the kernel (default; TCIM semantics)
    'gather_then_kernel'  legacy XLA-gather + total_pallas (the unfused
                          baseline benchmarks compare against)
    'pallas_items'        XLA gather + per-pair items kernel (debuggable)
    'jnp'                 gather + lax.population_count oracle

Future sharding/batching work should schedule Executors, not raw kernels:
an Executor is one device's worth of execute-stage state (stores + trace
cache + accumulator), so multi-store sharding, cross-graph batching and
async double-buffering all compose at this interface.
"""
from __future__ import annotations

import collections
import functools
import hashlib
import weakref

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import sbf as sbf_mod
from repro.core.plan import clamp_chunk_pairs, plan_fusion, pow2_ceil as _pow2_ceil
from repro.kernels import ops, ref
from repro.kernels.common import on_cpu, swar_popcount_u32
from repro.kernels.tc_gather_popcount import gather_and_words_reference
from repro.runtime.contracts import max_transfers, no_host_sync

__all__ = [
    "CountFuture",
    "MultiCountFuture",
    "VertexCountFuture",
    "VERTEX_EXECUTE_IMPL",
    "VERTEX_IMPL",
    "Executor",
    "ExecutorPool",
    "MultiGraphExecutor",
    "EXECUTOR_MODES",
    "staged_uploads",
    "apply_store_lanes",
    "scatter_update_trace_count",
]

EXECUTOR_MODES = ("fused", "gather_then_kernel", "pallas_items", "jnp")

# What runs a per-vertex count (``Executor.vertex_counts_async``): each
# pair's AND words come from the jnp gather mirror, and the attribution to
# vertices is XLA scatter-adds. The one place the vertex path names them.
VERTEX_EXECUTE_IMPL = "jnp_mirror"
VERTEX_IMPL = "xla_scatter"

_INT32_MAX = 2**31 - 1


class CountFuture:
    """A dispatched count whose host readback is deferred.

    The ``count_async`` family returns one of these with every device step
    already enqueued; ``result()`` performs the final host sync (summing the
    per-step device scalars exactly, in Python ints) and caches it. Fleet
    callers overlap the close with the next graph's work — dispatch graph
    i+1's stripe assembly and index uploads while graph i's readback is
    still in flight:

        futures = [pool.count_async(sb, wl) for sb, wl in jobs]
        counts = [f.result() for f in futures]

    ``result()`` is idempotent, and ``count(...) ==
    count_async(...).result()`` bit-identically on every path.

    A step whose readback fails (device loss, injected fault) surfaces as
    ``CountInterrupted`` carrying the failing step's index and the exact
    partial total of the steps before it — already-dispatched work is never
    silently dropped, and the resilient drivers resume from that prefix.
    """

    __slots__ = ("_totals", "_value", "__weakref__")

    def __init__(self, totals):
        self._totals = list(totals)
        self._value: int | None = None

    @property
    def resolved(self) -> bool:
        """True once no device buffers are still referenced — either
        ``result()`` ran or the dispatch held nothing (empty worklist).
        Pools use this to tell in-flight work from evictable executors."""
        return not self._totals

    def result(self) -> int:
        if self._totals is not None:
            totals = self._totals
            try:
                if len(totals) > 1:
                    # One stacked device->host transfer, not one per step.
                    # tclint: sync-ok(the one host sync per count, at CountFuture close)
                    totals = np.asarray(jnp.stack(totals))
                self._value = sum(int(t) for t in totals)  # exact: host ints
            except Exception as e:
                raise self._interrupted(e) from e
            self._totals = None
        return self._value

    def _interrupted(self, err: Exception) -> "CountInterrupted":
        """Recover the committed prefix: read the per-step scalars one by
        one until the poisoned step, so the caller gets the exact partial
        total plus the index of the step that died."""
        from repro.runtime.fault import CountInterrupted

        partial = 0
        failed = 0
        for i, t in enumerate(self._totals):
            try:
                partial += int(t)
            except Exception:
                failed = i
                break
        else:  # the stacked transfer itself failed, but every step reads
            failed = len(self._totals)
        return CountInterrupted(
            f"count failed at step {failed} of {len(self._totals)}: {err}",
            failed_step=failed,
            committed_step=failed,
            committed_total=partial,
        )


def staged_uploads(chunks, put, *, double_buffer: bool = True):
    """Stage device uploads one chunk ahead of the consumer.

    ``chunks`` yields host-side work units; ``put`` turns one into its
    device-resident form (e.g. ``jax.device_put``, possibly with an explicit
    sharding). With ``double_buffer`` the i+1-th ``put`` is issued before
    chunk i is yielded, so its host->device transfer proceeds while the
    consumer's dispatch of chunk i runs; the serial path stages on demand.
    Both yield the same sequence — shared by the replicated Executor and the
    sharded executors in ``distributed.tc``.
    """
    if not double_buffer:
        for chunk in chunks:
            yield put(chunk)
        return
    ahead = None
    for chunk in chunks:
        cur = put(chunk)
        if ahead is not None:
            yield ahead  # consumer dispatches i while i+1 uploads
        ahead = cur
    if ahead is not None:
        yield ahead


def _pad_rows_pow2(a: np.ndarray) -> np.ndarray:
    """Zero-pad a store's rows to the next power of two (trace bucketing)."""
    rows = a.shape[0]
    bucket = _pow2_ceil(max(rows, 1))
    if bucket == rows:
        return a
    return np.concatenate(
        [a, np.zeros((bucket - rows,) + a.shape[1:], dtype=a.dtype)]
    )


def _resident_pow2(a, *, pad: bool = True):
    """An int32 index array on the device: device arrays as they are, host
    arrays uploaded once, padded with zeros to their pow2 bucket (so traces
    are keyed by buckets) unless ``pad`` is off."""
    if isinstance(a, jax.Array):
        return a
    a = np.asarray(a, dtype=np.int32)
    if pad:
        a = np.concatenate([a, np.zeros(_pow2_ceil(max(len(a), 1)) - len(a), np.int32)])
    return jax.device_put(a)


@functools.partial(jax.jit, static_argnums=(2, 3))
def _resident_window(a, start, size: int, bucket: int):
    """Window of a device-resident index array, padded to its pow2 bucket
    with the ``-1`` no-op sentinel. The start offset is a *traced* operand
    (``dynamic_slice``), so every chunk of a multi-chunk worklist shares
    one compiled program per (shape, size) instead of one per position;
    only size/bucket — pow2, hence bounded in variety — key new traces.
    Jitted: an eager ``a[start:stop]`` would stage its start index through
    an implicit host->device transfer."""
    w = jax.lax.dynamic_slice_in_dim(a, start, size)
    if bucket != size:
        w = jnp.pad(w, (0, bucket - size), constant_values=-1)
    return w.astype(jnp.int32)


@functools.partial(jax.jit, static_argnums=(1,))
def _resident_pad_rows(a, bucket: int):
    """Zero-pad a device store's rows to ``bucket`` without a host bounce."""
    pad = ((0, bucket - a.shape[0]),) + ((0, 0),) * (a.ndim - 1)
    return jnp.pad(a, pad)


@functools.lru_cache(maxsize=None)
def _scatter_update_fn():
    """Module-level jitted word-scatter into a resident slice store.

    Applies ``(old | set_mask) & ~clear_mask`` at each ``(pos, word)`` cell
    and returns a NEW array — the input store is never donated, because a
    streaming before-count dispatched against it may still be in flight
    (the delta protocol counts touched pairs against the pre-update stores,
    then updates, then counts against the post-update stores). Sentinel
    lanes carry ``pos`` beyond any store bucket, so the ``mode='drop'``
    scatter ignores them; traces are keyed by (store shape, lane bucket) —
    both pow2 — so steady-state streaming batches add zero traces.
    """

    def upd(store, pos, word, set_mask, clear_mask):
        safe = jnp.minimum(pos, store.shape[0] - 1)
        cur = store[safe, word]
        new = (cur | set_mask) & ~clear_mask
        return store.at[pos, word].set(new, mode="drop")

    return jax.jit(upd)


def _pad_lanes(lanes, bucket: int):
    """Pow2-pad one side's update lanes; sentinel rows are exact no-ops."""
    pos = np.full(bucket, _INT32_MAX, dtype=np.int32)
    word = np.zeros(bucket, dtype=np.int32)
    set_mask = np.zeros(bucket, dtype=np.uint32)
    clear_mask = np.zeros(bucket, dtype=np.uint32)
    k = lanes.num_lanes
    pos[:k] = lanes.pos
    word[:k] = lanes.word
    set_mask[:k] = lanes.set_mask
    clear_mask[:k] = lanes.clear_mask
    return pos, word, set_mask, clear_mask


def apply_store_lanes(store, lanes):
    """Scatter one side's :class:`~repro.core.sbf.UpdateLanes` into a
    device-resident store, returning the updated array (input untouched —
    in-flight counts against the old store stay valid). Shared by the
    replicated :class:`Executor` and the sharded executors (which remap
    lane positions to block-local rows first)."""
    if lanes is None or lanes.num_lanes == 0:
        return store
    bucket = _pow2_ceil(lanes.num_lanes)
    padded = _pad_lanes(lanes, bucket)
    return _scatter_update_fn()(store, *(jax.device_put(a) for a in padded))


def scatter_update_trace_count() -> int:
    """Jit-cache size of the store-scatter step (regression tests assert a
    steady-state streaming batch adds zero here). -1 if the private jax
    API disappears."""
    try:
        return int(_scatter_update_fn()._cache_size())
    except Exception:
        return -1


def _execute_impl(mode: str, use_kernel: bool | None) -> str:
    if mode != "fused":
        return mode
    return "pallas" if ops.gather_kernel_selected(use_kernel) else "jnp_mirror"


@functools.lru_cache(maxsize=None)
def _chunk_step_fn(
    mode: str,
    interpret: bool | None,
    use_kernel: bool | None,
    donate: str,
):
    """Module-level jitted chunk step, shared by every Executor with the same
    config — one-shot API calls (tcim_count per graph) amortize traces and
    compiles across Executor instances instead of retracing per construction.

    ``donate`` picks the donation set: ``'all'`` (indices + accumulator —
    the host staging path, whose per-chunk index buffers are dead after the
    step), ``'acc'`` (accumulator only — the device-resident index path,
    whose index windows may be re-executed from a pooled worklist), or
    ``'none'`` (CPU, which ignores donation and warns about it).
    """

    def chunk_total(row_data, col_data, ridx, cidx):
        """Per-chunk total (int32 scalar); -1 indices are no-ops."""
        if mode == "fused":
            return ops.popcount_and_gather_total(
                row_data, col_data, ridx, cidx,
                use_kernel=use_kernel, interpret=interpret,
            )
        mask = (ridx >= 0) & (cidx >= 0)
        rows = jnp.take(row_data, jnp.maximum(ridx, 0), axis=0)
        cols = jnp.take(col_data, jnp.maximum(cidx, 0), axis=0)
        # Zeroing one side of the AND suffices: x & 0 == 0.
        rows = jnp.where(mask[:, None], rows, 0)
        if mode == "gather_then_kernel":
            return ops.popcount_and_total(rows, cols, interpret=interpret)
        if mode == "pallas_items":
            return ops.popcount_and_items(rows, cols, interpret=interpret).sum(
                dtype=jnp.int32
            )
        return ref.ref_popcount_and_total(rows, cols)  # 'jnp' oracle path

    def tc_chunk_step(row_data, col_data, ridx, cidx, acc):
        return acc + chunk_total(row_data, col_data, ridx, cidx)

    argnums = {"none": (), "acc": (4,), "all": (2, 3, 4)}[donate]
    return jax.jit(tc_chunk_step, donate_argnums=argnums)


def _select_bit(words, rank):
    """Position of the ``rank``-th set bit (from 0, least significant
    first) of each row of ``words`` ([L, W] uint32 read as one W*32-bit
    string), for ranks below the row's popcount. Word by word, then a
    5-step halving search inside the word: elementwise, no gather."""
    pos = jnp.zeros(rank.shape, jnp.int32)
    found = jnp.zeros(rank.shape, bool)
    for j in range(words.shape[1]):
        x = words[:, j]
        pc = swar_popcount_u32(x)
        here = ~found & (rank < pc)
        r = rank
        bit = jnp.zeros(rank.shape, jnp.int32)
        for width in (16, 8, 4, 2, 1):
            low = swar_popcount_u32(x & jnp.uint32((1 << width) - 1))
            up = r >= low
            r = jnp.where(up, r - low, r)
            x = jnp.where(up, x >> jnp.uint32(width), x)
            bit = jnp.where(up, bit + width, bit)
        pos = jnp.where(here, j * 32 + bit, pos)
        rank = jnp.where(found | here, rank, rank - pc)
        found = found | here
    return pos


@functools.lru_cache(maxsize=None)
def _vertex_step_fn(slice_bits: int, donate: bool):
    """Module-level jitted per-vertex attribution of one pair chunk.

    For the pair (edge (u, v), slice k), bit b of the AND word
    ``row[u] & col[v]`` is set exactly when w = k * slice_bits + b closes
    the triangle u < w < v (Eq. 5 before its BitCount is summed). The step
    adds the pair's popcount to ``counts[u]`` and ``counts[v]``, and one to
    ``counts[w]`` for every set bit. Set bits are spread one per lane: a
    lane finds its pair with a scatter of pair ids at their popcount
    offsets and a running max (as the device build's expansion does), and
    its bit with ``_select_bit``. A chunk whose set bits outnumber its
    lanes runs more windows of lanes; the loop stops at the chunk's total.
    Returns (counts, [chunk total, pairs with a non-zero word]), every
    count int32: one chunk's total is bounded by ``clamp_chunk_pairs``.
    ``counts`` (donated off the CPU) is indexed by the relabelled ids,
    padded to a pow2 bucket; negative indices are no-op pairs.
    """

    def tc_vertex_step(row_data, col_data, ridx, cidx, eidx, src, dst,
                       slice_idx, counts):
        words = gather_and_words_reference(row_data, col_data, ridx, cidx)
        pc = swar_popcount_u32(words).sum(axis=1)
        size = counts.shape[0]
        live = pc > 0
        e = jnp.maximum(eidx, 0)
        counts = (
            counts.at[jnp.where(live, src[e], size)].add(pc, mode="drop")
            .at[jnp.where(live, dst[e], size)].add(pc, mode="drop")
        )
        k = slice_idx[jnp.maximum(ridx, 0)]
        lanes = pc.shape[0]
        cum = jnp.cumsum(pc)
        start = cum - pc
        total = cum[-1]
        pair = jnp.arange(lanes, dtype=jnp.int32)

        def window(j, counts):
            base = j * lanes
            rel = start - base
            # The last pair starting at or before ``base`` owns lane 0;
            # zero-popcount pairs share their start with the next pair,
            # which the max keeps.
            seed = jnp.zeros(lanes, jnp.int32).at[
                jnp.where(rel >= 0, rel, lanes)
            ].max(pair, mode="drop")
            seed = seed.at[0].max(jnp.sum(start <= base) - 1)
            p = jax.lax.cummax(seed, axis=0)
            lane = base + pair
            bit = _select_bit(words[p], lane - start[p])
            w = jnp.where(lane < total, k[p] * slice_bits + bit, size)
            return counts.at[w].add(1, mode="drop")

        counts = jax.lax.fori_loop(0, (total + lanes - 1) // lanes, window,
                                   counts)
        return counts, jnp.stack([total, jnp.sum(live.astype(jnp.int32))])

    return jax.jit(tc_vertex_step, donate_argnums=(8,) if donate else ())


def tc_vertex_close(counts, new_id, stats):
    """Counts in the caller's vertex ids (``counts[new_id]``) and the
    chunks' stacked [total, non-zero pairs] scalars, for one readback."""
    return counts[new_id], jnp.stack(stats)


_vertex_close = jax.jit(tc_vertex_close)


class VertexCountFuture:
    """A dispatched per-vertex count whose readback is deferred.

    ``result(new_id)`` maps the counts back to the caller's ids on the
    device, reads the [n] counts and the per-chunk scalars back in one
    transfer, and returns (total triangles, int64 [n] counts, pairs with a
    non-zero AND word). Raises ``OverflowError`` when 3 x the total passes
    int32, the bound of the device's int32 counts.
    """

    def __init__(self, counts, stats: list):
        self._counts = counts
        self._stats = stats

    def result(self, new_id) -> tuple[int, np.ndarray, int]:
        if not self._stats:
            return 0, np.zeros(new_id.shape[0], np.int64), 0
        # tclint: sync-ok(the one readback of a per-vertex count, at future close)
        counts, stats = jax.device_get(_vertex_close(
            self._counts, _resident_pow2(new_id, pad=False), self._stats))
        total = sum(int(t) for t in stats[:, 0])  # exact: host ints
        if 3 * total > _INT32_MAX:
            raise OverflowError(
                f"{total} triangles: per-vertex counts past int32 "
                "(3 x the total bounds them) are not exact on the device"
            )
        return total, counts.astype(np.int64), sum(int(z) for z in stats[:, 1])


class Executor:
    """Device-resident execute stage for one pair of SBF slice stores.

    Upload the stores once, then ``count(worklist)`` (or the lower-level
    ``execute_indices``) any number of times; chunk shapes are bucketed so
    repeated counts never retrace.
    """

    def __init__(
        self,
        sb: sbf_mod.SlicedBitmap,
        *,
        mode: str = "fused",
        chunk_pairs: int = 1 << 20,
        interpret: bool | None = None,
        use_kernel: bool | None = None,
        double_buffer: bool = True,
        pad_stores_pow2: bool = True,
    ):
        if mode not in EXECUTOR_MODES:
            raise ValueError(f"mode {mode!r} not in {EXECUTOR_MODES}")
        self.mode = mode
        # What runs each chunk: in fused mode the Pallas kernel or the jnp
        # mirror (ops.gather_kernel_selected decides), else the named mode.
        self.execute_impl = _execute_impl(mode, use_kernel)
        self.words_per_slice = int(sb.row_slice_data.shape[1])
        self.slice_bits = int(sb.slice_bits)
        self.double_buffer = double_buffer
        # Round the chunk DOWN to a power of two (never exceed the caller's
        # memory bound), then clamp so one chunk's worst case provably fits
        # the int32 accumulator: chunk_pairs * words_per_slice * 32 <= 2**31-1.
        # Raises a clear ValueError when words_per_slice alone busts the bound.
        self.chunk_pairs = clamp_chunk_pairs(chunk_pairs, self.words_per_slice)
        # Stores go to the device once and stay resident across counts,
        # row-bucketed to pow2 so same-bucket graphs share chunk-step traces.
        # Device-built SBFs (core.build) arrive as jax arrays already in
        # that layout — adopt them as-is, without a host bounce.
        self.row_data = self._adopt_store(sb.row_slice_data, pad_stores_pow2)
        self.col_data = self._adopt_store(sb.col_slice_data, pad_stores_pow2)
        # CPU ignores donation (and warns about it); donate elsewhere. The
        # resident-index path never donates its index windows (a pooled
        # device worklist may be counted again).
        self._chunk_jit = _chunk_step_fn(
            mode, interpret, use_kernel,
            donate="none" if on_cpu() else "all",
        )
        self._chunk_jit_resident = _chunk_step_fn(
            mode, interpret, use_kernel,
            donate="none" if on_cpu() else "acc",
        )
        # Weakrefs to unresolved CountFutures. While any is alive the
        # executor's device stores back in-flight dispatches, so pools must
        # not free them (``busy``); resolved/collected futures prune lazily.
        self._pending: list = []

    def _track(self, fut: "CountFuture") -> "CountFuture":
        self._pending = [
            r for r in self._pending
            if (f := r()) is not None and not f.resolved
        ]
        if not fut.resolved:
            self._pending.append(weakref.ref(fut))
        return fut

    @property
    def busy(self) -> bool:
        """True while a dispatched ``CountFuture`` still awaits ``result()``.

        Evicting (freeing the stores of) a busy executor could invalidate
        the pending readback; ``ExecutorPool`` defers eviction instead."""
        self._pending = [
            r for r in self._pending
            if (f := r()) is not None and not f.resolved
        ]
        return bool(self._pending)

    @staticmethod
    def _adopt_store(store, pad_stores_pow2: bool):
        if isinstance(store, np.ndarray):
            if pad_stores_pow2:
                store = _pad_rows_pow2(store)
            return jnp.asarray(store)
        rows = int(store.shape[0])
        bucket = _pow2_ceil(max(rows, 1))
        if bucket != rows:  # device builds are pre-bucketed; pad stragglers
            store = _resident_pad_rows(store, bucket)
        return store

    # ---------------------------------------------------------------- public

    @property
    def trace_count(self) -> int:
        """Chunk shapes traced by this executor's (config-shared) jitted step.

        Shared across Executors with identical config, so regression tests
        should assert on deltas around a count, not absolute values. Reads a
        private jax API; returns -1 (tests skip) if a jax upgrade removes it.
        Covers both the host-staging and device-resident chunk steps (one
        object on CPU, where neither donates).
        """
        try:
            total = int(self._chunk_jit._cache_size())
            if self._chunk_jit_resident is not self._chunk_jit:
                total += int(self._chunk_jit_resident._cache_size())
            return total
        except Exception:
            return -1

    def _chunks(self, *index_arrays: np.ndarray):
        """Yield host-side int32 chunks of the index arrays (row, column,
        ...) in pow2 buckets."""
        p = len(index_arrays[0])
        c = self.chunk_pairs
        for start in range(0, p, c):
            size = min(c, p - start)
            bucket = _pow2_ceil(size)
            chunk = []
            for a in index_arrays:
                a = np.asarray(a[start : start + c], dtype=np.int32)
                if bucket != size:  # ragged tail -> pad to its pow2 bucket
                    a = np.concatenate([a, np.full(bucket - size, -1, np.int32)])
                chunk.append(a)
            yield tuple(chunk)

    def _device_chunks(self, *index_arrays: np.ndarray):
        """Upload chunks to the device, one ahead of the consumer.

        With double buffering, chunk i+1's pad/convert work and its
        ``device_put`` staging are issued before chunk i is yielded, so the
        i+1 transfer is already under way when the consumer dispatches chunk
        i's fused step (see ``staged_uploads``). Counts are bit-identical
        either way.
        """
        return staged_uploads(
            self._chunks(*index_arrays),
            lambda chunk: tuple(jax.device_put(a) for a in chunk),
            double_buffer=self.double_buffer,
        )

    def _resident_chunks(self, *index_arrays):
        """Pow2 chunk windows of device-resident index arrays (no staging —
        the indices are already on device; windows are jitted static slices)."""
        p = int(index_arrays[0].shape[0])
        c = self.chunk_pairs
        if p <= c and p == _pow2_ceil(p) and all(
            a.dtype == jnp.int32 for a in index_arrays
        ):
            # The common device-worklist shape (one pow2 bucket): no copy.
            yield index_arrays
            return
        for start in range(0, p, c):
            size = min(c, p - start)
            bucket = _pow2_ceil(size)
            yield tuple(
                _resident_window(a, start, size, bucket) for a in index_arrays
            )

    def _accumulate(self, device_chunks, step, worst_pairs: int) -> CountFuture:
        """Dispatch every chunk step; defer the host sync to the future."""
        # Worst case: every bit of every referenced slice set.
        if worst_pairs * self.slice_bits <= _INT32_MAX:
            acc = jnp.int32(0)
            for ridx, cidx in device_chunks:
                acc = step(self.row_data, self.col_data, ridx, cidx, acc)
            return CountFuture([acc])
        # Huge work lists: int32 carry could overflow across chunks; keep
        # per-chunk totals device-side, exact host sum at close.
        return CountFuture(
            [
                step(self.row_data, self.col_data, ridx, cidx, jnp.int32(0))
                for ridx, cidx in device_chunks
            ]
        )

    @no_host_sync()
    def execute_indices_async(
        self, row_idx, col_idx, *, num_real: int | None = None
    ) -> CountFuture:
        """Dispatch a count over explicit index arrays; defer the host sync.

        Every chunk step is enqueued before this returns; the returned
        future's ``result()`` is the one host transfer. Empty work lists
        dispatch nothing. The arrays may be host numpy (staged to the device
        chunk by chunk, double-buffered) or device-resident jax arrays
        (``core.build``'s worklists: chunked by static slicing, zero host
        bounces). ``num_real`` tightens the int32-overflow bound for padded
        device arrays whose real (non-sentinel) pair count is known.

        Contract (``TCIM_CONTRACTS=1``): the dispatch itself never syncs —
        ``Executor.count``'s one host transfer is the ``CountFuture`` close,
        which runs outside this region.
        """
        p = len(row_idx)
        if p == 0 or num_real == 0:
            return CountFuture([])
        if isinstance(row_idx, jax.Array):
            return self._track(self._accumulate(
                self._resident_chunks(row_idx, col_idx),
                self._chunk_jit_resident,
                num_real if num_real is not None else p,
            ))
        return self._track(self._accumulate(
            self._device_chunks(row_idx, col_idx), self._chunk_jit, p
        ))

    def execute_indices(
        self, row_idx, col_idx, *, num_real: int | None = None
    ) -> int:
        """Count over explicit work-list index arrays. One host sync total."""
        return self.execute_indices_async(row_idx, col_idx, num_real=num_real).result()

    def count_async(self, wl) -> CountFuture:
        """``count`` with the final host readback deferred to ``result()``.

        ``wl`` is a host ``Worklist`` or a device ``core.build
        .DeviceWorklist`` (whose padded pair arrays execute without ever
        touching the host).
        """
        return self.execute_indices_async(
            wl.pair_row_pos, wl.pair_col_pos, num_real=wl.num_pairs
        )

    def count(self, wl) -> int:
        """Triangle contribution of a work list (Eq. 5 execute+reduce)."""
        return self.count_async(wl).result()

    @no_host_sync()
    def vertex_counts_async(self, wl, src, dst, slice_idx, n: int) -> VertexCountFuture:
        """Dispatch the per-vertex triangle count T of a work list; defer
        the readback to the future's ``result(new_id)``.

        ``src``/``dst`` are the oriented edges the work list's
        ``pair_edge`` indexes, and ``slice_idx`` the row store's slice
        numbers (``SlicedBitmap.row_slice_idx``): device arrays of a
        device build, or host arrays, uploaded here pow2-padded. The pair
        chunks are ``count``'s, and each runs ``_vertex_step_fn``; the
        counts of the ``n`` vertices stay on the device in int32 across
        chunks, padded to a pow2 bucket.
        """
        counts = jnp.zeros(_pow2_ceil(max(n, 1)), jnp.int32)
        stats: list = []
        if not wl.num_pairs:
            return VertexCountFuture(counts, stats)
        src, dst, slice_idx = (_resident_pow2(a) for a in (src, dst, slice_idx))
        index_arrays = (wl.pair_row_pos, wl.pair_col_pos, wl.pair_edge)
        if isinstance(index_arrays[0], jax.Array):
            chunks = self._resident_chunks(*index_arrays)
        else:
            chunks = self._device_chunks(*index_arrays)
        step = _vertex_step_fn(self.slice_bits, not on_cpu())
        for ridx, cidx, eidx in chunks:
            counts, chunk_stats = step(self.row_data, self.col_data, ridx, cidx,
                                       eidx, src, dst, slice_idx, counts)
            stats.append(chunk_stats)
        return VertexCountFuture(counts, stats)

    def update_stores(self, row_lanes, col_lanes) -> None:
        """Scatter word-level edits (``sbf.UpdateLanes``) into the resident
        stores — the streaming steady state: a delta batch that touches only
        existing ``(vertex, slice)`` records edits the device stores in
        place of a re-upload. The scatter produces NEW arrays (no donation),
        so a before-count already dispatched against the old stores keeps
        its buffers; lane and store shapes are pow2-bucketed, so repeated
        same-bucket batches add zero traces (``scatter_update_trace_count``).
        Positions must be in-bounds for the resident (pow2-padded) stores —
        a grown SBF goes through :meth:`adopt_stores` instead.
        """
        for lanes, store in ((row_lanes, self.row_data), (col_lanes, self.col_data)):
            if lanes is not None and lanes.num_lanes and int(
                lanes.pos.max()
            ) >= int(store.shape[0]):
                raise ValueError(
                    "update lane position beyond the resident store bucket "
                    "— the SBF grew; re-adopt the stores (adopt_stores)"
                )
        self.row_data = apply_store_lanes(self.row_data, row_lanes)
        self.col_data = apply_store_lanes(self.col_data, col_lanes)

    def adopt_stores(self, sb: sbf_mod.SlicedBitmap) -> None:
        """Replace the resident stores with a (grown) SBF's — one upload.

        The growth path of streaming updates: merge-inserted records shift
        positions, so scatter editing is impossible and the stores re-adopt
        wholesale. Word width must match (the traces are keyed by it); the
        pow2 row bucket usually survives growth, in which case every
        existing chunk-step trace still applies.
        """
        if int(sb.row_slice_data.shape[1]) != self.words_per_slice:
            raise ValueError(
                f"adopt_stores: words_per_slice {sb.row_slice_data.shape[1]} "
                f"!= executor's {self.words_per_slice}"
            )
        self.row_data = self._adopt_store(sb.row_slice_data, True)
        self.col_data = self._adopt_store(sb.col_slice_data, True)


def sbf_content_key(sb: sbf_mod.SlicedBitmap) -> str:
    """Digest of an SBF's store contents (shape + data).

    Pools key entries by *content*, not object identity, so one-shot API
    calls that rebuild the SBF for the same graph still hit the cached
    executor (and two identical-content SBFs share one set of device
    stores). blake2b over the raw store bytes — tens of microseconds per MB,
    negligible next to a count. Device-built SBFs carry a precomputed
    ``content_key`` (a digest of the *input edge list*, taken before the
    upload), so keying them never reads the stores back from the device.
    """
    if getattr(sb, "content_key", None) is not None:
        return sb.content_key
    cached = getattr(sb, "_store_digest", None)
    if cached is not None:
        return cached
    h = hashlib.blake2b(digest_size=16)
    h.update(
        repr(
            (
                sb.slice_bits,
                sb.row_slice_data.shape,
                sb.col_slice_data.shape,
            )
        ).encode()
    )
    # tclint: sync-ok(content keys hash host-built SBFs; device SBFs carry a precomputed key)
    h.update(np.ascontiguousarray(sb.row_slice_data).tobytes())
    # tclint: sync-ok(content keys hash host-built SBFs; device SBFs carry a precomputed key)
    h.update(np.ascontiguousarray(sb.col_slice_data).tobytes())
    digest = h.hexdigest()
    # Stores are treated as immutable once built; memoize the digest on the
    # (frozen, slot-free) dataclass so a serving loop re-keying the same
    # objects every round pays the hash once, not per round.
    object.__setattr__(sb, "_store_digest", digest)
    return digest


class ExecutorPool:
    """Executors for a fleet serving many graphs, grouped by trace key.

    The pool caches one Executor per graph (LRU-bounded — an evicted graph's
    device stores are freed) and groups them by the *trace key*
    ``(words_per_slice, chunk bucket, mode)``: executors sharing a trace key
    share the module-level jitted chunk step, so admitting a second graph
    with an equal key adds **zero** new traces — only its store upload. That
    is the multi-graph analogue of TCIM's slice mapping: the expensive
    artifact (the compiled array program) is keyed by shape, not by graph.

    Entries are keyed by store *content* (``sbf_content_key``), so repeated
    counts of the same graph hit even when the caller rebuilds the SBF
    object each time — the case the one-shot ``tcim_count*`` API produces.
    """

    def __init__(self, *, max_graphs: int = 16):
        if max_graphs < 1:
            raise ValueError(f"max_graphs must be >= 1, got {max_graphs}")
        self.max_graphs = max_graphs
        # content key -> (trace_key, Executor); ordered for LRU.
        self._entries: collections.OrderedDict[tuple, tuple] = collections.OrderedDict()
        self.hits = 0
        self.misses = 0

    @staticmethod
    def trace_key(
        sb: sbf_mod.SlicedBitmap,
        *,
        mode: str = "fused",
        chunk_pairs: int = 1 << 20,
        pad_stores_pow2: bool = True,
    ) -> tuple:
        """The (words_per_slice, chunk bucket, mode, store buckets) an
        Executor traces under — equal keys share every chunk-step trace.

        ``pad_stores_pow2=False`` executors keep their exact store row
        counts, so their traces are keyed by those exact shapes — the key
        must report the same, or ``stats()`` overstates trace sharing.
        """
        wps = int(sb.words_per_slice)
        rows = int(sb.row_slice_data.shape[0])
        cols = int(sb.col_slice_data.shape[0])
        if pad_stores_pow2:
            rows = _pow2_ceil(max(rows, 1))
            cols = _pow2_ceil(max(cols, 1))
        return (wps, clamp_chunk_pairs(chunk_pairs, wps), mode, rows, cols)

    def get(
        self,
        sb: sbf_mod.SlicedBitmap,
        *,
        mode: str = "fused",
        chunk_pairs: int = 1 << 20,
        **executor_kwargs,
    ) -> Executor:
        """The pooled Executor for ``sb`` (uploading its stores on first use)."""
        key = (
            sbf_content_key(sb),
            mode,
            clamp_chunk_pairs(chunk_pairs, sb.words_per_slice),
            tuple(sorted(executor_kwargs.items())),  # config never aliases
        )
        entry = self._entries.get(key)
        if entry is not None:
            self.hits += 1
            self._entries.move_to_end(key)
            return entry[1]
        self.misses += 1
        ex = Executor(sb, mode=mode, chunk_pairs=chunk_pairs, **executor_kwargs)
        tkey = self.trace_key(
            sb,
            mode=mode,
            chunk_pairs=chunk_pairs,
            pad_stores_pow2=executor_kwargs.get("pad_stores_pow2", True),
        )
        self._entries[key] = (tkey, ex)
        self._entries.move_to_end(key)
        self._evict()
        return ex

    def _evict(self) -> None:
        """Drop LRU graphs above ``max_graphs`` — but never one whose
        executor is ``busy`` (a dispatched ``CountFuture`` still pending):
        freeing its device stores would invalidate the deferred readback.
        Busy executors are skipped (defer-free — the pool may transiently
        exceed ``max_graphs``) and reaped on the next ``get`` once their
        futures resolve."""
        while len(self._entries) > self.max_graphs:
            keys = list(self._entries)[:-1]  # never evict the MRU entry
            victim = next(
                (k for k in keys if not self._entries[k][1].busy), None
            )
            if victim is None:
                return  # everything in-flight; retry on a later get()
            del self._entries[victim]

    def count_async(
        self,
        sb: sbf_mod.SlicedBitmap,
        wl: sbf_mod.Worklist,
        *,
        mode: str = "fused",
        chunk_pairs: int = 1 << 20,
        **executor_kwargs,
    ) -> CountFuture:
        """Dispatch a count on the pooled executor for ``sb``; defer the sync.

        The fleet-serving primitive: the returned future's readback can be
        taken after the *next* graph's stripe assembly and uploads have been
        dispatched, hiding the per-graph end sync behind useful host work.
        """
        return self.get(
            sb, mode=mode, chunk_pairs=chunk_pairs, **executor_kwargs
        ).count_async(wl)

    def count(
        self,
        sb: sbf_mod.SlicedBitmap,
        wl: sbf_mod.Worklist,
        *,
        mode: str = "fused",
        chunk_pairs: int = 1 << 20,
        **executor_kwargs,
    ) -> int:
        """Blocking convenience over ``count_async`` (identical counts)."""
        return self.count_async(
            sb, wl, mode=mode, chunk_pairs=chunk_pairs, **executor_kwargs
        ).result()

    def __len__(self) -> int:
        return len(self._entries)

    def clear(self) -> None:
        """Drop every cached graph (frees their device-resident stores)."""
        self._entries.clear()

    def stats(self) -> dict:
        """Pool effectiveness: hit rate and trace sharing across graphs."""
        groups = collections.Counter(tkey for tkey, _ in self._entries.values())
        return {
            "graphs": len(self._entries),
            "hits": self.hits,
            "misses": self.misses,
            "trace_groups": len(groups),
            "max_group": max(groups.values(), default=0),
            "execute_impls": sorted(
                {ex.execute_impl for _, ex in self._entries.values()}
            ),
        }


class MultiCountFuture:
    """A fused multi-graph dispatch whose host readback is deferred.

    Holds the single ``[padded_graphs]`` device vector of per-graph
    subtotals; ``result()`` is ONE device->host transfer returning the real
    graphs' counts as a tuple of Python ints (idempotent, cached).
    """

    __slots__ = ("_totals", "_num", "_value")

    def __init__(self, totals, num_graphs: int):
        self._totals = totals
        self._num = int(num_graphs)
        self._value: tuple[int, ...] | None = None

    @property
    def resolved(self) -> bool:
        return self._totals is None

    def result(self) -> tuple[int, ...]:
        if self._totals is not None:
            host = np.asarray(self._totals)  # the one transfer
            self._value = tuple(int(t) for t in host[: self._num])
            self._totals = None
        return self._value


@functools.lru_cache(maxsize=None)
def _fused_step_fn(bucket: int, interpret: bool | None, use_kernel: bool | None):
    """Module-level jitted fused step: [G*bucket] indices -> [G] subtotals.

    Keyed by the segment ``bucket`` (static: it shapes the reduction), so
    every MultiGraphExecutor — and every fused batch whose graphs share a
    bucket — runs one compiled program. No donation: cached batches
    re-execute their resident index blocks.
    """

    def tc_fused_step(row_data, col_data, ridx, cidx):
        return ops.popcount_and_gather_segment_totals(
            row_data, col_data, ridx, cidx,
            bucket=bucket, use_kernel=use_kernel, interpret=interpret,
        )

    return jax.jit(tc_fused_step)


def _worklist_key(wl) -> str:
    """Digest of a worklist's pair positions (fused-batch cache keying).

    Store content alone is not enough — a caller may legitimately count a
    partial worklist against the same stores — so batch keys pair each
    graph's ``sbf_content_key`` with this digest. Worklists fused here are
    small (the admission bucket bound), so the hash cost is noise.
    """
    cached = getattr(wl, "_pairs_digest", None)
    if cached is not None:
        return cached
    h = hashlib.blake2b(digest_size=16)
    rp = np.ascontiguousarray(np.asarray(wl.pair_row_pos, dtype=np.int64))
    cp = np.ascontiguousarray(np.asarray(wl.pair_col_pos, dtype=np.int64))
    h.update(np.int64(len(rp)).tobytes())
    h.update(rp.tobytes())
    h.update(cp.tobytes())
    digest = h.hexdigest()
    object.__setattr__(wl, "_pairs_digest", digest)
    return digest


class _FusedBatch:
    """Device-resident state of one fused batch: stacked stores + index
    block + the shared jitted step. Re-dispatching is one jit call."""

    __slots__ = ("plan", "row_data", "col_data", "ridx", "cidx", "_step")

    def __init__(self, plan, row_data, col_data, ridx, cidx, step):
        self.plan = plan
        self.row_data = row_data
        self.col_data = col_data
        self.ridx = ridx
        self.cidx = cidx
        self._step = step

    def count_async(self) -> MultiCountFuture:
        totals = self._step(self.row_data, self.col_data, self.ridx, self.cidx)
        return MultiCountFuture(totals, self.plan.num_graphs)


class MultiGraphExecutor:
    """Fused execute stage for MANY small graphs per dispatch.

    The serving-side analogue of TCIM's array packing: an ``ExecutorPool``
    drains a fleet one dispatch per graph; this executor stacks a batch of
    small graphs' stores and pow2-bucketed worklists (``core.plan
    .plan_fusion``) and retires the whole batch with ONE jitted call that
    returns per-graph int32 subtotals (``kernels.ops
    .popcount_and_gather_segment_totals``). Big graphs should not come
    here — ``max_fused_pairs`` bounds the per-graph segment, and
    ``launch.tc_serve`` routes anything larger solo.

    Batches are cached LRU by content (store digests + worklist digests), so
    a recurring tenant mix re-counts with zero staging: one cached dispatch,
    one readback, regardless of batch size. Shapes are pow2-padded on every
    axis (segment bucket, graph count, stacked store rows), so distinct
    batches that land in the same buckets share the compiled step — the
    fused path's single-trace property, asserted in tests.
    """

    def __init__(
        self,
        *,
        max_batches: int = 8,
        max_fused_pairs: int = 1 << 16,
        interpret: bool | None = None,
        use_kernel: bool | None = None,
    ):
        if max_batches < 1:
            raise ValueError(f"max_batches must be >= 1, got {max_batches}")
        self.max_batches = max_batches
        self.max_fused_pairs = int(max_fused_pairs)
        self._interpret = interpret
        self._use_kernel = use_kernel
        self.execute_impl = _execute_impl("fused", use_kernel)
        self._batches: collections.OrderedDict[tuple, _FusedBatch] = (
            collections.OrderedDict()
        )
        self._steps: dict[int, object] = {}  # bucket -> jitted step
        self.hits = 0
        self.misses = 0

    @property
    def trace_count(self) -> int:
        """Traces across every fused step this executor has used (see
        ``Executor.trace_count`` for the caveats)."""
        try:
            return sum(int(s._cache_size()) for s in self._steps.values())
        except Exception:
            return -1

    def _step_for(self, bucket: int):
        step = self._steps.get(bucket)
        if step is None:
            step = _fused_step_fn(bucket, self._interpret, self._use_kernel)
            self._steps[bucket] = step
        return step

    def plan(self, jobs):
        """The ``FusionPlan`` this executor would run ``jobs`` under —
        exposed so admission control can cost a batch before committing."""
        # max_fused_pairs bounds each graph's worklist; the shared bucket is
        # its pow2 ceiling (admission accepts pairs == max_fused_pairs, and
        # the planner rounds the largest worklist up).
        return plan_fusion(
            jobs, max_bucket=_pow2_ceil(max(self.max_fused_pairs, 1))
        )

    @no_host_sync()
    def count_fused_async(self, jobs) -> MultiCountFuture:
        """Dispatch one fused count over ``jobs`` (list of host
        ``(SlicedBitmap, Worklist)``); defer the single host readback.

        Raises ``ValueError`` (via ``plan_fusion``) when a job exceeds the
        fused segment bound or mixes word widths — admission control filters
        those out before calling.

        Contract (``TCIM_CONTRACTS=1``): the fused dispatch never syncs, and
        a cached batch re-dispatches against its resident blocks with zero
        staging calls.
        """
        key = tuple(
            (sbf_content_key(sb), _worklist_key(wl)) for sb, wl in jobs
        )
        batch = self._batches.get(key)
        if batch is not None:
            self.hits += 1
            self._batches.move_to_end(key)
            with max_transfers(0):
                return batch.count_async()
        self.misses += 1
        plan = self.plan(jobs)
        row_data = _pad_rows_pow2(
            np.concatenate(
                # tclint: sync-ok(fusion stacks host SBF stores; one upload follows)
                [np.asarray(sb.row_slice_data) for sb, _ in jobs]
            ) if plan.row_rows else
            np.zeros((0, plan.words_per_slice), np.uint32)
        )
        col_data = _pad_rows_pow2(
            np.concatenate(
                # tclint: sync-ok(fusion stacks host SBF stores; one upload follows)
                [np.asarray(sb.col_slice_data) for sb, _ in jobs]
            ) if plan.col_rows else
            np.zeros((0, plan.words_per_slice), np.uint32)
        )
        batch = _FusedBatch(
            plan,
            jax.device_put(jnp.asarray(row_data)),
            jax.device_put(jnp.asarray(col_data)),
            jax.device_put(plan.row_idx),
            jax.device_put(plan.col_idx),
            self._step_for(plan.bucket),
        )
        self._batches[key] = batch
        while len(self._batches) > self.max_batches:
            self._batches.popitem(last=False)
        return batch.count_async()

    def count_fused(self, jobs) -> tuple[int, ...]:
        """Blocking convenience over ``count_fused_async``."""
        return self.count_fused_async(jobs).result()

    def __len__(self) -> int:
        return len(self._batches)

    def clear(self) -> None:
        self._batches.clear()

    def stats(self) -> dict:
        return {
            "batches": len(self._batches),
            "hits": self.hits,
            "misses": self.misses,
            "buckets": sorted(self._steps),
            "execute_impl": self.execute_impl,
        }
