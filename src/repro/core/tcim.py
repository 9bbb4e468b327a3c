"""TCIM engine — Eq. (5) of the paper as a composable JAX pipeline.

    TC(G) = sum_{A[i][j]=1} BitCount(AND(R_i, C_j))        [upper-triangular A]

Pipeline stages (each independently testable):
    orient      edges -> upper-triangular CSR (optional degree relabelling)
    compress    SBF: valid slices only (paper §IV-B)
    schedule    work list of valid slice pairs (the 0.01% that matter)
    plan        core.plan.plan_execution — placement (replicated /
                sharded_cols / sharded_2d), weighted or even range splits,
                owner-grouped stripes, pow2 chunk buckets
    execute     core.executor.Executor (replicated; pooled + double-
                buffered), distributed.tc.ShardedColsExecutor (column store
                NamedSharding-sharded over a mesh), or
                distributed.tc.Sharded2DExecutor (BOTH stores sharded over
                a 2-axis (row, col) owner grid with pair-count-balanced
                ranges)
    reduce      a single exact scalar readback (psum-closed when sharded)

The first three stages run on the host (NumPy reference, ``build='host'``)
or as jit-compiled device work (``core.build``, ``build='device'``): the
device build performs ONE host->device transfer (the pow2-bucket-padded edge
list) and keeps every array device-resident through the execute stage —
stores and worklists flow straight into the pooled Executor with zero host
bounces (two scalar readbacks size the static output buckets; the bulk
arrays never travel). ``build='auto'`` picks the device build on
accelerator backends for the single-device worklist path and the NumPy
reference elsewhere. Each stage runs under a named span
(``repro.runtime.spans``) on the profiler's clock: ``tc.count`` wraps one
call, with ``tc.orient``/``tc.compress``/``tc.schedule``/``tc.plan``/
``tc.execute`` (plus ``tc.close`` for async counts and ``tc.materialize``
when a device build feeds a sharded mesh path, which repacks stores on the
host) inside it. ``TCResult.timings_s`` holds the host's time in each
stage under the same keys without the ``tc.`` prefix; for a stage that
only dispatches device work that is the dispatch, and the stage's device
time is in the trace.

``tcim_vertex_counts`` runs the same build, pool and pair chunks for the
per-vertex answer: T(v), the triangles through each vertex, and the local
clustering coefficient (LDBC Graphalytics LCC). Its pair chunks run
``Executor.vertex_counts_async`` in place of the count's execute, under
``tc.vertex``; ``tc.vertex.materialize`` is the inverse relabel and the
one readback of T, and ``tc.vertex.lcc`` the host's float64 LCC.

Backends for the execute stage (mapped onto Executor modes):
    'pallas_total'   fused gather–AND–popcount executor (default; the TCIM
                     device — indices travel, slice stores stay put)
    'pallas_unfused' legacy XLA-gather + reduction kernel (the unfused
                     baseline benchmarks compare the fused path against)
    'pallas_items'   per-pair Pallas kernel (debuggable)
    'jnp'            pure-jnp oracle path (lax.population_count)
    'bitgemm'        blocked popcount-GEMM over the dense bitpacked matrix
    'mxu'            beyond-paper masked A @ A on the MXU (dense, small n)
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import build as build_mod
from repro.core import sbf as sbf_mod
from repro.core.bitmat import bitpack_matrix
from repro.core.executor import (
    VERTEX_EXECUTE_IMPL,
    VERTEX_IMPL,
    CountFuture,
    ExecutorPool,
)
from repro.core.plan import SCHEDULES, DeviceTopology, plan_execution
from repro.core.streaming import (  # noqa: F401  (re-exported: streaming API)
    DeltaResult,
    StreamingTCState,
    tcim_count_delta,
)
from repro.graphs.csr import Graph, build_graph, degree_relabel
from repro.kernels import ops
from repro.runtime.spans import next_count_id, span

__all__ = [
    "TCResult",
    "TCFuture",
    "TCVertexResult",
    "tcim_count",
    "tcim_count_graph",
    "tcim_vertex_counts",
    "tcim_count_delta",
    "StreamingTCState",
    "DeltaResult",
    "default_executor_pool",
    "BACKENDS",
    "BUILDS",
]

# One-shot API calls route through a shared pool keyed by store *content*,
# so recounting a graph skips the store upload even though each call builds
# a fresh SBF, and same-bucket graphs share traces. LRU-bounded: up to
# max_graphs recently-counted graphs keep their (pow2-padded) stores
# device-resident after the call returns — call default_executor_pool()
# .clear() to release them, or pass pool= to manage lifetimes yourself.
_DEFAULT_POOL = ExecutorPool(max_graphs=4)


def default_executor_pool() -> ExecutorPool:  # tclint: export-ok(user-facing accessor for pool lifetime management, documented above)
    """The module-level pool behind ``tcim_count*(pool=None)``."""
    return _DEFAULT_POOL

BACKENDS = ("pallas_total", "pallas_unfused", "pallas_items", "jnp", "bitgemm", "mxu")

# Build front ends for the orient/compress/schedule stages. "auto" resolves
# at call time: the jitted device build on accelerator backends (where the
# host NumPy front end would serialize against dispatched execute work),
# the NumPy reference on CPU and for every path that needs host arrays.
BUILDS = ("auto", "host", "device")

# User-facing backend -> Executor mode for the work-list execute stage.
_EXECUTOR_MODE = {
    "pallas_total": "fused",
    "pallas_unfused": "gather_then_kernel",
    "pallas_items": "pallas_items",
    "jnp": "jnp",
}


@dataclasses.dataclass
class TCResult:
    triangles: int
    backend: str
    stats: dict
    # Stage -> the host's seconds in it (the dispatch, for a stage that
    # only enqueues device work); the device's time is in the trace.
    timings_s: dict

    def __repr__(self) -> str:  # compact, log-friendly
        t = ", ".join(f"{k}={v:.4f}" for k, v in self.timings_s.items())
        return f"TCResult(triangles={self.triangles}, backend={self.backend}, {t})"


@dataclasses.dataclass(repr=False)
class TCVertexResult(TCResult):
    """A per-vertex count (``tcim_vertex_counts``), in the caller's ids."""

    vertex_triangles: np.ndarray  # int64 [n]: T(v), triangles through v
    lcc: np.ndarray  # float64 [n]: T(v) / (d(v)(d(v)-1)/2), 0 if d(v) < 2


class TCFuture:
    """A dispatched count whose ``TCResult`` is deferred to ``result()``.

    ``tcim_count*(async_=True)`` returns one of these with every device step
    already enqueued; ``result()`` performs the single host readback (adding
    its wall-clock as ``timings_s['close']``, under a ``tc.close`` span in a
    ``tc.count`` span that carries the dispatching call's ``count_id``) and
    caches the ``TCResult``. Fleet callers overlap graph i's close with
    graph i+1's build and dispatch. ``stats`` and ``timings_s`` are readable
    before the close.
    """

    def __init__(self, future: CountFuture, backend: str, stats: dict, timings_s: dict):
        self._future = future
        self.backend = backend
        self.stats = stats
        self.timings_s = timings_s
        self.count_id = 0
        self._result: TCResult | None = None

    def result(self) -> TCResult:
        if self._result is None:
            with span("tc.count", count_id=self.count_id), span(
                "tc.close", self.timings_s, "close"
            ):
                triangles = self._future.result()
            self._result = TCResult(
                triangles, self.backend, self.stats, self.timings_s
            )
        return self._result


def _resolve_build(build: str, backend: str, mesh, m: int) -> str:
    """Pick the build front end (see ``BUILDS``).

    Dense backends (bitgemm/mxu) and empty graphs have nothing to build on
    device; they always take the host path regardless of the request.
    """
    if build not in BUILDS:
        raise ValueError(f"build {build!r} not in {BUILDS}")
    if backend not in _EXECUTOR_MODE or m == 0:
        return "host"
    if build == "auto":
        return "device" if mesh is None and jax.default_backend() != "cpu" else "host"
    return build


def _try_device_build(make_build, build: str):
    """Run a device build; under ``build='auto'`` fall back to the host
    front end only when the device build refuses the graph's size
    (``DeviceCapacityError``: int32 index space) instead of crashing a
    request that never pinned the build. Every other error — a kernel the
    compiler refuses, a device fault — surfaces, and an explicit
    ``build='device'`` raises the capacity error too."""
    try:
        return make_build()
    except build_mod.DeviceCapacityError:
        if build != "auto":
            raise
        return None


def _plan_execute(
    sb: sbf_mod.SlicedBitmap,
    wl: sbf_mod.Worklist,
    chunk_pairs: int,
    placement: str,
    mesh,
):
    """Resolve ``placement`` against the device topology (the mesh's, when
    given) and plan the execute stage."""
    grid = None
    if mesh is not None:
        topo = DeviceTopology(
            num_devices=int(np.prod(mesh.devices.shape)),
            platform=mesh.devices.reshape(-1)[0].platform,
        )
        if mesh.devices.ndim == 2:
            grid = tuple(int(x) for x in mesh.devices.shape)
    else:
        # Without a mesh there is nothing to shard over, so "auto" must
        # resolve to replicated regardless of how many devices exist —
        # only an *explicit* sharded request errors below.
        topo = DeviceTopology(num_devices=1)
    if placement == "sharded_2d" and grid is None:
        raise ValueError(
            "placement 'sharded_2d' needs a 2-axis mesh= "
            "(e.g. jax.make_mesh((4, 2), ('r', 'c'))) to place the "
            "(row_shard, col_shard) owner grid on"
        )
    return plan_execution(
        sb, wl, topo, placement=placement, chunk_pairs=chunk_pairs, grid=grid
    )


def _dispatch_plan(
    sb: sbf_mod.SlicedBitmap,
    wl: sbf_mod.Worklist,
    plan,
    backend: str,
    chunk_pairs: int,
    mesh,
    pool: ExecutorPool | None,
    schedule: str,
) -> tuple[CountFuture, str]:
    """Dispatch the planned execute stage; defer the host readback.

    Dispatches on a pooled replicated Executor, the column-sharded
    distributed path, or the 2-D owner-grid path — every branch returns
    with its steps enqueued and the close deferred to the future. Returns
    (future, execute implementation). Every mesh path runs the fused jnp
    mirror inside shard_map.
    """
    if plan.placement == "sharded_2d":
        # Imported here: core stays importable without the distributed layer.
        from repro.distributed.tc import pooled_sharded_2d_executor

        ex = pooled_sharded_2d_executor(
            sb, mesh, plan, chunk_pairs=chunk_pairs, schedule=schedule
        )
        # count(wl, plan) falls back to the pooled executor's resident
        # bounds when the fresh plan's ranges differ — no store re-upload.
        return ex.count_async(wl, plan), ex.execute_impl
    if plan.placement == "sharded_cols":
        if mesh is None:
            raise ValueError(
                "placement 'sharded_cols' needs a mesh= (jax.sharding.Mesh) "
                "to shard the column store over"
            )
        from repro.distributed.tc import pooled_sharded_executor

        ex = pooled_sharded_executor(
            sb, mesh, chunk_pairs=chunk_pairs, schedule=schedule
        )
        return ex.count_plan_async(plan), ex.execute_impl
    if mesh is not None and mesh.devices.size > 1:
        # Replicated over a real mesh: stores on every device, work-list
        # stripes dealt across it, scalar psum close. Runs the fused jnp
        # mirror inside shard_map, so `backend` does not apply here.
        from repro.distributed.tc import (
            MESH_EXECUTE_IMPL,
            distributed_tc_count_async,
        )

        fut = distributed_tc_count_async(
            sb, wl, mesh, max_step_pairs=plan.chunk_pairs
        )
        return fut, MESH_EXECUTE_IMPL
    ex = _pooled_executor(sb, backend, chunk_pairs, pool)
    return ex.count_async(wl), ex.execute_impl


def _pooled_executor(sb: sbf_mod.SlicedBitmap, backend: str, chunk_pairs: int,
                     pool: ExecutorPool | None):
    """The replicated Executor for ``sb`` from ``pool`` (or the module's),
    store adoption included, under the ``tc.execute.pool`` span."""
    with span("tc.execute.pool"):
        # NOT `pool or ...`: an empty ExecutorPool is falsy (it has __len__).
        return (pool if pool is not None else _DEFAULT_POOL).get(
            sb, mode=_EXECUTOR_MODE[backend], chunk_pairs=chunk_pairs
        )


def _execute_bitgemm(g: Graph, chunk_rows: int = 2048) -> int:
    """Whole-matrix popcount-GEMM path (dense bitpacked operands)."""
    a_up = g.dense_upper()
    x = jnp.asarray(bitpack_matrix(a_up))  # rows of A
    y = jnp.asarray(bitpack_matrix(a_up.T))  # columns of A as rows
    total = 0
    src = g.edges[:, 0]
    dst = g.edges[:, 1]
    for start in range(0, g.n, chunk_rows):
        stop = min(start + chunk_rows, g.n)
        b = ops.bitgemm(x[start:stop], y)  # [rows, n] counts
        sel = (src >= start) & (src < stop)
        if sel.any():
            total += int(
                np.asarray(b)[src[sel] - start, dst[sel]].astype(np.int64).sum()
            )
    return total


def _finish_host(
    g,
    sb: sbf_mod.SlicedBitmap,
    wl: sbf_mod.Worklist,
    *,
    backend: str,
    chunk_pairs: int,
    collect_stats: bool,
    placement: str,
    mesh,
    pool: ExecutorPool | None,
    schedule: str,
    timings: dict,
    build_label: str,
    async_: bool,
    resilience=None,
) -> TCResult | TCFuture:
    """Plan + execute a host-array (sbf, worklist) pair; close per async_."""
    if resilience is not None:
        # Checkpointed, elastic execution (distributed.resilient): commits
        # are synchronous readbacks, so the count closes eagerly and
        # async_=True hands back an already-resolved future.
        if mesh is None or mesh.devices.ndim != 2:
            raise ValueError(
                "resilience= runs the sharded_2d placement and needs a "
                "2-axis mesh= (e.g. jax.make_mesh((4, 2), ('r', 'c')))"
            )
        if placement not in ("auto", "sharded_2d"):
            raise ValueError(
                f"resilience= implies placement 'sharded_2d', got "
                f"{placement!r}"
            )
        from repro.distributed.resilient import resilient_tc_count
        from repro.distributed.tc import MESH_EXECUTE_IMPL

        with span("tc.execute", timings, "execute"):
            triangles, rinfo = resilient_tc_count(
                sb, wl, mesh, resilience, chunk_pairs=chunk_pairs,
                schedule=schedule,
            )
        if "step_ewma_s" in rinfo:
            timings["step_ewma_s"] = rinfo["step_ewma_s"]
        stats = (
            sbf_mod.sbf_stats(g, sb, wl)
            if collect_stats
            else {"n": g.n, "m": g.m}
        )
        stats["placement"] = "sharded_2d"
        stats["build"] = build_label
        stats["execute_impl"] = MESH_EXECUTE_IMPL
        stats["recovery"] = rinfo
        res = TCResult(triangles, backend, stats, timings)
        if async_:
            fut = TCFuture(CountFuture([triangles]), backend, stats, timings)
            fut._result = res
            return fut
        return res
    with span("tc.plan", timings, "plan"):
        plan = _plan_execute(sb, wl, chunk_pairs, placement, mesh)
    stats = sbf_mod.sbf_stats(g, sb, wl) if collect_stats else {"n": g.n, "m": g.m}
    stats["placement"] = plan.placement
    stats["build"] = build_label
    with span("tc.execute", timings, "execute"):
        fut, stats["execute_impl"] = _dispatch_plan(
            sb, wl, plan, backend, chunk_pairs, mesh, pool, schedule
        )
        if async_:
            return TCFuture(fut, backend, stats, timings)
        triangles = fut.result()
    return TCResult(triangles, backend, stats, timings)


def _finish_device(
    db: build_mod.DeviceBuild,
    *,
    backend: str,
    chunk_pairs: int,
    collect_stats: bool,
    placement: str,
    mesh,
    pool: ExecutorPool | None,
    schedule: str,
    timings: dict,
    async_: bool,
    resilience=None,
) -> TCResult | TCFuture:
    """Execute a device build: fully resident when replicated, else
    materialized to the host for the sharded/mesh paths (which repack
    stores per shard on the host anyway)."""
    timings.update(db.timings_s)
    if resilience is None and mesh is None and placement in ("auto", "replicated"):
        # Single-device replicated: one stripe, nothing to owner-group —
        # the plan stage is trivial, and skipping the planner keeps the
        # worklist arrays on device (plan_execution needs host arrays).
        timings["plan"] = 0.0
        stats = (
            sbf_mod.sbf_stats(db.graph, db.sbf, db.worklist)
            if collect_stats
            else {"n": db.graph.n, "m": db.graph.m}
        )
        stats["placement"] = "replicated"
        stats["build"] = "device"
        stats["search_steps"] = db.worklist.search_steps
        with span("tc.execute", timings, "execute"):
            ex = _pooled_executor(db.sbf, backend, chunk_pairs, pool)
            stats["execute_impl"] = ex.execute_impl
            fut = ex.count_async(db.worklist)
            if async_:
                return TCFuture(fut, backend, stats, timings)
            triangles = fut.result()
        return TCResult(triangles, backend, stats, timings)
    with span("tc.materialize", timings, "materialize"):
        sb, wl = db.to_host()
    return _finish_host(
        db.graph, sb, wl,
        backend=backend, chunk_pairs=chunk_pairs, collect_stats=collect_stats,
        placement=placement, mesh=mesh, pool=pool, schedule=schedule,
        timings=timings, build_label="device", async_=async_,
        resilience=resilience,
    )


def _counted(fn):
    """Run each call of ``fn`` under one ``tc.count`` span whose
    ``count_id`` a returned ``TCFuture`` keeps for its close."""

    @functools.wraps(fn)
    def counted(*args, **kwargs):
        count_id = next_count_id()
        with span("tc.count", count_id=count_id):
            res = fn(*args, **kwargs)
        if isinstance(res, TCFuture):
            res.count_id = count_id
        return res

    return counted


@_counted
def tcim_count_graph(
    g: Graph,
    *,
    slice_bits: int = 64,
    backend: str = "pallas_total",
    chunk_pairs: int = 1 << 20,
    collect_stats: bool = True,
    placement: str = "auto",
    mesh=None,
    pool: ExecutorPool | None = None,
    schedule: str = "packed",
    build: str = "auto",
    async_: bool = False,
    resilience=None,
) -> TCResult | TCFuture:
    """Count triangles of a prebuilt (oriented) Graph.

    ``resilience`` (a ``repro.distributed.ResilienceConfig``) routes the
    execute stage through the checkpointed elastic driver
    (``distributed.resilient.resilient_tc_count``): the count commits a
    resume cursor every ``checkpoint_every`` psum steps and survives
    device loss by shrinking the mesh and resuming the uncounted pairs —
    bit-identically. Requires a 2-axis ``mesh`` (the sharded_2d
    placement); ``stats['recovery']`` reports attempts/failures/replays,
    and a configured ``StragglerMonitor``'s per-step EWMA lands in
    ``timings_s['step_ewma_s']``.

    ``placement`` routes the execute stage through ``core.plan``:
    ``'replicated'`` (stores on every device, pooled Executor),
    ``'sharded_cols'`` (column store NamedSharding-sharded over ``mesh``;
    requires ``mesh``), ``'sharded_2d'`` (BOTH stores sharded over a 2-axis
    ``mesh`` with pair-count-weighted ranges; requires a 2-axis mesh), or
    ``'auto'`` (planner decides from store size and topology; single-device
    stays replicated, 2-axis meshes prefer 2-D). Every mesh path (sharded, or
    replicated with a multi-device mesh — the latter deals work-list stripes
    across the mesh via ``distributed_tc_count``) runs the fused jnp mirror
    inside shard_map, so ``backend`` selects the Executor mode only for the
    single-device replicated path; ``chunk_pairs`` bounds per-step work
    everywhere. ``pool`` overrides the module-level
    ExecutorPool for fleets managing their own executor lifetimes (the
    default pool keeps recent graphs' stores device-resident; see
    ``default_executor_pool``, and
    ``repro.distributed.clear_sharded_executor_cache`` for the sharded
    analogue). ``schedule`` picks the sharded paths' stripe scheduling
    policy — ``'packed'`` (default; per-shard window cursors, fewer psum
    steps on imbalanced fixed-bounds replans) or ``'lockstep'`` (the legacy
    shared-window baseline); single-stripe replicated execution is
    unaffected. Counts are bit-identical across policies.

    ``build`` selects the orient/compress/schedule front end: ``'host'``
    (the NumPy reference), ``'device'`` (``core.build``: jit-compiled,
    bit-identical, one host->device transfer, arrays device-resident
    through the execute stage on the single-device replicated path), or
    ``'auto'`` (device on accelerator backends without a mesh, host
    otherwise). Sharded and mesh paths materialize a device build back to
    the host (they repack stores per shard there; ``timings_s`` records it
    as ``materialize``); dense backends always build on host.
    ``async_=True`` returns a ``TCFuture`` with every step dispatched and
    the host readback deferred to ``result()`` — every placement serves
    fleets non-blocking.
    """
    if backend not in BACKENDS:
        raise ValueError(f"backend {backend!r} not in {BACKENDS}")
    if schedule not in SCHEDULES:
        raise ValueError(f"schedule {schedule!r} not in {SCHEDULES}")
    timings: dict[str, float] = {}

    if backend in ("bitgemm", "mxu"):
        _resolve_build(build, backend, mesh, g.m)  # validates the request
        with span("tc.execute", timings, "execute"):
            if backend == "mxu":
                count = int(ops.dense_mxu_tc(jnp.asarray(g.dense_upper())))
            else:
                count = _execute_bitgemm(g)
        res = TCResult(count, backend, {"n": g.n, "m": g.m}, timings)
        if async_:  # dense paths close eagerly; hand back a resolved future
            fut = TCFuture(CountFuture([count]), backend, res.stats, timings)
            fut._result = res
            return fut
        return res

    if _resolve_build(build, backend, mesh, g.m) == "device":
        db = _try_device_build(
            lambda: build_mod.device_build_graph(g, slice_bits), build
        )
        if db is not None:
            return _finish_device(
                db,
                backend=backend, chunk_pairs=chunk_pairs,
                collect_stats=collect_stats, placement=placement, mesh=mesh,
                pool=pool, schedule=schedule, timings=timings, async_=async_,
                resilience=resilience,
            )
        timings = {}  # auto fell back: restart stage timings on the host path

    with span("tc.compress", timings, "compress"):
        sb = sbf_mod.build_sbf(g, slice_bits)
    with span("tc.schedule", timings, "schedule"):
        wl = sbf_mod.build_worklist(g, sb)

    return _finish_host(
        g, sb, wl,
        backend=backend, chunk_pairs=chunk_pairs, collect_stats=collect_stats,
        placement=placement, mesh=mesh, pool=pool, schedule=schedule,
        timings=timings, build_label="host", async_=async_,
        resilience=resilience,
    )


@_counted
def tcim_count(
    edges: np.ndarray,
    *,
    n: int | None = None,
    slice_bits: int = 64,
    backend: str = "pallas_total",
    reorder: bool = True,
    chunk_pairs: int = 1 << 20,
    collect_stats: bool = True,
    placement: str = "auto",
    mesh=None,
    pool: ExecutorPool | None = None,
    schedule: str = "packed",
    build: str = "auto",
    async_: bool = False,
    resilience=None,
) -> TCResult | TCFuture:
    """End-to-end triangle count from a canonical undirected edge list.

    With ``build='device'`` (or ``'auto'`` on an accelerator) the edge list
    is the ONE host->device transfer: orientation (including the optional
    degree relabel), SBF compression and worklist construction all run as
    jit-compiled device work, and on the single-device replicated path the
    resulting stores and index arrays feed the executor without ever
    returning to the host. See ``tcim_count_graph`` for the remaining
    parameters.
    """
    if backend not in BACKENDS:
        raise ValueError(f"backend {backend!r} not in {BACKENDS}")
    if schedule not in SCHEDULES:
        raise ValueError(f"schedule {schedule!r} not in {SCHEDULES}")
    if _resolve_build(build, backend, mesh, len(edges)) == "device":
        db = _try_device_build(
            lambda: build_mod.device_build(
                edges, n=n, slice_bits=slice_bits, reorder=reorder
            ),
            build,
        )
        if db is not None:
            return _finish_device(
                db,
                backend=backend, chunk_pairs=chunk_pairs,
                collect_stats=collect_stats, placement=placement, mesh=mesh,
                pool=pool, schedule=schedule, timings={}, async_=async_,
                resilience=resilience,
            )
    orient: dict[str, float] = {}
    with span("tc.orient", orient, "orient"):
        g = build_graph(edges, n=n, reorder=reorder)
    # The undecorated body: this call is already inside its tc.count span.
    res = tcim_count_graph.__wrapped__(
        g,
        slice_bits=slice_bits,
        backend=backend,
        chunk_pairs=chunk_pairs,
        collect_stats=collect_stats,
        placement=placement,
        mesh=mesh,
        pool=pool,
        schedule=schedule,
        build="host",
        async_=async_,
        resilience=resilience,
    )
    res.timings_s = {**orient, **res.timings_s}
    return res


@_counted
def tcim_vertex_counts(
    edges: np.ndarray,
    *,
    n: int | None = None,
    chunk_pairs: int = 1 << 20,
    pool: ExecutorPool | None = None,
    build: str = "auto",
) -> TCVertexResult:
    """Per-vertex triangle counts and local clustering of a canonical
    undirected edge list, on the device.

    Builds as ``tcim_count`` does (degree relabel, 64-bit SBF, worklist;
    ``build`` as there, a ``DeviceCapacityError`` under ``'auto'`` answered
    by the host build), takes the replicated executor of ``pool`` (the
    module's by default) with ``chunk_pairs``-pair chunks, and runs each
    pair chunk through ``Executor.vertex_counts_async``: the pair's AND
    words and their attribution to its three vertices stay on the device,
    in int32, read back once. Returns a ``TCVertexResult``: the exact
    total, ``vertex_triangles`` (int64 [n]) and ``lcc`` (float64 [n],
    computed on the host: the TPU has no float64), both in the caller's
    vertex ids. ``stats`` adds ``vertex_impl``, ``vertex_pairs`` (pairs
    attributed), ``vertex_nonzero_pairs`` (pairs with a non-zero AND
    word) and, for a device build, ``search_steps``. Raises
    ``OverflowError`` past 3 x total > int32.
    """
    edges = np.asarray(edges)
    if n is None:
        n = int(edges.max()) + 1 if len(edges) else 0
    timings: dict[str, float] = {}
    db = None
    if _resolve_build(build, "pallas_total", None, len(edges)) == "device":
        db = _try_device_build(
            lambda: build_mod.device_build(edges, n=n), build,
        )
    if db is not None:
        timings.update(db.timings_s)
        g, sb, wl = db.graph, db.sbf, db.worklist
        src, dst, new_id = g.src, g.dst, g.new_id
    else:
        with span("tc.orient", timings, "orient"):
            g = build_graph(edges, n=n, reorder=True)
            new_id = degree_relabel(edges, n)
        with span("tc.compress", timings, "compress"):
            sb = sbf_mod.build_sbf(g, 64)
        with span("tc.schedule", timings, "schedule"):
            wl = sbf_mod.build_worklist(g, sb)
        src, dst = g.edges[:, 0], g.edges[:, 1]
    stats = sbf_mod.sbf_stats(g, sb, wl)
    stats.update(placement="replicated", build="device" if db else "host",
                 execute_impl=VERTEX_EXECUTE_IMPL, vertex_impl=VERTEX_IMPL)
    if db is not None:
        stats["search_steps"] = wl.search_steps
    with span("tc.vertex", timings, "vertex"):
        ex = _pooled_executor(sb, "pallas_total", chunk_pairs, pool)
        fut = ex.vertex_counts_async(wl, src, dst, sb.row_slice_idx, n)
        # The host's share, while the device attributes.
        deg = np.bincount(edges.reshape(-1), minlength=n).astype(np.int64)
    with span("tc.vertex.materialize", timings, "vertex.materialize"):
        total, counts, nonzero = fut.result(new_id)
    with span("tc.vertex.lcc", timings, "vertex.lcc"):
        wedges = deg * (deg - 1) // 2
        lcc = np.zeros(n, dtype=np.float64)
        np.divide(counts, wedges, out=lcc, where=wedges > 0)
    stats.update(vertex_pairs=wl.num_pairs, vertex_nonzero_pairs=nonzero)
    return TCVertexResult(total, "vertex", stats, timings, counts, lcc)
