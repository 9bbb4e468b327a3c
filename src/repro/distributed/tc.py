"""Distributed TCIM: shard the work list across the mesh, psum one scalar.

TCIM's reduction is a commutative monoid (integer +), so the parallelization
is embarrassing at slice-pair granularity: every device owns a contiguous
stripe of the work list, gathers its slice words, runs the AND+BitCount
kernel locally, and a single scalar ``psum`` closes the computation. This is
also why the engine is elastic- and straggler-friendly (runtime/elastic.py):
work stripes can be re-dealt to any surviving device set without touching
the slice data.

Slice data placement (chosen by ``core.plan.plan_execution``):
  * ``replicated``  (default) — row/col slice stores live on every device;
    right for graphs up to a few GB of SBF (all SNAP-class graphs: Table III
    tops out at 16.8 MB) and removes all communication except the final psum.
  * ``sharded_cols`` — the column store is genuinely ``NamedSharding``-
    sharded over the mesh (contiguous row ranges, dim 0 split across every
    axis); the row store stays replicated. The planner owner-groups the work
    list so each pair executes on the shard holding its column slice with
    *shard-local* indices — no per-step all-gather of column data, only each
    shard's own index stripe travels, and a single scalar psum still closes
    every step. ``ShardedColsExecutor`` is the device-resident unit: one
    Executor's worth of state (store shard + traced step + stripe schedule)
    per mesh device. For graphs whose SBF exceeds one device's HBM.
  * ``sharded_2d`` — BOTH stores sharded over a 2-axis mesh: device
    ``(i, j)`` holds row-store range ``i`` (sharded over the first mesh
    axis, replicated over the second) and column-store range ``j`` (the
    transpose). The planner routes every pair to its ``(row_shard,
    col_shard)`` owner block with block-local coordinates on both axes and
    balances the ranges by *pair count* (weighted split), so per-block work
    stays near-uniform even on degree-ordered graphs. The placement that
    lets row stores exceed one device's memory; ``Sharded2DExecutor`` is
    the device-resident unit, reusing the replicated Executor's pow2 step
    buckets and double-buffered index staging.

Both sharded executors run their owner stripes through
``core.plan.StripeSchedule`` (see ``_StripeScheduleDriver``): ``packed``
per-shard window cursors by default — drained shards stop consuming the
per-step pair budget, so imbalanced fixed-bounds replans take
``~ceil(total/budget)`` psum steps instead of lockstep's
``ceil(longest * num_shards / budget)`` — with the legacy ``lockstep``
policy kept as the benchmark/CI baseline. ``count*_async`` variants defer
the final host readback behind a ``CountFuture`` so fleet serving overlaps
graph i's close with graph i+1's stripe assembly.
"""
from __future__ import annotations

import collections

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.shard_map import shard_map
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

from repro.core.executor import CountFuture, staged_uploads
from repro.core.plan import (
    SCHEDULES,
    DeviceTopology,
    ExecutionPlan,
    StripeSchedule,
    build_stripe_schedule,
    even_range_bounds,
    plan_execution,
    pow2_ceil as _pow2_ceil,
    shard_col_bounds,
)
from repro.core.sbf import SlicedBitmap, Worklist
from repro.kernels.ops import INT32_SAFE_WORDS
from repro.kernels.tc_gather_popcount import gather_total_reference
from repro.runtime.contracts import no_host_sync
from repro.runtime.fault import CountInterrupted

__all__ = [
    "shard_worklist",
    "distributed_tc_count",
    "distributed_tc_count_async",
    "make_tc_step",
    "ShardedColsExecutor",
    "Sharded2DExecutor",
    "pooled_sharded_executor",
    "pooled_sharded_2d_executor",
    "clear_sharded_executor_cache",
    "TC_PLACEMENTS",
]

TC_PLACEMENTS = ("replicated", "sharded_cols", "sharded_2d")

# What every mesh step runs per device inside shard_map: the fused jnp
# mirror (``gather_total_reference``), whatever the single-device backend.
MESH_EXECUTE_IMPL = "jnp_mirror"


def _auto_axes(mesh: Mesh) -> Mesh:
    """``mesh`` with every axis ``Auto``: same devices, same names.

    ``jax.make_mesh`` builds ``Explicit`` axes, which make a store's
    sharding part of its type; the streaming store scatter
    (``core.executor.apply_store_lanes``) then cannot infer its gather's
    output sharding. The sharded executors place every array themselves
    through ``NamedSharding``, so they run on the Auto view of the mesh.
    """
    if all(t == AxisType.Auto for t in mesh.axis_types):
        return mesh
    return Mesh(
        mesh.devices, mesh.axis_names,
        axis_types=(AxisType.Auto,) * mesh.devices.ndim,
    )


def shard_worklist(wl: Worklist, num_shards: int) -> tuple[np.ndarray, np.ndarray]:
    """Pad the pair index arrays to a multiple of num_shards and stack.

    Padding points at record 0 on both sides with a sentinel weight of zero —
    implemented by masking in the step function, so padded lanes are exact
    no-ops regardless of what record 0 holds.
    Returns (row_pos [S, ppd], col_pos [S, ppd]) int32 plus an implicit mask
    encoded as negative indices.
    """
    p = wl.num_pairs
    per = -(-max(p, 1) // num_shards)
    total = per * num_shards
    row = np.full(total, -1, dtype=np.int32)
    col = np.full(total, -1, dtype=np.int32)
    row[:p] = wl.pair_row_pos.astype(np.int32)
    col[:p] = wl.pair_col_pos.astype(np.int32)
    return row.reshape(num_shards, per), col.reshape(num_shards, per)


def _local_count(row_data, col_data, row_idx, col_idx):
    """Per-device partial count: the executor's fused mirror (portable jnp).

    Shares ``gather_total_reference`` with core.executor — identical
    negative-index no-op contract, so ``shard_worklist`` padding composes
    with the fused execute semantics for free.
    """
    return gather_total_reference(row_data, col_data, row_idx, col_idx)


def make_tc_step(mesh: Mesh, axis_names: tuple[str, ...]):
    """Build the pjit'd distributed TC step for a mesh.

    Data layout: slice stores replicated; work-list stripes sharded over all
    mesh axes (flattened). Returns a function
    ``step(row_data, col_data, row_idx, col_idx) -> total (replicated)``.
    """
    flat = P(axis_names)  # leading dim sharded over every axis

    def tc_mesh_replicated_step(row_data, col_data, row_idx, col_idx):
        def local(row_data, col_data, r, c):
            # r, c: this device's stripe of the flat work list.
            partial = _local_count(row_data, col_data, r, c)
            return jax.lax.psum(partial[None], axis_names)

        return shard_map(
            local,
            mesh=mesh,
            in_specs=(P(), P(), flat, flat),
            out_specs=P(),
        )(row_data, col_data, row_idx, col_idx)[0]

    return jax.jit(
        tc_mesh_replicated_step,
        in_shardings=(
            NamedSharding(mesh, P()),
            NamedSharding(mesh, P()),
            NamedSharding(mesh, flat),
            NamedSharding(mesh, flat),
        ),
        out_shardings=NamedSharding(mesh, P()),
    )


def make_sharded_cols_step(mesh: Mesh, axis_names: tuple[str, ...]):
    """The pjit'd step for ``sharded_cols`` placement.

    Data layout: row store replicated; column store's dim 0 sharded over
    every mesh axis (each device holds one contiguous block of column
    slices); index stripes sharded the same flat way, with *block-local*
    column positions. Inside shard_map every device runs the fused mirror
    against only its resident column block — no all-gather — and one scalar
    psum closes the step.
    """
    flat = P(axis_names)
    col_spec = P(axis_names, None)

    def tc_mesh_cols_step(row_data, col_block, row_idx, col_idx):
        def local(row_data, col_block, r, c):
            partial = gather_total_reference(row_data, col_block, r, c)
            return jax.lax.psum(partial[None], axis_names)

        return shard_map(
            local,
            mesh=mesh,
            in_specs=(P(), col_spec, flat, flat),
            out_specs=P(),
        )(row_data, col_block, row_idx, col_idx)[0]

    return jax.jit(
        tc_mesh_cols_step,
        in_shardings=(
            NamedSharding(mesh, P()),
            NamedSharding(mesh, col_spec),
            NamedSharding(mesh, flat),
            NamedSharding(mesh, flat),
        ),
        out_shardings=NamedSharding(mesh, P()),
    )


class _StripeScheduleDriver:
    """Shared sharded execute driver: schedule -> staged uploads -> close.

    Both sharded executors hold NamedSharding-resident ``row_store`` /
    ``col_store`` arrays, a traced ``_step``, and plan validation
    (``_check_plan``); this mixin owns everything placement-independent:

    * **Stripe scheduling.** ``count_plan*`` runs the plan's owner stripes
      through ``core.plan.build_stripe_schedule`` under the executor's
      ``schedule`` policy (``packed`` by default — per-shard cursors, so a
      drained shard stops consuming the step budget; ``lockstep`` keeps the
      legacy shared-window baseline). The step budget is the caller's
      memory bound AND the int32 psum bound: ``min(plan.chunk_pairs,
      INT32_SAFE_WORDS // words_per_slice)`` **real pairs per step** —
      NOT per shard, so a step never stages ``num_shards`` times the
      caller's bound the way the pre-schedule driver did.
    * **Async close.** ``count_plan_async`` returns a ``CountFuture`` with
      every psum step dispatched through double-buffered index staging;
      the final host readback happens at ``result()``, so fleet callers
      overlap graph i's close with graph i+1's stripe assembly.
    """

    execute_impl = MESH_EXECUTE_IMPL

    def _validate_int32_floor(self, noun: str, remedy: str) -> None:
        """Constructor guard: the packed scheduler's width-1 progress floor
        can put one pair from EVERY shard in a step, so even that worst
        case must fit the closing psum's int32 accumulator."""
        safe = INT32_SAFE_WORDS // max(self.words_per_slice, 1)
        if safe // self.num_shards < 1:
            raise ValueError(
                f"words_per_slice={self.words_per_slice} x {self.num_shards} "
                f"{noun} cannot give every {noun.rstrip('s')} even one "
                f"int32-safe pair per step (INT32_SAFE_WORDS="
                f"{INT32_SAFE_WORDS}); use a smaller slice_bits or {remedy}"
            )

    def stripe_schedule(self, plan: ExecutionPlan) -> StripeSchedule:
        """The schedule ``count_plan`` would run for this plan (inspectable:
        benchmarks and the CI gate read ``num_steps`` off it).

        The budget honors BOTH memory bounds — the plan's and the
        executor's own ``chunk_pairs`` (a caller-built plan may carry a
        larger chunk than this executor was configured for) — plus the
        int32 psum bound.
        """
        safe = INT32_SAFE_WORDS // max(self.words_per_slice, 1)
        budget = min(max(plan.chunk_pairs, 1), max(self.chunk_pairs, 1), safe)
        return build_stripe_schedule(
            [s.num_pairs for s in plan.stripes], budget, policy=self.schedule
        )

    def _staged_windows(
        self, sched: StripeSchedule, plan: ExecutionPlan, start_step: int = 0
    ):
        """Double-buffered device index windows via the *compact* emission.

        ``StripeSchedule.emit_compact`` hands back per-shard rows, with
        every drained shard's all-sentinel row served from one shared
        cached buffer — so once a shard's stripe is exhausted its rows are
        never re-filled or re-copied host-side again (the budget-aware
        packed-width fix; ``staged_lanes`` vs ``total_lanes`` quantifies
        it, gated in CI). Each device then materializes its own row through
        ``jax.make_array_from_callback`` under the same flat sharding the
        dense ``device_put`` used — bit-identical step inputs.
        """
        flat = NamedSharding(self.mesh, P(self.axis_names))

        def put(step):
            bucket, row_rows, col_rows = step
            shape = (len(row_rows) * bucket,)

            def mk(rows):
                return jax.make_array_from_callback(
                    shape,
                    flat,
                    lambda idx: rows[(idx[0].start or 0) // bucket],
                )

            return mk(row_rows), mk(col_rows)

        return staged_uploads(
            sched.emit_compact(plan.stripes, start_step),
            put,
            double_buffer=self.double_buffer,
        )

    @no_host_sync()
    def count_plan_async(self, plan: ExecutionPlan) -> CountFuture:
        """Dispatch every scheduled psum step; defer the exact host sum.

        Contract (``TCIM_CONTRACTS=1``): the step loop stages windows and
        enqueues psum steps without ever reading a device scalar back — the
        one host sync is the ``CountFuture`` close (or, on the resumable
        path, its periodic cursor commits).
        """
        self._check_plan(plan)
        sched = self.stripe_schedule(plan)
        if sched.num_steps == 0:
            return CountFuture([])  # empty worklist: nothing dispatched
        staged = self._staged_windows(sched, plan)
        return CountFuture(
            [
                self._step(self.row_store, self.col_store, ridx, cidx)
                for ridx, cidx in staged
            ]
        )

    def count_plan(self, plan: ExecutionPlan) -> int:
        """Count an owner-grouped plan. One exact host sum at the end."""
        return self.count_plan_async(plan).result()

    def count_plan_resumable(
        self,
        plan: ExecutionPlan,
        *,
        checkpoint_every: int = 8,
        checkpointer=None,
        injector=None,
        monitor=None,
        monitor_interrupts: bool = False,
        start_step: int = 0,
        base_total: int = 0,
        attempt: int = 0,
    ) -> tuple[int, dict]:
        """The checkpointed step loop: every ``checkpoint_every`` psum steps
        the pending device scalars are read back, folded into the exact
        committed total, and the ``(shard_cursors, total)`` cursor is saved
        through ``checkpointer`` (async — file I/O overlaps the next steps).
        Any failure past that point surfaces as ``CountInterrupted``
        carrying the last committed cursor, so a resume replays at most
        ``checkpoint_every`` steps; replay is exact because uncommitted
        steps contributed nothing to the committed total (commutative
        integer monoid over disjoint pair windows).

        ``checkpointer`` is duck-typed (``distributed.resilient
        .TCCheckpoint``): ``save_snapshot`` persists the SBF stores +
        full worklist once per attempt, ``save_cursor`` the per-commit
        cursor. ``injector`` (``runtime.fault.FailureInjector``) hooks
        each dispatch; ``monitor`` (``StragglerMonitor``) makes the loop
        block per step to time it — observability costs the dispatch
        pipelining, so it is opt-in — and with ``monitor_interrupts`` a
        straggler flag commits and raises (reason ``"straggler"``) for
        the caller's checkpoint-and-remesh policy. ``start_step`` /
        ``base_total`` / ``attempt`` are the same-schedule resume inputs.

        Returns ``(total, info)``; ``info`` records steps, commits, and
        the step-time EWMA when monitored.
        """
        self._check_plan(plan)
        sched = self.stripe_schedule(plan)
        n = sched.num_steps
        if not 0 <= start_step <= n:
            raise ValueError(f"start_step must be in [0, {n}], got {start_step}")
        every = int(checkpoint_every) if checkpoint_every else 0
        if checkpointer is not None:
            checkpointer.save_snapshot(
                self._sbf, plan, attempt=attempt, base_total=base_total,
                schedule=self.schedule,
            )
        total = int(base_total)
        committed_step = start_step
        pending: list = []
        info: dict = {
            "steps": n,
            "start_step": start_step,
            "attempt": attempt,
            "checkpoints": 0,
        }

        def commit(upto: int) -> None:
            nonlocal total, committed_step
            if pending:
                # Small windows (the cadence path) read scalars one by one:
                # a jnp.stack over <= checkpoint_every scalars costs more in
                # dispatch than the transfers it batches. Big windows (no
                # cadence: one commit for the whole count) still stack.
                vals = (
                    # tclint: sync-ok(resumable cursor commit: the periodic exact fold)
                    np.asarray(jnp.stack(pending))
                    if len(pending) > 16
                    else pending
                )
                total += sum(int(v) for v in vals)
                pending.clear()
            committed_step = upto
            if checkpointer is not None:
                checkpointer.save_cursor(
                    attempt, upto, sched.cursor_after(upto), total, plan
                )
                info["checkpoints"] += 1

        staged = self._staged_windows(sched, plan, start_step)
        step_i = start_step
        try:
            for ridx, cidx in staged:
                if injector is not None:
                    injector.check(step_i)
                if monitor is not None:
                    monitor.start_step()
                t = self._step(self.row_store, self.col_store, ridx, cidx)
                pending.append(t)
                if monitor is not None:
                    jax.block_until_ready(t)
                    flagged = monitor.end_step()
                    ewma = getattr(monitor, "ewma", None)
                    if ewma is not None:
                        info["step_ewma_s"] = float(ewma)
                    if flagged:
                        info["straggler_flags"] = (
                            info.get("straggler_flags", 0) + 1
                        )
                    if flagged and monitor_interrupts:
                        # The flagged step finished — commit through it so
                        # the remesh replays nothing.
                        commit(step_i + 1)
                        raise CountInterrupted(
                            f"straggler flagged at step {step_i} of {n}",
                            failed_step=step_i + 1,
                            committed_step=committed_step,
                            committed_total=total,
                            shard_cursors=sched.cursor_after(committed_step),
                            reason="straggler",
                            attempt=attempt,
                        )
                step_i += 1
                if every and step_i < n and (step_i - start_step) % every == 0:
                    commit(step_i)
            commit(n)
        except CountInterrupted:
            raise
        except Exception as e:
            raise CountInterrupted(
                f"sharded count failed at step {step_i} of {n}: {e}",
                failed_step=step_i,
                committed_step=committed_step,
                committed_total=total,
                shard_cursors=sched.cursor_after(committed_step),
                reason="failure",
                attempt=attempt,
            ) from e
        return total, info

    def count_resumable(self, wl: Worklist, **kwargs) -> tuple[int, dict]:
        """``count_plan_resumable`` over a work list planned against this
        executor's resident store ranges."""
        return self.count_plan_resumable(self._plan(wl), **kwargs)

    def count_async(self, wl: Worklist) -> CountFuture:
        """``count`` with the final host readback deferred to ``result()``."""
        return self.count_plan_async(self._plan(wl))

    def count(self, wl: Worklist) -> int:
        """Count a work list against the executor's resident stores."""
        return self.count_async(wl).result()


class ShardedColsExecutor(_StripeScheduleDriver):
    """Device-resident ``sharded_cols`` execute stage for one mesh.

    One Executor's worth of state per column-store shard: the shard's block
    of column slices stays resident on its device (uploaded once, verifiably
    sharded — see ``col_store.sharding``), the row store is replicated, and
    the traced step is shared across counts. ``count`` schedules any work
    list through the planner's owner-grouped stripes under the ``schedule``
    policy (see ``_StripeScheduleDriver``); pow2 step buckets keep retraces
    bounded exactly like ``core.executor.Executor``.
    """

    def __init__(
        self,
        sbf: SlicedBitmap,
        mesh: Mesh,
        *,
        chunk_pairs: int = 1 << 20,
        double_buffer: bool = True,
        schedule: str = "packed",
    ):
        if schedule not in SCHEDULES:
            raise ValueError(f"schedule {schedule!r} not in {SCHEDULES}")
        self.schedule = schedule
        mesh = _auto_axes(mesh)
        self.mesh = mesh
        self.axis_names = tuple(mesh.axis_names)
        self.num_shards = int(np.prod(mesh.devices.shape))
        self.words_per_slice = int(sbf.words_per_slice)
        self.chunk_pairs = chunk_pairs
        self.double_buffer = double_buffer
        per, padded = shard_col_bounds(len(sbf.col_slice_idx), self.num_shards)
        self.col_shard_rows = per
        self.col_bounds = even_range_bounds(len(sbf.col_slice_idx), self.num_shards)
        # tclint: sync-ok(one-time shard repack at executor construction; ROADMAP: device-resident resharding)
        col = np.asarray(sbf.col_slice_data)
        if padded != col.shape[0]:
            col = np.concatenate(
                [col, np.zeros((padded - col.shape[0], col.shape[1]), col.dtype)]
            )
        # The actual sharded placement: dim 0 split over every mesh axis.
        self.col_store = jax.device_put(
            col, NamedSharding(mesh, P(self.axis_names, None))
        )
        self.row_store = jax.device_put(
            # tclint: sync-ok(one-time shard repack at executor construction; ROADMAP: device-resident resharding)
            np.asarray(sbf.row_slice_data), NamedSharding(mesh, P())
        )
        self._step = make_sharded_cols_step(mesh, self.axis_names)
        self._sbf = sbf
        self._validate_int32_floor("shards", "fewer shards")

    def _plan(self, wl: Worklist) -> ExecutionPlan:
        return plan_execution(
            self._sbf,
            wl,
            placement="sharded_cols",
            num_shards=self.num_shards,
            chunk_pairs=self.chunk_pairs,
        )

    def _check_plan(self, plan: ExecutionPlan) -> None:
        if plan.placement != "sharded_cols":
            raise ValueError(
                f"plan placement {plan.placement!r} is not 'sharded_cols'"
            )
        if plan.num_shards != self.num_shards:
            raise ValueError(
                f"plan has {plan.num_shards} shards, mesh has {self.num_shards}"
            )
        if plan.col_shard_rows != self.col_shard_rows or (
            plan.col_bounds is not None
            and not np.array_equal(plan.col_bounds, self.col_bounds)
        ):
            raise ValueError(
                "plan's shard-local coordinates assume different column "
                f"ranges (rows/shard {plan.col_shard_rows} vs "
                f"{self.col_shard_rows}); the plan was built for a different "
                "SBF, shard count, or split"
            )


def make_sharded_2d_step(mesh: Mesh, axis_names: tuple[str, ...]):
    """The pjit'd step for ``sharded_2d`` placement on a 2-axis mesh.

    Data layout: row store's dim 0 sharded over the FIRST mesh axis
    (replicated over the second), column store's dim 0 sharded over the
    SECOND axis (replicated over the first) — device ``(i, j)`` holds
    exactly row block ``i`` and col block ``j``. Index stripes are sharded
    over both axes flattened (stripe order is row-major ``i*C + j``, which
    is the mesh's device order), carrying *block-local* coordinates on both
    sides. Inside shard_map every device runs the fused mirror against only
    its resident blocks — owner-compute, no all-gather — and one scalar
    psum over both axes closes the step.
    """
    row_axis, col_axis = axis_names
    row_spec = P(row_axis, None)
    col_spec = P(col_axis, None)
    flat = P(axis_names)

    def tc_mesh_2d_step(row_block, col_block, row_idx, col_idx):
        def local(row_block, col_block, r, c):
            partial = gather_total_reference(row_block, col_block, r, c)
            return jax.lax.psum(partial[None], axis_names)

        return shard_map(
            local,
            mesh=mesh,
            in_specs=(row_spec, col_spec, flat, flat),
            out_specs=P(),
        )(row_block, col_block, row_idx, col_idx)[0]

    return jax.jit(
        tc_mesh_2d_step,
        in_shardings=(
            NamedSharding(mesh, row_spec),
            NamedSharding(mesh, col_spec),
            NamedSharding(mesh, flat),
            NamedSharding(mesh, flat),
        ),
        out_shardings=NamedSharding(mesh, P()),
    )


def _range_block_store(
    store: np.ndarray, bounds: np.ndarray, block_rows: int
) -> np.ndarray:
    """Repack contiguous ranges into equal zero-padded blocks.

    Block ``s`` holds ``store[bounds[s]:bounds[s+1]]`` at offset
    ``s * block_rows`` — the host layout whose dim-0 NamedSharding puts
    range ``s`` (and only it) on shard ``s``. Zero rows are harmless: no
    stripe index points at them, and ``popcount(0 & x) == 0``.
    """
    num_shards = len(bounds) - 1
    out = np.zeros((num_shards * block_rows, store.shape[1]), store.dtype)
    for s in range(num_shards):
        lo, hi = int(bounds[s]), int(bounds[s + 1])
        out[s * block_rows : s * block_rows + (hi - lo)] = store[lo:hi]
    return out


class Sharded2DExecutor(_StripeScheduleDriver):
    """Device-resident ``sharded_2d`` execute stage for one 2-axis mesh.

    Both slice stores are genuinely ``NamedSharding``-sharded: device
    ``(i, j)`` uploads (once) exactly its row range ``i`` and column range
    ``j`` — the first placement where NEITHER store is replicated, so row
    stores can exceed one device's memory. The ranges come from the
    constructing plan's (typically pair-count-weighted) bounds; ``count``
    re-plans any work list against those fixed bounds, so the stores never
    re-upload — which is exactly where blocks go imbalanced and the
    ``packed`` stripe schedule (see ``_StripeScheduleDriver``) earns its
    fewer psum steps. Pow2 step buckets bound retraces, and index staging
    is double-buffered (step i+1's upload in flight during step i's
    compute).
    """

    def __init__(
        self,
        sbf: SlicedBitmap,
        mesh: Mesh,
        plan: ExecutionPlan | None = None,
        *,
        chunk_pairs: int = 1 << 20,
        double_buffer: bool = True,
        schedule: str = "packed",
    ):
        if schedule not in SCHEDULES:
            raise ValueError(f"schedule {schedule!r} not in {SCHEDULES}")
        self.schedule = schedule
        if mesh.devices.ndim != 2:
            raise ValueError(
                f"sharded_2d needs a 2-axis mesh, got {mesh.devices.ndim} "
                f"axes {tuple(mesh.axis_names)}"
            )
        mesh = _auto_axes(mesh)
        self.mesh = mesh
        self.axis_names = tuple(mesh.axis_names)
        self.grid = tuple(int(x) for x in mesh.devices.shape)
        self.num_shards = self.grid[0] * self.grid[1]
        self.words_per_slice = int(sbf.words_per_slice)
        self.chunk_pairs = chunk_pairs
        self.double_buffer = double_buffer
        self._sbf = sbf
        nrow = len(sbf.row_slice_idx)
        ncol = len(sbf.col_slice_idx)
        if plan is None:
            # Worklist-independent fallback: even ranges on both axes. For
            # balanced (weighted) ranges construct from a sharded_2d plan.
            self.row_bounds = even_range_bounds(nrow, self.grid[0])
            self.col_bounds = even_range_bounds(ncol, self.grid[1])
        else:
            if plan.placement != "sharded_2d" or plan.grid != self.grid:
                raise ValueError(
                    f"plan is {plan.placement!r} over grid {plan.grid}, "
                    f"mesh is {self.grid[0]}x{self.grid[1]}"
                )
            self.row_bounds = np.asarray(plan.row_bounds, dtype=np.int64)
            self.col_bounds = np.asarray(plan.col_bounds, dtype=np.int64)
        self.row_shard_rows = _pow2_ceil(
            max(int(np.diff(self.row_bounds).max(initial=0)), 1)
        )
        self.col_shard_rows = _pow2_ceil(
            max(int(np.diff(self.col_bounds).max(initial=0)), 1)
        )
        row_axis, col_axis = self.axis_names
        self.row_store = jax.device_put(
            _range_block_store(
                # tclint: sync-ok(one-time shard repack at executor construction; ROADMAP: device-resident resharding)
                np.asarray(sbf.row_slice_data), self.row_bounds,
                self.row_shard_rows,
            ),
            NamedSharding(mesh, P(row_axis, None)),
        )
        self.col_store = jax.device_put(
            _range_block_store(
                # tclint: sync-ok(one-time shard repack at executor construction; ROADMAP: device-resident resharding)
                np.asarray(sbf.col_slice_data), self.col_bounds,
                self.col_shard_rows,
            ),
            NamedSharding(mesh, P(col_axis, None)),
        )
        self._step = make_sharded_2d_step(mesh, self.axis_names)
        self._validate_int32_floor("blocks", "a smaller grid")

    def _plan(self, wl: Worklist) -> ExecutionPlan:
        """Plan a work list against this executor's FIXED store ranges."""
        return plan_execution(
            self._sbf,
            wl,
            DeviceTopology(num_devices=self.num_shards),
            placement="sharded_2d",
            grid=self.grid,
            chunk_pairs=self.chunk_pairs,
            row_bounds=self.row_bounds,
            col_bounds=self.col_bounds,
        )

    def _check_plan(self, plan: ExecutionPlan) -> None:
        if plan.placement != "sharded_2d":
            raise ValueError(
                f"plan placement {plan.placement!r} is not 'sharded_2d'"
            )
        if plan.grid != self.grid:
            raise ValueError(
                f"plan grid {plan.grid} != mesh grid {self.grid}"
            )
        if not (
            np.array_equal(plan.row_bounds, self.row_bounds)
            and np.array_equal(plan.col_bounds, self.col_bounds)
        ):
            raise ValueError(
                "plan's block-local coordinates assume different store "
                "ranges than this executor's resident blocks; re-plan with "
                "row_bounds/col_bounds pinned to the executor's (or use "
                ".count, which does)"
            )

    def update_stores(self, sbf: SlicedBitmap, row_lanes, col_lanes) -> None:
        """Scatter an ``SBFUpdate``'s lanes into the resident sharded blocks.

        The streaming fast path for sharded placements: lane positions are
        *global* record coordinates (the same ones ``core.sbf.update_sbf``
        emits), so each is remapped to its owner block's local row —
        ``owner * shard_rows + (pos - bounds[owner])`` with the owner found
        by binary search over the resident range bounds — and scattered via
        the shared pow2-bucketed update jit. Only valid when the update did
        not grow either record set (``SBFUpdate.grew`` is False): growth
        changes record positions and hence the range bounds, so callers
        rebuild the executor instead. ``sbf`` becomes the executor's
        planning SBF (its host ptr/slice_idx arrays are unchanged under a
        non-growing update, but its data must match the scattered stores).
        """
        from repro.core.executor import apply_store_lanes
        from repro.core.sbf import UpdateLanes

        if int(sbf.words_per_slice) != self.words_per_slice:
            raise ValueError(
                f"words_per_slice {sbf.words_per_slice} != resident "
                f"{self.words_per_slice}"
            )
        if (
            len(sbf.row_slice_idx) != int(self.row_bounds[-1])
            or len(sbf.col_slice_idx) != int(self.col_bounds[-1])
        ):
            raise ValueError(
                "record counts changed — the SBF grew; rebuild the "
                "sharded executor (bounds and block layout are stale)"
            )

        def remap(lanes, bounds, shard_rows, side):
            if lanes is None or lanes.num_lanes == 0:
                return None
            pos = lanes.pos.astype(np.int64)
            if pos.max(initial=0) >= int(bounds[-1]) or pos.min(initial=0) < 0:
                raise ValueError(
                    f"{side} lane positions exceed the resident record "
                    "range — the SBF grew; rebuild the sharded executor"
                )
            owner = np.searchsorted(bounds, pos, side="right") - 1
            local = owner * shard_rows + (pos - bounds[owner])
            return UpdateLanes(
                pos=local.astype(np.int32),
                word=lanes.word,
                set_mask=lanes.set_mask,
                clear_mask=lanes.clear_mask,
            )

        row_axis, col_axis = self.axis_names
        rl = remap(row_lanes, self.row_bounds, self.row_shard_rows, "row")
        cl = remap(col_lanes, self.col_bounds, self.col_shard_rows, "col")
        if rl is not None:
            self.row_store = jax.device_put(
                apply_store_lanes(self.row_store, rl),
                NamedSharding(self.mesh, P(row_axis, None)),
            )
        if cl is not None:
            self.col_store = jax.device_put(
                apply_store_lanes(self.col_store, cl),
                NamedSharding(self.mesh, P(col_axis, None)),
            )
        self._sbf = sbf

    def _plan_matches_bounds(self, plan: ExecutionPlan | None) -> bool:
        return (
            plan is not None
            and plan.placement == "sharded_2d"
            and plan.grid == self.grid
            and np.array_equal(plan.row_bounds, self.row_bounds)
            and np.array_equal(plan.col_bounds, self.col_bounds)
        )

    def count_async(
        self, wl: Worklist, plan: ExecutionPlan | None = None
    ) -> CountFuture:
        """``count`` with the final host readback deferred to ``result()``."""
        if self._plan_matches_bounds(plan):
            return self.count_plan_async(plan)
        return self.count_plan_async(self._plan(wl))

    def count(self, wl: Worklist, plan: ExecutionPlan | None = None) -> int:
        """Count a work list against the resident sharded stores.

        A pre-built ``plan`` is used as-is when its ranges match the
        resident blocks (skips re-planning); otherwise — e.g. a fresh
        weighted plan for a new work list on a pooled executor — ``wl`` is
        re-planned against the executor's FIXED bounds, trading a little
        balance for keeping the uploaded shards and traced step.
        """
        return self.count_async(wl, plan).result()


# Bounded cache of sharded executors for the one-shot APIs, keyed by store
# *content* (like core.executor.ExecutorPool) so repeated counts of the same
# graph hit even though tcim_count* rebuilds the SBF object per call —
# reusing the uploaded shards and the traced step instead of paying both.
# Shared by the 1-D and 2-D executors (their key tuples cannot collide).
_SHARDED_CACHE: collections.OrderedDict = collections.OrderedDict()
_SHARDED_CACHE_MAX = 4


def pooled_sharded_executor(
    sbf: SlicedBitmap,
    mesh: Mesh,
    *,
    chunk_pairs: int = 1 << 20,
    double_buffer: bool = True,
    schedule: str = "packed",
) -> ShardedColsExecutor:
    from repro.core.executor import sbf_content_key

    # EVERY config knob is part of the key — a pooled hit must never hand
    # back an executor with different buffering or scheduling than requested.
    key = (sbf_content_key(sbf), mesh, chunk_pairs, double_buffer, schedule)
    entry = _SHARDED_CACHE.get(key)
    if entry is not None:
        _SHARDED_CACHE.move_to_end(key)
        return entry
    ex = ShardedColsExecutor(
        sbf,
        mesh,
        chunk_pairs=chunk_pairs,
        double_buffer=double_buffer,
        schedule=schedule,
    )
    _SHARDED_CACHE[key] = ex
    _SHARDED_CACHE.move_to_end(key)
    while len(_SHARDED_CACHE) > _SHARDED_CACHE_MAX:
        _SHARDED_CACHE.popitem(last=False)
    return ex


def pooled_sharded_2d_executor(
    sbf: SlicedBitmap,
    mesh: Mesh,
    plan: ExecutionPlan,
    *,
    chunk_pairs: int = 1 << 20,
    double_buffer: bool = True,
    schedule: str = "packed",
) -> Sharded2DExecutor:
    """Cached ``Sharded2DExecutor`` for (store content, mesh, grid, config).

    The bounds are deliberately NOT part of the key: a hit means the graph's
    stores are already resident under some (earlier-planned) ranges, and
    re-uploading both NamedSharding-sharded stores to chase a new work
    list's slightly-better-balanced cuts costs far more than it saves —
    callers route new work lists through ``count(wl, plan)``, which falls
    back to the resident fixed bounds when the plan's ranges differ. The
    config knobs (``double_buffer``, ``schedule``) ARE keyed: they change
    runtime behaviour, not the resident stores, and a hit must honor them.
    """
    from repro.core.executor import sbf_content_key

    key = (
        sbf_content_key(sbf), mesh, plan.grid, chunk_pairs, double_buffer,
        schedule,
    )
    entry = _SHARDED_CACHE.get(key)
    if entry is not None:
        _SHARDED_CACHE.move_to_end(key)
        return entry
    ex = Sharded2DExecutor(
        sbf,
        mesh,
        plan,
        chunk_pairs=chunk_pairs,
        double_buffer=double_buffer,
        schedule=schedule,
    )
    _SHARDED_CACHE[key] = ex
    _SHARDED_CACHE.move_to_end(key)
    while len(_SHARDED_CACHE) > _SHARDED_CACHE_MAX:
        _SHARDED_CACHE.popitem(last=False)
    return ex


def clear_sharded_executor_cache() -> None:
    """Release every cached sharded executor (frees the NamedSharding-sharded
    slice stores — sharded graphs are exactly the ones big enough to care)."""
    _SHARDED_CACHE.clear()


def distributed_tc_count_async(
    sbf: SlicedBitmap,
    wl: Worklist,
    mesh: Mesh,
    *,
    placement: str = "replicated",
    max_step_pairs: int | None = None,
    schedule: str = "packed",
) -> CountFuture:
    """``distributed_tc_count`` with the host readback deferred.

    Every placement dispatches all of its psum steps before returning — the
    replicated path included, which used to sync ``int(step(...))`` per
    stripe chunk; its per-stripe device scalars now ride the returned
    ``CountFuture`` and are summed exactly (host ints) at ``result()``.
    Fleet callers overlap graph i's close with graph i+1's build and
    stripe assembly on ANY placement.

    Like every async path in this repo (``Executor.execute_indices_async``,
    the sharded ``count_plan_async``), all steps' index uploads may be in
    flight at once: ``max_step_pairs`` bounds the per-step compute and the
    psum's int32 worst case, while total *staging* memory grows with the
    step count (8 index bytes per lane per side). Callers serving work
    lists with very many steps under tight device memory should sync in
    batches (loop sub-worklists through the blocking API) instead.
    """
    if placement not in TC_PLACEMENTS:
        raise ValueError(f"placement {placement!r} not in {TC_PLACEMENTS}")
    if schedule not in SCHEDULES:
        raise ValueError(f"schedule {schedule!r} not in {SCHEDULES}")
    chunk = max_step_pairs if max_step_pairs is not None else 1 << 20
    if placement == "sharded_cols":
        return pooled_sharded_executor(
            sbf, mesh, chunk_pairs=chunk, schedule=schedule
        ).count_async(wl)
    if placement == "sharded_2d":
        grid = tuple(int(x) for x in mesh.devices.shape)
        if len(grid) != 2:
            raise ValueError(
                f"placement 'sharded_2d' needs a 2-axis mesh, got "
                f"{len(grid)} axes {tuple(mesh.axis_names)}"
            )
        plan = plan_execution(
            sbf,
            wl,
            DeviceTopology(num_devices=grid[0] * grid[1]),
            placement="sharded_2d",
            grid=grid,
            chunk_pairs=chunk,
        )
        ex = pooled_sharded_2d_executor(
            sbf, mesh, plan, chunk_pairs=chunk, schedule=schedule
        )
        return ex.count_async(wl, plan)
    if wl.num_pairs == 0:
        # Match the sharded paths' empty-schedule guard: nothing to count,
        # so never pad, upload, or dispatch a psum step for it.
        return CountFuture([])
    axis_names = tuple(mesh.axis_names)
    n_dev = int(np.prod(mesh.devices.shape))
    step = make_tc_step(mesh, axis_names)
    row_store = jnp.asarray(sbf.row_slice_data)
    col_store = jnp.asarray(sbf.col_slice_data)
    max_pairs = max(INT32_SAFE_WORDS // max(sbf.words_per_slice, 1), 1)
    if max_step_pairs is not None:
        max_pairs = max(min(max_pairs, max_step_pairs), 1)
    totals = []
    for start in range(0, max(wl.num_pairs, 1), max_pairs):
        sub = _slice_worklist(wl, start, start + max_pairs)
        row_idx, col_idx = shard_worklist(sub, n_dev)
        totals.append(
            step(
                row_store,
                col_store,
                jnp.asarray(row_idx.reshape(-1)),
                jnp.asarray(col_idx.reshape(-1)),
            )
        )
    return CountFuture(totals)


def distributed_tc_count(
    sbf: SlicedBitmap,
    wl: Worklist,
    mesh: Mesh,
    *,
    placement: str = "replicated",
    max_step_pairs: int | None = None,
    schedule: str = "packed",
) -> int:
    """Execute the distributed count on an actual mesh (test/production path).

    Per-shard partials AND their psum accumulate in int32 (x64 is off), so
    the work list is split into stripes whose worst-case count provably fits
    int32 — one step per stripe, per-stripe totals summed exactly on the
    host (the distributed analogue of core.executor's escape hatch). Work
    lists under the bound take exactly one step, as before; either way the
    steps are all dispatched before the single host sync (see
    ``distributed_tc_count_async``, which defers even that).

    ``placement='sharded_cols'`` runs the column-sharded path instead: the
    column store is NamedSharding-sharded over the mesh and the work list is
    owner-grouped per shard (see ``ShardedColsExecutor``).
    ``placement='sharded_2d'`` shards BOTH stores over a 2-axis mesh with
    pair-count-weighted ranges (see ``Sharded2DExecutor``). Long-lived
    callers should construct the executors themselves and reuse them.

    ``max_step_pairs`` additionally bounds the pairs per psum step below the
    int32-safety budget (the caller's memory bound, e.g. the engine's
    ``chunk_pairs``). ``schedule`` picks the sharded paths' stripe
    scheduling policy (``packed`` default / ``lockstep`` baseline; the
    replicated path has a single stripe, so it does not apply there). All
    placements run the fused jnp mirror inside shard_map — Executor modes
    don't apply here.
    """
    return distributed_tc_count_async(
        sbf,
        wl,
        mesh,
        placement=placement,
        max_step_pairs=max_step_pairs,
        schedule=schedule,
    ).result()


def _slice_worklist(wl: Worklist, start: int, stop: int) -> Worklist:
    return Worklist(
        pair_edge=wl.pair_edge[start:stop],
        pair_row_pos=wl.pair_row_pos[start:stop],
        pair_col_pos=wl.pair_col_pos[start:stop],
        m_edges=wl.m_edges,
        n_slices=wl.n_slices,
    )
