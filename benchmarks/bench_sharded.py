"""Shard sweep: replicated vs sharded_cols vs sharded_2d on a CPU mesh.

A CPU-only sweep: it pins the CPU backend and forces 8 host devices (must
run standalone — both only take effect before jax initializes, so this
suite is NOT part of benchmarks/run.py):

    PYTHONPATH=src:. python benchmarks/bench_sharded.py

For each bench graph it reports the steady-state execute time of

  * ``replicated/S``  — work-list stripes dealt over S devices, both stores
    on every device (the zero-communication baseline),
  * ``sharded/S``     — the column store NamedSharding-sharded into S
    contiguous row ranges with owner-grouped index stripes (even split),
  * ``sharded2d/RxC`` — BOTH stores sharded over an R×C owner grid with
    pair-count-weighted ranges; the derived fields put the weighted split's
    per-block imbalance next to the even split's on the same grid, which is
    the planner claim the CI gate pins (weighted <= 1.25 where even shows
    up to ~4-5x on these degree-ordered graphs),
  * ``sched/RxC``     — packed vs lockstep stripe scheduling on the
    imbalanced fixed-bounds fixture (the even split's skewed blocks pinned
    as caller bounds): wall-clock of a multi-step ``count_plan`` under each
    policy plus both psum-step counts — the scheduler claim the CI gate
    pins (packed <= lockstep, >= 30% fewer on the fixture),
  * ``async/RxC``     — a 4-count serve loop with the final host readback
    overlapped (``count_plan_async``, collect futures, then close) vs the
    synchronous close after every count.

On a CPU mesh the sharded paths mostly measure scheduling overhead — the
point is the *scaling shape* (stripe/block imbalance, steps, psum count),
which is what transfers to a real pod. In particular the packed scheduler
optimizes *dispatch count*; its late steps can carry wide windows where
drained shards' sentinel lanes still occupy the [S, bucket] index block, so
on the largest CPU-mirror graphs the per-step gather work can outweigh the
saved dispatches (tracked in ROADMAP: budget-aware packed widths).
"""
from __future__ import annotations

import os

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import time  # noqa: E402

import jax  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import Mesh  # noqa: E402

from benchmarks.common import bench_graphs, emit, fixture_step_budget  # noqa: E402
from repro.core import DeviceTopology, plan_execution  # noqa: E402
from repro.distributed import distributed_tc_count  # noqa: E402
from repro.distributed.tc import Sharded2DExecutor, ShardedColsExecutor  # noqa: E402
from repro.runtime.compile_cache import enable_compile_cache  # noqa: E402

# The big bench graphs take minutes per shard count through shard_map on
# CPU; the sweep's subject is scheduling behaviour, so mid-size graphs do.
SWEEP_GRAPHS = ("ego-facebook", "email-enron", "com-amazon")

# (row_shards, col_shards) owner grids for the 2-D sweep: 1x1 up to 4x2.
SWEEP_GRIDS = ((1, 1), (2, 1), (1, 2), (2, 2), (4, 1), (4, 2))


def _time_host(fn, iters: int = 3) -> float:
    """Steady-state microseconds per call: warm up once (the first call pays
    tracing/compilation and any store upload), then report the MINIMUM of
    ``iters`` timed calls — the mean would let one GC pause or page fault
    skew a CI number, and tracing must never be inside the timed region."""
    fn()  # warm: compile + upload outside the timed region
    best = float("inf")
    for _ in range(iters):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best * 1e6


def run() -> None:
    devices = jax.devices()
    for name, cfg, scaled, g, sbf, wl in bench_graphs(SWEEP_GRAPHS):
        oracle = None
        for s in (1, 2, 4, 8):
            if s > len(devices):
                continue
            mesh = Mesh(np.asarray(devices[:s]), ("d",))
            rep = distributed_tc_count(sbf, wl, mesh)
            if oracle is None:
                oracle = rep
            assert rep == oracle, (name, s, rep, oracle)
            us_rep = _time_host(lambda: distributed_tc_count(sbf, wl, mesh))
            emit(
                f"bench_sharded/{name}/replicated/{s}",
                us_rep,
                f"pairs={wl.num_pairs};store_bytes={sbf.data_bytes}",
            )
            ex = ShardedColsExecutor(sbf, mesh)
            plan = plan_execution(
                sbf,
                wl,
                DeviceTopology(num_devices=s),
                placement="sharded_cols",
                num_shards=s,
            )
            sh = ex.count_plan(plan)
            assert sh == oracle, (name, s, sh, oracle)
            us_sh = _time_host(lambda: ex.count_plan(plan))
            emit(
                f"bench_sharded/{name}/sharded/{s}",
                us_sh,
                f"pairs={wl.num_pairs};shard_rows={ex.col_shard_rows};"
                f"imbalance={plan.imbalance:.2f};"
                f"rep_over_sharded={us_rep / max(us_sh, 1e-9):.2f}x",
            )
        for rows, cols in SWEEP_GRIDS:
            if rows * cols > len(devices):
                continue
            mesh2 = Mesh(
                np.asarray(devices[: rows * cols]).reshape(rows, cols),
                ("r", "c"),
            )
            topo = DeviceTopology(num_devices=rows * cols)
            plan_w = plan_execution(
                sbf, wl, topo, placement="sharded_2d", grid=(rows, cols),
                split="weighted",
            )
            plan_e = plan_execution(
                sbf, wl, topo, placement="sharded_2d", grid=(rows, cols),
                split="even",
            )
            ex2 = Sharded2DExecutor(sbf, mesh2, plan_w)
            got = ex2.count_plan(plan_w)
            assert got == oracle, (name, rows, cols, got, oracle)
            us_2d = _time_host(lambda: ex2.count_plan(plan_w))
            blocks = [s.num_pairs for s in plan_w.stripes]
            emit(
                f"bench_sharded/{name}/sharded2d/{rows}x{cols}",
                us_2d,
                f"pairs={wl.num_pairs};row_rows={ex2.row_shard_rows};"
                f"col_rows={ex2.col_shard_rows};"
                f"imbalance_weighted={plan_w.imbalance:.2f};"
                f"imbalance_even={plan_e.imbalance:.2f};"
                f"block_min={min(blocks)};block_max={max(blocks)}",
            )
            # Packed vs lockstep on the imbalanced fixed-bounds fixture:
            # the even split's skewed blocks pinned as caller bounds, with a
            # chunk budget small enough that the count is genuinely
            # multi-step (~16 lockstep windows over the longest block).
            budget = fixture_step_budget(
                [s.num_pairs for s in plan_e.stripes], rows * cols
            )
            fixed = plan_execution(
                sbf, wl, topo, placement="sharded_2d", grid=(rows, cols),
                chunk_pairs=budget,
                row_bounds=plan_e.row_bounds, col_bounds=plan_e.col_bounds,
            )
            ex_pack = Sharded2DExecutor(
                sbf, mesh2, fixed, chunk_pairs=budget, schedule="packed"
            )
            ex_lock = Sharded2DExecutor(
                sbf, mesh2, fixed, chunk_pairs=budget, schedule="lockstep"
            )
            got_pack = ex_pack.count_plan(fixed)
            assert got_pack == ex_lock.count_plan(fixed) == oracle, (
                name, rows, cols, got_pack, oracle,
            )
            steps_pack = ex_pack.stripe_schedule(fixed).num_steps
            steps_lock = ex_lock.stripe_schedule(fixed).num_steps
            us_pack = _time_host(lambda: ex_pack.count_plan(fixed))
            us_lock = _time_host(lambda: ex_lock.count_plan(fixed))
            emit(
                f"bench_sharded/{name}/sched/{rows}x{cols}",
                us_pack,
                f"pairs={wl.num_pairs};budget={budget};"
                f"imbalance_fixture={fixed.imbalance:.2f};"
                f"steps_packed={steps_pack};steps_lockstep={steps_lock};"
                f"lockstep_us={us_lock:.1f};"
                f"lockstep_over_packed={us_lock / max(us_pack, 1e-9):.2f}x",
            )
            # Async close: a 4-count serve loop with the host readback of
            # count i overlapped with the stripe assembly + uploads of
            # count i+1, vs closing synchronously after every count.
            def _serve_sync():
                return [ex2.count_plan(plan_w) for _ in range(4)]

            def _serve_async():
                futs = [ex2.count_plan_async(plan_w) for _ in range(4)]
                return [f.result() for f in futs]

            assert _serve_async() == _serve_sync() == [oracle] * 4
            us_async = _time_host(_serve_async)
            us_sync = _time_host(_serve_sync)
            emit(
                f"bench_sharded/{name}/async/{rows}x{cols}",
                us_async,
                f"counts=4;sync_us={us_sync:.1f};"
                f"sync_over_async={us_sync / max(us_async, 1e-9):.2f}x",
            )


if __name__ == "__main__":
    enable_compile_cache()
    print("name,us_per_call,derived")
    run()
