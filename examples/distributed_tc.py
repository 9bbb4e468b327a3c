"""Distributed TCIM across a device mesh via shard_map.

Three placements of the same count (see core/plan.py):

  * replicated   — both slice stores on every device, work-list stripes
    dealt across the mesh, one scalar psum closes it.
  * sharded_cols — the column store genuinely NamedSharding-sharded over
    the mesh (one contiguous row range per device) with the work list
    owner-grouped so each pair executes on the shard holding its column
    slice; only index stripes travel.
  * sharded_2d   — BOTH stores sharded over a 2-axis (row, col) owner
    grid with pair-count-weighted ranges; device (i, j) holds row range i
    and column range j, and every pair executes on its owner block. The
    placement that lets row stores exceed one device's memory.

The mesh is built from the devices present: every device on one axis for
the replicated and sharded_cols placements, and the most nearly square
(rows, cols) grid over them for sharded_2d. On a CPU, force several host
devices to make the demo genuinely multi-device:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      PYTHONPATH=src python examples/distributed_tc.py
"""
import math

import jax

from repro.core import build_sbf, build_worklist, plan_execution, DeviceTopology
from repro.distributed import (
    Sharded2DExecutor,
    ShardedColsExecutor,
    distributed_tc_count,
)
from repro.graphs import build_graph, rmat
from repro.graphs.exact import triangles_intersection
from repro.runtime.compile_cache import enable_compile_cache


def grid_for(n_dev: int) -> tuple[int, int]:
    """The most nearly square (rows, cols) grid with rows * cols == n_dev."""
    rows = max(r for r in range(1, math.isqrt(n_dev) + 1) if n_dev % r == 0)
    return n_dev // rows, rows


def main():
    edges = rmat(30_000, 200_000, seed=11)
    g = build_graph(edges, reorder=True)
    sbf = build_sbf(g)
    wl = build_worklist(g, sbf)
    n_dev = len(jax.devices())
    grid = grid_for(n_dev)
    mesh = jax.make_mesh((n_dev,), ("data",))
    print(f"graph |V|={g.n} |E|={g.m}; work list: {wl.num_pairs} slice pairs")
    print(f"mesh: {dict(zip(mesh.axis_names, mesh.devices.shape))} ({n_dev} devices)")

    want = triangles_intersection(g)
    got = distributed_tc_count(sbf, wl, mesh)
    print(f"replicated   count = {got}; exact = {want}; "
          f"{'OK' if got == want else 'MISMATCH'}")

    # The same count with the column store actually sharded over the mesh.
    plan = plan_execution(
        sbf, wl, DeviceTopology(num_devices=n_dev),
        placement="sharded_cols", num_shards=n_dev,
    )
    ex = ShardedColsExecutor(sbf, mesh)
    got_sh = ex.count_plan(plan)
    stripe_pairs = plan.stats["stripe_pairs"]
    print(f"sharded_cols count = {got_sh}; "
          f"{'OK' if got_sh == want else 'MISMATCH'}")
    print(f"  col store: {ex.col_store.shape} as {ex.col_store.sharding.spec}, "
          f"{ex.col_shard_rows} rows/shard "
          f"(replicated? {ex.col_store.sharding.is_fully_replicated})")
    print(f"  stripes: min={min(stripe_pairs)} max={max(stripe_pairs)} "
          f"imbalance={plan.imbalance:.2f}")

    # Both stores sharded over a (row, col) owner grid, pair-count-weighted
    # ranges: neither store is replicated any more.
    mesh2 = jax.make_mesh(grid, ("r", "c"))
    plan2 = plan_execution(
        sbf, wl, DeviceTopology(num_devices=n_dev),
        placement="sharded_2d", grid=grid,
    )
    ex2 = Sharded2DExecutor(sbf, mesh2, plan2)
    got_2d = ex2.count_plan(plan2)
    blocks = plan2.stats["stripe_pairs"]
    print(f"sharded_2d   count = {got_2d}; "
          f"{'OK' if got_2d == want else 'MISMATCH'}")
    print(f"  row store: {ex2.row_store.shape} as {ex2.row_store.sharding.spec} "
          f"(replicated? {ex2.row_store.sharding.is_fully_replicated})")
    print(f"  col store: {ex2.col_store.shape} as {ex2.col_store.sharding.spec} "
          f"(replicated? {ex2.col_store.sharding.is_fully_replicated})")
    print(f"  blocks: min={min(blocks)} max={max(blocks)} "
          f"imbalance={plan2.imbalance:.2f} (split={plan2.split})")

    # Per-shard packed stripe scheduling: pin the EVEN split's skewed blocks
    # as fixed bounds (the shape a pooled executor serves after re-planning
    # a new work list against resident stores) and compare psum steps under
    # a budget small enough that the count is genuinely multi-step.
    plan_even = plan_execution(
        sbf, wl, DeviceTopology(num_devices=n_dev),
        placement="sharded_2d", grid=grid, split="even",
    )
    budget = 1 << 13
    fixed = plan_execution(
        sbf, wl, DeviceTopology(num_devices=n_dev),
        placement="sharded_2d", grid=grid, chunk_pairs=budget,
        row_bounds=plan_even.row_bounds, col_bounds=plan_even.col_bounds,
    )
    ex_fix = Sharded2DExecutor(sbf, mesh2, fixed, chunk_pairs=budget)
    lock = Sharded2DExecutor(
        sbf, mesh2, fixed, chunk_pairs=budget, schedule="lockstep"
    )
    got_fix = ex_fix.count_plan(fixed)
    print(f"packed sched count = {got_fix}; "
          f"{'OK' if got_fix == want else 'MISMATCH'}")
    print(f"  fixture imbalance={fixed.imbalance:.2f}; psum steps: "
          f"packed={ex_fix.stripe_schedule(fixed).num_steps} vs "
          f"lockstep={lock.stripe_schedule(fixed).num_steps} "
          f"(budget {budget} pairs/step)")

    # Async close: dispatch both counts, then take both readbacks — the
    # fleet-serving overlap (graph i's close hides behind graph i+1's
    # stripe assembly and uploads).
    futs = [ex_fix.count_plan_async(fixed), ex2.count_plan_async(plan2)]
    got_async = [f.result() for f in futs]
    print(f"async close   counts = {got_async}; "
          f"{'OK' if got_async == [want, want] else 'MISMATCH'}")


if __name__ == "__main__":
    enable_compile_cache()
    main()
