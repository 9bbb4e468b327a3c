"""Multi-device behaviour on forced host devices (subprocess isolation:
XLA device count is locked at first jax init, so these spawn fresh
interpreters with XLA_FLAGS set)."""
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from graph_cases import GRAPH_CASES, GRAPH_IDS, num_vertices

SRC = str(Path(__file__).resolve().parents[1] / "src")


def _run(code: str, devices: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True, env=env,
        timeout=560,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_distributed_tc_matches_exact():
    out = _run(
        """
import jax
from repro.graphs import rmat, build_graph
from repro.graphs.exact import triangles_intersection
from repro.core import build_sbf, build_worklist
from repro.distributed import distributed_tc_count
edges = rmat(3000, 18000, seed=5)
g = build_graph(edges, reorder=True)
sbf = build_sbf(g); wl = build_worklist(g, sbf)
mesh = jax.make_mesh((4, 2), ('data', 'model'))
got = distributed_tc_count(sbf, wl, mesh)
want = triangles_intersection(g)
assert got == want, (got, want)
got_sh = distributed_tc_count(sbf, wl, mesh, placement='sharded_cols')
assert got_sh == want, (got_sh, want)
print('OK', got)
"""
    )
    assert "OK" in out


def test_sharded_cols_exact_on_4way_mesh_all_bench_configs():
    """Acceptance: sharded_cols produces exact counts on a 4-way CPU mesh for
    every tcim_graphs bench config (scaled), verified against the jnp oracle
    backend, with the column store provably sharded — not replicated."""
    out = _run(
        """
import jax, numpy as np
from repro.configs.tcim_graphs import GRAPHS
from repro.core import Executor, tcim_count_graph
from repro.data.graph_pipeline import load_graph
from repro.distributed import ShardedColsExecutor
assert len(jax.devices()) == 4
mesh = jax.make_mesh((4,), ('d',))
for name in GRAPHS:
    g, sbf, wl = load_graph(GRAPHS[name].scaled(0.02), 64)
    ex = ShardedColsExecutor(sbf, mesh)
    # The store is genuinely NamedSharding-sharded: 4 distinct device
    # shards, each holding only its contiguous row range.
    sh = ex.col_store.sharding
    assert not sh.is_fully_replicated, name
    assert len({s.device for s in ex.col_store.addressable_shards}) == 4, name
    assert ex.col_store.addressable_shards[0].data.shape[0] == ex.col_shard_rows
    assert ex.col_store.shape[0] == 4 * ex.col_shard_rows
    got = ex.count(wl)
    want = Executor(sbf, mode='jnp').count(wl)  # independent oracle backend
    assert got == want, (name, got, want)
    assert ex.schedule == 'packed'  # the default policy serves every config
    if name == 'ego-facebook':
        # Packed (default) and lockstep schedules are bit-identical, sync
        # or async, on a genuinely multi-step budget (~8 lockstep windows).
        from repro.core.plan import pow2_ceil
        assert ex.count_async(wl).result() == want
        plan0 = ex._plan(wl)
        longest = max(s.num_pairs for s in plan0.stripes)
        chunk = pow2_ceil(max(-(-longest // 8), 1)) * 4
        lock = ShardedColsExecutor(sbf, mesh, chunk_pairs=chunk,
                                   schedule='lockstep')
        pack = ShardedColsExecutor(sbf, mesh, chunk_pairs=chunk)
        plan = pack._plan(wl)
        sched_l = lock.stripe_schedule(plan)
        sched_p = pack.stripe_schedule(plan)
        assert sched_l.num_steps > 1  # genuinely multi-step
        assert sched_p.num_steps <= sched_l.num_steps
        assert sched_p.max_step_pairs <= chunk  # memory bound incl. shards
        assert lock.count(wl) == pack.count(wl) == want
    # The engine API reaches the same path and count.
    res = tcim_count_graph(g, placement='sharded_cols', mesh=mesh,
                           collect_stats=False)
    assert res.triangles == want and res.stats['placement'] == 'sharded_cols'
    print('OK', name, got)
print('ALL_OK')
""",
        devices=4,
    )
    assert "ALL_OK" in out


def test_sharded_2d_exact_on_4x2_mesh_all_bench_configs():
    """Acceptance: sharded_2d produces exact counts on a forced 4x2 CPU mesh
    for every tcim_graphs bench config (scaled), verified against the jnp
    oracle backend, with BOTH stores provably NamedSharding-sharded — the
    row store is no longer replicated."""
    out = _run(
        """
import jax, numpy as np
from repro.configs.tcim_graphs import GRAPHS
from repro.core import DeviceTopology, Executor, plan_execution, tcim_count_graph
from repro.data.graph_pipeline import load_graph
from repro.distributed import Sharded2DExecutor
assert len(jax.devices()) == 8
mesh = jax.make_mesh((4, 2), ('r', 'c'))
topo = DeviceTopology(num_devices=8)
for name in GRAPHS:
    g, sbf, wl = load_graph(GRAPHS[name].scaled(0.02), 64)
    plan = plan_execution(sbf, wl, topo, placement='sharded_2d', grid=(4, 2))
    ex = Sharded2DExecutor(sbf, mesh, plan)
    # Both stores genuinely sharded. Row store: dim 0 split 4-way over 'r'
    # (each device holds one row range, NOT the whole store); col store:
    # dim 0 split 2-way over 'c'.
    assert not ex.row_store.sharding.is_fully_replicated, name
    assert not ex.col_store.sharding.is_fully_replicated, name
    assert ex.row_store.shape[0] == 4 * ex.row_shard_rows
    assert ex.col_store.shape[0] == 2 * ex.col_shard_rows
    for shard in ex.row_store.addressable_shards:
        assert shard.data.shape[0] == ex.row_shard_rows, name
    for shard in ex.col_store.addressable_shards:
        assert shard.data.shape[0] == ex.col_shard_rows, name
    got = ex.count_plan(plan)
    want = Executor(sbf, mode='jnp').count(wl)  # independent oracle backend
    assert got == want, (name, got, want)
    if name == 'ego-facebook':
        # Packed vs lockstep schedules on a multi-step fixed-bounds replan
        # (~8 lockstep windows): identical counts, packed never more psum
        # steps, async == sync.
        from repro.core.plan import pow2_ceil
        assert ex.count_plan_async(plan).result() == want
        longest = max(s.num_pairs for s in plan.stripes)
        chunk = pow2_ceil(max(-(-longest // 8), 1)) * 8
        lock = Sharded2DExecutor(sbf, mesh, plan, chunk_pairs=chunk,
                                 schedule='lockstep')
        pack = Sharded2DExecutor(sbf, mesh, plan, chunk_pairs=chunk)
        small = pack._plan(wl)  # re-plan under the reduced budget
        sched_l = lock.stripe_schedule(small)
        sched_p = pack.stripe_schedule(small)
        assert sched_l.num_steps > 1  # genuinely multi-step
        assert sched_p.num_steps <= sched_l.num_steps
        assert sched_p.max_step_pairs <= chunk
        assert lock.count_plan(small) == pack.count_plan(small) == want
    # The engine API reaches the same path and count.
    res = tcim_count_graph(g, placement='sharded_2d', mesh=mesh,
                           collect_stats=False)
    assert res.triangles == want and res.stats['placement'] == 'sharded_2d'
    print('OK', name, got, 'imb=%.2f' % plan.imbalance)
print('ALL_OK')
""",
        devices=8,
    )
    assert "ALL_OK" in out


@pytest.mark.parametrize("name,edges", GRAPH_CASES, ids=GRAPH_IDS)
@pytest.mark.parametrize("placement", ["sharded_cols", "sharded_2d"])
def test_sharded_placements_on_every_graph_shape(name, edges, placement):
    """``tcim_count_graph`` on a one-device mesh of each placement's rank is
    exact on every graph shape, the degenerate ones included."""
    import jax

    from repro.core import tcim_count_graph
    from repro.graphs import build_graph
    from repro.graphs.exact import triangles_intersection

    g = build_graph(edges, n=num_vertices(edges))
    if placement == "sharded_2d":
        mesh = jax.make_mesh((1, 1), ("r", "c"))
    else:
        mesh = jax.make_mesh((1,), ("d",))
    res = tcim_count_graph(g, placement=placement, mesh=mesh)
    assert res.triangles == triangles_intersection(g), (name, placement)
    assert res.stats["placement"] == placement


def test_sharded_2d_single_device_mesh():
    """sharded_2d is exact on a degenerate 1x1 mesh (tier-1, no forced
    devices): double-buffered == serial == exact, stale-bounds plans are
    rejected, and the pooled path reuses one executor per bounds."""
    import jax

    from repro.core import DeviceTopology, build_sbf, build_worklist, plan_execution
    from repro.distributed import Sharded2DExecutor, pooled_sharded_2d_executor
    from repro.distributed.tc import clear_sharded_executor_cache
    from repro.graphs import build_graph, rmat
    from repro.graphs.exact import triangles_intersection

    g = build_graph(rmat(400, 2500, seed=1))
    sbf = build_sbf(g, 64)
    wl = build_worklist(g, sbf)
    want = triangles_intersection(g)
    mesh = jax.make_mesh((1, 1), ("r", "c"))
    topo = DeviceTopology(num_devices=1)
    plan = plan_execution(
        sbf, wl, topo, placement="sharded_2d", grid=(1, 1), chunk_pairs=256
    )
    buf = Sharded2DExecutor(sbf, mesh, plan, chunk_pairs=256)
    ser = Sharded2DExecutor(
        sbf, mesh, plan, chunk_pairs=256, double_buffer=False
    )
    assert buf.count_plan(plan) == ser.count_plan(plan) == want
    assert buf.count(wl) == want  # re-plan against the resident bounds
    # Schedule policies are bit-identical here too, sync and async.
    lock = Sharded2DExecutor(
        sbf, mesh, plan, chunk_pairs=256, schedule="lockstep"
    )
    assert lock.count_plan(plan) == want
    fut = buf.count_plan_async(plan)
    assert fut.result() == want and fut.result() == want
    assert buf.count_async(wl).result() == want
    with pytest.raises(ValueError, match="schedule"):
        Sharded2DExecutor(sbf, mesh, plan, schedule="best")
    # A caller-built plan with matching bounds but a bigger chunk budget
    # must still be clamped to THIS executor's memory bound.
    big = plan_execution(
        sbf, wl, topo, placement="sharded_2d", grid=(1, 1),
        row_bounds=buf.row_bounds, col_bounds=buf.col_bounds,
    )
    assert big.chunk_pairs > 256
    sched = buf.stripe_schedule(big)
    assert sched.budget == 256 and sched.max_step_pairs <= 256
    assert buf.count_plan(big) == want
    # A plan whose ranges differ from the resident blocks must be rejected,
    # not silently miscounted (here: a plan built for a different SBF).
    g2 = build_graph(rmat(300, 1500, seed=2))
    sbf2 = build_sbf(g2, 64)
    stale = plan_execution(
        sbf2, build_worklist(g2, sbf2), topo, placement="sharded_2d",
        grid=(1, 1),
    )
    assert not np.array_equal(stale.row_bounds, buf.row_bounds)
    with pytest.raises(ValueError, match="ranges"):
        buf.count_plan(stale)
    wrong_grid = plan_execution(
        sbf, wl, DeviceTopology(num_devices=2), placement="sharded_2d",
        grid=(2, 1),
    )
    with pytest.raises(ValueError, match="grid"):
        buf.count_plan(wrong_grid)
    clear_sharded_executor_cache()
    p1 = pooled_sharded_2d_executor(sbf, mesh, plan)
    p2 = pooled_sharded_2d_executor(sbf, mesh, plan)
    assert p1 is p2
    clear_sharded_executor_cache()


def test_sharded_executors_take_explicit_axis_meshes():
    """``jax.make_mesh`` builds Explicit axes. The sharded executors run on
    the mesh's Auto view, so the streaming store scatter still resolves its
    shardings, and every mesh path reports the implementation it ran."""
    import jax
    from jax.sharding import AxisType

    from repro.core import build_sbf, build_worklist, tcim_count_graph, update_sbf
    from repro.distributed import ShardedColsExecutor, Sharded2DExecutor
    from repro.distributed.tc import MESH_EXECUTE_IMPL
    from repro.graphs import build_graph, rmat
    from repro.graphs.exact import triangles_intersection

    g = build_graph(rmat(400, 2500, seed=1), reorder=False)
    sbf = build_sbf(g, 64)
    mesh = jax.make_mesh((1, 1), ("r", "c"), axis_types=(AxisType.Explicit,) * 2)
    ex = Sharded2DExecutor(sbf, mesh, chunk_pairs=256)
    assert all(t == AxisType.Auto for t in ex.mesh.axis_types)
    assert ex.mesh.axis_names == mesh.axis_names
    removed = g.edges[:40]
    upd = update_sbf(sbf, None, removed)
    assert not upd.grew
    ex.update_stores(upd.sbf, upd.row_lanes, upd.col_lanes)
    keep = ~np.isin(
        g.edges[:, 0] * g.n + g.edges[:, 1], removed[:, 0] * g.n + removed[:, 1]
    )
    g2 = build_graph(g.edges[keep], n=g.n, reorder=False)
    assert ex.count(build_worklist(g2, upd.sbf)) == triangles_intersection(g2)
    assert ex.execute_impl == MESH_EXECUTE_IMPL
    flat = jax.make_mesh((1,), ("d",), axis_types=(AxisType.Explicit,))
    assert ShardedColsExecutor(sbf, flat).execute_impl == MESH_EXECUTE_IMPL
    want = triangles_intersection(g)
    for placement, m in (("sharded_2d", mesh), ("sharded_cols", flat)):
        res = tcim_count_graph(g, placement=placement, mesh=m)
        assert res.triangles == want, placement
        assert res.stats["placement"] == placement
        assert res.stats["execute_impl"] == MESH_EXECUTE_IMPL, placement


def test_pooled_sharded_executor_config_not_aliased():
    """Satellite regression: the pooled sharded caches dropped double_buffer
    (and now schedule) from their keys, so a hit could hand back an executor
    with different buffering than requested. Every config knob is keyed."""
    import jax

    from repro.core import DeviceTopology, build_sbf, build_worklist, plan_execution
    from repro.distributed import (
        pooled_sharded_2d_executor,
        pooled_sharded_executor,
    )
    from repro.distributed.tc import clear_sharded_executor_cache
    from repro.graphs import build_graph, rmat

    g = build_graph(rmat(300, 1500, seed=4))
    sbf = build_sbf(g, 64)
    wl = build_worklist(g, sbf)
    clear_sharded_executor_cache()
    try:
        mesh1 = jax.make_mesh((1,), ("d",))
        e_buf = pooled_sharded_executor(sbf, mesh1)
        e_ser = pooled_sharded_executor(sbf, mesh1, double_buffer=False)
        e_lock = pooled_sharded_executor(sbf, mesh1, schedule="lockstep")
        assert e_buf is not e_ser and e_buf is not e_lock
        assert e_buf.double_buffer and not e_ser.double_buffer
        assert e_buf.schedule == "packed" and e_lock.schedule == "lockstep"
        # Repeat requests still hit their own entry.
        assert pooled_sharded_executor(sbf, mesh1, double_buffer=False) is e_ser
        assert pooled_sharded_executor(sbf, mesh1, schedule="lockstep") is e_lock

        mesh2 = jax.make_mesh((1, 1), ("r", "c"))
        plan = plan_execution(
            sbf, wl, DeviceTopology(num_devices=1), placement="sharded_2d",
            grid=(1, 1),
        )
        p_buf = pooled_sharded_2d_executor(sbf, mesh2, plan)
        p_ser = pooled_sharded_2d_executor(sbf, mesh2, plan, double_buffer=False)
        p_lock = pooled_sharded_2d_executor(sbf, mesh2, plan, schedule="lockstep")
        assert p_buf is not p_ser and p_buf is not p_lock
        assert not p_ser.double_buffer and p_lock.schedule == "lockstep"
        assert (
            pooled_sharded_2d_executor(sbf, mesh2, plan, double_buffer=False)
            is p_ser
        )
    finally:
        clear_sharded_executor_cache()


def test_stripe_split_int32_boundary(monkeypatch):
    """Satellite: the replicated path splits exactly at the int32-safe pair
    budget — one psum step at the bound, two one pair over the bound."""
    import jax

    from repro.core import build_sbf, build_worklist
    from repro.distributed import tc as dtc
    from repro.graphs import build_graph, rmat
    from repro.graphs.exact import triangles_intersection

    g = build_graph(rmat(400, 2500, seed=1))
    sbf = build_sbf(g, 64)
    wl = build_worklist(g, sbf)
    want = triangles_intersection(g)
    mesh = jax.make_mesh((1,), ("d",))
    wps = sbf.words_per_slice

    calls = []
    real = dtc.make_tc_step

    def counting(mesh_, axes):
        step = real(mesh_, axes)

        def wrapped(*a):
            calls.append(1)
            return step(*a)

        return wrapped

    monkeypatch.setattr(dtc, "make_tc_step", counting)
    # num_pairs exactly at the budget: a single stripe/step.
    monkeypatch.setattr(dtc, "INT32_SAFE_WORDS", wl.num_pairs * wps)
    assert dtc.distributed_tc_count(sbf, wl, mesh) == want
    assert len(calls) == 1, len(calls)
    # One pair over: exactly two stripes/steps, still exact.
    calls.clear()
    monkeypatch.setattr(dtc, "INT32_SAFE_WORDS", (wl.num_pairs - 1) * wps)
    assert dtc.distributed_tc_count(sbf, wl, mesh) == want
    assert len(calls) == 2, len(calls)


def test_distributed_empty_worklist(monkeypatch):
    """Satellite: empty work lists count zero on every placement WITHOUT
    dispatching a psum step. The replicated path used to pad the empty list
    to one pair per shard, upload it, and run a full step; it must now
    early-return like the sharded paths' empty-schedule guard — asserted by
    intercepting the step factory, which must never even be built."""
    import jax

    from repro.core import build_sbf, build_worklist
    from repro.distributed import distributed_tc_count
    from repro.distributed import tc as dtc
    from repro.distributed.tc import _slice_worklist
    from repro.graphs import build_graph, rmat

    g = build_graph(rmat(200, 800, seed=2))
    sbf = build_sbf(g, 64)
    empty = _slice_worklist(build_worklist(g, sbf), 0, 0)
    assert empty.num_pairs == 0
    built = []
    monkeypatch.setattr(
        dtc, "make_tc_step", lambda *a: built.append(a) or (lambda *_: 1)
    )
    mesh = jax.make_mesh((1,), ("d",))
    assert distributed_tc_count(sbf, empty, mesh) == 0
    assert built == []  # no step traced, no dispatch
    assert distributed_tc_count(sbf, empty, mesh, placement="sharded_cols") == 0
    mesh2 = jax.make_mesh((1, 1), ("r", "c"))
    assert distributed_tc_count(sbf, empty, mesh2, placement="sharded_2d") == 0
