"""ExecutionPlan + ExecutorPool + double-buffered Executor regression tests.

The planner's contract: every work-list pair lands in exactly one stripe, on
the shard owning its column slice, with shard-local coordinates; chunk
buckets are pow2 and provably int32-safe. The pool's contract: two graphs
with an equal trace key add zero new traces. The double-buffered executor's
contract: bit-identical counts to the serial path on every worklist shape.
"""
import numpy as np
import pytest

from repro.core import (
    DeviceTopology,
    Executor,
    ExecutorPool,
    build_sbf,
    build_stripe_schedule,
    build_worklist,
    clamp_chunk_pairs,
    even_range_bounds,
    plan_execution,
    range_owners,
    weighted_range_bounds,
)
from repro.core.plan import LANE_BUCKET_FLOOR, lane_bucket, pow2_ceil
from repro.core.sbf import SlicedBitmap
from repro.graphs import build_graph, rmat
from repro.graphs.exact import triangles_intersection
from repro.kernels.ops import INT32_SAFE_WORDS


@pytest.fixture(scope="module")
def small_graph():
    edges = rmat(400, 2500, seed=1)
    g = build_graph(edges)
    sbf = build_sbf(g, 64)
    wl = build_worklist(g, sbf)
    return g, sbf, wl


def _fake_sbf(words_per_slice: int) -> SlicedBitmap:
    """A store-shaped SBF with zero valid slices (shape-only tests)."""
    return SlicedBitmap(
        slice_bits=words_per_slice * 32,
        n=1,
        n_slices=1,
        row_ptr=np.zeros(2, np.int64),
        row_slice_idx=np.zeros(0, np.int32),
        row_slice_data=np.zeros((0, words_per_slice), np.uint32),
        col_ptr=np.zeros(2, np.int64),
        col_slice_idx=np.zeros(0, np.int32),
        col_slice_data=np.zeros((0, words_per_slice), np.uint32),
    )


# --------------------------------------------------------------------- planner


def test_replicated_plan_single_stripe(small_graph):
    _, sbf, wl = small_graph
    plan = plan_execution(
        sbf, wl, DeviceTopology(num_devices=1), placement="auto"
    )
    assert plan.placement == "replicated"
    assert plan.num_shards == 1 and len(plan.stripes) == 1
    s = plan.stripes[0]
    np.testing.assert_array_equal(s.row_pos, wl.pair_row_pos.astype(np.int32))
    np.testing.assert_array_equal(s.col_pos, wl.pair_col_pos.astype(np.int32))


@pytest.mark.parametrize("shards", [2, 4, 7])
def test_sharded_stripes_partition_worklist(small_graph, shards):
    """Owner-grouped stripes: every pair exactly once, shard-local coords."""
    _, sbf, wl = small_graph
    plan = plan_execution(
        sbf,
        wl,
        DeviceTopology(num_devices=shards),
        placement="sharded_cols",
    )
    assert plan.placement == "sharded_cols"
    assert plan.num_shards == shards
    assert plan.total_pairs == wl.num_pairs
    per = plan.col_shard_rows
    rebuilt = []
    for s in plan.stripes:
        assert s.col_pos.min(initial=0) >= 0
        assert s.col_pos.max(initial=-1) < per  # strictly shard-local
        glob = s.col_pos.astype(np.int64) + s.shard * per
        assert glob.max(initial=-1) < len(sbf.col_slice_idx)
        rebuilt.append(np.stack([s.row_pos.astype(np.int64), glob], axis=1))
    rebuilt = np.concatenate(rebuilt)
    want = np.stack(
        [wl.pair_row_pos.astype(np.int64), wl.pair_col_pos.astype(np.int64)],
        axis=1,
    )
    # Same multiset of (row, col) pairs, any order.
    assert sorted(map(tuple, rebuilt)) == sorted(map(tuple, want))


# ------------------------------------------------------------ sharded_2d plan


def _rebuild_pairs_2d(plan):
    """Global (row, col) pairs from a sharded_2d plan's block-local stripes."""
    out = []
    for s in plan.stripes:
        assert s.shard == s.row_shard * plan.grid[1] + s.col_shard
        assert s.row_pos.min(initial=0) >= 0 and s.col_pos.min(initial=0) >= 0
        assert s.row_pos.max(initial=-1) < plan.row_shard_rows
        assert s.col_pos.max(initial=-1) < plan.col_shard_rows
        gr = s.row_pos.astype(np.int64) + plan.row_bounds[s.row_shard]
        gc = s.col_pos.astype(np.int64) + plan.col_bounds[s.col_shard]
        assert (gr < plan.row_bounds[s.row_shard + 1]).all()
        assert (gc < plan.col_bounds[s.col_shard + 1]).all()
        out.append(np.stack([gr, gc], axis=1))
    return np.concatenate(out)


def test_weighted_range_bounds_properties():
    """Weighted cuts are a monotone exact partition, balanced to within one
    record's weight, for arbitrary weight vectors (incl. empty/zero)."""
    rng = np.random.default_rng(0)
    for n, shards in [(0, 4), (1, 1), (3, 8), (100, 4), (1000, 7)]:
        w = rng.integers(0, 50, n).astype(np.int64)
        b = weighted_range_bounds(w, shards)
        assert b.shape == (shards + 1,)
        assert b[0] == 0 and b[-1] == n and (np.diff(b) >= 0).all()
        if n and w.sum():
            sums = [int(w[b[s]: b[s + 1]].sum()) for s in range(shards)]
            # No range exceeds the ideal share by more than one record.
            assert max(sums) <= -(-int(w.sum()) // shards) + int(w.max())
        owners = range_owners(b, np.arange(n))
        assert ((owners >= 0) & (owners < shards)).all()
        for s in range(shards):
            assert (owners[b[s]: b[s + 1]] == s).all()


@pytest.mark.parametrize("grid", [(1, 4), (2, 2), (4, 2)])
def test_sharded_2d_partition_exact_all_configs(grid):
    """Satellite property test: across every tcim_graphs config, weighted
    2-D partitioning is exact — stripe pair counts sum to the worklist
    total and every pair lands in exactly one (row_owner, col_owner) block
    with in-range block-local coordinates."""
    from repro.configs.tcim_graphs import GRAPHS
    from repro.data.graph_pipeline import load_graph

    topo = DeviceTopology(num_devices=grid[0] * grid[1])
    for name, cfg in GRAPHS.items():
        _, sbf, wl = load_graph(cfg.scaled(0.02), 64)
        plan = plan_execution(
            sbf, wl, topo, placement="sharded_2d", grid=grid
        )
        assert plan.placement == "sharded_2d" and plan.grid == grid
        assert plan.split == "weighted"
        assert plan.total_pairs == wl.num_pairs, name
        assert sum(plan.stats["stripe_pairs"]) == wl.num_pairs, name
        rebuilt = _rebuild_pairs_2d(plan)
        want = np.stack(
            [wl.pair_row_pos.astype(np.int64), wl.pair_col_pos.astype(np.int64)],
            axis=1,
        )
        # Same multiset of (row, col) pairs, any order: exactly-once mapping.
        assert sorted(map(tuple, rebuilt)) == sorted(map(tuple, want)), name


def test_weighted_split_imbalance_regression():
    """Satellite regression: on the degree-ordered bench graph the weighted
    split pins plan.imbalance <= 1.25 on grids where the contiguous even
    split gives >= 2x."""
    from repro.configs.tcim_graphs import GRAPHS
    from repro.data.graph_pipeline import load_graph

    _, sbf, wl = load_graph(GRAPHS["ego-facebook"], 64)
    for grid in [(1, 8), (2, 2), (4, 2)]:
        topo = DeviceTopology(num_devices=grid[0] * grid[1])
        even = plan_execution(
            sbf, wl, topo, placement="sharded_2d", grid=grid, split="even"
        )
        weighted = plan_execution(
            sbf, wl, topo, placement="sharded_2d", grid=grid, split="weighted"
        )
        assert even.imbalance >= 2.0, (grid, even.imbalance)
        assert weighted.imbalance <= 1.25, (grid, weighted.imbalance)


def test_sharded_2d_plan_validation(small_graph):
    _, sbf, wl = small_graph
    topo = DeviceTopology(num_devices=8)
    with pytest.raises(ValueError, match="grid"):
        plan_execution(sbf, wl, topo, placement="sharded_2d")
    with pytest.raises(ValueError, match="sharded_2d"):
        plan_execution(
            sbf, wl, topo, placement="sharded_cols", split="weighted"
        )
    with pytest.raises(ValueError, match="num_shards"):
        plan_execution(
            sbf, wl, topo, placement="sharded_2d", grid=(4, 2), num_shards=4
        )
    with pytest.raises(ValueError, match="split"):
        plan_execution(
            sbf, wl, topo, placement="sharded_2d", grid=(4, 2), split="best"
        )
    with pytest.raises(ValueError, match="together"):
        plan_execution(
            sbf, wl, topo, placement="sharded_2d", grid=(4, 2),
            row_bounds=np.array([0, len(sbf.row_slice_idx)]),
        )
    with pytest.raises(ValueError, match="monotone"):
        plan_execution(
            sbf, wl, topo, placement="sharded_2d", grid=(1, 2),
            row_bounds=np.array([0, 5]),  # wrong end for 1 row shard
            col_bounds=even_range_bounds(len(sbf.col_slice_idx), 2),
        )


def test_sharded_2d_fixed_bounds_roundtrip(small_graph):
    """Caller-pinned bounds reproduce the weighted plan's stripes exactly —
    the executor's re-plan-new-worklists-against-resident-stores contract."""
    _, sbf, wl = small_graph
    topo = DeviceTopology(num_devices=8)
    base = plan_execution(sbf, wl, topo, placement="sharded_2d", grid=(4, 2))
    pinned = plan_execution(
        sbf, wl, topo, placement="sharded_2d", grid=(4, 2),
        row_bounds=base.row_bounds, col_bounds=base.col_bounds,
    )
    assert pinned.split == "fixed"
    assert np.array_equal(pinned.row_bounds, base.row_bounds)
    assert np.array_equal(pinned.col_bounds, base.col_bounds)
    for a, b in zip(base.stripes, pinned.stripes):
        np.testing.assert_array_equal(a.row_pos, b.row_pos)
        np.testing.assert_array_equal(a.col_pos, b.col_pos)


def test_auto_placement_thresholds(small_graph):
    _, sbf, wl = small_graph
    multi = DeviceTopology(num_devices=8)
    # Tiny store on a big mesh stays replicated under the default threshold…
    plan = plan_execution(sbf, wl, multi, placement="auto")
    assert plan.placement == "replicated"
    # …and shards once the store exceeds the (here: forced) threshold.
    plan = plan_execution(sbf, wl, multi, placement="auto", shard_above_bytes=1)
    assert plan.placement == "sharded_cols"
    # A genuinely 2-D grid steers auto to the 2-D owner-grid placement…
    plan = plan_execution(
        sbf, wl, multi, placement="auto", shard_above_bytes=1, grid=(4, 2)
    )
    assert plan.placement == "sharded_2d"
    # …but a degenerate grid (one axis) stays 1-D.
    plan = plan_execution(
        sbf, wl, multi, placement="auto", shard_above_bytes=1, grid=(8, 1)
    )
    assert plan.placement == "sharded_cols"
    # Single device can never shard.
    single = DeviceTopology(num_devices=1)
    plan = plan_execution(sbf, wl, single, placement="auto", shard_above_bytes=1)
    assert plan.placement == "replicated"


def test_chunk_bucket_pow2_and_int32_safe(small_graph):
    _, sbf, wl = small_graph
    for req in (1, 7, 300, 1 << 20, 1 << 40):
        plan = plan_execution(
            sbf, wl, DeviceTopology(num_devices=1), chunk_pairs=req
        )
        c = plan.chunk_pairs
        assert c & (c - 1) == 0 and c <= req
        assert c * sbf.words_per_slice * 32 <= 2**31 - 1


@pytest.mark.parametrize(
    "x,want",
    [(1, 1), (3, 4), (1000, 1024), (LANE_BUCKET_FLOOR - 1, LANE_BUCKET_FLOOR),
     (LANE_BUCKET_FLOOR, LANE_BUCKET_FLOOR),
     (LANE_BUCKET_FLOOR + 1, LANE_BUCKET_FLOOR + (1 << 17)),
     (3 << 19, 3 << 19), ((3 << 19) + 1, (3 << 19) + (1 << 17)),
     ((1 << 21) - 1, 1 << 21), (44_510_150, 46_137_344), (1 << 26, 1 << 26),
     ((1 << 26) + 1, (1 << 26) + (1 << 23)), (2**31 - 2**16, 2**31)],
)
def test_lane_bucket(x, want):
    """pow2 below the floor; from it up eight buckets an octave, padding
    under 1/8; covering, monotone, idempotent, powers of two fixed."""
    b = lane_bucket(x)
    assert b == want >= x
    assert lane_bucket(b) == b
    assert lane_bucket(max(x - 1, 1)) <= b <= lane_bucket(x + 1)
    if x & (x - 1) == 0:
        assert b == x
    if x < LANE_BUCKET_FLOOR:
        assert b == pow2_ceil(x)
    else:
        assert 8 * (b - x) < x


def test_clamp_chunk_pairs_overflow_raises():
    """Satellite: words_per_slice > INT32_SAFE_WORDS used to crash with
    ``1 << -1``; it must now raise a clear ValueError instead."""
    with pytest.raises(ValueError, match="words_per_slice"):
        clamp_chunk_pairs(1 << 20, INT32_SAFE_WORDS + 1)
    with pytest.raises(ValueError, match="chunk_pairs"):
        clamp_chunk_pairs(0, 2)
    # Boundary: exactly INT32_SAFE_WORDS words is still a legal 1-pair chunk.
    assert clamp_chunk_pairs(1 << 20, INT32_SAFE_WORDS) == 1


def test_executor_rejects_overflowing_words_per_slice():
    """Executor.__init__ regression: giant slices raise, not ``1 << -1``."""
    with pytest.raises(ValueError, match="words_per_slice"):
        Executor(_fake_sbf(INT32_SAFE_WORDS + 1))


# ------------------------------------------------------------- stripe schedule


def _assert_schedule_covers(sched, lens):
    """Every stripe consumed exactly once, in order, within the budget."""
    cursors = [0] * len(lens)
    for step in sched.steps:
        assert step.bucket & (step.bucket - 1) == 0  # pow2 window width
        assert max(step.lens, default=0) <= step.bucket
        for s, n in enumerate(step.lens):
            if n:
                assert step.starts[s] == cursors[s], (s, step)
                cursors[s] += n
    assert cursors == [int(x) for x in lens], cursors
    assert sched.total_pairs == sum(lens)


def test_stripe_schedule_validation():
    with pytest.raises(ValueError, match="schedule"):
        build_stripe_schedule([1, 2], 8, policy="greedy")
    with pytest.raises(ValueError, match=">= 0"):
        build_stripe_schedule([1, -2], 8)
    assert build_stripe_schedule([], 8).num_steps == 0
    assert build_stripe_schedule([0, 0, 0], 8).num_steps == 0
    assert build_stripe_schedule([0, 0], 8, policy="lockstep").num_steps == 0


def test_stripe_schedule_lockstep_matches_legacy_windows():
    """The lockstep policy reproduces the shared-window walk: per-shard
    window = budget // num_shards, ceil(longest/window) steps, every stripe
    sliced at the same [start, start+window) offsets."""
    lens = [5, 17, 3]
    sched = build_stripe_schedule(lens, budget=12, policy="lockstep")
    # window = 12 // 3 = 4 -> ceil(17/4) = 5 steps.
    assert sched.num_steps == 5
    assert [s.starts for s in sched.steps] == [
        (0, 0, 0), (4, 4, 3), (5, 8, 3), (5, 12, 3), (5, 16, 3)
    ]
    assert [s.lens for s in sched.steps] == [
        (4, 4, 3), (1, 4, 0), (0, 4, 0), (0, 4, 0), (0, 1, 0)
    ]
    _assert_schedule_covers(sched, lens)


def test_stripe_schedule_packed_respects_budget_and_covers():
    """Property sweep: packed and lockstep both consume every stripe exactly
    once; packed never exceeds the per-step real-pair budget (beyond the
    width-1 progress floor) and never takes more steps than lockstep."""
    rng = np.random.default_rng(7)
    cases = [
        ([0], 4), ([9], 4), ([1, 1, 1, 1], 1), ([1000, 0, 0, 0], 64),
        ([3, 1000, 3, 3], 64),
    ]
    for _ in range(20):
        n = int(rng.integers(1, 12))
        lens = rng.integers(0, 300, n).tolist()
        budget = int(rng.integers(1, 256))
        cases.append((lens, budget))
    for lens, budget in cases:
        lock = build_stripe_schedule(lens, budget, policy="lockstep")
        pack = build_stripe_schedule(lens, budget, policy="packed")
        _assert_schedule_covers(lock, lens)
        _assert_schedule_covers(pack, lens)
        assert pack.num_steps <= lock.num_steps, (lens, budget)
        active_floor = sum(1 for x in lens if x)  # width-1 floor worst case
        for step in pack.steps:
            assert step.real_pairs <= max(budget, active_floor), (lens, budget)


def test_stripe_schedule_packed_reduces_steps_on_imbalanced_fixture():
    """Acceptance fixture: one block holds 4x the pairs of the other seven
    (a fixed-bounds replan shape). Packed drops the psum step count >= 30%
    below lockstep — here 4x: drained shards stop consuming the budget."""
    lens = [4096] + [512] * 7
    lock = build_stripe_schedule(lens, 1024, policy="lockstep")
    pack = build_stripe_schedule(lens, 1024, policy="packed")
    assert lock.num_steps == 32  # ceil(4096 / (1024 // 8))
    assert pack.num_steps == 8  # ~ceil(total / budget): the packing bound
    assert pack.num_steps <= 0.7 * lock.num_steps
    _assert_schedule_covers(pack, lens)


def test_stripe_schedule_memory_bound_regression():
    """Satellite regression: the pre-schedule driver used chunk_pairs as the
    PER-SHARD window, staging num_shards * chunk real pairs per step. The
    budget now bounds the step's total real pairs, shard count included."""
    lens = [256] * 8
    for policy in ("packed", "lockstep"):
        sched = build_stripe_schedule(lens, 256, policy=policy)
        assert sched.max_step_pairs <= 256, policy
        # The old behaviour would have packed all 8 * 256 pairs in one step.
        assert sched.num_steps >= 8, policy


def test_stripe_schedule_emit_matches_stripes(small_graph):
    """Emission contract: the flat per-step arrays are [S * bucket] int32,
    sentinel-padded, and reassemble every owner stripe exactly — for both
    policies, on a real owner-grouped plan."""
    _, sbf, wl = small_graph
    plan = plan_execution(
        sbf,
        wl,
        DeviceTopology(num_devices=4),
        placement="sharded_cols",
        chunk_pairs=512,
    )
    lens = [s.num_pairs for s in plan.stripes]
    for policy in ("packed", "lockstep"):
        sched = build_stripe_schedule(lens, 512, policy=policy)
        seen = [([], []) for _ in plan.stripes]
        for (ridx, cidx), step in zip(sched.emit(plan.stripes), sched.steps):
            assert ridx.dtype == np.int32 and cidx.dtype == np.int32
            assert ridx.shape == cidx.shape == (4 * step.bucket,)
            r2 = ridx.reshape(4, step.bucket)
            c2 = cidx.reshape(4, step.bucket)
            real = int((r2 >= 0).sum())
            assert real == step.real_pairs
            assert ((r2 >= 0) == (c2 >= 0)).all()
            for s in range(4):
                n = step.lens[s]
                assert (r2[s, n:] == -1).all() and (c2[s, n:] == -1).all()
                seen[s][0].extend(r2[s, :n].tolist())
                seen[s][1].extend(c2[s, :n].tolist())
        for s, stripe in enumerate(plan.stripes):
            np.testing.assert_array_equal(seen[s][0], stripe.row_pos)
            np.testing.assert_array_equal(seen[s][1], stripe.col_pos)
    with pytest.raises(ValueError, match="stripes"):
        next(build_stripe_schedule(lens, 512).emit(plan.stripes[:2]))


# ------------------------------------------------------------------- executor


def test_double_buffered_matches_serial(small_graph):
    """Buffered and serial paths are semantics-identical on ragged, empty,
    and multi-chunk worklists (single-end-sync contract unchanged)."""
    g, sbf, wl = small_graph
    want = triangles_intersection(g)
    buf = Executor(sbf, chunk_pairs=256, double_buffer=True)
    ser = Executor(sbf, chunk_pairs=256, double_buffer=False)
    assert wl.num_pairs > 4 * 256  # genuinely multi-chunk
    assert buf.count(wl) == ser.count(wl) == want
    empty = np.zeros(0, np.int64)
    assert buf.execute_indices(empty, empty) == 0
    for sub in (1, 3, 255, 256, 257, wl.num_pairs - 1):
        r, c = wl.pair_row_pos[:sub], wl.pair_col_pos[:sub]
        assert buf.execute_indices(r, c) == ser.execute_indices(r, c), sub


def test_store_pow2_padding_is_noop(small_graph):
    g, sbf, wl = small_graph
    want = triangles_intersection(g)
    padded = Executor(sbf, pad_stores_pow2=True)
    exact = Executor(sbf, pad_stores_pow2=False)
    assert padded.count(wl) == exact.count(wl) == want
    rows = padded.row_data.shape[0]
    assert rows & (rows - 1) == 0  # genuinely bucketed


# ----------------------------------------------------------------------- pool


def _same_bucket_graphs():
    """Two *different* graphs that land in identical trace buckets."""
    out = []
    for seed in (1, 7):
        g = build_graph(rmat(400, 2500, seed=seed))
        sbf = build_sbf(g, 64)
        out.append((g, sbf, build_worklist(g, sbf)))
    k0 = ExecutorPool.trace_key(out[0][1], chunk_pairs=256)
    k1 = ExecutorPool.trace_key(out[1][1], chunk_pairs=256)
    assert k0 == k1, (k0, k1)  # precondition for the zero-trace guarantee
    return out


def test_pool_identity_hit_and_lru_eviction(small_graph):
    _, sbf, _ = small_graph
    pool = ExecutorPool(max_graphs=1)
    e1 = pool.get(sbf)
    assert pool.get(sbf) is e1 and pool.hits == 1
    other = build_sbf(build_graph(rmat(100, 500, seed=3)), 64)
    pool.get(other)
    assert len(pool) == 1  # LRU evicted the first graph's stores
    assert pool.get(sbf) is not e1  # re-admitted fresh
    assert pool.stats()["graphs"] == 1


def test_pool_zero_new_traces_across_graphs():
    """Acceptance: counting a second graph with an equal (words_per_slice,
    bucket, mode, store-bucket) key adds zero new traces."""
    (g1, sbf1, wl1), (g2, sbf2, wl2) = _same_bucket_graphs()
    pool = ExecutorPool()
    e1 = pool.get(sbf1, chunk_pairs=256)
    # Count in fixed 256-buckets on both graphs: prefixes are multiples of
    # 256, so every chunk shape the second count sees, the first traced.
    n1 = (wl1.num_pairs // 256) * 256
    n2 = (wl2.num_pairs // 256) * 256
    assert n1 > 0 and n2 > 0
    r1 = e1.execute_indices(wl1.pair_row_pos[:n1], wl1.pair_col_pos[:n1])
    if e1.trace_count == -1:
        pytest.skip("jit cache size API unavailable on this jax")
    before = e1.trace_count
    e2 = pool.get(sbf2, chunk_pairs=256)
    assert e2 is not e1
    r2 = e2.execute_indices(wl2.pair_row_pos[:n2], wl2.pair_col_pos[:n2])
    assert e2.trace_count - before == 0
    assert r1 != r2 or g1.m != g2.m  # genuinely different graphs/counts
    stats = pool.stats()
    assert stats["trace_groups"] == 1 and stats["graphs"] == 2


def test_pool_distinct_modes_do_not_collide(small_graph):
    g, sbf, wl = small_graph
    want = triangles_intersection(g)
    pool = ExecutorPool()
    assert pool.get(sbf, mode="fused").count(wl) == want
    assert pool.get(sbf, mode="jnp").count(wl) == want
    assert len(pool) == 2


def test_pool_distinct_executor_kwargs_do_not_collide(small_graph):
    """Review regression: config kwargs are part of the cache key — a
    serial-path request must never be handed the buffered executor."""
    _, sbf, _ = small_graph
    pool = ExecutorPool()
    buffered = pool.get(sbf)
    serial = pool.get(sbf, double_buffer=False)
    assert buffered is not serial
    assert buffered.double_buffer and not serial.double_buffer
    assert pool.get(sbf, double_buffer=False) is serial  # still a hit


def test_pool_content_key_hits_across_rebuilt_sbf(small_graph):
    """Review regression: the pool keys by store content, so the one-shot
    API (fresh SlicedBitmap per call) actually hits on a recount."""
    from repro.core import tcim_count_graph

    g, sbf, _ = small_graph
    pool = ExecutorPool()
    r1 = tcim_count_graph(g, pool=pool)
    r2 = tcim_count_graph(g, pool=pool)  # rebuilds the SBF internally
    assert r1.triangles == r2.triangles
    assert pool.hits >= 1 and len(pool) == 1
    # An identical-content rebuild of the SBF hits the same entry.
    rebuilt = build_sbf(g, 64)
    assert rebuilt is not sbf
    assert pool.get(rebuilt) is pool.get(sbf)


def test_pool_trace_key_honors_pad_stores_pow2():
    """Satellite regression: with pad_stores_pow2=False the executor traces
    on EXACT store shapes, so the trace key (and stats()) must report those
    — not the pow2 buckets — or trace sharing is overstated."""
    g1 = build_graph(rmat(400, 2500, seed=1))
    g2 = build_graph(rmat(400, 2500, seed=7))
    sbf1, sbf2 = build_sbf(g1, 64), build_sbf(g2, 64)
    # Same pow2 bucket, different exact valid-slice counts.
    assert sbf1.row_slice_data.shape[0] != sbf2.row_slice_data.shape[0]
    assert ExecutorPool.trace_key(sbf1) == ExecutorPool.trace_key(sbf2)
    k1 = ExecutorPool.trace_key(sbf1, pad_stores_pow2=False)
    k2 = ExecutorPool.trace_key(sbf2, pad_stores_pow2=False)
    assert k1 != k2
    assert k1[-2:] == sbf1.row_slice_data.shape[:1] + sbf1.col_slice_data.shape[:1]
    # stats() must see two trace groups for unpadded executors...
    pool = ExecutorPool()
    pool.get(sbf1, pad_stores_pow2=False)
    pool.get(sbf2, pad_stores_pow2=False)
    assert pool.stats()["trace_groups"] == 2
    # ...where padded executors genuinely share one.
    pool.clear()
    pool.get(sbf1)
    pool.get(sbf2)
    assert pool.stats()["trace_groups"] == 1


def test_count_async_matches_count(small_graph):
    """count_async == count bit-identically (Executor + pool), the future
    is idempotent, and empty work lists resolve to 0 with no dispatch."""
    g, sbf, wl = small_graph
    want = triangles_intersection(g)
    ex = Executor(sbf, chunk_pairs=256)
    fut = ex.count_async(wl)
    assert fut.result() == want == ex.count(wl)
    assert fut.result() == want  # idempotent
    empty = np.zeros(0, np.int64)
    assert ex.execute_indices_async(empty, empty).result() == 0
    pool = ExecutorPool()
    futures = [pool.count_async(sbf, wl) for _ in range(3)]  # overlap shape
    assert [f.result() for f in futures] == [want] * 3
    assert pool.count(sbf, wl) == want
    assert len(pool) == 1  # all four counts hit one pooled executor


def test_auto_placement_without_mesh_stays_replicated(small_graph):
    """Review regression: 'auto' with no mesh must resolve to replicated
    (nothing to shard over), never raise the needs-a-mesh error."""
    from repro.core import tcim_count_graph

    g, _, _ = small_graph
    res = tcim_count_graph(g, placement="auto")
    assert res.stats["placement"] == "replicated"
