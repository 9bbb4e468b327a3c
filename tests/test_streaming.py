"""Streaming incremental counting: bit-identical parity with recounts.

The invariant every test here pins: after ANY sequence of add/remove edge
batches, ``StreamingTCState.triangles`` (seed count + accumulated signed
deltas, O(touched pairs) per batch) equals a from-scratch count of the
final edge set — exactly, not approximately. Plus the systems properties
the delta path promises: steady-state batches add zero retraces, removals
keep records resident (zero rows), growth adopts new store buckets, and
malformed batches are rejected before any state mutates.
"""
import itertools
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from repro.configs.tcim_graphs import GRAPHS
from repro.core import (
    DeviceTopology,
    StreamingTCState,
    build_sbf,
    build_worklist_pairs,
    device_delta_worklist,
    plan_execution,
    replan_fixed,
    tcim_count,
    tcim_count_delta,
)
from repro.core.executor import scatter_update_trace_count
from repro.data.graph_pipeline import load_graph
from repro.graphs import build_graph, rmat
from repro.graphs.exact import triangles_intersection

from graph_cases import GRAPH_CASES, GRAPH_IDS, num_vertices

SRC = str(Path(__file__).resolve().parents[1] / "src")

# The full-config sweep caps each scaled fixture's edge count so 9 configs
# x 3 slice widths of multi-batch streaming stay a seconds-scale job.
_SWEEP_M_CAP = 20000


def _sweep_cfg(name):
    cfg = GRAPHS[name]
    return cfg.scaled(min(0.02, _SWEEP_M_CAP / cfg.m))


def _oracle(edges, n):
    return triangles_intersection(build_graph(edges, n=n, reorder=False))


@pytest.mark.parametrize("slice_bits", [32, 64, 128])
@pytest.mark.parametrize("name", list(GRAPHS))
def test_streaming_matches_oracle_after_every_batch(name, slice_bits):
    """Every tcim_graphs config x slice width: random add/remove batches,
    running count == independent recount after EVERY batch.

    Starts from ~85% of the fixture's edges; each round removes a random
    resident subset and adds a random absent subset (from the held-out
    pool plus earlier removals), so batches exercise growth, zero-record
    reuse, and mixed add+remove in one call.
    """
    cfg = _sweep_cfg(name)
    g, _, _ = load_graph(cfg, 64)
    rng = np.random.default_rng(cfg.seed + slice_bits)
    order = rng.permutation(g.m)
    cut = max(int(g.m * 0.85), 1)
    state = StreamingTCState(g.edges[order[:cut]], n=g.n,
                             slice_bits=slice_bits)
    assert state.triangles == _oracle(state.current_edges(), g.n)
    absent = {tuple(e) for e in g.edges[order[cut:]].tolist()}
    for _ in range(3):
        cur = state.current_edges()
        k_rm = min(max(len(cur) // 20, 1), len(cur))
        rm = cur[rng.permutation(len(cur))[:k_rm]]
        pool = np.array(sorted(absent), dtype=np.int64).reshape(-1, 2)
        k_ad = min(max(len(pool) // 2, 1), len(pool))
        ad = pool[rng.permutation(len(pool))[:k_ad]] if len(pool) else None
        res = state.apply_batch(added=ad, removed=rm)
        assert res.triangles == state.triangles
        assert state.triangles == _oracle(state.current_edges(), g.n)
        for e in rm.tolist():
            absent.add(tuple(e))
        if ad is not None:
            for e in ad.tolist():
                absent.discard(tuple(e))
    # And against the public end-to-end API on the final edge set.
    assert state.verify() == state.triangles


def _closing_batch(edges, n):
    """Absent edges that close triangles: up to eight wedges of the graph
    closed in place, a new vertex ``n`` joined to both ends of the first
    three edges, and a triangle on the new vertices ``n .. n + 2``."""
    present = {(int(u), int(v)) for u, v in edges}
    nbrs: dict[int, list[int]] = {}
    for u, v in present:
        nbrs.setdefault(u, []).append(v)
        nbrs.setdefault(v, []).append(u)
    batch = set()
    for v in sorted(nbrs):
        for wedge in itertools.combinations(sorted(nbrs[v]), 2):
            if len(batch) < 8 and wedge not in present:
                batch.add(wedge)
    for u, v in edges[:3]:
        batch |= {(int(u), n), (int(v), n)}
    batch |= {(n, n + 1), (n, n + 2), (n + 1, n + 2)}
    return np.array(sorted(batch), dtype=np.int64)


@pytest.mark.parametrize("name,edges", GRAPH_CASES, ids=GRAPH_IDS)
@pytest.mark.parametrize("build", ["host", "device"])
def test_streaming_closes_and_reopens_triangles_on_every_graph_shape(name, edges, build):
    """Seeded from each graph shape, a batch that closes triangles and then
    its removal each leave the running count equal to a recount."""
    n = num_vertices(edges)
    batch = _closing_batch(edges, n)
    state = StreamingTCState(edges, n=n + 3, build=build)
    base = _oracle(edges, n + 3)
    assert state.triangles == base
    grown = _oracle(np.concatenate([edges, batch]), n + 3)
    assert grown > base
    assert state.apply_batch(added=batch).triangles == grown == state.triangles
    assert state.apply_batch(removed=batch).triangles == base == state.triangles


def test_tcim_count_delta_wrapper():
    g = build_graph(rmat(400, 2400, seed=3), reorder=False)
    state = StreamingTCState(g.edges[: g.m // 2], n=g.n)
    seed_count = state.triangles
    res = tcim_count_delta(state, edges_added=g.edges[g.m // 2:])
    assert res.triangles == state.triangles == _oracle(g.edges, g.n)
    assert seed_count + res.delta == res.triangles
    back = tcim_count_delta(state, edges_removed=g.edges[g.m // 2:])
    assert back.delta == -res.delta and back.triangles == seed_count


def test_empty_delta_is_noop():
    g = build_graph(rmat(300, 1800, seed=4), reorder=False)
    state = StreamingTCState(g.edges, n=g.n)
    before = state.triangles
    res = state.apply_batch()
    assert res.delta == 0 and res.touched_edges == 0
    assert state.triangles == before
    res = state.apply_batch(added=np.zeros((0, 2), np.int64), removed=[])
    assert res.delta == 0 and state.triangles == before


def test_remove_only_batches_and_readd():
    g = build_graph(rmat(300, 1800, seed=5), reorder=False)
    state = StreamingTCState(g.edges, n=g.n)
    seed_count = state.triangles
    rng = np.random.default_rng(0)
    rm = g.edges[rng.permutation(g.m)[: g.m // 3]]
    res = state.apply_batch(removed=rm)
    assert res.delta <= 0
    assert state.triangles == _oracle(state.current_edges(), g.n)
    # Removal keeps records resident as zero rows — re-adding the same
    # edges is a pure scatter (no growth) and restores the exact count.
    res2 = state.apply_batch(added=rm)
    assert not res2.grew
    assert state.triangles == seed_count


def test_remove_all_then_rebuild():
    g = build_graph(rmat(120, 600, seed=6), reorder=False)
    state = StreamingTCState(g.edges, n=g.n)
    state.apply_batch(removed=g.edges)
    assert state.triangles == 0 and state.num_edges == 0
    state.apply_batch(added=g.edges)
    assert state.triangles == _oracle(g.edges, g.n)
    assert state.verify() == state.triangles


def test_steady_state_batches_add_zero_retraces():
    """After a warmup cycle, same-bucket add/remove batches reuse every
    compiled trace: no executor retrace, no scatter retrace, no growth."""
    g = build_graph(rmat(500, 3000, seed=7), reorder=False)
    rng = np.random.default_rng(1)
    hold = g.edges[rng.permutation(g.m)[:200]]
    state = StreamingTCState(np.array(
        [e for e in g.edges.tolist() if e not in hold.tolist()],
        dtype=np.int64).reshape(-1, 2), n=g.n)
    state.apply_batch(added=hold)   # growth: records merge-inserted
    state.apply_batch(removed=hold)  # steady: records persist as zeros
    traces0 = state.executor.trace_count + scatter_update_trace_count()
    for _ in range(3):
        r1 = state.apply_batch(added=hold)
        r2 = state.apply_batch(removed=hold)
        assert not r1.grew and not r2.grew
    traces1 = state.executor.trace_count + scatter_update_trace_count()
    assert traces1 == traces0
    assert state.verify() == state.triangles


def test_batch_validation_rejects_before_mutating():
    g = build_graph(rmat(200, 1000, seed=8), reorder=False)
    state = StreamingTCState(g.edges, n=g.n)
    before = (state.triangles, state.num_edges)
    present = {tuple(e) for e in g.edges.tolist()}
    miss = next([0, v] for v in range(g.n - 1, 0, -1)
                if (0, v) not in present)
    cases = [
        dict(added=np.array([[5, 5]])),                      # self-loop
        dict(added=np.array([[1, 2], [2, 1]])),              # dup in batch
        dict(added=g.edges[:1]),                             # already present
        dict(removed=np.array([miss])),                      # absent
        dict(added=np.array([[0, g.n + 7]])),                # out of range
        dict(added=np.array([[3, 4]]),
             removed=np.array([[3, 4]])),                    # add ∩ remove
    ]
    for kw in cases:
        with pytest.raises(ValueError):
            state.apply_batch(**kw)
        assert (state.triangles, state.num_edges) == before
    assert state.verify() == state.triangles


def test_device_delta_worklist_matches_host():
    """The jitted delta-worklist enumerator returns the host pairs."""
    g = build_graph(rmat(400, 2400, seed=9), reorder=False)
    sb = build_sbf(g, 64)
    rng = np.random.default_rng(2)
    idx = rng.permutation(g.m)[:150]
    src = g.edges[idx, 0].astype(np.int64)
    dst = g.edges[idx, 1].astype(np.int64)
    host = build_worklist_pairs(src, dst, sb)
    dwl = device_delta_worklist(src, dst, sb)
    longest = int(np.diff(sb.col_ptr).max(initial=0))
    assert dwl.search_steps == longest.bit_length() > 0
    dev = dwl.to_host()
    assert np.array_equal(dev.pair_edge, host[0])
    assert np.array_equal(dev.pair_row_pos, host[1])
    assert np.array_equal(dev.pair_col_pos, host[2])


def test_streaming_device_build_path_parity():
    g = build_graph(rmat(600, 3600, seed=10), reorder=False)
    rng = np.random.default_rng(3)
    order = rng.permutation(g.m)
    state = StreamingTCState(g.edges[order[: g.m // 2]], n=g.n,
                             build="device")
    state.apply_batch(added=g.edges[order[g.m // 2:]])
    assert state.triangles == _oracle(g.edges, g.n)
    rm = g.edges[order[:100]]
    state.apply_batch(removed=rm)
    assert state.triangles == _oracle(state.current_edges(), g.n)


def test_replan_fixed_pins_bounds_and_placement():
    """replan_fixed re-plans a new worklist onto a plan's resident bounds
    (the per-batch path sharded streaming uses) and rejects non-sharded
    plans."""
    g = build_graph(rmat(800, 4800, seed=11), reorder=True)
    sb = build_sbf(g, 64)
    from repro.core import build_worklist

    wl = build_worklist(g, sb)
    topo = DeviceTopology(num_devices=8)
    plan = plan_execution(sb, wl, topo, placement="sharded_2d", grid=(4, 2))
    half = wl.__class__(
        pair_edge=wl.pair_edge[: wl.num_pairs // 2],
        pair_row_pos=wl.pair_row_pos[: wl.num_pairs // 2],
        pair_col_pos=wl.pair_col_pos[: wl.num_pairs // 2],
        m_edges=wl.m_edges,
        n_slices=wl.n_slices,
    )
    re = replan_fixed(plan, sb, half)
    assert re.split == "fixed"
    assert np.array_equal(re.row_bounds, plan.row_bounds)
    assert np.array_equal(re.col_bounds, plan.col_bounds)
    assert re.grid == plan.grid and re.num_shards == plan.num_shards
    assert re.total_pairs == half.num_pairs
    solo = plan_execution(sb, wl, DeviceTopology(num_devices=1))
    with pytest.raises(ValueError):
        replan_fixed(solo, sb, half)


def test_server_submit_delta_streaming():
    """TCServer hosts streams next to one-shot requests: deltas drain
    FIFO, rejected batches leave the stream untouched, budgets carry the
    stream's standing charge, and the final count matches the oracle."""
    from repro.launch.tc_serve import ServeConfig, TCServer

    g = build_graph(rmat(300, 1800, seed=12), reorder=False)
    rng = np.random.default_rng(4)
    order = rng.permutation(g.m)
    base, hold = g.edges[order[:-120]], g.edges[order[-120:]]

    server = TCServer(ServeConfig(mode="jnp", fuse=False))
    sid = server.create_stream(base, n=g.n)
    assert server.stream_count(sid) == _oracle(base, g.n)
    assert server.server_stats()["streams_resident"] == 1
    assert server.server_stats()["stream_bytes"] > 0

    r_add = server.submit_delta(sid, added=hold)
    r_bad = server.submit_delta(sid, added=hold[:1])  # now-duplicate edge
    results = {r.request_id: r for r in server.drain()}
    assert results[r_add].status == "ok"
    assert results[r_add].count == _oracle(g.edges, g.n)
    assert results[r_add].placement == "streaming"
    assert results[r_bad].status == "rejected"
    assert "present" in results[r_bad].detail
    assert server.stream_count(sid) == _oracle(g.edges, g.n)

    with pytest.raises(ValueError):
        server.submit_delta(sid + 999, added=hold)
    final = server.close_stream(sid)
    assert final == _oracle(g.edges, g.n)
    assert server.server_stats()["streams_resident"] == 0
    assert server.server_stats()["stream_bytes"] == 0

    # A stream that cannot fit the budget is refused outright.
    tiny = TCServer(ServeConfig(memory_budget_bytes=64))
    with pytest.raises(ValueError):
        tiny.create_stream(base, n=g.n)


# ------------------------------------------------------------- sharded

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


def test_sharded_streaming_delta_parity():
    """Sharded placement (4x2 mesh, resident Sharded2DExecutor): the delta
    path replans each batch against FIXED bounds and scatters store blocks
    in place; growth rebuilds the executor. Counts stay bit-identical to
    the oracle through adds, removes, and growth."""
    out = _run(
        """
import jax, numpy as np
from repro.core.streaming import StreamingTCState
from repro.graphs import build_graph, rmat
from repro.graphs.exact import triangles_intersection

g = build_graph(rmat(2000, 12000, seed=13), reorder=False)
rng = np.random.default_rng(5)
order = rng.permutation(g.m)
base, hold = g.edges[order[:-400]], g.edges[order[-400:]]
mesh = jax.make_mesh((4, 2), ('rows', 'cols'))
state = StreamingTCState(base, n=g.n, mesh=mesh)
def oracle(e):
    return triangles_intersection(build_graph(e, n=g.n, reorder=False))
assert state.triangles == oracle(base), 'seed'
ex0 = state.executor
res = state.apply_batch(added=hold)           # growth -> rebuilt executor
assert res.grew and state.executor is not ex0, 'growth must rebuild'
assert state.triangles == oracle(g.edges), 'after add'
ex1 = state.executor
res = state.apply_batch(removed=hold)         # steady -> in-place scatter
assert not res.grew and state.executor is ex1, 'steady must update in place'
assert state.triangles == oracle(base), 'after remove'
res = state.apply_batch(added=hold[:200], removed=base[:100])
assert state.triangles == oracle(state.current_edges()), 'mixed'
assert state.verify() == state.triangles
print('OK', state.triangles)
"""
    )
    assert "OK" in out


def test_sharded_update_stores_rejects_growth_and_bad_positions():
    out = _run(
        """
import jax, numpy as np
from repro.core import build_sbf, build_worklist, update_sbf
from repro.distributed import Sharded2DExecutor
from repro.graphs import build_graph, rmat

g = build_graph(rmat(1000, 6000, seed=14), reorder=False)
sb = build_sbf(g, 64)
mesh = jax.make_mesh((4, 2), ('rows', 'cols'))
ex = Sharded2DExecutor(sb, mesh, chunk_pairs=4096)
want = ex.count(build_worklist(g, sb))

# A batch whose records all exist: in-place scatter, count updates.
from repro.graphs.exact import triangles_intersection
rm = g.edges[:50]
upd = update_sbf(sb, None, rm)
assert not upd.grew
ex.update_stores(upd.sbf, upd.row_lanes, upd.col_lanes)
g2 = build_graph(
    np.array([e for e in g.edges.tolist() if e not in rm.tolist()],
             dtype=np.int64).reshape(-1, 2), n=g.n, reorder=False)
got = ex.count(build_worklist(g2, upd.sbf))
assert int(got) == triangles_intersection(g2), (got,)
# Growth (new records) must be refused — the caller rebuilds instead.
present = {tuple(e) for e in g.edges.tolist()}
grown = None
for v in range(g.n - 1, 0, -1):
    if (0, v) in present:
        continue
    cand = update_sbf(upd.sbf, np.array([[0, v]], np.int64), None)
    if cand.grew:
        grown = cand
        break
assert grown is not None, 'fixture never grew a record'
try:
    ex.update_stores(grown.sbf, grown.row_lanes, grown.col_lanes)
    raise SystemExit('growth not rejected')
except ValueError as e:
    assert 'grew' in str(e)
print('OK', int(got), int(want))
"""
    )
    assert "OK" in out


# ---------------------------------------------------------------------------
# Durability primitives: snapshot/restore, spill/readmit, compaction
# ---------------------------------------------------------------------------


def test_stream_snapshot_roundtrip_and_divergence_free():
    """snapshot_tree/from_snapshot round-trips a stream exactly: same
    count, and the restored stream tracks the original batch-for-batch."""
    g = build_graph(rmat(300, 1800, seed=31), reorder=False)
    rng = np.random.default_rng(2)
    order = rng.permutation(g.m)
    a = StreamingTCState(g.edges[order[: g.m // 2]], n=g.n)
    a.apply_batch(added=g.edges[order[g.m // 2 : 3 * g.m // 4]])
    tree, extra = a.snapshot_tree()
    b = StreamingTCState.from_snapshot(tree, extra)
    assert b.triangles == a.triangles
    assert b.num_edges == a.num_edges
    tail = g.edges[order[3 * g.m // 4 :]]
    ra = a.apply_batch(added=tail)
    rb = b.apply_batch(added=tail)
    assert (ra.triangles, ra.delta) == (rb.triangles, rb.delta)
    rm = g.edges[order[:100]]
    assert a.apply_batch(removed=rm).triangles == \
        b.apply_batch(removed=rm).triangles
    assert b.verify() == b.triangles


def test_stream_spill_and_readmit_preserve_count_and_results():
    """spill() drops the executor (host mirror authoritative);
    ensure_resident() rebuilds it without recounting, and post-readmit
    batches are exact."""
    g = build_graph(rmat(200, 1200, seed=32), reorder=False)
    state = StreamingTCState(g.edges[: g.m // 2], n=g.n)
    before = state.triangles
    assert state.resident
    state.spill()
    assert not state.resident
    assert state.triangles == before  # count never touched the executor
    assert state.ensure_resident()
    assert state.resident
    assert not state.ensure_resident()  # idempotent, reports no rebuild
    res = state.apply_batch(added=g.edges[g.m // 2 :])
    assert res.triangles == _oracle(state.current_edges(), g.n)
    # Auto-readmit: apply_batch on a spilled stream rebuilds transparently.
    state.spill()
    res = state.apply_batch(removed=g.edges[: g.m // 4])
    assert state.resident
    assert res.triangles == _oracle(state.current_edges(), g.n)


def test_stream_compaction_reclaims_records_and_preserves_count():
    """After heavy removal the zero-record ratio crosses the threshold;
    compact() rebuilds smaller stores with the identical count, and the
    compacted stream keeps streaming exactly."""
    g = build_graph(rmat(200, 1400, seed=33), reorder=False)
    state = StreamingTCState(g.edges, n=g.n)
    rng = np.random.default_rng(3)
    rm = g.edges[rng.permutation(g.m)[: (3 * g.m) // 4]]
    state.apply_batch(removed=rm)
    count = state.triangles
    ratio = state.zero_record_ratio()
    assert ratio > 0.3
    stats = state.compact()
    assert stats["records_after"] < stats["records_before"]
    assert state.triangles == count  # count-preserving rebuild
    assert state.zero_record_ratio() == 0.0
    assert state.triangles == _oracle(state.current_edges(), g.n)
    res = state.apply_batch(added=rm[:50])
    assert res.triangles == _oracle(state.current_edges(), g.n)
    assert state.verify() == state.triangles


@pytest.mark.parametrize("name", ["ego-facebook", "email-enron"])
def test_spill_snapshot_compact_invariants_on_bench_configs(name):
    """Property-style pass over real bench configs: at every step of a
    remove-heavy schedule, spill/readmit, snapshot/restore, and compaction
    all preserve the exact running count."""
    cfg = _sweep_cfg(name)
    g, _, _ = load_graph(cfg, 64)
    rng = np.random.default_rng(cfg.seed)
    state = StreamingTCState(g.edges, n=g.n)
    for step in range(3):
        cur = state.current_edges()
        rm = cur[rng.permutation(len(cur))[: max(len(cur) // 3, 1)]]
        state.apply_batch(removed=rm)
        want = _oracle(state.current_edges(), g.n)
        assert state.triangles == want
        state.spill()
        state.ensure_resident()
        assert state.triangles == want
        clone = StreamingTCState.from_snapshot(*state.snapshot_tree())
        assert clone.triangles == want
        if state.zero_record_ratio() >= 0.5:
            state.compact()
            assert state.triangles == want
