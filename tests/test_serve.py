"""Cross-graph fused serving: fusion planner, multi-graph executor, server.

The fused path's contract is bit-identical counts: fusing any mix of
graphs into shared dispatches must return exactly what the per-graph
``Executor`` loop returns — across every ``tcim_graphs`` config, empty and
tiny graphs, mixed pow2 buckets, and mixed placements — while retracing
once per batch shape and respecting the admission budget. Also pins the
``ExecutorPool`` eviction guard: evicting an executor with an unresolved
``CountFuture`` must never invalidate the result.
"""
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from repro.configs.tcim_graphs import GRAPHS
from repro.core import Executor, build_sbf, build_worklist
from repro.core.executor import ExecutorPool, MultiGraphExecutor
from repro.core.plan import plan_fusion, pow2_ceil
from repro.data.graph_pipeline import load_graph
from repro.graphs import build_graph, rmat
from repro.graphs.exact import triangles_intersection
from repro.launch.tc_serve import ServeConfig, TCServer

from graph_cases import GRAPH_CASES, GRAPH_IDS, num_vertices

SRC = str(Path(__file__).resolve().parents[1] / "src")


def _job(n, m, seed, slice_bits=64):
    g = build_graph(rmat(n, m, seed=seed))
    sbf = build_sbf(g, slice_bits)
    wl = build_worklist(g, sbf)
    return g, sbf, wl


@pytest.fixture(scope="module")
def mixed_jobs():
    """Heterogeneous mix spanning several pow2 pair buckets + a tiny graph."""
    jobs, want = [], []
    for i, (n, m) in enumerate(
        [(16, 24), (64, 300), (100, 700), (200, 1400), (400, 2500), (64, 320)]
    ):
        g, sbf, wl = _job(n, m, seed=i + 1)
        jobs.append((sbf, wl))
        want.append(triangles_intersection(g))
    return jobs, want


# ---------------------------------------------------------------------------
# Fusion planner
# ---------------------------------------------------------------------------


def test_plan_fusion_layout(mixed_jobs):
    jobs, _ = mixed_jobs
    plan = plan_fusion(jobs)
    assert plan.num_graphs == len(jobs)
    assert plan.padded_graphs == pow2_ceil(len(jobs))
    assert plan.bucket == pow2_ceil(max(wl.num_pairs for _, wl in jobs))
    ridx = plan.row_idx.reshape(plan.padded_graphs, plan.bucket)
    # Padded segments are all-sentinel; real segments carry offset indices.
    for i in range(plan.num_graphs, plan.padded_graphs):
        assert (ridx[i] == -1).all()
    for i, (sb, wl) in enumerate(jobs):
        n = wl.num_pairs
        np.testing.assert_array_equal(
            ridx[i, :n],
            np.asarray(wl.pair_row_pos[:n]) + plan.row_offsets[i],
        )
        assert (ridx[i, n:] == -1).all()


def test_plan_fusion_rejects_bad_groups(mixed_jobs):
    jobs, _ = mixed_jobs
    with pytest.raises(ValueError, match="at least one"):
        plan_fusion([])
    with pytest.raises(ValueError, match="max_bucket"):
        plan_fusion(jobs, max_bucket=1)
    g, sbf32, wl32 = _job(64, 300, seed=9, slice_bits=32)
    with pytest.raises(ValueError, match="words_per_slice"):
        plan_fusion([jobs[0], (sbf32, wl32)])


# ---------------------------------------------------------------------------
# MultiGraphExecutor: fused == per-graph loop, bit-identical
# ---------------------------------------------------------------------------


def test_fused_matches_loop_and_exact(mixed_jobs):
    jobs, want = mixed_jobs
    multi = MultiGraphExecutor()
    got = multi.count_fused(jobs)
    loop = tuple(Executor(sb, mode="jnp").count(wl) for sb, wl in jobs)
    assert got == loop == tuple(want)
    # Re-dispatch hits the batch cache and stays bit-identical.
    assert multi.count_fused(jobs) == got
    assert multi.stats()["hits"] == 1


def test_fused_handles_empty_and_tiny_graphs(mixed_jobs):
    jobs, want = mixed_jobs
    g_e = build_graph(np.zeros((0, 2), dtype=np.int64))
    sbf_e = build_sbf(g_e, 64)
    wl_e = build_worklist(g_e, sbf_e)
    assert wl_e.num_pairs == 0
    batch = [jobs[0], (sbf_e, wl_e), jobs[1]]
    got = MultiGraphExecutor().count_fused(batch)
    assert got == (want[0], 0, want[1])


def test_fused_order_and_subset_invariance(mixed_jobs):
    """Any permutation/subset fuses to the same per-graph counts."""
    jobs, want = mixed_jobs
    multi = MultiGraphExecutor()
    perm = [3, 0, 5, 2]
    got = multi.count_fused([jobs[i] for i in perm])
    assert got == tuple(want[i] for i in perm)


def test_fused_single_trace_for_shared_bucket():
    """Batches sharing (padded_graphs, bucket) share ONE jitted trace.

    The fused step is a module-level lru-cached jit shared across executor
    instances (and earlier tests), so the regression asserts on cache-size
    *deltas* around the counts, like the Executor retrace test.
    """
    mk = lambda seed: _job(200, 1200, seed=seed)[1:]
    multi = MultiGraphExecutor()
    a = [mk(s) for s in (1, 2, 3, 4)]
    b = [mk(s) for s in (5, 6, 7, 8)]
    pa, pb = multi.plan(a), multi.plan(b)
    assert (pa.padded_graphs, pa.bucket) == (pb.padded_graphs, pb.bucket)
    if multi.trace_count == -1:
        pytest.skip("private jit cache-size API unavailable on this jax")
    step = multi._step_for(pa.bucket)
    t0 = int(step._cache_size())
    multi.count_fused(a)
    t1 = int(step._cache_size())
    assert t1 - t0 <= 1  # one new batch shape -> at most one new trace
    multi.count_fused(b)  # same shape, different content: zero new traces
    assert int(step._cache_size()) == t1
    loop = tuple(Executor(sb, mode="jnp").count(wl) for sb, wl in b)
    assert multi.count_fused(b) == loop
    # A second executor reuses the shared trace outright.
    assert MultiGraphExecutor().count_fused(a) is not None
    assert int(step._cache_size()) == t1


@pytest.mark.parametrize("name", list(GRAPHS))
def test_server_matches_loop_on_bench_configs(name):
    """Every tcim_graphs config served fused == per-graph loop == exact."""
    cfg = GRAPHS[name].scaled(0.02)
    g, sbf, wl = load_graph(cfg, 64)
    want = triangles_intersection(g)
    srv = TCServer(ServeConfig(max_fused_pairs=1 << 18))
    (res,) = srv.serve([(sbf, wl)])
    assert res.status == "ok" and res.count == want, name


# ---------------------------------------------------------------------------
# TCServer: placements, admission, rejection
# ---------------------------------------------------------------------------


def test_server_mixed_placements(mixed_jobs):
    """Graphs over the fusion bound go solo; everything stays exact."""
    jobs, want = mixed_jobs
    cut = sorted(wl.num_pairs for _, wl in jobs)[len(jobs) // 2]
    srv = TCServer(ServeConfig(max_fused_pairs=cut))
    results = {r.request_id: r for r in srv.serve(jobs)}
    placements = {r.placement for r in results.values()}
    assert placements == {"fused", "replicated"}
    for rid, (sb, wl) in enumerate(jobs):
        assert results[rid].count == want[rid], rid
        expect = "fused" if wl.num_pairs <= cut else "replicated"
        assert results[rid].placement == expect, rid


def test_server_admission_waves_and_rejection(mixed_jobs):
    jobs, want = mixed_jobs
    foot = sorted(
        pow2_ceil(max(int(sb.row_slice_data.shape[0]), 1)) * 8
        + pow2_ceil(max(int(sb.col_slice_data.shape[0]), 1)) * 8
        + pow2_ceil(max(wl.num_pairs, 1)) * 8
        for sb, wl in jobs
    )
    budget = foot[-2]  # biggest graph can never fit; the rest wave through
    srv = TCServer(ServeConfig(memory_budget_bytes=budget))
    results = {r.request_id: r for r in srv.serve(jobs)}
    assert len(results) == len(jobs)  # nothing silently dropped
    rejected = [r for r in results.values() if r.status == "rejected"]
    assert len(rejected) >= 1
    assert all("exceeds budget" in r.detail for r in rejected)
    for rid, r in results.items():
        if r.status == "ok":
            assert r.count == want[rid]
    assert srv.stats["waves"] >= 2  # the budget forced multiple waves
    assert srv.stats["rejected"] == len(rejected)
    assert srv.pending == 0


def test_server_fuse_off_still_exact(mixed_jobs):
    jobs, want = mixed_jobs
    srv = TCServer(ServeConfig(fuse=False))
    results = {r.request_id: r for r in srv.serve(jobs)}
    assert all(r.placement == "replicated" for r in results.values())
    assert [results[i].count for i in range(len(jobs))] == want


def _case_job(edges):
    g = build_graph(edges, n=num_vertices(edges))
    sbf = build_sbf(g, 64)
    return (sbf, build_worklist(g, sbf)), triangles_intersection(g)


@pytest.mark.parametrize("name,edges", GRAPH_CASES, ids=GRAPH_IDS)
def test_server_mixed_wave_on_every_graph_shape(name, edges, mixed_jobs):
    """Each graph shape served inside one wave with the mixed jobs: its
    count and every other request's are exact."""
    jobs, want = mixed_jobs
    job, count = _case_job(edges)
    srv = TCServer(ServeConfig())
    results = {r.request_id: r for r in srv.serve([job] + jobs)}
    assert srv.stats["waves"] == 1
    assert [results[i].status for i in range(len(jobs) + 1)] == ["ok"] * (len(jobs) + 1)
    assert [results[i].count for i in range(len(jobs) + 1)] == [count] + want, name


@pytest.mark.parametrize("name,edges", GRAPH_CASES, ids=GRAPH_IDS)
def test_server_solo_unfused_on_every_graph_shape(name, edges):
    job, count = _case_job(edges)
    srv = TCServer(ServeConfig(fuse=False))
    (res,) = srv.serve([job])
    assert res.status == "ok" and res.count == count, name
    assert res.placement == "replicated"


def test_server_sharded_solo_placement():
    """With a mesh and a tiny shard threshold, solo requests go sharded —
    counts still exact (subprocess: 4 forced host devices)."""
    code = """
import numpy as np
import jax
from jax.sharding import Mesh
from repro.core import Executor, build_sbf, build_worklist
from repro.graphs import build_graph, rmat
from repro.launch.tc_serve import ServeConfig, TCServer

g = build_graph(rmat(400, 2500, seed=1))
sbf = build_sbf(g, 64)
wl = build_worklist(g, sbf)
want = Executor(sbf, mode='jnp').count(wl)
mesh = Mesh(np.asarray(jax.devices(), dtype=object).reshape(2, 2),
            ('rows', 'cols'))
srv = TCServer(ServeConfig(fuse=False, mesh=mesh, shard_above_bytes=1))
(res,) = srv.serve([(sbf, wl)])
assert res.status == 'ok' and res.count == want, (res.count, want)
assert res.placement.startswith('sharded'), res.placement
print('OK', res.placement)
"""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        env=env, timeout=560,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    assert "OK sharded" in out.stdout


# ---------------------------------------------------------------------------
# ExecutorPool eviction vs in-flight futures (regression)
# ---------------------------------------------------------------------------


def test_pool_eviction_defers_while_future_in_flight():
    """Evicting an executor with a pending CountFuture must not invalidate
    the result: the pool defers the eviction until the future resolves."""
    _, sbf_a, wl_a = _job(200, 1200, seed=1)
    _, sbf_b, wl_b = _job(200, 1200, seed=2)
    want_a = Executor(sbf_a, mode="jnp").count(wl_a)
    want_b = Executor(sbf_b, mode="jnp").count(wl_b)
    pool = ExecutorPool(max_graphs=1)
    fut_a = pool.count_async(sbf_a, wl_a)
    assert not fut_a.resolved
    # B's admission would evict A (capacity 1), but A has work in flight:
    # the pool transiently holds both rather than freeing A's stores.
    fut_b = pool.count_async(sbf_b, wl_b)
    assert len(pool._entries) == 2
    assert fut_a.result() == want_a  # the deferred eviction kept A valid
    assert fut_b.result() == want_b
    # With both futures resolved the next admission evicts down to bound.
    _, sbf_c, wl_c = _job(200, 1200, seed=3)
    assert pool.count(sbf_c, wl_c) == Executor(sbf_c, mode="jnp").count(wl_c)
    assert len(pool._entries) == 1


# ---------------------------------------------------------------------------
# Durable serving: WAL, kill/restore, eviction, compaction, isolation
# ---------------------------------------------------------------------------

import itertools
import threading

from repro.core import StreamingTCState
from repro.launch.tc_serve import StreamWAL
from repro.runtime.fault import FailureInjector


def _edge_pool(n, seed):
    """Every undirected edge on n vertices, shuffled — slicing it yields
    pairwise-disjoint batches (stream validation rejects re-adds)."""
    pool = np.array(list(itertools.combinations(range(n), 2)), dtype=np.int64)
    np.random.default_rng(seed).shuffle(pool)
    return pool


def _recount(edges, n):
    return StreamingTCState(edges, n=n).triangles


def test_stream_unknown_id_errors_and_budget_released_once():
    """close_stream/stream_count on an unknown id raise ValueError naming
    the id (like submit_delta); double-close releases the budget charge
    exactly once."""
    srv = TCServer(ServeConfig())
    pool = _edge_pool(20, 0)
    sid = srv.create_stream(pool[:40], n=20)
    charged = srv._stream_bytes
    assert charged > 0
    for bad_call in (srv.close_stream, srv.stream_count,
                     lambda i: srv.submit_delta(i, added=pool[40:42])):
        with pytest.raises(ValueError, match="999"):
            bad_call(999)
    assert srv._stream_bytes == charged  # failed calls charge nothing
    srv.close_stream(sid)
    assert srv._stream_bytes == 0
    with pytest.raises(ValueError, match=str(sid)):
        srv.close_stream(sid)  # double close: error, not a double release
    assert srv._stream_bytes == 0


def test_wal_torn_tail_truncates(tmp_path):
    """A kill mid-append leaves a torn last line; read_records keeps the
    intact prefix and drops everything at/after the corruption."""
    wal = StreamWAL(tmp_path / "s")
    wal.log_delta(0, [[0, 1]], None)
    wal.log_delta(1, [[1, 2]], None)
    wal.log_apply(0, 5)
    wal.close()
    good = StreamWAL.read_records(wal.path)
    assert [r[0] for r in good] == ["delta", "delta", "apply"]
    with wal.path.open("a") as fh:
        fh.write('deadbeef ["delta",2,9,[[3,4]],null]\n')  # bad crc
        fh.write("not a frame at all\n")
    assert StreamWAL.read_records(wal.path) == good
    # Torn tail mid-line too:
    with wal.path.open("a") as fh:
        fh.write("00aa")  # truncated frame, no newline
    assert StreamWAL.read_records(wal.path) == good


@pytest.mark.parametrize("kill_after", [1, 4, 8],
                         ids=["early", "middle", "late"])
def test_server_kill_and_restore_replays_to_exact_count(tmp_path, kill_after):
    """Kill-anywhere recovery: a server abandoned after ``kill_after``
    applied deltas (plus an undrained tail) restores to the exact live
    count, replaying <= checkpoint_every deltas, and drains the tail to
    the same final count a never-killed stream reaches."""
    n, cadence = 24, 3
    pool = _edge_pool(n, kill_after)
    srv = TCServer(ServeConfig(wal_dir=str(tmp_path), checkpoint_every=cadence))
    sid = srv.create_stream(pool[:50], n=n)
    batches = [pool[50 + 8 * i : 58 + 8 * i] for i in range(10)]
    for b in batches[:kill_after]:
        srv.submit_delta(sid, added=b)
    srv.drain()
    live = srv.stream_count(sid)
    for b in batches[kill_after:]:
        srv.submit_delta(sid, added=b)  # write-ahead logged, never drained
    srv._streams[sid].wal.snaps.wait()  # deterministic replay bound below
    del srv  # kill: no close_stream, no checkpoint()

    srv2 = TCServer.restore(tmp_path)
    info = srv2.restore_info["streams"][sid]
    assert srv2.stream_count(sid) == live
    assert info["replayed"] <= cadence
    assert info["requeued"] == len(batches) - kill_after
    assert srv2.pending == len(batches) - kill_after
    out = {r.request_id: r for r in srv2.drain()}
    assert all(r.status == "ok" for r in out.values())
    want = _recount(np.concatenate([pool[:50]] + batches), n)
    assert srv2.stream_count(sid) == want


def test_server_kill_minus_nine_subprocess(tmp_path):
    """End-to-end kill: a subprocess dies via os._exit (no atexit, no
    flush-on-close) mid-serving; the parent restores from its WAL root and
    recovers the exact pre-kill count plus the logged-but-undrained tail."""
    code = f"""
import itertools, os
import numpy as np
from repro.launch.tc_serve import ServeConfig, TCServer

pool = np.array(list(itertools.combinations(range(24), 2)), dtype=np.int64)
np.random.default_rng(7).shuffle(pool)
np.save({str(tmp_path)!r} + "/pool.npy", pool)
srv = TCServer(ServeConfig(wal_dir={str(tmp_path)!r}, checkpoint_every=3))
sid = srv.create_stream(pool[:60], n=24)
for i in range(5):
    srv.submit_delta(sid, added=pool[60 + 8 * i : 68 + 8 * i])
srv.drain()
print("LIVE", sid, srv.stream_count(sid), flush=True)
srv.submit_delta(sid, added=pool[100:108])  # logged, never drained
os._exit(9)  # hard kill: no destructors run
"""
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        env=env, timeout=560,
    )
    assert out.returncode == 9, out.stderr[-3000:]
    _, sid, live = out.stdout.split()
    sid, live = int(sid), int(live)
    pool = np.load(tmp_path / "pool.npy")
    srv = TCServer.restore(tmp_path)
    assert srv.stream_count(sid) == live
    assert srv.pending == 1
    assert all(r.status == "ok" for r in srv.drain())
    assert srv.stream_count(sid) == _recount(pool[:108], 24)


def test_crash_between_wal_append_and_snapshot_commit(tmp_path):
    """A kill after the WAL append but mid-snapshot leaves an uncommitted
    .tmp_step_* staging dir; restore ignores it (discovery only sees
    committed snapshots), replays from the last committed one to the exact
    pre-kill count, and GCs the orphan."""
    n = 24
    pool = _edge_pool(n, 11)
    srv = TCServer(ServeConfig(wal_dir=str(tmp_path), checkpoint_every=2))
    sid = srv.create_stream(pool[:50], n=n)
    for i in range(5):
        srv.submit_delta(sid, added=pool[50 + 6 * i : 56 + 6 * i])
    srv.drain()
    live = srv.stream_count(sid)
    sdir = srv._streams[sid].wal.directory
    srv._streams[sid].wal.snaps.wait()
    del srv
    # Plant the crash artifact: a staged-but-uncommitted snapshot.
    orphan = sdir / "snap" / ".tmp_step_00000099"
    orphan.mkdir()
    (orphan / "leaf_00000.npy").write_bytes(b"partial write")

    srv2 = TCServer.restore(tmp_path)
    info = srv2.restore_info["streams"][sid]
    assert info["orphans_gc"] >= 1
    assert not orphan.exists()
    assert srv2.stream_count(sid) == live
    assert info["replayed"] <= 2


def test_server_fault_injected_soak():
    """Fault-injected drain waves: a transient failure recovers via the
    bounded retry, a hard failure reports status='error' — and NEITHER
    changes any other request's count (failure isolation)."""
    jobs, want = [], []
    for i in range(8):
        g, sbf, wl = _job(64, 350, seed=40 + i)
        jobs.append((sbf, wl))
        want.append(triangles_intersection(g))
    # rid 2 transient (fires once), rid 5 hard (outlives max_retries).
    inj = FailureInjector(fail_at_steps=(2,))
    inj2 = FailureInjector(fail_at_steps=(5,), repeats=99)

    srv = TCServer(ServeConfig(injector=inj, max_fused_pairs=1 << 12))
    res = sorted(srv.serve(jobs), key=lambda r: r.request_id)
    assert [r.count for r in res] == want  # transient: everything exact
    assert res[2].retries >= 1 and "recovered" in res[2].detail
    assert srv.stats["wave_failures"] >= 1

    srv = TCServer(ServeConfig(injector=inj2, max_fused_pairs=1 << 12,
                               max_retries=2, retry_backoff_s=0.0))
    res = sorted(srv.serve(jobs), key=lambda r: r.request_id)
    assert res[5].status == "error"
    assert "SimulatedFailure" in res[5].detail
    assert res[5].retries == 2
    for i, r in enumerate(res):
        if i != 5:
            assert r.status == "ok" and r.count == want[i], i
    assert srv.stats["errors"] == 1


def test_stream_delta_failure_isolated_and_durable(tmp_path):
    """A hard-failing delta errors without poisoning its neighbors, and the
    WAL's error marker makes restore bit-identical to the live server: the
    NACKed delta is consumed (the producer already knows it failed), the
    acknowledged neighbors survive."""
    n = 20
    pool = _edge_pool(n, 21)
    inj = FailureInjector(repeats=99)
    srv = TCServer(ServeConfig(wal_dir=str(tmp_path), injector=inj,
                               max_retries=1, retry_backoff_s=0.0))
    sid = srv.create_stream(pool[:40], n=n)
    r_ok1 = srv.submit_delta(sid, added=pool[40:46])
    r_bad = srv.submit_delta(sid, added=pool[46:52])
    r_ok2 = srv.submit_delta(sid, added=pool[52:58])
    inj.fail_at_steps = (r_bad,)
    res = {r.request_id: r for r in srv.drain()}
    assert res[r_ok1].status == "ok" and res[r_ok2].status == "ok"
    assert res[r_bad].status == "error"
    live = srv.stream_count(sid)
    assert live == _recount(
        np.concatenate([pool[:40], pool[40:46], pool[52:58]]), n)
    del srv
    srv2 = TCServer.restore(tmp_path)  # no injector this time
    assert srv2.pending == 0  # NACKed work is not resurrected
    assert srv2.stream_count(sid) == live  # bit-identical, hole and all


def test_stream_eviction_spill_readmit_count_preserving():
    """Under a tiny budget streams LRU-spill and transparently re-admit;
    every stream's count stays exact through arbitrary interleavings."""
    n = 26
    pools = [_edge_pool(n, 60 + i) for i in range(3)]
    # Budget sized off the actual footprint: holds two streams, not three.
    probe = StreamingTCState(pools[0][:48], n=n)
    cost = TCServer._stream_footprint(probe._sbf)
    budget = int(2.5 * cost)
    srv = TCServer(ServeConfig(memory_budget_bytes=budget))
    sids = [srv.create_stream(p[:48], n=n) for p in pools]
    st = srv.server_stats()
    assert st["streams_spilled"] >= 1  # the budget can't hold all three
    cursors = [48] * 3
    rng = np.random.default_rng(0)
    for _ in range(6):
        i = int(rng.integers(0, 3))
        srv.submit_delta(sids[i], added=pools[i][cursors[i] : cursors[i] + 6])
        cursors[i] += 6
        out = srv.drain()
        assert all(r.status == "ok" for r in out)
        for j, sid in enumerate(sids):
            assert srv.stream_count(sid) == _recount(pools[j][: cursors[j]], n)
    assert srv.server_stats()["readmits"] >= 1
    assert srv._stream_bytes <= budget


def test_stream_compaction_triggers_and_preserves_counts():
    """Remove-heavy streams compact once the zero-record ratio crosses
    compact_ratio; the rebuild preserves the running count exactly and
    later deltas stay exact."""
    n = 26
    pool = _edge_pool(n, 70)
    srv = TCServer(ServeConfig(compact_ratio=0.3))
    sid = srv.create_stream(pool[:90], n=n)
    for i in range(0, 70, 10):
        srv.submit_delta(sid, removed=pool[i : i + 10])
    out = srv.drain()
    assert all(r.status == "ok" for r in out)
    assert srv.stats["compactions"] >= 1
    assert srv.stream_count(sid) == _recount(pool[70:90], n)
    # Post-compaction deltas still exact (executor was rebuilt/adopted).
    srv.submit_delta(sid, added=pool[90:100])
    srv.drain()
    assert srv.stream_count(sid) == _recount(
        np.concatenate([pool[70:90], pool[90:100]]), n)


def test_server_daemon_multi_producer_threads():
    """Three producer threads share one server under serve_forever; every
    producer's counts are exact and stop() drains in-flight work."""
    srv = TCServer(ServeConfig(max_fused_pairs=1 << 12))
    daemon = threading.Thread(target=srv.serve_forever, daemon=True)
    daemon.start()
    errs = []

    def producer(tid):
        try:
            for i in range(3):
                g, sbf, wl = _job(48, 220, seed=100 * tid + i)
                want = triangles_intersection(g)
                rid = srv.submit(sbf, wl)
                r = srv.wait_result(rid, timeout=60)
                assert r.status == "ok" and r.count == want, (r, want)
        except Exception as e:  # surfaced to the main thread below
            errs.append(e)

    threads = [threading.Thread(target=producer, args=(t,)) for t in range(3)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    srv.stop()
    daemon.join(timeout=60)
    assert not daemon.is_alive()
    assert not errs, errs


def test_server_resilience_wires_sharded_solo():
    """With ServeConfig.resilience set, sharded_2d solos run through the
    remesh-on-failure driver: an injected device loss mid-count recovers
    and the request still returns the exact count (subprocess: 4 forced
    host devices)."""
    code = """
import tempfile
import numpy as np
import jax
from jax.sharding import Mesh
from repro.core import Executor, build_sbf, build_worklist
from repro.distributed.resilient import ResilienceConfig
from repro.graphs import build_graph, rmat
from repro.launch.tc_serve import ServeConfig, TCServer
from repro.runtime.fault import FailureInjector

g = build_graph(rmat(400, 2500, seed=1))
sbf = build_sbf(g, 64)
wl = build_worklist(g, sbf)
want = Executor(sbf, mode='jnp').count(wl)
mesh = Mesh(np.asarray(jax.devices(), dtype=object).reshape(2, 2),
            ('rows', 'cols'))
res_cfg = ResilienceConfig(
    checkpoint_dir=tempfile.mkdtemp(), checkpoint_every=1,
    injector=FailureInjector(fail_at_steps=(1,)), lose_devices=0,
)
srv = TCServer(ServeConfig(fuse=False, mesh=mesh, shard_above_bytes=1,
                           chunk_pairs=256, resilience=res_cfg))
(res,) = srv.serve([(sbf, wl)])
assert res.status == 'ok' and res.count == want, (res.count, want)
assert res.placement == 'sharded_2d', res.placement
assert srv.stats['resilient_solos'] == 1, dict(srv.stats)
assert res_cfg.injector.failures == 1  # the loss really happened
print('OK resilient', res.count)
"""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        env=env, timeout=560,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    assert "OK resilient" in out.stdout
