"""Device build pipeline: bit-identical to the NumPy reference, zero bounces.

The jitted orient -> SBF -> worklist front end (core.build) must reproduce
``build_graph``/``build_sbf``/``build_worklist`` exactly — same CSR offsets,
same valid-slice records, same worklist pairs in the same order — on every
bench-graph config and slice width, while performing exactly one
host->device transfer and never retracing for a same-bucket rebuild.
"""
import dataclasses

import jax
import numpy as np
import pytest

from repro.configs.tcim_graphs import GRAPHS
from repro.core import (
    Executor,
    ExecutorPool,
    build_sbf,
    build_worklist,
    device_build,
    device_build_async,
    device_build_graph,
    device_build_sbf,
    device_build_trace_counts,
    device_build_worklist,
    device_delta_worklist,
    tcim_count,
    tcim_count_graph,
    tcim_vertex_counts,
)
from repro.core import plan as plan_mod
from repro.core.plan import lane_bucket, pow2_ceil
from repro.core.sbf import Worklist, _window_searchsorted, build_worklist_pairs
from repro.data.graph_pipeline import load_graph
from repro.graphs import build_graph, complete_graph, device_orient, rmat
from repro.graphs.exact import triangles_intersection

from graph_cases import GRAPH_CASES, GRAPH_IDS, num_vertices


def _assert_build_matches(g, slice_bits):
    """Device (sbf, worklist) == host reference, array for array."""
    sb_h = build_sbf(g, slice_bits)
    wl_h = build_worklist(g, sb_h)
    db = device_build_graph(g, slice_bits)
    sb_d = db.sbf.to_host()
    wl_d = db.worklist.to_host()
    assert db.sbf.row_valid == len(sb_h.row_slice_idx)
    assert db.sbf.col_valid == len(sb_h.col_slice_idx)
    assert db.worklist.num_pairs == wl_h.num_pairs
    pairs = [
        ("row_ptr", sb_d.row_ptr, sb_h.row_ptr),
        ("row_slice_idx", sb_d.row_slice_idx, sb_h.row_slice_idx),
        ("row_slice_data", sb_d.row_slice_data, sb_h.row_slice_data),
        ("col_ptr", sb_d.col_ptr, sb_h.col_ptr),
        ("col_slice_idx", sb_d.col_slice_idx, sb_h.col_slice_idx),
        ("col_slice_data", sb_d.col_slice_data, sb_h.col_slice_data),
        ("pair_edge", wl_d.pair_edge, wl_h.pair_edge),
        ("pair_row_pos", wl_d.pair_row_pos, wl_h.pair_row_pos),
        ("pair_col_pos", wl_d.pair_col_pos, wl_h.pair_col_pos),
    ]
    for name, got, want in pairs:
        assert got.dtype == want.dtype, name
        assert np.array_equal(got, want), name
    return db


@pytest.mark.parametrize("slice_bits", [32, 64, 128])
@pytest.mark.parametrize("name", list(GRAPHS))
def test_device_build_bit_identical_on_bench_configs(name, slice_bits):
    """Every tcim_graphs config x slice_bits: device build == NumPy build."""
    cfg = GRAPHS[name].scaled(0.02)
    g, _, _ = load_graph(cfg, 64)
    _assert_build_matches(g, slice_bits)


@pytest.mark.parametrize("reorder", [False, True])
def test_device_orient_matches_build_graph(reorder):
    edges = rmat(350, 2200, seed=11)
    g = build_graph(edges, reorder=reorder)
    dg = device_orient(edges, reorder=reorder)
    gh = dg.to_host()
    assert gh.n == g.n and gh.m == g.m
    assert np.array_equal(gh.edges, g.edges)
    assert np.array_equal(gh.indptr, g.indptr)
    assert np.array_equal(gh.indices, g.indices)


def test_device_build_from_edges_matches_reordered_host():
    """device_build(reorder=True) mirrors the full host front end."""
    edges = rmat(500, 3000, seed=7)
    g = build_graph(edges, reorder=True)
    db = device_build(edges, reorder=True)
    sb_h = build_sbf(g, 64)
    wl_h = build_worklist(g, sb_h)
    assert np.array_equal(db.sbf.to_host().row_slice_data, sb_h.row_slice_data)
    wl_d = db.worklist.to_host()
    assert np.array_equal(wl_d.pair_row_pos, wl_h.pair_row_pos)
    assert np.array_equal(wl_d.pair_col_pos, wl_h.pair_col_pos)


def test_granular_stages_match_host():
    """device_build_sbf + device_build_worklist (the unfused entry points)."""
    edges = rmat(300, 1500, seed=5)
    g = build_graph(edges, reorder=True)
    dg = device_orient(g.edges, n=g.n, reorder=False)
    dsb = device_build_sbf(dg, 64)
    dwl = device_build_worklist(dg, dsb)
    sb_h = build_sbf(g, 64)
    wl_h = build_worklist(g, sb_h)
    assert dsb.nvs == sb_h.nvs
    assert np.array_equal(dsb.to_host().col_slice_data, sb_h.col_slice_data)
    assert np.array_equal(dwl.to_host().pair_col_pos, wl_h.pair_col_pos)


def test_device_count_matches_exact_and_host():
    edges = rmat(400, 2500, seed=1)
    g = build_graph(edges, reorder=True)
    want = triangles_intersection(g)
    res = tcim_count(edges, build="device")
    assert res.triangles == want
    assert res.stats["build"] == "device"
    assert res.stats["placement"] == "replicated"
    for stage in ("orient", "compress", "schedule", "plan", "execute"):
        assert stage in res.timings_s, stage
    res_h = tcim_count(edges, build="host")
    assert res_h.stats["build"] == "host"
    assert "plan" in res_h.timings_s
    assert res_h.triangles == want


@pytest.mark.parametrize(
    "edges,n,want",
    [
        (np.zeros((0, 2), dtype=np.int64), 4, 0),
        (np.array([[0, 1]], dtype=np.int64), None, 0),
        (np.array([[0, 1], [0, 2], [1, 2]], dtype=np.int64), None, 1),
    ],
    ids=["empty", "single_edge", "triangle"],
)
def test_device_build_tiny_graphs(edges, n, want):
    assert tcim_count(edges, n=n, build="device").triangles == want


def _lane_graph():
    """A 64-vertex graph whose vertices 0-7, 20-21 and 40-41 are isolated, so
    an edge out of them has no candidate lanes (32-bit slices: 0-2 per edge)."""
    rng = np.random.default_rng(14)
    live = np.r_[8:20, 22:40, 42:64]
    pairs = np.sort(rng.choice(live, size=(400, 2)), axis=1)
    g = build_graph(np.unique(pairs[pairs[:, 0] < pairs[:, 1]], axis=0), n=64)
    return g, build_sbf(g, 32)


def _lane_case(name, g, sb):
    """Oriented edge subsets that put zero-candidate edges, padding and the
    candidate total where the lane->edge map can go wrong."""
    cnt = sb.row_ptr[g.edges[:, 0] + 1] - sb.row_ptr[g.edges[:, 0]]
    some = g.edges[cnt > 0]
    sink = np.array([[0, 30], [1, 31], [2, 50], [20, 44], [21, 45], [40, 60],
                     [41, 61]])
    if name == "leading_zero":
        return np.r_[sink[:3], some[:13]]
    if name == "zero_runs_in_middle":
        lo, mid, hi = (some[(some[:, 0] > a) & (some[:, 0] < b)]
                       for a, b in ((0, 20), (21, 40), (41, 64)))
        return np.r_[lo[:5], sink[3:5], mid[:4], sink[5:], hi[:6]]
    if name == "trailing_padded":
        return some[:13]
    if name == "pow2_total":
        ones = g.edges[cnt == 1]
        return np.r_[ones[:16], [[41, 63]]]
    return some[10:11]  # single_edge


@pytest.mark.parametrize(
    "case",
    ["leading_zero", "zero_runs_in_middle", "trailing_padded", "pow2_total",
     "single_edge"],
)
def test_lane_to_edge_map_edge_cases(case):
    """Both device worklist entry points equal the host pairs on subsets whose
    candidate lanes start late, skip runs of edges, end before the padding,
    fill their pow2 bucket exactly (starts at the bucket drop) or come from
    a single edge."""
    g, sb_h = _lane_graph()
    sb_d = device_build_graph(g, 32).sbf
    sub = np.asarray(_lane_case(case, g, sb_h), dtype=np.int64)
    dg = device_orient(sub, n=g.n, reorder=False)
    src, dst = (dg.to_host().edges[:, i] for i in (0, 1))
    assert np.array_equal(np.sort(sub, axis=0), np.sort(np.c_[src, dst], axis=0))
    cnt = sb_h.row_ptr[src + 1] - sb_h.row_ptr[src]
    m, total = len(src), int(cnt.sum())
    assert {
        "leading_zero": cnt[0] == 0 and cnt[-1] > 0,
        "zero_runs_in_middle": cnt[0] > 0 and cnt[-1] > 0
        and np.count_nonzero(np.diff((cnt == 0).astype(int)) == 1) == 2,
        "trailing_padded": m & (m - 1) != 0 and (cnt > 0).all(),
        "pow2_total": total & (total - 1) == 0 and m & (m - 1) != 0,
        "single_edge": m == 1,
    }[case]
    want = build_worklist_pairs(src, dst, sb_h)
    assert len(want[0]) > 0
    for got in (device_build_worklist(dg, sb_d).to_host(),
                device_delta_worklist(src, dst, sb_d).to_host()):
        assert got.num_pairs == len(want[0])
        assert np.array_equal(got.pair_edge, want[0])
        assert np.array_equal(got.pair_row_pos, want[1])
        assert np.array_equal(got.pair_col_pos, want[2])


def _hub_edges(spokes, n):
    """Triangles (u, u + 1, n - 1) for each spoke u: the top vertex's column
    window holds one record per distinct 32-bit slice among the spokes, every
    other window one."""
    h = n - 1
    return np.asarray(
        [[[u, u + 1], [u, h], [u + 1, h]] for u in spokes], dtype=np.int64
    ).reshape(-1, 2)


@pytest.mark.parametrize("longest", [0, 1, 7, 8, 9, 15, 16, 17, 20],
                         ids=lambda w: f"window{w}")
def test_search_steps_at_window_bit_lengths(longest):
    """The binary search runs the longest column window's bit length and
    stays exact at its off-by-one points: an empty column side, one slice,
    2^k - 1, 2^k, 2^k + 1, and a window of every slice (n_slices = 20)."""
    n = 640
    g = build_graph(_hub_edges(range(0, 32 * longest, 32), n), n=n)
    sb_h = build_sbf(g, 32)
    assert sb_h.n_slices == 20
    assert int(np.diff(sb_h.col_ptr).max(initial=0)) == longest
    wl_h = build_worklist(g, sb_h)
    got = [device_delta_worklist(g.edges[:, 0], g.edges[:, 1], sb_h)]
    if longest:  # the full build needs an edge
        got.append(_assert_build_matches(g, 32).worklist)
    for dwl in got:
        assert dwl.search_steps == longest.bit_length()
        wl = dwl.to_host()
        assert dwl.num_pairs == wl_h.num_pairs
        for name in ("pair_edge", "pair_row_pos", "pair_col_pos"):
            assert np.array_equal(getattr(wl, name), getattr(wl_h, name)), name


def test_one_transfer_before_execute():
    """The device build performs exactly ONE host->device transfer (the
    padded edge list) and no implicit transfers anywhere before the execute
    stage; its outputs are device-resident jax arrays end to end."""
    edges = rmat(300, 1800, seed=3)
    g = build_graph(edges, reorder=True)
    want = triangles_intersection(g)
    calls = []
    orig = jax.device_put

    def counting_put(*args, **kwargs):
        calls.append(1)
        return orig(*args, **kwargs)

    jax.device_put = counting_put
    try:
        # "disallow" blocks implicit transfers; the explicit device_put of
        # the edge list is the only permitted one.
        with jax.transfer_guard("disallow"):
            db = device_build(edges, reorder=True)
    finally:
        jax.device_put = orig
    assert len(calls) == 1, f"expected 1 host->device transfer, saw {len(calls)}"
    for arr in (
        db.sbf.row_slice_data,
        db.sbf.col_slice_data,
        db.worklist.pair_row_pos,
        db.worklist.pair_col_pos,
    ):
        assert isinstance(arr, jax.Array)
    assert db.sbf.is_device
    # The executor adopts the device stores and indices without a bounce.
    ex = Executor(db.sbf)
    assert ex.count(db.worklist) == want


def test_same_bucket_rebuild_adds_zero_traces():
    """A second graph in the same pow2 buckets reuses every build trace."""
    edges_a = rmat(400, 2500, seed=1)
    edges_b = rmat(400, 2500, seed=9)  # same n-bucket, same edge bucket
    db_a = device_build(edges_a, n=400)
    before = device_build_trace_counts()
    if -1 in before.values():
        pytest.skip("private jit cache-size API unavailable on this jax")
    db_b = device_build(edges_b, n=400)
    # Identical-size graphs always share the orient/sbf traces; the
    # worklist/prefix traces are shared when the data-dependent buckets
    # agree (arranged by the chosen seeds — verified here, not assumed).
    same_buckets = (
        db_a.sbf.row_slice_data.shape == db_b.sbf.row_slice_data.shape
        and db_a.sbf.col_slice_data.shape == db_b.sbf.col_slice_data.shape
        and db_a.worklist.pair_row_pos.shape == db_b.worklist.pair_row_pos.shape
        and db_a.worklist.num_candidates // max(db_b.worklist.num_candidates, 1) == 1
    )
    after = device_build_trace_counts()
    assert after["orient"] == before["orient"]
    assert after["sbf"] == before["sbf"]
    if same_buckets:
        assert after == before, (before, after)
    # Rebuilding the SAME graph is always a pure cache hit.
    device_build(edges_a, n=400)
    assert device_build_trace_counts() == after


def test_longest_window_change_adds_zero_traces():
    """Two same-bucket graphs whose longest column windows differ in bit
    length (7 -> 3 search steps, 9 -> 4) share one compiled worklist step:
    the trip count is traced, not static."""
    n = 512
    rng = np.random.default_rng(0)
    pairs = np.sort(rng.choice(np.arange(416, n - 1), size=(120, 2)), axis=1)
    filler = pairs[pairs[:, 0] < pairs[:, 1]]
    graphs = [
        build_graph(np.unique(np.r_[_hub_edges(spokes, n), filler], axis=0), n=n)
        for spokes in ([0, 32, 64, 96, 128, 160, 192, 2, 34], range(0, 288, 32))
    ]
    db_a = device_build_graph(graphs[0], 32)
    before = device_build_trace_counts()
    if -1 in before.values():
        pytest.skip("private jit cache-size API unavailable on this jax")
    db_b = device_build_graph(graphs[1], 32)
    assert (db_a.worklist.search_steps, db_b.worklist.search_steps) == (3, 4)
    assert db_a.sbf.row_slice_data.shape == db_b.sbf.row_slice_data.shape
    assert db_a.sbf.col_slice_data.shape == db_b.sbf.col_slice_data.shape
    assert db_a.worklist.pair_row_pos.shape == db_b.worklist.pair_row_pos.shape
    assert (pow2_ceil(db_a.worklist.num_candidates)
            == pow2_ceil(db_b.worklist.num_candidates))
    assert device_build_trace_counts() == before
    for g, db in zip(graphs, (db_a, db_b)):
        want = build_worklist(g, build_sbf(g, 32))
        assert np.array_equal(db.worklist.to_host().pair_col_pos,
                              want.pair_col_pos)


def test_device_build_async_overlaps():
    """build_async returns with the SBF dispatched; result() is idempotent
    and equal to the blocking build."""
    edges = rmat(300, 1500, seed=13)
    fut = device_build_async(edges, reorder=True)
    assert "compress" in fut.timings_s and "schedule" not in fut.timings_s
    db = fut.result()
    assert fut.result() is db
    assert "schedule" in db.timings_s
    blocking = device_build(edges, reorder=True)
    assert db.worklist.num_pairs == blocking.worklist.num_pairs
    g = build_graph(edges, reorder=True)
    assert Executor(db.sbf).count(db.worklist) == triangles_intersection(g)


def test_pool_keys_device_builds_by_content():
    """Two device builds of the same edges hit one pooled executor (the
    content key digests the input edge list — no store readback)."""
    edges = rmat(250, 1200, seed=17)
    pool = ExecutorPool()
    db1 = device_build(edges)
    db2 = device_build(edges)
    assert db1.sbf.content_key == db2.sbf.content_key
    ex1 = pool.get(db1.sbf)
    ex2 = pool.get(db2.sbf)
    assert ex1 is ex2
    assert pool.hits == 1 and pool.misses == 1
    # A different graph misses.
    db3 = device_build(rmat(250, 1200, seed=19))
    pool.get(db3.sbf)
    assert pool.misses == 2


def test_device_build_sharded_paths_materialize():
    """Device builds feed mesh placements through to_host() — same counts."""
    edges = rmat(300, 1800, seed=3)
    g = build_graph(edges, reorder=True)
    want = triangles_intersection(g)
    mesh = jax.make_mesh((len(jax.devices()),), ("d",))
    res = tcim_count_graph(g, build="device", mesh=mesh)
    assert res.triangles == want
    assert res.stats["build"] == "device"
    assert "materialize" in res.timings_s
    res_sc = tcim_count_graph(
        g, build="device", mesh=mesh, placement="sharded_cols"
    )
    assert res_sc.triangles == want
    assert res_sc.stats["placement"] == "sharded_cols"


def test_async_api_matches_sync():
    """tcim_count*(async_=True).result() == the blocking call, every path."""
    edges = rmat(350, 2000, seed=21)
    g = build_graph(edges, reorder=True)
    want = triangles_intersection(g)
    for kwargs in (
        {"build": "host"},
        {"build": "device"},
        {"build": "host", "backend": "jnp"},
    ):
        fut = tcim_count_graph(g, async_=True, **kwargs)
        res = fut.result()
        assert res.triangles == want, kwargs
        assert "close" in res.timings_s
        assert fut.result() is res  # idempotent
    # Dense backends hand back an eagerly-resolved future.
    res = tcim_count_graph(g, backend="mxu", async_=True).result()
    assert res.triangles == want
    # Overlapped fleet serve: all dispatched before any close.
    futs = [
        tcim_count(rmat(200, 900, seed=s), build="device", async_=True)
        for s in (1, 2, 3)
    ]
    counts = [f.result().triangles for f in futs]
    wants = [
        triangles_intersection(build_graph(rmat(200, 900, seed=s), reorder=True))
        for s in (1, 2, 3)
    ]
    assert counts == wants


def test_distributed_async_matches_sync():
    from repro.distributed import distributed_tc_count, distributed_tc_count_async

    edges = rmat(300, 1500, seed=23)
    g = build_graph(edges, reorder=True)
    sb = build_sbf(g, 64)
    wl = build_worklist(g, sb)
    mesh = jax.make_mesh((len(jax.devices()),), ("d",))
    want = triangles_intersection(g)
    fut = distributed_tc_count_async(sb, wl, mesh)
    assert fut.result() == want == distributed_tc_count(sb, wl, mesh)
    empty = Worklist(
        pair_edge=np.zeros(0, np.int64),
        pair_row_pos=np.zeros(0, np.int64),
        pair_col_pos=np.zeros(0, np.int64),
        m_edges=g.m,
        n_slices=sb.n_slices,
    )
    assert distributed_tc_count_async(sb, empty, mesh).result() == 0


def test_build_argument_validation():
    edges = np.array([[0, 1], [0, 2], [1, 2]], dtype=np.int64)
    with pytest.raises(ValueError, match="build"):
        tcim_count(edges, build="gpu")
    # Dense backends quietly build on host (nothing to build on device).
    assert tcim_count(edges, backend="mxu", build="device").triangles == 1


def test_candidate_overflow_guard_and_auto_fallback(monkeypatch):
    """The overflow guard reads the float32 shadow sum (the int32 total
    wraps silently past 2**31), and build='auto' falls back to the host
    front end when the device build rejects a graph — only an explicit
    build='device' surfaces the error."""
    from repro.core import build as build_mod
    from repro.core import tcim as tcim_mod

    edges = rmat(300, 1500, seed=29)
    g = build_graph(edges, reorder=True)
    want = triangles_intersection(g)
    monkeypatch.setattr(build_mod, "_CAND_GUARD", 1.0)
    with pytest.raises(build_mod.DeviceCapacityError, match="host"):
        device_build(edges)
    with pytest.raises(build_mod.DeviceCapacityError, match="host"):
        tcim_count(edges, build="device")
    # Pretend we're on an accelerator so 'auto' resolves to the device
    # build, then let the (monkeypatched) guard reject it: the count must
    # quietly complete on the host front end. backend='jnp' keeps the
    # execute stage off the Pallas kernels, whose interpret-mode routing
    # also reads the (patched) default backend.
    monkeypatch.setattr(tcim_mod.jax, "default_backend", lambda: "tpu")
    res = tcim_count(edges, build="auto", backend="jnp")
    assert res.triangles == want
    assert res.stats["build"] == "host"


def test_auto_build_surfaces_device_faults(monkeypatch):
    """build='auto' answers only the capacity refusal with the host build:
    any other device-build error (a kernel the compiler refuses, a device
    fault) must surface, not be hidden behind a host count."""
    from repro.core import build as build_mod
    from repro.core import tcim as tcim_mod

    def broken(*args, **kwargs):
        raise ValueError("Mosaic failed to compile TPU kernel")

    monkeypatch.setattr(tcim_mod.jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(build_mod, "device_build", broken)
    with pytest.raises(ValueError, match="Mosaic"):
        tcim_count(rmat(300, 1500, seed=29), build="auto", backend="jnp")


def test_window_searchsorted_empty_concat():
    """Regression: an empty sorted side used to index sorted_concat[-1]."""
    out = _window_searchsorted(
        np.zeros(0, dtype=np.int64),
        np.zeros(3, dtype=np.int64),
        np.zeros(3, dtype=np.int64),
        np.array([5, 0, 7], dtype=np.int64),
    )
    assert np.array_equal(out, np.zeros(3, dtype=np.int64))


def test_build_worklist_empty_side_guard():
    """Regression: an SBF with an empty column side (e.g. a hand-sliced
    edge block) used to raise IndexError in build_worklist."""
    edges = np.array([[0, 1], [0, 2], [0, 3]], dtype=np.int64)
    g = build_graph(edges)
    sb = build_sbf(g, 64)
    hollow = dataclasses.replace(
        sb,
        col_ptr=np.zeros(g.n + 1, dtype=np.int64),
        col_slice_idx=np.zeros(0, dtype=np.int32),
        col_slice_data=np.zeros((0, sb.words_per_slice), dtype=np.uint32),
    )
    wl = build_worklist(g, hollow)
    assert wl.num_pairs == 0
    hollow_row = dataclasses.replace(
        sb,
        row_ptr=np.zeros(g.n + 1, dtype=np.int64),
        row_slice_idx=np.zeros(0, dtype=np.int32),
        row_slice_data=np.zeros((0, sb.words_per_slice), dtype=np.uint32),
    )
    assert build_worklist(g, hollow_row).num_pairs == 0


@pytest.fixture
def fine_buckets(monkeypatch):
    """Every candidate total takes the eighth-octave lane bucket, so graphs
    far below ``LANE_BUCKET_FLOOR`` run non-pow2 worklist steps."""
    monkeypatch.setattr(plan_mod, "LANE_BUCKET_FLOOR", 1)


def _assert_padded_pairs(dwl, want):
    """A device worklist equals the host pairs, ``-1``-padded to pow2."""
    pb = pow2_ceil(max(len(want[0]), 1))
    assert dwl.num_pairs == len(want[0])
    assert dwl.cand_bucket == lane_bucket(max(dwl.num_candidates, 1))
    for got, ref in zip((dwl.pair_edge, dwl.pair_row_pos, dwl.pair_col_pos), want):
        got = np.asarray(got)
        assert got.shape == (pb,)
        assert np.array_equal(got[: len(ref)], ref)
        assert (got[len(ref):] == -1).all()


@pytest.mark.parametrize("name,edges", GRAPH_CASES, ids=GRAPH_IDS)
def test_fine_lane_bucket_pairs_match_host(fine_buckets, name, edges):
    """Both device worklist entry points, run in the eighth-octave candidate
    bucket, equal the host pairs on every graph shape, and so does the
    device-built count."""
    n = num_vertices(edges)
    g = build_graph(edges, n=n)
    sb_h = build_sbf(g, 32)
    src, dst = g.edges[:, 0], g.edges[:, 1]
    want = build_worklist_pairs(src, dst, sb_h)
    got = [device_delta_worklist(src, dst, sb_h)]
    if len(edges):  # the device orient needs an edge
        dg = device_orient(g.edges, n=n, reorder=False)
        got.append(device_build_worklist(dg, device_build_sbf(dg, 32)))
    for dwl in got:
        _assert_padded_pairs(dwl, want)
    res = tcim_count(edges, n=n, slice_bits=32, build="device")
    assert res.triangles == triangles_intersection(g)


def test_pair_bucket_past_fine_lane_bucket(fine_buckets):
    """K40 in one 64-bit slice hits on all 780 candidates: the step runs
    832 lanes and its pairs are ``-1``-padded to the 1,024-pair bucket."""
    g = build_graph(complete_graph(40), n=40)
    sb_h = build_sbf(g, 64)
    src, dst = g.edges[:, 0], g.edges[:, 1]
    want = build_worklist_pairs(src, dst, sb_h)
    dg = device_orient(g.edges, n=40, reorder=False)
    dsb = device_build_sbf(dg, 64)
    for dwl in (device_build_worklist(dg, dsb), device_delta_worklist(src, dst, sb_h)):
        assert (dwl.num_candidates, dwl.num_pairs, dwl.cand_bucket) == (780, 780, 832)
        assert pow2_ceil(dwl.num_pairs) > dwl.cand_bucket
        _assert_padded_pairs(dwl, want)
        assert Executor(dsb).count(dwl) == 9880
    assert tcim_count(complete_graph(40), build="device").triangles == 9880


def test_fine_lane_bucket_shared_across_totals(fine_buckets):
    """Two graphs whose candidate totals differ (5,326 and 5,206) but share
    the eighth-octave bucket 5,632, and every other bucket, reuse every
    compiled build step."""
    edges = [rmat(400, 2500, seed=s) for s in (1, 2)]
    db_a = device_build(edges[0], n=400)
    before = device_build_trace_counts()
    if -1 in before.values():
        pytest.skip("private jit cache-size API unavailable on this jax")
    db_b = device_build(edges[1], n=400)
    assert device_build_trace_counts() == before
    wa, wb = db_a.worklist, db_b.worklist
    assert (wa.num_candidates, wb.num_candidates) == (5326, 5206)
    assert wa.cand_bucket == wb.cand_bucket == 5632
    for e, db in zip(edges, (db_a, db_b)):
        g = build_graph(e, n=400, reorder=True)
        want = build_worklist(g, build_sbf(g, 64))
        assert np.array_equal(db.worklist.to_host().pair_col_pos, want.pair_col_pos)


@pytest.mark.parametrize("floor", ["default", "lowered"])
def test_cand_bucket_counter(monkeypatch, floor):
    """A device count, an LCC query and a delta worklist report the
    candidate lanes their worklist step ran: below ``LANE_BUCKET_FLOOR``
    the pow2 bucket (8,192 for 5,326 candidates), else the eighth-octave
    one (5,632)."""
    want = 8192
    if floor == "lowered":
        monkeypatch.setattr(plan_mod, "LANE_BUCKET_FLOOR", 1)
        want = 5632
    edges = rmat(400, 2500, seed=1)
    assert tcim_count(edges, n=400, build="device").stats["cand_bucket"] == want
    assert tcim_vertex_counts(edges, n=400, build="device").stats["cand_bucket"] == want
    g = build_graph(edges, n=400, reorder=True)
    dwl = device_delta_worklist(g.edges[:, 0], g.edges[:, 1], build_sbf(g, 64))
    assert (dwl.num_candidates, dwl.cand_bucket) == (5326, want)
