"""Per-vertex triangle counts and local clustering on the device path.

``tcim_vertex_counts`` must give, through the device build and the pooled
executor's pair chunks, exactly the per-vertex counts T(v) and the LDBC
Graphalytics LCC of the NumPy references in ``graphs/exact.py``.
"""
import numpy as np
import pytest

from repro.core import ExecutorPool, tcim_count
from repro.core import build as build_mod
from repro.core import executor as executor_mod
from repro.core.metrics import _triangle_list, clustering_coefficients
from repro.core import tcim as tcim_mod
from repro.core.tcim import tcim_vertex_counts
from repro.graphs import build_graph, complete_graph, erdos_renyi, rmat
from repro.graphs.csr import degree_order, degree_relabel
from repro.graphs.exact import (
    local_clustering,
    triangles_intersection,
    vertex_triangles,
)

from graph_cases import GRAPH_CASES, GRAPH_IDS, num_vertices


def _star(leaves: int) -> np.ndarray:
    return np.stack([np.zeros(leaves, np.int64), np.arange(1, leaves + 1)], axis=1)


GRAPHS = {
    "rmat-a": (rmat(2000, 16000, seed=11), 2000),
    "rmat-b": (rmat(1500, 12000, seed=12), 1500),
    "erdos-renyi": (erdos_renyi(400, 3000, seed=13), 400),
    "k6": (complete_graph(6), 6),
    "star": (_star(40), 41),
    "isolated": (rmat(300, 900, seed=14), 420),  # vertices 300..419 have no edge
    "empty": (np.zeros((0, 2), np.int64), 7),
}


def _check_exact(res, edges, n):
    g = build_graph(edges, n=n)
    want_t = vertex_triangles(g)
    assert res.vertex_triangles.dtype == np.int64 and res.lcc.dtype == np.float64
    np.testing.assert_array_equal(res.vertex_triangles, want_t)
    np.testing.assert_array_equal(res.lcc, local_clustering(g))
    assert res.triangles == triangles_intersection(g)
    assert int(res.vertex_triangles.sum()) == 3 * res.triangles


@pytest.mark.parametrize("name", sorted(GRAPHS))
def test_vertex_counts_match_the_reference_exactly(name):
    edges, n = GRAPHS[name]
    res = tcim_vertex_counts(edges, n=n, build="device")
    _check_exact(res, edges, n)
    assert res.stats["build"] == ("device" if len(edges) else "host")
    assert res.stats["vertex_impl"] == executor_mod.VERTEX_IMPL
    assert res.stats["execute_impl"] == executor_mod.VERTEX_EXECUTE_IMPL
    assert {"vertex", "vertex.materialize", "vertex.lcc"} <= set(res.timings_s)


@pytest.mark.parametrize("name,edges", GRAPH_CASES, ids=GRAPH_IDS)
@pytest.mark.parametrize("build", ["host", "device"])
def test_vertex_counts_on_every_graph_shape(name, edges, build):
    n = num_vertices(edges)
    res = tcim_vertex_counts(edges, n=n, build=build)
    _check_exact(res, edges, n)
    assert res.stats["build"] == (build if len(edges) else "host")


@pytest.mark.parametrize("name", ["rmat-a", "k6", "star"])
def test_host_build_gives_the_same_answer(name):
    edges, n = GRAPHS[name]
    res = tcim_vertex_counts(edges, n=n, build="host")
    assert res.stats["build"] == "host"
    _check_exact(res, edges, n)


def test_special_graphs_read_as_defined():
    res = tcim_vertex_counts(complete_graph(6), n=6, build="device")
    np.testing.assert_array_equal(res.vertex_triangles, np.full(6, 10))
    np.testing.assert_array_equal(res.lcc, np.ones(6))
    star = tcim_vertex_counts(_star(40), n=41, build="device")
    assert star.triangles == 0 and not star.vertex_triangles.any()
    assert not star.lcc.any()
    empty = tcim_vertex_counts(np.zeros((0, 2), np.int64), n=7)
    assert empty.triangles == 0 and empty.vertex_triangles.shape == (7,)
    iso = tcim_vertex_counts(GRAPHS["isolated"][0], n=420, build="device")
    assert not iso.vertex_triangles[300:].any() and not iso.lcc[300:].any()


@pytest.mark.parametrize("chunk_pairs", [256, 1 << 20])
def test_total_matches_the_count_over_any_chunking(chunk_pairs):
    edges, n = GRAPHS["rmat-a"]
    res = tcim_vertex_counts(edges, n=n, build="device", chunk_pairs=chunk_pairs)
    _check_exact(res, edges, n)
    count = tcim_count(edges, n=n, build="device")
    assert res.triangles == count.triangles
    assert res.stats["vertex_pairs"] == count.stats["num_pairs"]
    assert 0 < res.stats["vertex_nonzero_pairs"] <= res.stats["vertex_pairs"]


@pytest.mark.parametrize("seed", [0, 2**31 + 3])
def test_relabelling_permutes_the_counts(seed):
    edges, n = GRAPHS["rmat-b"]
    perm = np.random.default_rng(seed).permutation(n)
    e = perm[edges]
    copy = np.stack([e.min(axis=1), e.max(axis=1)], axis=1)
    base = tcim_vertex_counts(edges, n=n, build="device")
    moved = tcim_vertex_counts(copy, n=n, build="device")
    np.testing.assert_array_equal(moved.vertex_triangles[perm], base.vertex_triangles)
    np.testing.assert_array_equal(moved.lcc[perm], base.lcc)
    assert moved.triangles == base.triangles


def test_same_bucket_graphs_add_no_vertex_step_trace():
    pool = ExecutorPool(max_graphs=2)
    edges, n = GRAPHS["rmat-b"]
    tcim_vertex_counts(edges, n=n, build="device", pool=pool)
    step = executor_mod._vertex_step_fn(64, False)
    before = (step._cache_size(), build_mod.device_build_trace_counts())
    perm = np.random.default_rng(5).permutation(n)
    e = perm[edges]
    res = tcim_vertex_counts(np.stack([e.min(axis=1), e.max(axis=1)], axis=1),
                             n=n, build="device", pool=pool)
    assert (step._cache_size(), build_mod.device_build_trace_counts()) == before
    assert res.stats["build"] == "device"


def test_capacity_error_builds_on_the_host_under_auto(monkeypatch):
    def refuse(*a, **k):
        raise build_mod.DeviceCapacityError("too many candidates")

    monkeypatch.setattr(build_mod, "device_build", refuse)
    # What "auto" resolves to on an accelerator.
    monkeypatch.setattr(tcim_mod, "_resolve_build", lambda *a: "device")
    edges, n = GRAPHS["erdos-renyi"]
    res = tcim_vertex_counts(edges, n=n, build="auto")
    assert res.stats["build"] == "host"
    _check_exact(res, edges, n)
    with pytest.raises(build_mod.DeviceCapacityError):
        tcim_vertex_counts(edges, n=n, build="device")


def test_counts_past_int32_raise(monkeypatch):
    edges, n = GRAPHS["k6"]
    monkeypatch.setattr(executor_mod, "_INT32_MAX", 3 * 20 - 1)
    with pytest.raises(OverflowError):
        tcim_vertex_counts(edges, n=n, build="device")
    monkeypatch.setattr(executor_mod, "_INT32_MAX", 3 * 20)
    assert tcim_vertex_counts(edges, n=n, build="device").triangles == 20


def test_select_bit_finds_each_set_bit():
    rng = np.random.default_rng(1)
    words = rng.integers(0, 2**32, size=(64, 2), dtype=np.uint64).astype(np.uint32)
    words[0] = 0xFFFFFFFF
    words[1] = [0, 1 << 31]
    bits = np.unpackbits(words.view(np.uint8), axis=1, bitorder="little")
    rows, ranks, want = [], [], []
    for i, row in enumerate(bits):
        for r, pos in enumerate(np.flatnonzero(row)):
            rows.append(i)
            ranks.append(r)
            want.append(pos)
    got = executor_mod._select_bit(words[np.array(rows)],
                                   np.array(ranks, dtype=np.int32))
    np.testing.assert_array_equal(np.asarray(got), want)


def test_degree_relabel_is_the_relabel_degree_order_applies():
    edges, n = GRAPHS["rmat-b"]
    e = degree_relabel(edges, n)[edges]
    want = degree_order(edges, n)
    got = np.stack([e.min(axis=1), e.max(axis=1)], axis=1)
    np.testing.assert_array_equal(got[np.lexsort((got[:, 1], got[:, 0]))], want)


def _old_clustering(g):
    """``clustering_coefficients`` as it was on the host: a Python list of
    every triangle, three ``np.add.at`` passes."""
    tris = _triangle_list(g)
    per_vertex = np.zeros(g.n, dtype=np.int64)
    for col in range(3):
        np.add.at(per_vertex, tris[:, col], 1)
    deg = np.zeros(g.n, dtype=np.int64)
    np.add.at(deg, g.edges[:, 0], 1)
    np.add.at(deg, g.edges[:, 1], 1)
    wedges = deg * (deg - 1) // 2
    local = np.where(wedges > 0, per_vertex / np.maximum(wedges, 1), 0.0)
    total = int(wedges.sum())
    return local, (3.0 * len(tris) / total if total else 0.0)


@pytest.mark.parametrize("make", [
    lambda: complete_graph(8),
    lambda: erdos_renyi(60, 250, seed=5),
    lambda: rmat(300, 2000, seed=21),
    lambda: np.array([[0, 1], [0, 2], [1, 2]], dtype=np.int64),
    lambda: np.array([[0, 1], [0, 2], [1, 2], [1, 3], [2, 3], [3, 4]], dtype=np.int64),
])
def test_clustering_coefficients_equal_the_old_host_result(make):
    g = build_graph(make())
    local, trans = clustering_coefficients(g)
    want_local, want_trans = _old_clustering(g)
    np.testing.assert_array_equal(local, want_local)
    assert trans == want_trans
