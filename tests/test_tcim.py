"""TCIM engine correctness: every backend vs two independent exact oracles."""
import numpy as np
import pytest

from repro.core import BACKENDS, build_sbf, build_worklist, simulate_lru, tcim_count
from repro.core.sbf import sbf_stats
from repro.core.tcim import TCFuture
from repro.graphs import build_graph, erdos_renyi, rmat
from repro.graphs.exact import triangles_dense_trace, triangles_intersection

from graph_cases import GRAPH_CASES, GRAPH_IDS, num_vertices


@pytest.mark.parametrize("name,edges", GRAPH_CASES, ids=GRAPH_IDS)
@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("build", ["host", "device"])
def test_backend_matches_oracles(name, edges, backend, build):
    n = num_vertices(edges)
    g = build_graph(edges, n=n)
    want = triangles_dense_trace(g)
    assert triangles_intersection(g) == want
    got = tcim_count(edges, n=n, backend=backend, build=build).triangles
    assert got == want, (name, backend, build)


@pytest.mark.parametrize("name,edges", GRAPH_CASES, ids=GRAPH_IDS)
@pytest.mark.parametrize("build", ["host", "device"])
def test_async_count_matches_oracle(name, edges, build):
    """``async_=True`` hands back a future whose result is the exact count."""
    n = num_vertices(edges)
    fut = tcim_count(edges, n=n, build=build, async_=True)
    assert isinstance(fut, TCFuture)
    res = fut.result()
    assert res.triangles == triangles_intersection(build_graph(edges, n=n))
    assert res.stats["build"] == (build if len(edges) else "host")


@pytest.mark.parametrize("slice_bits", [32, 64, 128, 256])
def test_slice_size_invariance(slice_bits):
    """Eq. 5 result must not depend on |S| — slicing is pure scheduling."""
    edges = rmat(600, 4000, seed=7)
    base = tcim_count(edges, slice_bits=64).triangles
    assert tcim_count(edges, slice_bits=slice_bits).triangles == base


@pytest.mark.parametrize("reorder", [False, True])
def test_degree_reorder_invariance(reorder):
    edges = rmat(500, 3000, seed=9)
    g = build_graph(edges)
    want = triangles_intersection(g)
    assert tcim_count(edges, reorder=reorder).triangles == want


def test_worklist_only_valid_pairs():
    """Every work item points at genuinely valid slices on both sides; and
    the pair count matches a dense recomputation of valid-pair overlap."""
    edges = rmat(300, 1800, seed=11)
    g = build_graph(edges)
    sbf = build_sbf(g, 64)
    wl = build_worklist(g, sbf)
    # Slice data referenced by the work list is never all-zero.
    rows = sbf.row_slice_data[wl.pair_row_pos]
    cols = sbf.col_slice_data[wl.pair_col_pos]
    assert (rows.sum(axis=1) > 0).all()
    assert (cols.sum(axis=1) > 0).all()
    # Dense check of the pair count.
    a = g.dense_upper()
    n_slices = sbf.n_slices
    count = 0
    for i, j in g.edges:
        for k in range(n_slices):
            lo, hi = k * 64, min((k + 1) * 64, g.n)
            if a[i, lo:hi].any() and a[:, j][lo:hi].any():
                count += 1
    assert wl.num_pairs == count


def test_sbf_memory_formula():
    """Paper §IV-B: footprint = N_VS x (|S|/8 + 4) bytes."""
    edges = erdos_renyi(500, 3000, seed=13)
    g = build_graph(edges)
    sbf = build_sbf(g, 64)
    assert sbf.total_bytes == sbf.nvs * (64 // 8 + 4)
    stats = sbf_stats(g, sbf)
    assert 0 < stats["valid_slice_pct"] <= 100


def test_cachesim_bounds_and_compulsory_misses():
    edges = rmat(400, 2500, seed=17)
    g = build_graph(edges)
    sbf = build_sbf(g, 64)
    wl = build_worklist(g, sbf)
    st = simulate_lru(sbf, wl, array_bytes=1 << 20)
    assert st.hits + st.misses == st.loads == wl.num_pairs
    # Compulsory misses: at least one per distinct column slice used.
    assert st.misses >= len(np.unique(wl.pair_col_pos))
    # Infinite cache -> only compulsory misses.
    st_inf = simulate_lru(sbf, wl, array_bytes=1 << 40)
    assert st_inf.misses == len(np.unique(wl.pair_col_pos))
    assert st_inf.exchanges == 0
    # Tiny cache cannot have more hits than infinite cache.
    assert st.hits <= st_inf.hits
