"""Hypothesis property tests on the system's invariants."""
import numpy as np
import pytest

pytest.importorskip("hypothesis", reason="hypothesis not installed; skipping property tests")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.core import tcim_count
from repro.core.bitmat import bitpack_matrix, bitunpack_matrix
from repro.core.sbf import build_sbf, build_worklist
from repro.graphs import build_graph
from repro.graphs.exact import triangles_bruteforce, triangles_dense_trace


@st.composite
def small_graphs(draw):
    n = draw(st.integers(min_value=2, max_value=40))
    max_edges = n * (n - 1) // 2
    m = draw(st.integers(min_value=0, max_value=min(max_edges, 120)))
    pairs = draw(
        st.lists(
            st.tuples(st.integers(0, n - 1), st.integers(0, n - 1)),
            min_size=m,
            max_size=m,
        )
    )
    edges = [(min(a, b), max(a, b)) for a, b in pairs if a != b]
    edges = sorted(set(edges))
    return n, np.array(edges, dtype=np.int64).reshape(-1, 2)


@settings(max_examples=60, deadline=None)
@given(small_graphs(), st.sampled_from([32, 64]))
def test_tcim_equals_bruteforce(graph, slice_bits):
    n, edges = graph
    g = build_graph(edges, n=n)
    want = triangles_bruteforce(g)
    assert triangles_dense_trace(g) == want
    got = tcim_count(edges, n=n, slice_bits=slice_bits, backend="jnp").triangles
    assert got == want


@settings(max_examples=40, deadline=None)
@given(small_graphs())
def test_permutation_invariance(graph):
    """TC is invariant under vertex relabelling."""
    n, edges = graph
    base = tcim_count(edges, n=n, backend="jnp").triangles
    rng = np.random.default_rng(42)
    perm = rng.permutation(n)
    if len(edges):
        e2 = perm[edges]
        lo = np.minimum(e2[:, 0], e2[:, 1])
        hi = np.maximum(e2[:, 0], e2[:, 1])
        e2 = np.stack([lo, hi], 1)
    else:
        e2 = edges
    assert tcim_count(e2, n=n, backend="jnp").triangles == base


@settings(max_examples=40, deadline=None)
@given(small_graphs())
def test_worklist_popcount_identity(graph):
    """Sum of AND-popcounts over the work list == triangle count (Eq. 5)."""
    n, edges = graph
    g = build_graph(edges, n=n)
    sbf = build_sbf(g, 32)
    wl = build_worklist(g, sbf)
    rows = sbf.row_slice_data[wl.pair_row_pos]
    cols = sbf.col_slice_data[wl.pair_col_pos]
    from repro.core.bitmat import popcount_u32

    total = int(popcount_u32(rows & cols).sum()) if len(rows) else 0
    assert total == triangles_bruteforce(g)


@settings(max_examples=50, deadline=None)
@given(
    st.integers(min_value=1, max_value=30),
    st.integers(min_value=1, max_value=200),
)
def test_bitpack_roundtrip_property(n, c):
    rng = np.random.default_rng(n * 1000 + c)
    dense = (rng.random((n, c)) < 0.5).astype(np.uint8)
    assert (bitunpack_matrix(bitpack_matrix(dense), c) == dense).all()
