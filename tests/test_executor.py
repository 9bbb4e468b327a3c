"""Executor + fused gather–AND–popcount correctness and retrace regression.

The fused path must match the independent jnp oracle (lax.population_count)
bit-for-bit on every work-list shape the engine can produce — ragged, empty,
multi-chunk, every bench-graph config — and must never retrace per chunk.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.tcim_graphs import GRAPHS
from repro.core import Executor, EXECUTOR_MODES, build_sbf, build_worklist
from repro.data.graph_pipeline import load_graph
from repro.graphs import build_graph, rmat
from repro.graphs.exact import triangles_intersection
from repro.kernels import ops
from repro.kernels.tc_gather_popcount import (
    _segment_totals,
    gather_segment_totals_pallas,
    gather_segment_totals_reference,
    gather_total_pallas,
    gather_total_reference,
    modeled_hbm_bytes,
)


def _oracle(sbf, row_idx, col_idx):
    """Independent total: lax.population_count over a host-side gather."""
    mask = (row_idx >= 0) & (col_idx >= 0)
    rows = sbf.row_slice_data[np.maximum(row_idx, 0)][mask]
    cols = sbf.col_slice_data[np.maximum(col_idx, 0)][mask]
    if len(rows) == 0:
        return 0
    import jax

    return int(
        jax.lax.population_count(jnp.asarray(rows & cols)).astype(jnp.int32).sum()
    )


@pytest.fixture(scope="module")
def small_graph():
    edges = rmat(400, 2500, seed=1)
    g = build_graph(edges)
    sbf = build_sbf(g, 64)
    wl = build_worklist(g, sbf)
    return g, sbf, wl


@pytest.mark.parametrize("mode", EXECUTOR_MODES)
def test_executor_modes_match_oracle(small_graph, mode):
    g, sbf, wl = small_graph
    want = triangles_intersection(g)
    ex = Executor(sbf, mode=mode)
    assert ex.count(wl) == want
    assert _oracle(sbf, wl.pair_row_pos, wl.pair_col_pos) == want


@pytest.mark.parametrize("chunk_pairs", [1, 7, 64, 300, 1 << 20])
def test_executor_chunking_invariance(small_graph, chunk_pairs):
    """Ragged/multi-chunk splits must not change the count (Eq. 5)."""
    g, sbf, wl = small_graph
    want = triangles_intersection(g)
    ex = Executor(sbf, chunk_pairs=chunk_pairs)
    assert ex.count(wl) == want


def test_executor_empty_and_ragged_indices(small_graph):
    _, sbf, wl = small_graph
    ex = Executor(sbf)
    assert ex.execute_indices(np.zeros(0, np.int64), np.zeros(0, np.int64)) == 0
    # Odd ragged prefix sizes, including sentinel padding inside a bucket.
    for sub in (1, 3, wl.num_pairs // 2 + 1, wl.num_pairs - 1):
        r = wl.pair_row_pos[:sub]
        c = wl.pair_col_pos[:sub]
        assert ex.execute_indices(r, c) == _oracle(sbf, r, c), sub


def test_executor_negative_indices_are_noops(small_graph):
    _, sbf, wl = small_graph
    ex = Executor(sbf)
    r = wl.pair_row_pos[:100].astype(np.int64).copy()
    c = wl.pair_col_pos[:100].copy()
    base = ex.execute_indices(r, c)
    r2 = np.concatenate([r, np.full(37, -1, np.int64)])
    c2 = np.concatenate([c, np.full(37, -1, np.int64)])
    assert ex.execute_indices(r2, c2) == base


def test_single_trace_across_chunks(small_graph):
    """Fixed pow2 buckets: a multi-chunk count never retraces per chunk.

    The jitted chunk step is shared across same-config executors, so the
    regression asserts on cache-size *deltas* around the counts.
    """
    _, sbf, wl = small_graph
    ex = Executor(sbf, chunk_pairs=256)
    assert wl.num_pairs > 4 * 256  # genuinely multi-chunk
    if ex.trace_count == -1:
        pytest.skip("private jit cache-size API unavailable on this jax")
    before = ex.trace_count
    ex.count(wl)
    first = ex.trace_count
    # At most: one full-chunk shape + one tail bucket shape — NOT one trace
    # per chunk (a per-chunk retrace would add ~wl.num_pairs/256 entries).
    assert first - before <= 2, (before, first)
    # Recounts (and different ragged prefixes in the same buckets) hit cache.
    ex.count(wl)
    ex.execute_indices(wl.pair_row_pos[: 3 * 256], wl.pair_col_pos[: 3 * 256])
    assert ex.trace_count == first
    # A second same-config executor reuses the shared traces outright.
    ex2 = Executor(sbf, chunk_pairs=256)
    ex2.count(wl)
    assert ex2.trace_count == first


def test_kernel_matches_mirror_and_oracle(small_graph):
    """Pallas gather kernel (interpret) == jnp mirror == oracle."""
    _, sbf, wl = small_graph
    row_data = jnp.asarray(sbf.row_slice_data)
    col_data = jnp.asarray(sbf.col_slice_data)
    sub = 600
    ridx = jnp.asarray(wl.pair_row_pos[:sub].astype(np.int32))
    cidx = jnp.asarray(wl.pair_col_pos[:sub].astype(np.int32))
    got_kernel = int(gather_total_pallas(row_data, col_data, ridx, cidx, interpret=True))
    got_mirror = int(gather_total_reference(row_data, col_data, ridx, cidx))
    want = _oracle(sbf, np.asarray(ridx), np.asarray(cidx))
    assert got_kernel == got_mirror == want


@pytest.mark.parametrize("block_pairs", [2, 8, 16])
def test_batched_kernel_matches_mirror(small_graph, block_pairs):
    """B pairs' tile DMAs in flight together: identical totals to the mirror
    when P is not a multiple of B, with negative-index padding mid-batch."""
    _, sbf, wl = small_graph
    row_data = jnp.asarray(sbf.row_slice_data)
    col_data = jnp.asarray(sbf.col_slice_data)
    for sub in (1, block_pairs - 1, block_pairs, 3 * block_pairs + 1, 137):
        ridx = np.asarray(wl.pair_row_pos[:sub], dtype=np.int32).copy()
        cidx = np.asarray(wl.pair_col_pos[:sub], dtype=np.int32).copy()
        ridx[::5] = -1  # padding sentinels interleaved mid-block
        # One segment spanning the 1024-padded index block, as
        # gather_total_pallas runs it, with a batch size of this case's.
        got = int(
            _segment_totals(
                row_data, col_data, jnp.asarray(ridx), jnp.asarray(cidx),
                1024, True, block_pairs=block_pairs,
            )[0]
        )
        want = int(
            gather_total_reference(
                row_data, col_data, jnp.asarray(ridx), jnp.asarray(cidx)
            )
        )
        assert got == want, (block_pairs, sub)


@pytest.mark.parametrize(
    "words,graphs,bucket,block_pairs",
    [(1, 4, 64, 16), (2, 3, 512, 128), (2, 2, 2048, 128), (4, 5, 16, 8),
     (8, 1, 1024, 1024)],
)
def test_segment_kernel_matches_mirror(words, graphs, bucket, block_pairs):
    """The segment kernel (interpret) == its jnp mirror, per segment, over
    store sizes that are not multiples of a tile, buckets below and above
    one 1024-pair index block, and sentinel lanes mid-segment."""
    rng = np.random.default_rng(words * 1000 + bucket)
    rows, cols = 300, 1000  # not multiples of 128: the tile view pads
    row_data = jnp.asarray(rng.integers(0, 2**32, (rows, words), dtype=np.uint32))
    col_data = jnp.asarray(rng.integers(0, 2**32, (cols, words), dtype=np.uint32))
    p = graphs * bucket
    ridx = rng.integers(0, rows, p).astype(np.int32)
    cidx = rng.integers(0, cols, p).astype(np.int32)
    ridx[::7] = -1
    cidx[::11] = -1
    got = _segment_totals(
        row_data, col_data, jnp.asarray(ridx), jnp.asarray(cidx), bucket, True,
        block_pairs=block_pairs,
    )[:graphs]
    want = gather_segment_totals_reference(
        row_data, col_data, jnp.asarray(ridx), jnp.asarray(cidx), bucket=bucket
    )
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    public = gather_segment_totals_pallas(
        row_data, col_data, jnp.asarray(ridx), jnp.asarray(cidx),
        bucket=bucket, interpret=True,
    )
    assert public.shape == (graphs,)
    np.testing.assert_array_equal(np.asarray(public), np.asarray(want))


def test_segment_kernel_rejects_bad_tiling():
    store = jnp.zeros((8, 2), jnp.uint32)
    idx = jnp.zeros((24,), jnp.int32)
    with pytest.raises(ValueError, match="bucket"):
        gather_segment_totals_pallas(store, store, idx, idx, bucket=12)
    with pytest.raises(ValueError, match="block_pairs"):
        _segment_totals(store, store, idx, idx, 8, True, block_pairs=3)


def test_execute_impl_reports_the_kernel_choice(small_graph):
    """Fused executors name what runs each chunk; ``use_kernel=None`` is
    the mirror only off a TPU."""
    _, sbf, wl = small_graph
    assert Executor(sbf).execute_impl == (
        "pallas" if ops.gather_kernel_selected() else "jnp_mirror"
    )
    kernel = Executor(sbf, use_kernel=True)
    assert kernel.execute_impl == "pallas"
    assert kernel.count(wl) == Executor(sbf, mode="jnp").count(wl)
    assert Executor(sbf, mode="jnp").execute_impl == "jnp"


def test_kernel_negative_index_noop(small_graph):
    _, sbf, wl = small_graph
    row_data = jnp.asarray(sbf.row_slice_data)
    col_data = jnp.asarray(sbf.col_slice_data)
    ridx = jnp.asarray(
        np.concatenate([wl.pair_row_pos[:50], np.full(14, -1)]).astype(np.int32)
    )
    cidx = jnp.asarray(
        np.concatenate([wl.pair_col_pos[:50], np.full(14, -1)]).astype(np.int32)
    )
    got = int(gather_total_pallas(row_data, col_data, ridx, cidx, interpret=True))
    assert got == _oracle(sbf, np.asarray(ridx), np.asarray(cidx))


@pytest.mark.parametrize("name", list(GRAPHS))
def test_fused_matches_oracle_on_bench_configs(name):
    """Every tcim_graphs config (scaled down): fused == jnp oracle == exact."""
    cfg = GRAPHS[name].scaled(0.02)
    g, sbf, wl = load_graph(cfg, 64)
    want = triangles_intersection(g)
    fused = Executor(sbf, mode="fused", chunk_pairs=1 << 12)
    oracle = Executor(sbf, mode="jnp", chunk_pairs=1 << 12)
    assert fused.count(wl) == oracle.count(wl) == want, name


def test_chunk_overflow_guard():
    """chunk_pairs * words_per_slice * 32 is pinned under the int32 bound."""
    edges = rmat(64, 200, seed=3)
    g = build_graph(edges)
    sbf = build_sbf(g, 64)
    ex = Executor(sbf, chunk_pairs=1 << 40)  # absurd request gets clamped
    assert ex.chunk_pairs * ex.words_per_slice * 32 <= 2**31 - 1
    # Non-pow2 requests round DOWN — never exceed the caller's memory bound.
    assert Executor(sbf, chunk_pairs=3 << 8).chunk_pairs == 1 << 9
    import jax

    w = sbf.row_slice_data.shape[1]
    bad = ops.INT32_SAFE_WORDS // w + 1
    idx = jax.ShapeDtypeStruct((bad,), jnp.int32)
    words = jax.ShapeDtypeStruct((bad, w), jnp.uint32)
    store = jax.ShapeDtypeStruct(sbf.row_slice_data.shape, jnp.uint32)
    # eval_shape: the guards fire at trace time, nothing is allocated.
    with pytest.raises(ValueError, match="overflow"):
        jax.eval_shape(ops.popcount_and_gather_total, store, store, idx, idx)
    with pytest.raises(ValueError, match="overflow"):
        jax.eval_shape(ops.popcount_and_total, words, words)


def test_distributed_stripe_split_matches_exact(small_graph, monkeypatch):
    """distributed_tc_count splits over-bound work lists into int32-safe
    stripes (multiple psum steps + exact host sum) instead of raising."""
    import jax

    from repro.distributed import tc as dtc

    g, sbf, wl = small_graph
    want = triangles_intersection(g)
    mesh = jax.make_mesh((1,), ("d",))
    assert dtc.distributed_tc_count(sbf, wl, mesh) == want
    # Shrink the bound so this work list needs many stripes.
    monkeypatch.setattr(dtc, "INT32_SAFE_WORDS", 512 * sbf.words_per_slice)
    assert wl.num_pairs > 512 * 4
    assert dtc.distributed_tc_count(sbf, wl, mesh) == want


def test_modeled_hbm_bytes_fused_advantage():
    """The fused kernel moves one whole (W, 128) tile per operand, so its
    modeled traffic is 128x the gathered words; unfused moves the words 3x.
    The fused path's advantage is no [P, W] buffer, not fewer bytes."""
    fused = modeled_hbm_bytes(1000, 2, fused=True)
    unfused = modeled_hbm_bytes(1000, 2, fused=False)
    gathered = 2 * 1000 * 2 * 4
    index_and_out = 2 * 1000 * 4 + 4
    assert fused == 128 * gathered + index_and_out
    assert unfused == 3 * gathered + index_and_out
