"""Fused gather–AND–popcount: the TCIM execute stage in one HBM pass.

TCIM's core claim (paper §IV-C) is that computing AND+BitCount *where the
slice words live* removes the bandwidth bottleneck. The legacy execute path
did the opposite on TPU: XLA gathered the work-list slice pairs into fresh
``[P, W]`` HBM buffers, then the reduction kernel read them back — every
gathered word crossed HBM twice, plus a full materialized intermediate.

This module is the device analogue of the MRAM computational array: the
*indices* travel to the kernel, not the operands.

  * ``gather_segment_totals_pallas`` — the Pallas kernel. On a TPU, XLA
    lays a ``[R, W]`` uint32 slice store out as W word planes in
    ``(W, 128)`` tiles (layout ``{0,1:T(W,128)}``), so the kernel views the
    store as ``[R/128, W, 128]`` — a bitcast, no copy — and its DMA unit is
    one whole tile: the 128 slices that share slice ``r``'s tile. The
    stores stay in HBM (``memory_space=ANY``). Each grid step takes a
    1024-pair block of both index arrays into SMEM (1024 matches XLA's
    ``T(1024)`` layout of a 1-D int32 array), then walks it in batches of
    ``BLOCK_PAIRS``: it starts one tile DMA per operand of every valid pair
    of the batch, waits for all of them, and for each pair rotates the row
    tile so slice ``r``'s lane lines up with slice ``c``'s, ANDs, popcounts,
    and keeps only that lane. Negative indices are masked no-ops (no DMA),
    which is how the executor and the distributed engine pad ragged chunks.
    The ``[G]`` per-segment totals live in SMEM; a batch never straddles a
    segment, because both ``bucket`` and the batch size are powers of two.
    The index arrays stream through SMEM block by block, so a call's pair
    count is bounded only by the int32 accumulator, not by SMEM.

    Established on a TPU v5e: Mosaic accepts this kernel at real sizes
    (``tests/test_tpu_compile.py`` compiles it for a described ``v5e:2x2``
    at 2^21-row stores), and ``chip_smoke.py`` matches the exact oracle
    through it on the chip. It takes about 175 ns per pair whether 32, 128
    or 512 pairs' DMAs are in flight, 10x the jnp mirror's time
    (``python -m benchmarks.kernel_micro --chip``), so the batch size is
    fixed: the cost is the per-pair scalar loop, not the DMA depth.
  * ``gather_total_pallas`` — the same kernel with one segment.
  * ``gather_and_words_reference`` — the per-pair AND words themselves,
    before any popcount is summed (the per-vertex attribution's input).
  * ``gather_total_reference`` / ``gather_segment_totals_reference`` —
    vectorized jnp mirrors with identical semantics (including the
    negative-index contract). On the CPU backend the interpreted kernel is a
    correctness tool, not a performance path, so the executor runs the
    mirror there; XLA fuses gather+AND+popcount+reduce into one loop. The
    mirrors use the kernels' SWAR popcount so the ``lax.population_count``
    oracle in ``kernels/ref.py`` stays an independent check.

Accumulation is int32; callers bound ``num_pairs * words_per_slice * 32``
against the int32 limit (see ``kernels/ops.py`` and ``core/executor.py``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import swar_popcount_u32

__all__ = [
    "gather_total_pallas",
    "gather_total_reference",
    "gather_segment_totals_pallas",
    "gather_segment_totals_reference",
    "gather_and_words_reference",
]

# Lane width of a store tile: the DMA unit is one (W, 128) tile.
LANES = 128
# Pairs per grid step. 1-D int32 arrays are laid out in T(1024) tiles, and
# Mosaic refuses an SMEM index block that does not match that layout.
INDEX_BLOCK = 1024
# Pairs whose tile DMAs are in flight together (a power of two <= INDEX_BLOCK).
BLOCK_PAIRS = 128


def _tile_view(store: jax.Array) -> jax.Array:
    """``[R, W]`` store -> ``[ceil(R/128), W, 128]`` tiles (slice r is lane
    ``r % 128`` of tile ``r // 128``). A bitcast of XLA's TPU layout when R
    is a multiple of 128 (the executors' pow2 stores from 128 rows up);
    smaller stores are zero-padded, which is exact (zero slices add 0)."""
    rows, w = store.shape
    padded = -(-rows // LANES) * LANES
    if padded != rows:
        store = jnp.pad(store, ((0, padded - rows), (0, 0)))
    return store.reshape(padded // LANES, LANES, w).transpose(0, 2, 1)


def _gather_segment_kernel(
    ridx_ref, cidx_ref, row_tiles, col_tiles, out_ref, row_buf, col_buf, sems,
    *, block_pairs: int, bucket: int,
):
    """One 1024-pair index block: tile DMAs in batches, per-segment sums."""
    step = pl.program_id(0)
    w = row_buf.shape[1]
    lane = jax.lax.broadcasted_iota(jnp.int32, (w, LANES), 1)

    @pl.when(step == 0)
    def _zero():
        def zero(g, carry):
            out_ref[g] = 0
            return carry

        jax.lax.fori_loop(0, out_ref.shape[0], zero, 0)

    def copies(b, r, c):
        return (
            pltpu.make_async_copy(row_tiles.at[r // LANES], row_buf.at[b], sems.at[0]),
            pltpu.make_async_copy(col_tiles.at[c // LANES], col_buf.at[b], sems.at[1]),
        )

    def batch(j, carry):
        base = j * block_pairs

        def pair(b):
            r = ridx_ref[base + b]
            c = cidx_ref[base + b]
            return r, c, (r >= 0) & (c >= 0)

        def start(b, carry):
            r, c, valid = pair(b)

            @pl.when(valid)
            def _():
                for dma in copies(b, r, c):
                    dma.start()

            return carry

        def wait(b, carry):
            # Every copy of a side shares one semaphore and one size, so all
            # of them are waited for before any buffer is read.
            r, c, valid = pair(b)

            @pl.when(valid)
            def _():
                for dma in copies(b, r, c):
                    dma.wait()

            return carry

        def accumulate(b, acc):
            r, c, valid = pair(b)
            lc = c % LANES
            # Bring slice r's lane to slice c's lane, then AND the W words.
            row = pltpu.roll(row_buf[b], (lc - r % LANES) % LANES, 1)
            pc = swar_popcount_u32(row & col_buf[b])
            return acc + jnp.where(valid & (lane == lc), pc, 0)

        jax.lax.fori_loop(0, block_pairs, start, 0)
        jax.lax.fori_loop(0, block_pairs, wait, 0)
        acc = jax.lax.fori_loop(
            0, block_pairs, accumulate, jnp.zeros((w, LANES), jnp.int32)
        )
        seg = (step * INDEX_BLOCK + base) // bucket
        out_ref[seg] += jnp.sum(acc)
        return carry

    jax.lax.fori_loop(0, INDEX_BLOCK // block_pairs, batch, 0)


def _segment_totals(
    row_data, col_data, row_idx, col_idx, bucket, interpret,
    block_pairs=BLOCK_PAIRS,
):
    """Run the kernel over ``row_idx``/``col_idx`` in ``bucket``-wide
    segments; returns every segment of the 1024-padded index arrays.
    ``block_pairs`` is left open only so tests can cover batches that do
    not divide the pair count."""
    if block_pairs < 1 or block_pairs & (block_pairs - 1) or block_pairs > INDEX_BLOCK:
        raise ValueError(
            f"block_pairs must be a power of two <= {INDEX_BLOCK}, got {block_pairs}"
        )
    p = row_idx.shape[0]
    padded = -(-p // INDEX_BLOCK) * INDEX_BLOCK
    if padded != p:
        row_idx = jnp.pad(row_idx, (0, padded - p), constant_values=-1)
        col_idx = jnp.pad(col_idx, (0, padded - p), constant_values=-1)
    w = row_data.shape[1]
    block_pairs = min(block_pairs, bucket)
    grid_spec = pl.GridSpec(
        grid=(padded // INDEX_BLOCK,),
        in_specs=[
            pl.BlockSpec((INDEX_BLOCK,), lambda i: (i,), memory_space=pltpu.SMEM),
            pl.BlockSpec((INDEX_BLOCK,), lambda i: (i,), memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec(memory_space=pltpu.SMEM),
        scratch_shapes=[
            pltpu.VMEM((block_pairs, w, LANES), jnp.uint32),
            pltpu.VMEM((block_pairs, w, LANES), jnp.uint32),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    return pl.pallas_call(
        functools.partial(
            _gather_segment_kernel, block_pairs=block_pairs, bucket=bucket
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((-(-padded // bucket),), jnp.int32),
        interpret=interpret,
    )(row_idx, col_idx, _tile_view(row_data), _tile_view(col_data))


def _check_operands(row_data, col_data, row_idx, col_idx) -> None:
    assert row_idx.shape == col_idx.shape, (row_idx.shape, col_idx.shape)
    assert row_data.ndim == col_data.ndim == 2
    assert col_data.shape[1] == row_data.shape[1], (row_data.shape, col_data.shape)


@functools.partial(jax.jit, static_argnames=("interpret",))
def gather_total_pallas(
    row_data: jax.Array,  # [R, W] uint32 — row-side slice store (stays put)
    col_data: jax.Array,  # [C, W] uint32 — col-side slice store (stays put)
    row_idx: jax.Array,  # [P] int32 work-list row positions (< 0 = no-op)
    col_idx: jax.Array,  # [P] int32 work-list col positions (< 0 = no-op)
    *,
    interpret: bool = False,
) -> jax.Array:
    """Fused total popcount(row_data[row_idx] & col_data[col_idx]) -> int32.

    The gather happens *inside* the kernel: the indices drive tile DMAs
    straight from the HBM-resident stores, ``BLOCK_PAIRS`` pairs in flight
    at a time. Negative index pairs contribute zero.
    """
    _check_operands(row_data, col_data, row_idx, col_idx)
    p = row_idx.shape[0]
    if p == 0:
        return jnp.int32(0)
    # One segment spanning the padded index arrays: the padding is -1.
    padded = -(-p // INDEX_BLOCK) * INDEX_BLOCK
    return _segment_totals(
        row_data, col_data, row_idx, col_idx, padded, interpret
    )[0]


@functools.partial(jax.jit, static_argnames=("bucket", "interpret"))
def gather_segment_totals_pallas(
    row_data: jax.Array,  # [R, W] uint32 — stacked row-side slice stores
    col_data: jax.Array,  # [C, W] uint32 — stacked col-side slice stores
    row_idx: jax.Array,  # [G * bucket] int32, store-global (< 0 = no-op)
    col_idx: jax.Array,  # [G * bucket] int32, store-global (< 0 = no-op)
    *,
    bucket: int,
    interpret: bool = False,
) -> jax.Array:
    """Per-segment popcount totals over a fused multi-graph index block.

    ``row_idx``/``col_idx`` hold ``G = len(row_idx) // bucket`` graphs'
    worklists, each padded to the shared pow2 ``bucket`` with the ``-1``
    sentinel and shifted into the stacked stores' coordinates. Returns the
    ``[G]`` int32 per-graph subtotals of one dispatch. Each segment's worst
    case ``bucket * W * 32`` must fit int32 (callers bound it — see
    ``kernels/ops.py``).
    """
    _check_operands(row_data, col_data, row_idx, col_idx)
    p = row_idx.shape[0]
    if bucket < 1 or bucket & (bucket - 1) or p % bucket:
        raise ValueError(
            f"{p} pairs do not tile into power-of-two bucket={bucket} segments"
        )
    g = p // bucket
    if g == 0:
        return jnp.zeros((0,), jnp.int32)
    return _segment_totals(
        row_data, col_data, row_idx, col_idx, bucket, interpret
    )[:g]


def gather_segment_totals_reference(
    row_data: jax.Array,
    col_data: jax.Array,
    row_idx: jax.Array,
    col_idx: jax.Array,
    *,
    bucket: int,
) -> jax.Array:
    """Vectorized mirror of ``gather_segment_totals_pallas`` (same contract).

    One fused gather + AND + SWAR popcount over all ``G * bucket`` lanes,
    segment-summed by a ``[G, bucket]`` reshape — the executor's CPU path
    for cross-graph fused dispatch, sharing ``gather_total_reference``'s
    negative-index no-op semantics exactly.
    """
    p = row_idx.shape[0]
    if bucket < 1 or p % bucket:
        raise ValueError(f"{p} pairs do not tile into bucket={bucket} segments")
    g = p // bucket
    if g == 0:
        return jnp.zeros((0,), jnp.int32)
    words = gather_and_words_reference(row_data, col_data, row_idx, col_idx)
    per_pair = swar_popcount_u32(words).sum(axis=1)
    return per_pair.reshape(g, bucket).sum(axis=1, dtype=jnp.int32)


def gather_total_reference(
    row_data: jax.Array,
    col_data: jax.Array,
    row_idx: jax.Array,
    col_idx: jax.Array,
) -> jax.Array:
    """Vectorized mirror of ``gather_total_pallas`` (same no-op contract).

    Pure jnp, so it is portable inside jit/shard_map and is the executor's
    CPU path. Uses the SWAR popcount (not ``lax.population_count``) so the
    ref.py oracle remains algorithm-independent evidence of correctness.
    """
    if row_idx.shape[0] == 0:
        return jnp.int32(0)
    words = gather_and_words_reference(row_data, col_data, row_idx, col_idx)
    return swar_popcount_u32(words).sum(dtype=jnp.int32)


def gather_and_words_reference(
    row_data: jax.Array,
    col_data: jax.Array,
    row_idx: jax.Array,
    col_idx: jax.Array,
) -> jax.Array:
    """Per-pair AND words ``row_data[row_idx] & col_data[col_idx]`` ->
    ``[P, W]`` uint32, all-zero for a pair with a negative index.

    Eq. 5 before its BitCount is summed: bit ``b`` of pair (edge (u, v),
    slice k) is set exactly when ``w = k * slice_bits + b`` closes the
    triangle u < w < v. The totals above are its popcount sums; the
    per-vertex attribution (``core.executor``) reads the words themselves.
    """
    mask = (row_idx >= 0) & (col_idx >= 0)
    rows = jnp.take(row_data, jnp.maximum(row_idx, 0), axis=0)
    cols = jnp.take(col_data, jnp.maximum(col_idx, 0), axis=0)
    # Zeroing one side of the AND suffices: x & 0 == 0.
    return jnp.where(mask[:, None], rows, 0) & cols
