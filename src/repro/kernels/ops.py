"""Public jit'd wrappers for the TCIM kernels.

Handle padding/layout so callers never think about block alignment, and pick
``interpret=True`` automatically on the CPU backend (the validation mode for
tests; on a TPU the same calls compile to Mosaic).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.common import on_cpu, on_tpu
from repro.kernels.slice_and_popcount import items_pallas, total_pallas
from repro.kernels.tc_bitgemm import bitgemm_pallas
from repro.kernels.tc_dense_mxu import dense_mxu_tc_pallas
from repro.kernels.tc_gather_popcount import (
    gather_segment_totals_pallas,
    gather_segment_totals_reference,
    gather_total_pallas,
    gather_total_reference,
)

__all__ = [
    "popcount_and_items",
    "popcount_and_total",
    "popcount_and_gather_total",
    "popcount_and_gather_segment_totals",
    "bitgemm",
    "dense_mxu_tc",
    "INT32_SAFE_WORDS",
    "gather_kernel_selected",
]

# Largest number of uint32 words whose AND-popcount total provably fits the
# kernels' int32 accumulator: each word contributes at most 32 to the sum.
INT32_SAFE_WORDS = (2**31 - 1) // 32


def _interpret(flag: bool | None) -> bool:
    return on_cpu() if flag is None else flag


def gather_kernel_selected(use_kernel: bool | None = None) -> bool:
    """Whether the gather primitives below run the Pallas kernel.

    ``use_kernel=None`` means: on a TPU, always the kernel — the jnp mirror
    never stands in for it there; elsewhere the mirror (on the CPU the
    interpreted kernel is a correctness tool, and on a GPU XLA fuses the
    mirror). The one place this choice is made, so callers can report it.
    """
    return on_tpu() if use_kernel is None else bool(use_kernel)


def _pad_rows(a: jax.Array, multiple: int) -> jax.Array:
    p = a.shape[0]
    rem = (-p) % multiple
    if rem:
        a = jnp.pad(a, ((0, rem),) + ((0, 0),) * (a.ndim - 1))
    return a


def popcount_and_items(
    rows: jax.Array,
    cols: jax.Array,
    *,
    block_p: int = 512,
    interpret: bool | None = None,
) -> jax.Array:
    """Per-pair popcount(rows & cols): [P, W] x [P, W] uint32 -> [P] int32.

    Also reused as a generic primitive (e.g. MoE routing-mask overlap stats).
    """
    p = rows.shape[0]
    if p == 0:
        return jnp.zeros((0,), jnp.int32)
    block_p = min(block_p, max(8, 1 << int(np.ceil(np.log2(p)))))
    rows = _pad_rows(rows, block_p)
    cols = _pad_rows(cols, block_p)
    out = items_pallas(rows, cols, block_p=block_p, interpret=_interpret(interpret))
    return out[:p]


def popcount_and_total(
    rows: jax.Array,
    cols: jax.Array,
    *,
    block_rows: int = 256,
    lanes: int = 1024,
    interpret: bool | None = None,
) -> jax.Array:
    """Fused scalar int32 total of popcount(rows & cols) over all pairs.

    Flattens [P, W] word streams into zero-padded (T, lanes) blocks — zero
    words contribute nothing, so padding is free — then runs the fused
    reduction kernel (one HBM pass, no per-item materialization).

    The kernel accumulates in int32, so a single call is only safe when the
    worst-case count ``total_words * 32`` (i.e. ``chunk_pairs *
    words_per_slice * 32`` for the executor's chunks) fits int32; the guard
    below enforces it. Callers chunk larger streams and accumulate the
    per-chunk int32 totals exactly (host Python ints or a checked device
    accumulator — see core/executor.py).
    """
    assert rows.shape == cols.shape, (rows.shape, cols.shape)
    total_words = int(np.prod(rows.shape))
    if total_words == 0:
        return jnp.int32(0)
    if total_words > INT32_SAFE_WORDS:
        raise ValueError(
            f"{total_words} words could overflow the int32 accumulator "
            f"(max safe: {INT32_SAFE_WORDS} = (2**31-1)//32); "
            "chunk the stream and accumulate per-chunk totals"
        )
    r = rows.reshape(-1)
    c = cols.reshape(-1)
    tile = block_rows * lanes
    rem = (-total_words) % tile
    if rem:
        r = jnp.pad(r, (0, rem))
        c = jnp.pad(c, (0, rem))
    r = r.reshape(-1, lanes)
    c = c.reshape(-1, lanes)
    return total_pallas(
        r, c, block_rows=block_rows, lanes=lanes, interpret=_interpret(interpret)
    )


def popcount_and_gather_total(
    row_data: jax.Array,
    col_data: jax.Array,
    row_idx: jax.Array,
    col_idx: jax.Array,
    *,
    use_kernel: bool | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """Fused gather–AND–popcount total over a work-list chunk -> int32 scalar.

    The TCIM execute primitive: slice stores stay resident, the index arrays
    select the valid slice pairs, and the gather happens inside the fused
    computation — no ``[P, W]`` gathered operands ever materialize in HBM.
    Negative indices are exact no-ops (the chunk-padding/sharding sentinel).

    ``use_kernel`` picks the Pallas kernel or the vectorized jnp mirror
    (``gather_kernel_selected``; both share semantics and are cross-checked
    in tests).
    """
    assert row_idx.shape == col_idx.shape, (row_idx.shape, col_idx.shape)
    p = row_idx.shape[0]
    w = row_data.shape[1]
    if p == 0:
        return jnp.int32(0)
    if p * w > INT32_SAFE_WORDS:
        raise ValueError(
            f"chunk of {p} pairs x {w} words could overflow the int32 "
            f"accumulator (max safe words: {INT32_SAFE_WORDS}); "
            "reduce chunk_pairs"
        )
    if gather_kernel_selected(use_kernel):
        return gather_total_pallas(
            row_data,
            col_data,
            row_idx.astype(jnp.int32),
            col_idx.astype(jnp.int32),
            interpret=_interpret(interpret),
        )
    return gather_total_reference(row_data, col_data, row_idx, col_idx)


def popcount_and_gather_segment_totals(
    row_data: jax.Array,
    col_data: jax.Array,
    row_idx: jax.Array,
    col_idx: jax.Array,
    *,
    bucket: int,
    use_kernel: bool | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """Per-graph int32 subtotals over a fused multi-graph index block.

    The cross-graph serving primitive: ``row_idx``/``col_idx`` are ``G``
    back-to-back ``bucket``-wide worklist segments (one per fused graph,
    sentinel-padded, indices shifted into the stacked stores), and one
    dispatch returns the ``[G]`` per-graph totals — a segment-summed
    accumulator instead of ``popcount_and_gather_total``'s single scalar.

    Each segment accumulates independently, so the int32 bound is per
    segment: ``bucket * words_per_slice * 32`` must fit int32.
    """
    assert row_idx.shape == col_idx.shape, (row_idx.shape, col_idx.shape)
    p = row_idx.shape[0]
    w = row_data.shape[1]
    if bucket < 1 or p % bucket:
        raise ValueError(
            f"{p} fused pairs do not tile into bucket={bucket} segments"
        )
    if p == 0:
        return jnp.zeros((0,), jnp.int32)
    if bucket * w > INT32_SAFE_WORDS:
        raise ValueError(
            f"fused segment of {bucket} pairs x {w} words could overflow "
            f"the int32 accumulator (max safe words: {INT32_SAFE_WORDS}); "
            "route the graph solo with a smaller chunk_pairs"
        )
    if gather_kernel_selected(use_kernel):
        return gather_segment_totals_pallas(
            row_data,
            col_data,
            row_idx.astype(jnp.int32),
            col_idx.astype(jnp.int32),
            bucket=bucket,
            interpret=_interpret(interpret),
        )
    return gather_segment_totals_reference(
        row_data, col_data, row_idx, col_idx, bucket=bucket
    )


def bitgemm(
    x: jax.Array,
    y: jax.Array,
    *,
    block_i: int = 128,
    block_j: int = 128,
    block_w: int = 8,
    interpret: bool | None = None,
) -> jax.Array:
    """Popcount-GEMM: [I, W] x [J, W] uint32 -> [I, J] int32."""
    i_dim, w = x.shape
    j_dim = y.shape[0]
    block_i = min(block_i, i_dim) if i_dim else block_i
    block_j = min(block_j, j_dim) if j_dim else block_j
    block_w = min(block_w, w) if w else block_w
    xp = _pad_rows(x, block_i)
    yp = _pad_rows(y, block_j)
    rem_w = (-w) % block_w
    if rem_w:
        xp = jnp.pad(xp, ((0, 0), (0, rem_w)))
        yp = jnp.pad(yp, ((0, 0), (0, rem_w)))
    out = bitgemm_pallas(
        xp,
        yp,
        block_i=block_i,
        block_j=block_j,
        block_w=block_w,
        interpret=_interpret(interpret),
    )
    return out[:i_dim, :j_dim]


def dense_mxu_tc(
    a: jax.Array,
    *,
    block: int = 256,
    interpret: bool | None = None,
) -> jax.Array:
    """Masked A @ A triangle count on the MXU. a: [N, N] {0,1} (any int/bool dtype)."""
    n = a.shape[0]
    block = min(block, n)
    rem = (-n) % block
    ab = a.astype(jnp.bfloat16)
    if rem:
        ab = jnp.pad(ab, ((0, rem), (0, rem)))
    return dense_mxu_tc_pallas(
        ab,
        block_i=block,
        block_j=block,
        block_k=block,
        interpret=_interpret(interpret),
    )
