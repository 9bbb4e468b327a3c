"""The execute kernel's work, counted the same whatever implements it.

For P valid slice pairs (``bench.graphs.slice_pairs``) the kernel has to
read, per pair, one row slice and one column slice (``slice_bits / 8``
bytes each) and one 4-byte index for each, and write one 4-byte total per
output segment. Its AND and popcount operations are far below the chip's
compute peak, so the least time is these bytes over the HBM bandwidth.
"""
from __future__ import annotations


def kernel_bytes(pairs: int, slice_bits: int, segments: int) -> int:
    return int(pairs) * (2 * (slice_bits // 8) + 2 * 4) + 4 * int(segments)


def least_time_s(pairs: int, slice_bits: int, segments: int,
                 hbm_bytes_per_s: float) -> float:
    return kernel_bytes(pairs, slice_bits, segments) / hbm_bytes_per_s
