"""The per-vertex attribution's work, counted the same whatever implements it.

For P valid slice pairs (``bench.graphs.slice_pairs``) and S triangles, a
per-vertex count has to read, per pair, the row and the column slice
(``slice_bits / 8`` bytes each) and their two 4-byte store positions, as
the count's kernel does (``bench.roofline``), and besides the pair's
4-byte edge index, that edge's two 4-byte endpoints and the row slice's
4-byte slice number, which name the pair's three vertices. Per triangle it
has to update one 4-byte count for each of its three vertices: 12 bytes
written, the read of a count held in HBM left out so the sum stays a
least. The AND, popcount and bit selection are far below the chip's
compute peak, so the least time is these bytes over the HBM bandwidth.
"""
from __future__ import annotations


def vertex_bytes(pairs: int, triangles: int, slice_bits: int) -> int:
    return int(pairs) * (2 * (slice_bits // 8) + 6 * 4) + 3 * 4 * int(triangles)


def least_time_s(pairs: int, triangles: int, slice_bits: int,
                 hbm_bytes_per_s: float) -> float:
    return vertex_bytes(pairs, triangles, slice_bits) / hbm_bytes_per_s
