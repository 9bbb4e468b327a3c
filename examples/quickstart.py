"""Quickstart: TCIM triangle counting end-to-end on one machine.

    PYTHONPATH=src python examples/quickstart.py

Builds a power-law graph, compresses it into the paper's sliced bitmap
format, counts triangles through every backend (bitwise Pallas kernels, the
pure-jnp oracle, the popcount-GEMM, the beyond-paper MXU path), and prints
the paper's headline statistics (valid-slice %, compute reduction, LRU cache
hit rate, modeled MRAM latency/energy).
"""
import numpy as np

from repro.core import (
    BACKENDS,
    build_sbf,
    build_worklist,
    sbf_stats,
    simulate_lru,
    tcim_count,
    tcim_count_graph,
)
from repro.core.energymodel import tcim_latency_energy
from repro.graphs import build_graph, rmat
from repro.graphs.exact import triangles_intersection
from repro.runtime.compile_cache import enable_compile_cache


def main():
    print("== TCIM quickstart ==")
    # Small graph: every backend, incl. the dense MXU/bitgemm paths (which
    # run the Pallas interpreter per tile on CPU — keep n modest here).
    small = rmat(1500, 9000, seed=7)
    g_small = build_graph(small, reorder=True)
    exact_small = triangles_intersection(g_small)
    print(f"small graph |V|={g_small.n} |E|={g_small.m}: "
          f"exact={exact_small}, all backends:")
    for backend in BACKENDS:
        res = tcim_count(small, backend=backend)
        flag = "OK" if res.triangles == exact_small else "MISMATCH!"
        timing = ", ".join(f"{k}={v*1e3:.1f}ms" for k, v in res.timings_s.items())
        print(f"  backend={backend:13s} triangles={res.triangles} [{flag}] {timing}")

    # Larger sparse graph: the sparse TCIM pipeline proper.
    edges = rmat(20_000, 120_000, seed=7)
    g = build_graph(edges, reorder=True)
    print(f"\ngraph: |V|={g.n} |E|={g.m} (RMAT power-law)")
    exact = triangles_intersection(g)
    print(f"exact triangles (set-intersection baseline): {exact}")
    res = tcim_count(edges, backend="pallas_total")
    flag = "OK" if res.triangles == exact else "MISMATCH!"
    timing = ", ".join(f"{k}={v*1e3:.1f}ms" for k, v in res.timings_s.items())
    print(f"  backend=pallas_total  triangles={res.triangles} [{flag}] {timing}")

    # Device build: orient -> SBF -> worklist as jit-compiled device work.
    # One host->device transfer (the edge list); stores and worklist stay
    # device-resident into the fused executor — bit-identical results. On
    # accelerators build="auto" picks this path by itself.
    res_dev = tcim_count(edges, backend="pallas_total", build="device")
    flag = "OK" if res_dev.triangles == exact else "MISMATCH!"
    timing = ", ".join(f"{k}={v*1e3:.1f}ms" for k, v in res_dev.timings_s.items())
    print(f"  build=device          triangles={res_dev.triangles} [{flag}] {timing}")

    sbf = build_sbf(g, slice_bits=64)
    wl = build_worklist(g, sbf)
    stats = sbf_stats(g, sbf, wl)
    print(f"\nSBF compression: {stats['total_mb']:.2f} MB "
          f"({stats['kb_per_1000_vertices']:.1f} KB / 1000 vertices)")
    print(f"valid slices: {stats['valid_slice_pct']:.3f}% of all slices")
    print(f"compute reduction from slicing: {stats['compute_reduction_pct']:.2f}% "
          f"(paper: 99.99% on large sparse graphs)")

    cache = simulate_lru(sbf, wl)
    print(f"LRU data reuse: {cache.hit_pct:.1f}% hits -> that many column "
          f"WRITEs avoided (paper avg: 72%)")

    lat, en = tcim_latency_energy(wl.num_pairs, cache.misses, g.m)
    print(f"modeled in-MRAM execution: {lat*1e3:.2f} ms, {en*1e3:.3f} mJ")


if __name__ == "__main__":
    enable_compile_cache()
    main()
