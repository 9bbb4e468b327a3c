"""End-to-end behaviour tests for the paper's system (TCIM)."""

from repro.core import tcim_count
from repro.core.cachesim import simulate_lru
from repro.core.energymodel import tcim_latency_energy
from repro.core.sbf import build_sbf, build_worklist, sbf_stats
from repro.graphs import build_graph, rmat
from repro.graphs.exact import triangles_intersection


def test_tcim_end_to_end_pipeline():
    """The full paper pipeline: orient -> compress -> schedule -> count,
    with the headline stats all materializing."""
    edges = rmat(5000, 40000, seed=3)
    g = build_graph(edges, reorder=True)
    res = tcim_count(edges, backend="pallas_total")
    assert res.triangles == triangles_intersection(g)
    sbf = build_sbf(g)
    wl = build_worklist(g, sbf)
    stats = sbf_stats(g, sbf, wl)
    # Slicing must eliminate the vast majority of naive slice-pair work.
    assert stats["compute_reduction_pct"] > 90.0
    # Compression formula = N_VS * (S/8 + 4) bytes.
    assert stats["total_bytes"] == stats["nvs"] * 12
    cache = simulate_lru(sbf, wl)
    assert 0 < cache.hit_pct < 100
    lat, en = tcim_latency_energy(wl.num_pairs, cache.misses, g.m)
    assert lat > 0 and en > 0
