"""Graph analytics on the TCIM engine: the metrics the paper motivates.

Clustering coefficient / transitivity (paper §I) and k-truss decomposition
(computed by the paper's GPU/FPGA baselines), all built on the Eq. 5
AND+BitCount per-pair counts.

    PYTHONPATH=src python examples/graph_analytics.py
"""
import numpy as np

from repro.core.metrics import clustering_coefficients, edge_support, max_truss
from repro.graphs import build_graph, rmat
from repro.graphs.exact import triangles_intersection
from repro.runtime.compile_cache import enable_compile_cache


def main():
    edges = rmat(3000, 24000, seed=42)
    g = build_graph(edges, reorder=True)
    print(f"graph |V|={g.n} |E|={g.m}")

    sup = edge_support(g)
    tri = triangles_intersection(g)
    assert sup.sum() == tri
    print(f"triangles={tri}; per-edge support: max={sup.max()}, "
          f"mean={sup.mean():.2f} (sum == TC, Eq. 5 aggregated per edge)")

    local, trans = clustering_coefficients(g)
    print(f"transitivity={trans:.4f}; mean local clustering={local.mean():.4f}")
    top = np.argsort(local)[-3:][::-1]
    print(f"most clustered vertices: {[(int(v), round(float(local[v]), 3)) for v in top]}")

    k = max_truss(g)
    print(f"max k-truss: k={k} (densest cohesive subgraph survives {k - 2} "
          f"triangles per edge)")


if __name__ == "__main__":
    enable_compile_cache()
    main()
