"""The graph shapes every counting entry point is checked on.

Each case is ``(name, edges)`` with a canonical undirected edge list; the
shapes cover the degenerate graphs (empty, a single edge, one triangle), a
clique, a triangle-free graph, a low-degree lattice and two random graphs.
"""
import numpy as np

from repro.graphs import (
    complete_graph,
    erdos_renyi,
    grid_road,
    rmat,
    triangle_free_bipartite,
)

GRAPH_CASES = [
    ("rmat", rmat(400, 2500, seed=1)),
    ("er", erdos_renyi(300, 1500, seed=2)),
    ("k16", complete_graph(16)),
    ("bipartite", triangle_free_bipartite(200, 800, seed=3)),
    ("road", grid_road(400, seed=4)),
    ("empty", np.zeros((0, 2), dtype=np.int64)),
    ("single_edge", np.array([[0, 1]], dtype=np.int64)),
    ("triangle", np.array([[0, 1], [0, 2], [1, 2]], dtype=np.int64)),
]
GRAPH_IDS = [c[0] for c in GRAPH_CASES]


def num_vertices(edges: np.ndarray) -> int:
    """The case's vertex count; the empty graph gets four isolated vertices."""
    return int(edges.max()) + 1 if len(edges) else 4
