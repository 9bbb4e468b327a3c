from repro.data.graph_pipeline import load_graph

__all__ = ["load_graph"]
