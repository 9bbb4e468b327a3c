"""Config registry: the TCIM paper's graph workloads (``GRAPHS``)."""
from repro.configs.tcim_graphs import GRAPHS

__all__ = ["GRAPHS"]
