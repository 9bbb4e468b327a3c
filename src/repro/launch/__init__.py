"""Launcher: the multi-tenant triangle-count server (``TCServer``)."""
from repro.launch.tc_serve import ServeConfig, ServeRequest, ServeResult, TCServer

__all__ = ["ServeConfig", "ServeRequest", "ServeResult", "TCServer"]
