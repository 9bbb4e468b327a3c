"""Drivers: one module per kind of traffic, named by the traffic file's
``driver`` key, each exposing ``run(h: Harness) -> Outcome``."""
