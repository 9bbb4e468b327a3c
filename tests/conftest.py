"""Shared fixtures. NOTE: no XLA_FLAGS here on purpose — smoke tests and
benches must see 1 device; tests that need more force them in a
subprocess (``tests/test_distributed.py``)."""
import numpy as np
import pytest


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)
