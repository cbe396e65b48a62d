"""Shared fixtures.  NOTE: no XLA_FLAGS here — smoke tests and benches see
the real single CPU device."""

import numpy as np
import pytest


@pytest.fixture
def rng():
    return np.random.default_rng(0)
