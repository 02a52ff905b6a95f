import os
import sys

# Tests run on the CPU; a test that needs more devices starts its own process.
os.environ.setdefault("JAX_PLATFORMS", "cpu")

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import numpy as np
import pytest


@pytest.fixture
def rng():
    return np.random.RandomState(0)


@pytest.fixture
def key():
    return jax.random.PRNGKey(0)
