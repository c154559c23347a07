import numpy as np
import pytest


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: multi-second end-to-end runs; deselect with -m 'not slow' "
        "(the fast CI lane)")


@pytest.fixture
def rng():
    return np.random.default_rng(0)
