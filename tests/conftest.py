import os

# the suite runs on the CPU: on a TPU host JAX would otherwise take the
# chips, and tests that count devices or force a CPU device count break
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import pytest


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running scenario/compile tests")
