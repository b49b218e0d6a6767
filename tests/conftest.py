# NOTE: do NOT set XLA_FLAGS / device-count overrides here — smoke tests and
# benches must see the real single CPU device. Multi-device tests spawn
# subprocesses with their own XLA_FLAGS (see test_gossip_distributed.py).
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(ROOT, "src")
if SRC not in sys.path:
    sys.path.insert(0, SRC)


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: multi-device subprocess / dry-run tests")


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)
