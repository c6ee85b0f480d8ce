import os
import sys

# Tests run on the CPU (Pallas kernels in interpret mode), also on a host
# that holds an accelerator: the device belongs to one process at a time,
# and a test run must never take it.  Set before anything imports JAX.
os.environ.setdefault("JAX_PLATFORMS", "cpu")

# NOTE: no XLA_FLAGS here on purpose — smoke tests and benches must see the
# real single-device CPU; only the subprocesses in test_distributed.py fake
# 8 devices.
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(scope="session")
def churn():
    from repro.data.generators import churn_network
    return churn_network(n_initial_edges=150, n_events=1200, seed=1)


@pytest.fixture(scope="session")
def growing():
    from repro.data.generators import growing_network
    return growing_network(n_events=1500, seed=2)


def assert_state_equal(got, truth, check_attrs=True, msg=""):
    assert np.array_equal(got.node_mask, truth.node_mask), f"node mask {msg}"
    assert np.array_equal(got.edge_mask, truth.edge_mask), f"edge mask {msg}"
    if check_attrs:
        assert truth.equal(got), f"attrs {msg}"
