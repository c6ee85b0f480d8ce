"""The control comes out not correct, at a size a test run holds."""
import pytest

from bench import control
from bench.run import load_cell


@pytest.mark.parametrize("seed", [1, 2**31 + 3])
def test_control_fails_a_limit(seed):
    workload = "churn-1m.interval-loader"
    limits = load_cell(workload, True)[3]["limits"]
    got = control.readings(workload, seed, rehearse=True)
    assert any(v > limits[k] for k, v in got.items()), got
