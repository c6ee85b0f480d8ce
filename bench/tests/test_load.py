"""The load generator: the windows it draws."""
import numpy as np
import pytest

from bench import load


@pytest.mark.parametrize("seed", [3, 2**31 + 9])
def test_windows_are_evenly_spaced_and_leave_the_tail(seed):
    rng = np.random.default_rng(seed)
    for _ in range(200):
        iv = load.interval(rng, 100_000, 0.05, 32, tail=10_000)
        assert len(iv) == 32 and 0 <= iv[0] and iv[-1] <= 90_000
        assert iv[-1] - iv[0] == 5_000
        assert max(np.diff(iv)) - min(np.diff(iv)) <= 1


def test_the_same_seed_draws_the_same_windows():
    def draw(seed):
        rng = np.random.default_rng([seed, 2])
        return [load.interval(rng, 1_000_000, 0.05, 32, tail=100_000)
                for _ in range(4)]

    assert draw(2**31 + 1) == draw(2**31 + 1) != draw(2**31 + 2)
