"""The generator's arrivals: both spacings an open loop can ask for give
`rate_per_s` x `seconds` due times inside the window, in order; "even"
is the same for every seed, "poisson" is the seed's."""
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark import traffic                           # noqa: E402


@pytest.mark.parametrize("spacing", ["even", "poisson"])
def test_arrivals_count_order_and_window(spacing):
    mix = {"rate_per_s": 2.5, "spacing": spacing}
    due = traffic.arrivals(mix, 2147483659, 45.0)
    assert len(due) == 112
    assert (np.diff(due) >= 0).all() and due[0] >= 0.0 and due[-1] <= 45.0
    assert np.array_equal(due, traffic.arrivals(mix, 2147483659, 45.0))


def test_even_is_evenly_spaced_for_every_seed():
    mix = {"rate_per_s": 4.0, "spacing": "even"}
    due = traffic.arrivals(mix, 1, 10.0)
    assert np.allclose(np.diff(due), 0.25)
    assert np.array_equal(due, traffic.arrivals(mix, 2, 10.0))


def test_poisson_is_the_seeds():
    mix = {"rate_per_s": 4.0, "spacing": "poisson"}
    a, b = traffic.arrivals(mix, 1, 10.0), traffic.arrivals(mix, 2, 10.0)
    assert not np.array_equal(a, b)
    assert np.std(np.diff(a)) > 0.05


def test_unknown_spacing_is_an_error():
    with pytest.raises(ValueError):
        traffic.arrivals({"rate_per_s": 1.0, "spacing": "bursty"}, 1, 10.0)


def test_committed_open_loops_name_their_spacing():
    folder = os.path.join(os.path.dirname(traffic.__file__), "traffic")
    for name in os.listdir(folder):
        mix = traffic.load(name[: -len(".json")])
        if mix["arrivals"] == "open":
            assert mix["spacing"] in ("even", "poisson"), name
