"""The controls of `correct`, at a size a test run can hold: the plain
reference in the program's place passes in float32, fails
`unexplained_jobs_share` in bfloat16 (the step below the precision the
configuration states), and fails `misplaced_jobs_share` with the better
half of the nodes hidden from its argmax (right scores, wrong choice)."""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark import control, reference                # noqa: E402


@pytest.mark.parametrize("workload", ["c2m-10k.backlog",
                                      "c2m-10k.spread-steady"])
@pytest.mark.parametrize("seed", [5, 2147483659])
def test_controls_are_not_correct(workload, seed):
    got = control.readings(workload, seed, jobs=10, n_nodes=1024)
    assert got["sound"]["correct"], got
    assert got["sound"]["unexplained_jobs_share"] == 0.0
    assert got["sound"]["misplaced_jobs_share"] == 0.0
    assert not got["control"]["correct"], got
    assert got["control"]["unexplained_jobs_share"] \
        > reference.LIMITS["unexplained_jobs_share"], got
    assert not got["half_hidden"]["correct"], got
    assert got["half_hidden"]["misplaced_jobs_share"] \
        > reference.LIMITS["misplaced_jobs_share"], got
    assert got["half_hidden"]["unexplained_jobs_share"] == 0.0, got
