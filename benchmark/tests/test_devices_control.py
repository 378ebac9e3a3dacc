"""The controls of `devices-10k`'s `correct`, at a size a test run can
hold: the plain reference in the program's place passes in float32; fails
`unexplained_jobs_share` in bfloat16 and with the `devices` scorer left
out; fails `misplaced_jobs_share` with the better half of the nodes
hidden from its argmax; and fails `violations` with groups admitted by
their name alone.  The last needs jobs enough for the preferred 80 GiB
cards to run out: until then the affinity steers `train` to cards that
pass the constraint anyway."""
import os
import signal
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark import control                           # noqa: E402
from benchmark.devices import reference                 # noqa: E402

LIMIT_S = 120


@pytest.fixture(autouse=True)
def time_limit():
    def late(_sig, _frame):
        raise TimeoutError(f"over {LIMIT_S} s")
    old = signal.signal(signal.SIGALRM, late)
    signal.alarm(LIMIT_S)
    yield
    signal.alarm(0)
    signal.signal(signal.SIGALRM, old)


@pytest.mark.parametrize("seed", [5, 2147483659])
def test_controls_are_not_correct(seed):
    got = control.readings("devices-10k.gpu-asks", seed, jobs=12,
                           n_nodes=1024)
    limits = reference.LIMITS
    assert got["sound"] == {"correct": True, "violations": 0,
                            "unexplained_jobs_share": 0.0,
                            "misplaced_jobs_share": 0.0}, got
    for name, number in (("control", "unexplained_jobs_share"),
                         ("affinity_dropped", "unexplained_jobs_share"),
                         ("half_hidden", "misplaced_jobs_share"),
                         ("constraint_dropped", "violations")):
        assert not got[name]["correct"], (name, got)
        assert got[name][number] > limits[number], (name, got)
    # (with half the nodes hidden a fleet of this size runs out of cards
    # for the last gangs: a group off its count, no fault of the scores)
    assert got["half_hidden"]["unexplained_jobs_share"] == 0.0, got
    assert got["control"]["violations"] == 0, got
