"""The controls of `system-10k`'s `correct`, at a size a test run can
hold: the plain reference in the program's place, on the run's own jobs
(the warm pass's rack job, then the fleet's), passes in float32; fails
`unexplained_jobs_share`, and nothing else, in bfloat16; and fails
`violations`, and nothing else, with the highest tier taken first, with
every hundredth node of a scope left out, and with the rack's job placed
on every rack.  With the priority delta dropped it passes, and that is
the cluster's doing: the lowest tier goes first, every node keeps two
fillers of tiers 20 / 35 and gives at most two, so tier 45 is never
reached (`preempt-10k`'s controls hold the delta)."""
import os
import signal
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark import control                           # noqa: E402
from benchmark.system import reference                  # noqa: E402

LIMIT_S = 120
SOUND = {"violations": 0, "unexplained_jobs_share": 0.0}


@pytest.fixture(autouse=True)
def time_limit():
    def late(_sig, _frame):
        raise TimeoutError(f"over {LIMIT_S} s")
    old = signal.signal(signal.SIGALRM, late)
    signal.alarm(LIMIT_S)
    yield
    signal.alarm(0)
    signal.signal(signal.SIGALRM, old)


@pytest.fixture(scope="module", params=[5, 2147483659])
def readings(request):
    return control.readings("system-10k.fleet-rollout", request.param,
                            jobs=1, n_nodes=1024)


@pytest.mark.parametrize("name", ["sound", "delta_dropped"])
def test_reads_as_sound(readings, name):
    assert readings[name] == {"correct": True, **SOUND}, readings


@pytest.mark.parametrize("name, number", [
    ("control", "unexplained_jobs_share"), ("highest_first", "violations"),
    ("node_skipped", "violations"), ("scope_dropped", "violations")])
def test_control_fails_the_one_number_it_is_for(readings, name, number):
    got = readings[name]
    assert not got["correct"], readings
    assert got[number] > reference.LIMITS[number], readings
    others = {k: v for k, v in got.items() if k not in ("correct", number)}
    assert others == {k: v for k, v in SOUND.items() if k != number}, readings
