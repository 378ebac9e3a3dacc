"""The reduction from a profiler trace to busy time, kernel time and the
breakdown lists, held to a small trace recorded on a TPU v5e (six calls
of a jitted `place_bulk_tiny`, 256 x 256, each inside a
`bench.register` annotation with a 4 ms `bench.wait` between them;
`.chipcheck/dev/tiny_trace.py` of PR 24 recorded it)."""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark import trace_reduce                      # noqa: E402
from benchmark.readers import complement, ratio, roofline  # noqa: E402

TRACE = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "testdata", "tiny.xplane.pb")


@pytest.fixture(scope="module")
def reduced():
    return trace_reduce.reduce_trace(
        TRACE, {"place_bulk": "place_bulk", "place_scan": "place_batch"})


def test_one_chip_and_its_busy_time(reduced):
    assert reduced["chips"] == 1
    # the union of the op intervals: 6 programs of about 5.8 us each
    assert reduced["busy_s"] == pytest.approx(3.4844e-05, rel=1e-6)


def test_kernel_time_is_the_named_programs(reduced):
    assert reduced["kernel_s"]["place_bulk"] == pytest.approx(
        3.4913e-05, rel=1e-6)
    assert reduced["kernel_s"]["place_scan"] == 0.0
    # busy time cannot pass the programs' own time by more than rounding
    assert reduced["busy_s"] <= reduced["kernel_s"]["place_bulk"] * 1.01


def test_breakdown_lists(reduced):
    ops = reduced["device_ops"]
    assert 0 < len(ops) <= 10
    assert ops[0][0] == "%while" and ops[0][1] == pytest.approx(
        2.7082e-05, rel=1e-6)
    assert [s for _, s in ops] == sorted((s for _, s in ops), reverse=True)
    gaps = dict(reduced["idle_gaps"])
    # Worked by hand from the trace (PR 40; until then this asserted that
    # `bench.register` heads the list: the old rule gave each whole gap to
    # the name with the most summed overlap, and all 27 ms to the span that
    # held the client's thread for a seventh of it).  The chip idles for
    # 27.019 ms, all but 24 ns of it in the five spaces of 5.29-5.55 ms
    # between the six programs.  The client's line holds a `bench.wait` of
    # 4.49-4.68 ms after each call and a `bench.register` of 0.76-0.90 ms
    # around it.  The first five waits lie in the gaps but for the fifth's
    # last 0.92 ms (the device's clock runs 1 ms ahead of the host's here,
    # so the last gap ends first) and 30 us of programs: 21.826 ms, and no
    # other line has an event open meanwhile, so all of it is theirs.  The
    # other 5.19 ms are the registers' (1.56 ms where the client's line is
    # alone), the runtime's own events on `main/288` and `futex-...`, which
    # run inside the registers and share those slices (`ReadSyncFlag` 0.95,
    # `PjitFunction` 0.82, ...), and 1.00 ms with nothing open (0.94 of it
    # before the first register).  So the client waiting heads the list,
    # which is the truth of this trace: nothing was there to dispatch.
    assert max(gaps, key=gaps.get) == "bench.wait"
    assert gaps["bench.wait"] == pytest.approx(21.826044e-3, rel=1e-6)
    assert gaps["bench.register"] == pytest.approx(1.559571e-3, rel=1e-6)
    assert gaps["no host span"] == pytest.approx(1.002664e-3, rel=1e-6)
    assert sum(gaps.values()) <= 27.019358e-3      # the ten largest of 30


def test_readers_return_nothing_when_there_is_nothing_to_read():
    spec = {"kernel": "place_bulk", "steps": ["trace.engine.bulk_evals"],
            "bytes": "bulk_eval_bytes"}
    assert roofline.read({"device.kind": "TPU v5 lite"}, spec) is None
    assert complement.read({}, {"part": "a", "whole": "b"}) is None
    assert ratio.read({}, {"num": ["a"], "den": ["b"], "scale": 1.0}) is None
    assert ratio.read({"a": 1.0, "b": 0.0},
                      {"num": ["a"], "den": ["b"], "scale": 1.0}) is None


def test_roofline_share_from_shapes_and_peak():
    spec = {"kernel": "place_bulk", "steps": ["trace.engine.bulk_evals"],
            "bytes": "bulk_eval_bytes"}
    facts = {"device.kind": "TPU v5 lite", "shape.rows": 16384.0,
             "shape.resource_dims": 4.0, "trace.kernel_s.place_bulk": 0.1,
             "trace.engine.bulk_evals": 1000.0}
    least = 3 * 16384 * 4 * 4
    assert roofline.read(facts, spec) == pytest.approx(
        100.0 * 1000 * least / 819e9 / 0.1)
    with pytest.raises(KeyError):
        roofline.read({**facts, "device.kind": "TPU v9"}, spec)
