"""`trace_reduce.blame`: who the host was while the chip idled, on made-up
events.  Times in ms for reading; the function takes any unit."""
import os
import random
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark.trace_reduce import NO_SPAN, blame       # noqa: E402


def test_a_span_that_touches_a_gap_gets_what_it_touched():
    # the chip idles for 350 ms; a client waits on its register all the
    # while, and the program writes the job to the store for 7 ms of it:
    # the old rule gave all 350 to the name with the most overlap
    gaps = [(100.0, 450.0)]
    lines = {"client": [("bench.register", 90.0, 460.0)],
             "server": [("raft.fsm_apply", 200.0, 207.0)]}
    got = blame(gaps, lines)
    assert got == pytest.approx({"raft.fsm_apply": 7.0,
                                 "bench.register": 343.0})
    assert sum(got.values()) == pytest.approx(350.0, abs=1e-9)


def test_two_threads_open_together_share_a_slice():
    gaps = [(0.0, 10.0)]
    lines = {"w1": [("sched.feasible", 0.0, 10.0)],
             "w2": [("sched.materialise", 4.0, 8.0)],
             "w3": [("sched.feasible", 6.0, 8.0)]}
    got = blame(gaps, lines)
    # 0-4 and 8-10: w1 alone; 4-6: halves; 6-8: thirds, two of them
    # under one name
    assert got == pytest.approx({
        "sched.feasible": 4.0 + 2.0 + 1.0 + 2 * (2.0 / 3),
        "sched.materialise": 1.0 + 2.0 / 3})
    assert sum(got.values()) == pytest.approx(10.0, abs=1e-9)


def test_a_nested_event_takes_the_slice_from_its_parent_on_its_thread_only():
    gaps = [(0.0, 12.0)]
    lines = {"engine": [("engine.put", 0.0, 12.0),
                        ("PjitFunction(place_batch_packed)", 2.0, 6.0),
                        ("PJRT_LoadedExecutable_Execute", 3.0, 4.0)],
             "worker": [("engine.put", 5.0, 7.0)]}
    got = blame(gaps, lines)
    assert got == pytest.approx({
        # 0-2 and 7-12 the engine's alone; 5-6 the worker's half; 6-7 both
        "engine.put": 2.0 + 5.0 + 0.5 + 2 * 0.5,
        # 2-3 and 4-5 alone; 5-6 its half beside the worker's engine.put
        "PjitFunction(place_batch_packed)": 1.0 + 1.0 + 0.5,
        "PJRT_LoadedExecutable_Execute": 1.0})
    assert sum(got.values()) == pytest.approx(12.0, abs=1e-9)


def test_the_client_claims_only_where_no_one_else_does_and_waiting_last():
    gaps = [(0.0, 10.0), (20.0, 30.0)]
    lines = {"c1": [("bench.register", 0.0, 4.0), ("bench.wait", 4.0, 30.0)],
             "c2": [("bench.idle", 0.0, 30.0), ("bench.confirm", 22.0, 23.0)],
             "srv": [("rpc.Job.Register", 1.0, 2.0)]}
    got = blame(gaps, lines)
    assert got == pytest.approx({
        "rpc.Job.Register": 1.0, "bench.register": 3.0,
        "bench.confirm": 1.0,
        # 4-10 and 20-30 less the confirm: the two waiting threads halve it
        "bench.wait": (6.0 + 9.0) / 2, "bench.idle": (6.0 + 9.0) / 2})
    assert sum(got.values()) == pytest.approx(20.0, abs=1e-9)


def test_no_thread_has_an_event_open():
    got = blame([(0.0, 5.0), (7.0, 8.0)],
                {"t": [("plan.commit", 4.0, 7.5), ("empty", 1.0, 1.0)]})
    assert got == pytest.approx({NO_SPAN: 4.0 + 0.5, "plan.commit": 1.5})
    assert blame([(0.0, 5.0)], {}) == {NO_SPAN: 5.0}
    assert blame([], {"t": [("plan.commit", 4.0, 7.5)]}) == {}


def _slow(gaps, lines):
    """The rule as its words have it, slice by slice, thread by thread."""
    cuts = sorted({t for evs in lines.values() for _n, s, e in evs
                   for t in (s, e)})
    out = {}
    for gs, ge in gaps:
        edges = [gs] + [t for t in cuts if gs < t < ge] + [ge]
        for a, b in zip(edges, edges[1:]):
            claims = []
            for evs in lines.values():
                held = [(s, i, n) for i, (n, s, e) in enumerate(evs)
                        if s <= a and b <= e and e > s]
                if held:
                    claims.append(max(held)[2])
            tiers = ([n for n in claims if not n.startswith("bench.")],
                     [n for n in claims if n.startswith("bench.") and
                      n not in ("bench.wait", "bench.idle")], claims)
            share = next((t for t in tiers if t), [NO_SPAN])
            for n in share:
                out[n] = out.get(n, 0.0) + (b - a) / len(share)
    return out


@pytest.mark.parametrize("seed", range(6))
def test_the_sweep_reads_what_the_rule_says_and_adds_up(seed):
    rng = random.Random(seed)
    names = ["sched.feasible", "engine.put", "raft.fsm_apply", "bench.wait",
             "bench.register", "bench.idle", "PjitFunction(x)"]
    lines = {}
    for line in range(5):
        evs, t = [], 0.0
        while t < 1000.0:
            t += rng.expovariate(1 / 5.0)
            e = t + rng.expovariate(1 / 20.0)
            evs.append((rng.choice(names), t, e))
            if rng.random() < 0.5:        # a child, and at times its own
                cs = t + (e - t) * rng.random() * 0.5
                ce = cs + (e - cs) * rng.random()
                evs.append((rng.choice(names), cs, ce))
                if rng.random() < 0.5:
                    evs.append((rng.choice(names), cs, cs + (ce - cs) / 2))
            t = e
        lines[line] = evs
    gaps, t = [], 0.0
    while t < 1000.0:
        t += rng.expovariate(1 / 3.0)
        e = t + rng.expovariate(1 / 15.0)
        gaps.append((t, e))
        t = e
    got, want = blame(gaps, lines), _slow(gaps, lines)
    assert got == pytest.approx(want, rel=1e-9, abs=1e-9)
    assert sum(got.values()) == pytest.approx(
        sum(e - s for s, e in gaps), abs=1e-9)
