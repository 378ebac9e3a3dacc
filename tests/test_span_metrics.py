"""The metric files that read the commit's, the system loop's, the
collector's and the CPU-time spans (`benchmark/metrics/`, PR 41): every
fact a file names is a Sample that one job mix on a dev agent really
emits, the `ratio` reader makes a number of them, the file's entries in
`BENCHMARK.json` agree with it and pass `harness.check_benchmark`; and
the rule the spans inside the FSM's apply stand under: no clock reading
reaches the store, so two replicas fed one log end byte-identical
whatever their spans measured.
"""
import copy
import json
import os
import random
import time

import pytest

from benchmark import harness
from benchmark.readers import ratio
from nomad_tpu import mock
from nomad_tpu.raft import MessageType, NomadFSM
from nomad_tpu.state import StateStore
from nomad_tpu.state import digest as state_digest
from nomad_tpu.state.store import AppliedPlanResults
from nomad_tpu.telemetry import global_metrics

NEW_FILES = (
    "plan_evaluate_ms", "plan_flatten_ms", "plan_store_write_ms",
    "plan_notify_ms", "allocs_read_ms", "system_settle_ms",
    "system_build_alloc_ms", "system_evict_copy_ms", "gc_pause_share",
    "gc_full_pause_ms", "bucket_copy_ms", "bucket_copies_per_apply",
    "sched_cpu_share", "system_cpu_share", "commit_cpu_share",
    "engine_cpu_share")

# facts the harness makes itself (`run_cell`), not Samples
HARNESS_FACTS = {"window.seconds": 45.0}


def _spec(name):
    with open(os.path.join(harness.HERE, "metrics", f"{name}.json")) as f:
        return json.load(f)


def _facts(spine_metrics):
    """The window's facts as `harness.snapshot` and `difference` make
    them, from the fixture's two readings of `/v1/metrics`."""
    out = dict(HARNESS_FACTS)
    for name, s in spine_metrics["later"].items():
        n0 = spine_metrics["before"].get(name, 0)
        if s["count"] > n0:
            out[f"telemetry.{name}.count"] = float(s["count"] - n0)
            out[f"telemetry.{name}.total_ms"] = s["mean"] * s["count"]
    return out


@pytest.mark.parametrize("name", NEW_FILES)
def test_every_fact_of_the_file_is_emitted_and_read(spine_metrics, name):
    spec = _spec(name)
    facts = _facts(spine_metrics)
    assert spec["reader"] == "ratio" and spec["name"] == name
    missing = [k for k in spec["num"] + spec["den"] if k not in facts]
    assert not missing, missing
    value = ratio.read(facts, spec)
    assert value is not None and value >= 0.0
    if spec["unit"] == "%" and name != "gc_pause_share":
        # a thread cannot run for longer than its span was open (the
        # clocks differ by their resolution: a little room)
        assert value <= 105.0


@pytest.mark.parametrize("name", NEW_FILES)
def test_entries_of_the_file_pass_check_benchmark(name):
    spec = _spec(name)
    bench = harness.load_benchmark()
    harness.check_benchmark(bench)
    entries = [m for m in bench["per_layer"]
               if m["name"] in (name, name + ".backlog")]
    assert entries and entries[-1]["name"] == name
    for m in entries:
        assert harness._metric_file(m["name"]).endswith(f"/{name}.json")
        assert {k: m[k] for k in ("unit", "better", "source", "layer")} \
            == {k: spec[k] for k in ("unit", "better", "source", "layer")}
        assert m["moves"] == ("allocs_per_s" if m["name"] != name
                              else "job_placed_p50_ms")


def _log(rng, n_nodes=6, n_plans=12):
    """Log entries whose plan results place, stop and evict."""
    nodes = [mock.node() for _ in range(n_nodes)]
    job, low = mock.job(), mock.job(priority=20)
    ops = [(MessageType.NODE_REGISTER, {"node": n}) for n in nodes]
    ops += [(MessageType.JOB_REGISTER, {"job": j}) for j in (job, low)]
    fillers = [mock.alloc_for(low, n.id, index=i)
               for i, n in enumerate(nodes * 40)]
    ops.append((MessageType.ALLOC_UPDATE, {"allocs": fillers}))
    placed = 0
    for p in range(n_plans):
        gone = copy.deepcopy(fillers.pop(rng.randrange(len(fillers))))
        gone.desired_status = "evict"
        new = [mock.alloc_for(job, rng.choice(nodes).id, index=placed + i)
               for i in range(rng.randrange(1, 5))]
        placed += len(new)
        gone.preempted_by_allocation = new[0].id
        res = AppliedPlanResults(allocs_to_place=new,
                                 allocs_preempted=[gone], plan_id=f"p{p}")
        # the applier's two payload shapes: one plan, a coalesced batch
        ops.append((MessageType.APPLY_PLAN_RESULTS,
                    {"results": res if p % 2 else [res]}))
    return ops


def _replay(ops, watcher=None):
    store = StateStore()
    if watcher is not None:
        store.watch(watcher)
    fsm = NomadFSM(store)
    for i, (mt, payload) in enumerate(copy.deepcopy(ops)):
        fsm.apply(i + 1, mt, payload)
        store.snapshot()        # a read point: the next write copies
    return fsm


def _count(name):
    return {s["Name"]: s["count"]
            for s in global_metrics.snapshot()["Samples"]}.get(name, 0)


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_replicas_with_spans_in_the_cone_end_byte_identical(seed):
    """`store.plan_write`, `store.plan_notify` and `store.bucket_copy`
    open under the FSM's apply.  What they measure differs between two
    replicas (one has a slow watcher); what they store does not."""
    ops = _log(random.Random(seed))
    plans = sum(mt == MessageType.APPLY_PLAN_RESULTS for mt, _ in ops)
    names = ("nomad.store.plan_write", "nomad.store.plan_notify",
             "nomad.cpu.store.plan_write", "nomad.store.bucket_copy")
    n0 = {n: _count(n) for n in names}
    a = _replay(ops)
    b = _replay(ops, watcher=lambda _table, _obj: time.sleep(0.0005))
    moved = {n: _count(n) - n0[n] for n in names}
    assert [moved[n] for n in names[:3]] == [2 * plans] * 3
    assert moved["nomad.store.bucket_copy"] >= 2 * plans
    assert a.store.stats["buckets_copied"] \
        == b.store.stats["buckets_copied"]
    assert state_digest.canon(a.snapshot()) == state_digest.canon(
        b.snapshot())
    assert state_digest.combine(state_digest.tables_digests(
        a.snapshot_tables())) == state_digest.combine(
        state_digest.tables_digests(b.snapshot_tables()))
