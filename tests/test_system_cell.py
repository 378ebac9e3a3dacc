"""The system scheduler as the cell `system-10k.fleet-rollout` measures
it: `SystemScheduler` against the plain reference of the `system-10k`
configuration (benchmark/system/reference.py, which imports nothing of
the program) on seeded random full worlds; the rack scope; the cluster's
repair (every node two fillers that may go); the refusal, by name, of a
program that cannot run the world; the spans; the comparison on worlds
the reference placed itself; and whole runs of the cell on the CPU, which
are not `correct` once the program takes the highest tier first or puts a
rack node's allocation on a node of another rack.
"""
import time
import types

import numpy as np
import pytest

from nomad_tpu import mock
from nomad_tpu.scheduler import preemption, system
from nomad_tpu.scheduler.testing import Harness
from nomad_tpu.structs import AllocDesiredStatus
from nomad_tpu.structs.alloc import AllocMetric
from nomad_tpu.telemetry import global_metrics

from benchmark import harness, traffic
from benchmark.preempt import cluster as preempt_cluster
from benchmark.system import cluster as world
from benchmark.system import jobs as shapes
from benchmark.system import reference as ref
from test_preempt_cell import _world, time_limit

CELL = "system-10k.fleet-rollout"
CFG = harness.load_config("system-10k")


def _system_job(h, cpu, mem):
    job = shapes.build({"kind": "system", "cpu": int(cpu),
                        "memory_mb": int(mem), "priority": 50,
                        "datacenters": ["dc1"]}, "agent")
    h.store.upsert_job(h.next_index(), job)
    h.process("system", mock.eval(job_id=job.id, type="system", priority=50))
    return job


# ----------------------- the scheduler against the plain reference

@pytest.mark.parametrize("seed", [3, 7, 11, 2147483659])
@pytest.mark.parametrize("evictions", [0, 1, 2])
@time_limit(120)
def test_system_scheduler_against_the_plain_reference(seed, evictions):
    """Every node once and none twice; a node that fits evicts nothing
    and reports its binpack fit; one that does not gives the set the
    reference's search picks and reports the reference's score of it,
    both parts; a node whose fillers may not go (tiers 45, 60) or do not
    suffice is left out and counted as queued."""
    n = 64 + 64 * evictions
    h, rows, cap, used, res, prio, alive = _world(seed, n)
    free = cap - used
    if evictions:
        ask = np.median(free, axis=0) + (evictions - 0.4) * np.array([390., 530.])
    else:
        ask = np.floor(free.min(axis=0) * 0.9)
    ask = np.floor(ask)
    job = _system_job(h, *ask)

    fits = (free >= ask).all(axis=1)
    met, picked = ref.search(free, ask, res, prio, alive, 50)
    assert fits.all() if not evictions else met.sum() >= n // 8
    cm = h.store.matrix
    at = {cm.node_ids[r]: i for i, r in enumerate(rows)}
    live = [a for a in h.store.allocs_by_job("default", job.id)
            if a.desired_status == AllocDesiredStatus.RUN]
    assert sorted(at[a.node_id] for a in live) == \
        sorted(np.flatnonzero(fits | met)), "nodes covered differ"
    assert h.last_scheduler.queued_allocs == {"g0": int((~(fits | met)).sum())}
    for a in live:
        i = at[a.node_id]
        gone = [h.store.alloc_by_id(x) for x in a.preempted_allocations]
        assert all(g.desired_status == AllocDesiredStatus.EVICT
                   and g.preempted_by_allocation == a.id for g in gone)
        mine = sorted((g.job.priority,
                       g.allocated_resources.tasks["web"].cpu_shares,
                       g.allocated_resources.tasks["web"].memory_mb)
                      for g in gone)
        plain = sorted((int(prio[i, k]), int(res[i, k, 0]), int(res[i, k, 1]))
                       for k in np.flatnonzero(picked[i]))
        assert mine == plain, f"node {i}: evicted sets differ"
        (meta,) = a.metrics.score_meta
        assert meta["node_id"] == a.node_id
        after = used[i] - res[i][picked[i]].sum(axis=0) + ask
        if mine:
            norm, fit, pre = ref.score(cap[i], after, prio[i][picked[i]])
            assert abs(meta["scores"]["preemption"] - pre) <= 1e-6
        else:
            norm = fit = ref.c2m.fit_score(cap[i], after)
            assert set(meta["scores"]) == {"binpack"}
        assert abs(meta["norm_score"] - norm) <= 1e-6
        assert abs(meta["scores"]["binpack"] - fit) <= 1e-6


def _installed(seed, n_nodes):
    """The configuration's own world at a small size in a scheduler
    harness (what `Cluster.install` writes into an agent's store)."""
    cl = world.Cluster(CFG, seed, n_nodes)
    h = Harness()
    cl.install(types.SimpleNamespace(server=types.SimpleNamespace(
        store=h.store, next_index=h.next_index)))
    return cl, h


@time_limit(120)
def test_a_rack_job_runs_on_its_rack_and_a_fleet_job_everywhere():
    """The warm pass's `pool` (rack r0) and then the fleet's job, on the
    cell's own world: one allocation a node in scope by one eviction a
    node, a rack node gives a second filler, and the reference's
    comparison reads both from the store's lists as sound."""
    cl, h = _installed(5, 150)
    mix = traffic.load("fleet-rollout")
    specs, stubs, full = {}, [], []
    for k, name in enumerate(("pool", "agent")):
        spec = ref.JobSpec(f"j{k}-{name}", "default", mix["shapes"][name])
        job = shapes.build(spec.shape, spec.id)
        h.store.upsert_job(h.next_index(), job)
        spec.registered = h.store.latest_index
        h.process("system", mock.eval(job_id=job.id, type="system",
                                      priority=50))
        specs[spec.id] = spec
    assert [s.allocs for s in specs.values()] == [3, 150]
    nodes = {}
    for node_id in cl.node_ids:
        nodes[node_id] = [
            {"id": a.id, "job_id": a.job_id, "name": a.name,
             "desired_status": a.desired_status,
             "create_index": a.create_index,
             "preempted_by_allocation": a.preempted_by_allocation}
            for a in h.store.allocs_by_node(node_id)]
    for spec in specs.values():
        for a in h.store.allocs_by_job("default", spec.id):
            stubs.append({"ID": a.id, "JobID": a.job_id, "NodeID": a.node_id,
                          "TaskGroup": a.task_group, "Name": a.name,
                          "EvalID": a.eval_id, "ModifyIndex": a.modify_index,
                          "DesiredStatus": a.desired_status})
            full.append({"id": a.id, "job_id": a.job_id, "name": a.name,
                         "node_id": a.node_id, "task_group": a.task_group,
                         "desired_status": a.desired_status,
                         "create_index": a.create_index,
                         "metrics": {"score_meta": a.metrics.score_meta}})
    on_rack = {cl.node_ids[r] for r in np.flatnonzero(cl.rack == 0)}
    assert {s["NodeID"] for s in stubs if s["JobID"] == "j0-pool"} == on_rack
    got = ref.compare(cl, specs, stubs, full, set(specs), {"nodes": nodes})
    assert got["correct"] and got["evictions"] == got["placements"] == 153, \
        (got["compared"], got["problems"])
    assert got["allocations_compared"] == 153


# ------------------------------------------------ the cluster's repair

@pytest.mark.parametrize("seed", list(range(18)) + [2147483659, 4294967291])
def test_every_node_keeps_two_fillers_that_may_go(seed):
    """At the cell's own size, on every seed: two fillers of tiers
    20 / 35 on every node, and nothing else moved from `preempt-10k`'s
    draw: every job its size, every node its nine fillers and `used0`."""
    cl = world.Cluster(CFG, seed)
    base = preempt_cluster.Cluster(CFG, seed)
    may_go = (cl.pre_prio <= 40).reshape(cl.n, cl.per_node).sum(axis=1)
    assert may_go.min() >= world.MIN_EVICTABLE
    short = ((base.pre_prio <= 40).reshape(cl.n, cl.per_node).sum(axis=1)
             < world.MIN_EVICTABLE).sum()
    assert 0 < short <= cl.swapped <= 2 * short < 40
    assert (cl.pre_job != base.pre_job).sum() == 2 * cl.swapped
    assert np.array_equal(np.bincount(cl.pre_job), np.bincount(base.pre_job))
    assert np.array_equal(cl.pre_prio, cl.job_prio[cl.pre_job])
    assert np.array_equal(cl.used0, base.used0)
    assert cl.node_ids == base.node_ids and cl.pre_ids == base.pre_ids


# --------------------------------------------------------- the refusal

def _no_score(monkeypatch):
    monkeypatch.setattr(AllocMetric, "populate_score_meta",
                        lambda self, entries: None)


def _no_eviction(monkeypatch):
    monkeypatch.setattr(system.SystemScheduler, "_try_preempt",
                        lambda self, *a: {})


@pytest.mark.parametrize("fault, says", [
    (_no_score, "SystemScheduler reports no score"),
    (_no_eviction, "SystemScheduler does not place a system job")],
    ids=["no_score", "no_eviction"])
@time_limit(60)
def test_a_program_that_cannot_run_the_world_is_refused_by_name(
        fault, says, monkeypatch):
    cl = world.Cluster(CFG, 5, 64)
    cl.refuse_a_program_that_cannot_run_this()
    fault(monkeypatch)
    with pytest.raises(harness.Refused, match=says):
        cl.refuse_a_program_that_cannot_run_this()


# ----------------------------------------------------------- the spans

@time_limit(120)
def test_one_diff_one_node_loop_and_one_search_however_many_nodes_ask():
    h, rows, cap, used, res, prio, alive = _world(7, 48)
    free = cap - used
    ask = np.floor(np.median(free, axis=0) + 0.6 * np.array([390., 530.]))
    names = ("system_diff", "system_place", "preempt_find", "preempt_build",
             "preempt_search")

    def counts():
        got = {s["Name"]: s["count"] for s in
               global_metrics.snapshot().get("Samples", ())}
        return [got.get(f"nomad.sched.{n}", 0) for n in names]
    before = counts()
    _system_job(h, *ask)
    asked = int((~(free >= ask).all(axis=1)).sum())
    assert 0 < asked < 48
    assert [a - b for a, b in zip(counts(), before)] == [1, 1, 1, 1, 1]


# ------------------------------- the comparison, on the reference's own

def _placed_by_the_reference(n_nodes=128):
    cl = world.Cluster(CFG, 5, n_nodes)
    shape = {"kind": "system", "cpu": 400, "memory_mb": 256, "priority": 50,
             "datacenters": ["dc1"]}
    specs = [ref.JobSpec("c0-pool", "default", dict(shape, rack="r0")),
             ref.JobSpec("c1-agent", "default", shape)]
    stubs, full, seen = ref.place_reference(cl, specs, "float32")
    gone = {a["id"] for listed in seen["nodes"].values() for a in listed
            if a["desired_status"] == "evict"}
    seen["allocs"] = [(aid, cl.job_ids[cl.pre_job[s]],
                       cl.node_ids[cl.pre_node[s]],
                       "evict" if aid in gone else "run")
                      for s, aid in enumerate(cl.pre_ids)]
    seen["allocs"] += [(a["ID"], a["JobID"], a["NodeID"], "run")
                       for a in stubs]
    # as `readback` leaves it: the rack's nodes and a few more are read
    keep = set(cl.node_ids[:6]) | {cl.node_ids[r] for r in
                                   np.flatnonzero(cl.rack == 0)}
    seen["nodes"] = {n: l for n, l in seen["nodes"].items() if n in keep}
    return cl, {s.id: s for s in specs}, stubs, full, seen


def _on_the_neighbour(cl, stubs, full, seen):
    """A fleet allocation of an unread node stands on another."""
    s = next(s for s in stubs if s["JobID"] == "c1-agent"
             and s["NodeID"] not in seen["nodes"])
    there = next(n for n in cl.node_ids
                 if n not in seen["nodes"] and n != s["NodeID"])
    seen["allocs"] = [(a, j, there if a == s["ID"] else n, st)
                      for a, j, n, st in seen["allocs"]]
    s["NodeID"] = there


def _evicted_twice(cl, stubs, full, seen):
    """A second filler gone on a node that was not read."""
    k = next(i for i, (a, _j, n, st) in enumerate(seen["allocs"])
             if n not in seen["nodes"] and st == "run" and a in cl.filler)
    a, j, n, _ = seen["allocs"][k]
    seen["allocs"][k] = (a, j, n, "evict")


def _nobody_named(cl, stubs, full, seen):
    """On a node read, the evicted filler names no placement."""
    listed = next(l for n, l in seen["nodes"].items()
                  if cl.rack[cl.index[n]] != 0)
    next(a for a in listed if a["desired_status"] == "evict")[
        "preempted_by_allocation"] = None


def _rounded_scores(cl, stubs, full, seen):
    for a in full:
        for m in a["metrics"]["score_meta"]:
            m["norm_score"] = round(m["norm_score"], 3)


@pytest.mark.parametrize("fault, number", [
    (None, None), (_on_the_neighbour, "violations"),
    (_evicted_twice, "violations"), (_nobody_named, "violations"),
    (_rounded_scores, "unexplained_jobs_share")],
    ids=["sound", "on_the_neighbour", "evicted_twice", "nobody_named",
         "rounded_scores"])
def test_the_comparison_reads_the_list_and_the_nodes(fault, number):
    cl, specs, stubs, full, seen = _placed_by_the_reference()
    if fault is not None:
        fault(cl, stubs, full, seen)
    got = ref.compare(cl, specs, stubs, full, set(specs), seen)
    values = {k: v["value"] for k, v in got["compared"].items()}
    assert got["correct"] == (fault is None), got["problems"]
    sound = {"violations": 0, "unexplained_jobs_share": 0.0}
    assert {k: v for k, v in values.items() if k != number} == \
        {k: v for k, v in sound.items() if k != number}, got["problems"]
    if number:
        assert values[number] > ref.LIMITS[number]


# ----------------------------------------- the cell, whole, on the CPU

def _highest_first(monkeypatch):
    """Of the tiers that may go, the highest goes first."""
    real = preemption.preempt_for_task_group_np

    def flipped(cand_res, cand_prio, *rest, **kw):
        return real(cand_res, -cand_prio, *rest, **kw)
    monkeypatch.setattr(preemption, "preempt_for_task_group_np", flipped)


def _node_skipped(monkeypatch):
    """The first node of a rack job's scope is skipped and its allocation
    put on the next node, which is of another rack.  (Within one scope
    the program cannot double a node: the store drops a second live
    allocation of a system job's name on a node, so a job that skips a
    node is short of its count and never seen placed: a failed job, not
    a wrong one.)"""
    real = system.SystemScheduler._settle
    done = set()

    def moved(self, plan, job, *rest):
        todo = real(self, plan, job, *rest)
        if len(job.constraints) > 1 and job.id not in done:
            done.add(job.id)
            row = todo[0][1] + 1
            todo[0] = (self.state.matrix.node_ids[row], row, None)
        return todo
    monkeypatch.setattr(system.SystemScheduler, "_settle", moved)


@pytest.mark.parametrize("fault", [None, _highest_first, _node_skipped],
                         ids=["sound", "highest_first", "node_skipped"])
@time_limit(420)
def test_cell_whole_on_the_cpu(fault, monkeypatch):
    """`system-10k.fleet-rollout` through Agent, HTTP and ApiClient at
    512 nodes: the warm pass's service and rack job, then the one fleet
    job of the window (due half-way through it)."""
    if fault is not None:
        fault(monkeypatch)
    line = harness.run_cell(CELL, 11, 23.0, False, time.monotonic(),
                            n_nodes=512, require_tpu=False)
    compared = {k: v["value"] for k, v in line["compared"].items()}
    assert line["attempted"] == 1 and line["failed"] == 0, line
    assert set(compared) == {"violations", "unexplained_jobs_share"}
    if fault is None:
        assert line["correct"], line
        assert compared == {"violations": 0, "unexplained_jobs_share": 0.0}
    else:
        assert not line["correct"], line
        assert compared["violations"] > 0
        assert compared["unexplained_jobs_share"] == 0.0, line
