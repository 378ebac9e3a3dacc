"""The system scheduler's one preemption search a task group
(`SystemScheduler._place_nodes`: one mask, one `Preemptor.find_many`, then
the node loop) against the loop it replaced, kept here as the plain twin:
a node at a time, one one-row `Preemptor.find` for each node that does not
fit.  Same allocations on the same nodes in the same order, the same
evicted ids for each, the same scores, the same failed-group metrics and
queued counts; and what one search for all nodes must not get wrong: a
second task group, a job update on a full node, preemption switched off,
a sysbatch job.
"""
import time

import numpy as np
import pytest

from nomad_tpu import mock
from nomad_tpu.encode.matrixizer import comparable_vec
from nomad_tpu.scheduler import system
from nomad_tpu.scheduler.preemption import Preemptor
from nomad_tpu.scheduler.reconcile import tasks_updated
from nomad_tpu.scheduler.testing import Harness
from nomad_tpu.structs import AllocClientStatus
from nomad_tpu.structs.alloc import alloc_name
from nomad_tpu.structs.config import PreemptionConfig, SchedulerConfiguration
from nomad_tpu.telemetry import global_metrics

from test_preempt_cell import _filler, _world, time_limit

SPANS = ("preempt_find", "preempt_build", "preempt_search")


class _PerNode:
    """The node loop as it was: the mask, the search and the placement of
    one node before the next node is looked at."""

    def _place_nodes(self, plan, job, groups, live, terminal_newest, used):
        cm = self.state.matrix
        ports = system.PortClaims(cm)
        fits = np.zeros(cm.n_rows, bool)
        for gi, tg in enumerate(job.task_groups):
            name = alloc_name(job.id, tg.name, 0)
            d = groups[gi].demand
            for node_id, row in cm.row_of.items():
                if not groups[gi].feasible[row]:
                    continue
                key = (node_id, name)
                cur = live.get(key)
                if cur is not None:
                    if cur.job is None or cur.job.version == job.version:
                        continue
                    old_tg = cur.job.lookup_task_group(tg.name)
                    if old_tg is not None and not tasks_updated(old_tg, tg):
                        plan.append_alloc(cur.copy(), job)
                        continue
                    plan.append_stopped_alloc(
                        cur, "alloc not needed due to job update")
                    used[row] -= comparable_vec(cur.comparable_resources())
                elif self.sysbatch:
                    t = terminal_newest.get(key)
                    if t is not None and t.ran_successfully():
                        continue
                elif key in terminal_newest and terminal_newest[key] \
                        .client_status == AllocClientStatus.COMPLETE:
                    continue
                fits[row] = np.all(used[row] + d <= cm.capacity[row])
                found = {} if fits[row] else self._one_row(job, row, d, used)
                self._try_place(plan, job, tg, name, node_id, row, used, d,
                                ports, time.time(), fits, found)

    def _one_row(self, job, row, d, used):
        if not self.state.scheduler_config.preemption_enabled(
                "sysbatch" if self.sysbatch else "system"):
            return {}
        if self._preemptor is None:
            self._preemptor = Preemptor(self.state, job.priority)
        alone = np.zeros(self.state.matrix.n_rows, bool)
        alone[row] = True
        found = self._preemptor.find(alone, d, used)
        if found is None:
            return {}
        self._preemptor.invalidate({a.id for a in found.evicted})
        return {row: found}


class _SystemPerNode(_PerNode, system.SystemScheduler):
    pass


class _SysBatchPerNode(_PerNode, system.SysBatchScheduler):
    pass


def _plan_of(h, cls, job):
    """The plan `cls` makes of `job` on the store as it stands, read and
    not applied.  -> (what the plan says, the scheduler)"""
    h.plans.clear()
    h.reject_plan = True
    ev = mock.eval(job_id=job.id, type=job.type, priority=job.priority)
    sched = cls(h.store.snapshot(), h)
    sched.process(ev)
    h.reject_plan = False
    plans = list(h.plans)
    h.plans.clear()
    if not plans:
        return {"placed": [], "evicted": [], "stopped": [], "failed": {},
                "queued": ev.queued_allocations}, sched
    (plan,) = plans
    placed, at = [], {}
    for node_id, allocs in plan.node_allocation.items():
        for a in allocs:
            at[a.id] = (node_id, a.name)
            placed.append((node_id, a.name, a.task_group, a.job.version,
                           list(a.preempted_allocations),
                           a.metrics.score_meta))
    evicted = [(node_id, [(a.id, at[a.preempted_by_allocation],
                           a.desired_status) for a in gone])
               for node_id, gone in plan.node_preemptions.items()]
    stopped = [(node_id, [(a.id, a.desired_description) for a in gone])
               for node_id, gone in plan.node_update.items()]
    failed = {tg: (m.nodes_exhausted, dict(m.dimension_exhausted))
              for tg, m in sched.failed_tg_allocs.items()}
    return {"placed": placed, "evicted": evicted, "stopped": stopped,
            "failed": failed, "queued": ev.queued_allocations}, sched


def _same_plan(got, want):
    """Everything equal; a score may differ by the last place it is
    rounded to (a batched np.power against a one-row one)."""
    assert [p[:5] for p in got["placed"]] == [p[:5] for p in want["placed"]]
    for mine, theirs in zip(got["placed"], want["placed"]):
        (a,), (b,) = mine[5], theirs[5]
        assert a["node_id"] == b["node_id"] == mine[0]
        assert set(a["scores"]) == set(b["scores"])
        assert a["norm_score"] == pytest.approx(b["norm_score"], abs=1.5e-6)
        for k in a["scores"]:
            assert a["scores"][k] == pytest.approx(b["scores"][k],
                                                   abs=1.5e-6)
    for key in ("evicted", "stopped", "failed", "queued"):
        assert got[key] == want[key], key


def _span_counts():
    got = {s["Name"]: s["count"] for s in
           global_metrics.snapshot().get("Samples", ())}
    return [got.get(f"nomad.sched.{n}", 0) for n in SPANS]


def _ask_for(free, evictions):
    if evictions:
        return np.floor(np.median(free, axis=0)
                        + (evictions - 0.4) * np.array([390., 530.]))
    return np.floor(free.min(axis=0) * 0.9)


def _job(h, kind, groups, priority=50):
    """A system or sysbatch job of one task group for each (cpu, mem)."""
    job = (mock.system_job if kind == "system" else mock.sysbatch_job)()
    job.priority = priority
    first = job.task_groups[0]
    job.task_groups = []
    for k, (cpu, mem) in enumerate(groups):
        tg = first.copy()
        tg.name = f"g{k}"
        tg.tasks[0].resources.cpu = int(cpu)
        tg.tasks[0].resources.memory_mb = int(mem)
        tg.ephemeral_disk.size_mb = 0
        job.task_groups.append(tg)
    h.store.upsert_job(h.next_index(), job)
    return job


# ------------------------------------------ the twin, on seeded worlds

@pytest.mark.parametrize("seed", [3, 7, 11, 2147483659])
@pytest.mark.parametrize("evictions", [0, 1, 2])
@time_limit(120)
def test_one_search_makes_the_plan_of_the_per_node_loop(seed, evictions):
    """Nodes that fit, nodes that give one or two fillers, and nodes
    whose fillers may not go (tiers 45, 60) or do not suffice, mixed."""
    n = 64 + 32 * evictions
    h, rows, cap, used, res, prio, alive = _world(seed, n)
    job = _job(h, "system", [_ask_for(cap - used, evictions)])
    want, _ = _plan_of(h, _SystemPerNode, job)
    before = _span_counts()
    got, _ = _plan_of(h, system.SystemScheduler, job)
    searched = [a - b for a, b in zip(_span_counts(), before)]
    _same_plan(got, want)
    assert len(got["placed"]) + got["queued"]["g0"] == n
    if evictions:
        assert searched == [1, 1, 1]
        assert len(got["evicted"]) >= n // 8 and got["queued"]["g0"] >= 2
        assert max(len(p[4]) for p in got["placed"]) >= evictions
        assert got["failed"]["g0"][0] == got["queued"]["g0"]
    else:
        assert searched == [0, 0, 0]
        assert not got["evicted"] and not got["failed"]


# ------------------------------- what one search for all must keep right

def _two_filler_world(n_nodes, sizes=((1400, 600), (1500, 700))):
    """Nodes of 4,000 MHz that hold two fillers of tier 20 each."""
    h = Harness()
    low = mock.job(priority=20)
    h.store.upsert_job(h.next_index(), low)
    nodes, fillers = [], []
    for _ in range(n_nodes):
        node = mock.node()
        h.store.upsert_node(h.next_index(), node)
        nodes.append(node)
        for cpu, mem in sizes:
            fillers.append(_filler(low, node.id, len(fillers), cpu, mem))
    h.store.upsert_allocs(h.next_index(), fillers)
    return h, nodes, fillers


@time_limit(120)
def test_a_second_group_searches_against_what_the_first_left():
    """Two groups, two evictable fillers a node: the first takes the
    filler nearest its ask, the second takes the other and has room only
    because the first's eviction and placement are in `used`; nothing is
    evicted twice; one search a group, one build an eval."""
    h, nodes, fillers = _two_filler_world(12)
    cm = h.store.matrix
    free = (cm.capacity - cm.used)[cm.row_of[nodes[0].id], 0]
    # the first is 100 short, frees 1,400 and leaves 1,300; the second needs
    # those and the 1,500, 50 more than a stale `used` would let it have,
    # and 50 less than the 1,400 would give it were they still to be had
    first, second = free + 100, 1300 + 1350
    job = _job(h, "system", [(first, 64), (second, 64)])
    want, _ = _plan_of(h, _SystemPerNode, job)
    before = _span_counts()
    got, _ = _plan_of(h, system.SystemScheduler, job)
    assert [a - b for a, b in zip(_span_counts(), before)] == [2, 1, 2]
    _same_plan(got, want)
    assert got["queued"] == {"g0": 0, "g1": 0} and not got["failed"]
    assert [(p[0], p[2]) for p in got["placed"]] == \
        [(nd.id, g) for nd in nodes for g in ("g0", "g1")]
    gone = [aid for _node, listed in got["evicted"] for aid, _by, _st in listed]
    assert sorted(gone) == sorted(a.id for a in fillers)
    by_id = {a.id: a for a in fillers}
    for node_id, listed in got["evicted"]:
        assert [(by_id[aid].allocated_resources.tasks["web"].cpu_shares,
                 by[1]) for aid, by, _st in listed] == \
            [(1400, alloc_name(job.id, "g0", 0)),
             (1500, alloc_name(job.id, "g1", 0))]


@time_limit(120)
def test_an_update_frees_its_room_before_the_mask():
    """A full node whose only room is what the job's old allocation
    holds: the update stops it and places the new one there, with no
    search (nothing else on the node may go)."""
    h = Harness()
    nodes = [mock.node() for _ in range(6)]
    for node in nodes:
        h.store.upsert_node(h.next_index(), node)
    job = _job(h, "system", [(1000, 256)])
    h.process("system", mock.eval(job_id=job.id, type="system"))
    old = {a.node_id: a for a in h.store.allocs_by_job("default", job.id)}
    assert len(old) == 6
    cm = h.store.matrix
    peer = mock.job(priority=50)      # no lower than the job: it stays
    h.store.upsert_job(h.next_index(), peer)
    h.store.upsert_allocs(h.next_index(), [
        _filler(peer, node.id, i,
                (cm.capacity - cm.used)[cm.row_of[node.id], 0] - 100, 256)
        for i, node in enumerate(nodes[:4])])
    assert ((cm.capacity - cm.used)[[cm.row_of[n.id] for n in nodes[:4]], 0]
            == 100).all()

    new = job.copy()
    new.task_groups[0].tasks[0].config = {"command": "/bin/true"}
    h.store.upsert_job(h.next_index(), new)
    assert new.version == 1
    want, _ = _plan_of(h, _SystemPerNode, new)
    before = _span_counts()
    got, sched = _plan_of(h, system.SystemScheduler, new)
    assert [a - b for a, b in zip(_span_counts(), before)] == [0, 0, 0]
    assert sched._preemptor is None
    _same_plan(got, want)
    assert [(p[0], p[3]) for p in got["placed"]] == [(n.id, 1) for n in nodes]
    assert got["stopped"] == [
        (n.id, [(old[n.id].id, "alloc not needed due to job update")])
        for n in nodes]
    assert not got["evicted"] and got["queued"] == {"g0": 0}


@time_limit(120)
def test_with_system_preemption_off_every_short_node_is_queued():
    h, rows, cap, used, res, prio, alive = _world(7, 48)
    h.store.set_scheduler_config(h.next_index(), SchedulerConfiguration(
        preemption_config=PreemptionConfig(system_scheduler_enabled=False)))
    free = cap - used
    ask = _ask_for(free, 1)
    short = int((~(free >= ask).all(axis=1)).sum())
    assert 0 < short < 48
    job = _job(h, "system", [ask])
    want, _ = _plan_of(h, _SystemPerNode, job)
    before = _span_counts()
    got, sched = _plan_of(h, system.SystemScheduler, job)
    assert [a - b for a, b in zip(_span_counts(), before)] == [0, 0, 0]
    assert sched._preemptor is None
    _same_plan(got, want)
    assert len(got["placed"]) == 48 - short and not got["evicted"]
    assert got["queued"] == {"g0": short}
    assert got["failed"]["g0"] == (short, {"resources": short})


@pytest.mark.parametrize("preempts", [False, True],
                         ids=["default_off", "switched_on"])
@time_limit(120)
def test_a_sysbatch_job_takes_the_same_path(preempts):
    """`SysBatchScheduler` is the same class with one flag: its own
    switch decides whether it evicts, and a node it ran on is left out of
    the mask, the search and the loop."""
    h, rows, cap, used, res, prio, alive = _world(11, 48)
    if preempts:
        h.store.set_scheduler_config(h.next_index(), SchedulerConfiguration(
            preemption_config=PreemptionConfig(
                sysbatch_scheduler_enabled=True)))
    job = _job(h, "sysbatch", [_ask_for(cap - used, 1)])
    cm = h.store.matrix
    ran = [cm.node_ids[r] for r in rows[:5]]
    done = []
    for node_id in ran:
        a = mock.alloc_for(job, node_id, index=0)
        a.name = alloc_name(job.id, "g0", 0)
        a.client_status = AllocClientStatus.COMPLETE
        a.allocated_resources.tasks["web"].cpu_shares = 1
        a.allocated_resources.tasks["web"].memory_mb = 1
        done.append(a)
    h.store.upsert_allocs(h.next_index(), done)
    want, _ = _plan_of(h, _SysBatchPerNode, job)
    before = _span_counts()
    got, sched = _plan_of(h, system.SysBatchScheduler, job)
    searched = [a - b for a, b in zip(_span_counts(), before)]
    _same_plan(got, want)
    assert not {p[0] for p in got["placed"]} & set(ran)
    assert len(got["placed"]) + got["queued"]["g0"] == 48 - len(ran)
    if preempts:
        assert searched == [1, 1, 1] and got["evicted"]
        assert not {n for n, _ in got["evicted"]} & set(ran)
    else:
        assert searched == [0, 0, 0] and not got["evicted"]
        assert sched._preemptor is None and got["queued"]["g0"] > 0


# ----------------- the plan's own copies: shared parts, the same plan

def _whole_plan(h, job, monkeypatch, deep):
    """The `Plan` object the system scheduler makes of `job`, not
    applied, with the ids it gives out counted from zero and, for
    `deep`, the plan's copies made as they were until PR 45: a deep copy
    of the record, its job and its metrics, the job then dropped."""
    import itertools

    from nomad_tpu.scheduler import placement
    from nomad_tpu.structs.alloc import Allocation

    ids = itertools.count()
    with monkeypatch.context() as m:
        m.setattr(placement, "generate_uuid",
                  lambda: f"00000000-0000-4000-8000-{next(ids):012x}")
        if deep:
            m.setattr(Allocation, "copy_shallow", Allocation.copy)
        h.plans.clear()
        h.reject_plan = True
        sched = system.SystemScheduler(h.store.snapshot(), h)
        sched.process(mock.eval(id="eval-of-both", job_id=job.id,
                                type=job.type, priority=job.priority))
        h.reject_plan = False
    (plan,) = h.plans
    h.plans.clear()
    for allocs in plan.node_allocation.values():
        for a in allocs:            # the one thing the clock decides
            a.create_time = a.modify_time = 0.0
    return plan


def _fleet_that_evicts(seed):
    h, rows, cap, used, res, prio, alive = _world(seed, 96)
    return h, _job(h, "system", [_ask_for(cap - used, 1)])


def _update_and_a_lost_node(seed):
    """A system job on 8 nodes; then one node goes down and the job's
    task changes: one `lost`, seven stopped for the update."""
    from nomad_tpu.structs.node import NodeStatus
    h = Harness()
    nodes = [mock.node() for _ in range(8)]
    for node in nodes:
        h.store.upsert_node(h.next_index(), node)
    job = _job(h, "system", [(1000, 256)])
    h.process("system", mock.eval(job_id=job.id, type="system"))
    assert len(h.store.allocs_by_job("default", job.id)) == 8
    h.store.update_node_status(h.next_index(), nodes[seed % 8].id,
                               NodeStatus.DOWN)
    new = job.copy()
    new.task_groups[0].tasks[0].config = {"command": "/bin/true"}
    h.store.upsert_job(h.next_index(), new)
    return h, new


@pytest.mark.parametrize("seed", [3, 2147483659])
@pytest.mark.parametrize("world", [_fleet_that_evicts,
                                   _update_and_a_lost_node],
                         ids=lambda w: w.__name__[1:])
@time_limit(120)
def test_shared_parts_change_no_plan(world, seed, monkeypatch):
    """The fleet eval at the tests' scale, its ids counted from zero,
    makes the plan it made when every stopped or evicted allocation was
    deep-copied: the same placements under the same ids, the same
    `node_preemptions` and `node_update` value for value, the same
    `score_meta`.  And it leaves the records it read as they were."""
    import copy
    h, job = world(seed)
    records = {a.id: a for a in h.store.allocs()}
    before = copy.deepcopy(records)
    want = _whole_plan(h, job, monkeypatch, deep=True)
    got = _whole_plan(h, job, monkeypatch, deep=False)
    assert got.node_allocation == want.node_allocation
    assert got.node_preemptions == want.node_preemptions
    assert got.node_update == want.node_update
    assert [a.id for v in got.node_allocation.values() for a in v] == \
        [a.id for v in want.node_allocation.values() for a in v]
    assert [a.metrics.score_meta
            for v in got.node_allocation.values() for a in v] == \
        [a.metrics.score_meta
         for v in want.node_allocation.values() for a in v]
    gone = [a for v in list(got.node_preemptions.values())
            + list(got.node_update.values()) for a in v]
    if world is _fleet_that_evicts:
        assert len(got.node_preemptions) >= 12 and not got.node_update
    else:
        assert sorted(a.client_status == AllocClientStatus.LOST
                      for a in gone) == [False] * 7 + [True]
    for a in gone:
        assert a is not records[a.id] and a.job is None
        assert a.metrics is records[a.id].metrics
    assert {a.id: a for a in h.store.allocs()} == before
    assert all(a is records[a.id] for a in h.store.allocs())
