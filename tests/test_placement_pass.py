"""The placement pass of a service or batch eval, step by step
(scheduler/generic.py PlacementPass): which kernel a compiled group gets,
that no engine overlay ticket outlives its eval however the plan ends,
and that each round of a one-by-one eval reports its own kernel pass.
"""
import numpy as np
import pytest

from nomad_tpu import mock
from nomad_tpu.parallel.engine import get_engine
from nomad_tpu.scheduler import generic
from nomad_tpu.scheduler.generic import PlacementPass
from nomad_tpu.scheduler.placement import allocs_leave
from nomad_tpu.scheduler.reconcile import PlacementRequest
from nomad_tpu.scheduler.testing import Harness
from nomad_tpu.structs.config import PreemptionConfig, SchedulerConfiguration
from nomad_tpu.structs.job import Affinity, Constraint, Operand, Spread
from nomad_tpu.structs.plan import Plan
from nomad_tpu.structs.resources import (
    DeviceRequest, NetworkPort, NetworkResource, NodeDevice)


def _world(h, n_nodes=4, **node_overrides):
    nodes = [mock.node(**node_overrides) for _ in range(n_nodes)]
    for i, n in enumerate(nodes):
        n.attributes["rack"] = f"r{i % 2}"
        h.store.upsert_node(h.next_index(), n)
    return nodes


def _job(count, cpu=500, priority=50, **overrides):
    job = mock.job(priority=priority, **overrides)
    job.task_groups[0].count = count
    job.task_groups[0].tasks[0].resources.cpu = cpu
    return job


def _ports(tg, static=(), dynamic=0):
    tg.networks = [NetworkResource(
        reserved_ports=[NetworkPort(label=f"s{p}", value=p) for p in static],
        dynamic_ports=[NetworkPort(label=f"d{i}") for i in range(dynamic)])]


def _gpus(model, n, memory):
    return NodeDevice(vendor="nvidia", type="gpu", name=model,
                      instance_ids=[f"{model}-{k}" for k in range(n)],
                      attributes={"memory": memory})


def _two_card_node(h):
    """One node, two device groups an ask scores differently: a job that
    prefers the larger card goes one slot a kernel pass."""
    node = mock.node()
    node.node_resources.devices = [_gpus("t4", 2, "16 GiB"),
                                   _gpus("a100", 2, "80 GiB")]
    h.store.upsert_node(h.next_index(), node)
    return node


def _prefers_large(job):
    job.task_groups[0].tasks[0].resources.devices = [DeviceRequest(
        name="nvidia/gpu", count=1, affinities=[Affinity(
            "${device.attr.memory}", "64 GiB", Operand.GTE, weight=75)])]
    return job


def _pass_for(h, job):
    """A pass as `_attempt` builds one, before any step has run."""
    sched = generic.ServiceScheduler(h.store.snapshot(), h)
    sched.eval = mock.eval(job_id=job.id)
    sched.job = job
    sched.plan = sched.eval.make_plan(job)
    return PlacementPass(sched)


def _process(h, job):
    h.store.upsert_job(h.next_index(), job)
    ev = mock.eval(job_id=job.id, type=job.type, priority=job.priority)
    h.store.upsert_evals(h.next_index(), [ev])
    h.process(job.type, ev)
    return [a for a in h.store.allocs_by_job(job.namespace, job.id)
            if not a.terminal_status()]


# ----------------------------------------------- the kernel a group gets

def _spread(job):
    job.task_groups[0].spreads = [Spread("${attr.rack}", 100, ())]


def _distinct_hosts_job(job):
    job.constraints.append(Constraint("", "", Operand.DISTINCT_HOSTS))


def _distinct_hosts_group(job):
    job.task_groups[0].constraints.append(
        Constraint("", "", Operand.DISTINCT_HOSTS))


def _distinct_property(job):
    job.constraints.append(
        Constraint("${attr.rack}", "1", Operand.DISTINCT_PROPERTY))


KERNELS = [
    ("plain", lambda job: None, 2, "bulk"),
    ("lone slot", lambda job: None, 1, "scan"),
    ("spread", _spread, 2, "scan"),
    ("distinct_hosts of the job", _distinct_hosts_job, 2, "scan"),
    ("distinct_hosts of the group", _distinct_hosts_group, 2, "scan"),
    ("distinct_property", _distinct_property, 2, "scan"),
    ("static port", lambda job: _ports(job.task_groups[0], (8080,)), 2,
     "scan"),
    ("dynamic port", lambda job: _ports(job.task_groups[0], dynamic=1), 2,
     "scan"),
    ("device ask", _prefers_large, 2, "scan"),
]


@pytest.mark.parametrize("change,n_slots,want",
                         [k[1:] for k in KERNELS], ids=[k[0] for k in KERNELS])
def test_the_kernel_a_group_gets(change, n_slots, want):
    h = Harness()
    _world(h)
    job = _job(n_slots)
    change(job)
    placing = _pass_for(h, job)
    tg = job.task_groups[0]
    placing.groups = [placing.stack.compile_group(job, tg)]
    slots = [PlacementRequest(tg.name, f"{job.id}.{tg.name}[{i}]")
             for i in range(n_slots)]

    bulk, scan = placing.split(slots)

    assert placing.groups[0].uncoupled == (want == "bulk" or n_slots == 1)
    if want == "bulk":
        assert (bulk, scan) == ([(0, slots)], [])
    else:
        assert (bulk, scan) == ([], slots)


# ------------------------------------------- no ticket outlives its eval

def _scan_eval(h):
    _world(h)
    job = _job(3)
    _spread(job)
    return job, 1


def _bulk_eval(h):
    _world(h)
    return _job(4), 1


def _two_round_device_eval(h):
    _two_card_node(h)
    # two scan passes and the grants' ticket
    return _prefers_large(_job(2, cpu=1000)), 3


def _preempting_eval(h):
    h.store.set_scheduler_config(h.next_index(), SchedulerConfiguration(
        preemption_config=PreemptionConfig(service_scheduler_enabled=True)))
    _world(h, n_nodes=1)
    assert len(_process(h, _job(1, cpu=2500, priority=20))) == 1
    # one slot fits beside the low-priority allocation, the other evicts it
    return _job(2, cpu=1400, priority=70), 1


def _port_eval(h):
    _world(h)
    job = _job(2)
    _ports(job.task_groups[0], dynamic=2)
    return job, 1


def _committed(h, monkeypatch):
    pass


def _no_op(h, monkeypatch):
    # the pass has run and holds its tickets when the scheduler finds
    # nothing to submit
    monkeypatch.setattr(Plan, "is_no_op", lambda self: True)


def _submit_raises(h, monkeypatch):
    def refuse(plan):
        raise RuntimeError("the leader is gone")
    monkeypatch.setattr(h, "submit_plan", refuse)


@pytest.mark.parametrize("ending", [_committed, _no_op, _submit_raises])
@pytest.mark.parametrize("world", [_scan_eval, _bulk_eval,
                                   _two_round_device_eval, _preempting_eval,
                                   _port_eval])
def test_no_ticket_outlives_its_eval(world, ending, monkeypatch):
    h = Harness()
    job, least = world(h)
    eng = get_engine()
    held = []
    real_close = PlacementPass.close

    def close(self, handed_over):
        held.append(list(self.tickets))
        assert not handed_over
        real_close(self, handed_over)

    monkeypatch.setattr(PlacementPass, "close", close)
    ending(h, monkeypatch)
    before = (eng.stats["tickets_open"], len(eng._dev_tickets))

    if ending is _submit_raises:
        with pytest.raises(RuntimeError, match="leader is gone"):
            _process(h, job)
    else:
        placed = _process(h, job)
        assert len(placed) == (job.task_groups[0].count
                               if ending is _committed else 0)

    assert len(held) == 1 and len(held[0]) >= least, held
    assert None not in held[0] and len(set(held[0])) == len(held[0])
    assert (eng.stats["tickets_open"], len(eng._dev_tickets)) == before
    assert not set(held[0]) & (set(eng._tickets) | set(eng._dev_tickets))


class _CountingEngine:
    def __init__(self):
        self.completed = []

    def complete_many(self, tickets):
        self.completed.extend(tickets)


@pytest.mark.parametrize("handed_over", [False, True])
def test_a_commit_in_flight_leaves_the_tickets_to_the_applier(handed_over):
    h = Harness()
    _world(h, n_nodes=1)
    job = _job(1)
    placing = _pass_for(h, job)
    placing.eng = _CountingEngine()
    placing.tickets = [7, 8]

    placing.close(handed_over=handed_over)

    assert placing.eng.completed == ([] if handed_over else [7, 8])
    assert placing.tickets == []


# --------------------------------- a round reports its own kernel pass

def test_each_round_reports_the_pass_that_placed_it(monkeypatch):
    """Two slots on one node, one kernel pass each: the second pass sees
    the first's allocation, scores the node's fit differently, and each
    allocation carries the figures of the pass that chose its row."""
    h = Harness()
    node = _two_card_node(h)
    job = _prefers_large(_job(2, cpu=1000))
    eng = get_engine()
    passes = []
    real_place = eng.place

    def place(*args, **kwargs):
        result, ticket = real_place(*args, **kwargs)
        passes.append(result)
        return result, ticket

    monkeypatch.setattr(eng, "place", place)
    assert len(_process(h, job)) == 2

    assert len(passes) == 2
    fits = [round(float(r.fit_score[0]), 6) for r in passes]
    assert fits[0] != fits[1], "the rounds cannot be told apart"
    row = h.store.matrix.row_of[node.id]
    placed = h.plans[-1].node_allocation[node.id]     # in placement order
    for alloc, result, fit in zip(placed, passes, fits):
        assert int(result.node[0]) == row
        (meta,) = [m for m in alloc.metrics.score_meta
                   if m["node_id"] == node.id]
        assert meta["scores"]["binpack"] == fit
        (k,) = np.flatnonzero(result.top_nodes[0] == row)
        assert meta["norm_score"] == round(float(result.top_scores[0, k]), 6)
        assert alloc.metrics.nodes_evaluated == int(result.nodes_evaluated[0])


# ------------------------------------------- an allocation leaves its node

class _Invalidated:
    def __init__(self):
        self.ids = set()

    def invalidate(self, ids):
        self.ids |= ids


@pytest.mark.parametrize("keeps", ["usage", "ports", "candidates", "deltas"])
def test_an_allocation_that_leaves_gives_its_room_back(keeps):
    job = _job(1)
    _ports(job.task_groups[0], (8080,))
    a, b = (mock.alloc_for(job, "n", i) for i in range(2))
    a.allocated_resources.shared_ports = [NetworkPort(label="s", value=8080)]
    used = np.full((3, 4), 1000.0, np.float32)
    freed = {} if keeps != "usage" else None
    search = _Invalidated() if keeps == "candidates" else None
    deltas = [] if keeps == "deltas" else None

    allocs_leave(used, 1, [a, b], freed, search, deltas)

    res = job.task_groups[0].tasks[0].resources
    assert used[1].tolist() == [1000.0 - 2 * res.cpu,
                                1000.0 - 2 * res.memory_mb,
                                1000.0 - 2 * a.comparable_resources().disk_mb,
                                1000.0]
    assert (used[0] == 1000.0).all() and (used[2] == 1000.0).all()
    assert freed == (None if keeps == "usage" else {1: set(a.ports())})
    assert search is None or search.ids == {a.id, b.id}
    # what the engine re-applies to its dispatch-time basis is what left
    assert deltas is None or (
        [r for r, _ in deltas] == [1, 1]
        and (1000.0 + sum(v for _, v in deltas) == used[1]).all())
