"""`distinct_hosts` and `distinct_property` (the `constraint` block's
operators: feasible.go DistinctHostsIterator and DistinctPropertyIterator,
propertyset.go) hold between the slots of one eval: the scan step's carry
knows which rows took an allocation of the scope and how many sit on each
value of a property, started from the job's existing allocations.  The
single, the batched and the mesh form of the step agree, the host honours
the constraints wherever it picks a row itself, and the plain reference of
the `distinct-10k` configuration (benchmark/distinct/reference.py, which
imports nothing of the program) agrees with whole runs of the cell
`distinct-10k.ha-services` on the CPU.
"""
import dataclasses
import functools
import signal
import subprocess
import sys
import time
from concurrent.futures import Future

import numpy as np
import pytest

from nomad_tpu import mock
from nomad_tpu.scheduler.testing import Harness
from nomad_tpu.structs import Task, TaskGroup
from nomad_tpu.structs.job import Constraint, Operand
from nomad_tpu.structs.resources import Resources

RACKS = (5, 3, 2, 2)          # nodes a rack: 12 nodes, uneven


def time_limit(seconds: int):
    """The test fails, and does not hang, after `seconds`."""
    def wrap(fn):
        @functools.wraps(fn)
        def run(*a, **kw):
            def late(_sig, _frame):
                raise TimeoutError(f"{fn.__name__}: over {seconds} s")
            old = signal.signal(signal.SIGALRM, late)
            signal.alarm(seconds)
            try:
                return fn(*a, **kw)
            finally:
                signal.alarm(0)
                signal.signal(signal.SIGALRM, old)
        return run
    return wrap


def _world(h, racks=RACKS, seed=5, **node_overrides):
    """Nodes of uneven size and uneven load, so that bin-packing prefers
    some and stacks on them wherever nothing forbids it."""
    rng = np.random.default_rng(seed)
    nodes = []
    for r, size in enumerate(racks):
        for _ in range(size):
            n = mock.node(**node_overrides)
            n.attributes["rack"] = f"r{r}"
            n.node_resources.cpu.cpu_shares = int(rng.choice([8000, 16000]))
            n.node_resources.memory_mb = int(rng.choice([16384, 32768]))
            n.reserved_resources.cpu_shares = int(rng.integers(0, 3000))
            nodes.append(n)
            h.store.upsert_node(h.next_index(), n)
    return nodes


def _job(count, groups=("web",)):
    job = mock.job()
    job.task_groups = [TaskGroup(
        name=name, count=count,
        tasks=[Task(name=name, driver="exec",
                    resources=Resources(cpu=200, memory_mb=128))])
        for name in groups]
    for tg in job.task_groups:
        tg.ephemeral_disk.size_mb = 0
    return job


def _hosts(where):
    where.constraints.append(Constraint("", "", Operand.DISTINCT_HOSTS))


def _prop(where, limit, attr="${attr.rack}"):
    where.constraints.append(
        Constraint(attr, limit, Operand.DISTINCT_PROPERTY))


def _process(h, job):
    h.store.upsert_job(h.next_index(), job)
    ev = mock.eval(job_id=job.id, type=job.type, priority=job.priority)
    h.store.upsert_evals(h.next_index(), [ev])
    h.process(job.type, ev)
    return [a for a in h.store.allocs_by_job(job.namespace, job.id)
            if not a.terminal_status()]


def _per_host(allocs):
    out: dict = {}
    for a in allocs:
        out[a.node_id] = out.get(a.node_id, 0) + 1
    return out


def _per_rack(h, allocs):
    out: dict = {}
    for a in allocs:
        rack = h.store.node_by_id(a.node_id).attributes["rack"]
        out[rack] = out.get(rack, 0) + 1
    return out


# (what the job asks, the most the 12 nodes allow, one a host, most a
# rack, the names a slot over that most is filtered by)
ASKS = {
    "job-level distinct_hosts":
        (lambda job: _hosts(job), 12, True, None, {"distinct_hosts"}),
    "group-level distinct_hosts":
        (lambda job: _hosts(job.task_groups[0]), 12, True, None,
         {"distinct_hosts"}),
    "distinct_property limit 1":
        (lambda job: _prop(job, "1"), 4, False, 1, {"distinct_property"}),
    "distinct_property limit left out":
        (lambda job: _prop(job.task_groups[0], ""), 4, False, 1,
         {"distinct_property"}),
    "distinct_property limit 3":
        (lambda job: _prop(job.task_groups[0], "3"), 12, False, 3,
         {"distinct_property"}),
    "both at once":
        (lambda job: (_hosts(job.task_groups[0]),
                      _prop(job.task_groups[0], "2")), 8, True, 2,
         {"distinct_hosts", "distinct_property"}),
}


@pytest.mark.parametrize("fill", ["under", "at", "over"])
@pytest.mark.parametrize("ask", list(ASKS))
@time_limit(120)
def test_the_constraint_holds_between_the_slots_of_one_eval(ask, fill):
    change, most, one_a_host, most_a_rack, names = ASKS[ask]
    h = Harness()
    _world(h)
    count = {"under": most - 2, "at": most, "over": most + 4}[fill]
    job = _job(count)
    change(job)

    allocs = _process(h, job)

    assert len(h.plans) == 1            # one eval, one kernel pass, one plan
    assert not h.results[0].rejected_nodes
    assert len(allocs) == min(count, most)
    if one_a_host:
        assert max(_per_host(allocs).values()) == 1
    if most_a_rack:
        assert max(_per_rack(h, allocs).values()) <= most_a_rack
    blocked = [e for e in h.create_evals_list if e.status == "blocked"]
    if fill != "over":
        assert not blocked and not h.last_scheduler.failed_tg_allocs
        return
    assert len(blocked) == 1
    failed = h.last_scheduler.failed_tg_allocs["web"]
    assert failed.coalesced_failures == count - most - 1
    assert set(failed.constraint_filtered) == names
    # every node is closed to the slot by one of them, and by nothing else
    assert sum(failed.constraint_filtered.values()) == 12
    assert failed.nodes_filtered == 12 and failed.nodes_exhausted == 0
    assert h.last_scheduler.queued_allocs["web"] == count - most


@time_limit(120)
def test_a_node_without_the_attribute_takes_none():
    h = Harness()
    nodes = _world(h, racks=(2, 2))
    bare = mock.node()
    h.store.upsert_node(h.next_index(), bare)
    job = _job(6)
    _prop(job, "2")
    allocs = _process(h, job)
    assert len(allocs) == 4
    assert bare.id not in {a.node_id for a in allocs}
    assert {a.node_id for a in allocs} <= {n.id for n in nodes}
    # an attribute no node has: every node lacks it
    job = _job(2)
    _prop(job, "2", "${meta.zone}")
    assert _process(h, job) == []
    failed = h.last_scheduler.failed_tg_allocs["web"]
    assert failed.constraint_filtered == {"distinct_property": 5}


@pytest.mark.parametrize("level", ["job", "group"])
@time_limit(120)
def test_job_level_scope_excludes_across_groups_group_level_does_not(level):
    h = Harness()
    _world(h, racks=(3, 2))             # five nodes
    job = _job(3, groups=("api", "cache"))
    if level == "job":
        _hosts(job)
    else:
        for tg in job.task_groups:
            _hosts(tg)
    allocs = _process(h, job)
    by_group = {name: {a.node_id for a in allocs if a.task_group == name}
                for name in ("api", "cache")}
    if level == "group":
        # three hosts each, and bin-packing puts both groups on the same
        assert len(allocs) == 6
        assert len(by_group["api"]) == len(by_group["cache"]) == 3
        assert by_group["api"] & by_group["cache"]
        return
    # five hosts for six slots: no host twice, whichever group
    assert len(allocs) == 5 and max(_per_host(allocs).values()) == 1
    assert not by_group["api"] & by_group["cache"]
    failed = h.last_scheduler.failed_tg_allocs
    assert list(failed) == ["cache"]
    assert failed["cache"].constraint_filtered == {"distinct_hosts": 5}


@pytest.mark.parametrize("ask", ["hosts", "property"])
@time_limit(120)
def test_a_job_scaled_up_counts_the_allocations_it_has(ask):
    h = Harness()
    _world(h, racks=(2, 2, 2, 2))
    job = _job(4)
    if ask == "hosts":
        _hosts(job)
    else:
        _prop(job.task_groups[0], "2")
    first = _process(h, job)
    assert len(first) == 4
    more = job.copy()
    more.task_groups[0].count = 8
    allocs = _process(h, more)
    assert len(allocs) == 8 and {a.id for a in first} <= {a.id for a in allocs}
    if ask == "hosts":
        assert max(_per_host(allocs).values()) == 1
    else:
        assert set(_per_rack(h, allocs).values()) == {2}
    assert not [e for e in h.create_evals_list if e.status == "blocked"]
    # one more than the nodes (the racks) hold: the ninth fails
    again = more.copy()
    again.task_groups[0].count = 9
    assert len(_process(h, again)) == 8
    name = "distinct_hosts" if ask == "hosts" else "distinct_property"
    assert h.last_scheduler.failed_tg_allocs["web"].constraint_filtered \
        == {name: 8}


# --------------------------------- single, batched and mesh forms of the step

def _eval_inputs(cm, kind):
    from nomad_tpu.scheduler.stack import DenseStack
    job = _job(7, groups=("api", "cache") if kind == "two groups" else ("web",))
    for tg in job.task_groups:          # two do not fit the smaller nodes
        tg.tasks[0].resources.cpu = 2500
    if kind == "hosts":
        _hosts(job.task_groups[0])
    elif kind == "property":
        _prop(job, "3")
    elif kind == "both":
        _hosts(job)
        _prop(job.task_groups[0], "2")
    else:
        _hosts(job)
        _prop(job.task_groups[0], "2")
        _prop(job.task_groups[1], "1")
    st = DenseStack(cm)
    groups = [st.compile_group(job, tg) for tg in job.task_groups]
    slots = [i % len(groups) for i in range(14 if len(groups) > 1 else 7)]
    return st.build_inputs(job, groups, slots, {}), len(slots)


@pytest.mark.parametrize("kind", ["hosts", "property", "both", "two groups"])
@time_limit(300)
def test_single_batched_and_mesh_steps_choose_the_same_rows(kind):
    """Two evals of one job state chained in one dispatch: the first
    places what the single-eval step places, the second what it places
    on the usage the first left (the carry of hosts and values is an
    eval's own and starts anew).  On one device through the packed batch
    kernel, and on the eight-device mesh through the sharded step."""
    from nomad_tpu.encode import ClusterMatrix
    from nomad_tpu.ops.place import place_eval
    from nomad_tpu.parallel import (
        make_mesh, place_eval_batch_sharded, stack_inputs)
    from nomad_tpu.parallel.engine import PlacementEngine, _Request

    cm = ClusterMatrix()
    rng = np.random.default_rng(9)
    for i in range(64):
        n = mock.node()
        n.attributes["rack"] = f"r{i % 5}"
        n.node_resources.cpu.cpu_shares = int(rng.choice([4000, 8000]))
        n.reserved_resources.cpu_shares = int(rng.integers(0, 2000))
        if i % 9 == 0:
            del n.attributes["rack"]
        cm.upsert_node(n)
    inp, n_slots = _eval_inputs(cm, kind)
    first = place_eval(inp)
    second = place_eval(dataclasses.replace(inp, used=np.asarray(first.used)))
    want = [first.node[:n_slots], second.node[:n_slots]]
    assert (want[0] >= 0).sum() >= 6     # the constraints bind, not starve
    assert not np.array_equal(want[0], want[1])

    for shard_min in (10**9, 8):
        eng = PlacementEngine(shard_min_nodes=shard_min)
        try:
            assert (eng._mesh_for(cm.n_rows) is None) == (shard_min > 8)
            reqs = [_Request(cm=cm, inputs=inp, deltas=[],
                             spread_algorithm=False, future=Future())
                    for _ in range(2)]
            eng._dispatch(reqs)
            for req, rows in zip(reqs, want):
                res, ticket = req.future.result(timeout=120)
                np.testing.assert_array_equal(res.node[:n_slots], rows)
                eng.complete(ticket)
        finally:
            eng.stop()
    # independent evals side by side on the ('node_shard', 'wave') mesh
    node, *_ = place_eval_batch_sharded(
        make_mesh(n_wave_shards=2, n_node_shards=4), stack_inputs([inp, inp]))
    for b in range(2):
        np.testing.assert_array_equal(np.asarray(node[b])[:n_slots], want[0])


# ------------------------------------------------ rows the host picks itself

@time_limit(120)
def test_a_sticky_slot_goes_back_only_where_the_constraint_lets_it():
    """A destructive update of a job with sticky disks puts each slot
    back on its node, and the kernel's pass sees those hosts taken: the
    two new slots go elsewhere."""
    h = Harness()
    _world(h, racks=(3, 3))
    job = _job(4)
    _hosts(job)
    job.task_groups[0].ephemeral_disk.sticky = True
    first = _process(h, job)
    assert len(first) == 4
    update = job.copy()
    update.task_groups[0].count = 6
    update.task_groups[0].tasks[0].config = {"command": "/bin/other"}
    allocs = _process(h, update)
    assert len(allocs) == 6 and max(_per_host(allocs).values()) == 1
    assert {a.node_id for a in first} <= {a.node_id for a in allocs}


@time_limit(120)
def test_a_device_alternative_honours_the_constraint():
    """The kernel's node has no instance left (taken between the pass
    and the placement), so the host walks the pass's alternatives: one
    that holds an allocation of the job is not taken."""
    from nomad_tpu.scheduler import generic
    from nomad_tpu.structs.resources import DeviceRequest, NodeDevice
    h = Harness()
    nodes = _world(h, racks=(2,))
    for n in nodes:
        n.node_resources.devices = [NodeDevice(
            vendor="nvidia", type="gpu", name="t4",
            instance_ids=[f"{n.id[:4]}-{k}" for k in range(4)])]
        h.store.upsert_node(h.next_index(), n)
    job = _job(2)
    _hosts(job)
    job.task_groups[0].tasks[0].resources.devices = [
        DeviceRequest(name="nvidia/gpu", count=1)]
    real = generic.PlacementPass.assign_devices
    refused = []

    def refuse_the_second_slots_node(self, gi, node, preempted):
        # the second slot's kernel node grants nothing, once
        if len(self.plan.node_allocation) == 1 and not refused:
            refused.append(node.id)
            return None
        return real(self, gi, node, preempted)

    generic.PlacementPass.assign_devices = refuse_the_second_slots_node
    try:
        allocs = _process(h, job)
    finally:
        generic.PlacementPass.assign_devices = real
    # the one alternative of a two-node world holds the first slot (the
    # pass lists it, at minus infinity): it has room and instances, and
    # is not taken
    assert len(refused) == 1 and len(allocs) == 1
    assert allocs[0].node_id != refused[0]
    failed = h.last_scheduler.failed_tg_allocs["web"]
    assert failed.dimension_exhausted == {"devices exhausted": 1}


@pytest.mark.parametrize("ask", ["hosts", "property"])
@time_limit(120)
def test_preemption_finds_room_only_where_the_constraint_lets_it(ask):
    """Every node is full of a lower-priority job.  The search may evict
    on a node only while the constraint leaves it open: one a host, or
    one a rack."""
    from nomad_tpu.structs.config import (
        PreemptionConfig, SchedulerConfiguration)
    h = Harness()
    h.store.set_scheduler_config(h.next_index(), SchedulerConfiguration(
        preemption_config=PreemptionConfig(service_scheduler_enabled=True)))
    nodes = []
    for r in range(2):
        for _ in range(2):
            n = mock.node()
            n.attributes["rack"] = f"r{r}"
            nodes.append(n)
            h.store.upsert_node(h.next_index(), n)
    filler = mock.job(priority=20)
    filler.task_groups[0].count = 4
    filler.task_groups[0].tasks[0].resources = Resources(cpu=3600,
                                                         memory_mb=256)
    filler.task_groups[0].ephemeral_disk.size_mb = 0
    _hosts(filler)
    assert len(_process(h, filler)) == 4
    job = _job(6)
    job.priority = 70
    job.task_groups[0].tasks[0].resources = Resources(cpu=1000, memory_mb=128)
    if ask == "hosts":
        _hosts(job)
    else:
        _prop(job, "1")
    allocs = _process(h, job)
    # three of them fit a node once its filler is gone
    if ask == "hosts":
        assert len(allocs) == 4 and max(_per_host(allocs).values()) == 1
    else:
        assert len(allocs) == 2
        assert set(_per_rack(h, allocs).values()) == {1}
    assert all(a.preempted_allocations for a in allocs)


# ------------------------------------------------------- the documented form

JOBSPEC = """
job "quorum" {
  datacenters = ["dc1"]
  constraint {
    operator = "distinct_hosts"
    value    = "true"
  }
  group "store" {
    count = 5
    constraint {
      distinct_property = "${attr.rack}"
      value             = "2"
    }
    task "server" {
      driver = "exec"
      config { command = "/bin/date" }
      resources { cpu = 100  memory = 64 }
    }
  }
}
"""


@time_limit(120)
def test_constraint_blocks_over_http():
    """The documented form, from a jobspec, through a dev agent: HTTP
    register, broker, worker, engine, plan queue, applier, store."""
    from nomad_tpu.agent import Agent, AgentConfig
    from nomad_tpu.api import ApiClient
    from nomad_tpu.jobspec.parse import parse_job
    job = parse_job(JOBSPEC)
    assert [c.operand for c in job.constraints] == ["distinct_hosts"]
    assert job.task_groups[0].constraints == [
        Constraint("${attr.rack}", "2", "distinct_property")]
    a = Agent(AgentConfig(http_port=0, num_schedulers=2, heartbeat_ttl=60.0))
    a.start()
    try:
        rack_of = {}
        for i in range(9):
            n = mock.node()
            n.attributes["rack"] = f"r{i % 3}"
            rack_of[n.id] = n.attributes["rack"]
            a.server.register_node(n)
        api = ApiClient(a.http_addr)
        api.jobs.register(job)
        assert a.server.wait_for_idle(30.0)
        stubs = api.get(f"/v1/job/{job.id}/allocations")
        assert len(stubs) == 5
        assert {s["DesiredStatus"] for s in stubs} == {"run"}
        assert len({s["EvalID"] for s in stubs}) == 1       # one plan
        assert len({s["NodeID"] for s in stubs}) == 5
        racks = [rack_of[s["NodeID"]] for s in stubs]
        assert sorted(racks.count(r) for r in set(racks)) == [1, 2, 2]
    finally:
        a.stop()


# ------------------------------------------------- the cell and its reference

CELL = "distinct-10k.ha-services"
# 120 `ingress` slots want 120 `edge` hosts, a fifth of the nodes: 512
# nodes have 102
CELL_NODES = 1024


@pytest.mark.parametrize("seed", [11, 2147483659])
@time_limit(400)
def test_cell_whole_on_the_cpu(seed):
    """`distinct-10k.ha-services` through Agent, HTTP and ApiClient at
    1,024 nodes, held to the plain reference."""
    from benchmark import harness
    line = harness.run_cell(CELL, seed, 3.0, False, time.monotonic(),
                            n_nodes=CELL_NODES, require_tpu=False)
    compared = {k: v["value"] for k, v in line["compared"].items()}
    assert line["attempted"] >= 3
    assert line["correct"] and line["failed"] == 0, line
    assert compared == {"violations": 0, "unexplained_jobs_share": 0.0,
                        "misplaced_jobs_share": 0.0}


@time_limit(300)
def test_controls_are_not_correct():
    from benchmark import control
    from benchmark.distinct import reference as ref
    got = control.readings(CELL, 5, jobs=6, n_nodes=CELL_NODES)
    limits = ref.LIMITS
    assert got["sound"] == {"correct": True, "violations": 0,
                            "unexplained_jobs_share": 0.0,
                            "misplaced_jobs_share": 0.0}, got
    for name, number in (("control", "unexplained_jobs_share"),
                         ("half_hidden", "misplaced_jobs_share")):
        assert not got[name]["correct"], (name, got)
        assert got[name][number] > limits[number], (name, got)
    assert ref.FAULTS == ("two_on_a_host", "cross_group", "rack_over_limit",
                          "constraint_dropped")
    for name in ref.FAULTS:
        assert not got[name]["correct"], (name, got)
        assert got[name]["violations"] > 0, (name, got)
        assert got[name]["unexplained_jobs_share"] == 0.0, (name, got)


@time_limit(60)
def test_the_cluster_module_refuses_a_program_that_carries_neither(
        monkeypatch):
    """The parent's step put back (the kernel is handed no scope, and the
    host asks nothing of its own twin): the probe names the constraint,
    before an agent would start."""
    from benchmark import harness
    from benchmark.distinct import cluster as distinct_cluster
    from nomad_tpu.scheduler.stack import DistinctCarry

    cl = distinct_cluster.Cluster(harness.load_config("distinct-10k"), 1, 64)
    cl.refuse_a_program_that_cannot_run_this()          # this tree: runs
    G = 1
    monkeypatch.setattr(DistinctCarry, "inputs", lambda self: dict(
        hosts_taken=np.zeros((0, self.hosts_taken.shape[1]), bool),
        hosts_of=np.zeros((G, 0), bool),
        prop_vidx=np.zeros((0, self.hosts_taken.shape[1]), np.int32),
        prop_counts=np.zeros((0, 1), np.int32),
        prop_limit=np.zeros(0, np.int32), prop_of=np.zeros((G, 0), bool)))
    monkeypatch.setattr(DistinctCarry, "allows", lambda self, gi, row: True)
    with pytest.raises(harness.Refused, match="does not carry distinct_"):
        cl.refuse_a_program_that_cannot_run_this()


@pytest.mark.parametrize("shape", ["ingress", "quorum", "paired"])
@time_limit(120)
def test_the_programs_masks_are_the_references(shape):
    """`regexp`, `version` and `set_contains` over the cluster's own
    columns (2,048 distinct node names): `compile_group`'s mask is the
    reference's own reading of the operators, node for node, and a job of
    the shape placed on the preloaded cluster passes the reference's
    comparison."""
    import types
    from benchmark import harness, traffic
    from benchmark.distinct import (
        cluster as distinct_cluster, jobs as distinct_jobs, reference as ref)
    from nomad_tpu.scheduler.stack import DenseStack

    cl = distinct_cluster.Cluster(harness.load_config("distinct-10k"), 7,
                                  2048)
    h = Harness()
    distinct_cluster.c2m.Cluster.install(cl, types.SimpleNamespace(
        server=types.SimpleNamespace(store=h.store,
                                     next_index=h.next_index)))
    cm = h.store.matrix
    rows = np.array([cm.row_of[i] for i in cl.node_ids])
    body = traffic.load("ha-services")["shapes"][shape]
    job = distinct_jobs.build(body, f"{shape}-5")
    spec = ref.JobSpec(job.id, job.namespace, body)
    world = ref.World(cl)
    st = DenseStack(cm)
    for tg in job.task_groups:
        got = st.compile_group(job, tg)
        want = world.static(spec, tg.name)
        assert np.array_equal(got.feasible[rows], want)
        assert 0 < want.sum() < cl.n
        assert not got.uncoupled
    allocs = _process(h, job)
    assert len(allocs) == spec.allocs and len(h.plans) == 1
    stubs = [{"ID": a.id, "JobID": a.job_id, "TaskGroup": a.task_group,
              "NodeID": a.node_id, "Name": a.name, "EvalID": a.eval_id,
              "DesiredStatus": "run", "ModifyIndex": a.modify_index}
             for a in allocs]
    spec.registered = h.store.job_by_id(job.namespace, job.id).modify_index
    verdict = ref.compare(cl, {job.id: spec}, stubs, [], {job.id},
                          {"violations": 0})
    assert verdict["correct"], verdict["problems"]


@time_limit(60)
def test_the_reference_imports_nothing_of_the_program():
    code = ("import sys; import benchmark.distinct.reference, "
            "benchmark.distinct.cluster; "
            "bad = [m for m in sys.modules if m.startswith('nomad_tpu')]; "
            "sys.exit(1 if bad else 0)")
    import os
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert subprocess.run([sys.executable, "-c", code], cwd=root).returncode \
        == 0


def test_the_references_versions_and_sets():
    from benchmark.distinct.reference import meets, version_meets
    assert version_meets("5.4.0", ">= 5.4")
    assert version_meets("5.15.0", ">= 5.4") and version_meets("6.1.0", "> 5")
    assert not version_meets("4.19.0", ">= 5.4")
    assert version_meets("5.4.0", ">= 5.4, < 5.15")
    assert not version_meets("5.15.0", ">= 5.4, < 5.15")
    assert not version_meets("five", ">= 5.4")
    assert meets("set_contains", "ssd,nvme,25g", "ssd")
    assert meets("set_contains", "ssd, 10g", "10g,ssd")
    assert not meets("set_contains", "10g", "ssd")
    assert meets("regexp", "edge-00017", "^edge-[0-9]+$")
    assert not meets("regexp", "storage-00017", "^edge-[0-9]+$")
    assert not meets("=", None, "edge") and meets("!=", None, "edge")
