"""A task's `device` block: the ask's constraints and affinities are read
(scheduler/devices.py, feasible.device_fit), the kernel scores the
`devices` scorer, and the plain reference of the `devices-10k`
configuration (benchmark/devices/reference.py, which imports nothing of
the program) agrees: operator by operator, unit pair by unit pair, group
choice on a two-group node, the mean on random fleets, and one whole run
of the cell `devices-10k.gpu-asks` on the CPU, which is not `correct` once
the program drops the constraints or the affinities.
"""
import functools
import hashlib
import json
import os
import signal
import time

import numpy as np
import pytest

from nomad_tpu import mock
from nomad_tpu.encode import ClusterMatrix
from nomad_tpu.scheduler import devices as dv
from nomad_tpu.scheduler import feasible as fz
from nomad_tpu.scheduler.testing import Harness
from nomad_tpu.structs.job import Affinity, Constraint
from nomad_tpu.structs.resources import DeviceRequest, NodeDevice

from benchmark import harness, reference as c2m
from benchmark.devices import jobs as device_jobs, reference as ref

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "devices-10k.gpu-asks"
FLEET = harness.load_config("devices-10k")["fleet"]["groups"]


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


# ------------------------------------------------ operators and unit pairs

OPERATORS = [
    ("=", "16 GiB", "16 GiB", True), ("==", "t4", "t4", True),
    ("is", "t4", "v100", False), ("!=", "t4", "v100", True),
    ("not", "16 GiB", "16 GiB", False), ("!=", None, "t4", True),
    ("!=", None, None, False), ("<", "16 GiB", "32 GiB", True),
    ("<=", "32 GiB", "32 GiB", True), (">", "16 GiB", "32 GiB", False),
    (">=", "80 GiB", "64 GiB", True), (">=", None, "64 GiB", False),
    (">=", "abc", "abd", False), ("<", "abc", "abd", True),
    ("=", "true", "true", True), ("<", "true", "false", False),
    ("=", "2560", "2560.0", True), (">", "6912", "5000", True),
    ("regexp", "a100-80g", "^a100", True), ("regexp", "t4", "^a100", False),
    ("regexp", "16 GiB", "16", False),
    ("version", "470.82.01", ">= 450.0, < 500", True),
    ("version", "418.1", ">= 450.0", False), ("version", "11", ">= 10", True),
    ("semver", "1.2.3", "> 1.2.0", True),
    ("set_contains", "fp16,int8,tf32", "int8,fp16", True),
    ("set_contains_all", "fp16,int8", "int8,bf16", False),
    ("set_contains_any", "fp16,int8", "bf16,int8", True),
    ("set_contains_any", "fp16", "bf16", False),
    ("is_set", "16 GiB", None, True), ("is_set", None, None, False),
    ("is_not_set", None, None, True), ("is_not_set", "x", None, False),
    ("no_such_operator", "a", "a", False),
]
UNIT_PAIRS = [
    (">=", "16 GiB", "16384 MiB", True), (">", "16 GiB", "16384 MiB", False),
    (">", "16 GiB", "16 GB", True), ("<", "1000 MB", "1 GiB", True),
    ("=", "1 TiB", "1024 GiB", True), ("=", "1 kB", "1000", False),
    (">=", "1590 MHz", "1.5 GHz", True), ("<", "1410 MHz", "1.5 GHz", True),
    ("=", "2 GHz", "2000 MHz", True),
    (">", "900 GB/s", "320 GB/s", True), ("<", "1 GiB/s", "1 GB/s", False),
    ("<", "1 MiB/s", "1 GB/s", True), (">=", "250 W", "0.25 kW", True),
    ("<", "900 mW", "1 W", True),
    (">=", "16 GiB", "1 GHz", False), ("!=", "16 GiB", "1 GHz", False),
    (">=", "16 GiB", "16", False), (">=", "16", "16 GiB", False),
    ("<", "16 GiB", "1 GB/s", False),
]


@pytest.mark.parametrize("operator,left,right,want", OPERATORS + UNIT_PAIRS)
@time_limit(20)
def test_operator_against_the_plain_reference(operator, left, right, want):
    got = dv.check_attribute(
        operator, None if left is None else dv.parse_attribute(left),
        None if right is None else dv.parse_attribute(right))
    plain = ref.holds(operator, None if left is None else ref.parse(left),
                      None if right is None else ref.parse(right))
    assert got == plain == want


@time_limit(30)
def test_constraint_masks_over_the_matrix_follow_the_reference():
    """`device_check_mask` (codes and a gather) on a small mixed fleet
    against the reference's scalar rule, node by node, for targets of
    every kind and a node whose group lacks the attribute."""
    cm = ClusterMatrix()
    rows = {}
    for g in FLEET + [dict(FLEET[0], model="bare", attributes={})]:
        n = mock.node()
        n.node_resources.devices = [NodeDevice(
            vendor=g["vendor"], type=g["type"], name=g["model"],
            instance_ids=["i0"], attributes=dict(g["attributes"]))]
        rows[cm.upsert_node(n)] = g
    rules = [("${device.attr.memory}", ">=", "32 GiB"),
             ("${device.attr.memory}", "<", "40960 MiB"),
             ("${device.attr.graphics_clock}", ">", "1.5 GHz"),
             ("${device.attr.cuda_cores}", ">=", "5120"),
             ("${device.attr.memory_bandwidth}", ">", "800000 MB/s"),
             ("${device.model}", "regexp", "^a100"),
             ("${device.vendor}", "=", "nvidia"),
             ("${device.type}", "!=", "gpu"),
             ("${device.attr.memory}", "is_set", ""),
             ("${device.attr.nvlink}", "is_not_set", ""),
             ("64 GiB", "<=", "${device.attr.memory}"),
             ("${device.attr.memory}", "!=", "${device.attr.cuda_cores}"),
             ("${device.ids}", "=", "i0")]
    for left, op, right in rules:
        for row, g in rows.items():
            gid = f"{g['vendor']}/{g['type']}/{g['model']}"
            got = fz.device_check_mask(cm, gid, left, right, op)[row]
            want = ref._rule_holds(
                {"attribute": left, "operator": op, "value": right}, g)
            assert got == want, (left, op, right, g["model"])


# --------------------------------------------------------- two-group node

def _device(g, n_instances):
    return NodeDevice(vendor=g["vendor"], type=g["type"], name=g["model"],
                      instance_ids=[f"{g['model']}-{k}"
                                    for k in range(n_instances)],
                      attributes=dict(g["attributes"]))


def _run_job(h, job):
    ev = mock.eval(job_id=job.id, type=job.type, priority=job.priority)
    h.store.upsert_job(h.next_index(), job)
    h.process(job.type, ev)
    allocs = h.store.allocs_by_job(job.namespace, job.id)
    return sorted(allocs, key=lambda a: c2m._slot(a.name))


PREFER_80 = {"name": "nvidia/gpu", "count": 1,
             "constraints": [{"attribute": "${device.attr.memory}",
                              "operator": ">=", "value": "12 GiB"}],
             "affinities": [{"attribute": "${device.attr.memory}",
                             "operator": ">=", "value": "64 GiB",
                             "weight": 75}]}


def _job(ask, count, job_id="asks"):
    return device_jobs.build(
        {"kind": "batch", "groups": 1, "count": count, "cpu": 100,
         "memory_mb": 64, "datacenters": ["dc1"], "device": ask}, job_id)


def _devices_of(alloc):
    (task,) = alloc.allocated_resources.tasks.values()
    (d,) = task.devices
    return d


def _reported(alloc):
    (meta,) = [m for m in alloc.metrics.score_meta
               if m["node_id"] == alloc.node_id]
    return meta["norm_score"], meta["scores"].get("devices")


@time_limit(120)
def test_assign_device_takes_the_better_group_then_the_other():
    """One node, a T4 group and an 80 GiB A100 group of two instances
    each, an ask that prefers 64 GiB and up: the A100s go first
    (`devices` 1.0), then the T4s (0.0), and the score the node reports
    changes with the group, placement by placement."""
    t4, a100 = FLEET[0], FLEET[3]
    h = Harness()
    node = mock.node()
    node.node_resources.devices = [_device(t4, 2), _device(a100, 2)]
    h.store.upsert_node(h.next_index(), node)

    req = device_jobs.build({"kind": "batch", "count": 1, "cpu": 1,
                             "memory_mb": 1, "datacenters": ["dc1"],
                             "device": PREFER_80}, "x").task_groups[0] \
        .tasks[0].resources.devices[0]
    got, weight = dv.assign_device_instances(node, [], req)
    assert (got["name"], weight) == ("a100-80g", 75.0)
    got, weight = dv.assign_device_instances(
        node, [], req, extra_used={"nvidia/gpu/a100-80g":
                                   {"a100-80g-0", "a100-80g-1"}})
    assert (got["name"], weight) == ("t4", 0.0)

    fit = fz.device_fit(h.store.matrix, [req])
    row = h.store.matrix.row_of[node.id]
    assert fit.multi_level and fit.has_score
    assert (int(fit.place_cap[row]), float(fit.score[row])) == (4, 1.0)

    allocs = _run_job(h, _job(PREFER_80, 4))
    assert [_devices_of(a)["name"] for a in allocs] == \
        ["a100-80g", "a100-80g", "t4", "t4"]
    assert [_reported(a)[1] for a in allocs] == [1.0, 1.0, 0.0, 0.0]
    ids = [i for a in allocs for i in _devices_of(a)["device_ids"]]
    assert len(set(ids)) == 4
    # the reference walks the same node the same way
    groups = sorted([dict(t4), dict(a100)],
                    key=lambda g: f"{g['vendor']}/{g['type']}/{g['model']}")
    free = [2, 2]
    walk = [ref.devices_score(groups, free, [PREFER_80])[0]
            for _ in range(4)]
    assert walk == [1.0, 1.0, 0.0, 0.0]
    assert ref.devices_score(groups, free, [PREFER_80]) is None


@time_limit(60)
def test_a_card_the_constraint_rules_out_is_never_given():
    h = Harness()
    for g in (FLEET[0], FLEET[1], FLEET[2]):
        node = mock.node()
        node.node_resources.devices = [_device(g, 4)]
        h.store.upsert_node(h.next_index(), node)
    ask = dict(PREFER_80, constraints=[{
        "attribute": "${device.attr.memory}", "operator": ">=",
        "value": "32 GiB"}])
    allocs = _run_job(h, _job(ask, 6))
    assert len(allocs) == 4, "four 40 GiB cards, and no 16 GiB card stands in"
    assert {_devices_of(a)["name"] for a in allocs} == {"a100-40g"}


# ------------------------------------------- the mean, on random fleets

ASKS = [
    PREFER_80,
    {"name": "gpu", "count": 2,
     "constraints": [{"attribute": "${device.attr.memory}",
                      "operator": ">=", "value": "32 GiB"}],
     "affinities": [{"attribute": "${device.attr.memory}",
                     "operator": ">=", "value": "64 GiB", "weight": 75}]},
    {"name": "nvidia/gpu", "count": 1,
     "constraints": [{"attribute": "${device.attr.memory}",
                      "operator": ">=", "value": "12 GiB"}],
     "affinities": [{"attribute": "${device.model}", "operator": "=",
                     "value": "t4", "weight": 50},
                    {"attribute": "${device.attr.memory}", "operator": ">=",
                     "value": "40 GiB", "weight": -50}]},
    {"name": "nvidia/gpu", "count": 1,
     "constraints": [{"attribute": "${device.attr.graphics_clock}",
                      "operator": ">", "value": "1.5 GHz"}],
     "affinities": [{"attribute": "${device.attr.cuda_cores}",
                     "operator": ">=", "value": "5000", "weight": 30},
                    {"attribute": "${device.vendor}", "operator": "=",
                     "value": "amd", "weight": 20}]},
    {"name": "nvidia/gpu/v100", "count": 1},
]
SHAPES = [(4000, 8192), (8000, 16384), (8000, 32768), (16000, 65536)]


@pytest.mark.parametrize("n_nodes,seed", [(64, 5), (64, 6), (256, 7),
                                          (256, 2147483659)])
@time_limit(240)
def test_devices_scorer_in_the_mean_against_the_reference(n_nodes, seed):
    """Seeded random fleets (no group, one, or two on a node): every
    placement's reported norm score and `devices` score (float32, on the
    kernel) within 1e-6 of the reference's float64, the node chosen the
    reference's best, the group taken the reference's group."""
    rng = np.random.default_rng([seed, 0xDE71CE])
    h = Harness()
    nodes, fleet = [], []
    for _ in range(n_nodes):
        node = mock.node()
        cpu, mem = SHAPES[rng.integers(len(SHAPES))]
        node.node_resources.cpu.cpu_shares = int(cpu)
        node.node_resources.memory_mb = int(mem)
        models = rng.choice(len(FLEET), size=rng.choice(3, p=[.3, .5, .2]),
                            replace=False)
        groups = sorted((FLEET[m] for m in models), key=lambda g: g["model"])
        counts = [int(rng.integers(1, 5)) for _ in groups]
        node.node_resources.devices = [_device(g, k)
                                       for g, k in zip(groups, counts)]
        h.store.upsert_node(h.next_index(), node)
        nodes.append(node)
        fleet.append((groups, counts))
    cm = h.store.matrix
    cap = np.array([[n.node_resources.cpu.cpu_shares,
                     n.node_resources.memory_mb] for n in nodes], float)
    used = np.zeros_like(cap)
    index = {n.id: i for i, n in enumerate(nodes)}
    checked = 0
    for j, ask in enumerate(ASKS):
        count = 10
        demand = np.array([900.0, 2048.0])
        job = device_jobs.build(
            {"kind": "batch", "groups": 1, "count": count, "cpu": 900,
             "memory_mb": 2048, "datacenters": ["dc1"], "device": ask},
            f"fleet-{j}")
        allocs = _run_job(h, job)
        coll = np.zeros(n_nodes)
        for a in allocs:
            offers = np.full(n_nodes, -np.inf)
            taken = {}
            fit = c2m.fit_score(cap, used + demand)
            for i, (groups, free) in enumerate(fleet):
                if ((used[i] + demand) > cap[i]).any():
                    continue
                trial = list(free)
                got = ref.devices_score(groups, trial, [ask])
                if got is None:
                    continue
                taken[i] = (groups[got[1][0]]["model"], trial)
                offers[i] = ref.total_score(fit[i], coll[i], count, got[0])
            i = index[a.node_id]
            norm, dev = _reported(a)
            plain_dev = ref.devices_score(fleet[i][0], list(fleet[i][1]),
                                          [ask])[0]
            assert i in taken, "placed where the reference finds no group"
            assert abs(norm - offers[i]) <= 1e-6
            assert (dev is None) == (plain_dev is None)
            assert dev is None or abs(dev - plain_dev) <= 1e-6
            assert offers.max() - offers[i] <= 1e-6, "not the best node"
            assert _devices_of(a)["name"] == taken[i][0]
            assert len(_devices_of(a)["device_ids"]) == ask["count"]
            fleet[i] = (fleet[i][0], taken[i][1])
            used[i] += demand
            coll[i] += 1
            checked += 1
        # whatever was left unplaced, the reference cannot place either
        if len(allocs) < count:
            fit_left = [i for i, (groups, free) in enumerate(fleet)
                        if ((used[i] + demand) <= cap[i]).all()
                        and ref.devices_score(groups, list(free), [ask])]
            assert not fit_left
    assert checked >= 20
    assert cm.device_attr_codes, "attributes are columns of the matrix"


# ------------------------------------- jobs that ask for no device: as before

@time_limit(240)
def test_no_device_affinity_places_bit_equal_to_before():
    """`c2m-10k`'s three job shapes at 256 nodes on three seeds: the
    whole `PlaceResult` hashes as it did before the `devices` scorer was
    an input of the scan step (recorded at the parent commit)."""
    from benchmark import jobs, traffic
    from benchmark.cluster import Cluster
    from nomad_tpu.agent.agent import Agent, AgentConfig
    from nomad_tpu.ops.place import place_eval
    from nomad_tpu.scheduler.stack import DenseStack
    with open(os.path.join(HERE, "fixtures", "place_digests.json")) as f:
        recorded = json.load(f)
    cfg = harness.load_config("c2m-10k")
    got = {}
    for seed in (3, 7, 2147483659):
        cl = Cluster(cfg, seed, 256)
        agent = Agent(AgentConfig(http_port=0, num_schedulers=1,
                                  heartbeat_ttl=3600.0))
        cl.install(agent)
        cm = agent.server.store.matrix
        for mix in (traffic.load("backlog"), traffic.load("spread-steady")):
            for name, shape in mix["shapes"].items():
                job = jobs.build(shape, f"digest-{name}")
                st = DenseStack(cm)
                groups = [st.compile_group(job, tg)
                          for tg in job.task_groups]
                slots = [gi for gi, tg in enumerate(job.task_groups)
                         for _ in range(min(tg.count, 64))]
                res = place_eval(st.build_inputs(job, groups, slots, {}))
                m = hashlib.sha256()
                for field in ("node", "score", "fit_score",
                              "nodes_evaluated", "nodes_exhausted",
                              "top_nodes", "top_scores", "used"):
                    m.update(np.ascontiguousarray(
                        np.asarray(getattr(res, field))).tobytes())
                got[f"{seed}.{name}"] = m.hexdigest()
        agent.stop()
    assert got == recorded


# ----------------------------------------------- the cell, whole, on the CPU

def _drop(monkeypatch, field):
    """The program as it was: what arrives in a `device` block's `field`
    is never read."""
    real = DeviceRequest.__post_init__

    def post_init(self):
        real(self)
        setattr(self, field, [])
    monkeypatch.setattr(DeviceRequest, "__post_init__", post_init)


@pytest.mark.parametrize("dropped,fails_by", [
    (None, None), ("constraints", "violations"),
    ("affinities", "unexplained_jobs_share")])
@time_limit(300)
def test_cell_whole_on_the_cpu(dropped, fails_by, monkeypatch):
    """`devices-10k.gpu-asks` through Agent, HTTP and ApiClient at 512
    nodes (at 256 the fleet holds three `train` gangs' cards only
    barely, and a job that blocks waits out the drain)."""
    if dropped:
        _drop(monkeypatch, dropped)
    line = harness.run_cell(CELL, 11, 3.0, False, time.monotonic(),
                            n_nodes=512, require_tpu=False)
    compared = {k: v["value"] for k, v in line["compared"].items()}
    assert line["attempted"] == 6
    if dropped is None:
        assert line["correct"] and line["failed"] == 0, line
        assert compared == {"violations": 0, "unexplained_jobs_share": 0.0,
                            "misplaced_jobs_share": 0.0}
    else:
        assert not line["correct"], line
        assert compared[fails_by] > line["compared"][fails_by]["limit"]


def test_device_request_comes_off_the_wire_typed():
    from nomad_tpu.api.codec import from_wire, to_wire
    req = DeviceRequest(name="nvidia/gpu", count=2, constraints=[
        Constraint("${device.attr.memory}", "32 GiB", ">=")],
        affinities=[Affinity("${device.model}", "t4", "=", 50)])
    assert from_wire(DeviceRequest, to_wire(req)) == req
