"""A `network` block's ports, group-level (the form the job
specification has documented since 0.12) and task-level: an allocation
holds each asked port once in the one definition the plan applier, the
cluster matrix and the scheduler share (`Allocation.ports`), the kernel's
place_cap keeps one eval's slots inside what a node's ports allow, and
the plain reference of the `ports-10k` configuration
(benchmark/ports/reference.py, which imports nothing of the program)
agrees with whole runs of the cell `ports-10k.web-services` on the CPU.
"""
import functools
import signal
import subprocess
import sys
import time

import numpy as np
import pytest

from nomad_tpu import mock
from nomad_tpu.scheduler.placement import PortClaims
from nomad_tpu.scheduler.testing import Harness
from nomad_tpu.structs import Evaluation
from nomad_tpu.structs.resources import NetworkPort, NetworkResource

from benchmark import control, harness
from benchmark.ports import cluster as ports_cluster, reference as ref

CELL = "ports-10k.web-services"


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


def _port_job(level, static, dynamic, count):
    job = mock.job()
    tg = job.task_groups[0]
    tg.count = count
    net = NetworkResource(
        reserved_ports=[NetworkPort(label=f"s{p}", value=p) for p in static],
        dynamic_ports=[NetworkPort(label=f"d{i}") for i in range(dynamic)])
    tg.networks = []
    for t in tg.tasks:
        t.resources.networks = []
    if level == "group":
        tg.networks = [net]
    else:
        tg.tasks[0].resources.networks = [net]
    return job


def _process(h, job):
    h.store.upsert_job(h.next_index(), job)
    ev = Evaluation(namespace=job.namespace, job_id=job.id, type=job.type,
                    triggered_by="job-register", status="pending",
                    priority=job.priority)
    h.store.upsert_evals(h.next_index(), [ev])
    h.process("service", ev)
    return [a for a in h.store.allocs_by_job(job.namespace, job.id)
            if not a.terminal_status()]


def _bits(words) -> set:
    return {(w << 5) + b for w in np.flatnonzero(words)
            for b in range(32) if (int(words[w]) >> b) & 1}


@pytest.mark.parametrize("level", ["group", "task"])
@pytest.mark.parametrize("static,dynamic", [((8080,), 0), ((), 2),
                                            ((8443,), 1)])
@time_limit(120)
def test_every_slot_placed_in_one_plan_each_port_once(level, static,
                                                      dynamic):
    h = Harness()
    for _ in range(8):
        h.store.upsert_node(h.next_index(), mock.node())
    job = _port_job(level, static, dynamic, 4)
    allocs = _process(h, job)
    assert len(allocs) == 4 and len(h.plans) == 1
    assert not h.results[0].rejected_nodes
    assert h.applier.stats["port_nodes"] == len(h.plans[0].node_allocation)
    assert h.applier.stats["port_rejected_nodes"] == 0
    cm = h.store.matrix
    by_node: dict = {}
    for a in allocs:
        ports = a.ports()
        # once each: as the applier and the scheduler count them ...
        assert len(ports) == len(set(ports)) == len(static) + dynamic
        assert set(static) <= set(ports)
        for p in set(ports) - set(static):
            assert 20000 <= p <= 32000
        # ... and as the matrix tracks them
        assert cm._node_allocs[a.node_id][a.id][1] == ports
        by_node.setdefault(a.node_id, set()).update(ports)
        if level == "group":
            res = a.allocated_resources
            assert [p.value for p in res.shared_ports] == list(ports)
            assert res.shared_networks        # told twice, counted once
    for node_id, row in cm.row_of.items():
        assert _bits(cm.port_words[row]) == by_node.get(node_id, set())
    # the scheduler's freed-port bookkeeping reads the same definition:
    # what a stopped allocation frees is exactly what it held
    a = allocs[0]
    row = cm.row_of[a.node_id]
    claims = PortClaims(cm)
    for p in a.ports():
        assert not claims.claim_static(row, p, set())
        assert claims.claim_static(row, p, set(a.ports()))
    # released when the allocations stop
    job = job.copy()
    job.stop = True
    assert _process(h, job) == []
    for row in cm.row_of.values():
        assert not cm.port_words[row].any()


@time_limit(120)
def test_a_static_port_is_one_placement_a_free_node():
    """More slots than free nodes: one on every node that has the port
    free, in one plan, none refused by the host or the applier, and the
    rest blocked."""
    h = Harness()
    rng = np.random.default_rng(3)
    nodes = []
    for _ in range(24):
        n = mock.node()
        n.node_resources.cpu.cpu_shares = int(rng.choice([4000, 8000, 16000]))
        n.node_resources.memory_mb = int(rng.choice([8192, 16384, 32768]))
        nodes.append(n)
        h.store.upsert_node(h.next_index(), n)
    first = _process(h, _port_job("group", (8080,), 0, 4))
    taken = {a.node_id for a in first}
    assert len(taken) == 4
    from nomad_tpu.parallel.engine import get_engine
    stats = get_engine().stats
    before = (stats["port_placements"], stats["port_fallbacks"])
    job = _port_job("group", (8080,), 1, 30)
    allocs = _process(h, job)
    assert len(allocs) == 20 and len(h.plans) == 2
    assert {a.node_id for a in allocs} == {n.id for n in nodes} - taken
    assert not h.results[-1].rejected_nodes
    # the kernel never chose a node the host then had to refuse
    assert stats["port_placements"] - before[0] == 20
    assert stats["port_fallbacks"] - before[1] == 0
    blocked = [e for e in h.create_evals_list if e.status == "blocked"]
    assert len(blocked) == 1
    failed = h.last_scheduler.failed_tg_allocs["web"]
    assert failed.coalesced_failures == 9


@time_limit(120)
def test_a_narrowed_node_gives_out_no_more_than_its_range_holds():
    h = Harness()
    for _ in range(6):
        n = mock.node()
        n.node_resources.min_dynamic_port = 20000
        n.node_resources.max_dynamic_port = 20004      # five values
        n.reserved_resources.reserved_ports = [22, 20002]
        h.store.upsert_node(h.next_index(), n)
    allocs = _process(h, _port_job("group", (), 2, 40))
    # four free values a node, two an allocation
    assert len(allocs) == 12 and len(h.plans) == 1
    per_node: dict = {}
    for a in allocs:
        per_node.setdefault(a.node_id, []).extend(a.ports())
    for ports in per_node.values():
        assert sorted(ports) == [20000, 20001, 20003, 20004]
    assert not h.results[0].rejected_nodes


def _held(cm, row, p):
    return bool((int(cm.port_words[row, p >> 5]) >> (p & 31)) & 1)


def _walk_from_start(cm, row, start, taken, freed):
    """What `assign_dynamic` has to give: the first value of the row's
    range at or after its start, else the first below it, that this plan
    has not taken and that is free or freed."""
    lo, hi = int(cm.dyn_port_lo[row]), int(cm.dyn_port_hi[row])
    first = lo + start % (hi - lo + 1)
    for p in list(range(first, hi + 1)) + list(range(lo, first)):
        if p not in taken and (p in freed or not _held(cm, row, p)):
            return p
    return None


@pytest.mark.parametrize("seed", range(8))
def test_a_dynamic_port_is_the_first_free_from_the_evals_own_start(seed):
    """Ranges with either end inside a word and inside one word, random
    held ports, freed ones and a random eval id: every value the claims
    give out is the naive walk's from the eval's start, around the
    range's end, until the range is exhausted and the answer is None."""
    import zlib
    from nomad_tpu.encode import ClusterMatrix
    rng = np.random.default_rng(seed)
    cm = ClusterMatrix()
    rows = []
    for lo, hi in [(20000, 20031), (20005, 20100), (30000, 30010),
                   (20000, 20200)]:
        n = mock.node()
        n.node_resources.min_dynamic_port = lo
        n.node_resources.max_dynamic_port = hi
        held = rng.choice(np.arange(lo - 3, hi + 4),
                          int(rng.integers(0, hi - lo)), replace=False)
        n.reserved_resources.reserved_ports = [int(p) for p in held]
        rows.append(cm.upsert_node(n))
    eval_id = f"eval-{seed}-{rng.integers(1 << 30)}"
    claims = PortClaims(cm, eval_id)
    assert claims.start == zlib.crc32(eval_id.encode())
    for row in rows:
        lo, hi = int(cm.dyn_port_lo[row]), int(cm.dyn_port_hi[row])
        freed = {int(p) for p in rng.choice(np.arange(lo, hi + 1), 3)}
        free = int(cm.free_dynamic_ports()[row]) + sum(
            _held(cm, row, p) for p in freed)
        taken: set = set()
        for _ in range(free):
            got = claims.assign_dynamic(row, freed)
            assert got == _walk_from_start(cm, row, claims.start, taken,
                                           freed)
            taken.add(got)
        assert len(taken) == free and all(lo <= p <= hi for p in taken)
        assert claims.assign_dynamic(row, freed) is None


def test_two_evals_that_choose_one_node_take_different_dynamic_ports():
    """The default claims (no eval) walk from the range's low end, as
    they always did; two evals' claims over the same committed bits start
    apart, so neither plan's node is refused for the other's port."""
    from nomad_tpu.encode import ClusterMatrix
    cm = ClusterMatrix()
    wide, narrow = mock.node(), mock.node()
    narrow.node_resources.min_dynamic_port = 20000
    narrow.node_resources.max_dynamic_port = 20031
    rows = [cm.upsert_node(wide), cm.upsert_node(narrow)]
    plain = PortClaims(cm)
    assert [plain.assign_dynamic(r, set()) for r in rows] == [20000, 20000]
    assert plain.assign_dynamic(rows[0], set()) == 20001
    for row, span in zip(rows, (12001, 32)):
        picks = []
        for i in range(16):
            claims = PortClaims(cm, f"e{i}")
            pair = [claims.assign_dynamic(row, set()) for _ in range(2)]
            assert pair[1] == 20000 + (pair[0] - 20000 + 1) % span
            picks.append(pair[0])
        # crc32 of sixteen ids: distinct starts on the wide range, and
        # most of them apart even on the 32 values of the narrowed node
        assert len(set(picks)) == 16 if span > 32 else len(set(picks)) > 8


JOBSPEC = """
job "web" {
  datacenters = ["dc1"]
  group "frontend" {
    count = 3
    network {
      port "http" {}
      port "https" { static = 8443 }
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
def test_group_network_block_over_http():
    """The documented form, from a jobspec, through a dev agent: HTTP
    register, broker, worker, engine, plan queue, applier, store."""
    from nomad_tpu.agent import Agent, AgentConfig
    from nomad_tpu.api import ApiClient
    from nomad_tpu.jobspec.parse import parse_job
    job = parse_job(JOBSPEC)
    assert [p.label for p in job.task_groups[0].networks[0].dynamic_ports] \
        == ["http"]
    a = Agent(AgentConfig(http_port=0, num_schedulers=2, heartbeat_ttl=60.0))
    a.start()
    try:
        for _ in range(4):
            a.server.register_node(mock.node())
        api = ApiClient(a.http_addr)
        api.jobs.register(job)
        assert a.server.wait_for_idle(30.0)
        stubs = api.get(f"/v1/job/{job.id}/allocations")
        assert len(stubs) == 3
        assert {s["DesiredStatus"] for s in stubs} == {"run"}
        assert len({s["NodeID"] for s in stubs}) == 3      # 8443 once a node
        assert len({s["EvalID"] for s in stubs}) == 1
        for s in stubs:
            full = api.get(f"/v1/allocation/{s['ID']}")
            held = {p["label"]: p["value"]
                    for p in full["allocated_resources"]["shared_ports"]}
            assert held["https"] == 8443
            assert 20000 <= held["http"] <= 32000
        stats = a.server.applier.stats
        assert stats["port_nodes"] == 3 and not stats["port_rejected_nodes"]
    finally:
        a.stop()


# ------------------------------------------------- the cell and its reference

@pytest.mark.parametrize("seed", [11, 2147483659])
@time_limit(300)
def test_cell_whole_on_the_cpu(seed):
    """`ports-10k.web-services` through Agent, HTTP and ApiClient at 512
    nodes, held to the plain reference."""
    line = harness.run_cell(CELL, seed, 3.0, False, time.monotonic(),
                            n_nodes=512, require_tpu=False)
    compared = {k: v["value"] for k, v in line["compared"].items()}
    assert line["attempted"] >= 2
    assert line["correct"] and line["failed"] == 0, line
    assert compared == {"violations": 0, "unexplained_jobs_share": 0.0,
                        "misplaced_jobs_share": 0.0}


@time_limit(300)
def test_controls_are_not_correct():
    got = control.readings(CELL, 5, jobs=6, n_nodes=1024)
    limits = ref.LIMITS
    assert got["sound"] == {"correct": True, "violations": 0,
                            "unexplained_jobs_share": 0.0,
                            "misplaced_jobs_share": 0.0}, got
    for name, number in (("control", "unexplained_jobs_share"),
                         ("half_hidden", "misplaced_jobs_share")):
        assert not got[name]["correct"], (name, got)
        assert got[name][number] > limits[number], (name, got)
    for name in ref.FAULTS:
        assert not got[name]["correct"], (name, got)
        assert got[name]["violations"] > 0, (name, got)
        assert got[name]["unexplained_jobs_share"] == 0.0, (name, got)


@time_limit(60)
def test_the_cluster_module_refuses_a_program_that_counts_a_port_twice(
        monkeypatch):
    """The parent's definition (a group's ports from `shared_networks`
    and again from `shared_ports`) put back: the probe names it, before
    an agent would start."""
    from nomad_tpu.structs.alloc import AllocatedResources

    def twice(self):
        out = [p.value for n in self.shared_networks
               for p in n.reserved_ports + n.dynamic_ports]
        return tuple(out + [p.value for p in self.shared_ports])

    cl = ports_cluster.Cluster(harness.load_config("ports-10k"), 1, 64)
    cl.refuse_a_program_that_cannot_run_this()          # this tree: runs
    monkeypatch.setattr(AllocatedResources, "ports", twice)
    with pytest.raises(harness.Refused, match="counts a group-level port"):
        cl.refuse_a_program_that_cannot_run_this()


@time_limit(60)
def test_the_preload_holds_its_ports_without_collision():
    cfg = harness.load_config("ports-10k")
    cl = ports_cluster.Cluster(cfg, 2147483659, 512)
    seen = [set(cl.reserved) for _ in range(cl.n)]
    for row, ports in zip(cl.pre_node, cl.pre_ports):
        for _label, value, static in ports:
            assert value not in seen[row]
            seen[row].add(value)
            assert static or cl.lo[row] <= value <= cl.hi[row]
    assert seen == cl.held
    assert cl.narrow.sum() == 51
    assert (cl.dyn_free0[cl.narrow] >= cfg["ports"]["narrowed"]["keep_free"]
            ).all()
    free = [sum(1 for p in range(cl.lo[r], cl.hi[r] + 1)
                if p not in cl.held[r]) for r in range(cl.n)]
    assert free == cl.dyn_free0.tolist()


@pytest.mark.parametrize("shape", ["web", "edge"])
@time_limit(120)
def test_compile_group_reads_the_counts_a_recount_gives(shape):
    """The cell's cluster at 512 nodes and its two shapes: the place_cap
    and the feasible mask `compile_group` gets from the matrix's kept
    column are those the recount from `port_words` gives, and before any
    plan those of the cluster's own free counts; again after a job of
    the cell has committed its plan."""
    import types
    from benchmark import traffic
    from benchmark.ports import jobs as ports_jobs
    from nomad_tpu.scheduler.stack import DenseStack

    cl = ports_cluster.Cluster(harness.load_config("ports-10k"), 7, 512)
    h = Harness()
    cl.install(types.SimpleNamespace(server=types.SimpleNamespace(
        store=h.store, next_index=h.next_index)))
    cm = h.store.matrix
    rows = np.array([cm.row_of[i] for i in cl.node_ids])
    job = ports_jobs.build(traffic.load("web-services")["shapes"][shape],
                           f"{shape}-5")
    tg = job.task_groups[0]
    static = [p.value for p in tg.networks[0].reserved_ports]
    dyn = len(tg.networks[0].dynamic_ports)
    assert (len(static), dyn) == {"web": (0, 2), "edge": (1, 1)}[shape]

    def check(free):
        got = DenseStack(cm).compile_group(job, tg)
        want_cap = free // dyn
        want_ok = want_cap > 0
        if static:
            want_cap = np.minimum(want_cap, 1)
            want_ok &= cm.static_ports_free(static)
        assert np.array_equal(got.place_cap, want_cap)
        assert np.array_equal(got.feasible,
                              got.feasible_pre_ports & want_ok)
        return got

    before = cm._recount_free_dynamic_ports()
    assert np.array_equal(before[rows], cl.dyn_free0)
    got = check(before)
    narrowed = rows[cl.narrow]
    assert np.array_equal(got.place_cap[narrowed],
                          np.minimum(cl.dyn_free0[cl.narrow], 1) if static
                          else cl.dyn_free0[cl.narrow] // 2)
    assert got.place_cap[rows[~cl.narrow]].min() >= (1 if static else 5000)

    allocs = _process(h, job)
    assert len(allocs) == 96 and not h.results[0].rejected_nodes
    after = cm._recount_free_dynamic_ports()
    assert before.sum() - after.sum() == 96 * dyn
    assert np.array_equal(cm.free_dynamic_ports(), after)
    check(after)


@time_limit(60)
def test_the_reference_imports_nothing_of_the_program():
    code = ("import sys; import benchmark.ports.reference, "
            "benchmark.ports.cluster; "
            "bad = [m for m in sys.modules if m.startswith('nomad_tpu')]; "
            "sys.exit(1 if bad else 0)")
    import os
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert subprocess.run([sys.executable, "-c", code], cwd=root).returncode \
        == 0
