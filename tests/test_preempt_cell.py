"""Preemption as the cell `preempt-10k.tiers` measures it: the program's
search (`scheduler/preemption.py` `Preemptor.find_many`) against the
plain reference of the `preempt-10k` configuration
(benchmark/preempt/reference.py, which imports nothing of the program) on
seeded random worlds; what a preempting placement reports and what the
spans and the applier count; the repair the cell forced (an eviction goes
with its placement) and the wake-up it left alone (an evicted allocation
wakes its node's class, as any terminal one does); and whole runs of the
cell on the CPU, which are not `correct` once the program drops the
priority delta or takes the highest tier first.
"""
import functools
import signal
import time

import numpy as np
import pytest

from nomad_tpu import mock
from nomad_tpu.core.plan_apply import PlanApplier
from nomad_tpu.scheduler import preemption
from nomad_tpu.scheduler.testing import Harness
from nomad_tpu.state import StateStore
from nomad_tpu.structs import AllocDesiredStatus
from nomad_tpu.structs.config import PreemptionConfig, SchedulerConfiguration
from nomad_tpu.structs.plan import Plan
from nomad_tpu.telemetry import global_metrics

from benchmark import harness
from benchmark.preempt import reference as ref

CELL = "preempt-10k.tiers"
CPU, MEM = 0, 1           # columns of the matrix the reference reads


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


def _enable_service_preemption(h):
    h.store.set_scheduler_config(h.next_index(), SchedulerConfiguration(
        preemption_config=PreemptionConfig(service_scheduler_enabled=True)))


def _filler(job, node_id, index, cpu, mem):
    a = mock.alloc_for(job, node_id, index=index)
    (task,) = a.allocated_resources.tasks.values()
    task.cpu_shares, task.memory_mb = int(cpu), int(mem)
    a.allocated_resources.shared_disk_mb = 0
    return a


# ------------------------------- the search against the plain reference

TIERS = (20, 35, 45, 60)      # 45 and 60 may not go for a priority-50 ask
WIDTH = 6                     # fillers a node, at most


def _world(seed, n_nodes):
    """Seeded random nodes full of fillers of random tiers and sizes.
    Returns (harness, rows, free f64[n, 2], res [n, WIDTH, 2], prio
    [n, WIDTH], alive [n, WIDTH])."""
    rng = np.random.default_rng([seed, 0x9EE])
    h = Harness()
    jobs = {}
    for p in TIERS:
        jobs[p] = mock.job(priority=p)
        h.store.upsert_job(h.next_index(), jobs[p])
    res = np.zeros((n_nodes, WIDTH, 2))
    prio = np.zeros((n_nodes, WIDTH), np.int64)
    alive = np.zeros((n_nodes, WIDTH), bool)
    nodes, allocs = [], []
    for i in range(n_nodes):
        node = mock.node()
        h.store.upsert_node(h.next_index(), node)
        nodes.append(node)
        for k in range(int(rng.integers(2, WIDTH + 1))):
            p = int(rng.choice(TIERS, p=[0.35, 0.35, 0.2, 0.1]))
            # sizes that no two fillers of a node share: no tie to break
            cpu = 300 + 37 * k + int(rng.integers(0, 30))
            mem = 400 + 53 * k + int(rng.integers(0, 40))
            res[i, k], prio[i, k], alive[i, k] = (cpu, mem), p, True
            allocs.append(_filler(jobs[p], node.id, len(allocs), cpu, mem))
    h.store.upsert_allocs(h.next_index(), allocs)
    cm = h.store.matrix
    rows = np.array([cm.row_of[n.id] for n in nodes])
    cap = cm.capacity[rows][:, (CPU, MEM)].astype(np.float64)
    used = cm.used[rows][:, (CPU, MEM)].astype(np.float64)
    return h, rows, cap, used, res, prio, alive


@pytest.mark.parametrize("seed", [3, 7, 11, 2147483659])
@pytest.mark.parametrize("evictions", [1, 2, 3])
@time_limit(120)
def test_find_many_against_the_plain_reference(seed, evictions):
    """Same rows met, same evicted sets, scores within 1e-6, for asks
    that need about 1, 2 and 3 evictions on nodes that hold a tier
    (45, 60) that may not go."""
    n = 96
    h, rows, cap, used, res, prio, alive = _world(seed, n)
    cm = h.store.matrix
    free = cap - used
    # what is free plus about `evictions` mean fillers, on the median node
    ask = np.median(free, axis=0) + (evictions - 0.4) * np.array([390., 530.])
    demand = np.zeros(cm.capacity.shape[1], np.float32)
    demand[[CPU, MEM]] = ask
    want_met, want_picked = ref.search(free, ask, res, prio, alive, 50)
    assert want_met.sum() >= n // 8, "the world is too easy or too hard"
    counts = want_picked.sum(axis=1)[want_met]
    assert counts.min() >= 1 and counts.max() >= evictions

    search = preemption.Preemptor(h.store.snapshot(), 50)
    feasible = np.zeros(cm.n_rows, bool)
    feasible[rows] = True
    found = search.find_many(feasible, demand, cm.used.copy(), n)
    # best first, by the score each is reported with
    assert [f.score for f in found] == sorted((f.score for f in found),
                                              reverse=True)
    got = {f.row: f for f in found}
    at = {int(r): i for i, r in enumerate(rows)}
    assert {at[r] for r in got} == set(np.flatnonzero(want_met)), \
        "rows met differ"
    for r, f in got.items():
        i, evicted = at[r], f.evicted
        mine = sorted((a.job.priority,
                       a.allocated_resources.tasks["web"].cpu_shares,
                       a.allocated_resources.tasks["web"].memory_mb)
                      for a in evicted)
        plain = sorted((int(prio[i, k]), int(res[i, k, 0]), int(res[i, k, 1]))
                       for k in np.flatnonzero(want_picked[i]))
        assert mine == plain, f"node {i}: evicted sets differ"
        assert all(p <= 40 for p, _c, _m in mine), "a tier that may not go"
        after = used[i] - res[i][want_picked[i]].sum(axis=0) + ask
        norm, fit, pre = ref.score(cap[i], after, prio[i][want_picked[i]])
        assert abs(f.score - norm) <= 1e-6
        assert abs(f.binpack - fit) <= 1e-6
        assert abs(f.preemption - pre) <= 1e-6


# ------------------------------- what the program reports and counts

@time_limit(120)
def test_a_preempting_placement_reports_its_score_and_is_counted():
    h = Harness()
    _enable_service_preemption(h)
    node = mock.node()
    h.store.upsert_node(h.next_index(), node)
    low = mock.job(priority=20)
    h.store.upsert_job(h.next_index(), low)
    cap = float(node.node_resources.cpu.cpu_shares)
    fillers = [_filler(low, node.id, k, cap * 0.45, 900) for k in range(2)]
    h.store.upsert_allocs(h.next_index(), fillers)

    before = {s["Name"]: s["count"] for s in
              global_metrics.snapshot().get("Samples", ())}
    high = mock.job(priority=50)
    high.task_groups[0].count = 1
    high.task_groups[0].tasks[0].resources.cpu = int(cap * 0.5)
    h.store.upsert_job(h.next_index(), high)
    h.process("service", mock.eval(job_id=high.id, priority=50))

    (placed,) = [a for a in h.store.allocs_by_job("default", high.id)
                 if a.desired_status == AllocDesiredStatus.RUN]
    evicted = [a for a in fillers if h.store.alloc_by_id(a.id)
               .desired_status == AllocDesiredStatus.EVICT]
    assert len(evicted) == 1
    assert h.store.alloc_by_id(evicted[0].id).preempted_by_allocation \
        == placed.id
    (meta,) = [m for m in placed.metrics.score_meta
               if m["node_id"] == node.id]
    assert set(meta["scores"]) == {"binpack", "preemption"}
    assert abs(meta["norm_score"] - (meta["scores"]["binpack"]
                                     + meta["scores"]["preemption"]) / 2) \
        <= 1e-6
    assert abs(meta["scores"]["preemption"]
               - float(ref.logistic(ref.net_priority([20])))) <= 1e-6
    assert h.applier.stats["preempted"] == 1
    assert h.applier.stats["placed"] == 1
    after = {s["Name"]: s["count"] for s in
             global_metrics.snapshot().get("Samples", ())}
    # one eviction meets the ask: one pass picks, no second one starts
    for name in ("nomad.sched.preempt_find", "nomad.sched.preempt_build",
                 "nomad.sched.preempt_search", "nomad.sched.preempt_pass"):
        assert after.get(name, 0) == before.get(name, 0) + 1, name


# ------------------------------- the repair the cell forced, and the wake

@time_limit(60)
def test_an_eviction_is_dropped_with_the_placement_it_was_for():
    """Two plans evict the same filler for a placement each (what two
    overlapping evals do): the second does not fit any more, its node is
    rejected, and its eviction goes with it (of a filler of its own)."""
    store = StateStore()
    node = mock.node()
    store.upsert_node(1, node)
    low, high = mock.job(priority=20), mock.job(priority=50)
    store.upsert_job(2, low)
    store.upsert_job(3, high)
    cap = float(node.node_resources.cpu.cpu_shares)
    shared = _filler(low, node.id, 0, cap * 0.5, 500)
    other = _filler(low, node.id, 1, cap * 0.1, 100)
    store.upsert_allocs(4, [shared, other])
    applier = PlanApplier(store)

    def plan_for(index, evict):
        new = _filler(high, node.id, index, cap * 0.8, 500)
        plan = Plan(eval_id=f"e{index}", job=high)
        for a in evict:
            plan.append_preempted_alloc(a, new.id)
        plan.append_alloc(new, high)
        return plan

    first = applier.apply(plan_for(0, [shared]))
    assert first.rejected_nodes == []
    assert store.alloc_by_id(shared.id).desired_status == \
        AllocDesiredStatus.EVICT
    # scored before the first committed: it still counts on `shared`
    second = applier.apply(plan_for(1, [shared, other]))
    assert second.rejected_nodes == [node.id]
    assert second.node_preemptions == {}
    assert store.alloc_by_id(other.id).desired_status == \
        AllocDesiredStatus.RUN
    assert applier.stats["preempted"] == 1 and applier.stats["placed"] == 1


@time_limit(120)
def test_an_evicted_allocation_wakes_its_nodes_class():
    """The commit of an eviction is where this server unblocks (it
    stands for the client's terminal update a moment later, where the
    upstream does): the room an evicted set leaves beyond its evictor's
    ask is room a blocked eval may fit in."""
    from nomad_tpu.core.server import Server, ServerConfig
    server = Server(ServerConfig(num_schedulers=0))
    woken = []
    server.blocked_evals.unblock = lambda cls, index: woken.append(cls)
    node = mock.node()
    job = mock.job(priority=20)
    store = server.store
    store.upsert_node(1, node)
    store.upsert_job(2, job)
    evicted = _filler(job, node.id, 0, 100, 100)
    store.upsert_allocs(3, [evicted])
    woken.clear()                     # the node's own registration
    evicted = evicted.copy()
    evicted.desired_status = AllocDesiredStatus.EVICT
    evicted.preempted_by_allocation = "the-placement-that-took-its-room"
    store.upsert_allocs(4, [evicted])
    assert woken == [node.computed_class]


# ------------------- the cluster's own list: an eviction is read anywhere

def _listed_world():
    """A small cluster with two jobs placed by the reference itself, and
    the list `readback` would have made of it."""
    cfg = harness.load_config("preempt-10k")
    cl = harness.world_module(cfg, "cluster").Cluster(cfg, 5, 64)
    shape = {"kind": "service", "groups": 1, "count": 4, "cpu": 700,
             "memory_mb": 1500, "priority": 50, "datacenters": ["dc1"]}
    specs = [ref.JobSpec(f"c{k}-svc", "default", shape) for k in range(2)]
    stubs, full, seen = ref.place_reference(cl, specs, "float32")
    gone = {a["id"] for listed in seen["nodes"].values() for a in listed
            if a["desired_status"] == "evict"}
    seen["allocs"] = [(aid, cl.job_ids[cl.pre_job[s]],
                       cl.node_ids[cl.pre_node[s]],
                       "evict" if aid in gone else "run")
                      for s, aid in enumerate(cl.pre_ids)]
    seen["allocs"] += [(a["ID"], a["JobID"], a["NodeID"], "run")
                       for a in stubs]
    return cl, {s.id: s for s in specs}, stubs, full, seen


def _stray_eviction(cl, seen):
    """A filler evicted on a node that holds no placement of the run."""
    unread = next(i for i, (_a, _j, node, _s) in enumerate(seen["allocs"])
                  if node not in seen["nodes"])
    aid, job, node, _ = seen["allocs"][unread]
    seen["allocs"][unread] = (aid, job, node, "evict")


def _stranger(cl, seen):
    """A filler job's allocation the preload did not bring."""
    _aid, job, node, _ = seen["allocs"][0]
    seen["allocs"].append(("not-of-the-preload", job, node, "run"))


def _missing(cl, seen):
    del seen["allocs"][0]


def _moved(cl, seen):
    aid, job, node, st = seen["allocs"][0]
    seen["allocs"][0] = (aid, job, cl.node_ids[-1 if node != cl.node_ids[-1]
                                               else 0], st)


@pytest.mark.parametrize("fault", [None, _stray_eviction, _stranger,
                                   _missing, _moved],
                         ids=["sound", "stray_eviction", "stranger",
                              "missing", "moved"])
def test_the_clusters_list_is_held_to_the_preload(fault):
    cl, specs, stubs, full, seen = _listed_world()
    if fault is not None:
        fault(cl, seen)
    got = ref.compare(cl, specs, stubs, full, set(specs), seen)
    assert got["compared"]["violations"]["value"] == (0 if fault is None
                                                      else 1), got["problems"]
    assert got["correct"] == (fault is None)


# ----------------------------------------- the cell, whole, on the CPU

def _delta_dropped(monkeypatch):
    """Any lower priority may go: tier 45 falls to a priority-50 ask."""
    monkeypatch.setattr(preemption, "PRIORITY_DELTA", 1)


def _highest_first(monkeypatch):
    """Of the tiers that may go, the highest goes first."""
    real = preemption.preempt_for_task_group_np

    def flipped(cand_res, cand_prio, *rest, **kw):
        return real(cand_res, -cand_prio, *rest, **kw)
    monkeypatch.setattr(preemption, "preempt_for_task_group_np", flipped)


@pytest.mark.parametrize("fault", [None, _delta_dropped, _highest_first],
                         ids=["sound", "delta_dropped", "highest_first"])
@time_limit(420)
def test_cell_whole_on_the_cpu(fault, monkeypatch):
    """`preempt-10k.tiers` through Agent, HTTP and ApiClient at 512
    nodes, eight jobs with the warm pass's four: enough for a program
    without the delta to run out of tiers 20 and 35 on the nodes it keeps
    going back to."""
    if fault is not None:
        fault(monkeypatch)
    line = harness.run_cell(CELL, 11, 12.0, False, time.monotonic(),
                            n_nodes=512, require_tpu=False)
    compared = {k: v["value"] for k, v in line["compared"].items()}
    assert line["attempted"] == 4 and line["failed"] == 0, line
    if fault is None:
        assert line["correct"], line
        assert compared == {"violations": 0, "unexplained_jobs_share": 0.0,
                            "misplaced_jobs_share": 0.0}
    else:
        assert not line["correct"], line
        assert compared["violations"] > 0
        assert compared["unexplained_jobs_share"] == 0.0, line
