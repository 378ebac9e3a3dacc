"""Preemption tests (reference analog: scheduler/preemption_test.go)."""
import numpy as np
import pytest

from nomad_tpu import mock
from nomad_tpu.ops.preempt import (
    net_priority,
    preempt_for_task_group,
    preemption_score,
)
from nomad_tpu.scheduler import preemption
from nomad_tpu.scheduler.testing import Harness
from nomad_tpu.structs.resources import NetworkPort
from nomad_tpu.telemetry import global_metrics
from nomad_tpu.structs import AllocDesiredStatus
from nomad_tpu.structs.config import PreemptionConfig, SchedulerConfiguration


def test_kernel_picks_lowest_priority_first():
    # one node, 3 candidates: prio 20 (big), prio 10 (small), prio 40
    cand_res = np.array([[[2000, 2000, 0], [1000, 1000, 0], [3000, 3000, 0]]],
                        np.float32)
    cand_prio = np.array([[20, 10, 40]], np.int32)
    cand_valid = np.ones((1, 3), bool)
    remaining = np.array([[0, 0, 0]], np.float32)
    ask = np.array([800, 800, 0], np.float32)
    met, picked, avail = preempt_for_task_group(
        cand_res, cand_prio, cand_valid, remaining, ask, max_steps=4)
    assert bool(met[0])
    assert picked[0].tolist() == [False, True, False]   # prio 10 suffices


def test_kernel_spans_priority_tiers_when_needed():
    cand_res = np.array([[[500, 500, 0], [600, 600, 0]]], np.float32)
    cand_prio = np.array([[10, 20]], np.int32)
    cand_valid = np.ones((1, 2), bool)
    remaining = np.array([[0, 0, 0]], np.float32)
    ask = np.array([1000, 1000, 0], np.float32)
    met, picked, _ = preempt_for_task_group(
        cand_res, cand_prio, cand_valid, remaining, ask, max_steps=4)
    assert bool(met[0]) and picked[0].all()


def test_kernel_unmet_when_insufficient():
    cand_res = np.array([[[100, 100, 0]]], np.float32)
    cand_prio = np.array([[10]], np.int32)
    cand_valid = np.ones((1, 1), bool)
    remaining = np.array([[0, 0, 0]], np.float32)
    ask = np.array([1000, 1000, 0], np.float32)
    met, _, _ = preempt_for_task_group(
        cand_res, cand_prio, cand_valid, remaining, ask, max_steps=2)
    assert not bool(met[0])


def test_preemption_score_logistic():
    assert preemption_score(2048.0) == 0.5
    assert preemption_score(0.0) > 0.99
    assert preemption_score(10000.0) < 0.01


def _enable_service_preemption(h):
    cfg = SchedulerConfiguration(
        preemption_config=PreemptionConfig(service_scheduler_enabled=True,
                                           system_scheduler_enabled=True))
    h.store.set_scheduler_config(h.next_index(), cfg)


def test_service_scheduler_preempts_lower_priority():
    h = Harness()
    _enable_service_preemption(h)
    node = mock.node()
    h.store.upsert_node(h.next_index(), node)

    low = mock.job(priority=20)
    low.task_groups[0].tasks[0].resources.cpu = 3500
    low.task_groups[0].count = 1
    h.store.upsert_job(h.next_index(), low)
    h.process("service", mock.eval(job_id=low.id, priority=20))
    assert len(h.store.allocs_by_job("default", low.id)) == 1

    high = mock.job(priority=70)
    high.task_groups[0].tasks[0].resources.cpu = 3500
    high.task_groups[0].count = 1
    h.store.upsert_job(h.next_index(), high)
    h.process("service", mock.eval(job_id=high.id, priority=70))

    high_allocs = [a for a in h.store.allocs_by_job("default", high.id)
                   if a.desired_status == AllocDesiredStatus.RUN]
    assert len(high_allocs) == 1
    low_allocs = h.store.allocs_by_job("default", low.id)
    assert low_allocs[0].desired_status == AllocDesiredStatus.EVICT
    assert low_allocs[0].preempted_by_allocation == high_allocs[0].id
    assert high_allocs[0].preempted_allocations == [low_allocs[0].id]


def test_no_preemption_within_priority_delta():
    h = Harness()
    _enable_service_preemption(h)
    node = mock.node()
    h.store.upsert_node(h.next_index(), node)
    low = mock.job(priority=50)
    low.task_groups[0].tasks[0].resources.cpu = 3500
    h.store.upsert_job(h.next_index(), low)
    h.process("service", mock.eval(job_id=low.id))

    close = mock.job(priority=55)      # delta < 10: not preemptible
    close.task_groups[0].tasks[0].resources.cpu = 3500
    close.task_groups[0].count = 1
    h.store.upsert_job(h.next_index(), close)
    h.process("service", mock.eval(job_id=close.id, priority=55))
    assert len([a for a in h.store.allocs_by_job("default", close.id)
                if a.desired_status == AllocDesiredStatus.RUN]) == 0
    assert h.store.allocs_by_job("default", low.id)[0].desired_status == \
        AllocDesiredStatus.RUN


def test_system_job_preempts_by_default():
    h = Harness()   # default config: system preemption enabled
    node = mock.node()
    h.store.upsert_node(h.next_index(), node)
    svc = mock.job(priority=50)
    svc.task_groups[0].tasks[0].resources.cpu = 3500
    h.store.upsert_job(h.next_index(), svc)
    h.process("service", mock.eval(job_id=svc.id))

    sysj = mock.system_job()           # priority 100
    sysj.task_groups[0].tasks[0].resources.cpu = 1000
    h.store.upsert_job(h.next_index(), sysj)
    h.process("system", mock.eval(job_id=sysj.id, type="system", priority=100))
    placed = [a for a in h.store.allocs_by_job("default", sysj.id)
              if a.desired_status == AllocDesiredStatus.RUN]
    assert len(placed) == 1
    assert h.store.allocs_by_job("default", svc.id)[0].desired_status == \
        AllocDesiredStatus.EVICT


def test_superset_filter_minimizes_evictions():
    """Placing a small ask on a node with several low-prio allocs should
    evict as few as possible."""
    h = Harness()
    _enable_service_preemption(h)
    node = mock.node()
    h.store.upsert_node(h.next_index(), node)
    low = mock.job(priority=20)
    low.task_groups[0].tasks[0].resources.cpu = 1300
    low.task_groups[0].tasks[0].resources.memory_mb = 2000
    low.task_groups[0].count = 3
    h.store.upsert_job(h.next_index(), low)
    h.process("service", mock.eval(job_id=low.id, priority=20))
    assert len(h.store.allocs_by_job("default", low.id)) == 3

    high = mock.job(priority=70)
    high.task_groups[0].tasks[0].resources.cpu = 1000
    high.task_groups[0].tasks[0].resources.memory_mb = 1500
    high.task_groups[0].count = 1
    h.store.upsert_job(h.next_index(), high)
    h.process("service", mock.eval(job_id=high.id, priority=70))
    evicted = [a for a in h.store.allocs_by_job("default", low.id)
               if a.desired_status == AllocDesiredStatus.EVICT]
    assert len(evicted) == 1           # one eviction covers the ask


# ---------------- the served search against the scan and the plain loop
#
# The host search leaves out work that changes nothing (passes in which
# no row picks, rows that cannot answer, records nobody asked for): what
# it returns is held, array for array and float for float, to the jitted
# scan at its full trip count and to the loop that builds every record.

TIERS = (20, 35, 45)          # 45 may not go for a priority-50 ask
WIDTHS = (0, 1, 5, 8)         # candidates a node, so A = 8
PORT = 8080


def _random_world(seed, n_nodes=240):
    """Nodes of 8 to 11 fillers (no two of a node one size) of which 0, 1,
    5 or 8 may go (tiers 20, 35) and the rest may not (45); every fifth
    node has a filler on PORT, one in three of those a tier-45 one.
    -> (harness, rows of the nodes)"""
    rng = np.random.default_rng([seed, 0x5EA])
    h = Harness()
    jobs = {p: mock.job(priority=p) for p in TIERS}
    for j in jobs.values():
        h.store.upsert_job(h.next_index(), j)
    nodes, allocs = [], []
    for i in range(n_nodes):
        node = mock.node()
        h.store.upsert_node(h.next_index(), node)
        nodes.append(node)
        prios = [int(p) for p in rng.choice(TIERS[:2], WIDTHS[i % 4])]
        prios = [int(p) for p in rng.permutation(
            prios + [45] * (int(rng.integers(8, 12)) - len(prios)))]
        holder = None
        if i % 5 == 0:
            holder = int(rng.integers(0, len(prios)))
            if i % 15 == 0 and 45 in prios:
                holder = prios.index(45)
        for k, p in enumerate(prios):
            a = mock.alloc_for(jobs[p], node.id, index=len(allocs))
            (task,) = a.allocated_resources.tasks.values()
            task.cpu_shares = 150 + 23 * k + int(rng.integers(0, 20))
            task.memory_mb = 260 + 41 * k + int(rng.integers(0, 30))
            a.allocated_resources.shared_disk_mb = 0
            if k == holder:
                a.allocated_resources.shared_ports = [
                    NetworkPort(label="http", value=PORT)]
            allocs.append(a)
    h.store.upsert_allocs(h.next_index(), allocs)
    cm = h.store.matrix
    return h, np.array([cm.row_of[n.id] for n in nodes])


def _ask(h, rows, evictions):
    """What the median node has free and about `evictions` fillers."""
    cm = h.store.matrix
    free = np.median((cm.capacity - cm.used)[rows], axis=0)
    demand = np.zeros(cm.capacity.shape[1], np.float32)
    demand[:2] = free[:2] + (evictions - 0.4) * np.array([240., 420.])
    return demand


def _search_of(h, rows, seed):
    """A built Preemptor with a tenth of its candidates invalidated, and
    a feasibility mask that leaves out a fifth of the nodes."""
    rng = np.random.default_rng([seed, 0xFEA5])
    search = preemption.Preemptor(h.store.snapshot(), 50)
    search._build()
    ids = sorted(search._cand_index, key=search._cand_index.get)
    search.invalidate({ids[i] for i in rng.choice(
        len(ids), len(ids) // 10, replace=False)})
    feasible = np.zeros(h.store.matrix.n_rows, bool)
    feasible[rows] = rng.random(len(rows)) < 0.8
    return search, feasible


def _by_the_loop(search, feasible, demand, used, static_ports=None,
                 feasible_pre_ports=None, device_blocked=None):
    """Every met row's record, best first: the scan at its full trip
    count over every row, then one record a row and a sort of them all
    (the search as it stood before it followed the ask)."""
    cm = search.cm
    remaining = cm.capacity - used
    feasible = feasible.copy()
    forced = {}
    if static_ports:
        forced = search._port_forced_evictions(
            static_ports, np.flatnonzero(feasible_pre_ports & ~feasible))
    in_forced = np.isin(np.arange(len(feasible)), list(forced))
    feasible |= in_forced
    dev_rows = np.zeros(len(feasible), bool)
    if device_blocked is not None:
        dev_rows = device_blocked & ~feasible
        feasible |= dev_rows
    met, picked, _ = preempt_for_task_group(
        search.cand_res, search.cand_prio, search.cand_valid,
        remaining.astype(np.float32), demand.astype(np.float32),
        max_steps=search.cand_valid.shape[1])
    met, picked = np.array(met) & feasible, np.array(picked)
    fits_plain = np.all(remaining >= demand, axis=-1)
    met &= ~(fits_plain & ~in_forced & ~dev_rows)
    met |= (in_forced | dev_rows) & fits_plain
    for row, holders in forced.items():
        picked[row, list(holders)] = True
        freed = search.cand_res[row][picked[row]].sum(axis=0)
        met[row] = bool(np.all(remaining[row] + freed >= demand))
    rows = np.flatnonzero(met)
    picked = search._superset_filter(picked, rows, remaining, demand, forced)
    freed_all = (search.cand_res * picked[:, :, None]).sum(axis=1)
    fit_all = preemption._score_fit_np(
        cm.capacity, used - freed_all + demand[None, :]) / 18.0
    ranked = []
    for row in rows:
        evicted = [search.cand_allocs[row][i]
                   for i in np.flatnonzero(picked[row])]
        p_score = preemption_score(net_priority(
            [a.job.priority for a in evicted]))
        fit = float(fit_all[row])
        ranked.append(preemption.Eviction(
            int(row), evicted, (fit + p_score) / 2.0, fit, p_score))
    if not ranked:
        return []
    best = max(ranked, key=lambda e: e.score)
    ranked.sort(key=lambda e: (e.score, e.row), reverse=True)
    return [best] + [e for e in ranked if e is not best and e.evicted]


def _plain(found):
    return [(f.row, [a.id for a in f.evicted], f.score, f.binpack,
             f.preemption) for f in found]


@pytest.mark.parametrize("seed", [3, 7, 2147483659])
@pytest.mark.parametrize("evictions", [0, 1, 2, 4])
def test_greedy_passes_against_the_jitted_scan(seed, evictions):
    """Same `met`, same `picked` on every feasible row as the scan run
    with the full `max_steps` over every row."""
    h, rows = _random_world(seed)
    search, feasible = _search_of(h, rows, seed)
    cm = h.store.matrix
    remaining = (cm.capacity - cm.used).astype(np.float32)
    demand = _ask(h, rows, evictions)
    met, picked = search._greedy(feasible, remaining, demand)
    want_met, want_picked, _ = preempt_for_task_group(
        search.cand_res, search.cand_prio, search.cand_valid, remaining,
        demand, max_steps=search.cand_valid.shape[1])
    assert search.cand_valid.shape[1] == max(WIDTHS)
    assert (met[feasible] == np.asarray(want_met)[feasible]).all()
    assert (picked[feasible] == np.asarray(want_picked)[feasible]).all()
    counts = picked[feasible & met].sum(axis=1)
    assert (counts == evictions).any() and len(set(counts)) > 1, counts


@pytest.mark.parametrize("seed", [3, 7, 2147483659])
@pytest.mark.parametrize("count", [1, 8, 64, 10**6])
def test_find_many_returns_the_first_of_what_the_loop_ranks(seed, count):
    """`count` records, and they are the loop's first `count`: same rows,
    same evicted ids in the same order, `==` on every score."""
    h, rows = _random_world(seed)
    search, feasible = _search_of(h, rows, seed)
    demand, used = _ask(h, rows, 2), h.store.matrix.used.copy()
    want = _plain(_by_the_loop(search, feasible, demand, used))
    assert len(want) > 64
    got = _plain(search.find_many(feasible, demand, used, count))
    assert got == want[:count]


@pytest.mark.parametrize("rule", ["static_ports", "device_blocked"])
def test_find_many_with_rows_the_rules_add_back(rule):
    """A port's holder is forced out on the rows the port filter took
    away (not where a tier that may not go holds it); a device-blocked
    row is a target with whatever the ask needs."""
    h, rows = _random_world(11)
    search, pre = _search_of(h, rows, 11)
    cm = h.store.matrix
    demand, used = _ask(h, rows, 0.5), cm.used.copy()   # half the rows fit
    held = ((cm.port_words[:, PORT >> 5] >> np.uint32(PORT & 31)) & 1) > 0
    assert held[rows].sum() >= 8
    kw = (dict(static_ports=[PORT], feasible_pre_ports=pre)
          if rule == "static_ports" else dict(device_blocked=pre & held))
    feasible = pre & ~held
    want = _by_the_loop(search, feasible, demand, used, **kw)
    added = [f for f in want if held[f.row]]
    assert len(added) >= 4 and len(added) < len(want)
    if rule == "static_ports":
        assert len(added) < (pre & held).sum(), "a tier-45 holder went"
        for f in added:
            assert any(PORT in a.ports() for a in f.evicted)
    for count in (1, 8, 10**6):
        got = search.find_many(feasible, demand, used, count, **kw)
        assert _plain(got) == _plain(want)[:count]


def test_a_single_feasible_row_is_searched_alone(monkeypatch):
    """A one-row ask (a rack of one, a retry of one slot; the system
    scheduler's shape until it asked once a group).  Its passes see that
    row and no other, and give what the whole cluster's search gives for
    it."""
    h, rows = _random_world(5)
    search, feasible = _search_of(h, rows, 5)
    demand, used = _ask(h, rows, 2), h.store.matrix.used.copy()
    every = {f.row: f for f in _by_the_loop(search, feasible, demand, used)}
    seen = []
    real = preemption.preempt_for_task_group_np

    def spy(cand_res, *rest, **kw):
        seen.append(cand_res.shape[0])
        return real(cand_res, *rest, **kw)
    monkeypatch.setattr(preemption, "preempt_for_task_group_np", spy)
    for row in np.flatnonzero(feasible)[:24]:
        alone = np.zeros_like(feasible)
        alone[row] = True
        got = search.find(alone, demand, used)
        want = every.get(int(row))
        assert (got is None) == (want is None)
        if got is not None:
            assert _plain([got]) == _plain([want])
    assert seen and set(seen) == {1}


def _sample_counts():
    return {s["Name"]: s["count"]
            for s in global_metrics.snapshot().get("Samples", ())}


def test_a_search_counts_its_passes():
    """An ask that two evictions meet: two passes pick, a third may start
    and finds no row; one `sched.preempt_search` in the eval's one
    `sched.preempt_find`."""
    h = Harness()
    _enable_service_preemption(h)
    node = mock.node()
    h.store.upsert_node(h.next_index(), node)
    low = mock.job(priority=20)
    low.task_groups[0].tasks[0].resources.cpu = 1300
    low.task_groups[0].count = 3
    h.store.upsert_job(h.next_index(), low)
    h.process("service", mock.eval(job_id=low.id, priority=20))
    assert len(h.store.allocs_by_job("default", low.id)) == 3

    before = _sample_counts()
    high = mock.job(priority=70)
    high.task_groups[0].tasks[0].resources.cpu = 2500
    high.task_groups[0].count = 1
    h.store.upsert_job(h.next_index(), high)
    h.process("service", mock.eval(job_id=high.id, priority=70))
    assert len([a for a in h.store.allocs_by_job("default", low.id)
                if a.desired_status == AllocDesiredStatus.EVICT]) == 2
    after = _sample_counts()
    moved = {name: after.get(f"nomad.sched.{name}", 0)
             - before.get(f"nomad.sched.{name}", 0)
             for name in ("preempt_find", "preempt_search", "preempt_pass")}
    assert moved["preempt_find"] == 1 and moved["preempt_search"] == 1
    assert moved["preempt_pass"] in (2, 3), moved
    # the search is a child of the find: the find has self time to report
    assert after.get("nomad.self.sched.preempt_find", 0) \
        == before.get("nomad.self.sched.preempt_find", 0) + 1


def test_a_preempting_plan_leaves_three_servers_equal_by_value():
    """The plan's entry for an evicted allocation shares its parts with
    the leader's stored record; the log carries an encoding of it, so
    every server's objects are its own and all read alike."""
    import time

    from nomad_tpu.core.cluster import Cluster

    def wait(cond, timeout=30.0):
        deadline = time.time() + timeout
        while time.time() < deadline and not cond():
            time.sleep(0.05)
        return cond()

    c = Cluster(n=3)
    c.start()
    try:
        leader = c.leader(10.0)
        for _ in range(2):
            leader.register_node(mock.node())
        svc = mock.job(priority=50)
        svc.task_groups[0].tasks[0].resources.cpu = 3500
        svc.task_groups[0].count = 2
        leader.endpoints.handle("Job.Register", {"job": svc})
        assert wait(lambda: len(
            leader.store.allocs_by_job("default", svc.id)) == 2)
        sysj = mock.system_job()            # priority 100: preempts
        sysj.task_groups[0].tasks[0].resources.cpu = 1000
        leader.endpoints.handle("Job.Register", {"job": sysj})

        def evicted(store):
            return [a for a in store.allocs_by_job("default", svc.id)
                    if a.desired_status == AllocDesiredStatus.EVICT]

        assert wait(lambda: len(evicted(leader.store)) == 2)
        assert wait(lambda: all(
            len(evicted(s.store)) == 2 and
            len(s.store.allocs_by_job("default", sysj.id)) == 2
            for s in c.servers))
        # later entries (the follow-up evals) touch none of these records
        assert c.wait_replication(leader.store.latest_index)
        want = {a.id: a for a in leader.store.allocs()
                if a.job_id in (svc.id, sysj.id)}
        placed = {a.id: a for a in want.values() if a.job_id == sysj.id}
        assert len(want) == 4 and len(placed) == 2
        for a in evicted(leader.store):
            assert a.preempted_by_allocation in placed
            assert placed[a.preempted_by_allocation].preempted_allocations \
                == [a.id]
            assert a.job is not None and a.job.id == svc.id   # restored
        for s in c.followers():
            got = {a.id: a for a in s.store.allocs() if a.id in want}
            assert got == want
            assert all(got[i] is not want[i] and
                       got[i].metrics is not want[i].metrics and
                       got[i].allocated_resources is not
                       want[i].allocated_resources for i in want)
    finally:
        c.stop()
