"""Preemption tests (reference analog: scheduler/preemption_test.go)."""
import numpy as np
import pytest

from nomad_tpu import mock
from nomad_tpu.encode import pad_to_bucket
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
    ids = _valid_ids(search)
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
        evicted = search.snapshot.allocs.rows(
            search.cand_ids[row][k] for k in np.flatnonzero(picked[row]))
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


def _valid_ids(search):
    """The ids of the valid candidates, by row and index."""
    return [search.cand_ids[row][k]
            for row, k in zip(*np.nonzero(search.cand_valid))]


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


# ------------------------------------- the view against the walk it replaced

def _by_the_walk(snapshot, job_priority):
    """The candidates as `Preemptor._build` found them until the matrix
    kept them: every node's allocations read from the snapshot.
    -> {row: {allocation id: (resources, priority)}}, rows with none left
    out"""
    from nomad_tpu.encode.matrixizer import comparable_vec
    out = {}
    for node_id, row in snapshot.matrix.row_of.items():
        for a in snapshot.allocs_by_node(node_id):
            if a.terminal_status():
                continue
            prio = a.job.priority if a.job is not None else 50
            if job_priority - prio < preemption.PRIORITY_DELTA:
                continue
            out.setdefault(row, {})[a.id] = (
                tuple(comparable_vec(a.comparable_resources()).tolist()),
                prio)
    return out


def _of_the_view(search):
    out = {}
    for row, k in zip(*np.nonzero(search.cand_valid)):
        out.setdefault(int(row), {})[search.cand_ids[row][k]] = (
            tuple(search.cand_res[row, k].tolist()),
            int(search.cand_prio[row, k]))
    assert sum(map(len, out.values())) == search.cand_valid.sum()
    return out


@pytest.mark.parametrize("job_priority", [30, 50, 100])
def test_the_view_holds_what_the_walk_of_the_snapshot_found(job_priority):
    """A few hundred nodes with a history behind them (fillers stopped,
    reported failed, replaced, one without a job, a node gone): per row
    the same candidates with the same resources and priorities, and the
    padding the walk would have chosen."""
    h, rows = _random_world(13)
    rng = np.random.default_rng(13)
    cm = h.store.matrix
    fillers = sorted(h.store.snapshot().allocs.values(), key=lambda a: a.name)
    gone = [fillers[i] for i in rng.choice(len(fillers), 300, replace=False)]
    h.store.delete_eval(h.next_index(), [], [a.id for a in gone[:100]])
    failed = [a.copy_shallow() for a in gone[100:200]]
    for a in failed:
        a.client_status = "failed"
    h.store.upsert_allocs(h.next_index(), failed)
    stray = mock.alloc_for(mock.job(priority=20), cm.node_ids[rows[3]])
    stray.job = None                     # no such job: priority 50
    late = [mock.alloc_for(gone[200 + i].job, cm.node_ids[rows[i]])
            for i in range(40)]
    h.store.upsert_allocs(h.next_index(), late + [stray])
    h.store.delete_node(h.next_index(), cm.node_ids[rows[7]])
    assert h.store.snapshot().allocs.get(stray.id).job is None

    snapshot = h.store.snapshot()
    search = preemption.Preemptor(snapshot, job_priority)
    search._build()
    want = _by_the_walk(snapshot, job_priority)
    assert _of_the_view(search) == want
    assert (stray.id in want[rows[3]]) == (job_priority == 100)
    widest = max(map(len, want.values()))
    assert search.cand_valid.shape == (
        cm.n_rows, pad_to_bucket(widest, minimum=4))
    assert search.max_steps == search.cand_valid.shape[1]
    # candidates fill a row from index 0
    assert (search.cand_valid[:, :-1] >= search.cand_valid[:, 1:]).all()


def test_an_allocation_committed_after_the_read_point_is_never_evicted():
    """The matrix is live and the snapshot is a read point: fillers that
    commit after the eval took its snapshot are in the table and not in
    any `Eviction`; a row that could only answer with one is skipped."""
    h, rows = _random_world(21)
    cm = h.store.matrix
    snapshot = h.store.snapshot()
    seen = set(snapshot.allocs.keys())
    # every node gets a large tier-20 filler the snapshot has not seen:
    # the lowest tier and the closest to the ask, so the search wants it
    low = mock.job(priority=20)
    h.store.upsert_job(h.next_index(), low)
    late = []
    for row in rows:
        a = mock.alloc_for(low, cm.node_ids[row], index=len(late))
        (task,) = a.allocated_resources.tasks.values()
        task.cpu_shares, task.memory_mb = 400, 700
        a.allocated_resources.shared_disk_mb = 0
        late.append(a)
    h.store.upsert_allocs(h.next_index(), late)
    before = _counter("nomad.sched.preempt_unseen")
    search = preemption.Preemptor(snapshot, 50)
    feasible = np.zeros(cm.n_rows, bool)
    feasible[rows] = True
    used = cm.used.copy()
    demand = _ask(h, rows, 2)
    in_view = 0
    for _ in range(4):                    # later rounds use what is left
        found = search.find_many(feasible, demand, used, 64)
        in_view += len(found)
        for f in found:
            assert f.evicted and {a.id for a in f.evicted} <= seen
            search.invalidate({a.id for a in f.evicted})
    assert {a.id for a in late} <= {i for ids in search.cand_ids for i in ids}
    assert _counter("nomad.sched.preempt_unseen") > before
    assert in_view > 0, "rows that answer with what the snapshot holds"


def _counter(name):
    return {c["Name"]: c["Count"] for c in
            global_metrics.snapshot()["Counters"]}.get(name, 0)


def test_views_taken_while_a_writer_commits_are_never_torn():
    """A writer commits and stops allocations whose resources spell their
    own name, on slots it keeps reusing, while views are taken: no slot of
    any view pairs one allocation's id with another's resources, and a
    plan's allocations are in a view all together or not at all."""
    import sys
    import threading

    h = Harness()
    job = mock.job(priority=20)
    h.store.upsert_job(h.next_index(), job)
    nodes = [mock.node() for _ in range(24)]
    for n in nodes:
        h.store.upsert_node(h.next_index(), n)
    spelled, batch_of = {}, {}            # id -> resources, id -> its plan
    stop = threading.Event()
    failed = []

    def writer():
        rng = np.random.default_rng(4)
        live = []
        try:
            for plan in range(400):
                batch = []
                for k in range(3):
                    a = mock.alloc_for(job, nodes[rng.integers(24)].id,
                                       index=3 * plan + k)
                    (task,) = a.allocated_resources.tasks.values()
                    task.cpu_shares = 3 * plan + k + 1
                    task.memory_mb = 7 * (3 * plan + k) + 1
                    spelled[a.id] = (task.cpu_shares, task.memory_mb)
                    batch_of[a.id] = plan
                    batch.append(a)
                h.store.upsert_allocs(h.next_index(), batch)
                live.append(batch)
                if len(live) > 6:         # stop an older plan: slots free
                    old = live.pop(int(rng.integers(len(live))))
                    for a in old:
                        a.client_status = "complete"
                    h.store.upsert_allocs(h.next_index(), old)
        except Exception as e:            # pragma: no cover
            failed.append(e)
        finally:
            stop.set()

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    views = 0
    try:
        t = threading.Thread(target=writer)
        t.start()
        while not stop.is_set() or views < 3:
            search = preemption.Preemptor(h.store.snapshot(), 50)
            search._build()
            res = search.cand_res[search.cand_valid]
            plans = {}
            for aid, vec in zip(_valid_ids(search), res.tolist()):
                assert (vec[0], vec[1]) == spelled.get(aid)
                plans[batch_of[aid]] = plans.get(batch_of[aid], 0) + 1
            assert set(plans.values()) <= {3}, plans
            views += 1
        t.join(60)
    finally:
        stop.set()
        sys.setswitchinterval(old)
    assert not failed and not t.is_alive() and views >= 3


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
