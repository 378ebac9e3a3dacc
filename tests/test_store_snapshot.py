"""A snapshot is a read point on the store's versioned tables
(state/table.py), not a copy of them: what it answers is held here to a
plain copy made at the same index, whatever is written later, and what
taking it and writing past it cost is counted, not timed."""
import copy
import gc
import pickle
import random
import sys
import threading

import pytest

from nomad_tpu import mock
from nomad_tpu.raft.fsm import MessageType, NomadFSM
from nomad_tpu.state import StateStore
from nomad_tpu.state.store import AppliedPlanResults
from nomad_tpu.state.table import IndexTable, Table
from nomad_tpu.structs import AllocClientStatus, AllocDesiredStatus
from nomad_tpu.structs.alloc import TaskState
from nomad_tpu.structs.deployment import Deployment, DeploymentStatus
from nomad_tpu.structs.plan import Plan
from nomad_tpu.utils import generate_uuid


class Frozen:
    """What the copying constructor used to make: plain copies of the
    seven tables, taken under the store's lock at the snapshot's index."""

    def __init__(self, store: StateStore):
        with store._lock:
            self.index = store.latest_index
            self.nodes = dict(store._nodes.items())
            self.jobs = dict(store._jobs.items())
            self.evals = dict(store._evals.items())
            self.allocs = dict(store._allocs.items())
            self.deployments = dict(store._deployments.items())
            self.by_job = {k: set(v) for k, v in store._allocs_by_job.items()}
            self.by_node = {k: set(v)
                            for k, v in store._allocs_by_node.items()}


def _same(got, want):
    """Same objects (identity, not equality), order aside."""
    assert sorted(map(id, got)) == sorted(map(id, want))


def hold(snap, frozen: Frozen, universe) -> None:
    """Every read of `snap` answers as `frozen` does; `universe` names
    every key any table has ever had, so later keys are asked for too."""
    assert snap.index == frozen.index
    for name in ("nodes", "jobs", "evals", "allocs", "deployments"):
        view, want = getattr(snap, name), getattr(frozen, name)
        assert len(view) == len(want)
        assert set(view) == set(want) == set(view.keys())
        _same(view.values(), want.values())
        assert {k: id(v) for k, v in view.items()} == \
            {k: id(v) for k, v in want.items()}
        for k in universe[name]:
            assert view.get(k) is want.get(k)
            assert (k in view) == (k in want)
            if k in want:
                assert view[k] is want[k]
            else:
                with pytest.raises(KeyError):
                    view[k]
    for nid in universe["nodes"]:
        assert snap.node_by_id(nid) is frozen.nodes.get(nid)
        want = [frozen.allocs[i] for i in frozen.by_node.get(nid, ())]
        _same(snap.allocs_by_node(nid), want)
        for terminal in (False, True):
            _same(snap.allocs_by_node_terminal(nid, terminal),
                  [a for a in want if a.terminal_status() == terminal])
    for ns, jid in universe["jobs"]:
        assert snap.job_by_id(ns, jid) is frozen.jobs.get((ns, jid))
        _same(snap.allocs_by_job(ns, jid),
              [frozen.allocs[i] for i in frozen.by_job.get((ns, jid), ())])
        mine = [d for d in frozen.deployments.values()
                if (d.namespace, d.job_id) == (ns, jid)]
        latest = snap.latest_deployment_by_job_id(ns, jid)
        if not mine:
            assert latest is None
        else:
            top = max(d.create_index for d in mine)
            assert latest in mine and latest.create_index == top
    for eid in universe["evals"]:
        assert snap.eval_by_id(eid) is frozen.evals.get(eid)
    for did in universe["deployments"]:
        assert snap.deployment_by_id(did) is frozen.deployments.get(did)
    for aid in universe["allocs"]:
        assert snap.allocs.get(aid) is frozen.allocs.get(aid)
    for dcs in (["dc1"], ["dc2"], ["dc1", "dc2"]):
        _same(snap.ready_nodes_in_dcs(dcs),
              [n for n in frozen.nodes.values()
               if n.ready() and n.datacenter in dcs])


class _Keys(set):
    """Every key a table has had, and the order they came in."""

    def __init__(self):
        super().__init__()
        self.order = []

    def add(self, key) -> None:
        if key not in self:
            super().add(key)
            self.order.append(key)


class Churn:
    """A seeded stream of writes over all seven tables."""

    def __init__(self, seed: int):
        self.rng = random.Random(seed)
        self.store = StateStore()
        self.index = 0
        self.universe = {k: _Keys() for k in (
            "nodes", "jobs", "evals", "allocs", "deployments")}
        self.job_specs = {}

    def next_index(self) -> int:
        self.index += 1
        return self.index

    def pick(self, table):
        """A key the table holds now, or None."""
        keys = self.universe[table[1:]].order
        with self.store._lock:
            live = getattr(self.store, table)
            for _ in range(16 if keys else 0):
                k = self.rng.choice(keys)
                if k in live:
                    return k
        return None

    def new_alloc(self):
        jkey, nid = self.pick("_jobs"), self.pick("_nodes")
        if jkey is None or nid is None:
            return None
        a = mock.alloc_for(self.job_specs[jkey], node_id=nid,
                           index=self.rng.randrange(10 ** 6))
        self.universe["allocs"].add(a.id)
        return a

    def stop_of(self, aid):
        a = self.store.alloc_by_id(aid).copy()
        a.desired_status = AllocDesiredStatus.STOP
        a.client_status = AllocClientStatus.COMPLETE
        return a

    # ---- one write each; a return of False means nothing to write on

    def op_upsert_node(self):
        nid = self.pick("_nodes") if self.rng.random() < 0.3 else None
        n = mock.node(datacenter=self.rng.choice(["dc1", "dc2", "dc3"]))
        if nid is not None:
            n.id = nid
        self.universe["nodes"].add(n.id)
        self.store.upsert_node(self.next_index(), n)

    def op_node_state(self):
        nid = self.pick("_nodes")
        if nid is None:
            return False
        idx, r = self.next_index(), self.rng.random()
        if r < 0.4:
            self.store.update_node_status(
                idx, nid, self.rng.choice(["ready", "down"]))
        elif r < 0.7:
            self.store.update_node_eligibility(
                idx, nid, self.rng.choice(["eligible", "ineligible"]))
        else:
            self.store.update_node_statuses_many(idx, [
                {"node_id": nid, "status": "ready", "updated_at": 1.0}])

    def op_delete_node(self):
        nid = self.pick("_nodes")
        if nid is None or len(self.store._nodes) < 6:
            return False
        self.store.delete_node(self.next_index(), nid)

    def op_upsert_job(self):
        key = self.pick("_jobs") if self.rng.random() < 0.4 else None
        j = mock.job()
        if key is not None:
            j.namespace, j.id = key
        self.universe["jobs"].add((j.namespace, j.id))
        self.job_specs[(j.namespace, j.id)] = j
        self.store.upsert_job(self.next_index(), j)

    def op_delete_job(self):
        key = self.pick("_jobs")
        if key is None or len(self.store._jobs) < 4:
            return False
        self.store.delete_job(self.next_index(), *key)

    def op_upsert_evals(self):
        evs = []
        for _ in range(self.rng.randrange(1, 4)):
            eid = self.pick("_evals") if self.rng.random() < 0.3 else None
            e = mock.eval()
            if eid is not None:
                e.id = eid
            evs.append(e)
            self.universe["evals"].add(e.id)
        self.store.upsert_evals(self.next_index(), evs)

    def op_delete_eval(self):
        eid, aid = self.pick("_evals"), self.pick("_allocs")
        if eid is None:
            return False
        self.store.delete_eval(self.next_index(), [eid],
                               [aid] if aid and self.rng.random() < 0.5
                               else [])

    def op_upsert_allocs(self):
        allocs = [a for a in (self.new_alloc() for _ in range(
            self.rng.randrange(1, 6))) if a is not None]
        if not allocs:
            return False
        self.store.upsert_allocs(self.next_index(), allocs)

    def op_client_update(self):
        aid = self.pick("_allocs")
        if aid is None:
            return False
        u = self.store.alloc_by_id(aid).copy()
        u.client_status = self.rng.choice([
            AllocClientStatus.RUNNING, AllocClientStatus.COMPLETE,
            AllocClientStatus.FAILED])
        self.store.update_allocs_from_client(self.next_index(), [u])

    def op_deployment(self):
        did = self.pick("_deployments")
        if did is not None and self.rng.random() < 0.3:
            self.store.delete_deployment(self.next_index(), did)
            return
        jkey = self.pick("_jobs")
        if jkey is None:
            return False
        d = Deployment(namespace=jkey[0], job_id=jkey[1],
                       job_version=self.rng.randrange(3))
        self.universe["deployments"].add(d.id)
        self.store.upsert_deployment(self.next_index(), d)

    def plan(self):
        places = [a for a in (self.new_alloc() for _ in range(
            self.rng.randrange(1, 12))) if a is not None]
        if not places:
            return None
        stops = []
        aid = self.pick("_allocs")
        if aid is not None and self.rng.random() < 0.6:
            stops.append(self.stop_of(aid))
        res = AppliedPlanResults(
            alloc_updates=stops, allocs_to_place=places,
            plan_id=generate_uuid())
        if self.rng.random() < 0.3:
            res.deployment = Deployment(
                namespace=places[0].namespace, job_id=places[0].job_id,
                job_version=self.rng.randrange(100, 10 ** 6))
            self.universe["deployments"].add(res.deployment.id)
        did = self.pick("_deployments")
        if did is not None and self.rng.random() < 0.3:
            res.deployment_updates = [{
                "deployment_id": did, "status": DeploymentStatus.FAILED}]
        return res

    def op_plan(self):
        res = self.plan()
        if res is None:
            return False
        self.store.upsert_plan_results(self.next_index(), res)

    def op_plans_many(self):
        batch = [r for r in (self.plan() for _ in range(3)) if r is not None]
        if not batch:
            return False
        self.store.upsert_plan_results_many(self.next_index(), batch)

    OPS = ("op_upsert_node", "op_upsert_node", "op_node_state",
           "op_delete_node", "op_upsert_job", "op_delete_job",
           "op_upsert_evals", "op_delete_eval", "op_upsert_allocs",
           "op_client_update", "op_deployment", "op_plan", "op_plan",
           "op_plans_many")

    def step(self) -> None:
        while getattr(self, self.rng.choice(self.OPS))() is False:
            pass


@pytest.mark.parametrize("seed", [1, 7, 2147483659])
def test_snapshots_hold_through_every_later_write(seed):
    """Upserts, updates, deletes and plan applies over all seven tables,
    snapshots taken along the way, each held to a plain copy made at its
    index through every read method after every later write."""
    churn = Churn(seed)
    for _ in range(8):
        churn.op_upsert_node()
    for _ in range(4):
        churn.op_upsert_job()
    held = []
    for step in range(260):
        churn.step()
        if step % 9 == 0:
            snap = churn.store.snapshot()
            assert churn.store.snapshot() is snap       # memoized per index
            held.append((snap, Frozen(churn.store)))
            if len(held) > 5:
                held.pop(churn.rng.randrange(len(held)))
        for snap, frozen in held:
            hold(snap, frozen, churn.universe)
    # the run reached every table, and the store itself reads as its
    # newest state does
    assert all(churn.universe[k] for k in churn.universe)
    hold(churn.store.snapshot(), Frozen(churn.store), churn.universe)
    assert churn.store.stats["buckets_copied"] > 0
    assert churn.store.stats["sets_copied"] > 0


def _fingerprint(snap) -> tuple:
    """Everything a snapshot can be asked, as values: ids, the objects'
    identities and the indexes they carried."""
    allocs = sorted((a.id, id(a), a.modify_index, a.client_status)
                    for a in snap.allocs.values())
    by_node = sorted((n.id, tuple(sorted(
        a.id for a in snap.allocs_by_node(n.id))))
        for n in snap.nodes.values())
    by_job = sorted((k, tuple(sorted(a.id for a in snap.allocs_by_job(*k))))
                    for k in snap.jobs)
    return (snap.index, allocs, by_node, by_job,
            sorted((n.id, id(n), n.status) for n in snap.nodes.values()),
            sorted(map(id, snap.evals.values())),
            sorted(map(id, snap.deployments.values())))


def test_snapshot_outlives_ten_thousand_writes():
    churn = Churn(11)
    for _ in range(12):
        churn.op_upsert_node()
    for _ in range(4):
        churn.op_upsert_job()
    for _ in range(40):
        churn.op_plan()
    snap = churn.store.snapshot()
    frozen, before = Frozen(churn.store), _fingerprint(snap)
    start = churn.index
    while churn.index - start < 10_000:
        churn.step()
    assert churn.store.latest_index >= start + 10_000
    assert _fingerprint(snap) == before
    hold(snap, frozen, churn.universe)
    assert churn.store.snapshot().index > snap.index


def test_writer_thread_against_reading_threads():
    """One thread applies plans and client updates as fast as it can;
    more threads than this machine has cores take snapshots, and each
    snapshot has to agree with itself (the indexes with the allocation
    table, nothing from past its index) and to read the same after the
    writer has moved on."""
    import os
    churn = Churn(23)
    for _ in range(16):
        churn.op_upsert_node()
    for _ in range(4):
        churn.op_upsert_job()
    store, stop, errors = churn.store, threading.Event(), []

    def write():
        try:
            while not stop.is_set():
                churn.rng.choice((churn.op_plan, churn.op_client_update,
                                  churn.op_upsert_evals,
                                  churn.op_node_state))()
        except Exception as e:                      # noqa: BLE001
            errors.append(e)
            stop.set()

    def read():
        try:
            seen = 0
            while not stop.is_set() or seen < 2:
                snap = store.snapshot()
                before = _fingerprint(snap)
                ids = set(snap.allocs)
                assert len(ids) == len(snap.allocs)
                for a in snap.allocs.values():
                    assert a.modify_index <= snap.index
                for n in snap.nodes.values():
                    assert n.modify_index <= snap.index
                filed = [a.id for n in snap._allocs_by_node
                         for a in snap.allocs_by_node(n)]
                assert sorted(filed) == sorted(ids)
                filed = [a.id for k in snap._allocs_by_job
                         for a in snap.allocs_by_job(*k)]
                assert sorted(filed) == sorted(ids)
                store.wait_for_index(snap.index + 5, timeout=0.2)
                assert _fingerprint(snap) == before
                seen += 1
        except Exception as e:                      # noqa: BLE001
            errors.append(e)
            stop.set()

    threads = [threading.Thread(target=write)] + [
        threading.Thread(target=read)
        for _ in range((os.cpu_count() or 4) + 2)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for t in threads:
            t.start()
        store.wait_for_index(300, timeout=30.0)
        stop.set()
        for t in threads:
            t.join(60.0)
    finally:
        sys.setswitchinterval(interval)
    assert not errors, errors[0]
    assert not any(t.is_alive() for t in threads)
    assert store.latest_index >= 300


def _fleet(n_nodes: int, per_node: int) -> StateStore:
    """`n_nodes` nodes with `per_node` allocations of one job each."""
    store = StateStore()
    j = mock.job()
    store.upsert_job(1, j)
    nodes = [mock.node() for _ in range(n_nodes)]
    for n in nodes:
        store.upsert_node(2, n)
    store.upsert_allocs(3, [
        mock.alloc_for(j, node_id=n.id, index=i * per_node + k)
        for i, n in enumerate(nodes) for k in range(per_node)])
    return store


def test_one_job_plan_copies_that_jobs_id_set_once():
    """1,200 placements of one job on nodes that hold allocations
    already: the job's id set is copied once, not 1,200 times, and each
    node's once."""
    store = _fleet(100, 2)
    j = store.jobs()[0]
    store.snapshot()
    before = dict(store.stats)
    nodes = store.nodes()
    store.upsert_plan_results(4, AppliedPlanResults(
        allocs_to_place=[
            mock.alloc_for(j, node_id=nodes[i % 100].id, index=1000 + i)
            for i in range(1200)],
        plan_id=generate_uuid()))
    assert len(store.allocs_by_job(j.namespace, j.id)) == 1400
    # 1 id set of the job + 100 of the nodes; a second plan inside the
    # same generation copies none
    assert store.stats["sets_copied"] - before["sets_copied"] == 101
    assert store.stats["roots_copied"] - before["roots_copied"] == 3
    store.upsert_plan_results(5, AppliedPlanResults(
        allocs_to_place=[mock.alloc_for(j, node_id=nodes[0].id, index=5000)],
        plan_id=generate_uuid()))
    assert store.stats["sets_copied"] - before["sets_copied"] == 101


def _system_rollout_reads(n_nodes: int, monkeypatch) -> int:
    """One plan that puts a system job on every node of `_fleet(n, 1)`
    and evicts the node's filler, a snapshot outstanding: how often the
    write read the allocations table by id."""
    store = _fleet(n_nodes, 1)
    sj = mock.system_job()
    store.upsert_job(4, sj)
    evicted = []
    for filler in store.allocs():
        e = filler.copy()
        e.desired_status = AllocDesiredStatus.EVICT
        evicted.append(e)
    placed = [mock.alloc_for(sj, node_id=e.node_id) for e in evicted]
    store.snapshot()
    reads = [0]
    plain_get = Table.get

    def counting_get(table, key, default=None):
        if table is store._allocs:
            reads[0] += 1
        return plain_get(table, key, default)

    with monkeypatch.context() as m:
        m.setattr(Table, "get", counting_get)
        store.upsert_plan_results(5, AppliedPlanResults(
            allocs_to_place=placed, allocs_preempted=evicted,
            plan_id=generate_uuid()))
    assert store.stats["name_guard_drops"] == 0
    assert len(store.allocs_by_job(sj.namespace, sj.id)) == n_nodes
    by_node = store._live_names[(sj.namespace, sj.id, placed[0].name)]
    assert len(by_node) == n_nodes
    assert all(len(ids) == 1 for ids in by_node.values())
    return reads[0]


def test_a_system_jobs_plan_reads_the_allocations_once_a_node(monkeypatch):
    """A system job's allocations all share one name, so the store's
    duplicate-name guard asks "is it held on this node" of every
    placement after the ones just written: that is a lookup in the
    liveness index, not a walk over the name's holders (n squared over
    two reads of the allocations table, 21.5 of the 21.9 s a
    10,000-node plan took).  Counted, not timed: twice the nodes, twice
    the reads."""
    small = _system_rollout_reads(2_000, monkeypatch)
    large = _system_rollout_reads(4_000, monkeypatch)
    assert small <= 4 * 2_000, small    # a few reads a node, none a holder
    assert large <= 2.2 * small, (small, large)


def test_taking_a_snapshot_allocates_nothing_per_node():
    """10,000 nodes, 100,000 allocations: the snapshot copies no piece
    of any table and allocates a handful of objects; the first write
    past it copies three bucket lists, three buckets and two id sets."""
    store = _fleet(10_000, 10)
    assert len(store._allocs) == 100_000
    store.snapshot()                    # whatever the first one sets up
    store.upsert_evals(4, [mock.eval()])
    before = dict(store.stats)
    gc.collect()
    gc.disable()
    try:
        blocks = sys.getallocatedblocks()
        snap = store.snapshot()
        blocks = sys.getallocatedblocks() - blocks
    finally:
        gc.enable()
    assert blocks < 64, blocks          # 10,000 node sets would be 10,000
    after = dict(store.stats)
    assert after.pop("snapshots") == before.pop("snapshots") + 1
    assert after == before              # nothing copied to take it
    assert len(snap.allocs) == 100_000 and len(snap.nodes) == 10_000
    node = store.nodes()[0]
    store.upsert_allocs(5, [mock.alloc_for(store.jobs()[0], node_id=node.id,
                                           index=10 ** 6)])
    copied = {k: store.stats[k] - before[k] for k in before}
    assert copied == {"roots_copied": 3, "buckets_copied": 3,
                      "sets_copied": 2, "name_guard_drops": 0}
    assert len(snap.allocs) == 100_000
    assert len(snap.allocs_by_node(node.id)) == 10
    assert len(store.allocs_by_node(node.id)) == 11


def test_restore_refills_the_tables_and_spares_held_snapshots():
    churn = Churn(5)
    for _ in range(6):
        churn.op_upsert_node()
    for _ in range(3):
        churn.op_upsert_job()
    for _ in range(30):
        churn.step()
    fsm = NomadFSM(churn.store)
    blob = fsm.snapshot()
    want = Frozen(churn.store)
    for _ in range(30):
        churn.step()
    snap, frozen = churn.store.snapshot(), Frozen(churn.store)
    fsm.restore(blob)
    hold(snap, frozen, churn.universe)          # taken before the restore
    got = Frozen(churn.store)
    assert got.index == want.index
    for name in ("nodes", "jobs", "evals", "allocs", "deployments"):
        assert set(getattr(got, name)) == set(getattr(want, name))
    assert got.by_job == want.by_job and got.by_node == want.by_node
    again = NomadFSM(StateStore())
    again.restore(blob)
    from nomad_tpu.state.digest import canon
    assert canon(again.snapshot()) == canon(blob) == canon(fsm.snapshot())


# ------------------ a plan's entries share their parts with the records

def _evicting_plan(store: StateStore):
    """`_fleet(2, 1)` with both fillers running on their clients, and a
    plan that evicts the first node's for a system job's placement and
    stops the second's.  -> (plan, its payload, evicted, stopped, placed)"""
    fillers = sorted(store.allocs(), key=lambda a: a.name)
    running = []
    for f in fillers:
        u = f.copy()
        u.client_status = AllocClientStatus.RUNNING
        u.task_states = {"web": TaskState(state="running", started_at=1.0,
                                          events=[{"type": "Started"}])}
        running.append(u)
    store.update_allocs_from_client(4, running)
    evicted, stopped = (store.alloc_by_id(f.id) for f in fillers)
    sj = mock.system_job()
    store.upsert_job(5, sj)
    placed = mock.alloc_for(sj, node_id=evicted.node_id)
    placed.preempted_allocations = [evicted.id]
    plan = Plan(job=sj)
    plan.append_preempted_alloc(evicted, placed.id)
    plan.append_stopped_alloc(stopped, "alloc not needed due to job update",
                              followup_eval_id="eval-17")
    plan.append_alloc(placed, None)
    applied = AppliedPlanResults(
        alloc_updates=[a for v in plan.node_update.values() for a in v],
        allocs_to_place=[a for v in plan.node_allocation.values() for a in v],
        allocs_preempted=[a for v in plan.node_preemptions.values()
                          for a in v],
        plan_id=plan.plan_id)
    return plan, applied, evicted, stopped, placed


def test_a_snapshot_keeps_the_record_a_plan_evicts_or_stops():
    """The plan's entry becomes the stored record and shares its parts
    with the record it replaces, which a snapshot still holds: the
    snapshot reads `run` at its old index, field for field what it read
    before the commit, and a client's later update of the evicted
    allocation reaches neither it nor the plan's entry."""
    store = _fleet(2, 1)
    plan, applied, evicted, stopped, placed = _evicting_plan(store)
    snap, frozen = store.snapshot(), Frozen(store)
    universe = {name: set(getattr(frozen, name))
                for name in ("nodes", "jobs", "evals", "allocs",
                             "deployments")}
    universe["allocs"].add(placed.id)
    universe["by_job"] = set(frozen.by_job) | {("default", placed.job_id)}
    universe["by_node"] = set(frozen.by_node)
    before = {a.id: copy.deepcopy(a) for a in (evicted, stopped)}
    jobs = {a.id: a.job for a in (evicted, stopped)}
    store.upsert_plan_results(6, applied)
    hold(snap, frozen, universe)
    for old in (evicted, stopped):
        assert snap.allocs[old.id] is old
        assert old == before[old.id]            # every field, job included
        assert old.desired_status == AllocDesiredStatus.RUN
        assert old.modify_index == 4 and old.job is jobs[old.id]
    (evict_entry,), (stop_entry,) = (plan.node_preemptions[evicted.node_id],
                                     plan.node_update[stopped.node_id])
    live = store.alloc_by_id(evicted.id)
    assert live is evict_entry and live is not evicted
    assert live.desired_status == AllocDesiredStatus.EVICT
    assert live.preempted_by_allocation == placed.id
    assert live.job is jobs[evicted.id] and live.modify_index == 6
    assert live.create_index == evicted.create_index
    assert live.task_states is evicted.task_states      # shared, not copied
    live = store.alloc_by_id(stopped.id)
    assert live is stop_entry
    assert live.desired_status == AllocDesiredStatus.STOP
    assert live.followup_eval_id == "eval-17"
    assert live.job is jobs[stopped.id]
    assert live.client_status == AllocClientStatus.RUNNING
    # the client reports the evicted allocation's end
    entry_was = copy.deepcopy(evict_entry)
    u = evict_entry.copy()
    u.client_status = AllocClientStatus.COMPLETE
    u.task_states = {"web": TaskState(state="dead", finished_at=2.0,
                                      events=[{"type": "Killed"}])}
    store.update_allocs_from_client(7, [u])
    now = store.alloc_by_id(evicted.id)
    assert now is not evict_entry
    assert now.client_status == AllocClientStatus.COMPLETE
    assert now.desired_status == AllocDesiredStatus.EVICT
    assert now.task_states["web"].state == "dead" and now.modify_index == 7
    assert evict_entry == entry_was
    assert evict_entry.task_states["web"].state == "running"
    assert evicted == before[evicted.id]
    assert evicted.task_states["web"].events == [{"type": "Started"}]
    hold(snap, frozen, universe)


def _by_value(store: StateStore) -> dict:
    with store._lock:
        return {"index": store.latest_index,
                "allocs": dict(store._allocs.items()),
                "jobs": dict(store._jobs.items()),
                "by_node": {k: set(v)
                            for k, v in store._allocs_by_node.items()},
                "live_names": {k: {n: set(ids) for n, ids in v.items()}
                               for k, v in store._live_names.items()},
                "used": store.matrix.used.copy().tolist()}


def test_entries_that_share_parts_go_through_the_log_and_a_snapshot():
    """One store applies the plan's own objects (a dev agent: the
    applier hands them over by reference), two the raft payload's
    encoding of them (the servers of a cluster, each its own decoding);
    then the first is saved and restored.  All four read the same, value
    for value; the two that applied the encoding, and the restored one
    beside the blob it came from, the same bytes a table."""
    from nomad_tpu.state.digest import canon
    leader = _fleet(2, 1)
    plan, applied, evicted, stopped, placed = _evicting_plan(leader)
    blob = NomadFSM(leader).snapshot()
    follower, other = StateStore(), StateStore()
    NomadFSM(follower).restore(blob)
    NomadFSM(other).restore(blob)
    assert _by_value(follower) == _by_value(leader)
    payload = {"results": applied}
    wire = pickle.loads(pickle.dumps(payload))      # raft/node.py `apply`
    again = pickle.loads(pickle.dumps(payload))
    entry = wire["results"].allocs_preempted[0]
    assert entry.job is None and entry is not applied.allocs_preempted[0]
    assert entry == applied.allocs_preempted[0]
    NomadFSM(leader).apply(6, MessageType.APPLY_PLAN_RESULTS, payload)
    NomadFSM(follower).apply(6, MessageType.APPLY_PLAN_RESULTS, wire)
    NomadFSM(other).apply(6, MessageType.APPLY_PLAN_RESULTS, again)
    assert leader.alloc_by_id(evicted.id) is applied.allocs_preempted[0]
    want = _by_value(leader)
    assert _by_value(follower) == want == _by_value(other)
    assert canon(NomadFSM(follower).snapshot()) == \
        canon(NomadFSM(other).snapshot())
    assert want["allocs"][evicted.id].desired_status == \
        AllocDesiredStatus.EVICT
    assert want["allocs"][stopped.id].desired_status == \
        AllocDesiredStatus.STOP
    assert want["allocs"][evicted.id].job == evicted.job
    assert want["allocs"][placed.id].preempted_allocations == [evicted.id]
    saved = NomadFSM(leader).snapshot()
    restored = StateStore()
    NomadFSM(restored).restore(saved)
    assert _by_value(restored) == want
    assert canon(NomadFSM(restored).snapshot()) == canon(saved)
    # the old records a reader may still hold were not written to
    assert evicted.desired_status == stopped.desired_status == \
        AllocDesiredStatus.RUN


# ------------------------------------------------------ the tables alone

@pytest.mark.parametrize("seed", range(4))
def test_table_reads_as_a_dict_does(seed):
    rng = random.Random(seed)
    stats = {"roots_copied": 0, "buckets_copied": 0, "sets_copied": 0}
    table, model, views = Table(16, stats), {}, []
    keys = [f"k{i}" for i in range(60)] + [("ns", f"j{i}") for i in range(20)]
    for step in range(600):
        k, r = rng.choice(keys), rng.random()
        if r < 0.55:
            table[k] = model[k] = object()
        elif r < 0.75:
            assert table.pop(k, None) is model.pop(k, None)
        elif r < 0.85 and k in model:
            del table[k], model[k]
        elif r < 0.9 and k not in model:
            with pytest.raises(KeyError):
                del table[k]
        if step % 37 == 0:
            views.append((table.view(), dict(model)))
        for t, m in [(table, model)] + views[-4:]:
            assert len(t) == len(m) and bool(t) == bool(m)
            assert dict(t.items()) == m == dict(t)
            assert set(t) == set(t.keys()) == set(m)
            assert t.get(k) is m.get(k) and (k in t) == (k in m)
            assert t == m
    for write in (lambda: table.update(a=1), lambda: table.setdefault("a", 1),
                  table.popitem):
        with pytest.raises(TypeError):      # would go around the shadow
            write()
    table.clear()
    assert len(table) == 0 and list(table) == []
    assert all(dict(v.items()) == m for v, m in views)


@pytest.mark.parametrize("seed", range(4))
def test_index_table_files_ids_as_sets_do(seed):
    rng = random.Random(seed)
    stats = {"roots_copied": 0, "buckets_copied": 0, "sets_copied": 0}
    table, model, views = IndexTable(8, stats), {}, []
    for step in range(800):
        k, member = f"n{rng.randrange(30)}", rng.randrange(12)
        if rng.random() < 0.6:
            table.add(k, member)
            model.setdefault(k, set()).add(member)
        else:
            table.discard(k, member)
            model.get(k, set()).discard(member)
            if not model.get(k, True):
                del model[k]                    # an emptied key is dropped
        if step % 41 == 0:
            views.append((table.view(), {k: set(v) for k, v in model.items()}))
        for t, m in [(table, model)] + views[-4:]:
            assert dict(t.items()) == m and len(t) == len(m)
            assert t.get(k, ()) == m.get(k, ())
    # between two views a set is copied at most once
    table.view()
    copies = stats["sets_copied"]
    table.add("n0", 99)
    table.add("n0", 100)
    table.discard("n0", 99)
    assert stats["sets_copied"] - copies <= 1


def test_bucket_order_does_not_depend_on_the_process():
    """`hash()` of a str is salted per process; a view's order is its
    buckets', so the slot has to be a function of the key alone."""
    import subprocess
    prog = ("from nomad_tpu.state.table import Table\n"
            "t = Table(64, {})\n"
            "for i in range(200): t['id-%d' % i] = i\n"
            "t[('ns', 'job')] = 1\n"
            "print(list(t.view()), list(t))")
    outs = {subprocess.run([sys.executable, "-c", prog], check=True,
                           capture_output=True, text=True,
                           env={"PYTHONHASHSEED": seed, "PATH": ""}).stdout
            for seed in ("1", "2")}
    assert len(outs) == 1


def _span_count(name: str) -> int:
    from nomad_tpu.telemetry import global_metrics
    return {s["Name"]: s["count"]
            for s in global_metrics.snapshot()["Samples"]}.get(name, 0)


def _span_total_ms(name: str) -> float:
    from nomad_tpu.telemetry import global_metrics
    return {s["Name"]: s["mean"] * s["count"]
            for s in global_metrics.snapshot()["Samples"]}.get(name, 0.0)


def test_the_span_times_the_snapshot_and_not_a_wait_for_it(monkeypatch):
    """`store.snapshot` (what `snapshot_ms` reads) is the work alone, on
    the profiler's timeline, once for each snapshot taken and not for a
    memo hit; what a worker waits for an index that has not come is in
    `worker.snapshot_wait`, a wait, and in no sample of the other."""
    from nomad_tpu import tracing

    opened = []

    class FakeTraceMe:
        def __init__(self, name):
            opened.append(name)

        @staticmethod
        def is_enabled():
            return True

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(tracing, "_TraceMe", FakeTraceMe)
    store = _fleet(10, 2)
    n0, w0 = _span_count("nomad.store.snapshot"), \
        _span_count("nomad.worker.snapshot_wait")
    store.snapshot()
    store.snapshot()                        # the memo: no work, no sample
    assert _span_count("nomad.store.snapshot") == n0 + 1
    store.upsert_evals(store.latest_index + 1, [mock.eval()])
    store.snapshot()
    assert _span_count("nomad.store.snapshot") == n0 + 2
    assert opened.count("store.snapshot") == 2      # work, not a wait

    class _Server:
        name = "s1"

    from nomad_tpu.core.worker import Worker
    w = Worker.__new__(Worker)
    w.server = _Server()
    w.server.store = store
    want = store.latest_index + 1
    t = threading.Timer(0.05, store.upsert_evals, (want, [mock.eval()]))
    t.start()
    del opened[:]
    ms0 = (_span_total_ms("nomad.store.snapshot"),
           _span_total_ms("nomad.worker.snapshot_wait"))
    gc.disable()
    try:
        snap = w.refresh_snapshot(want)     # waits 50 ms for the index
    finally:
        gc.enable()
    t.join()
    assert snap.index == want
    assert _span_count("nomad.worker.snapshot_wait") == w0 + 1
    assert _span_count("nomad.store.snapshot") == n0 + 3
    assert opened == ["store.snapshot"]     # the wait is on no timeline
    assert _span_total_ms("nomad.worker.snapshot_wait") - ms0[1] >= 40.0
    assert _span_total_ms("nomad.store.snapshot") - ms0[0] < 20.0
