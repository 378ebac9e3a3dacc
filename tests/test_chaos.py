"""Seeded chaos layer + failure-recovery hardening.

Unit legs pin the contracts one at a time: spec grammar, zero behavior
change when disabled, seeded determinism, the native circuit breaker,
exactly-once plan commit under injected applier crashes, plan-id replay
dedup, broker lease-expiry redelivery, bounded worker nack retry, the
heartbeat invalidate retry path, and ApiClient GET retries.

The soak leg boots a real in-process 3-server cluster under a fixed-seed
fault schedule (drops, delays, instant lease expiry, applier crashes,
partitions) plus a seeded isolate/heal schedule, then turns chaos off and
asserts the control plane converges: full placement, every eval terminal,
no outstanding leases.
"""
import os
import random
import subprocess
import sys
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import pytest

from nomad_tpu import chaos, mock, native
from nomad_tpu.api.client import ApiClient, ApiError
from nomad_tpu.chaos import ChaosError, ChaosRegistry
from nomad_tpu.core.cluster import Cluster
from nomad_tpu.core.broker import EvalBroker
from nomad_tpu.core.heartbeat import HeartbeatTracker
from nomad_tpu.core.plan_apply import PlanApplier
from nomad_tpu.core.plan_queue import PlanQueue
from nomad_tpu.core.server import ServerConfig
from nomad_tpu.core.worker import TRANSIENT_ERRORS, RemoteWorker
from nomad_tpu.raft import RaftConfig
from nomad_tpu.rpc.endpoints import RpcError
from nomad_tpu.state.store import AppliedPlanResults, StateStore
from nomad_tpu.structs import (AllocClientStatus, AllocDesiredStatus,
                               EvalStatus, Evaluation)
from nomad_tpu.structs.node import NodeStatus
from nomad_tpu.structs.plan import Plan
from nomad_tpu.utils import generate_uuid

import numpy as np


def _wait(cond, timeout=10.0, interval=0.02):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if cond():
            return True
        time.sleep(interval)
    return False


@pytest.fixture(autouse=True)
def _no_leaked_chaos():
    """Every test starts and ends with chaos disabled."""
    chaos.uninstall()
    yield
    chaos.uninstall()


# ---------------------------------------------------------------- registry


def test_spec_grammar_roundtrip():
    reg = ChaosRegistry.from_spec(
        "seed=42; rpc.drop=0.05;delay_ms=5;plan.crash_after_commit=1")
    assert reg.seed == 42
    assert reg.delay_ms == 5.0
    assert reg.rates["rpc.drop"] == 0.05
    assert reg.rates["plan.crash_after_commit"] == 1.0
    assert reg.rates["raft.partition"] == 0.0
    # spec() round-trips through the parser
    again = ChaosRegistry.from_spec(reg.spec())
    assert again.seed == reg.seed
    assert again.rates == reg.rates
    assert again.delay_ms == reg.delay_ms


def test_spec_grammar_rejects_garbage():
    with pytest.raises(ValueError, match="unknown chaos fault point"):
        ChaosRegistry.from_spec("seed=1;rpc.dorp=0.1")
    with pytest.raises(ValueError, match=r"must be in \[0, 1\]"):
        ChaosRegistry.from_spec("rpc.drop=1.5")
    with pytest.raises(ValueError, match="want key=value"):
        ChaosRegistry.from_spec("rpc.drop")
    with pytest.raises(ValueError):
        ChaosRegistry.from_spec("seed=abc")


def test_disabled_is_default_and_inert():
    assert chaos.active is None
    assert chaos.should("rpc.drop") is False
    chaos.fire("plan.crash_before_commit")   # no-op, must not raise
    chaos.maybe_delay()


def test_installed_registry_never_touches_global_random():
    random.seed(1234)
    want = [random.random() for _ in range(8)]
    random.seed(1234)
    prev = chaos.install(ChaosRegistry(seed=7, rates={"rpc.drop": 0.5}))
    try:
        for _ in range(100):
            chaos.should("rpc.drop")
        got = [random.random() for _ in range(8)]
    finally:
        chaos.install(prev)
    assert got == want


def test_seeded_determinism():
    rates = {"rpc.drop": 0.3, "broker.lease_expire": 0.2}
    seq = [ChaosRegistry(seed=7, rates=rates).should("rpc.drop")
           for _ in range(1)]  # noqa: F841  (warm-up, single draw)
    a = ChaosRegistry(seed=7, rates=rates)
    b = ChaosRegistry(seed=7, rates=rates)
    c = ChaosRegistry(seed=8, rates=rates)
    seq_a = [a.should("rpc.drop") for _ in range(64)]
    seq_b = [b.should("rpc.drop") for _ in range(64)]
    seq_c = [c.should("rpc.drop") for _ in range(64)]
    assert seq_a == seq_b
    assert seq_a != seq_c
    assert a.stats["rpc.drop"] == sum(seq_a)
    # zero-rate points never draw, so they can't shift the schedule
    assert a.should("native.fail") is False


def test_env_var_installs_registry_at_import():
    code = ("from nomad_tpu import chaos; "
            "print(chaos.active.spec() if chaos.active else 'None')")
    env = dict(os.environ, NOMAD_TPU_CHAOS="seed=9;rpc.drop=0.25")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=60)
    assert out.returncode == 0, out.stderr
    assert "seed=9" in out.stdout
    assert "rpc.drop=0.25" in out.stdout

    env.pop("NOMAD_TPU_CHAOS")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=60)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "None"


# ---------------------------------------------------------- native breaker


def test_native_circuit_breaker_trips_and_resets():
    native._load()
    br = native.breaker
    br.reset()
    cap = np.full((4, 6), 100.0, np.float32)
    used = np.zeros((4, 6), np.float32)
    demand = np.full(6, 10.0, np.float32)
    want = native.allocs_fit(cap, used, demand)
    assert want.all()

    prev = chaos.install(ChaosRegistry(seed=1, rates={"native.fail": 1.0}))
    try:
        trips_before = br.stats["trips"]
        for _ in range(br.threshold):
            assert not br.open
            # every native attempt raises; the Python fallback still
            # returns the right answer
            got = native.allocs_fit(cap, used, demand)
            assert (got == want).all()
        assert br.open
        assert br.stats["trips"] == trips_before + 1
        # circuit open: native is skipped entirely, so chaos at rate 1.0
        # can no longer fail the call
        failures = br.stats["failures"]
        got = native.allocs_fit(cap, used, demand)
        assert (got == want).all()
        assert br.stats["failures"] == failures
    finally:
        chaos.install(prev)
        br.reset()
    assert not br.open
    assert (native.allocs_fit(cap, used, demand) == want).all()


# ------------------------------------------------- plan applier crash legs


def _applier_rig():
    store = StateStore()
    node = mock.node()
    store.upsert_node(1, node)
    applier = PlanApplier(store)
    queue = PlanQueue()
    queue.set_enabled(True)
    stop = threading.Event()
    loop = threading.Thread(target=applier.run_loop, args=(queue, stop),
                            daemon=True)
    loop.start()
    return store, node, applier, queue, stop, loop


def _plan_on(node, cpu=100):
    j = mock.job()
    j.task_groups[0].tasks[0].resources.cpu = cpu
    j.task_groups[0].tasks[0].resources.memory_mb = 64
    alloc = mock.alloc_for(j, node_id=node.id)
    plan = Plan(eval_id=generate_uuid(), job=j)
    plan.append_alloc(alloc, j)
    return plan, alloc


def test_crash_before_commit_resolves_futures_and_commits_nothing():
    store, node, applier, queue, stop, loop = _applier_rig()
    try:
        plans = [_plan_on(node)[0] for _ in range(3)]
        chaos.install(ChaosRegistry(
            seed=3, rates={"plan.crash_before_commit": 1.0}))
        futures = [queue.enqueue(p).future for p in plans]
        # every future resolves exactly once, with the injected error
        for f in futures:
            with pytest.raises(ChaosError):
                f.result(timeout=10)
        assert store.allocs() == []

        chaos.uninstall()
        # the submitter's retry path: the same plans go through clean
        for p in plans:
            r = queue.enqueue(p).future.result(timeout=10)
            assert r.node_allocation and not r.rejected_nodes
        assert len(store.allocs()) == 3
    finally:
        stop.set()
        loop.join(5)


def test_crash_after_commit_replay_dedups_on_plan_id():
    store, node, applier, queue, stop, loop = _applier_rig()
    try:
        plan, alloc = _plan_on(node)
        chaos.install(ChaosRegistry(
            seed=3, rates={"plan.crash_after_commit": 1.0}))
        with pytest.raises(ChaosError):
            queue.enqueue(plan).future.result(timeout=10)
        # the write landed even though the submitter saw an error
        assert [a.id for a in store.allocs()] == [alloc.id]
        index_after_crash = store.latest_index

        chaos.uninstall()
        # the submitter retries the same plan: replay must be a no-op
        r = queue.enqueue(plan).future.result(timeout=10)
        assert r.node_allocation and not r.rejected_nodes
        assert [a.id for a in store.allocs()] == [alloc.id]
        live = store.alloc_by_id(alloc.id)
        assert live is not None and not live.terminal_status()
        assert store.latest_index >= index_after_crash
    finally:
        stop.set()
        loop.join(5)


def test_store_dedups_applied_plan_results_by_plan_id():
    store = StateStore()
    node = mock.node()
    store.upsert_node(1, node)
    j = mock.job()
    a1 = mock.alloc_for(j, node_id=node.id)
    pid = generate_uuid()
    store.upsert_plan_results(2, AppliedPlanResults(
        allocs_to_place=[a1], eval_id="e1", plan_id=pid))
    assert store.alloc_by_id(a1.id) is not None
    # a replay carrying the same plan_id is ignored wholesale
    # (index 1: the live-name guard would drop a re-used name anyway)
    a2 = mock.alloc_for(j, node_id=node.id, index=1)
    store.upsert_plan_results(3, AppliedPlanResults(
        allocs_to_place=[a2], eval_id="e1", plan_id=pid))
    assert store.alloc_by_id(a2.id) is None
    # a fresh plan_id applies normally
    store.upsert_plan_results(4, AppliedPlanResults(
        allocs_to_place=[a2], eval_id="e1", plan_id=generate_uuid()))
    assert store.alloc_by_id(a2.id) is not None


def test_store_drops_placement_duplicating_live_name():
    """Racing plans for one redelivered eval both pass the submit-time
    token gate; the loser's same-name placement is dropped at apply."""
    store = StateStore()
    node = mock.node()
    store.upsert_node(1, node)
    j = mock.job()
    live = mock.alloc_for(j, node_id=node.id, index=0)
    store.upsert_plan_results(2, AppliedPlanResults(
        allocs_to_place=[live], eval_id="e1", plan_id=generate_uuid()))
    racer = mock.alloc_for(j, node_id=node.id, index=0)
    store.upsert_plan_results(3, AppliedPlanResults(
        allocs_to_place=[racer], eval_id="e1", plan_id=generate_uuid()))
    assert store.alloc_by_id(racer.id) is None
    assert store.alloc_by_id(live.id) is not None
    # a different name from the same job still applies
    other = mock.alloc_for(j, node_id=node.id, index=1)
    store.upsert_plan_results(4, AppliedPlanResults(
        allocs_to_place=[other], eval_id="e1", plan_id=generate_uuid()))
    assert store.alloc_by_id(other.id) is not None
    # system jobs share one name per node by design: same name on a
    # DIFFERENT node applies, same node is the duplicate
    node2 = mock.node()
    store.upsert_node(5, node2)
    sj = mock.system_job()
    s1 = mock.alloc_for(sj, node_id=node.id, index=0)
    s2 = mock.alloc_for(sj, node_id=node2.id, index=0)
    s3 = mock.alloc_for(sj, node_id=node.id, index=0)
    store.upsert_plan_results(6, AppliedPlanResults(
        allocs_to_place=[s1, s2, s3], eval_id="e2",
        plan_id=generate_uuid()))
    assert store.alloc_by_id(s1.id) is not None
    assert store.alloc_by_id(s2.id) is not None
    assert store.alloc_by_id(s3.id) is None


def _plan(store, index, **lists):
    store.upsert_plan_results(index, AppliedPlanResults(
        eval_id="e", plan_id=generate_uuid(), **lists))


def _guard_racer_in_a_later_plan(store, sj, node, node2):
    s1, s2 = (mock.alloc_for(sj, node_id=node.id) for _ in range(2))
    _plan(store, 10, allocs_to_place=[s1])
    _plan(store, 11, allocs_to_place=[s2])
    return [s1], [s2]


def _guard_same_name_on_another_node(store, sj, node, node2):
    s1 = mock.alloc_for(sj, node_id=node.id)
    s2 = mock.alloc_for(sj, node_id=node2.id)
    _plan(store, 10, allocs_to_place=[s1])
    _plan(store, 11, allocs_to_place=[s2])
    return [s1, s2], []


def _guard_racer_inside_one_plan(store, sj, node, node2):
    s1 = mock.alloc_for(sj, node_id=node.id)
    s2 = mock.alloc_for(sj, node_id=node2.id)
    s3 = mock.alloc_for(sj, node_id=node.id)
    s4 = mock.alloc_for(sj, node_id=node2.id)
    _plan(store, 10, allocs_to_place=[s1, s2, s3, s4])
    return [s1, s2], [s3, s4]


def _guard_holder_stopped_by_the_same_plan(store, sj, node, node2):
    old = mock.alloc_for(sj, node_id=node.id)
    keeps = mock.alloc_for(sj, node_id=node2.id)
    _plan(store, 10, allocs_to_place=[old, keeps])
    stopped = old.copy()
    stopped.desired_status = AllocDesiredStatus.STOP
    repl = mock.alloc_for(sj, node_id=node.id)
    racer = mock.alloc_for(sj, node_id=node2.id)    # its holder stays
    _plan(store, 11, alloc_updates=[stopped], allocs_to_place=[repl, racer])
    assert store.alloc_by_id(old.id).desired_status == AllocDesiredStatus.STOP
    return [repl, keeps], [racer]


def _guard_holder_evicted_by_an_earlier_plan(store, sj, node, node2):
    old = mock.alloc_for(sj, node_id=node.id)
    _plan(store, 10, allocs_to_place=[old])
    evicted = old.copy()
    evicted.desired_status = AllocDesiredStatus.EVICT
    _plan(store, 11, allocs_preempted=[evicted])
    repl = mock.alloc_for(sj, node_id=node.id)
    _plan(store, 12, allocs_to_place=[repl])
    return [repl], []


def _guard_holder_terminal_by_client_status(store, sj, node, node2):
    old = mock.alloc_for(sj, node_id=node.id)
    _plan(store, 10, allocs_to_place=[old])
    done = old.copy()
    done.client_status = AllocClientStatus.FAILED
    store.update_allocs_from_client(11, [done])
    repl = mock.alloc_for(sj, node_id=node.id)
    _plan(store, 12, allocs_to_place=[repl])
    return [repl], []


def _guard_update_of_an_existing_id(store, sj, node, node2):
    s1 = mock.alloc_for(sj, node_id=node.id)
    _plan(store, 10, allocs_to_place=[s1])
    upd = s1.copy()
    upd.deployment_id = "d-join"
    _plan(store, 11, allocs_to_place=[upd])
    assert store.alloc_by_id(s1.id).deployment_id == "d-join"
    return [s1], []


def _guard_holder_gone_by_gc(store, sj, node, node2):
    old = mock.alloc_for(sj, node_id=node.id)
    _plan(store, 10, allocs_to_place=[old])
    with store._lock:
        store._drop_alloc(old.id)
    repl = mock.alloc_for(sj, node_id=node.id)
    _plan(store, 11, allocs_to_place=[repl])
    return [repl], []


_GUARD_CASES = [
    _guard_racer_in_a_later_plan, _guard_same_name_on_another_node,
    _guard_racer_inside_one_plan, _guard_holder_stopped_by_the_same_plan,
    _guard_holder_evicted_by_an_earlier_plan,
    _guard_holder_terminal_by_client_status,
    _guard_update_of_an_existing_id, _guard_holder_gone_by_gc]


@pytest.mark.parametrize("make_job", [mock.system_job, mock.sysbatch_job],
                         ids=["system", "sysbatch"])
@pytest.mark.parametrize(
    "case", _GUARD_CASES,
    ids=[c.__name__[len("_guard_"):] for c in _GUARD_CASES])
def test_store_scopes_a_system_jobs_name_guard_to_the_node(case, make_job):
    """A system or sysbatch job's allocations share one name, one a
    node: the guard drops a second live holder on the SAME node, and
    only that, and `stats["name_guard_drops"]` counts each drop.  Each
    case returns the allocations that must hold their node's entry of
    the liveness index and the placements that must be gone."""
    store = StateStore()
    node, node2 = mock.node(), mock.node()
    store.upsert_node(1, node)
    store.upsert_node(2, node2)
    sj = make_job()
    # bystanders under the same name on nodes of their own: a holder
    # elsewhere neither drops a placement nor hides a racer
    for i in range(3):
        n = mock.node()
        store.upsert_node(3 + i, n)
        _plan(store, 6 + i, allocs_to_place=[mock.alloc_for(sj, node_id=n.id)])
    live, dropped = case(store, sj, node, node2)
    for a in dropped:
        assert store.alloc_by_id(a.id) is None
    assert store.stats["name_guard_drops"] == len(dropped)
    by_node = store._live_names[(sj.namespace, sj.id, live[0].name)]
    for a in live:
        assert store.alloc_by_id(a.id) is not None
        assert by_node[a.node_id] == {a.id}
    assert len(by_node) == 3 + len(live)


def test_store_allows_same_name_when_holder_stops_in_same_plan():
    """Destructive update: stop old + place new under one name rides a
    single plan; alloc_updates apply first, so the placement lands."""
    from nomad_tpu.structs import AllocDesiredStatus
    store = StateStore()
    node = mock.node()
    store.upsert_node(1, node)
    j = mock.job()
    old = mock.alloc_for(j, node_id=node.id, index=0)
    store.upsert_plan_results(2, AppliedPlanResults(
        allocs_to_place=[old], eval_id="e1", plan_id=generate_uuid()))
    stopped = old.copy()
    stopped.desired_status = AllocDesiredStatus.STOP
    repl = mock.alloc_for(j, node_id=node.id, index=0)
    store.upsert_plan_results(3, AppliedPlanResults(
        alloc_updates=[stopped], allocs_to_place=[repl],
        eval_id="e2", plan_id=generate_uuid()))
    assert store.alloc_by_id(repl.id) is not None
    assert store.alloc_by_id(old.id).desired_status == AllocDesiredStatus.STOP


def test_store_applies_update_of_existing_alloc_despite_dup_name():
    """Updates (same alloc id already in the store) are never dropped,
    even when a duplicate-name sibling exists — the reconciler's dedup
    stop must be able to land."""
    store = StateStore()
    node = mock.node()
    store.upsert_node(1, node)
    j = mock.job()
    a1 = mock.alloc_for(j, node_id=node.id, index=0)
    store.upsert_plan_results(2, AppliedPlanResults(
        allocs_to_place=[a1], eval_id="e1", plan_id=generate_uuid()))
    a2 = mock.alloc_for(j, node_id=node.id, index=0)
    # force the duplicate in (simulates pre-guard history)
    store._allocs[a2.id] = a2
    store._allocs_by_job.add((a2.namespace, a2.job_id), a2.id)
    upd = a1.copy()
    upd.deployment_id = "d-join"
    store.upsert_plan_results(3, AppliedPlanResults(
        allocs_to_place=[upd], eval_id="e1", plan_id=generate_uuid()))
    assert store.alloc_by_id(a1.id).deployment_id == "d-join"


# ----------------------------------------------------- broker lease expiry


def _eval(job_id="job-1"):
    return Evaluation(id=generate_uuid(), namespace="default", priority=50,
                      type="service", triggered_by="job-register",
                      job_id=job_id, status=EvalStatus.PENDING)


def test_expired_lease_auto_nacks_and_redelivers():
    broker = EvalBroker(nack_timeout=60.0)
    broker.set_enabled(True)
    ev = _eval()
    broker.enqueue(ev)
    chaos.install(ChaosRegistry(
        seed=5, rates={"broker.lease_expire": 1.0}))
    got, token = broker.dequeue(["service"], timeout=1.0)
    chaos.uninstall()
    assert got is ev
    # the lease expired the moment it was handed out: the next broker
    # operation settles it, so the token reads as stale everywhere
    assert broker.outstanding(ev.id) is None
    assert broker.ack(ev.id, token) is False
    # ...and the eval redelivers with the attempt count bumped
    got2, token2 = broker.dequeue(["service"], timeout=2.0)
    assert got2 is ev and token2 != token
    assert broker._attempts[ev.id] == 1
    assert broker.ack(ev.id, token2) is True


# ------------------------------------------------ device-world scatter loss


def test_world_scatter_fail_invalidates_then_reuploads():
    """Injected loss of the device-side rank-1 scatter: the host
    snapshot keeps the commit (it is authoritative), the resident basis
    is dropped rather than served stale, and the next update() restores
    device parity with one full re-upload — counted as a steady-state
    re-upload, which is how the bench gate sees injected device loss."""
    import jax

    from nomad_tpu.parallel.world import DeviceWorld

    N, R = 16, 4
    world = DeviceWorld(mesh=None)
    capacity = np.full((N, R), 100.0, np.float32)
    world.update(capacity, np.zeros((N, R), np.float32))

    rows = np.array([0, 3], np.int32)
    demand = np.array([5.0, 2.0, 0.0, 0.0], np.float32)
    chaos.install(ChaosRegistry(seed=3, rates={"world.scatter_fail": 1.0}))
    try:
        world.apply_rank1(rows, np.ones(2, np.int32), demand)
    finally:
        chaos.uninstall()

    expect = np.zeros((N, R), np.float32)
    expect[rows] = demand
    np.testing.assert_array_equal(world.host_basis(), expect)
    assert world.stats["chaos_invalidations"] == 1
    _, basis_dev = world.device_arrays()
    assert basis_dev is None

    _, basis_dev = world.update(capacity, expect)
    got = np.asarray(jax.device_get(basis_dev))
    np.testing.assert_array_equal(got, expect)
    assert world.stats["steady_reuploads"] == 1


# -------------------------------------------------- worker retry surfaces


class _FlakyLeader:
    """Stand-in server whose rpc_leader fails the first `fail_n` calls."""

    def __init__(self, fail_n, kind="internal"):
        self.calls = 0
        self.fail_n = fail_n
        self.kind = kind

    def rpc_leader(self, method, args):
        self.calls += 1
        if self.calls <= self.fail_n:
            raise RpcError(self.kind, "injected")
        return {"ok": True}


def test_remote_worker_nack_retries_then_succeeds():
    srv = _FlakyLeader(fail_n=2)
    w = RemoteWorker(srv)
    assert w._nack("ev-1", "tok-1") is True
    assert srv.calls == 3


def test_remote_worker_nack_is_bounded():
    srv = _FlakyLeader(fail_n=100)
    w = RemoteWorker(srv)
    t0 = time.monotonic()
    assert w._nack("ev-1", "tok-1") is False
    assert srv.calls == 3                    # three attempts, no more
    assert time.monotonic() - t0 < 5.0       # bounded, not a spin


def test_remote_worker_rpc_retries_leadership_churn_only():
    # retryable kind: keeps trying until the fake leader answers
    srv = _FlakyLeader(fail_n=3, kind="no_leader")
    w = RemoteWorker(srv)
    assert w._rpc("Eval.Ack", {}, deadline=5.0) == {"ok": True}
    assert srv.calls == 4
    # non-retryable kind: a real answer, surfaced immediately
    srv = _FlakyLeader(fail_n=100, kind="stale_eval_token")
    w = RemoteWorker(srv)
    with pytest.raises(RpcError, match="injected"):
        w._rpc("Plan.Submit", {}, deadline=5.0)
    assert srv.calls == 1


# -------------------------------------------------- heartbeat invalidate


class _FlakyHeartbeatServer:
    def __init__(self, node, fail_times=1):
        self.node = node
        self.fail_times = fail_times
        self.status_calls = []
        outer = self

        class _Store:
            def node_by_id(self, node_id):
                return outer.node

            def allocs_by_node(self, node_id):
                return []

        self.store = _Store()

    def update_node_status(self, node_id, status):
        self.status_calls.append((node_id, status))
        if len(self.status_calls) <= self.fail_times:
            raise RuntimeError("lost quorum mid-invalidate")


def test_heartbeat_invalidate_failure_rearms_retry():
    node = mock.node(status=NodeStatus.READY)
    srv = _FlakyHeartbeatServer(node, fail_times=1)
    hb = HeartbeatTracker(srv, ttl=0.15, tick=0.02)
    hb.start()
    try:
        hb.heartbeat(node.id)
        # first invalidate at ~0.15s raises; the re-armed retry deadline
        # (min(ttl, 1.0)) fires a second invalidate that lands
        assert _wait(lambda: len(srv.status_calls) >= 2, timeout=3.0)
    finally:
        hb.stop()
    assert all(c == (node.id, NodeStatus.DOWN) for c in srv.status_calls)


# ------------------------------------------------------- api client retry


class _RetryHandler(BaseHTTPRequestHandler):
    gets = 0
    puts = 0
    fail_first_gets = 1

    def _respond(self, code, body, retry_after=None):
        self.send_response(code)
        self.send_header("Content-Type", "application/json")
        self.send_header("Content-Length", str(len(body)))
        if retry_after is not None:
            self.send_header("Retry-After", retry_after)
        self.end_headers()
        self.wfile.write(body)

    def do_GET(self):
        cls = type(self)
        cls.gets += 1
        if cls.gets <= cls.fail_first_gets:
            self._respond(503, b'"busy"', retry_after="0")
        else:
            self._respond(200, b"[]")

    def do_PUT(self):
        type(self).puts += 1
        self._respond(503, b'"busy"')

    def log_message(self, *args):
        pass


def test_api_client_retries_idempotent_gets_only():
    _RetryHandler.gets = 0
    _RetryHandler.puts = 0
    httpd = ThreadingHTTPServer(("127.0.0.1", 0), _RetryHandler)
    t = threading.Thread(target=httpd.serve_forever, daemon=True)
    t.start()
    try:
        client = ApiClient(f"http://127.0.0.1:{httpd.server_port}",
                           retries=2, retry_backoff=0.01)
        # GET: first answer is a 503 with Retry-After; the retry succeeds
        assert client.get("/v1/jobs") == []
        assert _RetryHandler.gets == 2
        # PUT: never retried — the server may have applied the write
        with pytest.raises(ApiError) as exc:
            client.put("/v1/jobs", {"Job": {}})
        assert exc.value.status == 503
        assert _RetryHandler.puts == 1
        # GET exhausting its budget surfaces the last error
        _RetryHandler.gets = 0
        _RetryHandler.fail_first_gets = 100
        with pytest.raises(ApiError):
            client.get("/v1/jobs")
        assert _RetryHandler.gets == 3       # initial + 2 retries
    finally:
        _RetryHandler.fail_first_gets = 1
        httpd.shutdown()
        httpd.server_close()


# ------------------------------------------------------------------- soak


SOAK_RATES = {
    "rpc.drop": 0.02,
    "rpc.delay": 0.05,
    "raft.partition": 0.01,
    "broker.lease_expire": 0.05,
    "plan.crash_before_commit": 0.05,
    "plan.crash_after_commit": 0.05,
}


def _on_leader(cluster, fn, timeout=10.0):
    """Run fn(leader), retrying across leadership churn / chaos drops."""
    deadline = time.time() + timeout
    while True:
        try:
            return fn(cluster.leader(timeout=5.0))
        except TRANSIENT_ERRORS + (TimeoutError,):
            if time.time() >= deadline:
                raise
            time.sleep(0.05)


@pytest.mark.parametrize("seed", [1, 2, 3, 4, 5])
def test_chaos_soak_converges(seed):
    reg = ChaosRegistry(seed=seed, rates=SOAK_RATES, delay_ms=1.0)
    cfg = ServerConfig(num_schedulers=2, heartbeat_ttl=60.0,
                       failed_eval_followup_delay=0.3)
    cluster = Cluster(3, config=cfg, raft_config=RaftConfig(
        heartbeat_interval=0.02, election_timeout=0.1))
    for s in cluster.servers:
        # quick redelivery so injected nacks resolve inside the test
        s.broker.nack_timeout = 1.0
        s.broker.initial_nack_delay = 0.05
        s.broker.subsequent_nack_delay = 0.1
    rng = random.Random(seed)
    job = mock.job()
    job.task_groups[0].count = 3
    try:
        chaos.install(reg)
        cluster.start()
        try:
            nodes = [mock.node() for _ in range(4)]
            for n in nodes:
                _on_leader(cluster, lambda ld, n=n: ld.register_node(n))
            _on_leader(cluster, lambda ld: ld.register_job(job))
            # seeded kill/heal schedule: isolating the leader forces a
            # failover and the restoration path; a follower just churns
            for _ in range(2):
                victim = cluster.servers[rng.randrange(len(cluster.servers))]
                cluster.isolate(victim)
                time.sleep(0.3)
                cluster.heal(victim)
                cluster.leader(timeout=10.0)
            time.sleep(0.5)   # let the fault schedule bite mid-flight work
        finally:
            chaos.uninstall()

        def converged():
            try:
                ld = cluster.leader(timeout=2.0)
            except TimeoutError:
                return False
            live = [a for a in ld.store.allocs_by_job("default", job.id)
                    if not a.terminal_status()]
            if len(live) != 3:
                return False
            if any(not EvalStatus.terminal(e.status)
                   for e in ld.store.evals()):
                return False
            # nothing leased, nothing queued, nothing in flight
            return not ld.broker._unack and not ld.plan_queue._heap

        if not _wait(converged, timeout=20.0):
            ld = cluster.leader(timeout=5.0)
            live = [a for a in ld.store.allocs_by_job("default", job.id)
                    if not a.terminal_status()]
            stuck = [(e.id[:8], e.status, e.triggered_by, e.wait_until)
                     for e in ld.store.evals()
                     if not EvalStatus.terminal(e.status)]
            pytest.fail(
                f"seed {seed}: cluster did not converge; "
                f"chaos fired: {dict(reg.stats)}; leader={ld.name} "
                f"live={len(live)} stuck_evals={stuck} "
                f"unack={list(ld.broker._unack)} "
                f"queue={len(ld.plan_queue._heap)} "
                f"broker={dict(ld.broker.stats)}")
    finally:
        chaos.uninstall()
        cluster.stop()


# ------------------------------------------------- phased chaos schedules


def test_phase_grammar_roundtrip():
    reg = ChaosRegistry.from_spec(
        "seed=7;phase=storm:0.5-3.0;phase=calm2:4-6;"
        "rpc.drop=0.01;broker.lease_expire=0.4@storm;"
        "node.churn_kill=0.6@storm;scale.burst=0.2@calm2")
    assert reg.phases == {"storm": (0.5, 3.0), "calm2": (4.0, 6.0)}
    assert reg.phased["broker.lease_expire"]["storm"] == 0.4
    assert reg.phased["node.churn_kill"]["storm"] == 0.6
    assert reg.phased["scale.burst"]["calm2"] == 0.2
    assert reg.rates["rpc.drop"] == 0.01
    again = ChaosRegistry.from_spec(reg.spec())
    assert again.phases == reg.phases
    assert again.phased == reg.phased
    assert again.rates == reg.rates


def test_phase_grammar_rejects_garbage():
    with pytest.raises(ValueError, match="undeclared phase"):
        ChaosRegistry.from_spec("rpc.drop=0.1@ghost")
    with pytest.raises(ValueError, match="window must have"):
        ChaosRegistry.from_spec("phase=storm:3.0-1.0")
    with pytest.raises(ValueError, match="bad chaos phase"):
        ChaosRegistry.from_spec("phase=storm:oops")
    with pytest.raises(ValueError, match=r"must be in \[0, 1\]"):
        ChaosRegistry.from_spec("phase=s:0-1;rpc.drop=1.5@s")
    with pytest.raises(ValueError, match="unknown chaos fault point"):
        ChaosRegistry.from_spec("phase=s:0-1;rpc.dorp=0.5@s")
    with pytest.raises(ValueError, match="empty phase"):
        ChaosRegistry.from_spec("rpc.drop=0.5@")


def test_phased_rates_gated_by_arm_and_window():
    reg = ChaosRegistry.from_spec(
        "seed=1;phase=storm:10-20;node.churn_kill=1.0@storm")
    # not armed: phase rates contribute nothing
    assert reg.effective_rate("node.churn_kill") == 0.0
    assert reg.phase_now() == ()
    # armed, inside the window (arm with a monotonic anchor 15s ago)
    reg.arm(now=time.monotonic() - 15)
    assert reg.phase_now() == ("storm",)
    assert reg.effective_rate("node.churn_kill") == 1.0
    assert reg.should("node.churn_kill") is True
    # armed, after the window closes
    reg.arm(now=time.monotonic() - 25)
    assert reg.phase_now() == ()
    assert reg.effective_rate("node.churn_kill") == 0.0


def test_phased_rate_max_with_base_rate():
    reg = ChaosRegistry.from_spec(
        "phase=s:0-100;rpc.drop=0.3;rpc.drop=0.1@s")
    reg.arm(now=time.monotonic() - 1)
    # the open phase cannot LOWER a base rate: effective is the max
    assert reg.effective_rate("rpc.drop") == 0.3


def test_node_churn_kill_swallows_heartbeat_rearm():
    node = mock.node(status=NodeStatus.READY)
    srv = _FlakyHeartbeatServer(node, fail_times=0)
    hb = HeartbeatTracker(srv, ttl=0.15, tick=0.02)
    hb.start()
    try:
        chaos.install(ChaosRegistry(seed=3,
                                    rates={"node.churn_kill": 1.0}))
        hb.heartbeat(node.id)          # swallowed: TTL never re-armed
        assert _wait(lambda: len(srv.status_calls) == 0, timeout=0.3)
        chaos.uninstall()
        hb.heartbeat(node.id)          # real re-arm, then expire
        assert _wait(lambda: len(srv.status_calls) >= 1, timeout=3.0)
    finally:
        chaos.uninstall()
        hb.stop()
    assert srv.status_calls[0] == (node.id, NodeStatus.DOWN)
