"""Double-buffered commit waves (r15): the pipelined path — applier
resolves `evaluated` at overlay registration, the worker defers the
COMPLETE/ack settle until the durable commit lands — must commit
byte-identical FSM state to strict serial execution, and a commit that
fails mid-flight must discard the speculative continuation (tickets
released, eval redelivered) rather than half-apply it.

Also covers the r15 satellites: the engine stats shape (the once-dead
batched_evals/single_evals counters), the broker's wave dequeue, and the
wave feeder that fronts the local worker pool.
"""
import copy
import threading
import time

import numpy as np
import pytest

from nomad_tpu import mock
from nomad_tpu.core.broker import EvalBroker, EvalWaveFeeder
from nomad_tpu.core.plan_apply import PlanApplier
from nomad_tpu.core.plan_queue import PlanQueue
from nomad_tpu.state.store import StateStore
from nomad_tpu.structs import Evaluation
from nomad_tpu.structs.plan import Plan


# ---------------------------------------------------------------- stats shape

def test_engine_stats_shape_and_live_counters():
    """The engine stats dict carries every key bench/telemetry read, and
    the batch counters actually move: batched_evals on a >1 part,
    single_evals on a singleton, bulk_parts/bulk_groups on bulk waves
    (they were dead-always-0 before r15)."""
    from concurrent.futures import Future

    from nomad_tpu.encode import ClusterMatrix
    from nomad_tpu.parallel.engine import PlacementEngine, _Request
    from nomad_tpu.scheduler.stack import DenseStack

    eng = PlacementEngine()
    try:
        expected = {"dispatches", "batched_evals", "single_evals",
                    "max_batch_seen", "tickets_open", "stack_s", "put_s",
                    "device_s", "resolve_s", "cache_hits", "cache_misses",
                    "bulk_evals", "waves", "max_waves_seen",
                    "bulk_groups", "bulk_parts", "donated_carries",
                    "wave_lanes", "lane_evals", "lane_slots",
                    "overlap_chained"}
        assert expected <= set(eng.stats), \
            f"missing stats keys: {expected - set(eng.stats)}"
        for key in expected:
            assert eng.stats[key] == 0, f"{key} must start at 0"

        cm = ClusterMatrix(initial_rows=8)
        for i in range(8):
            cm.upsert_node(mock.node())

        def req(count):
            job = mock.batch_job()
            job.task_groups[0].count = count
            stack = DenseStack(cm)
            groups = [stack.compile_group(job, tg)
                      for tg in job.task_groups]
            inputs = stack.build_inputs(job, groups, [0] * count, {},
                                        used_override=cm.used.copy())
            return _Request(cm=cm, inputs=inputs, deltas=[],
                            spread_algorithm=False, future=Future())

        batch = [req(2) for _ in range(3)]
        eng._dispatch(batch)
        for r in batch:
            _res, ticket = r.future.result(timeout=30)
            eng.complete(ticket)
        assert eng.stats["batched_evals"] == 3
        assert eng.stats["single_evals"] == 0

        solo = [req(2)]
        eng._dispatch(solo)
        _res, ticket = solo[0].future.result(timeout=30)
        eng.complete(ticket)
        assert eng.stats["single_evals"] == 1
        assert eng.stats["batched_evals"] == 3
    finally:
        eng.stop()


# ------------------------------------------------------------- wave dequeue

def _eval(ns="default", job="j", prio=50):
    return Evaluation(id=mock._uuid(), namespace=ns, priority=prio,
                      type="batch", job_id=job)


def test_broker_dequeue_batch_drains_ready_without_waiting():
    broker = EvalBroker()
    broker.set_enabled(True)
    evs = [_eval(job=f"j{i}") for i in range(6)]
    for ev in evs:
        broker.enqueue(ev)
    t0 = time.time()
    wave = broker.dequeue_batch(["batch"], max_n=4, timeout=5.0)
    # drains up to max_n in ONE pass, and does NOT wait for the batch
    # to fill beyond what is ready
    assert len(wave) == 4
    assert time.time() - t0 < 1.0
    got_ids = {ev.id for ev, _tok in wave}
    assert got_ids <= {ev.id for ev in evs}
    # each entry carries a real lease
    for ev, tok in wave:
        assert broker.ack(ev.id, tok)
    # remaining two still dequeue
    rest = broker.dequeue_batch(["batch"], max_n=8, timeout=1.0)
    assert len(rest) == 2


def test_broker_dequeue_batch_times_out_empty():
    broker = EvalBroker()
    broker.set_enabled(True)
    t0 = time.time()
    assert broker.dequeue_batch(["batch"], max_n=4, timeout=0.2) == []
    assert 0.15 < time.time() - t0 < 2.0


def test_wave_feeder_buffers_and_closes():
    broker = EvalBroker()
    broker.set_enabled(True)
    for i in range(5):
        broker.enqueue(_eval(job=f"j{i}"))
    feeder = EvalWaveFeeder(broker, max_n=5)
    first = feeder.get(["batch"], timeout=1.0)
    assert first is not None
    # the filler drained the whole wave: peers get buffered entries
    # without touching the broker
    assert feeder.stats["waves"] == 1
    assert feeder.stats["wave_evals"] == 5
    second = feeder.get(["batch"], timeout=0.0)
    assert second is not None and second[0].id != first[0].id
    # close() nacks what is still buffered so no lease is stranded
    feeder.close()
    assert broker.stats["nacked"] == 3


# ------------------------------------------------- pipelined == serial parity

def _rand_world(rng, n_nodes=6):
    store = StateStore()
    nodes = []
    for _ in range(n_nodes):
        n = mock.node()
        store.upsert_node(store.latest_index + 1, n)
        nodes.append(n)
    return store, nodes


def _rand_plan(rng, nodes, k):
    """A plan placing 1-3 allocs on random nodes; sizes randomized so a
    fraction overcommits and exercises partial rejection."""
    j = mock.job()
    j.task_groups[0].tasks[0].resources.cpu = int(rng.integers(200, 2600))
    j.task_groups[0].tasks[0].resources.memory_mb = \
        int(rng.integers(200, 5200))
    plan = Plan(eval_id=f"eval-{k}", job=j)
    plan.plan_id = f"plan-{k}"
    for i in range(int(rng.integers(1, 4))):
        node = nodes[int(rng.integers(0, len(nodes)))]
        # distinct per-alloc name index: two live allocs of one job may
        # never share a name (the store's duplicate-name guard dedups
        # them at apply, which no well-formed scheduler plan triggers)
        alloc = mock.alloc_for(j, node_id=node.id, index=i)
        alloc.id = f"alloc-{k}-{i}-{node.id[:8]}"
        plan.append_alloc(alloc, j)
    return plan


def _fsm_fingerprint(store):
    """The comparable committed state: usage matrix bytes plus the exact
    (alloc id -> node) placement map."""
    allocs = tuple(sorted((a.id, a.node_id, a.desired_status)
                          for a in store._allocs.values()))
    return store.matrix.used.tobytes(), allocs


@pytest.mark.parametrize("seed", [0, 7, 1234])
def test_pipelined_commits_identical_state_to_serial(seed):
    """Randomized parity: plans pushed through the pipelined applier
    loop (evaluate(N+1) overlapping commit(N), batched commits, the
    `evaluated` future resolving early) land byte-identical FSM state to
    the same plans applied strictly serially."""
    rng = np.random.default_rng(seed)
    store_p, nodes = _rand_world(rng)
    plans = [_rand_plan(rng, nodes, k) for k in range(24)]

    # serial reference on an identical world: same node ids, same plan
    # payloads (deep-copied so committed allocs are distinct objects)
    store_s = StateStore()
    for n in nodes:
        store_s.upsert_node(store_s.latest_index + 1, copy.deepcopy(n))
    serial = PlanApplier(store_s)
    for p in plans:
        serial.apply(copy.deepcopy(p))

    # pipelined: run_loop + a commit_fn that stalls, forcing the next
    # batch's evaluation to overlap the in-flight commit
    def slow_commit(applied):
        time.sleep(0.003)
        idx = store_p.latest_index + 1
        if isinstance(applied, list):
            store_p.upsert_plan_results_many(idx, applied)
        else:
            store_p.upsert_plan_results(idx, applied)
        return idx

    pipelined = PlanApplier(store_p, commit_fn=slow_commit)
    pipelined.batch_n = 4
    queue = PlanQueue()
    queue.set_enabled(True)
    stop = threading.Event()
    t = threading.Thread(target=pipelined.run_loop, args=(queue, stop),
                         daemon=True)
    t.start()
    try:
        pendings = [queue.enqueue(p) for p in plans]
        for pend in pendings:
            # the evaluated future resolves no later than the commit
            ev_res = pend.evaluated.result(timeout=30)
            final = pend.future.result(timeout=30)
            # content identical: only alloc_index is commit-side
            assert ev_res is final
    finally:
        stop.set()
        t.join(5)

    assert pipelined.stats["pipelined"] > 0, \
        "the loop never overlapped a commit — parity not exercised"
    fp_p, fp_s = _fsm_fingerprint(store_p), _fsm_fingerprint(store_s)
    assert fp_p[1] == fp_s[1]
    assert fp_p[0] == fp_s[0]
    assert not pipelined._overlay and not serial._overlay


# ------------------------------------------------- mid-flight commit failure

def test_commit_failure_discards_speculative_wave():
    """commit(N) fails mid-flight: every submitter in the batch gets the
    error on its durable future even though `evaluated` already resolved
    (the speculative continuation must be discarded), engine tickets are
    released, the overlay drains, and NOTHING from the failed batch is
    visible in committed state — a clean resubmit then succeeds."""
    store = StateStore()
    node = mock.node()
    store.upsert_node(1, node)

    fail_once = {"armed": True}

    def flaky_commit(applied):
        if fail_once["armed"]:
            fail_once["armed"] = False
            raise RuntimeError("raft apply lost leadership mid-fsync")
        idx = store.latest_index + 1
        if isinstance(applied, list):
            store.upsert_plan_results_many(idx, applied)
        else:
            store.upsert_plan_results(idx, applied)
        return idx

    applier = PlanApplier(store, commit_fn=flaky_commit)
    applier.batch_n = 4
    queue = PlanQueue()
    queue.set_enabled(True)
    stop = threading.Event()
    t = threading.Thread(target=applier.run_loop, args=(queue, stop),
                         daemon=True)
    t.start()
    try:
        rng = np.random.default_rng(3)
        plans = [_rand_plan(rng, [node], k) for k in range(3)]
        pendings = [queue.enqueue(copy.deepcopy(p)) for p in plans]
        evaluated = [p.evaluated.result(timeout=30) for p in pendings]
        assert any(r.node_allocation for r in evaluated)
        for pend in pendings:
            with pytest.raises(RuntimeError, match="mid-fsync"):
                pend.future.result(timeout=30)
        # nothing from the failed wave landed
        assert len(store._allocs) == 0
        # overlay drained — the next evaluation sees clean state
        deadline = time.time() + 5
        while time.time() < deadline and applier._overlay:
            time.sleep(0.01)
        assert not applier._overlay

        # the crash-redelivery path: resubmitting the same plans (same
        # plan_id) now commits cleanly
        retry = [queue.enqueue(copy.deepcopy(p)) for p in plans]
        results = [p.future.result(timeout=30) for p in retry]
        committed = sum(len(v) for r in results
                        for v in r.node_allocation.values())
        assert committed == len(store._allocs) > 0
    finally:
        stop.set()
        t.join(5)


def test_commit_failure_releases_engine_tickets():
    """The applier's commit-failure path must hand back the scheduler's
    engine tickets (the pipelined submitter skipped its early release),
    or a failed wave leaks phantom usage into every later dispatch."""
    from nomad_tpu.parallel import engine as engine_mod

    store = StateStore()
    node = mock.node()
    store.upsert_node(1, node)

    eng = engine_mod.PlacementEngine()
    with engine_mod._engine_lock:
        prev, engine_mod._engine = engine_mod._engine, eng
    try:
        cm = store.matrix
        ticket = eng.register_external(
            cm, [(0, np.ones(cm.used.shape[1], np.float32))])
        assert eng._tickets

        def bad_commit(applied):
            raise RuntimeError("commit exploded")

        applier = PlanApplier(store, commit_fn=bad_commit)
        queue = PlanQueue()
        queue.set_enabled(True)
        stop = threading.Event()
        t = threading.Thread(target=applier.run_loop,
                             args=(queue, stop), daemon=True)
        t.start()
        try:
            plan = _rand_plan(np.random.default_rng(5), [node], 0)
            plan.engine_tickets = [ticket]
            pend = queue.enqueue(plan)
            with pytest.raises(RuntimeError, match="exploded"):
                pend.future.result(timeout=30)
            deadline = time.time() + 5
            while time.time() < deadline and eng._tickets:
                time.sleep(0.01)
            assert not eng._tickets, \
                "failed commit leaked the engine overlay ticket"
        finally:
            stop.set()
            t.join(5)
    finally:
        with engine_mod._engine_lock:
            engine_mod._engine = prev
        eng.stop()


# ----------------------------------------------------- worker deferred settle

class _FakeBrokerServer:
    """Just enough server surface for Worker._settle_eval."""

    def __init__(self):
        self.acked, self.nacked, self.updated = [], [], []
        self.eval_feeder = None

    class _Broker:
        def __init__(self, outer):
            self.outer = outer

        def ack(self, eval_id, token):
            self.outer.acked.append((eval_id, token))
            return True

        def nack(self, eval_id, token):
            self.outer.nacked.append((eval_id, token))
            return True

    @property
    def broker(self):
        return self._Broker(self)

    def update_eval(self, ev):
        self.updated.append(ev)


def test_worker_settle_discards_on_commit_failure():
    from concurrent.futures import Future

    from nomad_tpu.core.plan_queue import PendingPlan
    from nomad_tpu.core.worker import Worker

    srv = _FakeBrokerServer()
    w = Worker.__new__(Worker)           # skip thread/env plumbing
    w.server = srv
    w.stats = {"processed": 0, "failed": 0,
               "pipelined_evals": 0, "pipeline_discards": 0}

    ev = _eval()
    pend = PendingPlan.__new__(PendingPlan)
    pend.future = Future()
    pend.future.set_exception(RuntimeError("commit failed"))
    w._settle_eval(ev, "tok-1", [pend], time.perf_counter())
    assert srv.nacked == [(ev.id, "tok-1")]
    assert not srv.acked and not srv.updated
    assert w.stats["pipeline_discards"] == 1

    ok = PendingPlan.__new__(PendingPlan)
    ok.future = Future()
    ok.future.set_result(object())
    ev2 = _eval(job="j2")
    w._settle_eval(ev2, "tok-2", [ok], time.perf_counter())
    assert srv.acked == [(ev2.id, "tok-2")]
    assert srv.updated and srv.updated[0] is ev2
    assert w.stats["processed"] == 1
    assert w.stats["pipelined_evals"] == 1


def test_expired_lease_behind_stalled_commit_settles_exactly_once():
    """A lease that expires while its eval's settle sits pipelined behind
    a stalled commit must auto-nack and redeliver exactly ONCE, and the
    late settle with the stale token must be a no-op against the real
    broker — the redelivered lease is the only one that ever settles."""
    from concurrent.futures import Future

    from nomad_tpu.core.plan_queue import PendingPlan
    from nomad_tpu.core.worker import Worker

    broker = EvalBroker(nack_timeout=0.1, initial_nack_delay=60.0)
    broker.set_enabled(True)

    class _Srv:
        def __init__(self, broker):
            self.broker = broker
            self.updated = []

        def update_eval(self, ev):
            self.updated.append(ev)

    srv = _Srv(broker)
    w = Worker.__new__(Worker)           # skip thread/env plumbing
    w.server = srv
    w.stats = {"processed": 0, "failed": 0,
               "pipelined_evals": 0, "pipeline_discards": 0}

    ev = _eval()
    broker.enqueue(ev)
    got, stale_token = broker.dequeue(["batch"], timeout=1.0)
    assert got is not None and got.id == ev.id

    # the commit this settle waits on is stalled: park the settle on an
    # unresolved future in a thread, exactly like the pipelined worker
    stalled = PendingPlan.__new__(PendingPlan)
    stalled.future = Future()
    settle = threading.Thread(
        target=w._settle_eval,
        args=(got, stale_token, [stalled], time.perf_counter()),
        daemon=True)
    settle.start()

    # the lease expires under the parked settle; the broker's timer poll
    # auto-nacks (requeue_now: the expiry already cost nack_timeout) and
    # the eval redelivers exactly once, under a FRESH token
    deadline = time.time() + 5
    ev2, fresh_token = None, ""
    while time.time() < deadline and ev2 is None:
        ev2, fresh_token = broker.dequeue(["batch"], timeout=0.05)
    assert ev2 is not None and ev2.id == ev.id
    assert fresh_token != stale_token
    assert broker.stats["nacked"] == 1
    # only the fresh lease is live: the stale token must not be reported
    assert broker.outstanding(ev.id) == fresh_token

    # the stalled commit finally lands; the parked settle wakes with the
    # STALE token and must not settle: the ack is refused, nothing is
    # counted, and the fresh lease stays outstanding
    stalled.future.set_result(object())
    settle.join(5)
    assert not settle.is_alive()
    assert w.stats["processed"] == 0
    assert w.stats["pipelined_evals"] == 0
    assert broker.stats["acked"] == 0
    assert broker.outstanding(ev.id) == fresh_token

    # the redelivered lease settles exactly once
    landed = PendingPlan.__new__(PendingPlan)
    landed.future = Future()
    landed.future.set_result(object())
    w._settle_eval(ev2, fresh_token, [landed], time.perf_counter())
    assert w.stats["processed"] == 1
    assert broker.stats["acked"] == 1
    assert broker.stats["nacked"] == 1       # exactly one redelivery, ever
    assert broker.outstanding(ev.id) is None
    # nothing left behind: no duplicate copy ever re-enters the queue
    again, _ = broker.dequeue(["batch"], timeout=0.1)
    assert again is None
    assert broker.unacked_count() == 0


# ------------------------------------------- settle at the commit's landing

class _GatedBroker(EvalBroker):
    """A real broker whose WAITING dequeue parks until an eval arrives or
    the test's teardown sets `released`, however long that takes: never
    the clock.  What was asked, and how, is kept in `asked`."""

    def __init__(self):
        super().__init__(nack_timeout=60.0, initial_nack_delay=60.0)
        self.set_enabled(True)
        self.asked = []
        self.released = threading.Event()

    def dequeue(self, schedulers, timeout=0.0):
        self.asked.append(timeout)
        while True:
            got = super().dequeue(schedulers, timeout=min(timeout, 0.05))
            if got[0] is not None or timeout <= 0 or \
                    self.released.is_set():
                return got


class _HeldCommitServer:
    """Server surface for `Worker.run` with the applier faked: every
    submitted plan is evaluated at once and its durable commit is the
    test's to land (`plans[i].future`)."""

    name = "t"
    eval_feeder = None
    latest_index = 0

    def __init__(self):
        self.broker = _GatedBroker()
        self.store = self
        self.plans, self.updated = [], []

    def snapshot_min_index(self, index):
        return object()

    def enqueue_plan(self, plan):
        from nomad_tpu.core.plan_queue import PendingPlan
        pend = PendingPlan(plan)
        pend.evaluated.set_result(object())
        self.plans.append(pend)
        return pend

    def update_eval(self, ev):
        self.updated.append(ev)


class _OnePlanScheduler:
    def __init__(self, planner):
        self.planner = planner

    def process(self, ev):
        self.planner.submit_plan(Plan(eval_id=ev.id, job=mock.job()))


def _until(cond, timeout=10.0):
    deadline = time.time() + timeout
    while time.time() < deadline and not cond():
        time.sleep(0.005)
    return cond()


@pytest.fixture
def lone_worker(monkeypatch):
    """A started local worker over `_HeldCommitServer`, its scheduler
    replaced by one that submits one plan an eval."""
    from nomad_tpu.core import worker as worker_mod

    monkeypatch.setattr(
        worker_mod.factory, "new_scheduler",
        lambda _type, _snap, planner: _OnePlanScheduler(planner))
    srv = _HeldCommitServer()
    w = worker_mod.Worker(srv, enabled_schedulers=["batch"])
    assert w.pipeline_depth > 0
    w.start()
    yield srv, w
    for pend in srv.plans:               # let a failed test's worker go
        if not pend.future.done():
            pend.future.set_result(object())
    w.stop()
    srv.broker.released.set()
    w.join(10.0)
    assert not w._thread.is_alive()


def _defer_one(srv, ev):
    """Enqueue `ev` and wait until its worker, the commit held, has asked
    the broker once more without waiting: it now waits on the commit."""
    n_plans = len(srv.plans)
    srv.broker.enqueue(ev)
    assert _until(lambda: len(srv.plans) > n_plans)
    asked = len(srv.broker.asked)
    assert _until(lambda: 0.0 in srv.broker.asked[asked:]), \
        "the worker parked in a waiting dequeue with an eval deferred"
    return srv.plans[-1]


def test_lone_eval_settles_when_its_commit_lands(lone_worker, monkeypatch):
    """(fails on the parent of PR 31) With nothing else in the broker a
    deferred eval is COMPLETE and acked as soon as its commit resolves:
    the worker is not parked in its next dequeue for the timeout."""
    from nomad_tpu import tracing
    from nomad_tpu.structs import EvalStatus

    srv, w = lone_worker
    recorded = []
    record = tracing.record

    def recording(name, start, end, **kw):
        recorded.append((name, start, end, kw))
        record(name, start, end, **kw)

    monkeypatch.setattr(tracing, "record", recording)

    ev = _eval()
    pend = _defer_one(srv, ev)
    assert not srv.updated and srv.broker.stats["acked"] == 0
    assert srv.broker.outstanding(ev.id)

    pend.future.set_result(object())
    assert _until(lambda: srv.broker.stats["acked"] == 1)
    assert [(e.id, e.status) for e in srv.updated] == \
        [(ev.id, EvalStatus.COMPLETE)]
    assert srv.broker.outstanding(ev.id) is None
    assert w.stats["processed"] == w.stats["pipelined_evals"] == 1
    # no waiting dequeue but the one that delivered the eval returned:
    # nothing the test did released a second one
    assert _until(lambda: srv.broker.asked[-1] > 0)
    assert [t for t in srv.broker.asked[:-1] if t > 0] == \
        srv.broker.asked[:1]
    # one settle-wait a deferred eval, a wait, from defer to COMPLETE
    waits = [r for r in recorded if r[0] == "worker.settle_wait"]
    assert len(waits) == 1
    _name, start, end, kw = waits[0]
    assert kw == {"wait": True} and start <= end


def test_lone_eval_is_nacked_when_its_commit_fails(lone_worker):
    srv, w = lone_worker
    ev = _eval()
    pend = _defer_one(srv, ev)
    pend.future.set_exception(RuntimeError("commit failed"))
    assert _until(lambda: srv.broker.stats["nacked"] == 1)
    assert w.stats["pipeline_discards"] == 1
    assert not srv.updated and srv.broker.stats["acked"] == 0
    assert w.stats["processed"] == 0


def test_stalled_commit_does_not_keep_its_worker_from_a_new_eval(
        lone_worker):
    """The wait on a commit is a bounded slice: an eval enqueued while
    the commit stalls is taken at the worker's next ask, and both settle
    in order once their commits land."""
    srv, w = lone_worker
    first = _eval(job="j1")
    stalled = _defer_one(srv, first)
    asked = len(srv.broker.asked)
    second = _eval(job="j2")
    srv.broker.enqueue(second)
    assert _until(lambda: len(srv.plans) == 2)
    # at most the ask in flight when it arrived, and the next one
    assert len(srv.broker.asked) <= asked + 2
    assert srv.broker.asked[asked:] == \
        [0.0] * (len(srv.broker.asked) - asked)
    assert not srv.updated, "the stalled commit's eval settled early"

    srv.plans[1].future.set_result(object())
    stalled.future.set_result(object())
    assert _until(lambda: srv.broker.stats["acked"] == 2)
    assert [e.id for e in srv.updated] == [first.id, second.id]
    assert w.stats["pipelined_evals"] == 2


def test_stop_during_the_commit_wait_settles_what_is_deferred(lone_worker):
    srv, w = lone_worker
    ev = _eval()
    pend = _defer_one(srv, ev)
    w.stop()
    assert not srv.updated
    pend.future.set_result(object())
    w.join(10.0)
    assert not w._thread.is_alive()
    assert [e.id for e in srv.updated] == [ev.id]
    assert srv.broker.stats["acked"] == 1
    assert srv.broker.unacked_count() == 0


def test_remote_worker_never_defers_and_keeps_its_long_poll(monkeypatch):
    """`RemoteWorker` shares `run()`; its `submit_plan` blocks on the
    `Plan.Submit` RPC, so nothing is deferred, its evals settle inline
    and every `Eval.Dequeue` stays the long poll it was."""
    from nomad_tpu.core import worker as worker_mod

    monkeypatch.setattr(
        worker_mod.factory, "new_scheduler",
        lambda _type, _snap, planner: _OnePlanScheduler(planner))

    class _Member(_HeldCommitServer):
        def __init__(self):
            super().__init__()
            self.calls = []
            self.ev = _eval()
            self.idle = threading.Event()

        def rpc_leader(self, method, args):
            self.calls.append((method, args.get("timeout")))
            if method == "Eval.Dequeue":
                if self.ev is None:
                    self.idle.wait(args["timeout"])
                    return None
                ev, self.ev = self.ev, None
                return {"eval": ev, "token": "tok", "wait_index": 0}
            return {"Plan.Submit": object(), "Eval.Ack": {"ok": True}}[method]

    srv = _Member()
    ev = srv.ev
    w = worker_mod.RemoteWorker(srv, enabled_schedulers=["batch"])
    w.start()
    try:
        assert _until(lambda: [m for m, _ in srv.calls].count(
            "Eval.Dequeue") >= 4)
    finally:
        w.stop()
        srv.idle.set()
        w.join(10.0)
    assert not w._thread.is_alive()
    assert [e.id for e in srv.updated] == [ev.id]
    assert [m for m, _ in srv.calls[:3]] == \
        ["Eval.Dequeue", "Plan.Submit", "Eval.Ack"]
    assert {t for m, t in srv.calls if m == "Eval.Dequeue"} == \
        {worker_mod.DEQUEUE_TIMEOUT}
    assert not srv.plans and not w._deferred
    assert w.stats["processed"] == 1 and w.stats["pipelined_evals"] == 0
