"""Scheduler worker (reference: nomad/worker.go — run:386,
dequeueEvaluation:437, snapshotMinIndex:537, invokeScheduler:553,
SubmitPlan:593-660).

Each worker loops: dequeue an eval (with lease token), wait for the state
store to catch up to the eval's index, invoke the scheduler via the
factory, then ack/nack.  The worker object is the scheduler's Planner:
plans go to the plan queue and the worker blocks on the applier's result.
"""
from __future__ import annotations

import concurrent.futures
import logging
import os
import random
import threading
import time
from collections import deque
from typing import List, Optional

from nomad_tpu import chaos, knobs, tracing
from nomad_tpu import deadline as request_deadline
from nomad_tpu.core.plan_queue import LeadershipLostError
from nomad_tpu.raft import NotLeaderError
from nomad_tpu.raft.transport import Unreachable
from nomad_tpu.rpc.endpoints import RpcError
from nomad_tpu.scheduler import factory
from nomad_tpu.structs import Evaluation, EvalStatus
from nomad_tpu.structs.plan import Plan, PlanResult
from nomad_tpu.telemetry import global_metrics

log = logging.getLogger(__name__)

# transient cluster errors: the eval should be redelivered, not failed.
# A raft-apply commit timeout (futures.TimeoutError) belongs here: the
# write may or may not have landed, which is the same ambiguity as a
# leadership loss, and redelivery resolves both the same way (the worker
# re-snapshots past the eval's index before scheduling again).
TRANSIENT_ERRORS = (NotLeaderError, LeadershipLostError, RpcError,
                    Unreachable, concurrent.futures.TimeoutError,
                    TimeoutError)

# how long an idle worker parks in one dequeue before it looks at its
# own state again (stop, deferred settles)
DEQUEUE_TIMEOUT = 0.1


def _submit_by_namespace(plan: Plan, seconds: float) -> None:
    """Per-namespace plan-submit latency: the fairness gate in the
    multi-tenant scenarios asserts on victim-tenant p99, not the global
    mix."""
    ns = (plan.job.namespace or "default") if plan.job else "default"
    global_metrics.add_sample(f"nomad.plan.submit.ns.{ns}", seconds * 1e3)


class Worker:
    def __init__(self, server, worker_id: int = 0,
                 enabled_schedulers: Optional[List[str]] = None):
        self.server = server
        self.id = worker_id
        self.enabled_schedulers = enabled_schedulers or \
            ["service", "batch", "system", "sysbatch"]
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._snapshot = None
        # store index the scheduling snapshot must reach before this
        # worker's current eval may be processed (set at dequeue)
        self._wait_index = 0
        # double-buffered commit pipeline (plan_apply.go:71-178 carried
        # to the worker side): with depth > 0, submit_plan returns at
        # applier-EVALUATE time (the PlanResult is final then; only
        # alloc_index lands later) and the eval's COMPLETE/ack settle is
        # deferred until the raft append + fsync finishes — so wave N+1
        # schedules and dispatches on-device while commit(N) is durably
        # landing.  Depth bounds how many evals may be settle-deferred
        # at once; 0 restores strict blocking submits.
        self.pipeline_depth = max(0, knobs.get_int(
            "NOMAD_TPU_PIPELINE_DEPTH"))
        # (ev, token, [PendingPlan], perf_counter at defer) awaiting
        # durable commit, oldest first
        self._deferred = deque()
        self._eval_pendings: List = []
        self.stats = {"processed": 0, "failed": 0,
                      "pipelined_evals": 0, "pipeline_discards": 0,
                      # dequeues served off the wave-aligned feeder
                      # buffer (vs direct broker passes): the supply
                      # side of the engine's wave-lane batching
                      "wave_dequeues": 0}

    # ------------------------------------------------------------- lifecycle

    def start(self) -> None:
        self._thread = threading.Thread(
            target=self.run, name=f"worker-{self.id}", daemon=True)
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()

    def join(self, timeout: float = 5.0) -> None:
        if self._thread:
            self._thread.join(timeout)

    def run(self) -> None:
        while not self._stop.is_set():
            # a worker that holds deferred evals only asks the broker,
            # and with nothing to run waits on its oldest commit instead:
            # parked in the dequeue, a lone eval's COMPLETE would sit out
            # the whole timeout behind a commit of a few milliseconds.
            # The wait is one dequeue timeout at most, so a stalled
            # commit keeps this worker from a new eval no longer than an
            # empty broker does.
            got = self._dequeue(0.0 if self._deferred else DEQUEUE_TIMEOUT)
            self._drain_deferred()
            if got is None:
                if self._deferred:
                    concurrent.futures.wait(
                        [p.future for p in self._deferred[0][2]],
                        timeout=DEQUEUE_TIMEOUT)
                continue
            ev, token = got
            if self._stop.is_set():
                # stop() landed while the dequeue was in flight: hand the
                # lease back so a live worker gets the eval now rather
                # than after the nack timeout
                try:
                    self._nack(ev.id, token)
                except TRANSIENT_ERRORS:
                    pass
                break
            try:
                self.process_eval(ev, token)
            except TRANSIENT_ERRORS:
                # leadership moved mid-eval (reference: the worker's RPCs
                # start failing and the eval is nacked for redelivery);
                # nack best-effort — the lease expires server-side anyway
                try:
                    self._nack(ev.id, token)
                except TRANSIENT_ERRORS:
                    pass
            except Exception:                       # noqa: BLE001
                # never let the worker thread die (reference workers live
                # for the life of the server, worker.go:386) — and hand
                # the lease back so the eval redelivers now, not at the
                # nack timeout
                log.exception("worker %s: unhandled error", self.id)
                try:
                    self._nack(ev.id, token)
                except TRANSIENT_ERRORS:
                    pass
        # settle every still-deferred eval before the thread exits —
        # a clean stop must not leave acked-nowhere leases to time out
        while self._deferred:
            self._settle_eval(*self._deferred.popleft())

    # ------------------------------------------------------ pipelined settle

    def _drain_deferred(self) -> None:
        """Settle deferred evals: everything whose commits already landed
        settles for free; beyond `pipeline_depth` outstanding, block on
        the oldest so the pipeline stays bounded."""
        while self._deferred:
            pendings = self._deferred[0][2]
            if len(self._deferred) <= self.pipeline_depth and \
                    not all(p.future.done() for p in pendings):
                return
            self._settle_eval(*self._deferred.popleft())

    def _settle_eval(self, ev: Evaluation, token: str,
                     pendings: List, deferred_at: float) -> None:
        """Deferred tail of process_eval: wait for the durable commits
        backing this eval's plans, then publish COMPLETE and ack.  If a
        commit failed mid-flight, the speculative result is discarded —
        the eval is nacked for redelivery and the re-process snapshots
        past whatever DID commit (`_wait_index`), so a partial landing
        never double-places (same contract as crash-after-commit)."""
        try:
            for p in pendings:
                p.future.result(timeout=600.0)
        except Exception:                           # noqa: BLE001
            # transient or real commit failure: identical discard path
            self.stats["pipeline_discards"] += 1
            try:
                self._nack(ev.id, token)
            except TRANSIENT_ERRORS:
                pass
            return
        if chaos.active is not None and chaos.should("worker.settle_drop"):
            # worker dies between commit and ack: the lease expires and
            # the redelivered eval no-ops via plan dedup
            return
        # deferred -> COMPLETE written: what the pipeline adds to an
        # eval's time once its scheduler has returned
        tracing.record("worker.settle_wait", deferred_at,
                       time.perf_counter(), wait=True)
        try:
            self.server.update_eval(ev)
            if self._ack(ev.id, token):
                self.stats["processed"] += 1
                self.stats["pipelined_evals"] += 1
        except TRANSIENT_ERRORS:
            try:
                self._nack(ev.id, token)
            except TRANSIENT_ERRORS:
                pass

    # -- broker ops, overridable for the RPC path (RemoteWorker)

    def _dequeue(self, timeout: float):
        feeder = getattr(self.server, "eval_feeder", None)
        if feeder is not None:
            # wave-aligned path: one pool member drains a whole ready
            # wave in one broker pass; the rest pick from the buffer
            got = feeder.get(self.enabled_schedulers, timeout=timeout)
            if got is None:
                return None
            ev, token = got
            self.stats["wave_dequeues"] += 1
        else:
            ev, token = self.server.broker.dequeue(
                self.enabled_schedulers, timeout=timeout)
            if ev is None:
                return None
        self._wait_index = self.server.store.latest_index
        self._trace_ctx = tracing.take_eval_ctx(ev.id)
        return ev, token

    def _ack(self, eval_id: str, token: str) -> bool:
        return self.server.broker.ack(eval_id, token)

    def _nack(self, eval_id: str, token: str) -> bool:
        return self.server.broker.nack(eval_id, token)

    # ------------------------------------------------------------- process

    def process_eval(self, ev: Evaluation, token: str) -> None:
        server = self.server
        # _wait_index covers redelivery: a plan may already have committed
        # for this eval (crash-after-commit nack, lease expiry, failover)
        # at an index past the eval's own, and scheduling from an older
        # snapshot would double-place the job
        with tracing.span("worker.snapshot_wait", wait=True,
                          node=server.name):
            snap = server.store.snapshot_min_index(
                max(ev.modify_index, ev.snapshot_index, self._wait_index))
        if snap is None:
            self._nack(ev.id, token)
            return
        self._snapshot = snap
        self._token = token
        self._eval_pendings = []
        ev = ev.copy()
        # the scheduler invocation is a span; a sampled eval's trace
        # context stays bound for its duration, so plan submission (and
        # any follow-up evals it creates) joins the trace
        with tracing.span(f"worker.invoke_scheduler.{ev.type}",
                          ctx=getattr(self, "_trace_ctx", None),
                          node=server.name):
            try:
                sched = factory.new_scheduler(ev.type, snap, self)
                sched.process(ev)
            except TRANSIENT_ERRORS:
                raise
            except Exception as e:                      # noqa: BLE001
                log.exception("eval %s failed", ev.id)
                self.stats["failed"] += 1
                ev.status = EvalStatus.FAILED
                ev.status_description = str(e)
                server.update_eval(ev)  # raises TRANSIENT -> run() nacks
                self._nack(ev.id, token)
                return
        ev.status = EvalStatus.COMPLETE
        pendings, self._eval_pendings = self._eval_pendings, []
        if pendings:
            # pipelined submits are still committing: defer the
            # COMPLETE/ack settle and move on to the next eval now
            self._deferred.append(
                (ev, token, pendings, time.perf_counter()))
            self._drain_deferred()
            return
        server.update_eval(ev)
        if self._ack(ev.id, token):
            self.stats["processed"] += 1

    # ------------------------------------------------------------- planner

    def submit_plan(self, plan: Plan) -> PlanResult:
        plan.eval_token = getattr(self, "_token", "")
        with tracing.span("plan.submit", wait=True,
                          node=self.server.name) as sp:
            pending = self.server.enqueue_plan(plan)
            if self.pipeline_depth > 0:
                # pipelined: return as soon as the applier has validated
                # the plan and registered its overlay — the PlanResult's
                # content is final at evaluate time (only alloc_index
                # lands post-commit, and the scheduler never reads it).
                # The durable commit settles later in _settle_eval; the
                # applier owns the engine-ticket release either way, so
                # the scheduler must skip its early free.
                res = pending.evaluated.result(timeout=600.0)
                plan.commit_inflight = True
                self._eval_pendings.append(pending)
            else:
                # generous: under full-cluster bursts (the 1M-alloc C2M)
                # the serialized applier legitimately backs up for
                # minutes; an eval failed on a timed-out future gets
                # retried from scratch even though its plan still
                # commits — pure wasted recompute
                res = pending.future.result(timeout=600.0)
        _submit_by_namespace(plan, sp.seconds)
        return res

    def create_evals(self, evals: List[Evaluation]) -> None:
        self.server.create_evals(evals)

    def update_eval(self, ev: Evaluation) -> None:
        self.server.update_eval(ev)

    def reblock_eval(self, ev: Evaluation) -> None:
        self.server.blocked_evals.block(ev)

    def refresh_snapshot(self, min_index: int = 0):
        with tracing.span("worker.snapshot_wait", wait=True,
                          node=self.server.name):
            snap = self.server.store.snapshot_min_index(min_index)
        self._snapshot = snap
        return snap


class RemoteWorker(Worker):
    """Worker on any cluster member: broker and plan-queue operations RPC
    to the leader (short-circuiting locally when this member IS the
    leader), while scheduling reads come from the local replicated
    snapshot — the reference's every-server worker pool (worker.go:81-85,
    Eval.Dequeue / Plan.Submit RPCs)."""

    # RpcError kinds worth retrying: the request was rejected before it
    # executed (election in progress / forwarded to a dead leader).  Any
    # other kind (stale_eval_token, internal, ...) is a real answer.
    _RETRYABLE_KINDS = frozenset({"no_leader", "not_leader"})

    def _rpc(self, method: str, args: dict, deadline: float = 5.0):
        """rpc_leader with exponential backoff + jitter across leadership
        churn.  Retried requests never double-execute: dequeue/ack/nack
        are lease-guarded and Plan.Submit dedups on plan_id."""
        dl = time.monotonic() + deadline
        # a bound end-to-end request deadline caps the retry budget:
        # churn is only worth riding out while someone still waits
        budget_dl = request_deadline.current()
        if budget_dl is not None:
            dl = min(dl, budget_dl)
        delay = 0.02
        while True:
            if budget_dl is not None and time.monotonic() >= budget_dl:
                request_deadline.expire("worker")
                raise RpcError("deadline_exceeded",
                               f"{method}: retry budget exhausted")
            try:
                return self.server.rpc_leader(method, args)
            except TRANSIENT_ERRORS as e:
                if isinstance(e, RpcError) and \
                        e.kind not in self._RETRYABLE_KINDS:
                    raise
                if self._stop.is_set() or time.monotonic() >= dl:
                    raise
                sleep = min(delay, max(0.0, dl - time.monotonic()))
                self._stop.wait(sleep * (0.5 + random.random() * 0.5))
                delay = min(delay * 2.0, 0.5)

    def _dequeue(self, timeout: float):
        try:
            resp = self._rpc("Eval.Dequeue",
                             {"schedulers": self.enabled_schedulers,
                              "timeout": timeout})
        except TRANSIENT_ERRORS:
            self._stop.wait(0.05)
            return None
        if resp is None:
            return None
        self._wait_index = resp.get("wait_index", 0)
        self._trace_ctx = resp.get("trace")
        return resp["eval"], resp["token"]

    def _ack(self, eval_id: str, token: str) -> bool:
        return self._rpc("Eval.Ack",
                         {"eval_id": eval_id, "token": token})["ok"]

    def _nack(self, eval_id: str, token: str) -> bool:
        # bounded retry: a prompt nack redelivers in seconds where the
        # lease-expiry fallback costs the full nack_timeout
        delay = 0.02
        for attempt in range(3):
            try:
                return self._rpc("Eval.Nack",
                                 {"eval_id": eval_id, "token": token},
                                 deadline=1.0)["ok"]
            except TRANSIENT_ERRORS:
                if attempt == 2 or self._stop.is_set():
                    break
                self._stop.wait(delay * (0.5 + random.random() * 0.5))
                delay = min(delay * 2.0, 0.25)
        return False   # lease expires server-side; eval redelivers

    def submit_plan(self, plan: Plan) -> PlanResult:
        plan.eval_token = getattr(self, "_token", "")
        # the submit span covers RPC + leader-side queue + apply; a
        # sampled span's child context rides the args (restamp) so the
        # leader's Plan.Submit handler binds it for the enqueue ->
        # applier -> raft chain
        with tracing.span("plan.submit", wait=True,
                          node=self.server.name) as sp:
            res = self._rpc("Plan.Submit", {"plan": plan})
        _submit_by_namespace(plan, sp.seconds)
        return res

    def reblock_eval(self, ev: Evaluation) -> None:
        self._rpc("Eval.Reblock", {"eval": ev})
