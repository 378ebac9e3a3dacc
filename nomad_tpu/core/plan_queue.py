"""Leader-side plan queue (reference: nomad/plan_queue.go).

Workers submit plans; the single plan-apply loop pops them in priority
order.  Each pending plan carries a future the submitting worker blocks on.
"""
from __future__ import annotations

import heapq
import itertools
import threading
import time
from concurrent.futures import Future
from typing import List, Optional, Tuple

from nomad_tpu import deadline, tracing
from nomad_tpu.structs.plan import Plan


class LeadershipLostError(Exception):
    """Raised to plan submitters when the plan queue is torn down by a
    leadership transition (reference: plan submission RPCs erroring when
    the leader's planQueue is disabled, plan_queue.go SetEnabled)."""


class PendingPlan:
    # enqueued: perf_counter at enqueue, always — the applier records
    # the queue wait of every plan from it.  ctx: the submitter's
    # sampled trace context, else None — the applier stitches
    # queue-wait/evaluate/raft spans under it
    #
    # `evaluated` resolves with the PlanResult as soon as the applier has
    # validated the plan and registered its overlay — before the raft
    # append + fsync lands.  A pipelined worker continues scheduling off
    # this future while `future` (the durable commit) is still in
    # flight; if the commit later fails, `future` carries the error and
    # the worker discards the speculative continuation.
    # deadline: the submitter's absolute monotonic deadline (or None),
    # stamped at enqueue — the applier refuses an already-expired plan
    # BEFORE paying the raft append + fsync for it
    __slots__ = ("plan", "future", "evaluated", "enqueued", "ctx",
                 "deadline")

    def __init__(self, plan: Plan):
        self.plan = plan
        self.future: Future = Future()
        self.evaluated: Future = Future()
        self.deadline = deadline.current()
        self.enqueued = time.perf_counter()
        self.ctx = tracing.current()


class PlanQueue:
    def __init__(self):
        self._lock = threading.Condition()
        self.enabled = False
        self._heap: List[Tuple[int, int, PendingPlan]] = []
        self._counter = itertools.count()
        self.stats = {"depth": 0}

    def set_enabled(self, enabled: bool) -> None:
        with self._lock:
            self.enabled = enabled
            if not enabled:
                for _, _, p in self._heap:
                    err = LeadershipLostError("plan queue disabled")
                    p.future.set_exception(err)
                    p.evaluated.set_exception(err)
                self._heap = []
            self._lock.notify_all()

    def enqueue(self, plan: Plan) -> PendingPlan:
        with self._lock:
            if not self.enabled:
                raise LeadershipLostError("plan queue is disabled")
            pending = PendingPlan(plan)
            heapq.heappush(self._heap, (-plan.priority, next(self._counter), pending))
            self.stats["depth"] = len(self._heap)
            self._lock.notify_all()
            return pending

    def dequeue(self, timeout: Optional[float] = None) -> Optional[PendingPlan]:
        with self._lock:
            if not self._lock.wait_for(lambda: self._heap or not self.enabled,
                                       timeout=timeout):
                return None
            if not self._heap:
                return None
            _, _, pending = heapq.heappop(self._heap)
            self.stats["depth"] = len(self._heap)
            return pending

    def dequeue_batch(self, max_n: int,
                      timeout: Optional[float] = None
                      ) -> List[PendingPlan]:
        """One blocking wait, then drain up to max_n queued plans in
        priority order.  The applier coalesces adjacent plans from a
        wide worker pool into one commit instead of one store/raft
        round trip per plan."""
        with self._lock:
            if not self._lock.wait_for(
                    lambda: self._heap or not self.enabled,
                    timeout=timeout):
                return []
            out: List[PendingPlan] = []
            while self._heap and len(out) < max_n:
                _, _, pending = heapq.heappop(self._heap)
                out.append(pending)
            self.stats["depth"] = len(self._heap)
            return out

    def depth(self) -> int:
        with self._lock:
            return len(self._heap)
