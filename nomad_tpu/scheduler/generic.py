"""Generic (service/batch) scheduler over the dense placement engine.

Reference: scheduler/generic_sched.go — Process:144, process:242,
computeJobAllocs:358, computePlacements:499-679, findPreferredNode:783,
blocked-eval creation:219-238.  The reconcile step is host-side
(nomad_tpu.scheduler.reconcile); every placement decision for an eval runs
as ONE dense kernel call (ops.place) instead of per-node iterator pulls.
"""
from __future__ import annotations

import time as _time

from nomad_tpu.utils import generate_uuid
from typing import Dict, List, Optional, Set, Tuple

import numpy as np

from nomad_tpu import native, tracing
from nomad_tpu.parallel.engine import get_engine

from nomad_tpu.scheduler.preemption import Preemptor
from nomad_tpu.scheduler.placement import (
    PortClaims, allocs_leave, build_allocation, materialize_bulk_allocs)
from nomad_tpu.scheduler.reconcile import AllocReconciler, PlacementRequest
from nomad_tpu.scheduler.stack import (
    CompiledGroup, DenseStack, DistinctCarry)
from nomad_tpu.scheduler.util import (
    adjust_queued_allocations, progress_made, tainted_nodes)
from nomad_tpu.structs import Allocation, Evaluation, EvalStatus, Job
from nomad_tpu.structs.alloc import AllocMetric
from nomad_tpu.structs.evaluation import EvalTrigger
from nomad_tpu.structs.plan import Plan, PlanResult

MAX_SERVICE_SCHEDULE_ATTEMPTS = 5   # generic_sched.go:19-23
MAX_BATCH_SCHEDULE_ATTEMPTS = 2

BLOCKED_EVAL_MAX_PLAN_DESC = "created due to placement conflicts"
BLOCKED_EVAL_FAILED_PLACEMENT_DESC = "created to place remaining allocations"
BLOCKED_EVAL_QUOTA_DESC = "created due to quota limit"

# slots of one group in one eval from which the wavefront kernel takes them
BULK_MIN = 2


class SetStatusError(Exception):
    def __init__(self, desc: str):
        super().__init__(desc)
        self.desc = desc


class GenericScheduler:
    """One instance per eval invocation (the reference constructs a fresh
    scheduler per Process call via the factory)."""

    batch = False

    def __init__(self, state, planner):
        self.state = state            # StateSnapshot-like read view
        self.planner = planner        # Planner: submit_plan/create_evals/...
        self.eval: Optional[Evaluation] = None
        self.job: Optional[Job] = None
        self.plan: Optional[Plan] = None
        self.plan_result: Optional[PlanResult] = None
        self.deployment = None
        self.queued_allocs: Dict[str, int] = {}
        self.failed_tg_allocs: Dict[str, AllocMetric] = {}
        self.blocked: Optional[Evaluation] = None
        self.followup_evals: List[Evaluation] = []
        # bool[N], left by the last placement pass for _class_eligibility
        self._last_feasible_union: Optional[np.ndarray] = None

    # ------------------------------------------------------------- process

    def process(self, ev: Evaluation) -> None:
        self.eval = ev
        limit = MAX_BATCH_SCHEDULE_ATTEMPTS if self.batch \
            else MAX_SERVICE_SCHEDULE_ATTEMPTS
        attempts = 0
        while attempts < limit:
            done, made_progress = self._attempt()
            if done:
                return
            qname = self.plan_result.quota_limit_reached \
                if self.plan_result is not None else ""
            if qname:
                # over-quota placements were dropped by the applier's
                # quota filter; retrying cannot help until the namespace
                # quota is raised or usage drains — block keyed on the
                # quota so the spec-upsert hook releases this eval
                blocked = self._make_blocked_eval(BLOCKED_EVAL_QUOTA_DESC)
                blocked.quota_limit_reached = qname
                self.planner.create_evals([blocked])
                self.eval.queued_allocations = dict(self.queued_allocs)
                self.eval.blocked_eval = blocked.id
                return
            # a partial commit that made progress resets the retry budget
            # (reference retryMax's reset hook + progressMade, util.go:391-425)
            attempts = 0 if made_progress else attempts + 1
            snap = self.planner.refresh_snapshot(
                self.plan_result.refresh_index if self.plan_result else 0)
            if snap is None:
                raise SetStatusError("timed out refreshing state snapshot")
            self.state = snap
        # exhausted plan attempts: roll over into a blocked eval
        if not self.batch:
            blocked = self._make_blocked_eval(BLOCKED_EVAL_MAX_PLAN_DESC,
                                              triggered_by=EvalTrigger.MAX_PLANS)
            self.planner.create_evals([blocked])
        raise SetStatusError("maximum attempts reached")

    # ------------------------------------------------------------- attempt

    def _attempt(self) -> bool:
        ev = self.eval
        self.job = self.state.job_by_id(ev.namespace, ev.job_id)
        self.failed_tg_allocs = {}
        self.followup_evals = []

        stopped = self.job is None or self.job.stopped()
        self.deployment = None
        if not stopped:
            self.deployment = self.state.latest_deployment_by_job_id(
                ev.namespace, ev.job_id)

        allocs = self.state.allocs_by_job(ev.namespace, ev.job_id)
        tainted = tainted_nodes(self.state, allocs)

        self.plan = ev.make_plan(self.job)
        if ev.annotate_plan:
            from nomad_tpu.structs.plan import PlanAnnotations
            self.plan.annotations = PlanAnnotations()

        reconciler = AllocReconciler(
            job=None if stopped else self.job,
            job_id=ev.job_id,
            existing=allocs,
            tainted_nodes=tainted,
            deployment=self.deployment,
            eval_id=ev.id,
            batch=self.batch,
            eval_priority=ev.priority,
        )
        with tracing.span("sched.reconcile", cpu=True):
            results = reconciler.compute()
        self._plan_reconciled(results)

        placing = PlacementPass(self) \
            if not stopped and results.place else None
        try:
            if placing is not None:
                self._compute_placements(placing, results.place, results.stop
                                         + results.destructive_stop, allocs)

            if self.plan.is_no_op():
                self._finish_eval()
                return True, False

            # the applier releases these overlay tickets atomically with
            # the commit; close() below is only the abandoned-plan safety
            # net (complete() is idempotent)
            if placing is not None:
                self.plan.engine_tickets = list(placing.tickets)

            self.plan_result = self.planner.submit_plan(self.plan)
        finally:
            if placing is not None:
                placing.close(
                    handed_over=getattr(self.plan, "commit_inflight", False))
        adjust_queued_allocations(self.plan_result, self.queued_allocs)

        full, expected, actual = self.plan_result.full_commit(self.plan)
        if not full:
            return False, progress_made(self.plan_result)
        self._finish_eval()
        return True, True

    def _plan_reconciled(self, results) -> None:
        """Everything of the reconciler's results but the placements goes
        into the plan as it stands."""
        # follow-up (delayed) evals must exist before allocs reference them
        for evs in results.desired_followup_evals.values():
            self.followup_evals.extend(evs)
        if self.followup_evals:
            self.planner.create_evals(self.followup_evals)

        # stops / destructive stops
        for sr in results.stop + results.destructive_stop:
            self.plan.append_stopped_alloc(
                sr.alloc, sr.status_description, sr.client_status,
                sr.followup_eval_id)

        # in-place updates / attribute-only updates ride the plan as
        # same-node allocations
        for a in results.inplace_update:
            self.plan.append_alloc(a, self.job)
        for updates in (results.attribute_updates, results.disconnect_updates,
                        results.reconnect_updates):
            for a in updates.values():
                self.plan.append_alloc(a, a.job)

        # deployment changes
        if results.deployment is not None:
            self.plan.deployment = results.deployment
        self.plan.deployment_updates = results.deployment_updates

        if results.desired_tg_updates and self.plan.annotations is not None:
            self.plan.annotations.desired_tg_updates = results.desired_tg_updates

        # queued = placements desired this pass
        self.queued_allocs = {tg.name: 0 for tg in
                              (self.job.task_groups if self.job else [])}
        for pr in results.place:
            self.queued_allocs[pr.task_group] = \
                self.queued_allocs.get(pr.task_group, 0) + 1

    # ------------------------------------------------------------- finish

    def _finish_eval(self) -> None:
        ev = self.eval
        ev.queued_allocations = dict(self.queued_allocs)
        if self.failed_tg_allocs and self.blocked is None:
            blocked = self._make_blocked_eval(BLOCKED_EVAL_FAILED_PLACEMENT_DESC)
            blocked.status = EvalStatus.BLOCKED
            self.blocked = blocked
            self.planner.create_evals([blocked])
            ev.blocked_eval = blocked.id

    def _make_blocked_eval(self, desc: str, triggered_by: str = "") -> Evaluation:
        ev = self.eval
        classes, escaped = self._class_eligibility()
        return Evaluation(
            id=generate_uuid(),
            namespace=ev.namespace,
            priority=ev.priority,
            type=ev.type,
            triggered_by=triggered_by or EvalTrigger.QUEUED_ALLOCS,
            job_id=ev.job_id,
            status=EvalStatus.BLOCKED,
            status_description=desc,
            previous_eval=ev.id,
            class_eligibility=classes,
            escaped_computed_class=escaped,
            snapshot_index=getattr(self.state, "index", 0),
        )

    def _class_eligibility(self) -> Tuple[Dict[str, bool], bool]:
        """Which computed node classes were feasible (for unblock-on-capacity
        keying; reference EvalEligibility, context.go:252-420) — a
        vectorized groupby over the matrix's per-row class codes instead
        of the reference's per-node memoized walk."""
        classes: Dict[str, bool] = {}
        escaped = False
        if self.job is None:
            return classes, True
        for c in self.job.constraints:
            if "unique." in c.ltarget or "unique." in c.rtarget:
                escaped = True
        # device asks are per-node capacity, not class-constant: with
        # every instance taken the whole class reads infeasible, and a
        # blocked eval keyed on that verdict would never release when
        # instances free up — escape class tracking instead
        for tg in self.job.task_groups:
            for t in tg.tasks:
                if t.resources.devices:
                    escaped = True
        cm = self.state.matrix
        codes = cm.class_codes
        n_classes = len(cm.class_names)
        if n_classes == 0:
            return classes, escaped
        valid = codes >= 0
        feas_union = self._last_feasible_union
        if feas_union is not None and feas_union.shape[0] < codes.shape[0]:
            # matrix grew since the stack compiled; unseen rows count as
            # infeasible for this eval's view
            grown = np.zeros(codes.shape[0], bool)
            grown[:feas_union.shape[0]] = feas_union
            feas_union = grown
        present = np.bincount(codes[valid], minlength=n_classes) > 0
        if feas_union is None:
            ok = present
        else:
            ok = np.bincount(codes[valid],
                             weights=feas_union[valid].astype(np.float64),
                             minlength=n_classes) > 0
        for c in np.flatnonzero(present):
            classes[cm.class_names[c]] = bool(ok[c])
        return classes, escaped

    # ------------------------------------------------------------- placing

    def _compute_placements(self, placing: "PlacementPass", places, stops,
                            all_allocs) -> None:
        """Device-requesting evals serialize through the engine's gate:
        instance picks race-free across workers (basis read, placement,
        id assignment and overlay registration are atomic), mirroring how
        bulk evals serialize.  Everything else runs concurrently."""
        if not any(t.resources.devices
                   for tg in self.job.task_groups for t in tg.tasks):
            placing.run(places, stops, all_allocs)
            return
        t_ask = _time.perf_counter()
        with placing.eng.bulk_gate:
            tracing.record("sched.device_gate", t_ask, _time.perf_counter(),
                           wait=True)
            placing.run(places, stops, all_allocs)
            placing.register_device_grants()

    def _fail_placement(self, pr: PlacementRequest, metric: AllocMetric,
                        reason: str) -> None:
        prev = self.failed_tg_allocs.get(pr.task_group)
        if prev is not None:
            prev.coalesced_failures += 1
        else:
            metric.dimension_exhausted[reason] = 1
            self.failed_tg_allocs[pr.task_group] = metric
        self.eval.queued_allocations = self.queued_allocs


class ServiceScheduler(GenericScheduler):
    batch = False


class BatchScheduler(GenericScheduler):
    batch = True


class PlacementPass:
    """One attempt's placements, from the reconciler's requests to the
    plan's allocations.  `run` is the order of its steps: prepare, split,
    place_bulk, scan, materialise.  Built once an attempt, outside the
    device gate; what the steps share is stated here."""

    def __init__(self, sched: GenericScheduler):
        state, job = sched.state, sched.job
        # ---- the attempt's constants
        self.state = state
        self.job = job
        self.plan = sched.plan
        self.eval_id = sched.eval.id
        self.deployment = sched.plan.deployment or sched.deployment
        self.sched = sched          # takes _last_feasible_union
        self.fail = sched._fail_placement
        self.eng = get_engine()
        self.cm = state.matrix
        self.stack = DenseStack(self.cm, state.scheduler_config,
                                snapshot=state)
        self.tg_index = {tg.name: i for i, tg in enumerate(job.task_groups)}
        # preemption for failed slots (BinPackIterator's evict path,
        # rank.go:500-530; gated by SchedulerConfiguration like the
        # reference's per-scheduler-type preemption config)
        self.preemption_on = state.scheduler_config.preemption_enabled(
            "batch" if sched.batch else "service")
        self.ports = PortClaims(self.cm, self.eval_id)
        self.now = 0.0              # stamped as materialise starts
        # ---- what moves
        # one CompiledGroup a task group; a one-by-one eval compiles its
        # device groups again between rounds
        self.groups: List[CompiledGroup] = []
        # proposed usage f32[N, R]: committed usage PLUS the engine's
        # in-flight overlay (placements of concurrently scheduled,
        # not-yet-committed plans), minus what this plan stops, plus what
        # it has placed; `deltas` mirrors the adjustments made before a
        # kernel pass sparsely for the batching engine
        self.used: Optional[np.ndarray] = None
        self.deltas: List[Tuple[int, np.ndarray]] = []
        self.freed_ports: Dict[int, Set[int]] = {}
        self.stopped_ids: Set[str] = set()
        # remaining allocs for anti-affinity / spread / distinct_*
        self.allocs_by_tg: Dict[str, List[Allocation]] = {}
        # distinct_hosts / distinct_property: started from those, takes
        # every placement of the plan (place_on); the kernel carries its
        # own copy through a pass, and a row the host picks itself
        # (sticky slot, device alternative, preemption) is asked of this
        self.distinct: Optional[DistinctCarry] = None
        self.penalty_nodes: Dict[str, Set[str]] = {}
        self.preemptor: Optional[Preemptor] = None
        self.preempt_cache: Dict[int, List] = {}    # group -> found, unused
        # every overlay ticket the engine gave this attempt (bulk groups,
        # scan passes, device grants), in the order taken.  An open ticket
        # is phantom usage on every later kernel pass of every worker:
        # close() is the one place they end
        self.tickets: List[int] = []

    def run(self, places: List[PlacementRequest], stops, all_allocs) -> None:
        preplaced, slots = self.prepare(places, stops, all_allocs)
        bulk, slots = self.split(slots)
        placed = self.place_bulk(bulk)
        # one kernel pass places every slot, unless a group's `devices`
        # score moves inside the pass (CompiledGroup.dev_multi_level): the
        # kernel's score is then right for the next placement only, so
        # such an eval goes one slot a pass and is re-scored from its own
        # grants in between (exact, and slow: a fleet with one card model
        # a node never takes this path)
        one_by_one = any(self.groups[self.tg_index[pr.task_group]]
                         .dev_multi_level for pr in slots)
        rounds = ([[pr] for pr in slots] if one_by_one
                  else [slots] if slots else [])
        first = self.scan(rounds[0]) if rounds else None
        self.materialise(preplaced, placed, rounds, first, one_by_one)

    def close(self, handed_over: bool) -> None:
        """The attempt is over: the plan is either committed into the
        cluster matrix or abandoned, and the pass releases its in-flight
        usage.  Exception (`handed_over`): a pipelined submit returned at
        evaluate time with the durable commit still in flight — there the
        applier owns the release (success: _post_commit; failure: the
        commit thread's error path), and freeing here would show phantom
        capacity to concurrent kernels before the write lands."""
        if self.tickets and not handed_over:
            self.eng.complete_many(self.tickets)
        self.tickets = []

    # ------------------------------------------------------------- prepare

    def prepare(self, places, stops, all_allocs):
        """Compiles the groups, reads the usage basis, takes the stops
        out of it and puts sticky slots back on their nodes.
        -> ([(request, row)] placed here, [request] still to place)"""
        cm, job, stack = self.cm, self.job, self.stack
        self.stopped_ids = {sr.alloc.id for sr in stops}
        for a in all_allocs:
            if a.id in self.stopped_ids or a.terminal_status():
                continue
            self.allocs_by_tg.setdefault(a.task_group, []).append(a)
        with tracing.span("sched.feasible", cpu=True):
            self.groups = [stack.compile_group(job, tg)
                           for tg in job.task_groups]
            self.distinct = DistinctCarry(cm, self.groups, self.allocs_by_tg)
        # constraint-only union, NOT g.feasible: readiness and capacity
        # are transient, and a blocked eval keyed on them would mark its
        # class ineligible forever (a down node or full device must not
        # veto the class the recovery will unblock)
        self.sched._last_feasible_union = np.any(
            np.stack([g.class_feasible for g in self.groups]), axis=0)

        self.used = self.eng.basis_for(cm) \
            if cm.used.shape[0] == cm.capacity.shape[0] else cm.used.copy()
        for sr in stops:
            a = sr.alloc
            row = cm.row_of.get(a.node_id)
            if row is not None:
                allocs_leave(self.used, row, (a,), self.freed_ports,
                             deltas=self.deltas)
        for pr in places:
            if pr.is_rescheduling and pr.previous_alloc is not None:
                self.penalty_nodes.setdefault(pr.task_group, set()).add(
                    pr.previous_alloc.node_id)
        # sticky ephemeral disk: prefer the previous node when feasible
        # (findPreferredNode, generic_sched.go:783)
        used, groups = self.used, self.groups
        preplaced: List[Tuple[PlacementRequest, int]] = []
        rest: List[PlacementRequest] = []
        for pr in places:
            gi = self.tg_index[pr.task_group]
            g = groups[gi]
            if (g.tg.ephemeral_disk.sticky and pr.previous_alloc is not None
                    and not pr.is_rescheduling):
                row = cm.row_of.get(pr.previous_alloc.node_id)
                if row is not None and g.feasible[row] \
                        and self.distinct.allows(gi, row) \
                        and np.all(used[row] + g.demand <= cm.capacity[row]):
                    used[row] += g.demand
                    self.deltas.append((row, g.demand.astype(np.float32)))
                    # taken now, not when it materialises: the slots
                    # after it and the kernel's pass have to see it
                    self.distinct.take(gi, row)
                    preplaced.append((pr, row))
                    continue
            rest.append(pr)
        return preplaced, rest

    # ------------------------------------------------- bulk wave and scan

    def split(self, slots):
        """-> ([(group index, its requests)] for the bulk wave, [request]
        for the scan): BULK_MIN identical slots or more that nothing
        couples place via the wavefront kernel in O(waves) steps instead
        of an O(slots) scan, the C2M-scale path (ops.place._place_bulk_batch)."""
        by_group: Dict[int, List[PlacementRequest]] = {}
        for pr in slots:
            by_group.setdefault(self.tg_index[pr.task_group], []).append(pr)
        bulk, scan = [], []
        for gi, prs in by_group.items():
            if len(prs) >= BULK_MIN and self.groups[gi].uncoupled:
                bulk.append((gi, prs))
            else:
                scan.extend(prs)
        return bulk, scan

    def place_bulk(self, bulk) -> List[tuple]:
        """Submits EVERY bulk group before waiting, so a many-small-group
        job (the C2M-1M shape: 10 groups x count 10) is ONE chained
        device dispatch batched with other workers' evals, not one
        blocking round trip per group; FIFO + the engine's
        resolve-before-next-dispatch keep group g+1 scoring against g's
        placements.  Then folds what was placed into `used`.
        -> [(group index, its requests, the rows that took some, how many
        each, scores f32[N], nodes evaluated, nodes exhausted)]"""
        if not bulk:
            return []
        pending = [(gi, prs, self._bulk_begin(self.groups[gi], len(prs)))
                   for gi, prs in bulk]
        resolved = []
        with tracing.span("sched.wait_engine", wait=True):
            for gi, prs, fut in pending:
                *res, ticket = fut.result()
                resolved.append((gi, prs, res))
                if ticket is not None:
                    self.tickets.append(ticket)
        # cumulative usage for the scan path + host bookkeeping: apply
        # EVERY bulk group's placements (engine dispatch may reorder
        # parts, so no single returned matrix is complete; the engine
        # itself sees this usage through the overlay tickets)
        placed = []
        for gi, prs, (assign, _placed, n_eval, n_exh, scores) in resolved:
            rows = np.flatnonzero(assign)
            counts = assign[rows]
            native.scatter_add_rank1(
                self.used, rows, counts,
                self.groups[gi].demand.astype(np.float32))
            placed.append((gi, prs, rows, counts, scores, n_eval, n_exh))
        return placed

    def _bulk_begin(self, g: CompiledGroup, count: int):
        """Enqueue one group's wavefront placement; returns the engine
        Future (see engine.place_bulk_begin for ordering semantics)."""
        cm = self.cm
        penalty = np.zeros(cm.n_rows, bool)
        for nid in self.penalty_nodes.get(g.tg.name, ()):
            row = cm.row_of.get(nid)
            if row is not None:
                penalty[row] = True
        coll0 = np.zeros(cm.n_rows, np.int32)
        for a in self.allocs_by_tg.get(g.tg.name, ()):
            row = cm.row_of.get(a.node_id)
            if row is not None:
                coll0[row] += 1
        return self.eng.place_bulk_begin(
            cm, feasible=g.feasible, affinity=g.affinity,
            has_affinity=g.has_affinity, desired=max(g.tg.count, 1),
            penalty=penalty, coll0=coll0, demand=g.demand,
            count=count, deltas=self.deltas,
            spread_algorithm=self.stack.spread_algorithm,
            # namespace = wave-lane key: evals from different namespaces
            # are independent waves and may score concurrently on the
            # 2-D mesh's wave columns
            wave_key=self.job.namespace)

    def scan(self, prs):
        """One kernel pass over the slots `prs`, routed through the
        process-wide engine so concurrent evals coalesce into one device
        dispatch; `deltas` is already applied to `used` (the engine
        re-applies it to a dispatch-time basis in the batched path)."""
        with tracing.span("sched.feasible", cpu=True):
            inputs = self.stack.build_inputs(
                self.job, self.groups,
                [self.tg_index[pr.task_group] for pr in prs],
                self.allocs_by_tg, penalty_nodes=self.penalty_nodes,
                used_override=self.used, distinct=self.distinct)
        result, ticket = self.eng.place(
            self.cm, inputs, self.deltas,
            spread_algorithm=self.stack.spread_algorithm)
        if ticket is not None:
            self.tickets.append(ticket)
        return result

    def rescore(self, prs):
        """The next pass of a one-by-one eval: the earlier passes'
        tickets stay open, this eval's grants come out of the free
        counts, and the device groups are compiled again."""
        grants: Dict[str, np.ndarray] = {}
        for gid, row, count in self.device_grants():
            grants.setdefault(
                gid, np.zeros(self.cm.n_rows, np.int64))[row] += count
        self.stack.device_grants = grants
        with tracing.span("sched.feasible", cpu=True):
            for gi, g in enumerate(self.groups):
                if g.device_asks:
                    self.groups[gi] = self.stack.compile_group(self.job,
                                                               g.tg)
        return self.scan(prs)

    def device_grants(self) -> List[Tuple[str, int, int]]:
        """[(device group id, row, instances)] the plan's allocations
        hold: what this eval has granted and no state store has yet."""
        out = []
        for node_id, allocs in self.plan.node_allocation.items():
            row = self.cm.row_of.get(node_id)
            if row is None:
                continue
            for a in allocs:
                for tr in a.allocated_resources.tasks.values():
                    for d in tr.devices:
                        out.append((f"{d['vendor']}/{d['type']}/{d['name']}",
                                    row, len(d.get("device_ids", []))))
        return out

    def register_device_grants(self) -> None:
        """The plan's grants in the engine's overlay until it commits."""
        contribs = self.device_grants()
        if contribs:
            self.tickets.append(
                self.eng.register_devices(self.cm, contribs))

    # --------------------------------------------------------- materialise

    def materialise(self, preplaced, placed, rounds, result,
                    one_by_one: bool) -> None:
        """Rows become Allocation records (and slots that found no row
        go to the preemption search): one span for the eval.  `result`
        is the first round's; a later round of a one-by-one eval is
        scored here, against what the rounds before it placed."""
        self.now = _time.time()
        with tracing.span("sched.materialise", cpu=True):
            for pr, row in preplaced:
                self._place(pr, row, AllocMetric(), held=True)
            for group_placed in placed:
                self._materialise_bulk(*group_placed)
            for n_round, prs in enumerate(rounds):
                if n_round:
                    result = self.rescore(prs)
                self._materialise_round(prs, result, one_by_one)

    def _materialise_bulk(self, gi, prs, rows, counts, scores, n_eval,
                          n_exh) -> None:
        """One native expand_pairs call flattens the group's (row, count,
        score) triples to per-alloc arrays, and plain new placements
        materialize through the batch constructor instead of K
        build_allocation round trips."""
        cm, job, tg = self.cm, self.job, self.groups[gi].tg
        flat_rows, flat_scores = native.expand_pairs(
            rows, counts, np.asarray(scores)[rows])
        n_placed = min(len(flat_rows), len(prs))
        fast = (n_placed > 0
                and not tg.networks
                and not any(t.resources.networks for t in tg.tasks)
                and all(pr.previous_alloc is None
                        and not pr.is_canary
                        and not pr.is_rescheduling
                        for pr in prs[:n_placed]))
        if fast:
            node_by_id, node_names = self.state.node_by_id, {}
            for row in rows:
                row = int(row)
                node = node_by_id(cm.node_ids[row])
                node_names[row] = node.name if node else ""
            append = self.plan.append_alloc
            for alloc in materialize_bulk_allocs(
                    job, tg, [pr.name for pr in prs[:n_placed]],
                    flat_rows[:n_placed], flat_scores[:n_placed],
                    cm.node_ids, node_names, self.eval_id,
                    self._deployment_id(tg), int(n_eval), int(n_exh),
                    self.now):
                append(alloc, None)
        else:
            for pr, row, sc in zip(prs, flat_rows, flat_scores):
                row = int(row)
                m = AllocMetric(nodes_evaluated=n_eval,
                                nodes_exhausted=n_exh)
                if cm.node_ids[row]:
                    m.populate_score_meta([{
                        "node_id": cm.node_ids[row],
                        "norm_score": round(float(sc), 6)}])
                self.place_on(pr, row, m)
        for pr in prs[n_placed:]:
            if not self.try_preempt(pr):
                self.fail(pr, AllocMetric(nodes_evaluated=n_eval,
                                          nodes_exhausted=n_exh), "exhausted")

    def _materialise_round(self, prs, result, one_by_one: bool) -> None:
        cm, used, metric_for = self.cm, self.used, self.metric_for
        stats = self.eng.stats
        for i, pr in enumerate(prs):
            row = int(result.node[i])
            binds = bool(self.distinct.binds[self.tg_index[pr.task_group]])
            stats["distinct_slots"] += binds
            if row < 0:
                stats["distinct_unplaced"] += binds
                if not self.try_preempt(pr, result, prs, i):
                    self.fail(pr, metric_for(result, prs, i), "exhausted")
                continue
            alloc = self._place(pr, row, metric_for(result, prs, i),
                                alt_rows=result.top_nodes[i])
            if one_by_one and alloc is not None:
                # the next pass scores against this one
                if alloc.node_id == cm.node_ids[row]:
                    used[row] += self.groups[
                        self.tg_index[pr.task_group]].demand
                self.allocs_by_tg.setdefault(pr.task_group, []).append(alloc)

    def metric_for(self, result, prs, i: int) -> AllocMetric:
        """What the kernel pass `result` saw for slot `i` of `prs`, the
        requests that pass was given."""
        cm = self.cm
        m = AllocMetric(nodes_evaluated=int(result.nodes_evaluated[i]),
                        nodes_exhausted=int(result.nodes_exhausted[i]))
        gi = self.tg_index[prs[i].task_group]
        if int(result.node[i]) < 0 and self.distinct.binds[gi]:
            # what distinct_* closed to the slot, as the upstream's
            # iterators record it: the carry stands where the kernel's
            # stood when it reached this slot
            m.constraint_filtered = self.distinct.filtered(
                gi, self.groups[gi].feasible)
            m.nodes_filtered = sum(m.constraint_filtered.values())
        entries = []
        for k in range(result.top_nodes.shape[1]):
            r = int(result.top_nodes[i, k])
            s = float(result.top_scores[i, k])
            if r >= 0 and s > -np.inf and cm.node_ids[r]:
                entries.append({"node_id": cm.node_ids[r],
                                "norm_score": round(s, 6)})
                if r == int(result.node[i]):
                    entries[-1]["scores"] = self.chosen_scores(
                        result, prs, i, r)
        m.populate_score_meta(entries)
        return m

    def chosen_scores(self, result, prs, i: int, row: int) -> Dict[str, float]:
        """The chosen node's scorers by name, as rank.go's ScoreNode
        names them: `binpack` always, `devices` when the group's
        device asks carry affinities."""
        out = {"binpack": round(float(result.fit_score[i]), 6)}
        g = self.groups[self.tg_index[prs[i].task_group]]
        if g.has_dev:
            out["devices"] = round(float(g.dev_score[row]), 6)
        return out

    # ------------------------------------------------- one slot on one row

    def _place(self, pr, row, metric, alt_rows=None,
               held: bool = False) -> Optional[Allocation]:
        """place_on for a slot that evicts nothing by plan: what device
        preemption inside it evicts all the same still frees usage, and
        must not be chosen again by later slots."""
        extra: List[Allocation] = []
        alloc = self.place_on(pr, row, metric, preempted=extra,
                              alt_rows=alt_rows, held=held)
        allocs_leave(self.used, row, extra, self.freed_ports, self.preemptor)
        return alloc

    def place_on(self, pr: PlacementRequest, row: int, metric: AllocMetric,
                 preempted=None, extra_freed=None, alt_rows=None,
                 held: bool = False) -> Optional[Allocation]:
        """The allocation placed (on `row`, or for a device ask on
        the first of `alt_rows` that can grant instances), or None
        with the failure recorded.  `held`: the slot took the row in
        the distinct_* carry already (a sticky slot, in prepare)."""
        gi = self.tg_index[pr.task_group]
        g, stats = self.groups[gi], self.eng.stats
        node = self.state.node_by_id(self.cm.node_ids[row])
        # no copy: device-preemption evictions appended by
        # assign_devices must stay visible to the caller for
        # usage/invalidate bookkeeping
        preempted = preempted if preempted is not None else []
        # a row the kernel chose is open unless the host has since put
        # an earlier slot elsewhere than the kernel did (a device
        # alternative): then this slot looks for an alternative too
        devices = None
        if held or self.distinct.allows(gi, row):
            devices = self.assign_devices(gi, node, preempted) \
                if node is not None else {}
        if g.device_asks:
            stats["device_placements"] += 1
            stats["device_fallbacks"] += devices is None
        if devices is None:
            found = self._next_best_with_devices(gi, row, alt_rows,
                                                 preempted)
            if found is None:
                self.fail(pr, metric, "devices exhausted" if g.device_asks
                          else "exhausted")
                return None
            row, node, devices = found
            held = False
        freed = set(self.freed_ports.get(row, ()))
        if extra_freed:
            freed |= extra_freed
        alloc = build_allocation(
            job=self.job, tg=g.tg, name=pr.name, node_id=self.cm.node_ids[row],
            node_name=node.name if node else "", eval_id=self.eval_id,
            row=row, ports=self.ports, freed_ports=freed,
            metric=metric, previous=pr.previous_alloc,
            deployment_id=self._deployment_id(g.tg), is_canary=pr.is_canary,
            is_rescheduling=pr.is_rescheduling, now=self.now,
            task_devices=devices)
        if g.static_ports or g.dynamic_ports:
            stats["port_placements"] += 1
            stats["port_fallbacks"] += alloc is None
        if alloc is None:
            self.fail(pr, metric, "ports exhausted")
            return None
        if not held:
            self.distinct.take(gi, row)
        if pr.previous_alloc is not None:
            pr.previous_alloc.next_allocation = alloc.id
        if preempted:
            # handlePreemptions (generic_sched.go:822-843)
            alloc.preempted_allocations = [a.id for a in preempted]
            for a in preempted:
                self.plan.append_preempted_alloc(a, alloc.id)
        self.plan.append_alloc(alloc, None)
        if pr.is_canary and self.plan.deployment is not None:
            state = self.plan.deployment.task_groups.get(g.tg.name)
            if state is not None:
                state.placed_canaries.append(alloc.id)
        return alloc

    def _deployment_id(self, tg) -> str:
        d = self.deployment
        return d.id if d is not None and tg.name in d.task_groups else ""

    def _next_best_with_devices(self, gi, row, alt_rows, preempted):
        """The dense kernel scores cpu/mem, not per-node device
        instances; earlier placements of THIS eval may have claimed the
        node's instances — fall back to the next best candidates from
        the kernel's top-K (the reference iterator simply pulls the next
        node, rank.go:193).  -> (row, node, devices) of the first that
        grants, its demand taken from `used`; None when none does."""
        cm, used, g = self.cm, self.used, self.groups[gi]
        for alt in ([] if alt_rows is None else alt_rows):
            alt = int(alt)
            if alt < 0 or alt == row or not cm.node_ids[alt] \
                    or not g.feasible[alt] \
                    or not self.distinct.allows(gi, alt) \
                    or not np.all(used[alt] + g.demand <= cm.capacity[alt]):
                continue
            node = self.state.node_by_id(cm.node_ids[alt])
            devices = self.assign_devices(gi, node, preempted) \
                if node is not None else {}
            if devices is not None:
                used[alt] += g.demand
                return alt, node, devices
        return None

    def assign_devices(self, gi, node, preempted) -> Optional[Dict]:
        """Assign device instances for every device request of the
        group (scheduler/device.go AssignDevice), attempting device
        preemption (PreemptForDevice) when instances are exhausted.
        Returns {task: [assignment dicts]} or None on failure; appends
        extra evictions to `preempted` in place."""
        wants = self.groups[gi].device_asks
        with tracing.span("sched.assign_devices"):
            if not wants:
                return {}
            from nomad_tpu.scheduler.devices import assign_device_instances
            # instance ids are picked against the LIVE store view: under
            # the device gate all prior device plans have committed, so
            # the freshest state (not this eval's older snapshot) is what
            # prevents id collisions at the applier
            live_view = getattr(self.state, "_store", None) or self.state
            node_allocs = [a for a in live_view.allocs_by_node(node.id)
                           if not a.terminal_status()]
            node_allocs += self.plan.node_allocation.get(node.id, [])
            # allocs this plan already stops or preempts no longer hold
            # their device instances
            evicted_ids = {a.id for a in preempted} | self.stopped_ids
            evicted_ids |= {a.id for a in
                            self.plan.node_preemptions.get(node.id, [])}
            out: Dict[str, List[dict]] = {}
            granted: Dict[str, set] = {}   # in-flight grants of THIS alloc
            for t, req in wants:
                live = [a for a in node_allocs if a.id not in evicted_ids]
                got, _w = assign_device_instances(node, live, req,
                                                  extra_used=granted)
                if got is None and self.preemption_on:
                    extra = self._the_preemptor().preempt_for_device(
                        node, live, req, exclude=evicted_ids)
                    if extra:
                        preempted.extend(extra)
                        evicted_ids.update(a.id for a in extra)
                        live = [a for a in node_allocs
                                if a.id not in evicted_ids]
                        got, _w = assign_device_instances(
                            node, live, req, extra_used=granted)
                if got is None:
                    return None
                gid = f"{got['vendor']}/{got['type']}/{got['name']}"
                granted.setdefault(gid, set()).update(got["device_ids"])
                out.setdefault(t.name, []).append(got)
            return out

    def _the_preemptor(self) -> Preemptor:
        if self.preemptor is None:
            self.preemptor = Preemptor(self.state, self.job.priority)
        return self.preemptor

    def try_preempt(self, pr: PlacementRequest, result=None, prs=(),
                    i: Optional[int] = None) -> bool:
        """A slot no row was found for, placed where a search finds room
        to evict; False when the caller has to fail it.  `result`, `prs`,
        `i`: the kernel pass that scored the slot, where one did."""
        if not self.preemption_on:
            return False
        preemptor = self._the_preemptor()
        gi = self.tg_index[pr.task_group]
        g = self.groups[gi]
        cache = self.preempt_cache.setdefault(gi, [])
        if not cache:
            # one find round serves a batch of failed slots (each
            # find rebuilds the per-node candidate tensors)
            # among the rows distinct_* leaves the group now; one found
            # earlier may have closed since (another slot took its host,
            # or filled its value), so each is asked again as it is used
            masks = [g.feasible, g.feasible_pre_ports, g.device_blocked]
            if self.distinct.binds[gi]:
                open_rows = self.distinct.open_rows(gi)
                masks = [m if m is None else m & open_rows for m in masks]
            with tracing.span("sched.preempt_find"):
                cache.extend(preemptor.find_many(
                    masks[0], g.demand, self.used, 64,
                    static_ports=g.static_ports,
                    feasible_pre_ports=masks[1], device_blocked=masks[2]))
        while cache and not self.distinct.allows(gi, cache[0].row):
            cache.pop(0)
        if not cache:
            return False
        found = cache.pop(0)
        row, evicted = found.row, found.evicted
        # ports held by the evicted allocs become claimable — but only
        # commit that (and the usage adjustments) if the placement
        # actually lands, else later placements would claim ports of
        # allocs that keep running
        evicted_ports = {p for a in evicted for p in a.ports()}
        metric = AllocMetric() if i is None \
            else self.metric_for(result, prs, i)
        # the kernel found no row for this slot: what the chosen node
        # scores is what the search ranked it by, with this evicted set
        metric.populate_score_meta([found.score_meta(self.cm.node_ids[row])])
        if self.place_on(pr, row, metric, preempted=evicted,
                         extra_freed=evicted_ports):
            # `evicted` may have grown inside place_on (device
            # preemption); account for everything it now holds
            allocs_leave(self.used, row, evicted, self.freed_ports, preemptor)
            self.used[row] += g.demand
        return True   # a failure is already recorded by place_on
