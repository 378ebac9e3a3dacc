"""Generic (service/batch) scheduler over the dense placement engine.

Reference: scheduler/generic_sched.go — Process:144, process:242,
computeJobAllocs:358, computePlacements:499-679, findPreferredNode:783,
blocked-eval creation:219-238.  The reconcile step is host-side
(nomad_tpu.scheduler.reconcile); every placement decision for an eval runs
as ONE dense kernel call (ops.place) instead of per-node iterator pulls.
"""
from __future__ import annotations

import time as _time
import uuid

from nomad_tpu.utils import generate_uuid
from typing import Dict, List, Optional, Set, Tuple

import numpy as np

from nomad_tpu import tracing
from nomad_tpu.encode.matrixizer import comparable_vec
from nomad_tpu.parallel.engine import get_engine

from nomad_tpu.scheduler import factory
from nomad_tpu.scheduler.preemption import Preemptor
from nomad_tpu.scheduler.placement import (
    PortClaims,
    build_allocation,
    materialize_bulk_allocs,
)
from nomad_tpu.scheduler.reconcile import AllocReconciler, PlacementRequest
from nomad_tpu.scheduler.stack import DenseStack
from nomad_tpu.scheduler.util import (
    adjust_queued_allocations,
    progress_made,
    tainted_nodes,
)
from nomad_tpu.structs import Allocation, Evaluation, EvalStatus, Job
from nomad_tpu.structs.alloc import AllocMetric
from nomad_tpu.structs.evaluation import EvalTrigger
from nomad_tpu.structs.plan import Plan, PlanResult

MAX_SERVICE_SCHEDULE_ATTEMPTS = 5   # generic_sched.go:19-23
MAX_BATCH_SCHEDULE_ATTEMPTS = 2

BLOCKED_EVAL_MAX_PLAN_DESC = "created due to placement conflicts"
BLOCKED_EVAL_FAILED_PLACEMENT_DESC = "created to place remaining allocations"
BLOCKED_EVAL_QUOTA_DESC = "created due to quota limit"


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

        # follow-up (delayed) evals must exist before allocs reference them
        for evs in results.desired_followup_evals.values():
            self.followup_evals.extend(evs)
        if self.followup_evals:
            self.planner.create_evals(self.followup_evals)

        # stops / destructive stops
        for sr in results.stop:
            self.plan.append_stopped_alloc(
                sr.alloc, sr.status_description, sr.client_status,
                sr.followup_eval_id)
        for sr in results.destructive_stop:
            self.plan.append_stopped_alloc(
                sr.alloc, sr.status_description, sr.client_status,
                sr.followup_eval_id)

        # in-place updates / attribute-only updates ride the plan as
        # same-node allocations
        for a in results.inplace_update:
            self.plan.append_alloc(a, self.job)
        for a in results.attribute_updates.values():
            self.plan.append_alloc(a, a.job)
        for a in results.disconnect_updates.values():
            self.plan.append_alloc(a, a.job)
        for a in results.reconnect_updates.values():
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

        self._ext_tickets: List[int] = []
        try:
            if not stopped and results.place:
                self._compute_placements(results.place, results.stop +
                                         results.destructive_stop, allocs)

            if self.plan.is_no_op():
                self._finish_eval()
                return True, False

            # the applier releases these overlay tickets atomically with
            # the commit; the finally below is only the abandoned-plan
            # safety net (complete() is idempotent)
            tickets = list(self._ext_tickets)
            st = getattr(self, "_stack", None)
            if st is not None and getattr(st, "last_ticket", None) is not None:
                tickets.append(st.last_ticket)
            self.plan.engine_tickets = tickets

            self.plan_result = self.planner.submit_plan(self.plan)
        finally:
            # release the in-flight usage overlay: the plan is now either
            # committed into the cluster matrix or abandoned.  Exception:
            # a pipelined submit returned at evaluate time with the
            # durable commit still in flight — there the applier owns the
            # release (success: _post_commit; failure: the commit
            # thread's error path), and freeing here would show phantom
            # capacity to concurrent kernels before the write lands.
            if getattr(self.plan, "commit_inflight", False):
                if getattr(self, "_stack", None) is not None:
                    self._stack.last_ticket = None
                    self._stack = None
                self._ext_tickets = []
            else:
                if getattr(self, "_stack", None) is not None:
                    self._stack.release()
                    self._stack = None
                if self._ext_tickets:
                    eng = get_engine()
                    for t in self._ext_tickets:
                        eng.complete(t)
                    self._ext_tickets = []
        adjust_queued_allocations(self.plan_result, self.queued_allocs)

        full, expected, actual = self.plan_result.full_commit(self.plan)
        if not full:
            return False, progress_made(self.plan_result)
        self._finish_eval()
        return True, True

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
        feas_union = getattr(self, "_last_feasible_union", None)
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

    def _compute_placements(self, places: List[PlacementRequest],
                            stops, all_allocs: List[Allocation]) -> None:
        """Device-requesting evals serialize through the engine's gate:
        instance picks race-free across workers (basis read, placement,
        id assignment and overlay registration are atomic), mirroring how
        bulk evals serialize.  Everything else runs concurrently."""
        eng = get_engine()
        device_eval = any(t.resources.devices
                          for tg in self.job.task_groups
                          for t in tg.tasks)
        if not device_eval:
            self._compute_placements_inner(places, stops, all_allocs)
            return
        t_ask = _time.perf_counter()
        with eng.bulk_gate:
            tracing.record("sched.device_gate", t_ask, _time.perf_counter(),
                           wait=True)
            self._compute_placements_inner(places, stops, all_allocs)
            contribs = self._plan_device_grants()
            if contribs:
                self._ext_tickets.append(eng.register_devices(
                    self.state.matrix, contribs))

    def _plan_device_grants(self) -> List[Tuple[str, int, int]]:
        """[(device group id, row, instances)] the plan's allocations
        hold: what this eval has granted and no state store has yet."""
        out = []
        for node_id, allocs_ in self.plan.node_allocation.items():
            row = self.state.matrix.row_of.get(node_id)
            if row is None:
                continue
            for a_ in allocs_:
                for tr_ in a_.allocated_resources.tasks.values():
                    for d_ in tr_.devices:
                        out.append((f"{d_['vendor']}/{d_['type']}/"
                                    f"{d_['name']}", row,
                                    len(d_.get("device_ids", []))))
        return out

    def _compute_placements_inner(self, places: List[PlacementRequest],
                                  stops, all_allocs: List[Allocation]) -> None:
        cm = self.state.matrix
        stack = DenseStack(cm, self.state.scheduler_config,
                           snapshot=self.state)
        self._stack = stack
        job = self.job
        tg_index = {tg.name: i for i, tg in enumerate(job.task_groups)}
        with tracing.span("sched.feasible", cpu=True):
            groups = [stack.compile_group(job, tg)
                      for tg in job.task_groups]
        # constraint-only union, NOT g.feasible: readiness and capacity
        # are transient, and a blocked eval keyed on them would mark its
        # class ineligible forever (a down node or full device must not
        # veto the class the recovery will unblock)
        self._last_feasible_union = np.any(
            np.stack([g.class_feasible for g in groups]), axis=0)

        # proposed-usage basis: committed usage PLUS the engine's in-flight
        # overlay (placements of concurrently scheduled, not-yet-committed
        # plans) minus what this plan stops; `deltas` mirrors every
        # adjustment sparsely for the batching engine
        eng = get_engine()
        used = eng.basis_for(cm) \
            if cm.used.shape[0] == cm.capacity.shape[0] else cm.used.copy()
        deltas: List[Tuple[int, np.ndarray]] = []
        freed_ports: Dict[int, Set[int]] = {}
        stopped_ids: Set[str] = set()
        for sr in stops:
            a = sr.alloc
            stopped_ids.add(a.id)
            row = cm.row_of.get(a.node_id)
            if row is None:
                continue
            cr = a.comparable_resources()
            vec = comparable_vec(cr)
            used[row] -= vec
            deltas.append((row, -vec))
            freed_ports.setdefault(row, set()).update(a.ports())

        # remaining allocs for anti-affinity / spread / distinct_*
        allocs_by_tg: Dict[str, List[Allocation]] = {}
        for a in all_allocs:
            if a.id in stopped_ids or a.terminal_status():
                continue
            allocs_by_tg.setdefault(a.task_group, []).append(a)

        penalty_nodes: Dict[str, Set[str]] = {}
        for pr in places:
            if pr.is_rescheduling and pr.previous_alloc is not None:
                penalty_nodes.setdefault(pr.task_group, set()).add(
                    pr.previous_alloc.node_id)

        # sticky ephemeral disk: prefer the previous node when feasible
        # (findPreferredNode, generic_sched.go:783)
        slot_requests: List[PlacementRequest] = []
        preplaced: List[Tuple[PlacementRequest, int]] = []
        for pr in places:
            gi = tg_index[pr.task_group]
            tg = job.task_groups[gi]
            if (tg.ephemeral_disk.sticky and pr.previous_alloc is not None
                    and not pr.is_rescheduling):
                row = cm.row_of.get(pr.previous_alloc.node_id)
                if row is not None and groups[gi].feasible[row]:
                    d = groups[gi].demand
                    if np.all(used[row] + d <= cm.capacity[row]):
                        used[row] += d
                        deltas.append((row, d.astype(np.float32)))
                        preplaced.append((pr, row))
                        continue
            slot_requests.append(pr)

        # --- bulk path: groups of identical slots with no
        # placement-coupled constraints (spreads / distinct_*) place via
        # the wavefront kernel in O(waves) steps instead of an
        # O(slots) scan — the C2M-scale path (ops.place._place_bulk_batch).
        # The eval submits EVERY eligible group before waiting
        # (place_bulk_begin), so a many-small-group job (the C2M-1M
        # shape: 10 groups x count 10) is ONE chained device dispatch
        # batched with other workers' evals, not one blocking round trip
        # per group; FIFO + the engine's resolve-before-next-dispatch
        # keep group g+1 scoring against g's placements.
        BULK_MIN = 2
        by_group: Dict[int, List[PlacementRequest]] = {}
        for pr in slot_requests:
            by_group.setdefault(tg_index[pr.task_group], []).append(pr)
        bulk_results: List[Tuple[int, List[PlacementRequest], object]] = []
        scan_requests: List[PlacementRequest] = []
        pending_bulk: List[Tuple[int, List[PlacementRequest], object]] = []
        for gi, prs in by_group.items():
            g = groups[gi]
            eligible = (len(prs) >= BULK_MIN and not g.spreads
                        and not g.distinct_hosts_job
                        and not g.distinct_hosts_tg
                        and not g.distinct_property
                        and not g.static_ports
                        and not g.dynamic_ports
                        and not any(t.resources.devices
                                    for t in g.tg.tasks))
            if not eligible:
                scan_requests.extend(prs)
                continue
            fut = self._place_bulk_begin(eng, cm, g, prs, allocs_by_tg,
                                         penalty_nodes, deltas, stack)
            pending_bulk.append((gi, prs, fut))
        if pending_bulk:
            with tracing.span("sched.wait_engine", wait=True):
                for gi, prs, fut in pending_bulk:
                    assign, placed, n_eval, n_exh, scores, ticket = \
                        fut.result()
                    bulk_results.append(
                        (gi, prs, (assign, placed, n_eval, n_exh, scores)))
                    if ticket is not None:
                        self._ext_tickets.append(ticket)
        # cumulative usage for the scan path + host bookkeeping: apply
        # EVERY bulk group's placements (engine dispatch may reorder
        # parts, so no single returned matrix is complete; the engine
        # itself sees this usage through the overlay tickets)
        if bulk_results:
            from nomad_tpu import native as _native_mod
            used = used.copy()
            for gi, _prs, bulk in bulk_results:
                assign = bulk[0]
                rows_nz = np.flatnonzero(assign)
                _native_mod.scatter_add_rank1(
                    used, rows_nz, assign[rows_nz],
                    groups[gi].demand.astype(np.float32))
        slot_requests = scan_requests

        # one kernel pass places every slot, unless a group's `devices`
        # score moves inside the pass (a node with several admitted
        # device groups of different scores, CompiledGroup
        # .dev_multi_level): the kernel's score is then right for the
        # next placement only, so such an eval goes one slot a pass and
        # is re-scored from its own grants in between (exact, and slow:
        # a fleet with one card model a node never takes this path)
        one_by_one = any(groups[tg_index[pr.task_group]].dev_multi_level
                         for pr in slot_requests)
        rounds = ([[pr] for pr in slot_requests] if one_by_one
                  else [slot_requests] if slot_requests else [])

        def place_round(prs):
            with tracing.span("sched.feasible", cpu=True):
                inputs = stack.build_inputs(
                    job, groups, [tg_index[pr.task_group] for pr in prs],
                    allocs_by_tg, penalty_nodes=penalty_nodes,
                    used_override=used)
            return stack.place(inputs, deltas)

        def rescore(prs):
            """The next pass of a one-by-one eval: the last pass's ticket
            stays open, this eval's grants come out of the free counts,
            and the device groups are compiled again."""
            if getattr(stack, "last_ticket", None) is not None:
                self._ext_tickets.append(stack.last_ticket)
                stack.last_ticket = None
            grants: Dict[str, np.ndarray] = {}
            for gid, row, count in self._plan_device_grants():
                grants.setdefault(
                    gid, np.zeros(cm.n_rows, np.int64))[row] += count
            stack.device_grants = grants
            with tracing.span("sched.feasible", cpu=True):
                for gi_, tg_ in enumerate(job.task_groups):
                    if groups[gi_].device_blocked is not None:
                        groups[gi_] = stack.compile_group(job, tg_)
            return place_round(prs)

        result = place_round(rounds[0]) if rounds else None

        ports = PortClaims(cm, self.eval.id)
        now = _time.time()
        deployment = self.plan.deployment or self.deployment

        def metric_for(i: Optional[int]) -> AllocMetric:
            m = AllocMetric()
            if result is not None and i is not None:
                m.nodes_evaluated = int(result.nodes_evaluated[i])
                m.nodes_exhausted = int(result.nodes_exhausted[i])
                entries = []
                for k in range(result.top_nodes.shape[1]):
                    r = int(result.top_nodes[i, k])
                    s = float(result.top_scores[i, k])
                    if r >= 0 and s > -np.inf and cm.node_ids[r]:
                        entries.append({"node_id": cm.node_ids[r],
                                        "norm_score": round(s, 6)})
                        if r == int(result.node[i]):
                            entries[-1]["scores"] = chosen_scores(i, r)
                m.populate_score_meta(entries)
            m.allocation_time_s = 0.0
            return m

        def chosen_scores(i: int, row: int) -> Dict[str, float]:
            """The chosen node's scorers by name, as rank.go's ScoreNode
            names them: `binpack` always, `devices` when the group's
            device asks carry affinities."""
            out = {"binpack": round(float(result.fit_score[i]), 6)}
            g = groups[tg_index[round_prs[i].task_group]]
            if g.has_dev:
                out["devices"] = round(float(g.dev_score[row]), 6)
            return out

        def assign_devices(pr, tg, node, row, preempted) -> Optional[Dict]:
            with tracing.span("sched.assign_devices"):
                return assign_devices_inner(pr, tg, node, row, preempted)

        def assign_devices_inner(pr, tg, node, row, preempted
                                 ) -> Optional[Dict]:
            """Assign device instances for every device request of the
            group (scheduler/device.go AssignDevice), attempting device
            preemption (PreemptForDevice) when instances are exhausted.
            Returns {task: [assignment dicts]} or None on failure; appends
            extra evictions to `preempted` in place."""
            wants = [(t, req) for t in tg.tasks for req in t.resources.devices]
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
            evicted_ids = {a.id for a in preempted}
            evicted_ids |= stopped_ids
            evicted_ids |= {a.id for a in
                            self.plan.node_preemptions.get(node.id, [])}
            out: Dict[str, List[dict]] = {}
            granted: Dict[str, set] = {}   # in-flight grants of THIS alloc
            for t, req in wants:
                live = [a for a in node_allocs if a.id not in evicted_ids]
                got, _w = assign_device_instances(node, live, req,
                                                  extra_used=granted)
                if got is None and preemption_on:
                    nonlocal preemptor
                    if preemptor is None:
                        preemptor = Preemptor(self.state, job.priority)
                    extra = preemptor.preempt_for_device(
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

        def place_on(pr: PlacementRequest, row: int, metric: AllocMetric,
                     preempted=None, extra_freed=None,
                     alt_rows=None) -> Optional[Allocation]:
            """The allocation placed (on `row`, or for a device ask on
            the first of `alt_rows` that can grant instances), or None
            with the failure recorded."""
            gi = tg_index[pr.task_group]
            tg = job.task_groups[gi]
            node_id = cm.node_ids[row]
            node = self.state.node_by_id(node_id)
            dep_id = ""
            if deployment is not None and tg.name in deployment.task_groups:
                dep_id = deployment.id
            # no copy: device-preemption evictions appended by
            # assign_devices must stay visible to the caller for
            # usage/invalidate bookkeeping
            preempted = preempted if preempted is not None else []
            devices = assign_devices(pr, tg, node, row, preempted) \
                if node is not None else {}
            if groups[gi].device_blocked is not None:
                eng.stats["device_placements"] += 1
                eng.stats["device_fallbacks"] += devices is None
            if devices is None:
                # the dense kernel scores cpu/mem, not per-node device
                # instances; earlier placements of THIS eval may have
                # claimed the node's instances — fall back to the next
                # best candidates from the kernel's top-K (the reference
                # iterator simply pulls the next node, rank.go:193)
                alt_list = [] if alt_rows is None else list(alt_rows)
                for alt in alt_list:
                    alt = int(alt)
                    if alt < 0 or alt == row or not cm.node_ids[alt]:
                        continue
                    if not groups[gi].feasible[alt]:
                        continue
                    d = groups[gi].demand
                    if not np.all(used[alt] + d <= cm.capacity[alt]):
                        continue
                    alt_node = self.state.node_by_id(cm.node_ids[alt])
                    devices = assign_devices(pr, tg, alt_node, alt,
                                             preempted) \
                        if alt_node is not None else {}
                    if devices is not None:
                        row, node_id, node = alt, cm.node_ids[alt], alt_node
                        used[row] += d
                        break
                else:
                    self._fail_placement(pr, metric, "devices exhausted")
                    return None
            freed = set(freed_ports.get(row, set()))
            if extra_freed:
                freed |= extra_freed
            alloc = build_allocation(
                job=job, tg=tg, name=pr.name, node_id=node_id,
                node_name=node.name if node else "", eval_id=self.eval.id,
                row=row, ports=ports, freed_ports=freed,
                metric=metric, previous=pr.previous_alloc,
                deployment_id=dep_id, is_canary=pr.is_canary,
                is_rescheduling=pr.is_rescheduling, now=now,
                task_devices=devices)
            if groups[gi].static_ports or groups[gi].dynamic_ports:
                eng.stats["port_placements"] += 1
                eng.stats["port_fallbacks"] += alloc is None
            if alloc is None:
                self._fail_placement(pr, metric, "ports exhausted")
                return None
            if pr.previous_alloc is not None:
                pr.previous_alloc.next_allocation = alloc.id
            if preempted:
                # handlePreemptions (generic_sched.go:822-843)
                alloc.preempted_allocations = [a.id for a in preempted]
                for a in preempted:
                    self.plan.append_preempted_alloc(a, alloc.id)
            self.plan.append_alloc(alloc, None)
            if pr.is_canary and self.plan.deployment is not None:
                state = self.plan.deployment.task_groups.get(tg.name)
                if state is not None:
                    state.placed_canaries.append(alloc.id)
            return alloc

        # preemption for failed slots (BinPackIterator's evict path,
        # rank.go:500-530; gated by SchedulerConfiguration like the
        # reference's per-scheduler-type preemption config)
        preemptor = None
        scheduler_type = "batch" if self.batch else "service"
        preemption_on = self.state.scheduler_config.preemption_enabled(
            scheduler_type)

        preempt_cache: Dict[int, List] = {}

        def try_preempt(pr: PlacementRequest, i: Optional[int]) -> bool:
            nonlocal preemptor
            if not preemption_on:
                return False
            if preemptor is None:
                preemptor = Preemptor(self.state, job.priority)
            gi = tg_index[pr.task_group]
            cache = preempt_cache.setdefault(gi, [])
            if not cache:
                # one find round serves a batch of failed slots (each
                # find rebuilds the per-node candidate tensors)
                with tracing.span("sched.preempt_find"):
                    cache.extend(preemptor.find_many(
                        groups[gi].feasible, groups[gi].demand, used, 64,
                        static_ports=groups[gi].static_ports,
                        feasible_pre_ports=groups[gi].feasible_pre_ports,
                        device_blocked=groups[gi].device_blocked))
            if not cache:
                return False
            found = cache.pop(0)
            row, evicted = found.row, found.evicted
            # ports held by the evicted allocs become claimable — but only
            # commit that (and the usage adjustments) if the placement
            # actually lands, else later placements would claim ports of
            # allocs that keep running
            evicted_ports = set()
            for a in evicted:
                evicted_ports.update(a.ports())
            metric = metric_for(i)
            # the kernel found no row for this slot: what the chosen node
            # scores is what the search ranked it by, with this evicted set
            metric.populate_score_meta([found.score_meta(cm.node_ids[row])])
            if not place_on(pr, row, metric, preempted=evicted,
                            extra_freed=evicted_ports):
                return True   # failure already recorded by place_on
            # `evicted` may have grown inside place_on (device
            # preemption); account for everything it now holds
            for a in evicted:
                evicted_ports.update(a.ports())
                cr = a.comparable_resources()
                used[row] -= comparable_vec(cr)
            freed_ports.setdefault(row, set()).update(evicted_ports)
            used[row] += groups[gi].demand
            preemptor.invalidate({a.id for a in evicted})
            return True

        def account_device_evictions(row, extra) -> None:
            """Device-preemption evictions made inside place_on on a
            non-preemption path still free usage and must not be chosen
            again by later slots."""
            if not extra:
                return
            for a in extra:
                used[row] -= comparable_vec(a.comparable_resources())
                freed_ports.setdefault(row, set()).update(a.ports())
            if preemptor is not None:
                preemptor.invalidate({a.id for a in extra})

        # rows become Allocation records (and slots that found no row
        # go to the preemption search): one span for the eval
        with tracing.span("sched.materialise", cpu=True):
            for pr, row in preplaced:
                extra = []
                place_on(pr, row, metric_for(None), preempted=extra)
                account_device_evictions(row, extra)

            # bulk-kernel placements: one native expand_pairs call flattens
            # each group's (row, count, score) triples to per-alloc arrays,
            # and plain new placements materialize through the batch
            # constructor instead of K build_allocation round trips
            for gi, prs, bulk in bulk_results:
                assign, placed, n_eval, n_exh, bscores = bulk
                from nomad_tpu import native as _native_mod
                rows_nz = np.flatnonzero(assign)
                flat_rows, flat_scores = _native_mod.expand_pairs(
                    rows_nz, assign[rows_nz], np.asarray(bscores)[rows_nz])
                n_placed = min(len(flat_rows), len(prs))
                tg = job.task_groups[gi]
                fast = (n_placed > 0
                        and not tg.networks
                        and not any(t.resources.networks for t in tg.tasks)
                        and all(pr.previous_alloc is None
                                and not pr.is_canary
                                and not pr.is_rescheduling
                                for pr in prs[:n_placed]))
                if fast:
                    dep_id = ""
                    if deployment is not None \
                            and tg.name in deployment.task_groups:
                        dep_id = deployment.id
                    node_names = {}
                    for row in rows_nz:
                        row = int(row)
                        node = self.state.node_by_id(cm.node_ids[row])
                        node_names[row] = node.name if node else ""
                    for alloc in materialize_bulk_allocs(
                            job, tg, [pr.name for pr in prs[:n_placed]],
                            flat_rows[:n_placed], flat_scores[:n_placed],
                            cm.node_ids, node_names, self.eval.id, dep_id,
                            int(n_eval), int(n_exh), now):
                        self.plan.append_alloc(alloc, None)
                else:
                    for pr, row, sc in zip(prs, flat_rows, flat_scores):
                        row = int(row)
                        m = AllocMetric()
                        m.nodes_evaluated = n_eval
                        m.nodes_exhausted = n_exh
                        if cm.node_ids[row]:
                            m.populate_score_meta([{
                                "node_id": cm.node_ids[row],
                                "norm_score": round(float(sc), 6)}])
                        place_on(pr, row, m)
                for pr in prs[n_placed:]:
                    m = AllocMetric()
                    m.nodes_evaluated = n_eval
                    m.nodes_exhausted = n_exh
                    if not try_preempt(pr, None):
                        self._fail_placement(pr, m, "exhausted")
            for n_round, round_prs in enumerate(rounds):
                if n_round:
                    result = rescore(round_prs)
                for i, pr in enumerate(round_prs):
                    row = int(result.node[i])
                    if row < 0:
                        if not try_preempt(pr, i):
                            self._fail_placement(pr, metric_for(i),
                                                 "exhausted")
                    else:
                        extra = []
                        alts = result.top_nodes[i]
                        alloc = place_on(pr, row, metric_for(i),
                                         preempted=extra, alt_rows=alts)
                        account_device_evictions(row, extra)
                        if one_by_one and alloc is not None:
                            # the next pass scores against this one
                            if alloc.node_id == cm.node_ids[row]:
                                used[row] += groups[
                                    tg_index[pr.task_group]].demand
                            allocs_by_tg.setdefault(
                                pr.task_group, []).append(alloc)

    @staticmethod
    def _bulk_node_fields(cm, g, allocs_by_tg, penalty_nodes):
        """(penalty bool[N], coll0 i32[N]) for one bulk group."""
        N = cm.n_rows
        penalty = np.zeros(N, bool)
        for nid in (penalty_nodes or {}).get(g.tg.name, ()):
            row = cm.row_of.get(nid)
            if row is not None:
                penalty[row] = True
        coll0 = np.zeros(N, np.int32)
        for a in allocs_by_tg.get(g.tg.name, []):
            row = cm.row_of.get(a.node_id)
            if row is not None:
                coll0[row] += 1
        return penalty, coll0

    def _place_bulk_begin(self, eng, cm, g, prs, allocs_by_tg,
                          penalty_nodes, deltas, stack):
        """Enqueue one group's wavefront placement; returns the engine
        Future (see engine.place_bulk_begin for ordering semantics)."""
        penalty, coll0 = self._bulk_node_fields(cm, g, allocs_by_tg,
                                                penalty_nodes)
        return eng.place_bulk_begin(
            cm, feasible=g.feasible,
            affinity=g.affinity.astype(np.float32),
            has_affinity=bool(g.has_affinity),
            desired=max(g.tg.count, 1), penalty=penalty,
            coll0=coll0, demand=g.demand.astype(np.float32),
            count=len(prs), deltas=deltas,
            spread_algorithm=stack.spread_algorithm,
            # namespace = wave-lane key: evals from different namespaces
            # are independent waves and may score concurrently on the
            # 2-D mesh's wave columns
            wave_key=self.job.namespace)

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
