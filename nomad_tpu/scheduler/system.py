"""System / sysbatch schedulers (reference: scheduler/scheduler_system.go:27-527
and util.go diffSystemAllocsForNode:70).

One allocation per eligible node per task group.  Feasibility is one dense
mask over all nodes (`DenseStack.compile_group`); everything after it is a
host work over the node axis, a task group at a time: one mask says which
nodes fit, one preemption search answers every node that does not (one
`Preemptor.find_many` a group, on the `Preemptor` the eval builds once),
and the node loop only builds allocations.  No placement is coupled to
another across nodes (each node hosts its own instance, and the eviction
sets of distinct nodes are disjoint), so no scan is needed and the device
is not asked.  A placement
reports what the upstream's system stack scores the one node by: its
binpack fit, and for a preempting one the mean of the fit after the
eviction and the evicted set's preemption score, the value the search
ranked the row by.
"""
from __future__ import annotations

import time as _time
from time import perf_counter
from typing import Dict, List, Optional, Set, Tuple

import numpy as np

from nomad_tpu import tracing

from nomad_tpu.scheduler.placement import PortClaims, allocs_leave, build_allocation
from nomad_tpu.scheduler.preemption import Eviction, Preemptor, fit_score_meta
from nomad_tpu.scheduler.reconcile import tasks_updated
from nomad_tpu.scheduler.stack import DenseStack
from nomad_tpu.scheduler.util import tainted_nodes
from nomad_tpu.structs import Allocation, AllocClientStatus, Evaluation, EvalStatus
from nomad_tpu.structs.alloc import AllocMetric, alloc_name
from nomad_tpu.structs.node import NodeStatus
from nomad_tpu.structs.plan import PlanResult


class SystemScheduler:
    sysbatch = False

    def __init__(self, state, planner):
        self.state = state
        self.planner = planner
        self.eval: Optional[Evaluation] = None
        self.failed_tg_allocs: Dict[str, AllocMetric] = {}
        self.queued_allocs: Dict[str, int] = {}
        self._preemptor = None
        self._build_s = self._evict_s = 0.0    # summed over the node loop

    def process(self, ev: Evaluation) -> None:
        self.eval = ev
        with tracing.span("sched.system_diff", cpu=True):
            todo = self._diff(ev)
        if todo is None:
            return
        plan, job, groups, live, terminal_newest, used = todo
        with tracing.span("sched.system_place", cpu=True):
            self._place_nodes(plan, job, groups, live, terminal_newest, used)
        ev.queued_allocations = dict(self.queued_allocs)
        if not plan.is_no_op():
            self.planner.submit_plan(plan)

    def _diff(self, ev: Evaluation):
        """Everything before the node loop: the job, its allocations split
        into live and newest-terminal by (node, name), the stops for
        tainted nodes, the groups' feasibility masks.  None when the job
        is stopped (its plan is submitted here)."""
        job = self.state.job_by_id(ev.namespace, ev.job_id)
        allocs = self.state.allocs_by_job(ev.namespace, ev.job_id)
        plan = ev.make_plan(job)
        cm = self.state.matrix

        live: Dict[Tuple[str, str], Allocation] = {}
        terminal_newest: Dict[Tuple[str, str], Allocation] = {}
        for a in allocs:
            key = (a.node_id, a.name)
            if a.terminal_status():
                prev = terminal_newest.get(key)
                if prev is None or prev.create_index < a.create_index:
                    terminal_newest[key] = a
            else:
                live[key] = a

        stopped = job is None or job.stopped()
        if stopped:
            for a in live.values():
                plan.append_stopped_alloc(a, "alloc not needed due to job being stopped")
            if not plan.is_no_op():
                self.planner.submit_plan(plan)
            ev.queued_allocations = {}
            return None

        tainted = tainted_nodes(self.state, allocs)

        stack = DenseStack(cm, self.state.scheduler_config,
                           snapshot=self.state)
        groups = [stack.compile_group(job, tg) for tg in job.task_groups]
        used = cm.used.copy()
        self.queued_allocs = {tg.name: 0 for tg in job.task_groups}

        # stops: down nodes -> lost; draining -> migrate-stop
        for key, a in list(live.items()):
            node = tainted.get(a.node_id)
            if a.node_id in tainted:
                if node is None or node.status in (NodeStatus.DOWN,
                                                   NodeStatus.DISCONNECTED):
                    plan.append_stopped_alloc(
                        a, "alloc was lost since its node is down",
                        client_status=AllocClientStatus.LOST)
                else:   # draining
                    plan.append_stopped_alloc(a, "alloc is being migrated")
                del live[key]
                row = cm.row_of.get(a.node_id)
                if row is not None:
                    allocs_leave(used, row, (a,))
        return plan, job, groups, live, terminal_newest, used

    def _place_nodes(self, plan, job, groups, live, terminal_newest, used):
        """One allocation of each group on every feasible node that has
        none.  For a group: what a job update stops leaves `used` first,
        one mask over the node axis says which rows fit, one search
        answers every row that does not, and the node loop builds the
        allocations."""
        cm = self.state.matrix
        ports = PortClaims(cm, self.eval.id)
        now = _time.time()
        self._build_s = self._evict_s = 0.0
        for gi, tg in enumerate(job.task_groups):
            name = alloc_name(job.id, tg.name, 0)
            d = groups[gi].demand
            with tracing.span("sched.system_settle"):
                todo = self._settle(plan, job, tg, name, groups[gi].feasible,
                                    live, terminal_newest, used)
            fits = np.all(used + d <= cm.capacity, axis=1)
            asked = np.zeros(cm.n_rows, bool)
            asked[[row for _, row, kept in todo if kept is None]] = True
            asked &= ~fits
            found = self._try_preempt(job, asked, d, used)
            for node_id, row, kept in todo:
                if kept is not None:
                    plan.append_alloc(kept, job)
                else:
                    self._try_place(plan, job, tg, name, node_id, row, used,
                                    d, ports, now, fits, found)
        # the node loop's two pieces, summed: one interval an eval each (a
        # span a node would be two of 2.7 us around 450 us of work, and
        # 20,000 Dapper spans in a ring of 4,096)
        end = perf_counter()
        tracing.record("sched.system_build_alloc", end - self._build_s, end)
        tracing.record("sched.system_evict_copy", end - self._evict_s, end)

    def _settle(self, plan, job, tg, name, feas, live, terminal_newest, used):
        """Everything of a group that changes `used` ahead of a placement
        (an allocation the job's update stops gives its room back), and
        the rows the node loop walks, in `row_of`'s order.
        -> [(node_id, row, kept)]: `kept` the live allocation's copy where
        the update is in place, None where the row takes a new one."""
        cm = self.state.matrix
        todo = []
        for node_id, row in cm.row_of.items():
            if not feas[row]:
                continue
            key = (node_id, name)
            cur = live.get(key)
            if cur is not None:
                # update in place or destructively on job change
                if cur.job is None or cur.job.version == job.version:
                    continue
                old_tg = cur.job.lookup_task_group(tg.name)
                if old_tg is not None and not tasks_updated(old_tg, tg):
                    todo.append((node_id, row, cur.copy()))
                    continue
                plan.append_stopped_alloc(
                    cur, "alloc not needed due to job update")
                allocs_leave(used, row, (cur,))
            elif self.sysbatch:
                t = terminal_newest.get(key)
                if t is not None and t.ran_successfully():
                    continue   # sysbatch doesn't rerun completed nodes
            elif terminal_newest.get(key) is not None and \
                    terminal_newest[key].client_status == AllocClientStatus.COMPLETE:
                continue       # system alloc completed on purpose
            todo.append((node_id, row, None))
        return todo

    def _try_place(self, plan, job, tg, name, node_id, row, used, d, ports,
                   now, fits, found):
        cm = self.state.matrix
        evict = found.get(row)
        if evict is None and not fits[row]:
            m = self.failed_tg_allocs.setdefault(tg.name, AllocMetric())
            m.exhausted_node(node_id, "resources")
            self.queued_allocs[tg.name] = self.queued_allocs.get(tg.name, 0) + 1
            return
        node = self.state.node_by_id(node_id)
        metric = AllocMetric()
        metric.nodes_evaluated = 1
        # the one node the system stack looks at, scored as the upstream
        # scores it: what the search ranked the row by where it evicts,
        # the binpack fit where it does not
        metric.populate_score_meta([
            evict.score_meta(node_id) if evict is not None
            else fit_score_meta(node_id, cm.capacity[row], used[row] + d)])
        t0 = perf_counter()
        alloc = build_allocation(
            job=job, tg=tg, name=name, node_id=node_id,
            node_name=node.name if node else "", eval_id=self.eval.id,
            row=row, ports=ports, freed_ports=set(), metric=metric, now=now)
        self._build_s += perf_counter() - t0
        if alloc is None:
            m = self.failed_tg_allocs.setdefault(tg.name, AllocMetric())
            m.exhausted_node(node_id, "ports")
            return
        if evict is not None:
            alloc.preempted_allocations = [a.id for a in evict.evicted]
            t0 = perf_counter()
            for a in evict.evicted:
                plan.append_preempted_alloc(a, alloc.id)
            self._evict_s += perf_counter() - t0
            allocs_leave(used, row, evict.evicted)
        used[row] += d
        plan.append_alloc(alloc, None)

    def _try_preempt(self, job, asked, d, used) -> Dict[int, Eviction]:
        """System jobs preempt lower-priority work by default (reference
        SystemScheduler + PreemptionConfig.SystemSchedulerEnabled).  One
        search for all the rows of `asked`: eviction sets on distinct rows
        are disjoint and a group places once a node, so no row's answer
        depends on another's.  -> {row: Eviction} of the rows answered."""
        if not asked.any() or \
                not self.state.scheduler_config.preemption_enabled(
                    "sysbatch" if self.sysbatch else "system"):
            return {}
        if self._preemptor is None:
            self._preemptor = Preemptor(self.state, job.priority)
        with tracing.span("sched.preempt_find"):
            found = self._preemptor.find_many(asked, d, used,
                                              count=int(asked.sum()))
        self._preemptor.invalidate(
            {a.id for e in found for a in e.evicted})
        return {e.row: e for e in found}


class SysBatchScheduler(SystemScheduler):
    sysbatch = True
