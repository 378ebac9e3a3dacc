"""Host-side preemption orchestration around the dense kernel.

Reference: scheduler/preemption.go Preemptor.  The device kernel
(ops.preempt) answers met/picked for every node at once; this module
takes the padded candidate matrices from the slot table the cluster
matrix keeps (encode/matrixizer.py `candidates`), ranks the eligible
nodes (fit score after preemption + logistic preemption score, mirroring
PreemptionScoringIterator rank.go:817-868), and applies the reference's
final superset-filter pass (preemption.go:702-732) to the chosen node.

Network preemption (PreemptForNetwork, preemption.go:270-454): bandwidth
rides the RES_NET resource dimension, so the same greedy distance kernel
frees MBits; static-port conflicts are resolved here by force-evicting the
preemptible holders of the asked ports (ports held by non-preemptible
allocs make the node ineligible, mirroring filteredReservedPorts).

Device preemption (PreemptForDevice, preemption.go:472-555): per-node
instance-count preemption in preempt_for_device() — group matching allocs
by device group, take lowest-priority first until free+preempted instances
cover the ask.

Not yet modeled: per-job migrate max_parallel scoring penalty.
"""
from __future__ import annotations

from typing import Dict, List, NamedTuple, Optional, Set, Tuple

import numpy as np

from nomad_tpu import tracing
from nomad_tpu.ops.preempt import (
    net_priority,
    preempt_for_task_group_np,
    preemption_score,
)
from nomad_tpu.telemetry import global_metrics

PRIORITY_DELTA = 10   # preemption.go:663-697: need >= 10 priority gap


def _score_fit_np(capacity, util):
    """Numpy twin of ops.fit.score_fit (binpack) for the host ranking
    path — worker threads stay off the device."""
    from nomad_tpu.encode.matrixizer import RES_CPU, RES_MEM
    cap = capacity[:, (RES_CPU, RES_MEM)].astype(np.float64)
    use = util[:, (RES_CPU, RES_MEM)].astype(np.float64)
    with np.errstate(divide="ignore", invalid="ignore"):
        frac = 1.0 - use / cap
    zero = cap <= 0.0
    frac = np.where(zero & (use > 0.0), -np.inf, frac)
    frac = np.where(zero & (use <= 0.0), 1.0, frac)
    total = np.power(10.0, frac).sum(axis=-1)
    return np.clip(20.0 - total, 0.0, 18.0).astype(np.float32)


class Eviction(NamedTuple):
    """One preemption assignment: the node row, the allocations that go,
    and the score the search ranked the node by with that set gone (the
    mean of `binpack`, the fit after the eviction, and `preemption`, the
    logistic score of the set's net priority: rank.go's ScoreNode names)."""
    row: int
    evicted: List
    score: float
    binpack: float
    preemption: float

    def score_meta(self, node_id: str) -> dict:
        """What the placement that evicts this set reports for its node
        (`AllocMetric.score_meta`): the value the search ranked by."""
        return {"node_id": node_id, "norm_score": round(self.score, 6),
                "scores": {"binpack": round(self.binpack, 6),
                           "preemption": round(self.preemption, 6)}}


def fit_score_meta(node_id: str, capacity, util) -> dict:
    """A placement's `score_meta` entry where binpack alone speaks (the
    system stack's one node, nothing evicted): the normalised fit of the
    node at `util`, through the search's own arithmetic."""
    fit = round(float(_score_fit_np(capacity[None, :], util[None, :])[0])
                / 18.0, 6)
    return {"node_id": node_id, "norm_score": fit,
            "scores": {"binpack": fit}}


class Preemptor:
    def __init__(self, snapshot, job_priority: int):
        self.snapshot = snapshot
        self.cm = snapshot.matrix
        self.job_priority = job_priority
        self._built = False
        self.already_preempted: Set[str] = set()

    # ------------------------------------------------------------- build

    def _build(self) -> None:
        """The allocations this job may evict, from the matrix's slot
        table as one commit left it, padded to the widest row of them.
        Candidates fill a row from index 0, lowest priority first and
        among equals in the order their node tracks them, so a tie between
        equal candidates goes to the one tracked first."""
        self.cand_res, self.cand_prio, self.cand_valid, self.cand_ids = \
            self.cm.candidates(self.job_priority - PRIORITY_DELTA)
        self.max_steps = min(self.cand_valid.shape[1], 32)
        self._built = True

    def invalidate(self, alloc_ids: Set[str]) -> None:
        """Mark allocs chosen for preemption unusable for later slots."""
        if not self._built:
            return
        for aid in alloc_ids:
            a = self.snapshot.allocs.get(aid)
            row = self.cm.row_of.get(a.node_id) if a is not None else None
            if row is not None and aid in self.cand_ids[row]:
                at = self.cand_ids[row].index(aid)
                if at < self.cand_valid.shape[1]:   # past it, none may go
                    self.cand_valid[row, at] = False

    def _records(self, row: int, picked) -> Optional[List]:
        """The candidates at indices `picked` of `row` as this eval's
        snapshot has them.  The matrix is live and the snapshot is a read
        point: an allocation committed since is no candidate of this eval,
        so it leaves `cand_valid` and the row answers None."""
        seen = self.snapshot.allocs
        ids = self.cand_ids[row]
        found = [seen.get(ids[k]) for k in picked]
        unseen = [k for k, a in zip(picked, found) if a is None]
        if unseen:
            self.cand_valid[row, unseen] = False
            global_metrics.incr("nomad.sched.preempt_unseen", len(unseen))
            return None
        return found

    # ------------------------------------------------------------- ports

    def _port_forced_evictions(self, static_ports: List[int],
                               rows: np.ndarray):
        """For each port-conflicted row: which preemptible candidates hold
        the asked ports.  Returns {row: set(cand idx)} for eligible rows;
        rows where an asked port is held by a NON-preemptible alloc are
        excluded (reference filteredReservedPorts, preemption.go:290-323).
        """
        want = set(static_ports)
        out: Dict[int, Set[int]] = {}
        for row in rows:
            holders: Set[int] = set()
            eligible = True
            conflicted = {
                p for p in want
                if (self.cm.port_words[row, p >> 5] >> np.uint32(p & 31)) & 1}
            if not conflicted:
                continue
            cand_port_sets = [set(self.cm.alloc_ports(row, aid))
                              for aid in self.cand_ids[row][
                                  :self.cand_valid.shape[1]]]
            for p in conflicted:
                held_by = [i for i, ps in enumerate(cand_port_sets)
                           if p in ps and self.cand_valid[row, i]]
                if not held_by:
                    eligible = False   # a higher-priority alloc owns it
                    break
                holders.update(held_by)
            if eligible:
                out[int(row)] = holders
        return out

    # ------------------------------------------------------------- find

    def find(self, feasible: np.ndarray, demand: np.ndarray,
             used: np.ndarray,
             static_ports: Optional[List[int]] = None,
             feasible_pre_ports: Optional[np.ndarray] = None,
             device_blocked: Optional[np.ndarray] = None,
             count: int = 1) -> Optional[Eviction]:
        """-> the best node's Eviction, or None; the `count - 1` next
        best are kept for find_many.

        `used` is the eval's current proposed usage matrix; remaining =
        capacity - used per node.  When `static_ports` is given,
        `feasible_pre_ports` is the mask before the port-availability
        filter: port-conflicted nodes become eligible by force-evicting
        the preemptible holders of the asked ports."""
        if not self._built:
            with tracing.span("sched.preempt_build"):
                self._build()
        with tracing.span("sched.preempt_search"):
            return self._search(np.asarray(feasible).copy(), demand, used,
                                static_ports, feasible_pre_ports,
                                device_blocked, count)

    def _search(self, feasible, demand, used, static_ports,
                feasible_pre_ports, device_blocked, count):
        cm = self.cm
        remaining = cm.capacity - used

        forced: Dict[int, Set[int]] = {}
        if static_ports and feasible_pre_ports is not None:
            port_rows = np.flatnonzero(feasible_pre_ports & ~feasible)
            forced = self._port_forced_evictions(static_ports, port_rows)
        in_forced = np.zeros(len(feasible), bool)
        in_forced[list(forced)] = True
        feasible |= in_forced          # eligible again via eviction
        # instance-exhausted device nodes: eligible targets — the actual
        # device evictions are chosen later by preempt_for_device inside
        # the placement (PreemptForDevice, preemption.go:472)
        dev_rows = np.zeros(len(feasible), bool)
        if device_blocked is not None:
            dev_rows = np.asarray(device_blocked) & ~feasible
            feasible |= dev_rows

        met, picked = self._greedy(feasible, remaining.astype(np.float32),
                                   demand.astype(np.float32))
        met &= feasible
        # nodes that fit without eviction are not preemption targets --
        # unless a port eviction is what makes them usable
        fits_plain = np.all(remaining >= demand, axis=-1)
        met &= ~(fits_plain & ~in_forced & ~dev_rows)
        # port/device rows that fit resource-wise still need their evictions
        met |= in_forced & fits_plain & feasible
        met |= dev_rows & fits_plain
        # fold the forced port evictions into each row's pick set, and
        # re-check resource sufficiency with the combined freed set (the
        # kernel ran without knowing about the forced frees)
        for row, holders in forced.items():
            for i in holders:
                picked[row, i] = True
            freed = self.cand_res[row][picked[row]].sum(axis=0)
            met[row] = bool(np.all(remaining[row] + freed >= demand))
        if not met.any():
            return None

        # what goes is what the superset filter leaves of the greedy set,
        # and a node is ranked, and reported, by that set (the upstream
        # scores PreemptForTaskGroup's result, which is filtered)
        rows = np.flatnonzero(met)
        on = self._superset_filter(picked, rows, remaining, demand,
                                   forced)[rows]
        # mean of (binpack fit after preemption) and the logistic
        # preemption score of the evicted set, for every met row at once
        freed = (self.cand_res[rows] * on[:, :, None]).sum(axis=1)
        fit = (_score_fit_np(cm.capacity[rows],
                             used[rows] - freed + demand[None, :])
               / 18.0).astype(np.float64)
        # the logistic through the scalar arithmetic that reports it, once
        # for each distinct (max, sum) of a set's priorities: np.exp may
        # differ in the last place and reorder ties
        prio = np.where(on, self.cand_prio[rows], 0).astype(np.int64)
        _, first, which = np.unique(
            (prio.max(axis=1) << 32) + prio.sum(axis=1),
            return_index=True, return_inverse=True)
        p_score = np.array([
            preemption_score(net_priority(prio[i][on[i]].tolist()))
            for i in first])[which]
        score = (fit + p_score) / 2.0

        # the first of the best in row order, then every other met row
        # that evicts best-first, for find_many: eviction sets on distinct
        # rows are disjoint, so one search can serve a whole batch of
        # failed slots instead of one.  Records only for what is returned
        best = int(np.argmax(score))
        order = np.lexsort((rows, score))[::-1]
        order = order[(order != best) & on.any(axis=1)[order]]
        ranked: List[Eviction] = []
        for i in [best, *order]:
            if len(ranked) == count:
                break
            row = int(rows[i])
            evicted = self._records(row, np.flatnonzero(on[i]))
            if evicted is not None:
                ranked.append(Eviction(row, evicted, float(score[i]),
                                       float(fit[i]), float(p_score[i])))
        self._last_ranked = ranked[1:]
        return ranked[0] if ranked else None

    def _greedy(self, feasible, remaining, ask):
        """The greedy passes over the rows that can answer: feasible, not
        fitting as they are, holding a valid candidate; the candidate axis
        cut to the widest of them (candidates fill a row from index 0, and
        argmin's first-index tie-break survives a prefix).  Every other row
        is left as a pass leaves it: met if it fits, nothing picked.
        -> (met bool[N], picked bool[N, A])"""
        met = np.all(remaining >= ask, axis=-1)
        picked = np.zeros(self.cand_valid.shape, bool)
        go = np.flatnonzero(feasible & ~met & self.cand_valid.any(axis=1))
        if len(go):
            width = int(np.flatnonzero(self.cand_valid[go].any(axis=0))[-1]) + 1
            met[go], picked[go, :width], _ = preempt_for_task_group_np(
                self.cand_res[go, :width], self.cand_prio[go, :width],
                self.cand_valid[go, :width], remaining[go], ask,
                max_steps=self.max_steps)
        return met, picked

    def find_many(self, feasible: np.ndarray, demand: np.ndarray,
                  used: np.ndarray, count: int,
                  static_ports: Optional[List[int]] = None,
                  feasible_pre_ports: Optional[np.ndarray] = None,
                  device_blocked: Optional[np.ndarray] = None,
                  ) -> List[Eviction]:
        """Up to `count` preemption assignments from ONE search.
        Eviction sets on distinct rows are disjoint (an alloc lives on one
        node), so the search's ranked rows can serve `count` slots without
        paying one search per slot; later rounds (triggered by the caller
        when this batch is exhausted) see updated usage and invalidated
        candidates."""
        first = self.find(feasible, demand, used,
                          static_ports=static_ports,
                          feasible_pre_ports=feasible_pre_ports,
                          device_blocked=device_blocked, count=count)
        if first is None:
            return []
        return [first] + self._last_ranked

    # ------------------------------------------------------------- devices

    def preempt_for_device(self, node, allocs, request,
                           exclude: Optional[Set[str]] = None
                           ) -> Optional[List]:
        """PreemptForDevice (preemption.go:472-555) for one node: find the
        lowest-priority allocs holding instances of a device group matching
        `request` so that free + preempted instances cover request.count.
        Returns the allocs to evict, or None."""
        exclude = exclude or set()
        from nomad_tpu.scheduler.devices import _used_instances

        live = [a for a in allocs
                if not a.terminal_status() and a.id not in exclude]
        used_by_group = _used_instances(live)   # gid -> set(instance ids)

        best: Optional[Tuple[int, List]] = None   # (net_priority, allocs)
        for dev in node.node_resources.devices:
            if not dev.matches(request.name):
                continue
            # per-alloc instance counts on this device group (deduped view
            # shared with assign_device_instances via _used_instances)
            holders: List[Tuple[object, int]] = []
            for a in live:
                n_inst = 0
                for tr in a.allocated_resources.tasks.values():
                    for d in tr.devices:
                        gid = f"{d['vendor']}/{d['type']}/{d['name']}"
                        if gid == dev.id:
                            n_inst += len(d.get("device_ids", []))
                if n_inst == 0:
                    continue
                prio = a.job.priority if a.job is not None else 50
                if self.job_priority - prio < PRIORITY_DELTA:
                    continue
                holders.append((a, n_inst))
            free = len(dev.instance_ids) - len(used_by_group.get(dev.id, ()))
            if free >= request.count:
                return []          # no preemption needed on this group
            # lowest priority first into the option, then the reference's
            # refinement pass: sort picks by instance count descending and
            # keep only what's needed (selectBestAllocs, preemption.go:556+)
            holders.sort(key=lambda t: (
                t[0].job.priority if t[0].job else 50, t[1]))
            picked, got = [], free
            for a, n_inst in holders:
                picked.append((a, n_inst))
                got += n_inst
                if got >= request.count:
                    break
            if got < request.count:
                continue
            picked.sort(key=lambda t: -t[1])
            filtered, covered = [], free
            for a, n_inst in picked:
                if covered >= request.count:
                    break
                filtered.append(a)
                covered += n_inst
            # net priority = sum of UNIQUE priorities in the option
            # (selectBestAllocs, preemption.go:557-558); lowest wins
            prios = {p.job.priority if p.job else 50 for p in filtered}
            cand = (int(sum(prios)), filtered)
            if best is None or cand[0] < best[0]:
                best = cand
        return best[1] if best is not None else None

    # ------------------------------------------------------------- filter

    def _superset_filter(self, picked: np.ndarray, rows: np.ndarray,
                         remaining: np.ndarray, ask: np.ndarray,
                         forced: Dict[int, Set[int]]) -> np.ndarray:
        """Drop picks whose resources are already covered by the rest
        (reference filterSuperset: iterate largest-first, keep only while
        the remainder no longer satisfies the ask), on every row of `rows`
        at once.  Forced picks (port holders) are never dropped.  Filters
        the `picked` mask in place and returns it."""
        rows = rows[picked[rows].sum(axis=1) > 1]
        if not len(rows):
            return picked
        res = self.cand_res[rows]
        on = picked[rows]
        keep = np.zeros_like(on)
        for row, holders in forced.items():
            i = int(np.searchsorted(rows, row))
            if i < len(rows) and rows[i] == row:
                keep[i, list(holders)] = True
        size = np.where(on, res.sum(axis=2), -np.inf)
        order = np.argsort(-size, axis=1, kind="stable")
        at = np.arange(len(rows))
        for k in order[:, :int(on.sum(axis=1).max())].T:
            rest = on.copy()
            rest[at, k] = False
            room = remaining[rows] + (res * rest[:, :, None]).sum(axis=1)
            drop = (on[at, k] & ~keep[at, k] & rest.any(axis=1)
                    & np.all(room >= ask, axis=1))
            on[at[drop], k[drop]] = False
        picked[rows] = on
        return picked
