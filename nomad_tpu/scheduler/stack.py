"""DenseStack: compiles a job against the cluster mirror into PlaceInputs.

Dense analog of scheduler/stack.go (GenericStack/SystemStack): where the
reference wires an iterator chain per eval and pulls nodes through it, we
compile the job's constraints/affinities/spreads once into padded tensors
and hand them to the placement engine.  Job-level and task-group-level
checkers are merged exactly like the reference's FeasibilityWrapper
(feasible.go:1010-1174): job constraints apply to every group, task
constraints/drivers fold into their group.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from nomad_tpu import tracing
from nomad_tpu.encode.attrs import AttrTable
from nomad_tpu.encode.matrixizer import (
    ClusterMatrix,
    NUM_RESOURCE_DIMS,
    RES_CPU,
    RES_DISK,
    RES_MEM,
    RES_NET,
    pad_to_bucket,
)
from nomad_tpu.ops.place import PlaceInputs
from nomad_tpu.scheduler import feasible as fz
from nomad_tpu.structs.job import Constraint, Job, Operand, Spread, TaskGroup
from nomad_tpu.structs.config import (
    SCHEDULER_ALGORITHM_SPREAD,
    SchedulerConfiguration,
)

IMPLICIT_TARGET = "*"   # reference scheduler/spread.go implicitTarget


def group_demand(tg: TaskGroup) -> np.ndarray:
    """f32[R] total resource demand of one instance of the group."""
    d = np.zeros(NUM_RESOURCE_DIMS, dtype=np.float32)
    for t in tg.tasks:
        d[RES_CPU] += t.resources.cpu
        d[RES_MEM] += t.resources.memory_mb
        d[RES_NET] += sum(n.mbits for n in t.resources.networks)
    d[RES_DISK] = tg.ephemeral_disk.size_mb
    d[RES_NET] += sum(n.mbits for n in tg.networks)
    return d


def group_static_ports(tg: TaskGroup) -> List[int]:
    ports: List[int] = []
    for net in tg.networks:
        ports.extend(p.value for p in net.reserved_ports)
    for t in tg.tasks:
        for net in t.resources.networks:
            ports.extend(p.value for p in net.reserved_ports)
    return ports


def group_dynamic_port_count(tg: TaskGroup) -> int:
    n = sum(len(net.dynamic_ports) for net in tg.networks)
    n += sum(len(net.dynamic_ports) for t in tg.tasks for net in t.resources.networks)
    return n


@dataclass
class CompiledGroup:
    """Per-task-group dense artifacts."""
    tg: TaskGroup
    feasible: np.ndarray          # bool[N] static part (no distinct_*: DistinctCarry)
    affinity: np.ndarray          # f32[N]
    has_affinity: bool
    demand: np.ndarray            # f32[R]
    spreads: List[Spread]
    distinct_hosts_job: bool
    distinct_hosts_tg: bool
    distinct_property: List[Tuple[str, int, bool]]  # (target, limit, job-level)
    # for port-aware preemption: the mask before port-availability filters,
    # and the static ports the group asks for
    feasible_pre_ports: Optional[np.ndarray] = None   # bool[N]
    static_ports: List[int] = field(default_factory=list)
    dynamic_ports: int = 0
    # [(task, its DeviceRequest)] every device ask of the group
    device_asks: List[Tuple[object, object]] = field(default_factory=list)
    # nodes with device COUNT capacity but no free instances: preemption
    # targets for PreemptForDevice
    device_blocked: Optional[np.ndarray] = None       # bool[N]
    # per-node placement capacity for this eval (what the node's free
    # device instances and free ports allow the group; -1 = unlimited)
    place_cap: Optional[np.ndarray] = None            # i32[N]
    # the `devices` scorer: the score per node and whether the group's
    # device asks carry affinities at all (feasible.DeviceFit)
    dev_score: Optional[np.ndarray] = None            # f32[N]
    has_dev: bool = False
    # some node's `devices` score changes inside its place_cap: the
    # scheduler places such a group one slot at a time
    dev_multi_level: bool = False
    # constraint-only feasibility (datacenter/constraints/driver/volumes,
    # no readiness or capacity): the class-constant verdict that keys
    # blocked-eval unblocking — a down node or exhausted device must not
    # mark its whole class permanently ineligible
    class_feasible: Optional[np.ndarray] = None       # bool[N]

    @property
    def uncoupled(self) -> bool:
        """No placement of the group bears on its next (no spread, no
        distinct_*, no port or device instance for the host to hand out):
        generic.BULK_MIN such slots or more go to the bulk wave, else scan."""
        return not (self.spreads or self.distinct_hosts_job
                    or self.distinct_hosts_tg or self.distinct_property
                    or self.static_ports or self.dynamic_ports
                    or self.device_asks)


class DistinctCarry:
    """distinct_hosts and distinct_property of one eval (feasible.go
    DistinctHostsIterator and DistinctPropertyIterator, propertyset.go),
    started from the job's existing allocations: what the kernel's carry
    starts from (`inputs`), and its twin on the host, which takes every
    placement the plan makes (`take`) and is asked about every row the
    host picks itself (`allows`, `open_rows`).  A job-level constraint is
    one scope that every group checks and marks (it collides with any
    allocation of the job), a group-level one is its group's own.  A
    node without a property's attribute takes none; a limit left out is
    1.  Shapes are those of `PlaceInputs`; a job with neither constraint
    has no scope and every array is empty."""

    def __init__(self, cm: ClusterMatrix, groups: Sequence[CompiledGroup],
                 allocs_by_tg: Dict[str, List]):
        N, G = cm.n_rows, len(groups)
        # scope -> the indices of its groups (None: the job's, all of them)
        hosts: List[Optional[int]] = []
        props: List[Tuple[str, int, Optional[int]]] = []
        if any(g.distinct_hosts_job for g in groups):
            hosts.append(None)
        else:
            hosts += [gi for gi, g in enumerate(groups) if g.distinct_hosts_tg]
        for gi, g in enumerate(groups):
            for target, limit, job_level in g.distinct_property:
                scope = (target, limit, None if job_level else gi)
                if scope not in props:
                    props.append(scope)
        self.hosts_taken = np.zeros((len(hosts), N), bool)
        self.hosts_of = np.zeros((G, len(hosts)), bool)
        self.prop_vidx = np.zeros((len(props), N), np.int32)
        self.prop_limit = np.array([limit for _t, limit, _g in props],
                                   np.int32)
        self.prop_of = np.zeros((G, len(props)), bool)
        self.prop_counts = np.zeros((len(props), 1), np.int32)
        # bool[G]: the groups either constraint binds; the others are
        # asked and marked at no cost
        self.binds = np.zeros(G, bool)
        if not hosts and not props:
            return
        with tracing.span("sched.distinct_inputs", cpu=True):
            def rows_of(scope: Optional[int]) -> np.ndarray:
                names = allocs_by_tg if scope is None \
                    else (groups[scope].tg.name,)
                rows = [cm.row_of.get(a.node_id) for name in names
                        for a in allocs_by_tg.get(name, ())]
                return np.array([r for r in rows if r is not None], np.int64)

            for h, scope in enumerate(hosts):
                self.hosts_of[:, h] = True if scope is None \
                    else np.arange(G) == scope
                self.hosts_taken[h, rows_of(scope)] = True
            ordinals = []
            for target, _limit, _scope in props:
                col_name = AttrTable.target_to_column(target)
                col = cm.attrs.columns.get(col_name) if col_name else None
                ordinals.append(np.full(N, -1, np.int32) if col is None
                                else col.ordinals())
            W = max([int(o.max(initial=-1)) + 1 for o in ordinals] + [0])
            self.prop_counts = np.zeros((len(props), W + 1), np.int32)
            for p, (ords, (_t, _limit, scope)) in enumerate(
                    zip(ordinals, props)):
                self.prop_of[:, p] = True if scope is None \
                    else np.arange(G) == scope
                self.prop_vidx[p] = np.where(ords < 0, W, ords)
                np.add.at(self.prop_counts[p],
                          self.prop_vidx[p, rows_of(scope)], 1)
            self.prop_counts[:, W] = 0
            self.binds = self.hosts_of.any(axis=1) | self.prop_of.any(axis=1)

    def inputs(self) -> Dict[str, np.ndarray]:
        """The `PlaceInputs` fields, as they stand now (copies: the
        engine keeps what it is handed)."""
        return dict(hosts_taken=self.hosts_taken.copy(),
                    hosts_of=self.hosts_of, prop_vidx=self.prop_vidx,
                    prop_counts=self.prop_counts.copy(),
                    prop_limit=self.prop_limit, prop_of=self.prop_of)

    def _closed(self, gi: int, rows) -> Tuple[np.ndarray, np.ndarray]:
        """(bool[...] `rows` a host scope of the group closes, those a
        property of the group closes): ops.place.distinct_open on the host."""
        by_host = self.hosts_taken[self.hosts_of[gi]][:, rows].any(axis=0)
        ps = np.flatnonzero(self.prop_of[gi])
        v = self.prop_vidx[ps][:, rows]
        W = self.prop_counts.shape[1] - 1
        full = (v >= W) | (self.prop_counts[ps[:, None], v]
                           >= self.prop_limit[ps, None])
        return by_host, full.any(axis=0)

    def open_rows(self, gi: int) -> np.ndarray:
        """bool[N]: the rows the group's constraints leave open."""
        by_host, by_prop = self._closed(gi, slice(None))
        return ~(by_host | by_prop)

    def allows(self, gi: int, row: int) -> bool:
        if not self.binds[gi]:
            return True
        by_host, by_prop = self._closed(gi, np.array([row]))
        return not (by_host[0] or by_prop[0])

    def take(self, gi: int, row: int) -> None:
        """One allocation of group `gi` goes to `row`."""
        if not self.binds[gi]:
            return
        self.hosts_taken[self.hosts_of[gi], row] = True
        ps = np.flatnonzero(self.prop_of[gi])
        v = self.prop_vidx[ps, row]
        held = v < self.prop_counts.shape[1] - 1
        self.prop_counts[ps[held], v[held]] += 1

    def filtered(self, gi: int, feasible: np.ndarray) -> Dict[str, int]:
        """How many of the `feasible` rows each constraint closes to the
        group now, under the names the upstream's iterators filter by
        (hosts first, as its chain has them)."""
        by_host, by_prop = self._closed(gi, slice(None))
        out = {"distinct_hosts": int((feasible & by_host).sum()),
               "distinct_property": int((feasible & ~by_host & by_prop).sum())}
        return {k: n for k, n in out.items() if n}


class DenseStack:
    """Compiles one job against one ClusterMatrix generation."""

    def __init__(self, cm: ClusterMatrix, config: Optional[SchedulerConfiguration] = None,
                 snapshot=None):
        self.cm = cm
        self.config = config or SchedulerConfiguration()
        self.snapshot = snapshot   # state view for CSI volume/claim reads
        # {device group id: i32[N]} instances this eval has granted and
        # no plan carries yet, taken out of the free counts on recompile
        self.device_grants: Optional[Dict[str, np.ndarray]] = None
        self.spread_algorithm = (
            self.config.effective_scheduler_algorithm() == SCHEDULER_ALGORITHM_SPREAD)

    # ------------------------------------------------------------- compile

    def compile_group(self, job: Job, tg: TaskGroup) -> CompiledGroup:
        cm = self.cm
        n = cm.n_rows
        mask = cm.ready.copy()
        mask &= cm.dc_mask(job.datacenters)

        # job-level vs group-level matters for distinct_* scoping
        # (feasible.go:566-620: job-level collides with any job alloc,
        # group-level only with allocs of the same group)
        job_constraints = list(job.constraints)
        tg_constraints = list(tg.constraints)
        drivers = []
        device_asks = []
        affinities = list(job.affinities) + list(tg.affinities)
        for t in tg.tasks:
            tg_constraints += list(t.constraints)
            affinities += list(t.affinities)
            drivers.append(t.driver)
            device_asks.extend((t, r) for r in t.resources.devices)
        constraints = job_constraints + tg_constraints

        distinct_hosts_job = any(c.operand == Operand.DISTINCT_HOSTS
                                 for c in job_constraints)
        distinct_hosts_tg = any(c.operand == Operand.DISTINCT_HOSTS
                                for c in tg_constraints)
        distinct_property = [
            (c.ltarget, int(c.rtarget) if c.rtarget else 1, c in job_constraints)
            for c in constraints if c.operand == Operand.DISTINCT_PROPERTY]

        if fz.more_than_an_equality(constraints):
            with tracing.span("sched.constraint_mask", cpu=True):
                static = fz.constraints_mask(cm, constraints)
        else:
            static = fz.constraints_mask(cm, constraints)
        static &= fz.driver_mask(cm, drivers)
        static &= fz.host_volume_mask(cm, tg.volumes)
        class_feasible = cm.dc_mask(job.datacenters) & static
        mask &= static
        if any(v.type == "csi" for v in tg.volumes.values()):
            mask &= fz.csi_volume_mask(cm, self.snapshot, job.namespace,
                                       job.id, tg.volumes)

        # device COUNT capacity gates feasibility (reference DeviceChecker,
        # feasible.go:1192); instance AVAILABILITY applies after the
        # preemption-eligibility snapshot so device preemption can still
        # target instance-exhausted nodes
        fit = None
        if device_asks:
            with tracing.span("sched.device_mask"):
                fit = fz.device_fit(cm, [r for _, r in device_asks],
                                    self.device_grants)
            mask &= fit.capable
        feasible_pre_ports = mask.copy()
        device_blocked = None
        place_cap = None
        if fit is not None:
            avail = fit.place_cap > 0
            device_blocked = mask & ~avail
            mask = mask & avail
            # per-node instance budget for this eval: the kernel's
            # place_cap carry stops it over-subscribing a node's free
            # instances within one eval (deviceAllocator free counts)
            place_cap = fit.place_cap
        static_ports = group_static_ports(tg)
        dyn = group_dynamic_port_count(tg)
        if static_ports or dyn:
            # a node takes one placement of a static port and as many of
            # a dynamic ask as its free range holds: the kernel's
            # place_cap carry keeps one eval's slots inside that, since
            # the host has no second node to offer once it cannot assign
            # (NetworkIndex is rebuilt per candidate node, rank.go)
            with tracing.span("sched.port_mask", cpu=True):
                if static_ports:
                    mask &= cm.static_ports_free(static_ports)
                if dyn:
                    port_cap = cm.free_dynamic_ports() // dyn
                    if static_ports:
                        np.minimum(port_cap, 1, out=port_cap)
                    mask &= port_cap > 0
                else:
                    port_cap = np.ones(n, np.int32)
                place_cap = port_cap if place_cap is None \
                    else np.minimum(place_cap, port_cap)

        # affinity score: sum(weight * match) / sum(|weight|), rank.go:722-749
        aff = np.zeros(n, dtype=np.float32)
        has_aff = bool(affinities)
        if has_aff:
            total_w = sum(abs(a.weight) for a in affinities) or 1.0
            for a in affinities:
                m = fz.constraint_mask(
                    cm, Constraint(a.ltarget, a.rtarget, a.operand))
                aff += a.weight * m.astype(np.float32)
            aff /= total_w

        spreads = list(tg.spreads) + list(job.spreads)
        return CompiledGroup(tg=tg, feasible=mask, affinity=aff,
                             has_affinity=has_aff, demand=group_demand(tg),
                             spreads=spreads,
                             distinct_hosts_job=distinct_hosts_job,
                             distinct_hosts_tg=distinct_hosts_tg,
                             distinct_property=distinct_property,
                             feasible_pre_ports=feasible_pre_ports,
                             static_ports=static_ports,
                             dynamic_ports=dyn, device_asks=device_asks,
                             device_blocked=device_blocked,
                             place_cap=place_cap,
                             dev_score=fit.score if fit else None,
                             has_dev=bool(fit and fit.has_score),
                             dev_multi_level=bool(fit and fit.multi_level),
                             class_feasible=class_feasible)

    # ------------------------------------------------------------- assemble

    def build_inputs(
        self,
        job: Job,
        groups: Sequence[CompiledGroup],
        slots: Sequence[int],                      # tg index per placement slot
        allocs_by_tg: Dict[str, List],             # existing (non-terminal) job allocs
        penalty_nodes: Optional[Dict[str, set]] = None,   # tg name -> node ids
        used_override: Optional[np.ndarray] = None,
        distinct: Optional[DistinctCarry] = None,   # default: from the allocs
    ) -> PlaceInputs:
        cm = self.cm
        N = cm.n_rows
        G = len(groups)
        S = pad_to_bucket(max(len(slots), 1), minimum=1)
        R = NUM_RESOURCE_DIMS
        penalty_nodes = penalty_nodes or {}

        feas = np.zeros((G, N), bool)
        aff = np.zeros((G, N), np.float32)
        has_aff = np.zeros(G, bool)
        desired = np.ones(G, np.int32)
        penalty = np.zeros((G, N), bool)
        tg_count = np.zeros((G, N), np.int32)

        K = max([len(g.spreads) for g in groups] + [1])
        # distinct value space per (g, k): padded to the max across groups
        vidx_all, desired_all, targeted_all, wfrac_all, counts_all, active_all = \
            [], [], [], [], [], []
        Vmax = 1
        spread_specs = []
        for gi, g in enumerate(groups):
            per_k = []
            for sp in g.spreads:
                col_name = AttrTable.target_to_column(sp.attribute)
                col = cm.attrs.columns.get(col_name) if col_name and col_name != "__unresolvable__" else None
                values = col.distinct() if col is not None else []
                Vmax = max(Vmax, len(values))
                per_k.append((sp, col, values))
            spread_specs.append(per_k)

        vidx = np.full((G, K, N), 0, np.int32)
        sdesired = np.full((G, K, Vmax + 1), -1.0, np.float32)
        stargeted = np.zeros((G, K), bool)
        swfrac = np.zeros((G, K), np.float32)
        scounts = np.zeros((G, K, Vmax + 1), np.float32)
        sactive = np.zeros((G, K), bool)

        for gi, g in enumerate(groups):
            feas[gi] = g.feasible
            aff[gi] = g.affinity
            has_aff[gi] = g.has_affinity
            desired[gi] = max(g.tg.count, 1)
            for nid in penalty_nodes.get(g.tg.name, ()):  # reschedule penalties
                row = cm.row_of.get(nid)
                if row is not None:
                    penalty[gi, row] = True
            # existing co-placements for anti-affinity + spread counts
            existing = allocs_by_tg.get(g.tg.name, [])
            for a in existing:
                row = cm.row_of.get(a.node_id)
                if row is not None:
                    tg_count[gi, row] += 1

            sum_w = sum(sp.weight for sp, _, _ in spread_specs[gi]) or 1
            for ki, (sp, col, values) in enumerate(spread_specs[gi]):
                sactive[gi, ki] = True
                swfrac[gi, ki] = sp.weight / sum_w
                rank = {v: i for i, v in enumerate(values)}
                V = len(values)
                if col is not None:
                    vidx[gi, ki] = np.array(
                        [rank.get(v, Vmax) if v is not None else Vmax
                         for v in col.values], np.int32)
                else:
                    vidx[gi, ki] = Vmax
                if sp.targets:
                    stargeted[gi, ki] = True
                    total = max(g.tg.count, 1)
                    sum_desired = 0.0
                    for t in sp.targets:
                        dcount = (t.percent / 100.0) * total
                        if t.value in rank:
                            sdesired[gi, ki, rank[t.value]] = dcount
                        sum_desired += dcount
                    if 0 < sum_desired < total:
                        # implicit target: remaining count for untargeted values
                        rem = total - sum_desired
                        for v, i in rank.items():
                            if sdesired[gi, ki, i] < 0:
                                sdesired[gi, ki, i] = rem
                # initial counts from existing allocs of this tg
                if col is not None:
                    for a in allocs_by_tg.get(g.tg.name, []):
                        row = cm.row_of.get(a.node_id)
                        if row is not None and col.values[row] in rank:
                            scounts[gi, ki, rank[col.values[row]]] += 1

        place_cap = np.full((G, N), -1, np.int32)
        dev_score = np.zeros((G, N), np.float32)
        has_dev = np.zeros(G, bool)
        for gi, g in enumerate(groups):
            if g.place_cap is not None:
                place_cap[gi] = g.place_cap
            if g.has_dev:
                dev_score[gi] = g.dev_score
                has_dev[gi] = True

        demand = np.zeros((S, R), np.float32)
        slot_tg = np.zeros(S, np.int32)
        slot_active = np.zeros(S, bool)
        for si, gi in enumerate(slots):
            demand[si] = groups[gi].demand
            slot_tg[si] = gi
            slot_active[si] = True

        # distinct_*: the existing allocations (and what the host has
        # placed of this eval) are where the kernel's carry starts
        if distinct is None:
            distinct = DistinctCarry(cm, groups, allocs_by_tg)

        used = used_override if used_override is not None else self.cm.used
        return PlaceInputs(
            capacity=np.ascontiguousarray(cm.capacity),
            used=np.ascontiguousarray(used.astype(np.float32)),
            feasible=feas, affinity=aff, has_affinity=has_aff,
            desired_count=desired, penalty=penalty, tg_count=tg_count,
            spread_vidx=vidx, spread_desired=sdesired, spread_targeted=stargeted,
            spread_wfrac=swfrac, spread_counts=scounts, spread_active=sactive,
            place_cap=place_cap, dev_score=dev_score, has_dev=has_dev,
            **distinct.inputs(), demand=demand, slot_tg=slot_tg, slot_active=slot_active,
        )
