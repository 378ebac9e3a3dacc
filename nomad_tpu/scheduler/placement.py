"""Placement materialization: PlaceResult rows -> Allocation objects.

Port-offer construction stays on the host (SURVEY.md section 7 'hard
parts': dynamic port assignment is inherently sequential; the device checks
capacity/collisions, the host constructs the concrete offer — mirroring the
reference split where the plan applier re-validates).
"""
from __future__ import annotations

import uuid
import zlib

from nomad_tpu import tracing
from nomad_tpu.utils import generate_uuid
from typing import Dict, List, Optional, Set

import numpy as np

from nomad_tpu.encode.matrixizer import ClusterMatrix, comparable_vec
from nomad_tpu.structs import Allocation, AllocClientStatus, AllocDesiredStatus, Job, TaskGroup
from nomad_tpu.structs.alloc import (
    AllocatedResources,
    AllocatedTaskResources,
    AllocMetric,
    RescheduleEvent,
    RescheduleTracker,
)
from nomad_tpu.structs.resources import NetworkPort, NetworkResource


class PortClaims:
    """In-plan port claims per node row (plan-local view on top of the
    committed bitsets)."""

    def __init__(self, cm: ClusterMatrix, eval_id: str = ""):
        self.cm = cm
        self.claimed: Dict[int, Set[int]] = {}
        # where in a node's dynamic range the search for a free value
        # begins (modulo the range's length): the eval's own, so that two
        # evals in flight that choose one node do not both take its lowest
        # free value and lose the node at the applier
        self.start = zlib.crc32(eval_id.encode())

    def _is_free(self, row: int, port: int, freed: Set[int]) -> bool:
        if port in self.claimed.get(row, ()):
            return False
        if port in freed:
            return True
        bit = (self.cm.port_words[row, port >> 5] >> np.uint32(port & 31)) & 1
        return not bit

    def claim_static(self, row: int, port: int, freed: Set[int]) -> bool:
        if not self._is_free(row, port, freed):
            return False
        self.claimed.setdefault(row, set()).add(port)
        return True

    def assign_dynamic(self, row: int, freed: Set[int]) -> Optional[int]:
        """First free port of the node's dynamic range at or after the
        claims' `start`, wrapping to the range's low end (the reference
        draws at random and then walks, NetworkIndex.AssignPorts), via a
        vectorized scan of the port bitset words (the naive per-port loop
        was O(range) per assignment in the placement hot path)."""
        lo = int(self.cm.dyn_port_lo[row])
        hi = int(self.cm.dyn_port_hi[row])
        w0, w1 = lo >> 5, (hi >> 5) + 1
        words = self.cm.port_words[row, w0:w1].copy()
        # freed ports clear first, plan-local claims override after — a
        # port both freed (by a stop/eviction) and already claimed by this
        # plan must stay used (mirrors _is_free's claimed-first ordering)
        for p in freed:
            if lo <= p <= hi:
                words[(p >> 5) - w0] &= ~np.uint32(1 << (p & 31))
        for p in self.claimed.get(row, ()):
            if lo <= p <= hi:
                words[(p >> 5) - w0] |= np.uint32(1 << (p & 31))
        # mask bits outside [lo, hi] as used
        words[0] |= ~(np.uint32(0xFFFFFFFF) << np.uint32(lo & 31))
        hi_bit = hi & 31
        last_mask = np.uint32(
            (np.uint64(1) << np.uint64(hi_bit + 1)) - np.uint64(1))
        words[-1] |= ~last_mask
        first = lo + self.start % (hi - lo + 1)
        w = (first >> 5) - w0
        # the start's own word with the values below the start taken
        word = int(words[w]) | ((1 << (first & 31)) - 1)
        if word == 0xFFFFFFFF:
            free = np.flatnonzero(words != np.uint32(0xFFFFFFFF))
            if len(free) == 0:
                return None
            after = int(np.searchsorted(free, w + 1))
            w = int(free[after if after < len(free) else 0])
            word = int(words[w])
        inv = ~word & 0xFFFFFFFF
        bit = (inv & -inv).bit_length() - 1   # lowest free bit
        p = ((w0 + w) << 5) + bit
        self.claimed.setdefault(row, set()).add(p)
        return p


def allocs_leave(used: np.ndarray, row: int, allocs, freed_ports=None,
                 preemptor=None, deltas=None) -> None:
    """Allocations that leave node `row` (stopped, preempted) give their
    room back to `used`; where the caller keeps them, their ports are
    free ({row: set}), the `preemptor` offers them to no later slot and
    `deltas` takes (row, what was given back) for the engine."""
    for a in allocs:
        vec = comparable_vec(a.comparable_resources())
        used[row] -= vec
        if deltas is not None:
            deltas.append((row, -vec))
        if freed_ports is not None:
            freed_ports.setdefault(row, set()).update(a.ports())
    if preemptor is not None and allocs:
        preemptor.invalidate({a.id for a in allocs})


def build_allocation(
    job: Job,
    tg: TaskGroup,
    name: str,
    node_id: str,
    node_name: str,
    eval_id: str,
    row: int,
    ports: PortClaims,
    freed_ports: Set[int],
    metric: AllocMetric,
    previous: Optional[Allocation] = None,
    deployment_id: str = "",
    is_canary: bool = False,
    is_rescheduling: bool = False,
    now: float = 0.0,
    task_devices: Optional[Dict[str, List[dict]]] = None,
) -> Optional[Allocation]:
    """Construct the Allocation for one selected placement; returns None if
    port assignment fails (caller treats as exhausted node).
    `task_devices` carries pre-assigned device instances per task name
    (scheduler/device.go AllocateDevice output)."""
    task_nets: Dict[str, List[NetworkResource]] = {}
    shared_nets: List[NetworkResource] = []
    if tg.networks or any(t.resources.networks for t in tg.tasks):
        with tracing.span("sched.assign_ports"):
            got = _assign_ports(tg, row, ports, freed_ports)
        if got is None:
            return None
        task_nets, shared_nets = got
    tasks = {
        t.name: AllocatedTaskResources(
            cpu_shares=t.resources.cpu,
            memory_mb=t.resources.memory_mb,
            memory_max_mb=t.resources.memory_max_mb,
            networks=task_nets.get(t.name, []),
            devices=list((task_devices or {}).get(t.name, ())),
        ) for t in tg.tasks}
    shared_ports: List[NetworkPort] = [
        p for m in shared_nets for p in m.reserved_ports + m.dynamic_ports]

    alloc = Allocation(
        id=generate_uuid(),
        namespace=job.namespace,
        eval_id=eval_id,
        name=name,
        node_id=node_id,
        node_name=node_name,
        job_id=job.id,
        job=job,
        task_group=tg.name,
        allocated_resources=AllocatedResources(
            tasks=tasks,
            shared_disk_mb=tg.ephemeral_disk.size_mb,
            shared_networks=shared_nets,
            shared_ports=shared_ports,
        ),
        desired_status=AllocDesiredStatus.RUN,
        client_status=AllocClientStatus.PENDING,
        metrics=metric,
        deployment_id=deployment_id,
        create_time=now,
        modify_time=now,
    )
    if is_canary:
        alloc.deployment_status = {"canary": True, "healthy": None}
    if previous is not None:
        alloc.previous_allocation = previous.id
        if is_rescheduling:
            events = list(previous.reschedule_tracker.events) \
                if previous.reschedule_tracker else []
            events.append(RescheduleEvent(
                reschedule_time=now, prev_alloc_id=previous.id,
                prev_node_id=previous.node_id))
            alloc.reschedule_tracker = RescheduleTracker(events=events)
    return alloc


def materialize_bulk_allocs(
    job: Job,
    tg: TaskGroup,
    names: List[str],
    rows: np.ndarray,
    scores: np.ndarray,
    node_ids: List[str],
    node_names: Dict[int, str],
    eval_id: str,
    deployment_id: str,
    n_eval: int,
    n_exh: int,
    now: float,
) -> List[Allocation]:
    """Batch materialization for the bulk wavefront path: the resolved
    sparse output (already expanded to per-alloc `rows`/`scores` by
    native.expand_pairs) becomes Allocation records in one pass.

    Bulk-eligible groups have no ports, devices, or networks, so every
    alloc's resources are identical — ONE immutable AllocatedResources
    template is shared across the batch (read-only everywhere downstream,
    and it makes comparable_resources() memoization hit group-wide).
    Per-row AllocMetric instances are likewise shared by allocs landing
    on the same node.  uuids come from one native format_uuids call
    instead of K generate_uuid round trips."""
    from nomad_tpu import native as _native

    k_total = len(names)
    ids = _native.format_uuids(k_total)
    tasks = {
        t.name: AllocatedTaskResources(
            cpu_shares=t.resources.cpu,
            memory_mb=t.resources.memory_mb,
            memory_max_mb=t.resources.memory_max_mb,
            networks=[], devices=[])
        for t in tg.tasks}
    shared_res = AllocatedResources(
        tasks=tasks, shared_disk_mb=tg.ephemeral_disk.size_mb,
        shared_networks=[], shared_ports=[])
    metric_by_row: Dict[int, AllocMetric] = {}
    out: List[Allocation] = []
    for k in range(k_total):
        row = int(rows[k])
        m = metric_by_row.get(row)
        if m is None:
            m = AllocMetric()
            m.nodes_evaluated = n_eval
            m.nodes_exhausted = n_exh
            nid = node_ids[row]
            if nid:
                m.populate_score_meta([{
                    "node_id": nid,
                    "norm_score": round(float(scores[k]), 6)}])
            m.allocation_time_s = 0.0
            metric_by_row[row] = m
        out.append(Allocation(
            id=ids[k],
            namespace=job.namespace,
            eval_id=eval_id,
            name=names[k],
            node_id=node_ids[row],
            node_name=node_names.get(row, ""),
            job_id=job.id,
            job=job,
            task_group=tg.name,
            allocated_resources=shared_res,
            desired_status=AllocDesiredStatus.RUN,
            client_status=AllocClientStatus.PENDING,
            metrics=m,
            deployment_id=deployment_id,
            create_time=now,
            modify_time=now))
    return out


def _assign_ports(tg: TaskGroup, row: int, ports: PortClaims,
                  freed: Set[int]):
    """({task: its networks}, the group's networks) with every asked
    port claimed on `row`, or None at the first that cannot be."""
    task_nets: Dict[str, List[NetworkResource]] = {}
    for t in tg.tasks:
        nets = task_nets[t.name] = []
        for net in t.resources.networks:
            nets.append(_materialize_net(net, row, ports, freed))
            if nets[-1] is None:
                return None
    shared_nets: List[NetworkResource] = []
    for net in tg.networks:
        shared_nets.append(_materialize_net(net, row, ports, freed))
        if shared_nets[-1] is None:
            return None
    return task_nets, shared_nets


def _materialize_net(net: NetworkResource, row: int, ports: PortClaims,
                     freed: Set[int]) -> Optional[NetworkResource]:
    out = net.copy()
    for p in out.reserved_ports:
        if not ports.claim_static(row, p.value, freed):
            return None
    for p in out.dynamic_ports:
        got = ports.assign_dynamic(row, freed)
        if got is None:
            return None
        p.value = got
    return out
