"""ClusterMatrix: an incrementally-maintained columnar mirror of cluster
state, and the per-eval demand tensors shipped to the device kernels.

Reference analog: the scheduler's per-node object walks
(scheduler/rank.go BinPackIterator over RankedNode, nomad/state hot reads).
Here the state store maintains this mirror incrementally (SURVEY.md section
2.7 item 7) so an evaluation never rebuilds O(nodes) state from scratch —
it only assembles small per-job tensors plus views of resident arrays.

Axes and padding: the node axis is padded to power-of-two buckets (minimum
8) so XLA sees a small, stable set of shapes across evals (avoids
recompiles; SURVEY.md section 7 "dynamic shapes").
"""
from __future__ import annotations

import threading
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from nomad_tpu.encode.attrs import AttrTable
from nomad_tpu.telemetry import global_metrics

# Resource dimension layout of the dense matrices.  Network bandwidth is a
# first-class dimension: where the reference accounts MBits inside
# NetworkIndex (structs/network.go:39,178), the dense design folds it into
# the same capacity/used matrices so fit checks, plan validation and the
# preemption kernel all cover bandwidth for free (ScoreFitBinPack still
# scores cpu+mem only, matching funcs.go:259-279).
RES_CPU, RES_MEM, RES_DISK, RES_NET = 0, 1, 2, 3
NUM_RESOURCE_DIMS = 4


def comparable_vec(cr) -> "np.ndarray":
    """f32[R] dense resource vector of a ComparableResources."""
    return np.array(
        [cr.cpu_shares, cr.memory_mb, cr.disk_mb,
         sum(n.mbits for n in cr.networks)], dtype=np.float32)

_PORT_WORDS = 65536 // 32


def pad_to_bucket(n: int, minimum: int = 8) -> int:
    b = minimum
    while b < n:
        b *= 2
    return b


class ClusterMatrix:
    """Dense, incrementally-updated node-axis mirror.

    Rows are stable: a node keeps its row for its lifetime; removed rows are
    recycled.  All arrays are kept at `capacity_rows` (a power-of-two
    bucket) and grown by re-bucketing when full.
    """

    def __init__(self, initial_rows: int = 8):
        cap = pad_to_bucket(initial_rows)
        self._n_rows = cap
        self.row_of: Dict[str, int] = {}
        self.node_ids: List[Optional[str]] = [None] * cap
        self._free_rows: List[int] = list(range(cap - 1, -1, -1))

        self.capacity = np.zeros((cap, NUM_RESOURCE_DIMS), dtype=np.float32)
        self.used = np.zeros((cap, NUM_RESOURCE_DIMS), dtype=np.float32)
        self.ready = np.zeros(cap, dtype=bool)
        self.attrs = AttrTable(cap)
        # used ports bitset per node (static collision + dynamic capacity)
        self.port_words = np.zeros((cap, _PORT_WORDS), dtype=np.uint32)
        self.dyn_port_lo = np.full(cap, 20000, dtype=np.int32)
        self.dyn_port_hi = np.full(cap, 32000, dtype=np.int32)
        # free ports of each row's own [lo, hi], 0 for a row with no node:
        # moved where a bit of `port_words` flips, recounted where a row's
        # words are rebuilt, so an eval reads it and counts nothing
        self._dyn_ports_free = np.zeros(cap, dtype=np.int32)
        # device-group id -> i32[N] instance capacity / committed usage
        self.device_caps: Dict[str, np.ndarray] = {}
        self.device_used: Dict[str, np.ndarray] = {}
        # device-group id -> {attribute key -> i32[N] code of the node's
        # value in `device_attr_values`}; code 0 = the group (or the
        # attribute) is not on the node.  A device constraint is then one
        # predicate per distinct value and a gather, never a walk over
        # node structs (scheduler/feasible.py device_fit).
        self.device_attr_codes: Dict[str, Dict[str, np.ndarray]] = {}
        self.device_attr_values: List[object] = [None]
        self._device_attr_rank: Dict[Tuple[str, object], int] = {}
        # computed-class ordinal per row (-1 = empty row): lets blocked-eval
        # class-eligibility reduce as a vectorized groupby instead of an
        # O(N) Python node walk (reference EvalEligibility keying)
        self.class_codes = np.full(cap, -1, dtype=np.int32)
        self.class_names: List[str] = []
        self._class_rank: Dict[str, int] = {}
        # generation counter bumped on any mutation (device cache invalidation)
        self.generation = 0
        # authoritative live-alloc usage, keyed by node id so it survives node
        # churn and alloc-before-node replay order:
        #   node_id -> {alloc_id: (res_vec, ports, devices, job priority)}
        self._node_allocs: Dict[str, Dict[str, Tuple[
            np.ndarray, Tuple[int, ...], Dict[str, int], int]]] = {}
        self._alloc_node: Dict[str, str] = {}  # alloc_id -> node_id
        # the slot table: every live allocation of every row, whatever its
        # priority, a slot each, a row by priority (what a preemption
        # search takes its candidates from).  The writers of
        # `used` mark the row they change; `candidates` lays the marked
        # rows out again from `_node_allocs` before it reads, so a write
        # costs one set add and a reader the rows written since the last
        self.alloc_res = np.zeros((cap, 4, NUM_RESOURCE_DIMS), np.float32)
        self.alloc_prio = np.zeros((cap, 4), np.int32)
        self.alloc_live = np.zeros((cap, 4), bool)
        # row -> the ids of its slots, a tuple a layout replaces whole
        self.alloc_ids: List[Tuple[str, ...]] = [()] * cap
        self._stale_rows: set = set()
        # the writers' lock, the store's own once a store holds the matrix:
        # a reader that wants several arrays of one commit takes it
        self.lock = threading.RLock()

    # ------------------------------------------------------------- rows

    @property
    def n_rows(self) -> int:
        return self._n_rows

    def _grow(self) -> None:
        old = self._n_rows
        new = old * 2
        self.capacity = np.vstack([self.capacity, np.zeros((old, NUM_RESOURCE_DIMS), np.float32)])
        self.used = np.vstack([self.used, np.zeros((old, NUM_RESOURCE_DIMS), np.float32)])
        self.ready = np.concatenate([self.ready, np.zeros(old, bool)])
        self.port_words = np.vstack([self.port_words, np.zeros((old, _PORT_WORDS), np.uint32)])
        self.dyn_port_lo = np.concatenate([self.dyn_port_lo, np.full(old, 20000, np.int32)])
        self.dyn_port_hi = np.concatenate([self.dyn_port_hi, np.full(old, 32000, np.int32)])
        self._dyn_ports_free = np.concatenate(
            [self._dyn_ports_free, np.zeros(old, np.int32)])
        self._double_slot_table(axis=0)
        self.alloc_ids.extend([()] * old)
        self.node_ids.extend([None] * old)
        self._free_rows.extend(range(new - 1, old - 1, -1))
        self.class_codes = np.concatenate(
            [self.class_codes, np.full(old, -1, np.int32)])
        self.attrs.resize(new)
        for k in self.device_caps:
            self.device_caps[k] = np.concatenate(
                [self.device_caps[k], np.zeros(old, np.int32)])
        for k in self.device_used:
            self.device_used[k] = np.concatenate(
                [self.device_used[k], np.zeros(old, np.int32)])
        for cols in self.device_attr_codes.values():
            for k in cols:
                cols[k] = np.concatenate([cols[k], np.zeros(old, np.int32)])
        self._n_rows = new

    # ------------------------------------------------------------- nodes

    def upsert_node(self, node) -> int:
        row = self.row_of.get(node.id)
        if row is None:
            if not self._free_rows:
                self._grow()
            row = self._free_rows.pop()
            self.row_of[node.id] = row
            self.node_ids[row] = node.id
            # what was tracked before the row was (allocations that
            # arrived before their node, a node that comes back) gets its
            # slots; a row that stays keeps them: no field of a node moves one
            self._stale_rows.add(row)
        res = node.node_resources
        rr = node.reserved_resources
        self.capacity[row, RES_CPU] = res.cpu.cpu_shares - rr.cpu_shares
        self.capacity[row, RES_MEM] = res.memory_mb - rr.memory_mb
        self.capacity[row, RES_DISK] = res.disk_mb - rr.disk_mb
        self.capacity[row, RES_NET] = sum(n.mbits for n in res.networks)
        self.ready[row] = node.ready()
        cc = getattr(node, "computed_class", "") or ""
        code = self._class_rank.get(cc)
        if code is None:
            code = self._class_rank[cc] = len(self.class_names)
            self.class_names.append(cc)
        self.class_codes[row] = code
        self.attrs.set_node_row(row, node)
        # drivers become attr columns like the reference's driver.<name> attrs
        for name, info in node.drivers.items():
            healthy = info.get("detected") and info.get("healthy", True)
            self.attrs.column(f"attr.driver.{name}").set(
                row, "1" if healthy else None)
        # host volumes: column per volume name, value "ro"/"rw"
        for name, vol in node.host_volumes.items():
            self.attrs.column(f"hostvol.{name}").set(
                row, "ro" if vol.get("read_only") else "rw")
        # CSI node plugins: column per plugin id, "1" = healthy
        for pid, info in node.csi_node_plugins.items():
            self.attrs.column(f"csiplugin.{pid}").set(
                row, "1" if info.get("healthy") else None)
        # device capacity: numeric count column per device-group id (clear
        # stale groups first — re-registration may drop devices)
        for col in self.device_caps.values():
            col[row] = 0
        self._clear_device_attrs(row)
        for dev in node.node_resources.devices:
            col = self.device_caps.setdefault(
                dev.id, np.zeros(self._n_rows, dtype=np.int32))
            # unhealthy instances don't count as schedulable capacity
            col[row] = len(dev.healthy_ids())
            cols = self.device_attr_codes.setdefault(dev.id, {})
            for key, value in dev.attributes.items():
                ident = (type(value).__name__, value)
                code = self._device_attr_rank.get(ident)
                if code is None:
                    code = self._device_attr_rank[ident] = \
                        len(self.device_attr_values)
                    self.device_attr_values.append(value)
                if key not in cols:
                    cols[key] = np.zeros(self._n_rows, dtype=np.int32)
                cols[key][row] = code
        self.dyn_port_lo[row] = res.min_dynamic_port
        self.dyn_port_hi[row] = res.max_dynamic_port
        words = np.zeros(_PORT_WORDS, dtype=np.uint32)
        for p in rr.reserved_ports:
            words[p >> 5] |= np.uint32(1 << (p & 31))
        # re-apply this node's live-alloc usage (covers allocs that arrived
        # before the node row existed, and node re-registration)
        self.used[row] = 0
        for col in self.device_used.values():
            col[row] = 0
        for vec, ports, devs, _prio in \
                self._node_allocs.get(node.id, {}).values():
            self.used[row] += vec
            for p in ports:
                words[p >> 5] |= np.uint32(1 << (p & 31))
            for gid, cnt in devs.items():
                col = self.device_used.setdefault(
                    gid, np.zeros(self._n_rows, dtype=np.int32))
                col[row] += cnt
        self.port_words[row] = words
        self._dyn_ports_free[row] = self._count_free_dynamic_ports(row)
        self.generation += 1
        return row

    def remove_node(self, node_id: str) -> None:
        row = self.row_of.pop(node_id, None)
        if row is None:
            return
        self.node_ids[row] = None
        self.capacity[row] = 0
        self.used[row] = 0
        self.ready[row] = False
        self.class_codes[row] = -1
        self.port_words[row] = 0
        self._dyn_ports_free[row] = 0
        for col in self.device_caps.values():
            col[row] = 0
        for col in self.device_used.values():
            col[row] = 0
        self._clear_device_attrs(row)
        self.attrs.clear_row(row)
        self._stale_rows.add(row)
        self._free_rows.append(row)
        self.generation += 1

    def _clear_device_attrs(self, row: int) -> None:
        for cols in self.device_attr_codes.values():
            for col in cols.values():
                col[row] = 0

    # ------------------------------------------------------------- allocs

    @staticmethod
    def _alloc_res_vec(alloc) -> np.ndarray:
        return comparable_vec(alloc.comparable_resources())

    @staticmethod
    def _alloc_devices(alloc) -> Dict[str, int]:
        """device group id -> instance count used by this alloc."""
        out: Dict[str, int] = {}
        for tr in alloc.allocated_resources.tasks.values():
            for d in tr.devices:
                gid = f"{d['vendor']}/{d['type']}/{d['name']}"
                out[gid] = out.get(gid, 0) + len(d.get("device_ids", []))
        return out

    def _write_ports(self, row: int, ports: Sequence[int],
                     held: bool) -> None:
        """Set (or clear) `ports` in the row's bitset.  The row's free
        count follows the bits, not the allocations: it moves by one for
        each bit inside the row's dynamic range that really flips, so a
        port two allocations hold, or one the node also reserves, counts
        exactly as `port_words` shows it."""
        if not ports:
            return
        words = self.port_words[row]
        lo, hi = int(self.dyn_port_lo[row]), int(self.dyn_port_hi[row])
        flipped = 0
        for p in ports:
            w, bit = p >> 5, np.uint32(1 << (p & 31))
            if bool(words[w] & bit) == held:
                continue
            words[w] ^= bit
            flipped += lo <= p <= hi
        if flipped:
            self._dyn_ports_free[row] += -flipped if held else flipped

    def _double_slot_table(self, axis: int) -> None:
        """Twice the rows (axis 0) or twice the slots a row (axis 1)."""
        for name in ("alloc_res", "alloc_prio", "alloc_live"):
            old = getattr(self, name)
            setattr(self, name, np.concatenate(
                [old, np.zeros_like(old)], axis=axis))

    def _untrack(self, alloc_id: str) -> None:
        node_id = self._alloc_node.pop(alloc_id, None)
        if node_id is None:
            return
        vec, ports, devs, _prio = self._node_allocs[node_id].pop(alloc_id)
        row = self.row_of.get(node_id)
        if row is not None:
            self._stale_rows.add(row)
            self.used[row] -= vec
            self._write_ports(row, ports, False)
            for gid, n in devs.items():
                col = self.device_used.get(gid)
                if col is not None:
                    col[row] -= n

    def upsert_alloc(self, alloc) -> None:
        """Track / untrack an allocation's resource usage on its node.
        Terminal allocations contribute nothing (AllocsFit semantics,
        funcs.go:174-178).  Usage is tracked even when the node row does not
        exist yet (restore/replay order), and applied when the node appears.
        """
        self._untrack(alloc.id)
        if not alloc.terminal_status() and alloc.node_id:
            vec = self._alloc_res_vec(alloc)
            ports = alloc.ports()
            devs = self._alloc_devices(alloc)
            prio = alloc.job.priority if alloc.job is not None else 50
            self._node_allocs.setdefault(alloc.node_id, {})[alloc.id] = \
                (vec, ports, devs, prio)
            self._alloc_node[alloc.id] = alloc.node_id
            row = self.row_of.get(alloc.node_id)
            if row is not None:
                self._stale_rows.add(row)
                self.used[row] += vec
                self._write_ports(row, ports, True)
                for gid, n in devs.items():
                    col = self.device_used.setdefault(
                        gid, np.zeros(self._n_rows, dtype=np.int32))
                    col[row] += n
        self.generation += 1

    def remove_alloc(self, alloc_id: str) -> None:
        if alloc_id in self._alloc_node:
            self._untrack(alloc_id)
            self.generation += 1

    # ------------------------------------------------------------- views

    def rows_for(self, node_ids: Sequence[str]) -> np.ndarray:
        return np.array([self.row_of[i] for i in node_ids if i in self.row_of],
                        dtype=np.int32)

    def dc_mask(self, datacenters: Sequence[str]) -> np.ndarray:
        col = self.attrs.column("node.datacenter")
        want = set(datacenters)
        return np.array([v in want for v in col.values], dtype=bool)

    def free_dynamic_ports(self) -> np.ndarray:
        """i32[N], the caller's own: free ports in each node's own dynamic
        range [lo, hi], exact at bit granularity; 0 for a row that holds
        no node.  A copy of the column the writers of `port_words` keep."""
        return self._dyn_ports_free.copy()

    def _count_free_dynamic_ports(self, row: int) -> int:
        """The row's free count from its words: a masked popcount over
        the word window of its range (an inverted range holds nothing)."""
        lo, hi = int(self.dyn_port_lo[row]), int(self.dyn_port_hi[row])
        if hi < lo:
            return 0
        words = self.port_words[row, lo >> 5:(hi >> 5) + 1].copy()
        # mask off bits below lo in the first word / above hi in the last
        words[0] &= np.uint32(0xFFFFFFFF) << np.uint32(lo & 31)
        words[-1] &= np.uint32((1 << ((hi & 31) + 1)) - 1)
        return (hi - lo + 1) - int(_POPCOUNT_TABLE[words.view(np.uint8)].sum())

    def _recount_free_dynamic_ports(self) -> np.ndarray:
        """What `free_dynamic_ports` has to equal, counted from
        `port_words` row by row: the tests' oracle, on no eval's path."""
        out = np.zeros(self._n_rows, dtype=np.int32)
        for row in self.row_of.values():
            out[row] = self._count_free_dynamic_ports(row)
        return out

    def candidates(self, max_prio: int):
        """-> (res f32[N, A, R], prio i32[N, A], valid bool[N, A], ids),
        the caller's own: each row's live allocations of job priority
        `max_prio` or less, from index 0, `A` the bucket of the widest row
        of them, and row -> the ids of the row's slots, as one commit left
        them (under the writers' lock).  A row lies by priority, so what
        may go is a prefix of it."""
        with self.lock:
            stale = len(self._stale_rows)
            self._lay_out_stale_rows()
            valid = self.alloc_live & (self.alloc_prio <= max_prio)
            width = pad_to_bucket(
                int(valid.sum(axis=1).max(initial=1)), minimum=4)
            got = (self.alloc_res[:, :width].copy(),
                   self.alloc_prio[:, :width].copy(),
                   valid[:, :width].copy(), list(self.alloc_ids))
        global_metrics.incr("nomad.matrix.cand_views")
        global_metrics.incr("nomad.matrix.cand_rows_laid_out", stale)
        return got

    def _lay_out_stale_rows(self) -> None:
        """The slots of every row written since the last time, from what
        its node tracks now: lowest priority first, and among equals in
        the order the node tracks them; none for a row whose node has
        gone."""
        for row in self._stale_rows:
            tracked = self._node_allocs.get(self.node_ids[row], {})
            n = len(tracked)
            while n > self.alloc_live.shape[1]:
                self._double_slot_table(axis=1)
            held = sorted(tracked.items(), key=_by_priority)
            self.alloc_live[row] = False
            self.alloc_ids[row] = tuple(alloc_id for alloc_id, _h in held)
            if n:
                self.alloc_res[row, :n] = [h[0] for _id, h in held]
                self.alloc_prio[row, :n] = [h[3] for _id, h in held]
                self.alloc_live[row, :n] = True
        self._stale_rows.clear()

    def _recount_candidates(self) -> Dict[int, Dict[str, tuple]]:
        """What the slot table has to hold, row -> {allocation id ->
        (resources, priority)}, from `_node_allocs` row by row: the tests'
        oracle, on no eval's path."""
        return {row: {alloc_id: (tuple(vec.tolist()), prio)
                      for alloc_id, (vec, _ports, _devs, prio)
                      in self._node_allocs.get(node_id, {}).items()}
                for node_id, row in self.row_of.items()}

    def alloc_ports(self, row: int, alloc_id: str) -> Tuple[int, ...]:
        """The ports a live allocation of the row's node holds; none for
        one that is no longer tracked there."""
        held = self._node_allocs.get(self.node_ids[row], {}).get(alloc_id)
        return held[1] if held is not None else ()

    def static_ports_free(self, ports: Sequence[int]) -> np.ndarray:
        """bool[N]: True where none of `ports` is already claimed."""
        if not ports:
            return np.ones(self._n_rows, dtype=bool)
        mask = np.ones(self._n_rows, dtype=bool)
        for p in ports:
            bit = (self.port_words[:, p >> 5] >> np.uint32(p & 31)) & np.uint32(1)
            mask &= bit == 0
        return mask


def _by_priority(tracked_item) -> int:
    return tracked_item[1][3]


_POPCOUNT_TABLE = np.array([bin(i).count("1") for i in range(256)], dtype=np.int64)


@dataclass
class EvalTensors:
    """Everything one evaluation's placement pass needs, in dense form.

    Shapes: N = padded node rows, G = padded distinct task groups,
    S = padded placement slots (one per missing alloc instance).
    """
    # node axis (views/copies of ClusterMatrix state at snapshot time)
    capacity: np.ndarray          # f32[N, R]
    used: np.ndarray              # f32[N, R] — proposed usage basis for this eval
    # per-task-group
    feasible: np.ndarray          # bool[G, N] — constraints+driver+dc+ready+ports
    affinity: np.ndarray          # f32[G, N] — normalized affinity sum per node
    has_affinity: np.ndarray      # bool[G]
    desired_count: np.ndarray     # i32[G]
    penalty: np.ndarray           # bool[G, N] — rescheduling penalty nodes
    proposed_tg_count: np.ndarray # i32[G, N] — existing co-placed allocs of (job, tg)
    # spread scoring (zero-filled when the job has no spreads)
    spread_weight: np.ndarray     # f32[G] — sum of |weights| (0 = no spread)
    spread_boost: np.ndarray      # f32[G, N] — precomputed per-node spread boost
    # per-placement-slot
    demand: np.ndarray            # f32[S, R]
    slot_tg: np.ndarray           # i32[S] — index into G
    slot_active: np.ndarray       # bool[S]
    # metadata
    n_real_nodes: int = 0
    slot_names: List[str] = field(default_factory=list)      # alloc names per slot
    tg_names: List[str] = field(default_factory=list)
    node_rows: Optional[np.ndarray] = None                   # row -> ClusterMatrix row


def make_eval_tensors(n_nodes: int, n_groups: int, n_slots: int) -> EvalTensors:
    """Allocate zero-filled EvalTensors with padded shapes."""
    N = pad_to_bucket(max(n_nodes, 1))
    G = pad_to_bucket(max(n_groups, 1), minimum=1)
    S = pad_to_bucket(max(n_slots, 1), minimum=1)
    R = NUM_RESOURCE_DIMS
    return EvalTensors(
        capacity=np.zeros((N, R), np.float32),
        used=np.zeros((N, R), np.float32),
        feasible=np.zeros((G, N), bool),
        affinity=np.zeros((G, N), np.float32),
        has_affinity=np.zeros(G, bool),
        desired_count=np.ones(G, np.int32),
        penalty=np.zeros((G, N), bool),
        proposed_tg_count=np.zeros((G, N), np.int32),
        spread_weight=np.zeros(G, np.float32),
        spread_boost=np.zeros((G, N), np.float32),
        demand=np.zeros((S, R), np.float32),
        slot_tg=np.zeros(S, np.int32),
        slot_active=np.zeros(S, bool),
        n_real_nodes=n_nodes,
    )
