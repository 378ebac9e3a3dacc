"""PlacementEngine: adaptive batching dispatcher for the dense kernels.

The north-star serving path (reference nomad/worker.go:81-85 — N scheduler
workers processing evals concurrently — and BASELINE.json "pmap across
evaluations in the EvalBroker queue"): scheduler workers block in
`place()`, a single dispatcher thread coalesces every request that arrived
while the previous dispatch was in flight into ONE device call
(`ops.place.place_batch_packed_jit`, a chained `lax.scan` over the eval
axis over the packed single-leaf transport), resolves the G x N-scale
tensors through a content-addressed device-resident cache (hits ship
zero bytes), ships the rest with one host->device transfer and fetches
all results with one device->host transfer.

Why chained instead of independent (vmap/pmap): evals scored against the
same usage basis all argmax onto the same best nodes, so independent
batching turns into plan-applier conflicts and retries; the chained scan
threads the proposed-usage matrix through the batch, making results
identical to sequential worker processing while paying one dispatch
and one host<->device hand-off per *batch* instead of per *eval*.

Batching is adaptive with no artificial delay window: an idle engine
dispatches a lone request immediately (an E=1 variant of the packed
kernel, its own one-time XLA compile), and the in-flight device time is
the window in which the next batch accumulates.
"""
from __future__ import annotations

import logging
import os
import threading
import time as _time
from concurrent.futures import Future
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from nomad_tpu import chaos, knobs
from nomad_tpu import native as _native
from nomad_tpu import tracing
from nomad_tpu.analysis import race
from nomad_tpu.encode.matrixizer import NUM_RESOURCE_DIMS, pad_to_bucket
from nomad_tpu.ops.place import (
    SPARSE_CAP,
    PlaceInputs,
    PlaceResult,
    bulk_heavy_digest,
    heavy_digest,
    heavy_dims,
    pack_bulk_heavy,
    pack_bulk_light,
    pack_heavy,
    pack_light,
    place_batch_packed_jit,
    place_bulk_batch_donate_jit,
    unpack_bulk_batch,
    unpack_outputs,
)

from nomad_tpu.parallel.world import DeviceWorld, mesh_key

log = logging.getLogger(__name__)

# transfer-purity (nomad_tpu.analysis): the dispatch loop is hot-path —
# implicit host<->device movement is a finding; the few sanctioned
# device_put sites (cache fills, per-dispatch dynamic leaf) carry
# transfer-purity suppression comments with their reason
_TRANSFER_HOT_PATH = True

# fixed sparse-delta slot count per eval: a CONSTANT so the delta axis
# never forks another XLA compile variant (every distinct D was a full
# recompile, billed mid-serving).  Evals with more deltas than this fold
# them into a pre-applied basis instead (rare: deltas are one eval's
# stops + sticky preplacements).
_DELTA_BUCKET = 64
# canonical slot-axis buckets, same rationale: per-eval slot counts vary
# (retries place the remainder), and every distinct S was a compile
_S_BUCKETS = (16, 128, 1024)


def _s_bucket(n: int) -> int:
    return next((b for b in _S_BUCKETS if b >= n), pad_to_bucket(n))


def _scan_bound(slot_active) -> int:
    """Slot steps the scan kernel runs for one eval: the index of its
    last active slot + 1 (the host's reading of `ops.place._scan_slots`'
    bound)."""
    on = np.flatnonzero(slot_active)
    return int(on[-1]) + 1 if on.size else 0


def _fold_overflow(basis: "np.ndarray", deltas):
    """Apply an oversized delta list directly into a PRIVATE basis copy
    (the fixed delta bucket cannot carry it without forking an XLA
    compile variant).  Returns the effective shipped delta list ([]) —
    consumers must use it instead of the request's own deltas or the
    fold double-counts."""
    n = basis.shape[0]
    for row, vec in deltas:
        if row < n:
            basis[row] += vec
    return []


class _DeviceCache:
    """Content-addressed device-resident array cache (LRU).

    The G x N-scale placement tensors are identical across every eval of
    the same (job version, cluster epoch, alloc set) — the common case for
    a job's worth of evals and for retries — so a content fingerprint
    dedupes them and a hit ships ZERO bytes to the device.  This is the
    SURVEY §7 prescription ("keep the node matrix resident, ship deltas")
    applied to the per-eval tensors that actually dominate transfer bytes
    (VERDICT r3: put_s was 79%% of e2e wall)."""

    def __init__(self, max_entries: int = 128):
        from collections import OrderedDict
        self.max_entries = max_entries
        self._d = OrderedDict()
        self._stacks = OrderedDict()
        self._lock = threading.Lock()
        self.hits = 0
        self.misses = 0

    def _get_or_put(self, key, build):  # analysis: allow(transfer-purity) — cache-fill upload: a miss ships once so every later hit ships zero bytes
        import jax
        return self._get_or_put_device(key, lambda: jax.device_put(build()))

    def _get_or_put_device(self, key, build_device):
        """build_device() must return the final (device-resident) value."""
        with self._lock:
            v = self._d.get(key)
            if v is not None:
                self._d.move_to_end(key)
                self.hits += 1
                return v
        arr = build_device()
        with self._lock:
            self._d[key] = arr
            self.misses += 1
            while len(self._d) > self.max_entries:
                self._d.popitem(last=False)
        return arr

    def sharded(self, tag, mesh, pytree, shardings, key=None):
        """Content-addressed sharded placement of a pytree: a hit returns
        the device-resident (already mesh-sharded) arrays with zero bytes
        shipped — the multi-chip twin of heavy()/bulk_heavy().

        `pytree` may be a zero-arg callable so a hit skips BUILDING the
        host arrays entirely (the per-dispatch np.stack of an E-chain was
        itself a hit-path cost at C2M-1M rates).  `key` carries a
        caller-computed content key (per-request digests); when omitted
        the pytree leaves are hashed, which forces materialization.

        Keyed on the mesh's (axis layout, device ids) — `id(mesh)` is not
        an identity: a re-created Mesh can reuse a dead mesh's id and
        resurrect entries with stale shardings."""
        import hashlib

        import jax
        build = pytree if callable(pytree) else None
        if key is None:
            if build is not None:
                pytree = build()
                build = None
            h = hashlib.blake2b(digest_size=16)
            for leaf in jax.tree_util.tree_leaves(pytree):
                h.update(np.ascontiguousarray(leaf).tobytes())
            key = h.digest()
        if build is None:
            tree = pytree
            build = lambda: tree                 # noqa: E731
        full_key = ("sh", tag, mesh_key(mesh), key)
        return self._get_or_put_device(
            full_key,
            lambda: jax.device_put(build(), shardings))  # analysis: allow(transfer-purity) — sharded cache fill: one sanctioned upload per content key

    def heavy(self, inputs: PlaceInputs):
        """Device-resident packed heavy block for one eval's inputs."""
        key = (heavy_dims(inputs), heavy_digest(inputs))
        return self._get_or_put(key, lambda: pack_heavy(inputs))

    def bulk_heavy(self, r, digest: bytes = None):
        """Device-resident packed node-axis block of one bulk request.
        `digest` lets dispatch reuse a digest it already computed."""
        if digest is None:
            digest = bulk_heavy_digest(r.feasible, r.affinity, r.penalty,
                                       r.coll0)
        key = ("bulk", r.feasible.shape[0], digest)
        return self._get_or_put(
            key, lambda: pack_bulk_heavy(r.feasible, r.affinity,
                                         r.penalty, r.coll0))

    def stack(self, key, build_device):
        """Device-resident STACKED per-dispatch tensor (the [E, ...]
        chain of an entire bulk dispatch).  Entries are E x the per-eval
        size, so they keep their own short LRU instead of crowding the
        main cache; a hit skips both the host stack and the device-side
        jnp.stack dispatch."""
        with self._lock:
            v = self._stacks.get(key)
            if v is not None:
                self._stacks.move_to_end(key)
                self.hits += 1
                return v
        arr = build_device()
        with self._lock:
            self._stacks[key] = arr
            self.misses += 1
            while len(self._stacks) > 4:
                self._stacks.popitem(last=False)
        return arr


@dataclass
class _Request:
    cm: object                      # ClusterMatrix the inputs were built from
    inputs: PlaceInputs             # numpy-backed; .used already has deltas applied
    deltas: List[Tuple[int, np.ndarray]]   # (row, f32[R]) sparse usage deltas
    spread_algorithm: bool
    future: Future
    enq: float = 0.0                # perf_counter at submit (0: not queued)
    ctx: object = None              # sampled trace context, else None

    def shape_key(self):
        i = self.inputs
        # the slot axis pads to a canonical bucket at dispatch, so evals
        # sharing a bucket batch together regardless of raw slot count
        return (id(self.cm), self.spread_algorithm, i.feasible.shape,
                i.spread_vidx.shape, i.spread_desired.shape,
                i.hosts_taken.shape, i.prop_counts.shape,
                _s_bucket(i.demand.shape[0]), i.demand.shape[1])


@dataclass
class _BulkRequest:
    """One wavefront bulk eval (many identical slots of one task group,
    spreads/distinct/ports/devices inactive) for the batched bulk kernel."""
    cm: object
    feasible: np.ndarray            # bool[N]
    affinity: np.ndarray            # f32[N]
    has_affinity: bool
    desired: int
    penalty: np.ndarray             # bool[N]
    coll0: np.ndarray               # i32[N] existing co-placements
    demand: np.ndarray              # f32[R]
    count: int
    deltas: List[Tuple[int, np.ndarray]]
    spread_algorithm: bool
    future: Future
    enq: float = 0.0                # perf_counter at submit (0: not queued)
    ctx: object = None              # sampled trace context, else None
    # lane affinity on the 2-D mesh: requests sharing a wave_key (the
    # eval's namespace) chain in ONE lane; distinct keys spread across
    # the mesh's 'wave' columns and score concurrently
    wave_key: str = ""

    def shape_key(self):
        return ("bulk", id(self.cm), self.spread_algorithm,
                self.feasible.shape[0])


@dataclass
class _PendingBulk:
    """One in-flight bulk dispatch (donated-carry pipeline): the device
    computes while the engine preps + dispatches the next part against
    the adopted carry; _drain_record fetches and resolves it."""
    reqs: List
    out: object                     # device outputs (packed or tuple)
    world: object                   # DeviceWorld the dispatch scored on
    deltas_per: List
    mapping: object                 # sharded lane mapping or None


class PlacementEngine:
    """One per process.  Thread-safe; callers block in `place()`.

    In-flight usage overlay: the basis each dispatch starts from is
    `cm.used + overlay`, where the overlay sums the placements (and
    sticky pre-placement adds) of every eval whose plan has not yet
    committed.  Without it, batch N+1 would score against state that
    misses batch N's still-uncommitted plans and pile onto the same
    best-fit nodes (the reference pays for this optimism with plan-applier
    partial commits + scheduler retries, worker.go:81-85 /
    plan_apply.go:400).  Callers release their contribution via
    `complete(ticket)` once their plan has been applied (or abandoned) —
    the scheduler does this right after Planner.SubmitPlan returns."""

    # happens-before (nomad_tpu.analysis): the in-flight overlay table is
    # written by scheduler workers (register_external*), the plan applier
    # (complete_many) and the engine thread (_register/_basis_for)
    # concurrently; every access must hold _overlay_lock.  The runtime
    # race detector (NOMAD_TPU_RACE=1) traces it through these hooks.
    _RACE_TRACED = {"_overlays": "_overlay_lock"}

    # eval-axis compile buckets: lax.scan compile cost is E-independent
    # (one While body), so buckets only bound padding waste, and a pad
    # eval costs no slot step on either path: a scan-path pad has no
    # active slot, so its slot loop runs 0 steps (ops.place._scan_slots;
    # on a mesh the node-sharded scan still runs all S), and bulk pads
    # exit immediately.  Bulk chains run longer (each dispatch pays a
    # fixed launch + fetch cost, so more evals per dispatch wins at
    # C2M-1M rates); the scan buckets date from when a pad eval ran its
    # S steps and have not been retuned since (ROADMAP S34).
    E_BUCKETS = (1, 8, 16, 48)
    BULK_E_BUCKETS = (1, 8, 16, 48, 128, 512)

    def __init__(self, max_batch: int = 512,
                 shard_min_nodes: Optional[int] = None):
        # batches are sliced at max_batch before grouping; scan-path
        # groups re-chunk to their largest compile bucket below
        self.max_batch = min(max_batch, self.BULK_E_BUCKETS[-1])
        self.scan_max_batch = self.E_BUCKETS[-1]
        # multi-chip serving: when >1 device is visible, dispatches whose
        # node axis reaches shard_min_nodes (and divides the device
        # count) route through the ('nodes',)-mesh kernels — the
        # "pmap across the EvalBroker queue" north star, with the eval
        # axis kept chained for single-device-identical placements.
        # Sharding is the DEFAULT on multi-device meshes: the floor only
        # excludes toy worlds where per-wave collective latency exceeds
        # the scoring work (>=16 rows/shard on an 8-device mesh);
        # NOMAD_TPU_SHARD_MIN moves it.
        if shard_min_nodes is None:
            shard_min_nodes = knobs.get_int("NOMAD_TPU_SHARD_MIN")
        self.shard_min_nodes = shard_min_nodes
        # per-eval bulk heavy block is f32[4N]: cap the eval-axis chain
        # so one dispatch's stacked tensors stay under this byte budget
        # (100K-node worlds at the 512-eval bucket would be ~1 GB)
        self.bulk_bytes_budget = knobs.get_int("NOMAD_TPU_BULK_BYTES")
        # a bulk dispatch donates the usage-basis buffer to the kernel
        # and adopts its carry output as the new resident basis
        # (world.loan_basis/adopt_basis): no basis re-upload per wave.
        # ONE bulk dispatch is held in flight while the next part is
        # prepared and dispatched against the adopted carry, which is
        # what makes the in-flight placements visible to the chained
        # dispatch without a resolve barrier.  The carry is the only
        # place they live until the resolve (no ticket, not in the
        # world's host snapshot), so the chained part's update() ADDS
        # what commits and releases changed on the host to the carry's
        # rows and never sets a row (world.update force_scatter)
        self._pending: Optional[_PendingBulk] = None
        self._serving_mesh = None
        self._mesh_checked = False
        self._queue: List[_Request] = []
        self._cv = threading.Condition()
        self._stop = False
        self._overlay_lock = threading.Lock()
        # serializes bulk-path basis-read -> kernel -> register windows so
        # concurrent bulk evals cannot pile onto the same nodes
        self.bulk_gate = threading.RLock()
        self._overlays: Dict[int, np.ndarray] = {}   # id(cm) -> f32[N, R]
        # id(cm) -> {device gid -> i32[N] in-flight instance counts}
        self._dev_overlays: Dict[int, Dict[str, np.ndarray]] = {}
        self._tickets: Dict[int, Tuple[int, List[Tuple[int, np.ndarray]]]] = {}
        self._dev_tickets: Dict[int, Tuple[int, List[Tuple[str, int, int]]]] = {}
        self._next_ticket = 1
        # called (outside locks) whenever the in-flight overlay fully
        # drains: transient over-reservation may have failed placements
        # that would now succeed, so the server re-queues blocked evals
        self.on_drain = None
        self.stats = {"dispatches": 0, "batched_evals": 0, "single_evals": 0,
                      "max_batch_seen": 0, "tickets_open": 0,
                      "stack_s": 0.0, "put_s": 0.0, "device_s": 0.0,
                      "resolve_s": 0.0, "cache_hits": 0, "cache_misses": 0,
                      "bulk_evals": 0, "waves": 0, "max_waves_seen": 0,
                      # fused-path health: bulk_groups counts bulk wave
                      # groups, bulk_parts the device calls they took —
                      # fused steady state holds parts == groups, and
                      # bench --smoke gates on the ratio
                      "bulk_groups": 0, "bulk_parts": 0,
                      # how far the scan's bound engages: slot steps the
                      # scan kernel ran (each eval to its last active
                      # slot) over the E x S of its dispatches
                      "scan_steps_run": 0, "scan_steps_bucket": 0,
                      # donated-carry / 2-D-mesh health: donated_carries
                      # counts dispatches whose basis was donated (the
                      # steady state holds this == bulk_parts),
                      # wave_lanes the peak count
                      # of concurrently-scoring mesh lanes, lane_evals /
                      # lane_slots the laned occupancy (evals shipped vs
                      # W x E slots compiled), overlap_chained the bulk
                      # dispatches issued while the previous one was
                      # still in flight on device
                      "donated_carries": 0, "wave_lanes": 0,
                      "lane_evals": 0, "lane_slots": 0,
                      "overlap_chained": 0,
                      # device asks (scheduler/generic.py place_on, under
                      # bulk_gate): placements of groups with a device
                      # ask, and those whose kernel node had no grantable
                      # instance and went to a top-K alternative
                      "device_placements": 0, "device_fallbacks": 0,
                      # port asks (place_on): placements of groups that
                      # ask a port, and those the host could not assign
                      # ("ports exhausted") on the node the kernel chose
                      "port_placements": 0, "port_fallbacks": 0,
                      # distinct_hosts / distinct_property
                      # (_materialise_round): slots of groups under
                      # either that a kernel pass was given, and those
                      # it returned no row for
                      "distinct_slots": 0, "distinct_unplaced": 0}
        self._cache = _DeviceCache()
        # device-resident worlds: (id(cm), N, mesh identity) ->
        # DeviceWorld (epoch-uploaded capacity/basis, scatter deltas);
        # LRU over stale cm epochs
        from collections import OrderedDict
        self._worlds: "OrderedDict[tuple, DeviceWorld]" = OrderedDict()
        self._worlds_lock = threading.Lock()
        # serving readiness: compiled variants persist across processes
        # (utils.enable_compile_cache docstring) — must be set before the
        # first jit call of this process
        from nomad_tpu.utils import enable_compile_cache
        enable_compile_cache()
        # the resolve path scatters through the native library: build it
        # now, so a host that cannot raises at start-up with the
        # compiler's message instead of failing its first eval
        _native._load()
        self._thread = threading.Thread(
            target=self._run, name="placement-engine", daemon=True)
        self._thread.start()

    # ------------------------------------------------------------- public

    def place(self, cm, inputs: PlaceInputs,
              deltas: Optional[Sequence[Tuple[int, np.ndarray]]] = None,
              spread_algorithm: bool = False) -> Tuple[PlaceResult, int]:
        """Returns (result, ticket).  The caller MUST call
        `complete(ticket)` once the resulting plan has been submitted (or
        will never be), releasing its in-flight usage contribution."""
        req = _Request(cm=cm, inputs=inputs, deltas=list(deltas or ()),
                       spread_algorithm=spread_algorithm, future=Future())
        self._submit(req)
        with tracing.span("sched.wait_engine", wait=True):
            return req.future.result()

    def _submit(self, req) -> None:
        req.ctx = tracing.current()
        with self._cv:
            if self._stop:
                raise RuntimeError("placement engine stopped")
            req.enq = _time.perf_counter()
            self._queue.append(req)
            self._cv.notify()

    def place_bulk_begin(self, cm, *, feasible, affinity, has_affinity,
                         desired, penalty, coll0, demand, count,
                         deltas: Optional[Sequence[Tuple[int, np.ndarray]]]
                         = None,
                         spread_algorithm: bool = False,
                         wave_key: str = "") -> Future:
        """Enqueue a bulk wavefront placement and return its Future
        (result tuple = place_bulk's).  Lets a multi-group eval submit
        EVERY eligible group before waiting: the engine chains them (and
        other workers' evals) into one device dispatch instead of one
        blocking round trip per group — the C2M-1M path, where jobs are
        many small groups.  FIFO order + the engine thread's resolve-
        before-next-dispatch discipline preserve exact chained
        semantics.  `wave_key` (the eval's namespace) steers 2-D-mesh
        lane binning: requests sharing a key chain in one lane, distinct
        keys score concurrently across the mesh's wave columns."""
        req = _BulkRequest(
            cm=cm, feasible=np.asarray(feasible, bool),
            affinity=np.asarray(affinity, np.float32),
            has_affinity=bool(has_affinity), desired=int(desired),
            penalty=np.asarray(penalty, bool),
            coll0=np.asarray(coll0, np.int32),
            demand=np.asarray(demand, np.float32), count=int(count),
            deltas=list(deltas or ()), spread_algorithm=spread_algorithm,
            future=Future(), wave_key=str(wave_key))
        self._submit(req)
        return req.future

    def place_bulk(self, cm, *, feasible, affinity, has_affinity, desired,
                   penalty, coll0, demand, count,
                   deltas: Optional[Sequence[Tuple[int, np.ndarray]]] = None,
                   spread_algorithm: bool = False, wave_key: str = ""):
        """Wavefront bulk placement of `count` identical slots, batched
        with concurrent bulk evals into one chained device dispatch
        (ops.place.place_bulk_batch_donate_jit).  Blocks; returns
        (assign i32[N], placed, nodes_evaluated, nodes_exhausted,
        scores f32[N], ticket).
        Callers derive usage from `assign` (sparse) — the engine returns
        no usage matrix.  The caller MUST `complete(ticket)` once the
        plan is submitted (ticket may be None if nothing placed)."""
        fut = self.place_bulk_begin(
            cm, feasible=feasible, affinity=affinity,
            has_affinity=has_affinity, desired=desired, penalty=penalty,
            coll0=coll0, demand=demand, count=count, deltas=deltas,
            spread_algorithm=spread_algorithm, wave_key=wave_key)
        with tracing.span("sched.wait_engine", wait=True):
            return fut.result()

    def warmup(self, cm, inputs: Optional[PlaceInputs] = None,
               bulk: Optional[dict] = None) -> None:
        """Compile every E-bucket variant of the dispatch kernels for the
        given input shapes, so a serving or measurement window never pays
        a mid-run XLA compile (queue timing makes organically warmed
        bucket coverage nondeterministic).  `inputs`: a representative
        scan-path PlaceInputs; `bulk`: place_bulk-style field dict
        (feasible/affinity/has_affinity/desired/penalty/coll0/demand/
        count).  Results are discarded; nothing registers in the
        in-flight overlay.  Timing/cache stats are restored afterwards so
        one-time compile cost never skews serving diagnostics."""
        import jax

        import dataclasses

        stats_before = dict(self.stats)
        cache_before = (self._cache.hits, self._cache.misses)
        mesh = self._mesh_for(cm.n_rows)
        # every S bucket up to the sample's own (retry evals place the
        # remainder with fewer slots, hitting the smaller buckets)
        input_variants = []
        if inputs is not None:
            S_in = inputs.demand.shape[0]
            # every bucket below the sample's slot count, then the sample
            # itself (covering its own bucket even beyond _S_BUCKETS[-1])
            for cut in [b for b in _S_BUCKETS if b < S_in] + [S_in]:
                input_variants.append(dataclasses.replace(
                    inputs, demand=inputs.demand[:cut],
                    slot_tg=inputs.slot_tg[:cut],
                    slot_active=inputs.slot_active[:cut]))
        def scan_variant(E, inp_v):
            reqs = [_Request(cm=cm, inputs=inp_v, deltas=[],
                             spread_algorithm=False, future=Future())
                    for _ in range(E)]
            if mesh is not None:
                jax.block_until_ready(
                    self._dispatch_group_sharded(reqs, mesh))
            else:
                packed = self._dispatch_packed(
                    reqs, E=E,
                    basis=np.asarray(inp_v.used, np.float32),
                    deltas_per_req=[[] for _ in reqs],
                    capacity=np.asarray(inp_v.capacity))
                jax.block_until_ready(packed)

        def bulk_variant(E):
            # separate compiles serving mixes: sparse vs dense output
            # (count <=/> SPARSE_CAP) x delta-free (D=0) vs delta-
            # carrying (D=_DELTA_BUCKET) light blocks x the fill-grid
            # buckets (the dispatch derives fill_grid from the part's
            # max count, so the three warm counts induce the reachable
            # static combos: sparse x {16, 64} and dense x {64} —
            # retry evals place shrinking remainders, so a small-grid
            # sparse variant is reachable whatever the job's count)
            from nomad_tpu.ops.place import FILL_GRID_BUCKETS
            dummy_delta = [(0, np.zeros(NUM_RESOURCE_DIMS, np.float32))]
            for count in {min(bulk["count"], FILL_GRID_BUCKETS[0]),
                          SPARSE_CAP,
                          max(bulk["count"], SPARSE_CAP + 1)}:
                for deltas in ([], dummy_delta):
                    spec = dict(bulk, count=count)
                    breqs = [_BulkRequest(cm=cm, deltas=list(deltas),
                                          spread_algorithm=False,
                                          future=Future(), **spec)
                             for _ in range(E)]
                    # THROWAWAY world per thunk: the warmed variants
                    # include the donated-carry kernels, and donating /
                    # adopting against the real resident world would
                    # install a basis holding warmup placements the
                    # host snapshot never saw
                    if mesh is not None:
                        out = self._dispatch_bulk_group_sharded(
                            breqs, mesh, world=DeviceWorld(mesh))[0]
                        jax.block_until_ready(out)
                    else:
                        packed = self._dispatch_bulk_group(
                            breqs, world=DeviceWorld())[0]
                        jax.block_until_ready(packed)

        # XLA compiles release the GIL and run concurrently per variant,
        # cutting the grid from the sum of compile times toward the max.
        # Each thunk also EXECUTES its variant (block_until_ready), so
        # worker count bounds peak device memory: NOMAD_TPU_WARM_THREADS
        # tunes it down to 1 (sequential) for memory-tight configs.
        # (jit dispatch and the device cache are safe here: warmup thunks
        # never write overlays, and stats are restored below.)
        thunks = [(scan_variant, (E, v))
                  for E in self.E_BUCKETS for v in input_variants]
        if bulk is not None:
            # buckets above the byte-budget chunk can never be dispatched
            # for this world size — warming them would only stage the
            # oversized stacks the budget exists to avoid
            chunk = self._bulk_chunk(cm.n_rows)
            thunks += [(bulk_variant, (E,))
                       for E in self.BULK_E_BUCKETS if E <= chunk]
        def warm(fn, a):
            # one span per compiled variant: compile + first execution
            kind = f"{fn.__name__}:E={a[0]}" + (
                f":S={a[1].demand.shape[0]}" if len(a) > 1 else "")
            with tracing.span("engine.warmup", kind=kind) as sp:
                fn(*a)
            log.info("engine.warmup %s: %.2fs", kind, sp.seconds)

        workers = knobs.get_int("NOMAD_TPU_WARM_THREADS")
        from concurrent.futures import ThreadPoolExecutor
        with ThreadPoolExecutor(
                max_workers=max(1, min(workers, len(thunks)))) as ex:
            futs = [ex.submit(warm, fn, a) for fn, a in thunks]
            for f in futs:
                f.result()
        # world scatter pair: the measured window's first dirty-row
        # update must not pay its bucket's compile (shape-keyed on the
        # world size; the bulk path runs an unsharded world even when a
        # mesh exists)
        from nomad_tpu.parallel.world import warm_scatter
        cap = np.asarray(cm.capacity)
        warm_scatter(cap.shape, mesh)
        if mesh is not None:
            warm_scatter(cap.shape)
        if bulk is not None:
            # bulk warmup ran against throwaway worlds: pre-upload the
            # REAL world's epoch so the measured window's first dispatch
            # pays a dirty-row diff, not the epoch's full upload
            N = cm.n_rows
            self._world(cm, N, mesh).update(
                np.asarray(cm.capacity)[:N], self._basis_for(cm)[:N])
        self.stats.update(stats_before)
        self._cache.hits, self._cache.misses = cache_before

    def register_external(self, cm, contributions) -> int:
        """Record usage scheduled OUTSIDE the engine (the bulk wavefront
        path) in the in-flight overlay so engine dispatches see it before
        the plan commits.  `contributions`: [(row, f32[R])].  Returns a
        ticket for complete()."""
        with self._overlay_lock:
            race.write("PlacementEngine._overlays", self)
            key = id(cm)
            overlay = self._overlays.get(key)
            n = cm.used.shape[0]
            if overlay is None or overlay.shape[0] < n:
                grown = np.zeros((n, NUM_RESOURCE_DIMS), np.float32)
                if overlay is not None:
                    grown[:overlay.shape[0]] = overlay
                overlay = self._overlays[key] = grown
            contribs = []
            for row, vec in contributions:
                if row < overlay.shape[0]:
                    vec = np.asarray(vec, np.float32)
                    overlay[row] += vec
                    contribs.append((row, vec))
            ticket = self._next_ticket
            self._next_ticket += 1
            self._tickets[ticket] = (key, contribs)
            self.stats["tickets_open"] = len(self._tickets)
        return ticket

    def register_external_sparse(self, cm, rows: np.ndarray,
                                 counts: np.ndarray,
                                 demand: np.ndarray) -> int:
        """register_external for a resolved bulk eval without the
        per-row Python loop: overlay[rows[k]] += counts[k] * demand in
        one native scatter.  Ticket contribs stay in sparse form so
        complete() reverses them with the same rank-1 scatter."""
        rows = np.ascontiguousarray(rows, np.int32)
        counts = np.ascontiguousarray(counts, np.int32)
        with self._overlay_lock:
            race.write("PlacementEngine._overlays", self)
            key = id(cm)
            overlay = self._overlays.get(key)
            n = cm.used.shape[0]
            if overlay is None or overlay.shape[0] < n:
                grown = np.zeros((n, NUM_RESOURCE_DIMS), np.float32)
                if overlay is not None:
                    grown[:overlay.shape[0]] = overlay
                overlay = self._overlays[key] = grown
            keep = rows < overlay.shape[0]
            if not keep.all():
                rows, counts = rows[keep], counts[keep]
            d = np.zeros(overlay.shape[1], np.float32)
            d[:min(len(demand), len(d))] = \
                np.asarray(demand, np.float32)[:len(d)]
            _native.scatter_add_rank1(overlay, rows, counts, d)
            ticket = self._next_ticket
            self._next_ticket += 1
            self._tickets[ticket] = (key, ("rank1", rows, counts, d))
            self.stats["tickets_open"] = len(self._tickets)
        return ticket

    def basis_for(self, cm) -> np.ndarray:
        """Public view of committed usage + in-flight overlay."""
        return self._basis_for(cm)

    def register_devices(self, cm, contributions) -> int:
        """In-flight device instance counts: [(gid, row, count)].
        Steers concurrent evals away from nodes whose instances are
        claimed by not-yet-committed plans."""
        with self._overlay_lock:
            key = id(cm)
            per = self._dev_overlays.setdefault(key, {})
            n = cm.n_rows
            kept = []
            for gid, row, count in contributions:
                col = per.get(gid)
                if col is None or col.shape[0] < n:
                    grown = np.zeros(n, np.int32)
                    if col is not None:
                        grown[:col.shape[0]] = col
                    col = per[gid] = grown
                if row < col.shape[0]:
                    col[row] += count
                    kept.append((gid, row, count))
            ticket = self._next_ticket
            self._next_ticket += 1
            self._dev_tickets[ticket] = (key, kept)
        return ticket

    def device_overlay(self, cm, gid: str):
        """i32[N] in-flight instance counts for a device group, or None."""
        with self._overlay_lock:
            per = self._dev_overlays.get(id(cm))
            if not per:
                return None
            col = per.get(gid)
            return None if col is None else col.copy()

    def complete(self, ticket) -> None:
        """Release a placement's in-flight usage (its plan is now either
        committed into cm.used or abandoned)."""
        if ticket is not None:
            self.complete_many((ticket,))

    def complete_many(self, tickets) -> None:
        """complete() for a whole batch of tickets under ONE overlay-lock
        acquisition — the plan applier's commit->overlay hand-off
        releases every ticket of a coalesced plan batch at once, instead
        of bouncing the lock against concurrent dispatches per ticket."""
        chaos.maybe_delay("engine.complete_delay")
        drained = False
        with self._overlay_lock:
            race.write("PlacementEngine._overlays", self)
            for ticket in tickets:
                if ticket is None:
                    continue
                dev_entry = self._dev_tickets.pop(ticket, None)
                if dev_entry is not None:
                    key, contribs = dev_entry
                    per = self._dev_overlays.get(key, {})
                    for gid, row, count in contribs:
                        col = per.get(gid)
                        if col is not None and row < col.shape[0]:
                            col[row] -= count
                    if not self._dev_tickets:
                        self._dev_overlays.clear()
                        drained = drained or not self._tickets
                else:
                    entry = self._tickets.pop(ticket, None)
                    if entry is not None:
                        cm_key, contrib = entry
                        overlay = self._overlays.get(cm_key)
                        if overlay is not None:
                            if isinstance(contrib, tuple) \
                                    and contrib[0] == "rank1":
                                _, rows, counts, d = contrib
                                keep = rows < overlay.shape[0]
                                _native.scatter_add_rank1(
                                    overlay, rows[keep], -counts[keep],
                                    d[:overlay.shape[1]])
                            else:
                                for row, vec in contrib:
                                    if row < overlay.shape[0]:
                                        overlay[row] -= vec
                        self.stats["tickets_open"] = len(self._tickets)
                        if not self._tickets:
                            # nothing in flight: drop overlays entirely
                            # so numerical residue never accumulates
                            self._overlays.clear()
                            drained = drained or not self._dev_tickets
        if drained and self.on_drain is not None:
            try:
                self.on_drain()
            except Exception:                   # noqa: BLE001
                pass

    def stop(self) -> None:
        with self._cv:
            self._stop = True
            self._cv.notify()
        self._thread.join(timeout=5.0)

    # ------------------------------------------------------------- overlay

    def _world(self, cm, N: int, mesh=None) -> DeviceWorld:
        """The device-resident world for (matrix, padded node axis, mesh).

        The world's capacity/basis pair is uploaded ONCE per cluster
        epoch (the key changes when the matrix re-buckets its node axis)
        and lives on device — sharded over the ('nodes',) serving mesh
        when one is active — with subsequent dispatches scatter-applying
        row deltas (world.update / world.apply_rank1) instead of
        re-shipping the [N, R] matrices."""
        key = (id(cm), N, mesh_key(mesh))
        with self._worlds_lock:
            w = self._worlds.get(key)
            if w is None:
                w = self._worlds[key] = DeviceWorld(mesh)
            self._worlds.move_to_end(key)
            while len(self._worlds) > 4:         # stale cm epochs (LRU)
                self._worlds.popitem(last=False)
            return w

    def world_stats(self) -> Dict[str, int]:
        """Aggregate DeviceWorld.stats over every resident world.  The
        bench steady-state gate reads full_uploads / steady_reuploads
        here: after warmup a healthy run scatters rows and never
        re-ships a full matrix."""
        agg: Dict[str, int] = {}
        with self._worlds_lock:
            worlds = list(self._worlds.values())
        for w in worlds:
            with w.lock:
                for k, v in w.stats.items():
                    agg[k] = agg.get(k, 0) + int(v)
        return agg

    def _basis_for(self, cm) -> np.ndarray:
        """cm.used + in-flight overlay (copy).  The committed matrix is
        copied under ITS owner's lock: a copy taken mid-commit would see
        a plan half in the matrix while the overlay still counts it fully
        — phantom usage that silently shrinks placements."""
        import contextlib
        cm_lock = getattr(cm, "lock", None) or contextlib.nullcontext()
        with self._overlay_lock:
            race.read("PlacementEngine._overlays", self)
            with cm_lock:
                used = np.array(cm.used, dtype=np.float32)
            overlay = self._overlays.get(id(cm))
            if overlay is not None:
                n = min(overlay.shape[0], used.shape[0])
                used[:n] += overlay[:n]
            return used

    def _register(self, req: _Request, result: PlaceResult) -> int:
        """Record an eval's in-flight usage contribution; returns ticket."""
        contrib: List[Tuple[int, np.ndarray]] = []
        S = req.inputs.demand.shape[0]
        for si in range(S):
            row = int(result.node[si])
            if row >= 0:
                contrib.append((row, req.inputs.demand[si]))
        for row, vec in req.deltas:
            if vec.max(initial=0.0) > 0.0 and (vec >= 0.0).all():
                contrib.append((row, vec))    # sticky pre-placement adds
        if not contrib:
            # nothing placed: no overlay entry, no ticket — otherwise a
            # permanently-unplaceable eval would drain the overlay on
            # every retry and busy-loop the blocked-eval wakeups
            return None
        with self._overlay_lock:
            race.write("PlacementEngine._overlays", self)
            key = id(req.cm)
            overlay = self._overlays.get(key)
            n = req.cm.used.shape[0]
            if overlay is None or overlay.shape[0] < n:
                grown = np.zeros((n, NUM_RESOURCE_DIMS), np.float32)
                if overlay is not None:
                    grown[:overlay.shape[0]] = overlay
                overlay = self._overlays[key] = grown
            for row, vec in contrib:
                if row < overlay.shape[0]:
                    overlay[row] += vec
            ticket = self._next_ticket
            self._next_ticket += 1
            self._tickets[ticket] = (key, contrib)
            self.stats["tickets_open"] = len(self._tickets)
        return ticket

    # ------------------------------------------------------------- loop

    def _run(self) -> None:
        while True:
            with self._cv:
                if not self._queue and not self._stop \
                        and self._pending is None:
                    # nothing to dispatch and nothing in flight: this
                    # thread's idleness IS the device's
                    with tracing.span("engine.idle", wait=True):
                        while not self._queue and not self._stop:
                            self._cv.wait()
                if self._stop and not self._queue:
                    break
                batch, self._queue = (self._queue[:self.max_batch],
                                      self._queue[self.max_batch:])
            now = _time.perf_counter()
            for r in batch:
                tracing.record("engine.queue_wait", r.enq, now, wait=True,
                               ctx=r.ctx)
            if not batch:
                # idle with a bulk dispatch in flight: nothing arrived
                # to chain behind it, so fetch + resolve it now
                self._drain_pending()
                continue
            try:
                self._dispatch(batch)
            except Exception as e:              # noqa: BLE001
                self._drain_pending()
                for r in batch:
                    if not r.future.done():
                        r.future.set_exception(e)
        # stop: settle any in-flight dispatch so its futures resolve
        self._drain_pending()

    # ------------------------------------------------------------- dispatch

    def _dispatch(self, batch: List[_Request]) -> None:
        groups: Dict[tuple, List] = {}
        for r in batch:
            groups.setdefault(r.shape_key(), []).append(r)
        self.stats["dispatches"] += 1
        self.stats["max_batch_seen"] = max(self.stats["max_batch_seen"],
                                           len(batch))

        # groups resolve SEQUENTIALLY: each group's results register in
        # the in-flight overlay before the next group's basis is read, so
        # two groups in one cycle (a service scan group + a batch bulk
        # group on the same matrix is the C2M steady state) never score
        # against a basis blind to each other's placements — that
        # blindness showed up as plan-applier conflicts and eval retries.
        # Cost: one D2H round trip per group instead of one per cycle.
        for reqs in groups.values():
            try:
                self._dispatch_one_group(reqs)
            except Exception as e:              # noqa: BLE001
                for r in reqs:
                    if not r.future.done():
                        r.future.set_exception(e)

    def _dispatch_one_group(self, reqs: List) -> None:
        if isinstance(reqs[0], _BulkRequest):
            cm = reqs[0].cm
            N = reqs[0].feasible.shape[0]
            mesh = self._mesh_for(N)
            world = self._world(cm, N, mesh)
            lanes = mesh.shape.get("wave", 1) if mesh is not None else 1
            expected_shape = ((N, cm.capacity.shape[1]),
                              (N, cm.used.shape[1]))
            parts = 0
            for part in self._split_bulk(reqs, lanes=lanes):
                parts += 1
                # upload/compute overlap: the previous bulk dispatch may
                # still be computing.  Chaining behind it is sound ONLY
                # when this part scores against the same world via the
                # adopted donated carry, which holds the in-flight
                # placements and is the one place that does: update()
                # keeps them by ADDING each dirty row's host change to
                # the carry (force_scatter).  Setting a row, or a full
                # upload from the host snapshot, would put the host's
                # value where they are — a new epoch does the one and
                # chaos injection may force the other, so both bail to
                # a drain-first barrier.
                chained = (chaos.active is None
                           and self._pending is not None
                           and self._pending.world is world
                           and world.shape == expected_shape)
                if self._pending is not None and not chained:
                    self._drain_pending()
                if mesh is not None:
                    out, _w, dper, mapping = \
                        self._dispatch_bulk_group_sharded(
                            part, mesh, world=world,
                            force_scatter=chained)
                else:
                    out, _w, dper = self._dispatch_bulk_group(
                        part, world=world, force_scatter=chained)
                    mapping = None
                if chained:
                    self.stats["overlap_chained"] += 1
                prev, self._pending = self._pending, _PendingBulk(
                    reqs=part, out=out, world=world, deltas_per=dper,
                    mapping=mapping)
                if prev is not None:
                    self._drain_record(prev)
            self.stats["bulk_groups"] += 1
            self.stats["bulk_parts"] += parts
            self.stats["bulk_evals"] += len(reqs)
            return

        # scan-path groups resolve against the overlay basis: an
        # in-flight bulk dispatch's placements are not registered yet,
        # so a pending dispatch must land before this group's basis read
        self._drain_pending()
        rebucketed = (reqs[0].cm.capacity.shape[0]
                      != reqs[0].inputs.capacity.shape[0])
        mesh = None if rebucketed else \
            self._mesh_for(reqs[0].inputs.capacity.shape[0])
        # evals whose delta list exceeds the fixed slot bucket run alone
        # with the deltas folded into a private basis (no new compile
        # variant); on a mesh they stay SHARDED (an E=1 sharded dispatch
        # is a warmed bucket) rather than regressing to one device
        overflow = [r for r in reqs if len(r.deltas) > _DELTA_BUCKET]
        if overflow:
            reqs = [r for r in reqs if len(r.deltas) <= _DELTA_BUCKET]
            for r in overflow:
                if mesh is not None:
                    packed = self._dispatch_group_sharded(
                        [r], mesh, fold_deltas=True)
                    self._fetch_resolve_scan([r], packed)
                else:
                    self._run_single(r)
            self.stats["single_evals"] += len(overflow)
            if not reqs:
                return
        if mesh is None and (len(reqs) == 1 or rebucketed):
            # single path also when the matrix has grown (re-bucketed)
            # since these inputs were built: the dispatch-time basis no
            # longer matches the padded node axis
            for r in reqs:
                self._run_single(r)
            self.stats["single_evals"] += len(reqs)
            return
        # scan chains cap at their own bucket (queue slices can exceed it
        # now that bulk chains run longer); chunks chain through the
        # overlay between dispatches
        for i in range(0, len(reqs), self.scan_max_batch):
            chunk = reqs[i:i + self.scan_max_batch]
            if mesh is not None:
                packed = self._dispatch_group_sharded(chunk, mesh)
            else:
                packed = self._dispatch_group(chunk)
            self.stats["batched_evals"] += len(chunk)
            self._fetch_resolve_scan(chunk, packed)

    def _drain_pending(self) -> None:
        """Fetch + resolve the in-flight bulk dispatch, if any.  Called
        wherever the overlap pipeline must barrier: before any dispatch
        that cannot chain (different world, scan path, chaos active),
        when the queue idles with work in flight, and at stop."""
        p, self._pending = self._pending, None
        if p is not None:
            self._drain_record(p)

    def _drain_record(self, p: _PendingBulk) -> None:
        import jax

        ctx = self._ctx_of(p.reqs)
        try:
            # a host wait: the device works, this thread blocks
            with tracing.span("engine.device_get", wait=True,
                              ctx=ctx) as got:
                fetched = jax.device_get(p.out)
        except Exception as e:                  # noqa: BLE001
            # the adopted carry is suspect (failed dispatch): the
            # next update() re-uploads from the host snapshot
            p.world.invalidate_basis()
            for r in p.reqs:
                if not r.future.done():
                    r.future.set_exception(e)
            return
        self.stats["device_s"] += got.seconds
        try:
            with tracing.span("engine.resolve", ctx=ctx, cpu=True) as sp:
                self._resolve_bulk(p.reqs, fetched, p.world, p.deltas_per,
                                   mapping=p.mapping)
        except Exception as e:                  # noqa: BLE001
            for r in p.reqs:
                if not r.future.done():
                    r.future.set_exception(e)
            return
        self.stats["resolve_s"] += sp.seconds
        self._record_dispatch(p.reqs, got.seconds, "bulk")
        if len(p.reqs) > 1:
            self.stats["batched_evals"] += len(p.reqs)
        else:
            self.stats["single_evals"] += 1

    def _fetch_resolve_scan(self, reqs: List[_Request], packed) -> None:
        import jax

        ctx = self._ctx_of(reqs)
        with tracing.span("engine.device_get", wait=True, ctx=ctx) as got:
            fetched = jax.device_get(packed)
        self.stats["device_s"] += got.seconds
        with tracing.span("engine.resolve", ctx=ctx, cpu=True) as sp:
            node, score, fit_s, n_eval, n_exh, top_n, top_s = \
                unpack_outputs(np.asarray(fetched))
            for i, r in enumerate(reqs):
                res = PlaceResult(
                    node=node[i], score=score[i], fit_score=fit_s[i],
                    nodes_evaluated=n_eval[i], nodes_exhausted=n_exh[i],
                    top_nodes=top_n[i], top_scores=top_s[i], used=None)
                ticket = self._register(r, res)
                r.future.set_result((res, ticket))
        self.stats["resolve_s"] += sp.seconds
        self._record_dispatch(reqs, got.seconds, "scan")

    @staticmethod
    def _ctx_of(reqs: List) -> Optional[dict]:
        """The first sampled request's trace context: a group rides one
        dispatch, so the engine thread's spans for it join that trace."""
        if tracing.active is not None:
            for r in reqs:
                if r.ctx is not None:
                    return r.ctx
        return None

    @staticmethod
    def _record_dispatch(reqs: List, dev_s: float, kind: str) -> None:
        """Per request, submit -> resolved, with the shared device_get
        wait as an attribute (the whole group rides one chained device
        dispatch)."""
        now = _time.perf_counter()
        for r in reqs:
            if r.enq:
                tracing.record("engine.dispatch", r.enq, now, ctx=r.ctx,
                               kind=kind, batch=len(reqs),
                               device_get_s=round(dev_s, 6))

    # ------------------------------------------------------- sharded path

    def _mesh_for(self, N: int):
        """The ('node_shard','wave') serving mesh when sharding applies
        to this node axis, else None."""
        if not self._mesh_checked:
            import jax

            from nomad_tpu.parallel.sharded import make_serving_mesh
            if len(jax.devices()) > 1:
                self._serving_mesh = make_serving_mesh()
            self._mesh_checked = True
        mesh = self._serving_mesh
        if mesh is None or N < self.shard_min_nodes:
            return None
        # the node axis splits over 'node_shard' only (wave columns hold
        # replicas); shards need >= 2 local rows (the wave's top-2
        # reduction)
        n_shard = mesh.shape.get("node_shard", mesh.devices.size)
        if N % n_shard != 0 or N < 2 * n_shard:
            return None
        return mesh

    # per-eval PlaceInputs fields shipped to the sharded scan kernel
    _SHARD_FIELDS = (
        "feasible", "affinity", "has_affinity", "desired_count",
        "penalty", "tg_count", "spread_vidx", "spread_desired",
        "spread_targeted", "spread_wfrac", "spread_counts",
        "spread_active", "place_cap", "dev_score", "has_dev",
        "hosts_taken", "hosts_of", "prop_vidx", "prop_counts", "prop_limit",
        "prop_of", "demand", "slot_tg", "slot_active")

    def _stack_deltas(self, deltas_per_req, E: int, N: int):
        R = NUM_RESOURCE_DIMS
        D = _DELTA_BUCKET
        drows = np.full((E, D), N, np.int32)
        dvals = np.zeros((E, D, R), np.float32)
        for i, ds in enumerate(deltas_per_req):
            for d, (row, vec) in enumerate(ds[:D]):
                drows[i, d] = row
                dvals[i, d] = vec
        return drows, dvals

    def _dispatch_group_sharded(self, reqs: List[_Request], mesh,
                                fold_deltas: bool = False):
        """Scan-path dispatch over the node-sharded serving mesh.  Pads
        the eval axis to a compile bucket with inert evals (slot_active
        all False).  `fold_deltas` (overflow singletons only) folds the
        request's oversized delta list into the shipped basis copy."""
        from nomad_tpu.parallel.sharded import place_batch_sharded

        cm = reqs[0].cm
        N = reqs[0].inputs.capacity.shape[0]
        E = next(b for b in self.E_BUCKETS if b >= len(reqs))
        S = _s_bucket(reqs[0].inputs.demand.shape[0])
        ctx = self._ctx_of(reqs)
        with tracing.span("engine.stack", ctx=ctx, cpu=True) as sp:
            fields = {}
            for f in self._SHARD_FIELDS:
                arrs = [np.asarray(getattr(r.inputs, f)) for r in reqs]
                if f in ("demand", "slot_tg", "slot_active"):
                    # slot axis padded to the canonical bucket (pads inactive)
                    arrs = [np.concatenate(
                        [a, np.zeros((S - a.shape[0],) + a.shape[1:],
                                     a.dtype)]) if a.shape[0] < S else a
                            for a in arrs]
                if E > len(reqs):
                    pad = (np.zeros_like(arrs[0])
                           if f == "slot_active" else arrs[0])
                    arrs += [pad] * (E - len(reqs))
                fields[f] = np.stack(arrs)
            basis = self._basis_for(cm)
            deltas_per = [r.deltas for r in reqs]
            if fold_deltas:
                assert len(reqs) == 1
                deltas_per = [_fold_overflow(basis, reqs[0].deltas)]
            drows, dvals = self._stack_deltas(
                deltas_per + [[]] * (E - len(reqs)), E, N)
        self.stats["stack_s"] += sp.seconds
        with tracing.span("engine.put", ctx=ctx, cpu=True) as sp:
            # content-addressed sharded placement: identical job-state
            # batches (the common case) ship zero bytes; basis/deltas always
            # ship (they change every dispatch and are small)
            from jax.sharding import NamedSharding
            from nomad_tpu.parallel.sharded import _field_specs_batched
            fshard = {k: NamedSharding(mesh, s)
                      for k, s in _field_specs_batched().items()}
            fields_dev = self._cache.sharded("scan", mesh, fields, fshard)
            # device-resident world: capacity/basis live sharded across the
            # mesh; update() ships only the rows that changed since the last
            # dispatch (the overlay contributions of the previous cycle)
            cap_dev, basis_dev = self._world(cm, N, mesh).update(
                cm.capacity, basis)
            packed, _used = place_batch_sharded(
                mesh, cap_dev, basis_dev, fields_dev,
                drows, dvals, spread_algorithm=reqs[0].spread_algorithm)
        self.stats["put_s"] += sp.seconds
        # the node-sharded scan runs every slot of its bucket
        self.stats["scan_steps_run"] += E * S
        self.stats["scan_steps_bucket"] += E * S
        self.stats["sharded_evals"] = (
            self.stats.get("sharded_evals", 0) + len(reqs))
        return packed

    @staticmethod
    def _lane_bins(reqs: List[_BulkRequest], W: int):
        """Deterministic wave-lane binning: distinct wave_keys (eval
        namespaces) spread round-robin over the mesh's W wave columns in
        sorted-key order; requests sharing a key stay in ONE lane so
        their chained semantics are untouched.  Returns (bins — per-lane
        request lists, ALWAYS W of them so the stacks match the mesh's
        wave extent — and mapping[i] = (lane, slot) per input order).
        A single distinct key (or W == 1) degenerates to one active lane
        (padded lanes carry count=0 evals that exit immediately) —
        placement-identical to the pre-laned dispatch."""
        keys = sorted({r.wave_key for r in reqs})
        if W <= 1 or len(keys) <= 1:
            bins = [list(reqs)] + [[] for _ in range(max(0, W - 1))]
            return bins, [(0, i) for i in range(len(reqs))]
        lane_of = {k: i % W for i, k in enumerate(keys)}
        bins: List[List[_BulkRequest]] = [[] for _ in range(W)]
        mapping = []
        for r in reqs:
            lane = lane_of[r.wave_key]
            mapping.append((lane, len(bins[lane])))
            bins[lane].append(r)
        return bins, mapping

    def _dispatch_bulk_group_sharded(self, reqs: List[_BulkRequest],
                                     mesh, world=None,
                                     force_scatter: bool = False):
        from nomad_tpu.parallel.sharded import (
            NODE_AXIS_NAME,
            WAVE_AXIS_NAME,
            place_bulk_batch_sharded,
        )

        cm = reqs[0].cm
        N = reqs[0].feasible.shape[0]
        W = mesh.shape.get(WAVE_AXIS_NAME, 1)
        capacity = cm.capacity[:N]
        basis = self._basis_for(cm)[:N]
        deltas_per = [r.deltas for r in reqs]
        if len(reqs) == 1 and len(reqs[0].deltas) > _DELTA_BUCKET:
            deltas_per = [_fold_overflow(basis, reqs[0].deltas)]
            reqs = list(reqs)
            bins = [[reqs[0]]] + [[] for _ in range(max(0, W - 1))]
            mapping = [(0, 0)]
            deltas_bins = [deltas_per] + [[] for _ in range(max(0, W - 1))]
        else:
            bins, mapping = self._lane_bins(reqs, W)
            dp = {id(r): d for r, d in zip(reqs, deltas_per)}
            deltas_bins = [[dp[id(r)] for r in b] for b in bins]
        # lane eval extent: one compile bucket covering the fullest lane
        fullest = max(len(b) for b in bins)
        E = next(b for b in self.BULK_E_BUCKETS if b >= fullest)
        self.stats["wave_lanes"] = max(
            self.stats["wave_lanes"], sum(1 for b in bins if b))
        self.stats["lane_evals"] += len(reqs)
        self.stats["lane_slots"] += W * E

        ctx = self._ctx_of(reqs)
        with tracing.span("engine.stack", ctx=ctx, cpu=True) as sp:
            # content key from per-request digests (packbits + zero-marker
            # fast paths) — cheaper than hashing the stacked [W, E, N]
            # tensors, and a hit skips even BUILDING the host stacks.  The
            # per-lane tuples make the key sensitive to lane layout.
            r00 = reqs[0]
            digs = tuple(tuple(
                bulk_heavy_digest(r.feasible, r.affinity, r.penalty, r.coll0)
                for r in b) for b in bins)
            meta = tuple(tuple(
                (np.asarray(r.demand, np.float32).tobytes(),
                 bool(r.has_affinity), int(r.desired)) for r in b)
                for b in bins)

            def build_stacks():
                def lane_stack(get, dt, pad_val=None):
                    rows = []
                    for b in bins:
                        fill = b[0] if b else r00
                        lane = [np.asarray(get(r), dt) for r in b]
                        pad_a = np.asarray(get(fill), dt) \
                            if pad_val is None else pad_val
                        lane += [pad_a] * (E - len(b))
                        rows.append(np.stack(lane) if lane[0].ndim
                                    else np.array(lane, dt))
                    return np.stack(rows)
                feas = lane_stack(lambda r: r.feasible, bool)
                aff = lane_stack(lambda r: r.affinity, np.float32)
                pen = lane_stack(lambda r: r.penalty, bool)
                coll = lane_stack(lambda r: r.coll0, np.int32)
                dem = lane_stack(lambda r: r.demand, np.float32)
                hasa = np.stack([np.array(
                    [r.has_affinity for r in b] + [False] * (E - len(b)),
                    bool) for b in bins])
                des = np.stack([np.array(
                    [r.desired for r in b] + [1] * (E - len(b)), np.int32)
                    for b in bins])
                return feas, aff, pen, coll, dem, hasa, des

            # padded evals have count=0: the wavefront exits immediately
            cnt = np.stack([np.array(
                [r.count for r in b] + [0] * (E - len(b)), np.int32)
                for b in bins])
            lane_drows, lane_dvals = [], []
            for db in deltas_bins:
                dr, dv = self._stack_deltas(
                    list(db) + [[]] * (E - len(db)), E, N)
                lane_drows.append(dr)
                lane_dvals.append(dv)
            drows = np.stack(lane_drows)
            dvals = np.stack(lane_dvals)
        self.stats["stack_s"] += sp.seconds
        with tracing.span("engine.put", ctx=ctx, cpu=True) as sp:
            from jax.sharding import NamedSharding
            from jax.sharding import PartitionSpec as _P
            lane3 = NamedSharding(
                mesh, _P(WAVE_AXIS_NAME, None, NODE_AXIS_NAME))
            lane2 = NamedSharding(mesh, _P(WAVE_AXIS_NAME, None))
            lane2r = NamedSharding(mesh, _P(WAVE_AXIS_NAME, None, None))
            feas, aff, pen, coll, dem, hasa, des = self._cache.sharded(
                "bulk", mesh, build_stacks,
                (lane3, lane3, lane3, lane3, lane2r, lane2, lane2),
                key=("bulkstack", N, W, E, digs, meta))
            # device-resident world: one full upload per cluster epoch, then
            # dirty-row scatters; steady state ships zero basis bytes because
            # the adopted carry holds the placements on device and
            # _resolve_bulk applied them to the host snapshot
            # (apply_rank1_host)
            world = world if world is not None else self._world(cm, N, mesh)
            cap_dev, _ = world.update(capacity, basis,
                                      force_scatter=force_scatter)
            basis_dev = world.loan_basis()
            from nomad_tpu.ops.place import fill_grid_for
            out = place_bulk_batch_sharded(
                mesh, cap_dev, basis_dev,
                feas, aff, hasa, des, pen, coll, dem, cnt,
                drows, dvals, spread_algorithm=reqs[0].spread_algorithm,
                fill_grid=fill_grid_for(max(r.count for r in reqs)))
            assign, scores, placed, n_eval, n_exh, waves, used_tot = out
            world.adopt_basis(used_tot)
            self.stats["donated_carries"] += 1
        self.stats["put_s"] += sp.seconds
        self.stats["sharded_evals"] = (
            self.stats.get("sharded_evals", 0) + len(reqs))
        return (assign, scores, placed, n_eval, n_exh, waves), \
            world, deltas_per, mapping

    # ---------------------------------------------------------- bulk path

    def _split_bulk(self, reqs: List[_BulkRequest], lanes: int = 1):
        # oversized-delta requests always go alone so their deltas can
        # fold into the part's private basis copy (fixed delta bucket,
        # no compile variant forked)
        overflow = [r for r in reqs if len(r.deltas) > _DELTA_BUCKET]
        rest = [r for r in reqs if len(r.deltas) <= _DELTA_BUCKET]
        for r in overflow:
            yield [r]
        chunk = self._bulk_chunk(reqs[0].feasible.shape[0], lanes)
        # the rest of the wave is ONE device call (modulo the
        # byte-budget chunk).  The dispatch picks the output format
        # (sparse iff every count fits) and delta bucket (D=0 iff
        # nothing ships deltas) for the mixed part — all combinations
        # are warmed compile variants; the sharded kernel has ONE
        # (dense, fixed-D) format.  Splitting a wave by format bought
        # smaller D2H rows at the price of ~1.5-2 dispatch round trips
        # per wave; with device-resident heavy blocks the round trips
        # dominate.
        for i in range(0, len(rest), chunk):
            yield rest[i:i + chunk]

    def _bulk_chunk(self, N: int, lanes: int = 1) -> int:
        """Largest bulk E bucket whose stacked per-eval heavy blocks
        (f32[4N] each) fit the NOMAD_TPU_BULK_BYTES budget — 100K-node
        worlds cap their chains instead of staging ~1 GB stacks.  On a
        laned mesh the stacks carry [W, E, ...] so the budget divides by
        the wave extent."""
        cap = max(1, self.bulk_bytes_budget
                  // (4 * N * 4 * max(1, lanes)))
        allowed = [b for b in self.BULK_E_BUCKETS if b <= cap]
        return min(self.max_batch, allowed[-1] if allowed else 1)

    def _dispatch_bulk_group(self, reqs: List[_BulkRequest], world=None,
                             force_scatter: bool = False):
        import jax

        cm = reqs[0].cm
        N = reqs[0].feasible.shape[0]
        E = next(b for b in self.BULK_E_BUCKETS if b >= len(reqs))
        # rows are stable across matrix re-bucketing (growth only pads
        # the node axis), so the enqueue-time world is the prefix slice
        capacity = cm.capacity[:N]
        basis = self._basis_for(cm)[:N]
        deltas_per = [r.deltas for r in reqs]
        if len(reqs) == 1 and len(reqs[0].deltas) > _DELTA_BUCKET:
            # singleton overflow part (_split_bulk): fold into the
            # private basis copy instead of forking a compile variant
            deltas_per = [_fold_overflow(basis, reqs[0].deltas)]
        # D=0 when nothing ships deltas (the fresh-placement common case)
        D = _DELTA_BUCKET if any(deltas_per) else 0

        ctx = self._ctx_of(reqs)
        with tracing.span("engine.stack", ctx=ctx, cpu=True) as sp:
            lights = [pack_bulk_light(r.has_affinity, r.desired, r.count,
                                      r.demand, ds, N, D)
                      for r, ds in zip(reqs, deltas_per)]
            Ll = lights[0].shape[0]
            if E > len(reqs):
                # padded evals have count=0: the wavefront loop exits at once
                lights += [np.zeros(Ll, np.float32)] * (E - len(reqs))
            dyn = np.concatenate(lights)
        self.stats["stack_s"] += sp.seconds
        with tracing.span("engine.put", ctx=ctx, cpu=True) as sp:
            # device-resident world: epoch upload once, dirty-row scatters
            # after; steady state ships zero basis bytes (the kernel's
            # exact carry IS the new resident basis and only the host
            # snapshot catches up, apply_rank1_host in _resolve_bulk)
            world = world if world is not None else self._world(cm, N)
            cap_dev, _ = world.update(capacity, basis,
                                      force_scatter=force_scatter)
            used_dev = world.loan_basis()
            digs = tuple(bulk_heavy_digest(r.feasible, r.affinity, r.penalty,
                                           r.coll0) for r in reqs)
            heavy = [self._cache.bulk_heavy(r, dig)
                     for r, dig in zip(reqs, digs)]
            heavy += [heavy[0]] * (E - len(reqs))
            # the stacked [E, 4N] chain is itself content-addressed: C2M's
            # identical-content evals re-dispatch the same stack every wave,
            # and the jnp.stack launch was the dominant put_kernel_s cost
            import jax.numpy as jnp
            hstack = self._cache.stack(("hstack", N, E, digs),
                                       lambda: jnp.stack(heavy))
            self.stats["cache_hits"] = self._cache.hits
            self.stats["cache_misses"] = self._cache.misses
            dyn_dev = jax.device_put(dyn)  # analysis: allow(transfer-purity) — per-dispatch dynamic leaf, shipped explicitly
            sparse = all(r.count <= SPARSE_CAP for r in reqs)
            from nomad_tpu.ops.place import fill_grid_for
            fill_grid = fill_grid_for(max(r.count for r in reqs))
            # the adopted basis is the rank-1 reconstruction (bitwise
            # what the host snapshot's scatters produce), while the
            # scan's own carry keeps chain-scoring parity
            packed, _used_final, used_exact = place_bulk_batch_donate_jit(
                cap_dev, used_dev, hstack, dyn_dev, D,
                sparse_out=sparse,
                spread_algorithm=reqs[0].spread_algorithm,
                fill_grid=fill_grid)
            world.adopt_basis(used_exact)
            self.stats["donated_carries"] += 1
        self.stats["put_s"] += sp.seconds
        return packed, world, deltas_per

    def _resolve_bulk(self, reqs: List[_BulkRequest], packed: np.ndarray,
                      world, deltas_per, mapping=None) -> None:
        """Mirror the kernel's chained usage host-side so every caller
        gets the exact used matrix its placements produced: each eval
        sees basis + prior evals' PLACEMENTS + its own private deltas;
        deltas never chain forward (uncommitted stops of one eval are
        invisible to others, exactly like the in-flight overlay).
        `deltas_per` is what the dispatch actually SHIPPED per eval —
        empty for an overflow singleton whose deltas were folded into
        the shipped basis (re-applying r.deltas would double-count).
        `world` is the DeviceWorld this dispatch scored against: each
        eval's placements scatter onto its host snapshot
        (apply_rank1_host: the adopted carry already holds them on
        device) so the NEXT dispatch's update() diff is already clean
        and ships zero basis rows in steady state.  `mapping` (laned
        sharded dispatches) gives each request's (lane, slot) in the
        [W, E, ...] outputs."""
        import jax

        N = reqs[0].feasible.shape[0]
        # one EXPLICIT device->host fetch per resolve: np.asarray on the
        # device outputs would sync implicitly, invisible to profiles and
        # to the steady-state transfer discipline
        if isinstance(packed, tuple):       # sharded path: raw field tuple
            assign, scores, placed, n_eval, n_exh, waves = \
                [np.asarray(x) for x in jax.device_get(packed)]
            assign = assign.astype(np.int32)
            if mapping is not None:
                idx = (np.array([ln for ln, _ in mapping]),
                       np.array([s for _, s in mapping]))
                assign, scores, placed, n_eval, n_exh, waves = (
                    assign[idx], scores[idx], placed[idx], n_eval[idx],
                    n_exh[idx], waves[idx])
        else:
            sparse = all(r.count <= SPARSE_CAP for r in reqs)
            assign, scores, placed, n_eval, n_exh, waves = \
                unpack_bulk_batch(np.asarray(jax.device_get(packed)), N,
                                  sparse=sparse)
        # wave-count visibility: a workload that degrades toward one
        # placement per wave shows up here instead of as mystery latency
        self.stats["waves"] += int(np.sum(waves))
        self.stats["max_waves_seen"] = max(self.stats["max_waves_seen"],
                                           int(np.max(waves, initial=0)))
        for i, r in enumerate(reqs):
            # sparse contributions only — no per-request [N, R] copies:
            # at 512-eval chains those copies dominated resolve, and the
            # scheduler reconstructs its cumulative usage from assigns.
            # One rank-1 scatter per eval instead of a per-row loop.
            rows = np.flatnonzero(assign[i])
            ticket = self.register_external_sparse(
                r.cm, rows, assign[i][rows], r.demand) \
                if rows.size else None
            if ticket is not None:
                world.apply_rank1_host(rows, assign[i][rows], r.demand)
            r.future.set_result(
                (assign[i], int(placed[i]), int(n_eval[i]),
                 int(n_exh[i]), scores[i], ticket))

    def _run_single(self, r: _Request) -> None:
        """Lone request: packed E=1 dispatch through the same device
        cache.  Still scores against the in-flight overlay basis so
        concurrent-but-unbatched evals don't collide."""
        try:
            if r.cm.used.shape[0] == r.inputs.used.shape[0]:
                basis = self._basis_for(r.cm)
                deltas = r.deltas
                cap_src = r.cm.capacity
                if len(deltas) > _DELTA_BUCKET:
                    # basis is a fresh copy; no compile variant forked
                    deltas = _fold_overflow(basis, deltas)
            else:
                # matrix re-bucketed since inputs were built: inputs.used
                # already carries the deltas, score against it verbatim
                basis = np.asarray(r.inputs.used, np.float32)
                deltas = []
                cap_src = r.inputs.capacity
            packed = self._dispatch_packed(
                [r], E=1, basis=basis, deltas_per_req=[deltas],
                capacity=cap_src)
            self._fetch_resolve_scan([r], packed)
        except Exception as e:                  # noqa: BLE001
            r.future.set_exception(e)

    def _dispatch_group(self, reqs: List[_Request]):
        """One shape-group -> one packed dispatch: heavy blocks resolve
        through the device cache (hits ship nothing), light blocks + the
        usage basis concatenate into ONE device_put leaf.  Returns the
        device-side output array (fetch happens batched in _dispatch)."""
        cm = reqs[0].cm
        basis = self._basis_for(cm)
        E = next(b for b in self.E_BUCKETS if b >= len(reqs))
        return self._dispatch_packed(
            reqs, E=E, basis=basis,
            deltas_per_req=[r.deltas for r in reqs], capacity=cm.capacity)

    def _dispatch_packed(self, reqs: List[_Request], E: int,
                         basis: np.ndarray, deltas_per_req,
                         capacity: np.ndarray):
        import jax

        i0 = reqs[0].inputs
        S = _s_bucket(i0.demand.shape[0])
        R = NUM_RESOURCE_DIMS
        D = _DELTA_BUCKET

        ctx = self._ctx_of(reqs)
        with tracing.span("engine.stack", ctx=ctx, cpu=True) as sp:
            lights = [pack_light(r.inputs, d, D, S)
                      for r, d in zip(reqs, deltas_per_req)]
            Ll = lights[0].shape[0]
            if E > len(reqs):
                lights += [np.zeros(Ll, np.float32)] * (E - len(reqs))
            basis = np.ascontiguousarray(basis, dtype=np.float32)
            dyn = np.concatenate(lights)
        self.stats["stack_s"] += sp.seconds
        # cache resolution inside the put window: misses device_put the
        # heavy bytes, and that transfer cost belongs in put_s
        with tracing.span("engine.put", ctx=ctx, cpu=True) as sp:
            cap_dev, used_dev = self._world(
                reqs[0].cm, basis.shape[0]).update(capacity, basis)
            heavy = [self._cache.heavy(r.inputs) for r in reqs]
            heavy += [heavy[0]] * (E - len(reqs))   # pads place nothing
            self.stats["cache_hits"] = self._cache.hits
            self.stats["cache_misses"] = self._cache.misses
            dyn_dev = jax.device_put(dyn)  # analysis: allow(transfer-purity) — per-dispatch dynamic leaf (basis deltas + light blocks): payload that must ship, sent explicitly so the runtime guard stays armed
            packed, _used_final = place_batch_packed_jit(
                cap_dev, used_dev, tuple(heavy), dyn_dev,
                heavy_dims(i0) + (S, D),
                spread_algorithm=reqs[0].spread_algorithm)
        self.stats["put_s"] += sp.seconds
        self.stats["scan_steps_run"] += sum(
            _scan_bound(r.inputs.slot_active) for r in reqs)
        self.stats["scan_steps_bucket"] += E * S
        return packed


_engine: Optional[PlacementEngine] = None
_engine_lock = threading.Lock()


def get_engine() -> PlacementEngine:
    """The process-wide engine, made on first use."""
    global _engine
    with _engine_lock:
        if _engine is None:
            _engine = PlacementEngine()
        return _engine
