"""Device-resident world state: the node x resource matrices live on
device across dispatches, and changes scatter in as row deltas.

The capacity / usage-basis matrices are the only per-dispatch inputs
whose CONTENT survives from wave to wave: a plan cycle touches a few
hundred rows of a 10K-100K row world.  Re-shipping the full [N, R]
matrices host->device every dispatch (and, on the sharded path,
re-sharding them across the mesh) was the dominant transfer cost at
C2M-1M rates (BENCH_r05: put_basis_s/put_heavy_s ~0.35 s,
put_kernel_s ~14.7 s per run).

`DeviceWorld` keeps one (capacity, basis) pair resident per cluster
epoch — an epoch is a (matrix identity, padded row count) pair, so the
matrix growing (ClusterMatrix._grow re-buckets the node axis) starts a
new epoch with one full upload, while routine node churn (join/drain
mutates PADDED rows in place) and plan commits flow in as bucketed
dirty-row scatters:

- `update(capacity, basis)` diffs both matrices against the host
  snapshot shipped last time and scatters only the changed rows
  (bucketed pad so the row count never forks an XLA compile variant;
  >25% churn or a shape change falls back to one full device_put).
  Under a pending donated dispatch (`force_scatter`) the resident basis
  holds placements the host does not know yet, so a changed row ships
  its CHANGE (`host[row] - last[row]`) and the device adds it: the
  row's in-flight placements survive (`chained_rows_added`).
- `apply_rank1(rows, counts, demand)` is the commit/overlay hand-off
  twin of the native `scatter_add_rank1` export: the same rank-1
  update lands in the host snapshot (native scatter) and in the device
  basis (jitted scatter) in one call, so a resolved bulk eval's
  placements are already device-resident before the next dispatch
  diffs — the steady-state diff is empty and ships zero rows.  The
  engine's bulk path does not call it: the donated carry it adopts
  already holds the placements, and `apply_rank1_host` (below) brings
  only the host snapshot along.

On a multi-device mesh the buffers live sharded over the serving
mesh's 'node_shard' axis (`NamedSharding(mesh, P('node_shard', None))`,
replicated across 'wave' columns) and the scatters run through
`sharded.serving_update_fns` — a shard_map twin that translates global
rows to shard-local ones so each device only writes rows it owns (no
cross-device gather of the operand).

Updates are functional (`at[...].set` under jit): in-flight consumers
(a dispatched kernel, a concurrent warmup thread) keep the old buffer
alive until they finish, then it frees — replacing the buffer under
the lock while readers hold references is safe.  Explicit buffer
donation IS safe, but only through the loan/adopt lifecycle below:
`loan_basis()` transfers exclusive ownership of the resident basis to
a donating kernel (the world forgets it, so no later scatter can touch
a donated-away buffer), and `adopt_basis(used_final)` installs the
kernel's carry output as the new resident basis.  Because the donated
carry already contains the wave's placements, the resolve path pairs
it with `apply_rank1_host` — the host-snapshot-only rank-1 twin —
keeping host and device in lockstep with zero basis bytes shipped.
"""
from __future__ import annotations

import threading
from typing import Optional, Tuple

import numpy as np

from nomad_tpu import chaos
from nomad_tpu import native as _native
from nomad_tpu.analysis import race, recompile

# transfer-purity (nomad_tpu.analysis): this module is on the dispatch
# hot path AND is the one place sanctioned to jax.device_put world bytes
_TRANSFER_HOT_PATH = True
_TRANSFER_UPLOAD_SITE = True
# recompile-budget: every jit site here must be registered by name
_RECOMPILE_TRACKED = True

# dirty-row buckets: each size is one small compile of the row scatter
ROW_BUCKETS = (64, 512, 4096)

# canonical mesh identity lives with the kernel caches it keys
from nomad_tpu.parallel.sharded import mesh_key  # noqa: E402,F401


_set_rows_fn = None
_add_rank1_fn = None
_add_rows_fn = None


def _single_device_fns():
    """Jitted (set_rows, add_rank1, add_rows) scatters for the unsharded
    world (rows == N pad slots drop)."""
    global _set_rows_fn, _add_rank1_fn, _add_rows_fn
    if _set_rows_fn is None:
        import jax
        import jax.numpy as jnp
        _set_rows_fn = jax.jit(
            lambda d, r, v: d.at[r].set(v, mode="drop"))
        _add_rank1_fn = jax.jit(
            lambda d, r, c, dem: d.at[r].add(
                c[:, None].astype(jnp.float32) * dem, mode="drop"))
        _add_rows_fn = jax.jit(
            lambda d, r, v: d.at[r].add(v, mode="drop"))
        recompile.register("world.set_rows", _set_rows_fn)
        recompile.register("world.add_rank1", _add_rank1_fn)
        recompile.register("world.add_rows", _add_rows_fn)
    return _set_rows_fn, _add_rank1_fn, _add_rows_fn


def _scatter_fns(mesh):
    """(set_rows, add_rank1, add_rows) for a world resident on `mesh`
    (None: one device)."""
    if mesh is None:
        return _single_device_fns()
    from nomad_tpu.parallel.sharded import serving_update_fns
    return serving_update_fns(mesh)


def warm_scatter(shape: tuple, mesh=None) -> None:
    """Compile the row scatters (set, and the chained dispatch's add)
    for a world of `shape` (N, R) and every ROW_BUCKET before a measured
    window.  The first dirty-row update of an epoch otherwise pays its
    bucket's XLA compile inside the steady state (the recompile gate
    flags it).  Dispatches are pad-only no-ops — every row index is N,
    so `mode="drop"` discards them — against a throwaway zero world,
    never a resident one."""
    import jax

    N, R = shape
    w = DeviceWorld(mesh)
    dev = w._put_full(np.zeros((N, R), np.float32))
    set_fn, _, add_fn = _scatter_fns(mesh)
    for b in ROW_BUCKETS:
        rows = np.full(b, N, np.int32)
        vals = np.zeros((b, R), np.float32)
        rows_dev, vals_dev = w._put_operands(rows, vals)
        jax.block_until_ready(set_fn(dev, rows_dev, vals_dev))
        jax.block_until_ready(add_fn(dev, rows_dev, vals_dev))


class DeviceWorld:
    """One epoch's device-resident (capacity, basis) pair.

    Thread-safe: every read-modify-write of the resident pair happens
    under `self.lock` (warmup dispatches run concurrently with the
    engine thread)."""

    # happens-before (nomad_tpu.analysis): the host snapshot is written
    # by the plan applier (apply_rank1) and the engine thread (update)
    # concurrently; both must hold `lock`.  The race detector traces it.
    _RACE_TRACED = {"_basis_last": "lock"}

    def __init__(self, mesh=None):
        self.mesh = mesh
        self.lock = threading.Lock()
        self.shape: Optional[tuple] = None       # (N, R) of current epoch
        self._cap_last: Optional[np.ndarray] = None
        self._cap_dev = None
        self._basis_last: Optional[np.ndarray] = None
        self._basis_dev = None
        self.stats = {"full_uploads": 0, "rows_scattered": 0,
                      "clean_hits": 0, "rank1_applies": 0,
                      # full uploads AFTER the epoch's first (churn
                      # fallback or injected device loss): the bench's
                      # steady-state gate asserts this stays 0
                      "steady_reuploads": 0,
                      # rows brought up to date by ADDING the host's
                      # change under a pending donated dispatch (a
                      # subset of rows_scattered)
                      "chained_rows_added": 0,
                      # donated-carry lifecycle (loan_basis/adopt_basis)
                      "basis_loans": 0, "basis_adopts": 0}

    # ------------------------------------------------------------ helpers

    def _sharding(self):
        if self.mesh is None:
            return None
        from jax.sharding import NamedSharding, PartitionSpec as P
        axis = "node_shard" if "node_shard" in self.mesh.axis_names \
            else self.mesh.axis_names[0]
        return NamedSharding(self.mesh, P(axis, None))

    def _put_full(self, host: np.ndarray):
        import jax
        sh = self._sharding()
        # ALWAYS ship a private copy: on the CPU backend device_put
        # zero-copy aliases the numpy buffer, so uploading
        # _basis_last/_cap_last directly would let apply_rank1's native
        # host scatter mutate the "device" array in place behind jit
        arr = np.array(host, dtype=np.float32)
        return jax.device_put(arr) if sh is None \
            else jax.device_put(arr, sh)

    def _put_operands(self, *arrays):
        """Explicit upload of scatter operands (rows/counts/values).
        These are the per-update payload — they must ship — but shipping
        them IMPLICITLY (numpy straight into jit) is exactly what the
        steady-state transfer guard forbids; on a mesh the operands are
        replicated to match the serving kernels' P(None) in_specs."""
        import jax
        if self.mesh is None:
            return tuple(jax.device_put(a) for a in arrays)
        from jax.sharding import NamedSharding, PartitionSpec as P
        return tuple(
            jax.device_put(a, NamedSharding(self.mesh,
                                            P(*([None] * a.ndim))))
            for a in arrays)

    def _update_one(self, host: np.ndarray, last: Optional[np.ndarray],
                    dev, force_scatter: bool = False
                    ) -> Tuple[np.ndarray, object, bool]:
        """Sync one matrix; returns (new snapshot, new device array,
        full-upload?).  Caller holds self.lock.  `force_scatter` is the
        chained-dispatch (donated-carry pipeline) discipline: the device
        array is the snapshot PLUS in-flight placements the host lacks
        until the pending dispatch resolves.  Neither a full upload nor
        a row set may touch it — both would put the host's value where
        those placements are — so every dirty row ships
        `host[row] - last[row]` and the device adds it, in bucket-sized
        chunks however large the churn.  Usage is whole MHz / MB in
        float32, so the sum is exact: once the pending dispatch has
        resolved (apply_rank1_host) device and snapshot agree bit for
        bit."""
        if chaos.active is not None and \
                chaos.active.should("world.scatter_fail"):
            # injected device loss: forget what shipped so this update
            # falls through to one full re-upload (deterministic
            # recovery, nothing raises mid-dispatch)
            last, dev = None, None
        if last is None or last.shape != host.shape or dev is None:
            snap = np.array(host, dtype=np.float32)
            return snap, self._put_full(snap), True
        N, R = host.shape
        Bmax = ROW_BUCKETS[-1]
        changed = np.nonzero(np.any(last != host, axis=1))[0]
        if changed.size == 0:
            self.stats["clean_hits"] += 1
            return last, dev, False
        if not force_scatter and changed.size > min(N // 4, Bmax):
            snap = np.array(host, dtype=np.float32)
            return snap, self._put_full(snap), True
        # read the dirty rows ONCE: `host` may be live (node churn mutates
        # it concurrently) and the snapshot must equal what shipped, not
        # what the row holds a moment later
        changed_vals = np.array(host[changed], dtype=np.float32)
        set_fn, _, add_fn = _scatter_fns(self.mesh)
        if force_scatter:
            fn, ship = add_fn, changed_vals - last[changed]
            self.stats["chained_rows_added"] += int(changed.size)
        else:
            fn, ship = set_fn, changed_vals
        # chunks past the largest bucket (chained churn only): every
        # chunk a warmed compile variant
        for off in range(0, changed.size, Bmax):
            cr = changed[off:off + Bmax]
            b = next(b for b in ROW_BUCKETS if b >= cr.size)
            rows = np.full(b, N, np.int32)           # pad slots drop
            rows[:cr.size] = cr
            vals = np.zeros((b, R), np.float32)
            vals[:cr.size] = ship[off:off + Bmax]
            rows_dev, vals_dev = self._put_operands(rows, vals)
            dev = fn(dev, rows_dev, vals_dev)
        snap = last.copy()
        snap[changed] = changed_vals
        self.stats["rows_scattered"] += int(changed.size)
        return snap, dev, False

    # ------------------------------------------------------------- public

    def update(self, capacity: np.ndarray, basis: np.ndarray,
               force_scatter: bool = False):
        """Bring the resident pair up to date with the host truth;
        returns (capacity_dev, basis_dev).  `capacity` may be the LIVE
        cm.capacity (it is snapshot-copied before any caching decision);
        `basis` must already be a private copy (engine._basis_for).
        `force_scatter` (chained donated-carry dispatches only) says the
        resident basis carries in-flight placements the host lacks: the
        basis is brought up to date by adding each dirty row's change,
        never by a row set or a full upload, which would erase them."""
        with self.lock:
            shape = (capacity.shape, basis.shape)
            if shape != self.shape:              # new cluster epoch
                self.shape = shape
                self._cap_last = np.array(capacity, dtype=np.float32)
                self._cap_dev = self._put_full(self._cap_last)
                race.write("DeviceWorld._basis_last", self)
                self._basis_last = np.array(basis, dtype=np.float32)
                self._basis_dev = self._put_full(self._basis_last)
                self.stats["full_uploads"] += 1
                return self._cap_dev, self._basis_dev
            self._cap_last, self._cap_dev, full_c = self._update_one(
                capacity, self._cap_last, self._cap_dev)
            race.write("DeviceWorld._basis_last", self)
            self._basis_last, self._basis_dev, full_b = self._update_one(
                basis, self._basis_last, self._basis_dev,
                force_scatter=force_scatter)
            if full_c or full_b:
                self.stats["full_uploads"] += 1
                # a full ship after the epoch's first upload means the
                # steady state leaked world bytes (churn fallback or an
                # injected device loss) — the bench gate watches this
                self.stats["steady_reuploads"] += 1
            return self._cap_dev, self._basis_dev

    def loan_basis(self):
        """Transfer exclusive ownership of the resident basis buffer to
        a donating kernel.  The world forgets the buffer (no later
        scatter or update can alias a donated-away array); the caller
        MUST follow the dispatch with `adopt_basis(used_final)` — or, on
        a failed dispatch, leave the world invalidated so the next
        update() re-uploads from the host snapshot.  update() always
        leaves a basis resident and the donating kernels are the only
        ones warmed, so a loan with none resident is a broken
        invariant, not a case to fall back from."""
        with self.lock:
            dev, self._basis_dev = self._basis_dev, None
            if dev is None:
                raise RuntimeError(
                    "DeviceWorld.loan_basis: no resident basis "
                    "(loan must follow update())")
            self.stats["basis_loans"] += 1
            return dev

    def adopt_basis(self, dev) -> None:
        """Install a kernel's donated-carry output as the resident
        basis.  The caller pairs this with `apply_rank1_host` at resolve
        time: the adopted carry already holds the wave's placements on
        device, so only the host snapshot needs the rank-1 update."""
        with self.lock:
            self._basis_dev = dev
            if dev is not None:
                self.stats["basis_adopts"] += 1

    def invalidate_basis(self) -> None:
        """Forget the resident basis (failed donated dispatch / poisoned
        carry): the next update() ships a full upload from the host
        snapshot instead of serving a suspect buffer."""
        with self.lock:
            self._basis_dev = None

    def _rank1_host_locked(self, rows: np.ndarray, counts: np.ndarray,
                           demand: np.ndarray) -> Optional[tuple]:
        """Rank-1 update of the HOST snapshot (native scatter); caller
        holds self.lock.  Returns the clipped (rows, counts, d) for the
        device twin, or None if there is nothing to scatter."""
        race.write("DeviceWorld._basis_last", self)
        if self._basis_last is None:
            return None                          # next update ships full
        n, r = self._basis_last.shape
        rows = np.ascontiguousarray(rows, np.int32)
        counts = np.ascontiguousarray(counts, np.int32)
        keep = rows < n
        if not keep.all():
            rows, counts = rows[keep], counts[keep]
        if rows.size == 0:
            return None
        d = np.zeros(r, np.float32)
        d[:min(len(demand), r)] = np.asarray(
            demand, np.float32)[:r]
        _native.scatter_add_rank1(self._basis_last, rows, counts, d)
        return rows, counts, d

    def apply_rank1(self, rows: np.ndarray, counts: np.ndarray,
                    demand: np.ndarray) -> None:
        """Scatter `counts[k] * demand` into basis row `rows[k]` on BOTH
        copies (host snapshot via the native export, device via the
        jitted twin), keeping them in lockstep so the next update()'s
        diff sees those rows clean."""
        with self.lock:
            clipped = self._rank1_host_locked(rows, counts, demand)
            if clipped is None:
                return
            rows, counts, d = clipped
            if chaos.active is not None and \
                    chaos.active.should("world.scatter_fail"):
                # injected device loss of the scatter: the host snapshot
                # above is authoritative; drop the resident basis so the
                # next update() re-uploads it rather than serving a
                # basis missing this commit
                self._basis_dev = None
                self.stats["chaos_invalidations"] = \
                    self.stats.get("chaos_invalidations", 0) + 1
                return
            if self._basis_dev is None:
                return                   # loaned out: next update ships
            _, fn, _ = _scatter_fns(self.mesh)
            rows_dev, counts_dev, d_dev = self._put_operands(
                rows, counts, d)
            self._basis_dev = fn(self._basis_dev, rows_dev, counts_dev,
                                 d_dev)
            self.stats["rank1_applies"] += 1

    def apply_rank1_host(self, rows: np.ndarray, counts: np.ndarray,
                         demand: np.ndarray) -> None:
        """Host-snapshot-only rank-1 twin for the donated-carry path:
        the adopted device basis ALREADY contains these placements (the
        kernel's carry output), so scattering them on device would
        double-count — only the host snapshot catches up, restoring
        lockstep.  The chaos hook mirrors apply_rank1: an injected
        device loss drops the adopted carry and the next update()
        re-uploads from the (authoritative) host snapshot."""
        with self.lock:
            if self._rank1_host_locked(rows, counts, demand) is None:
                return
            if chaos.active is not None and \
                    chaos.active.should("world.scatter_fail"):
                self._basis_dev = None
                self.stats["chaos_invalidations"] = \
                    self.stats.get("chaos_invalidations", 0) + 1
                return
            self.stats["rank1_applies"] += 1

    def host_basis(self) -> Optional[np.ndarray]:
        """Copy of the host-side basis snapshot (tests / debugging)."""
        with self.lock:
            race.read("DeviceWorld._basis_last", self)
            return None if self._basis_last is None \
                else self._basis_last.copy()

    def device_arrays(self):
        """(capacity_dev, basis_dev) as currently resident (no sync)."""
        with self.lock:
            return self._cap_dev, self._basis_dev
