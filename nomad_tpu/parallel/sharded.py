"""Sharded placement: the dense engine over a ('node_shard', 'wave') mesh.

The serving mesh is 2-D.  Along `node_shard` each device owns a
contiguous row shard of the [N, R] world: inside one scan step every
shard scores its local nodes, the global best node is found with pmax
(max score) + pmin (lowest global row among ties, matching the
single-chip argmax tie-break), and each shard applies the carry update
only to rows it owns.  Cross-shard information (the selected node's
spread value indices) moves via psum of a masked gather — an
ICI-friendly scalar collective rather than an all-gather of the whole
matrix.

Along `wave`, INDEPENDENT ready waves (bulk evals from different
namespaces, binned by the engine's wave_key) score concurrently on
disjoint device columns: each lane runs its own chained eval scan
against the shared usage basis, and the merged basis is the psum of the
lane deltas.  Lanes are blind to each other within one dispatch — the
plan applier's overlay/commit validation remains the capacity authority,
exactly as it is for evals split across dispatches.

`wave_mesh_shape` factors a device count into the (node_shard, wave)
grid; `NOMAD_TPU_WAVE_SHARDS` pins the wave extent.
"""
from __future__ import annotations

import functools
import math
import os
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from nomad_tpu import knobs
from nomad_tpu.analysis import recompile
from nomad_tpu.ops.fit import score_fit
from nomad_tpu.ops.place import (
    PlaceInputs, PlaceResult, TOP_K, distinct_open, place_carry0,
    prop_counts_add)

# transfer-purity / recompile-budget (nomad_tpu.analysis): mesh dispatch
# is hot-path code; every jit built here is registered with the budget
_TRANSFER_HOT_PATH = True
_RECOMPILE_TRACKED = True

BIG = jnp.int32(2**31 - 1)

# mesh axis names: rows of the world along NODE_AXIS, independent eval
# waves along WAVE_AXIS
NODE_AXIS_NAME = "node_shard"
WAVE_AXIS_NAME = "wave"


def mesh_key(mesh) -> Optional[tuple]:
    """Stable identity of a device mesh: axis layout + device ids.

    `id(mesh)` is NOT a mesh identity — a re-created Mesh object can
    reuse the id of a dead one and resurrect its cache entries with
    stale shardings; conversely two distinct but equal Mesh objects must
    hit the same kernel cache entry (re-creating the mesh must not
    recompile).  Two meshes with the same axes over the same devices are
    interchangeable for sharding purposes."""
    if mesh is None:
        return None
    return (tuple(mesh.shape.items()),
            tuple(d.id for d in mesh.devices.flat))


def _put_host(mesh, spec, x):  # analysis: allow(transfer-purity) — per-wave delta/field operands are payload, shipped explicitly with their mesh sharding so the runtime guard stays "disallow"
    """Explicitly upload a host operand with its mesh sharding.  Device
    arrays pass through untouched (no reshard, no transfer); numpy
    operands would otherwise trip the steady-state transfer guard as
    implicit host->device (or, placed on one device, device->device)
    transfers inside jit."""
    if isinstance(x, np.ndarray):
        return jax.device_put(x, NamedSharding(mesh, spec))
    return x


def wave_mesh_shape(n_devices: int,
                    wave_shards: Optional[int] = None) -> Tuple[int, int]:
    """Factor a device count into the (node_shard, wave) grid.

    Node sharding is the always-profitable axis (it divides the [N, M]
    scoring grids, where the FLOPs live), so the wave extent is the
    LARGEST divisor of `n_devices` that is <= sqrt(n_devices): 1 -> 1x1,
    2 -> 2x1, 4 -> 2x2, 8 -> 4x2.  `wave_shards` (or the
    NOMAD_TPU_WAVE_SHARDS env knob) pins the wave extent instead; a
    value that does not divide the device count falls back to 1 rather
    than dropping devices from the mesh."""
    if n_devices < 1:
        raise ValueError(f"n_devices must be >= 1, got {n_devices}")
    if wave_shards is None:
        wave_shards = knobs.get_int("NOMAD_TPU_WAVE_SHARDS")
    if wave_shards is not None:
        w = max(1, int(wave_shards))
        if n_devices % w != 0:
            w = 1
        return n_devices // w, w
    w = max(d for d in range(1, math.isqrt(n_devices) + 1)
            if n_devices % d == 0)
    return n_devices // w, w


def make_mesh(n_wave_shards: Optional[int] = None,
              n_node_shards: Optional[int] = None, devices=None) -> Mesh:
    """Named 2-D ('node_shard', 'wave') device mesh.  With no explicit
    shape, `wave_mesh_shape` picks the factorization for the full
    device set."""
    devices = list(devices if devices is not None else jax.devices())
    if n_wave_shards is None and n_node_shards is None:
        n_node_shards, n_wave_shards = wave_mesh_shape(len(devices))
    elif n_node_shards is None:
        n_node_shards = len(devices) // n_wave_shards
    elif n_wave_shards is None:
        n_wave_shards = len(devices) // n_node_shards
    dev = np.array(devices[:n_wave_shards * n_node_shards]).reshape(
        n_node_shards, n_wave_shards)
    return Mesh(dev, (NODE_AXIS_NAME, WAVE_AXIS_NAME))


def stack_inputs(inputs) -> PlaceInputs:
    """Stack a list of PlaceInputs (same padded shapes) along a leading
    eval-batch axis."""
    return jax.tree_util.tree_map(lambda *xs: np.stack(xs), *inputs)


# PartitionSpecs for one eval's PlaceInputs, node axis sharded.  A leading
# 'wave' batch axis is prepended by place_eval_batch_sharded.
_NODE_AXIS = {
    "capacity": 0, "used": 0,
    "feasible": 1, "affinity": 1, "penalty": 1, "tg_count": 1,
    "spread_vidx": 2, "place_cap": 1, "dev_score": 1,
    "hosts_taken": 1, "prop_vidx": 1,
    "hosts_of": None, "prop_counts": None, "prop_limit": None,
    "prop_of": None,
    "has_affinity": None, "desired_count": None, "has_dev": None,
    "spread_desired": None, "spread_targeted": None, "spread_wfrac": None,
    "spread_counts": None, "spread_active": None,
    "demand": None, "slot_tg": None, "slot_active": None,
}


_NDIM = {"capacity": 2, "used": 2, "feasible": 2, "affinity": 2,
         "penalty": 2, "tg_count": 2, "spread_vidx": 3, "place_cap": 2,
         "dev_score": 2, "has_dev": 1, "has_affinity": 1,
         "desired_count": 1, "spread_desired": 3, "spread_targeted": 2,
         "spread_wfrac": 2, "spread_counts": 3, "spread_active": 2,
         "hosts_taken": 2, "hosts_of": 2, "prop_vidx": 2, "prop_counts": 2,
         "prop_limit": 1, "prop_of": 2,
         "demand": 2, "slot_tg": 1, "slot_active": 1}


def _input_specs(batched: bool) -> PlaceInputs:
    specs = {}
    for name, axis in _NODE_AXIS.items():
        parts = [None] * _NDIM[name]
        if axis is not None:
            parts[axis] = NODE_AXIS_NAME
        if batched:
            parts = [WAVE_AXIS_NAME] + parts
        specs[name] = P(*parts)
    return PlaceInputs(**specs)


def _place_step_sharded(inp: PlaceInputs, spread_algorithm: bool,
                        shard_offset: jax.Array, carry, slot):
    """One placement step on a node shard (mirrors ops.place._place_step;
    the selection and carry updates go through 'node_shard'
    collectives)."""
    used, tg_count, spread_counts, place_cap, hosts_taken, prop_counts = carry
    g = inp.slot_tg[slot]
    d = inp.demand[slot]
    active = inp.slot_active[slot]
    n_local = used.shape[0]
    global_rows = shard_offset + jnp.arange(n_local)

    feas = inp.feasible[g] & (place_cap[g] != 0) \
        & distinct_open(inp, g, hosts_taken, prop_counts)
    util = used + d
    fits = jnp.all(util <= inp.capacity, axis=-1) & feas

    fit_score = score_fit(inp.capacity, util, spread_algorithm) / 18.0
    total = fit_score
    n_scorers = jnp.ones_like(fit_score)

    coll = tg_count[g].astype(jnp.float32)
    anti = -(coll + 1.0) / jnp.maximum(inp.desired_count[g].astype(jnp.float32), 1.0)
    has_coll = coll > 0.0
    total = total + jnp.where(has_coll, anti, 0.0)
    n_scorers = n_scorers + has_coll

    pen = inp.penalty[g]
    total = total - pen
    n_scorers = n_scorers + pen

    aff = inp.affinity[g]
    aff_on = inp.has_affinity[g] & (aff != 0.0)
    total = total + jnp.where(aff_on, aff, 0.0)
    n_scorers = n_scorers + aff_on

    # spread scoring: counts carry is replicated; per-node boost local
    from nomad_tpu.ops.place import _spread_boost
    sboost = _spread_boost(inp, g, spread_counts[g])
    sb_on = jnp.any(inp.spread_active[g]) & (sboost != 0.0)
    total = total + jnp.where(sb_on, sboost, 0.0)
    n_scorers = n_scorers + sb_on

    dev_on = inp.has_dev[g]
    total = total + jnp.where(dev_on, inp.dev_score[g], 0.0)
    n_scorers = n_scorers + dev_on

    final = total / n_scorers
    masked = jnp.where(fits & active, final, -jnp.inf)

    # --- global argmax over 'node_shard': pmax score, pmin row among ties
    local_best = jnp.max(masked)
    global_best = jax.lax.pmax(local_best, NODE_AXIS_NAME)
    local_idx = jnp.argmax(masked)
    cand = jnp.where((local_best == global_best) & (global_best > -jnp.inf),
                     global_rows[local_idx], BIG)
    sel = jax.lax.pmin(cand, NODE_AXIS_NAME)
    ok = sel < BIG

    # --- carry updates: only the owning shard touches its rows
    sel_local = (global_rows == sel) & ok
    used = used + jnp.where(sel_local[:, None], d, 0.0)
    tg_count = tg_count + jnp.where(
        (jnp.arange(tg_count.shape[0]) == g)[:, None] & sel_local[None, :],
        1, 0)
    place_cap = place_cap - jnp.where(
        (jnp.arange(place_cap.shape[0]) == g)[:, None]
        & sel_local[None, :] & (place_cap > 0), 1, 0)
    # selected node's spread value indices: psum of masked gather
    K = inp.spread_vidx.shape[1]
    Vp1 = spread_counts.shape[-1]
    v_local = jnp.sum(jnp.where(sel_local[None, :], inp.spread_vidx[g], 0), axis=1)
    v = jax.lax.psum(v_local, NODE_AXIS_NAME)             # i32[K]
    upd = jax.nn.one_hot(jnp.minimum(v, Vp1 - 1), Vp1, dtype=spread_counts.dtype)
    upd = upd * (inp.spread_active[g] & (v < Vp1 - 1))[:, None] * ok
    spread_counts = spread_counts.at[g].add(upd)
    # distinct_*: the owning shard marks its row; the selected node's
    # property values come as the spread's do
    hosts_taken = hosts_taken | (inp.hosts_of[g][:, None] & sel_local[None, :])
    if prop_counts.shape[0]:
        pv = jax.lax.psum(
            jnp.sum(jnp.where(sel_local[None, :], inp.prop_vidx, 0), axis=1),
            NODE_AXIS_NAME)
        prop_counts = prop_counts_add(inp, g, prop_counts, pv, ok)

    # per-slot metrics (global)
    fit_sel = jax.lax.psum(
        jnp.sum(jnp.where(sel_local, fit_score, 0.0)), NODE_AXIS_NAME)
    n_eval = jax.lax.psum(jnp.sum(feas & active), NODE_AXIS_NAME)
    n_exh = jax.lax.psum(jnp.sum(feas & ~fits & active), NODE_AXIS_NAME)
    k_local = min(TOP_K, masked.shape[0])
    top_s_l, top_i_l = jax.lax.top_k(masked, k_local)
    top_s = jax.lax.all_gather(top_s_l, NODE_AXIS_NAME, tiled=True)
    top_i = jax.lax.all_gather(global_rows[top_i_l], NODE_AXIS_NAME,
                               tiled=True)
    order = jnp.argsort(-top_s)[:TOP_K]

    out = (
        jnp.where(ok, sel, -1).astype(jnp.int32),
        jnp.where(ok, global_best, 0.0),
        jnp.where(ok, fit_sel, 0.0),
        n_eval.astype(jnp.int32),
        n_exh.astype(jnp.int32),
        top_i[order].astype(jnp.int32),
        top_s[order],
    )
    return (used, tg_count, spread_counts, place_cap, hosts_taken,
            prop_counts), out


def _shard_body(inp: PlaceInputs, spread_algorithm: bool):
    """Runs inside shard_map for one eval: scan over slots.

    The full `lax.scan` over the padded slot axis stays here, where the
    one-device kernels cut theirs at the eval's last active slot
    (`ops.place._scan_slots`): this body is vmapped over the lane's
    evals, and a batched `while` runs every lane to the longest one with
    a collective in every step (ROADMAP S33)."""
    idx = jax.lax.axis_index(NODE_AXIS_NAME)
    n_local = inp.used.shape[0]
    shard_offset = idx * n_local
    S = inp.demand.shape[0]
    step = functools.partial(_place_step_sharded, inp, spread_algorithm,
                             shard_offset)
    carry, outs = jax.lax.scan(step, place_carry0(inp, inp.used),
                               jnp.arange(S))
    node, score, fit_s, n_eval, n_exh, top_i, top_s = outs
    return node, score, fit_s, n_eval, n_exh, top_i, top_s, carry[0]


def place_eval_batch_sharded(mesh: Mesh, stacked: PlaceInputs,
                             spread_algorithm: bool = False):
    """Place a batch of evals over the ('node_shard','wave') mesh.

    `stacked` has a leading eval-batch axis on every field (see
    stack_inputs); the batch is sharded over 'wave' and the node axis
    over 'node_shard'.  Returns per-eval (node, score, fit_score,
    nodes_evaluated, nodes_exhausted, top_nodes, top_scores, used_final).
    """
    in_specs = _input_specs(batched=True)

    def body(inp: PlaceInputs):
        # inside shard_map each device holds a local slice of the eval
        # batch; vmap over it (collectives batch across the vmapped axis)
        return jax.vmap(lambda one: _shard_body(one, spread_algorithm))(inp)

    W, NS = WAVE_AXIS_NAME, NODE_AXIS_NAME
    out_specs = (
        P(W, None), P(W, None), P(W, None),
        P(W, None), P(W, None), P(W, None, None),
        P(W, None, None), P(W, NS, None),
    )
    key = ("eval_batch", mesh_key(mesh), spread_algorithm)
    fn = _SERVING_FN_CACHE.get(key)
    if fn is None:
        fn = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=(in_specs,),
                                   out_specs=out_specs, check_vma=False))
        recompile.register("sharded.eval_batch", fn)
        _SERVING_FN_CACHE[key] = fn
    return fn(stacked)


# --------------------------------------------------------------------------
# Serving-path kernels: the PlacementEngine's chained batch semantics over
# the 2-D serving mesh.  Within a wave lane the eval axis stays a lax.scan
# (eval e+1 scores against usage including eval e's placements — identical
# placements to the single-device engine, the property the conflict-free
# design relies on); the node axis, where the FLOPs live, shards across
# 'node_shard'.  Selection/ordering runs on [N]-vector collectives
# (all_gather/pmax/psum over ICI), which are KBs per wave — the scoring
# stacks and the [N, M] fill grid never leave their shard.
# --------------------------------------------------------------------------


def _apply_deltas_local(used, delta_rows, delta_vals, shard_offset):
    """Scatter global-row sparse deltas into a node-sharded usage matrix
    (rows outside this shard drop)."""
    n_local = used.shape[0]
    lrows = delta_rows - shard_offset
    ok = (lrows >= 0) & (lrows < n_local)
    lrows = jnp.where(ok, lrows, n_local)
    return used.at[lrows].add(
        jnp.where(ok[:, None], delta_vals, 0.0), mode="drop")


def make_serving_mesh(devices=None,
                      wave_shards: Optional[int] = None) -> Mesh:
    """The engine's serving mesh: the 2-D ('node_shard','wave')
    factorization over all devices.  Basis/capacity shard over
    'node_shard' (replicated across wave columns); only the laned bulk
    kernel populates the 'wave' axis."""
    devices = list(devices if devices is not None else jax.devices())
    n_node, n_wave = wave_mesh_shape(len(devices), wave_shards)
    dev = np.array(devices[:n_node * n_wave]).reshape(n_node, n_wave)
    return Mesh(dev, (NODE_AXIS_NAME, WAVE_AXIS_NAME))


def _set_rows_local(dev, rows, vals):
    """Shard-local row SET: global `rows` translate to this shard's
    local indices; rows outside the shard (and the row==N pad slots)
    drop, so each device writes only rows it owns."""
    n_local = dev.shape[0]
    lrows = rows - jax.lax.axis_index(NODE_AXIS_NAME) * n_local
    ok = (lrows >= 0) & (lrows < n_local)
    lrows = jnp.where(ok, lrows, n_local)
    return dev.at[lrows].set(vals, mode="drop")


def _add_rows_local(dev, rows, vals):
    """Shard-local row ADD, `_set_rows_local`'s twin for a matrix that
    holds more than the host knows (DeviceWorld's chained update)."""
    return _apply_deltas_local(
        dev, rows, vals, jax.lax.axis_index(NODE_AXIS_NAME) * dev.shape[0])


def _add_rank1_local(dev, rows, counts, demand):
    """Shard-local twin of the native scatter_add_rank1 export:
    dev[rows[k]] += counts[k] * demand, rows translated per shard."""
    n_local = dev.shape[0]
    lrows = rows - jax.lax.axis_index(NODE_AXIS_NAME) * n_local
    ok = (lrows >= 0) & (lrows < n_local)
    lrows = jnp.where(ok, lrows, n_local)
    vals = counts[:, None].astype(jnp.float32) * demand
    return dev.at[lrows].add(vals, mode="drop")


def serving_update_fns(mesh: Mesh):
    """Jitted (set_rows, add_rank1, add_rows) scatters for a node-sharded
    [N, R] resident matrix (parallel.world.DeviceWorld).  Rows/values are
    replicated operands (KBs); the sharded matrix never moves — each
    shard scatters its own rows, no cross-device gather of the operand."""
    key = ("update", mesh_key(mesh))
    fns = _SERVING_FN_CACHE.get(key)
    if fns is None:
        NS = NODE_AXIS_NAME
        set_fn = jax.jit(jax.shard_map(
            _set_rows_local, mesh=mesh,
            in_specs=(P(NS, None), P(None), P(None, None)),
            out_specs=P(NS, None), check_vma=False))
        add_fn = jax.jit(jax.shard_map(
            _add_rank1_local, mesh=mesh,
            in_specs=(P(NS, None), P(None), P(None), P(None)),
            out_specs=P(NS, None), check_vma=False))
        add_rows_fn = jax.jit(jax.shard_map(
            _add_rows_local, mesh=mesh,
            in_specs=(P(NS, None), P(None), P(None, None)),
            out_specs=P(NS, None), check_vma=False))
        recompile.register("sharded.serving_set", set_fn)
        recompile.register("sharded.serving_add", add_fn)
        recompile.register("sharded.serving_add_rows", add_rows_fn)
        fns = (set_fn, add_fn, add_rows_fn)
        _SERVING_FN_CACHE[key] = fns
    return fns


def _field_specs_batched() -> dict:
    """PartitionSpecs for the per-eval field dict (PlaceInputs minus the
    shared capacity/used basis), leading eval batch axis unsharded on
    the serving mesh (the eval axis is a chained scan)."""
    specs = {}
    for name, axis in _NODE_AXIS.items():
        if name in ("capacity", "used"):
            continue
        parts = [None] * _NDIM[name]
        if axis is not None:
            parts[axis] = NODE_AXIS_NAME
        specs[name] = P(*([None] + parts))
    return specs


_SERVING_FN_CACHE: dict = {}

# Loan/adopt protocol for every donate_argnums jit in this module (the
# donation-safety checker fails an undeclared donating jit).  `fn` is
# the bulk serving kernel built in place_bulk_batch_sharded and
# registered as "sharded.bulk".
_DONATE_PROTOCOL = {
    "fn":
        "arg 1 (used0) is the loaned usage basis: the engine takes it "
        "via world.loan_basis() before dispatch, never reads the "
        "loaned buffer in flight, and adopts the psum-merged carry "
        "(used_tot) via world.adopt_basis() — or invalidates the "
        "basis when the dispatch fails",
}


def place_batch_sharded(mesh: Mesh, capacity, used0, fields: dict,
                        delta_rows, delta_vals,
                        spread_algorithm: bool = False):
    """Chained scan-path batch (engine _dispatch_group) over the serving
    mesh.  `fields`: per-eval PlaceInputs fields (minus capacity/used,
    which ride separately as the batch-shared basis), each with a leading
    E axis; `delta_rows` i32[E, D] / `delta_vals` f32[E, D, R] are each
    eval's sparse usage adjustments (row == N drops).  Returns (packed
    f32[E, S, 5+2K] — the engine's unpack_outputs layout — and the
    node-sharded final usage)."""
    from nomad_tpu.ops.place import _pack_outputs

    def body(cap, u0, flds, drows, dvals):
        idx = jax.lax.axis_index(NODE_AXIS_NAME)
        n_local = cap.shape[0]
        shard_offset = idx * n_local

        def eval_step(used, ev):
            one, dr, dv = ev
            used = _apply_deltas_local(used, dr, dv, shard_offset)
            inp = PlaceInputs(capacity=cap, used=used, **one)
            S = inp.demand.shape[0]
            step = functools.partial(_place_step_sharded, inp,
                                     spread_algorithm, shard_offset)
            # all S steps, pads and inert evals included: the bound of
            # ops.place._scan_slots is not taken here yet (no benchmark
            # cell runs the mesh; ROADMAP S33)
            carry, outs = jax.lax.scan(step, place_carry0(inp, used),
                                       jnp.arange(S))
            return carry[0], _pack_outputs(*outs)

        used_final, packed = jax.lax.scan(eval_step, u0,
                                          (flds, drows, dvals))
        return packed, used_final

    NS = NODE_AXIS_NAME
    key = ("scan", mesh_key(mesh), spread_algorithm)
    fn = _SERVING_FN_CACHE.get(key)
    if fn is None:
        in_specs = (P(NS, None), P(NS, None),
                    _field_specs_batched(), P(None, None),
                    P(None, None, None))
        out_specs = (P(None, None, None), P(NS, None))
        fn = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                                   out_specs=out_specs, check_vma=False))
        recompile.register("sharded.scan", fn)
        _SERVING_FN_CACHE[key] = fn
    return fn(capacity, used0, fields,
              _put_host(mesh, P(None, None), delta_rows),
              _put_host(mesh, P(None, None, None), delta_vals))


def place_bulk_batch_sharded(mesh: Mesh, capacity, used0,
                             feasible, affinity, has_affinity, desired,
                             penalty, coll0, demand, count,
                             delta_rows, delta_vals,
                             spread_algorithm: bool = False,
                             max_waves: int = 65536,
                             fill_grid: int = 64):
    """Laned chained bulk wavefront batch (engine place_bulk) over the
    2-D ('node_shard','wave') mesh — the C2M-scale multi-chip path.

    Every per-eval input carries leading [W, E] axes, W the mesh's wave
    extent: lane w holds its own chained eval sequence (the engine bins
    requests into lanes by wave_key; pad slots ride with count == 0).
    Node-axis fields are [W, E, N] sharded P('wave', None, 'node_shard');
    scalars [W, E].  Within a lane each wave computes its [N_local, M]
    scoring/fill grid on the shard, then resolves the global greedy
    order from two all_gathered [N] vectors (wave-start score +
    per-node run), every device in the lane's column deriving the
    identical per-node placement so only its own rows mutate.  Lanes
    never communicate until the final basis merge:
    `used_final = u0 + psum_over_wave(lane_delta)`.

    Returns (assign i32[W, E, N], scores f32[W, E, N], placed/n_eval/
    n_exh/waves i32[W, E] each, used_final node-sharded).  The `used0`
    buffer is donated to the kernel — the caller hands over its
    resident basis and adopts `used_final` in its place
    (world.loan_basis / adopt_basis), so the steady state ships zero
    basis bytes."""
    from nomad_tpu.ops.place import (
        _bulk_scores,
        bulk_run_lengths as _bulk_run_lengths,
        bulk_wave_grid as _bulk_wave_grid,
    )

    def body(cap, u0, feas_l, aff_l, hasa_l, des_l, pen_l, coll_l,
             dem_l, cnt_l, drows_l, dvals_l):
        idx = jax.lax.axis_index(NODE_AXIS_NAME)
        n_local = cap.shape[0]
        shard_offset = idx * n_local
        # lane-local blocks arrive [1, E, ...]: drop the unit wave axis
        feas_e, aff_e, pen_e, coll_e = (
            feas_l[0], aff_l[0], pen_l[0], coll_l[0])
        hasa_e, des_e, cnt_e = hasa_l[0], des_l[0], cnt_l[0]
        dem_e, drows, dvals = dem_l[0], drows_l[0], dvals_l[0]

        def eval_step(carry, ev):
            used_in, exact = carry
            feasible, affinity, has_aff, desired, penalty, coll0, \
                demand, count, dr, dv = ev
            # deltas are scoped to THIS eval (backed out of the carry
            # below), matching ops.place._place_bulk_batch: uncommitted
            # stops of one eval never leak into another's scoring
            used = _apply_deltas_local(used_in, dr, dv, shard_offset)
            delta_local = used - used_in
            desired_f = desired.astype(jnp.float32)

            def cond(c):
                u, coll, placed, assign, stuck, waves = c
                return (placed < count) & ~stuck & (waves < max_waves)

            def wave(c):
                u, coll, placed, assign, stuck, waves = c
                # the shared single-source-of-truth scoring grid
                # (ops.place.bulk_wave_grid) on this shard's rows; only
                # the reductions/selection go through collectives
                ms, fits_m, score_m = _bulk_wave_grid(
                    cap, u, demand, feasible, affinity, has_aff,
                    desired_f, penalty, coll, spread_algorithm,
                    fill_grid)

                fits = fits_m[:, 0]
                cur = jnp.where(fits, score_m[:, 0], -jnp.inf)
                any_fit = jax.lax.pmax(
                    jnp.any(fits).astype(jnp.int32), NODE_AXIS_NAME) > 0
                s_star = jax.lax.pmax(
                    jnp.max(jnp.where(fits_m[:, 1], score_m[:, 1],
                                      -jnp.inf)), NODE_AXIS_NAME)
                # global top-2 of cur: local top-2, gathered
                l2 = jax.lax.top_k(cur, 2)[0]
                g2 = jax.lax.top_k(
                    jax.lax.all_gather(l2, NODE_AXIS_NAME, tiled=True),
                    2)[0]
                gmax, gsecond = g2[0], g2[1]
                strict = fits & (cur > s_star)
                use_strict = jax.lax.pmax(
                    jnp.any(strict).astype(jnp.int32), NODE_AXIS_NAME) > 0
                tie = fits & (cur == gmax)
                wv = jnp.where(use_strict, strict, tie)
                second = jnp.where(cur == gmax, gsecond, gmax)
                run = _bulk_run_lengths(ms, fits_m, score_m, second)
                base = jnp.where(wv, run, 0).astype(jnp.int32)

                # global greedy order from gathered [N] vectors; every
                # shard computes the identical per-node allocation and
                # slices out its own rows
                cur_g = jax.lax.all_gather(cur, NODE_AXIS_NAME,
                                           tiled=True)
                base_g = jax.lax.all_gather(base, NODE_AXIS_NAME,
                                            tiled=True)
                wave_g = base_g > 0
                order = jnp.argsort(jnp.where(wave_g, -cur_g, jnp.inf))
                base_sorted = base_g[order]
                prefix = jnp.cumsum(base_sorted) - base_sorted
                remaining = count - placed
                alloc_sorted = jnp.clip(remaining - prefix, 0,
                                        base_sorted)
                per_node_g = jnp.zeros(base_g.shape[0], jnp.int32) \
                    .at[order].set(alloc_sorted)
                per_node = jax.lax.dynamic_slice(
                    per_node_g, (shard_offset,), (n_local,))

                u = u + per_node[:, None].astype(jnp.float32) * demand
                coll = coll + per_node
                assign = assign + per_node
                placed = placed + jnp.sum(per_node_g)
                return (u, coll, placed, assign, ~any_fit, waves + 1)

            c0 = (used, coll0, jnp.int32(0),
                  jnp.zeros(n_local, jnp.int32), jnp.array(False),
                  jnp.int32(0))
            used_f, coll_f, placed, assign, _, waves = \
                jax.lax.while_loop(cond, wave, c0)

            # final scores + metrics via the shared scoring stack
            # (ops.place._bulk_scores on local rows; counts via psum)
            scores, fits_f = _bulk_scores(
                cap, used_f, demand, feasible, affinity, has_aff,
                desired, penalty, coll_f, spread_algorithm)
            n_eval = jax.lax.psum(jnp.sum(feasible), NODE_AXIS_NAME)
            n_exh = jax.lax.psum(jnp.sum(feasible & ~fits_f),
                                 NODE_AXIS_NAME)
            out = (assign, scores, placed.astype(jnp.int32),
                   n_eval.astype(jnp.int32), n_exh.astype(jnp.int32),
                   waves.astype(jnp.int32))
            # two carries (see ops.place._place_bulk_batch exact_out):
            # the chain carry keeps the wavefront's incremental adds
            # (scoring parity with the single-device kernel), the exact
            # carry is the rank-1 reconstruction the adopted basis uses
            exact = exact + assign[:, None].astype(jnp.float32) * demand
            return (used_f - delta_local, exact), out

        (used_final, exact_final), outs = jax.lax.scan(
            eval_step, (u0, u0),
            (feas_e, aff_e, hasa_e, des_e, pen_e, coll_e, dem_e, cnt_e,
             drows, dvals))
        # merge lanes: each lane chained independently against the
        # shared basis; the combined usage is the basis plus every
        # lane's net rank-1 placement delta (the psum result is
        # identical on all wave columns, satisfying the replicated
        # out_spec; inactive lanes contribute exact zeros)
        used_tot = u0 + jax.lax.psum(exact_final - u0, WAVE_AXIS_NAME)
        assign, scores, placed, n_eval, n_exh, waves = outs
        return (assign[None], scores[None], placed[None], n_eval[None],
                n_exh[None], waves[None], used_tot)

    # Loan/adopt protocol for the donating jit below (`fn`, registered
    # as "sharded.bulk"): arg 1 (used0) is the loaned usage basis —
    # world.loan_basis() before dispatch, no reads of the loaned buffer
    # until world.adopt_basis(used_tot) lands the psum-merged carry.
    NS, W = NODE_AXIS_NAME, WAVE_AXIS_NAME
    in_specs = (P(NS, None), P(NS, None),
                P(W, None, NS), P(W, None, NS), P(W, None), P(W, None),
                P(W, None, NS), P(W, None, NS), P(W, None, None),
                P(W, None), P(W, None, None), P(W, None, None, None))
    key = ("bulk", mesh_key(mesh), spread_algorithm, max_waves,
           fill_grid)
    fn = _SERVING_FN_CACHE.get(key)
    if fn is None:
        out_specs = (P(W, None, NS), P(W, None, NS), P(W, None),
                     P(W, None), P(W, None), P(W, None), P(NS, None))
        mapped = jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                               out_specs=out_specs, check_vma=False)
        # donate_argnums=(1,): used0 and used_final share shape [N, R]
        # and sharding P('node_shard', None), so XLA aliases the carry
        # in place of a fresh allocation + a host re-upload next wave
        fn = jax.jit(mapped, donate_argnums=(1,))
        recompile.register("sharded.bulk", fn)
        _SERVING_FN_CACHE[key] = fn
    args = [capacity, used0, feasible, affinity, has_affinity, desired,
            penalty, coll0, demand, count, delta_rows, delta_vals]
    return fn(*[_put_host(mesh, spec, a) for spec, a in zip(in_specs, args)])
