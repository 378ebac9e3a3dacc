"""The dense placement engine.

One jitted loop over an evaluation's placement slots places every missing
allocation (`_scan_slots`: it runs to the eval's last active slot, not to
the end of the padded slot axis, and an eval with no active slot runs no
step): each step scores ALL candidate nodes at once (feasibility mask ->
resource fit -> binpack/spread fit score -> anti-affinity /
reschedule-penalty / affinity / spread scoring -> normalization -> masked
argmax) and the carry threads the proposed usage matrix, per-taskgroup
co-placement counts, per-spread-attribute value counts and, for
distinct_hosts and distinct_property, the rows a scope has taken and its
allocations a value, so sequential placement coupling (reference
scheduler/context.go:173-210 ProposedAllocs) is preserved.

This single kernel replaces the reference's entire iterator stack for one
eval (scheduler/stack.go:344-439 GenericStack.Select and everything it
pulls: feasible.go checkers, rank.go BinPackIterator/scoring iterators,
spread.go SpreadIterator, select.go Limit/MaxScore).  Candidate subsampling
(log2-n limits, power-of-two-choices, stack.go:79-92) is intentionally
absent: the TPU scores every node densely.

Tie-breaking: the reference shuffles nodes with a seeded shuffle and takes
the first strict maximum (scheduler/util.go:464, select.go:94-116); here
argmax takes the lowest node row among equals.  Deterministic, documented
deviation — score values are parity-tested, selections may differ on exact
ties.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from nomad_tpu.analysis import recompile
from nomad_tpu.ops.fit import score_fit

# recompile-budget (nomad_tpu.analysis): every jitted kernel defined here
# is registered with the recompile registry (see module tail) so the
# bench can fail a run whose jit caches grow after warmup
_RECOMPILE_TRACKED = True

TOP_K = 5  # score_meta entries kept per placement (structs.go:10341 kheap)
# m-grid bound for the bulk kernel's per-node fill-run length: a run
# longer than the grid just continues next wave, so this trades wave
# count against the [N, M] grid's per-wave compute (the grid is the
# dominant op in a wave's body)
_FILL_GRID = 64

# The grid width is bucketed: a wave whose largest eval places count
# instances never fills a run past count, so the [N, M] grid beyond
# M = count is pure wasted compute — at the C2M-1M shape (count = 10)
# the full 64-wide grid does 4x the work of the 16-wide one for
# identical placements (runs longer than M continue next wave; the
# wavefront is M-invariant).  Two buckets keep the compile-variant
# count at 2x, covered by warmup.
FILL_GRID_BUCKETS = (16, _FILL_GRID)


def fill_grid_for(max_count: int) -> int:
    """Smallest fill-grid bucket that lets the wave's longest possible
    run complete in one wave (capped at _FILL_GRID)."""
    for m in FILL_GRID_BUCKETS:
        if max_count <= m:
            return m
    return _FILL_GRID


@jax.tree_util.register_dataclass
@dataclass
class PlaceInputs:
    """Dense inputs for one evaluation's placement pass.

    Axes: N nodes, G task groups, S placement slots, K spread attributes,
    V spread attribute values (all padded).
    """
    capacity: jax.Array        # f32[N, R]
    used: jax.Array            # f32[N, R]  proposed-usage basis
    feasible: jax.Array        # bool[G, N]
    affinity: jax.Array        # f32[G, N]
    has_affinity: jax.Array    # bool[G]
    desired_count: jax.Array   # i32[G]
    penalty: jax.Array         # bool[G, N]
    tg_count: jax.Array        # i32[G, N] existing co-placed (job, tg) allocs
    # spread tensors (K may be 0)
    spread_vidx: jax.Array     # i32[G, K, N] value index per node (V = missing)
    spread_desired: jax.Array  # f32[G, K, V+1] desired counts, -1 = no target
    spread_targeted: jax.Array # bool[G, K] targets specified vs even-spread
    spread_wfrac: jax.Array    # f32[G, K] weight / sum(|weights|)
    spread_counts: jax.Array   # f32[G, K, V+1] initial per-value counts
    spread_active: jax.Array   # bool[G, K]
    # per-(group, node) placement capacity: how many instances of the
    # group this eval may still put on the node (-1 = unlimited).  Models
    # consumable per-node resources the R-dims don't cover — device
    # instances (reference deviceAllocator free counts) — as a carry.
    place_cap: jax.Array       # i32[G, N]
    # the `devices` scorer (rank.go: sum of the matched affinity weights
    # of the group's device asks over the sum of |weight|, appended
    # whenever that sum is not 0, a zero score included).  A group
    # without device affinities has `has_dev` false and the scorer off.
    dev_score: jax.Array       # f32[G, N]
    has_dev: jax.Array         # bool[G]
    # distinct_hosts and distinct_property (feasible.go DistinctHosts- and
    # DistinctPropertyIterator, propertyset.go) as a carry: H host scopes
    # and P property constraints, both 0 for a job with neither.  A
    # job-level constraint is one scope that every group checks and
    # marks, a group-level one its group's own.  Values are indexed as
    # spread's are (W = the node lacks the attribute, and takes none).
    hosts_taken: jax.Array     # bool[H, N] rows that hold one of the scope
    hosts_of: jax.Array        # bool[G, H]
    prop_vidx: jax.Array       # i32[P, N]
    prop_counts: jax.Array     # i32[P, W+1] allocations of the scope a value
    prop_limit: jax.Array      # i32[P]
    prop_of: jax.Array         # bool[G, P]
    # slots
    demand: jax.Array          # f32[S, R]
    slot_tg: jax.Array         # i32[S]
    slot_active: jax.Array     # bool[S]


@jax.tree_util.register_dataclass
@dataclass
class PlaceResult:
    node: jax.Array            # i32[S] selected node row, -1 = no placement
    score: jax.Array           # f32[S] final normalized score of the pick
    fit_score: jax.Array       # f32[S] raw binpack/spread component of the pick
    nodes_evaluated: jax.Array # i32[S] feasible nodes considered
    nodes_exhausted: jax.Array # i32[S] feasible but resource-exhausted nodes
    top_nodes: jax.Array       # i32[S, TOP_K]
    top_scores: jax.Array      # f32[S, TOP_K]
    used: jax.Array            # f32[N, R] final proposed usage


def _spread_boost(inp: PlaceInputs, g: jax.Array, counts: jax.Array) -> jax.Array:
    """f32[N]: total spread score per node for task group `g` given current
    per-value counts f32[K, V+1] (reference scheduler/spread.go:116-272)."""
    vidx = inp.spread_vidx[g]          # i32[K, N]
    desired = inp.spread_desired[g]    # f32[K, V+1]
    targeted = inp.spread_targeted[g]  # bool[K]
    wfrac = inp.spread_wfrac[g]        # f32[K]
    active = inp.spread_active[g]      # bool[K]
    K, Vp1 = desired.shape
    V = Vp1 - 1                        # last slot = "missing attribute"

    missing = vidx >= V                                    # bool[K, N]
    safe_idx = jnp.minimum(vidx, V)
    cur = jnp.take_along_axis(counts, safe_idx, axis=1)    # f32[K, N]
    des = jnp.take_along_axis(desired, safe_idx, axis=1)   # f32[K, N]

    # --- targeted spread: ((desired - (used+1)) / desired) * weight_frac
    has_target = des >= 0.0
    t_boost = jnp.where(
        missing, -1.0,                                     # attr build error
        jnp.where(has_target,
                  (des - (cur + 1.0)) / jnp.maximum(des, 1e-9) * wfrac[:, None],
                  -1.0))                                   # no target: flat -1

    # --- even spread: boost from delta vs min/max of *placed* values
    placed = counts[:, :V] > 0.0                           # bool[K, V]
    any_placed = jnp.any(placed, axis=1)                   # bool[K]
    big = jnp.float32(3.4e38)
    minc = jnp.min(jnp.where(placed, counts[:, :V], big), axis=1)   # f32[K]
    maxc = jnp.max(jnp.where(placed, counts[:, :V], -big), axis=1)
    minc_ = jnp.maximum(minc, 1e-9)
    at_min = cur == minc[:, None]
    e_boost = jnp.where(
        ~at_min, (minc[:, None] - cur) / minc_[:, None],
        jnp.where((minc == maxc)[:, None], -1.0,
                  ((maxc - minc) / minc_)[:, None]))
    e_boost = jnp.where(missing, -1.0, e_boost)
    e_boost = jnp.where(any_placed[:, None], e_boost, 0.0)  # empty map -> 0

    boost = jnp.where(targeted[:, None], t_boost, e_boost)  # f32[K, N]
    return jnp.sum(jnp.where(active[:, None], boost, 0.0), axis=0)


def place_carry0(inp: PlaceInputs, used: jax.Array):
    """What a scan over one eval's slots starts from."""
    return (used, inp.tg_count, inp.spread_counts, inp.place_cap,
            inp.hosts_taken, inp.prop_counts)


def distinct_open(inp: PlaceInputs, g: jax.Array, hosts_taken: jax.Array,
                  prop_counts: jax.Array) -> jax.Array:
    """bool[N]: the rows distinct_hosts and distinct_property leave open
    to group `g`: no allocation of a host scope of the group there, and
    every property of the group set there with its value under the limit.
    Shared with the node-sharded step (the counts are replicated)."""
    open_ = jnp.ones(inp.feasible.shape[1], bool)
    if hosts_taken.shape[0]:
        open_ &= ~jnp.any(inp.hosts_of[g][:, None] & hosts_taken, axis=0)
    if prop_counts.shape[0]:
        W = prop_counts.shape[1] - 1
        cur = jnp.take_along_axis(
            prop_counts, jnp.minimum(inp.prop_vidx, W), axis=1)   # [P, N]
        full = (inp.prop_vidx >= W) | (cur >= inp.prop_limit[:, None])
        open_ &= ~jnp.any(inp.prop_of[g][:, None] & full, axis=0)
    return open_


def prop_counts_add(inp: PlaceInputs, g: jax.Array, prop_counts: jax.Array,
                    v: jax.Array, ok: jax.Array) -> jax.Array:
    """The counts with one more of group `g` on a node of values `v`
    i32[P] (W = missing), where `ok`."""
    Wp1 = prop_counts.shape[1]
    hit = jax.nn.one_hot(jnp.minimum(v, Wp1 - 1), Wp1, dtype=jnp.int32)
    return prop_counts + hit * (inp.prop_of[g] & (v < Wp1 - 1)
                                & ok)[:, None]


def _place_step(inp: PlaceInputs, spread_algorithm: bool, carry, slot):
    used, tg_count, spread_counts, place_cap, hosts_taken, prop_counts = carry
    g = inp.slot_tg[slot]
    d = inp.demand[slot]
    active = inp.slot_active[slot]

    feas = inp.feasible[g] & (place_cap[g] != 0) \
        & distinct_open(inp, g, hosts_taken, prop_counts)
    util = used + d
    fits = jnp.all(util <= inp.capacity, axis=-1) & feas

    # --- scoring stack (normalization = mean over appended scorers only,
    # reference rank.go ScoreNormalizationIterator)
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

    sboost = _spread_boost(inp, g, spread_counts[g])
    sb_on = jnp.any(inp.spread_active[g]) & (sboost != 0.0)
    total = total + jnp.where(sb_on, sboost, 0.0)
    n_scorers = n_scorers + sb_on

    dev_on = inp.has_dev[g]
    total = total + jnp.where(dev_on, inp.dev_score[g], 0.0)
    n_scorers = n_scorers + dev_on

    final = total / n_scorers
    masked = jnp.where(fits & active, final, -jnp.inf)

    sel = jnp.argmax(masked)
    ok = masked[sel] > -jnp.inf

    # --- carry updates
    sel_onehot = (jnp.arange(used.shape[0]) == sel) & ok
    used = used + jnp.where(sel_onehot[:, None], d, 0.0)
    tg_count = tg_count.at[g, sel].add(jnp.where(ok, 1, 0))
    place_cap = place_cap.at[g, sel].add(
        jnp.where(ok & (place_cap[g, sel] > 0), -1, 0))
    v = inp.spread_vidx[g, :, sel]                      # i32[K]
    Vp1 = spread_counts.shape[-1]
    upd = jax.nn.one_hot(jnp.minimum(v, Vp1 - 1), Vp1, dtype=spread_counts.dtype)
    upd = upd * (inp.spread_active[g] & (v < Vp1 - 1))[:, None] * ok
    spread_counts = spread_counts.at[g].add(upd)
    hosts_taken = hosts_taken | (inp.hosts_of[g][:, None] & sel_onehot[None, :])
    prop_counts = prop_counts_add(inp, g, prop_counts, inp.prop_vidx[:, sel],
                                  ok)

    top_scores, top_nodes = jax.lax.top_k(masked, TOP_K)
    out = (
        jnp.where(ok, sel, -1).astype(jnp.int32),
        jnp.where(ok, masked[sel], 0.0),
        jnp.where(ok, fit_score[sel], 0.0),
        jnp.sum(feas & active).astype(jnp.int32),
        jnp.sum(feas & ~fits & active).astype(jnp.int32),
        top_nodes.astype(jnp.int32),
        top_scores,
    )
    return (used, tg_count, spread_counts, place_cap, hosts_taken,
            prop_counts), out


def _pack_outputs(node, score, fit_s, n_eval, n_exh, top_n, top_s) -> jax.Array:
    """Pack the per-slot outputs into ONE f32 array [..., S, 5 + 2*TOP_K]
    so the host fetches a single leaf: every device->host leaf is its
    own transfer with its own fixed cost, and the engine's resolve path
    unpacks one buffer.  Integers are VALUE-encoded as floats (exact
    below 2^24) — bitcasting them would produce denormals that TPU
    hardware flushes to zero."""
    as_f = lambda x: x.astype(jnp.float32)
    return jnp.concatenate([
        as_f(node)[..., None], score[..., None], fit_s[..., None],
        as_f(n_eval)[..., None], as_f(n_exh)[..., None],
        as_f(top_n), top_s], axis=-1)


def unpack_outputs(packed: np.ndarray):
    """Host-side inverse of _pack_outputs.
    packed: f32[..., S, 5 + 2*TOP_K]."""
    as_i = lambda x: np.rint(x).astype(np.int32)
    node = as_i(packed[..., 0])
    score = packed[..., 1]
    fit_s = packed[..., 2]
    n_eval = as_i(packed[..., 3])
    n_exh = as_i(packed[..., 4])
    top_n = as_i(packed[..., 5:5 + TOP_K])
    top_s = packed[..., 5 + TOP_K:5 + 2 * TOP_K]
    return node, score, fit_s, n_eval, n_exh, top_n, top_s


def _scan_slots(step, carry0, slot_active: jax.Array):
    """`lax.scan(step, carry0, arange(S))` cut at the eval's last active
    slot: the loop runs n = (index of the last active slot) + 1 steps, read
    from `slot_active` on the device, so a 300-slot service in the 1,024
    bucket runs 300 steps and an eval that only pads a batch runs none.
    The rows above n hold what an inactive step writes (it leaves the
    carry as it found it, every update being gated by `ok`, and emits no
    node, zero scores and counts, and `lax.top_k` of an all -inf vector),
    so carry and outputs are the full scan's, padded rows included.  The
    bound is the last active index and not the count of active slots: an
    inactive slot under it is stepped and masked, so callers need not pad
    a prefix.  Returns (carry, the seven per-slot outputs at [S, ...])."""
    S = slot_active.shape[0]
    n = jnp.max(jnp.where(slot_active, jnp.arange(1, S + 1), 0), initial=0)
    outs0 = (
        jnp.full(S, -1, jnp.int32),
        jnp.zeros(S, jnp.float32),
        jnp.zeros(S, jnp.float32),
        jnp.zeros(S, jnp.int32),
        jnp.zeros(S, jnp.int32),
        jnp.broadcast_to(jnp.arange(TOP_K, dtype=jnp.int32), (S, TOP_K)),
        jnp.full((S, TOP_K), -jnp.inf, jnp.float32),
    )

    def body(i, c):
        carry, outs = c
        carry, out = step(carry, i)
        return carry, tuple(o.at[i].set(v) for o, v in zip(outs, out))

    return jax.lax.fori_loop(0, n, body, (carry0, outs0))


@functools.partial(jax.jit, static_argnames=("spread_algorithm",))
def place_eval_packed_jit(inp: PlaceInputs, spread_algorithm: bool = False):
    """Single-eval kernel with packed output: returns (f32[S, 5+2K]
    packed outputs, f32[N, R] final usage)."""
    step = functools.partial(_place_step, inp, spread_algorithm)
    carry, outs = _scan_slots(step, place_carry0(inp, inp.used),
                              inp.slot_active)
    return _pack_outputs(*outs), carry[0]


@functools.partial(jax.jit, static_argnames=("spread_algorithm",))
def place_eval_jit(inp: PlaceInputs, spread_algorithm: bool = False) -> PlaceResult:
    """Place all slots of one evaluation.  Shapes are static; callers bucket
    N/G/S/K/V so the jit cache stays small."""
    step = functools.partial(_place_step, inp, spread_algorithm)
    carry, outs = _scan_slots(step, place_carry0(inp, inp.used),
                              inp.slot_active)
    node, score, fit_s, n_eval, n_exh, top_n, top_s = outs
    return PlaceResult(node=node, score=score, fit_score=fit_s,
                       nodes_evaluated=n_eval, nodes_exhausted=n_exh,
                       top_nodes=top_n, top_scores=top_s, used=carry[0])


# --------------------------------------------------------------------------
# Packed H2D transport.
#
# The D2H side ships ONE leaf (_pack_outputs) because every
# device<->host leaf is its own transfer; the H2D side of a batch
# dispatch would otherwise ship an ~18-leaf per-eval-field pytree and
# pay the per-leaf cost 18x.  Here every eval's placement inputs flatten
# into two f32 vectors:
#
#   heavy[Lh]: the G x N-scale tensors (feasibility, affinity, penalty,
#       co-placement counts, place capacity, spread programs).  These are
#       functions of (job version, cluster epoch, existing allocs) and are
#       IDENTICAL across evals of the same job state, so the engine
#       content-addresses them into a device-resident cache — a cache hit
#       ships zero bytes (SURVEY.md §7 "Host<->device latency": keep the
#       big tensors resident, ship only deltas).
#   light[Ll]: the per-eval slot demand/targets and sparse usage deltas —
#       KBs, always shipped, concatenated with the f32[N, R] usage basis
#       into one dyn buffer = ONE device_put per dispatch.
#
# Integers are VALUE-encoded as f32 (exact below 2^24); bitcasting would
# produce denormals that TPU hardware flushes to zero.
# --------------------------------------------------------------------------

def heavy_dims(inp: PlaceInputs):
    """(G, N, K, Vp1, H, P, Wp1) of one eval's inputs."""
    G, N = inp.feasible.shape
    K = inp.spread_wfrac.shape[1]
    Vp1 = inp.spread_desired.shape[2]
    H = inp.hosts_taken.shape[0]
    P, Wp1 = inp.prop_counts.shape
    return G, N, K, Vp1, H, P, Wp1


_HEAVY_FIELDS = ("feasible", "affinity", "penalty", "tg_count", "place_cap",
                 "spread_vidx", "spread_desired", "spread_counts",
                 "has_affinity", "desired_count", "spread_targeted",
                 "spread_wfrac", "spread_active", "dev_score", "has_dev",
                 # empty for a job with no distinct_* constraint: its
                 # block and digest are what they were without these
                 "hosts_taken", "hosts_of", "prop_vidx", "prop_counts",
                 "prop_limit", "prop_of")


def pack_heavy(inp: PlaceInputs) -> np.ndarray:
    """Flatten one eval's G x N-scale tensors into one f32 vector."""
    return np.concatenate(
        [np.asarray(getattr(inp, f), np.float32).ravel()
         for f in _HEAVY_FIELDS])


def heavy_digest(inp: PlaceInputs) -> bytes:
    """Content fingerprint of the heavy block WITHOUT materializing the
    packed array (the common case is a cache hit)."""
    import hashlib
    h = hashlib.blake2b(digest_size=16)
    for f in _HEAVY_FIELDS:
        h.update(np.ascontiguousarray(getattr(inp, f)).tobytes())
    return h.digest()


def _unpack_heavy(h: jax.Array, G: int, N: int, K: int, Vp1: int,
                  H: int, P: int, Wp1: int):
    """In-kernel inverse of pack_heavy; returns a field dict."""
    o = 0
    def take(n, shape):
        nonlocal o
        v = h[o:o + n].reshape(shape)
        o += n
        return v
    return dict(
        feasible=take(G * N, (G, N)) > 0.5,
        affinity=take(G * N, (G, N)),
        penalty=take(G * N, (G, N)) > 0.5,
        tg_count=take(G * N, (G, N)).astype(jnp.int32),
        place_cap=take(G * N, (G, N)).astype(jnp.int32),
        spread_vidx=take(G * K * N, (G, K, N)).astype(jnp.int32),
        spread_desired=take(G * K * Vp1, (G, K, Vp1)),
        spread_counts=take(G * K * Vp1, (G, K, Vp1)),
        has_affinity=take(G, (G,)) > 0.5,
        desired_count=take(G, (G,)).astype(jnp.int32),
        spread_targeted=take(G * K, (G, K)) > 0.5,
        spread_wfrac=take(G * K, (G, K)),
        spread_active=take(G * K, (G, K)) > 0.5,
        dev_score=take(G * N, (G, N)),
        has_dev=take(G, (G,)) > 0.5,
        hosts_taken=take(H * N, (H, N)) > 0.5,
        hosts_of=take(G * H, (G, H)) > 0.5,
        prop_vidx=take(P * N, (P, N)).astype(jnp.int32),
        prop_counts=take(P * Wp1, (P, Wp1)).astype(jnp.int32),
        prop_limit=take(P, (P,)).astype(jnp.int32),
        prop_of=take(G * P, (G, P)) > 0.5,
    )


def light_len(S: int, R: int, D: int) -> int:
    return S * (R + 2) + D * (R + 1)


def pack_light(inp: PlaceInputs, deltas, D: int,
               S: Optional[int] = None) -> np.ndarray:
    """Flatten one eval's slot tensors + sparse usage deltas.  `deltas` is
    [(row, f32[R])]; inactive delta slots encode row = N (dropped by the
    in-kernel scatter's mode='drop').  `S` pads the slot axis to a
    canonical bucket so the engine's compile variants stay fixed
    regardless of per-eval slot counts; the padded slots are inactive and
    lie above the last active one, so the kernel does not step them
    (`_scan_slots`)."""
    S_in, R = inp.demand.shape
    S = S_in if S is None else S
    N = inp.feasible.shape[1]
    out = np.zeros(light_len(S, R, D), np.float32)
    o = 0
    out[o:o + S_in * R] = np.asarray(inp.demand, np.float32).ravel()
    o += S * R
    out[o:o + S_in] = np.asarray(inp.slot_tg, np.float32); o += S
    out[o:o + S_in] = np.asarray(inp.slot_active, np.float32); o += S
    rows = np.full(D, N, np.float32)
    vals = np.zeros((D, R), np.float32)
    for d, (row, vec) in enumerate(deltas[:D]):
        rows[d] = row
        vals[d] = vec
    out[o:o + D] = rows; o += D
    out[o:o + D * R] = vals.ravel()
    return out


def _unpack_light(l: jax.Array, S: int, R: int, D: int):
    o = 0
    def take(n, shape):
        nonlocal o
        v = l[o:o + n].reshape(shape)
        o += n
        return v
    demand = take(S * R, (S, R))
    slot_tg = take(S, (S,)).astype(jnp.int32)
    slot_active = take(S, (S,)) > 0.5
    delta_rows = take(D, (D,)).astype(jnp.int32)
    delta_vals = take(D * R, (D, R))
    return demand, slot_tg, slot_active, delta_rows, delta_vals


@functools.partial(jax.jit, static_argnames=("dims", "spread_algorithm"))
def place_batch_packed_jit(capacity: jax.Array,     # f32[N, R]
                           used0: jax.Array,        # f32[N, R] (device)
                           heavy: tuple,            # E x f32[Lh] (device)
                           dyn: jax.Array,          # f32[E*Ll]
                           dims: tuple,             # heavy_dims + (S, D)
                           spread_algorithm: bool = False):
    """Chained batch placement over the packed transport: `heavy` is a
    tuple of E device-resident per-eval blocks (cache hits ship nothing),
    `used0` the device-resident usage basis (dirty rows shipped by the
    engine), `dyn` the per-eval light blocks.

    Chaining (a `lax.scan` over the eval axis, carrying f32[N, R] usage)
    makes the batch exactly equivalent to sequential worker processing:
    eval e+1 scores against usage that includes eval e's placements, so
    concurrently submitted plans never conflict on resources — any commit
    order of the resulting plans fits, because chained usage is
    cumulative.  This replaces the reference's optimistic
    conflict-then-retry dance (nomad/worker.go:81-85 concurrent workers +
    plan_apply.go partial commit) with a conflict-free device-side
    pipeline; the serialized plan applier still re-validates as defense
    in depth."""
    *hdims, S, D = dims
    R = capacity.shape[1]
    E = len(heavy)
    hstack = jnp.stack(heavy)
    light = dyn.reshape(E, -1)

    def eval_step(used, hl):
        h, l = hl
        f = _unpack_heavy(h, *hdims)
        demand, slot_tg, slot_active, delta_rows, delta_vals = \
            _unpack_light(l, S, R, D)
        used = used.at[delta_rows].add(delta_vals, mode="drop")
        inp = PlaceInputs(capacity=capacity, used=used, demand=demand,
                          slot_tg=slot_tg, slot_active=slot_active, **f)
        step = functools.partial(_place_step, inp, spread_algorithm)
        # each chained eval runs to its own last active slot; an inert
        # pad eval (a light block of zeros) runs no step
        carry, outs = _scan_slots(step, place_carry0(inp, used),
                                  slot_active)
        return carry[0], _pack_outputs(*outs)

    used_final, packed = jax.lax.scan(eval_step, used0, (hstack, light))
    return packed, used_final


def bulk_wave_grid(capacity, used, demand, feasible, affinity,
                   has_affinity, desired_f, penalty, coll,
                   spread_algorithm: bool, fill_grid: int = _FILL_GRID):
    """The [N, M] per-wave fill/scoring grid shared by the single-device
    (`_bulk_loop`) and node-sharded (parallel.sharded) bulk kernels —
    column m is every node's score/fitness with m more instances placed
    on it.  Returns (ms f32[M], fits_m bool[N, M], score_m f32[N, M]).
    Operates on whatever node slice it is given (a shard passes its
    local rows); MUST stay the single source of truth for the bulk
    scoring stack or sharded/single-device placement parity breaks."""
    M = fill_grid
    ms = jnp.arange(1, M + 1, dtype=jnp.float32)
    util_m = used[:, None, :] + ms[None, :, None] * demand    # [N, M, R]
    fits_m = (jnp.all(util_m <= capacity[:, None, :], axis=-1)
              & feasible[:, None])
    fit_m = score_fit(capacity[:, None, :], util_m,
                      spread_algorithm) / 18.0                 # [N, M]
    coll_m = coll[:, None].astype(jnp.float32) + ms[None, :] - 1.0
    total_m = fit_m
    n_sc = jnp.ones_like(fit_m)
    anti_m = -(coll_m + 1.0) / jnp.maximum(desired_f, 1.0)
    has_coll_m = coll_m > 0.0
    total_m = total_m + jnp.where(has_coll_m, anti_m, 0.0)
    n_sc = n_sc + has_coll_m
    total_m = total_m - penalty[:, None]
    n_sc = n_sc + penalty[:, None]
    aff_on = has_affinity & (affinity != 0.0)                  # [N]
    total_m = total_m + jnp.where(aff_on[:, None], affinity[:, None], 0.0)
    n_sc = n_sc + aff_on[:, None]
    return ms, fits_m, total_m / n_sc


def bulk_run_lengths(ms, fits_m, score_m, second):
    """Per-node greedy fill runs from the wave grid: leading m's where
    the node still fits and score_m strictly beats `second` (the best
    wave-start score among the OTHERS); m=1 is the FORCED placement —
    once a node is argmax (by score or lowest-row tie-break), greedy
    places on it regardless of its post-score."""
    ok_m = fits_m & ((score_m > second[:, None]) | (ms[None, :] == 1.0))
    return jnp.sum(jnp.cumprod(ok_m.astype(jnp.int32), axis=1),
                   axis=1).astype(jnp.int32)


def _bulk_scores(capacity, used, demand, feasible, affinity, has_affinity,
                 desired, penalty, coll, spread_algorithm: bool):
    """Composite per-node score for one task group with spreads inactive —
    exactly _place_step's scoring stack minus the spread scorer."""
    util = used + demand
    fits = jnp.all(util <= capacity, axis=-1) & feasible
    fit = score_fit(capacity, util, spread_algorithm) / 18.0
    total = fit
    n_scorers = jnp.ones_like(fit)
    anti = -(coll.astype(jnp.float32) + 1.0) / jnp.maximum(
        jnp.asarray(desired).astype(jnp.float32), 1.0)
    has_coll = coll > 0
    total = total + jnp.where(has_coll, anti, 0.0)
    n_scorers = n_scorers + has_coll
    total = total - penalty
    n_scorers = n_scorers + penalty
    aff_on = has_affinity & (affinity != 0.0)
    total = total + jnp.where(aff_on, affinity, 0.0)
    n_scorers = n_scorers + aff_on
    final = total / n_scorers
    return jnp.where(fits, final, -jnp.inf), fits


def _bulk_loop(capacity, used0, feasible, affinity, has_affinity, desired,
               penalty, coll0, demand, count,
               spread_algorithm: bool, max_waves: int,
               fill_grid: int = _FILL_GRID):
    """The wavefront placement loop shared by the single-eval
    (`place_bulk_jit`) and batched (`_place_bulk_batch`) kernels.
    Places `count` IDENTICAL slots of one task group (spreads inactive)
    in O(waves) device steps instead of O(count) scan steps — the
    C2M-scale path (SURVEY.md §7 "slot-batching smarter than a 100K-step
    scan").

    Exactness vs the sequential scan: scoring is row-independent, so
    sequential greedy fills nodes in contiguous "runs" — it keeps
    picking node i while score_i(after m instances) strictly exceeds
    every other node's current score — and the FIRST placement on a node
    that became argmax (by score or the lowest-row tie-break) is forced
    regardless of its post-score.  Each wave computes, for EVERY node,
    that run length on a vectorized [N, M] fill grid (anti-affinity
    decays linearly, binpack fit rises as the node fills; non-monotone
    dips are honored because the run counts LEADING m's only, and
    `second_i` uses wave-start scores of the others, which can only
    UNDER-count a run — the next wave catches the remainder), then
    places the runs of the active wave set in greedy order
    (score desc, row asc — the argmax tie-break), cumulatively capped by
    the remaining count:

      * strict set (cur > s* = best post-placement score anywhere): the
        nodes greedy provably drains before revisiting anyone;
      * else the tie set (cur == global max): every tied node places at
        least one instance (greedy visits each in row order before any
        score re-enters the tie) plus its fill run.

    A uniform cluster thus fills in O(count / (nodes x per-node run))
    waves — one wave in the common fresh-world case — instead of one
    node-fill per wave.

    max_waves is a runaway guard only — it must exceed any realistic
    count, because packed clusters can degrade to one placement per wave
    and an exhausted guard silently strands unplaced slots.

    Returns (used_f f32[N, R], coll_f i32[N], assign i32[N], placed i32).
    """
    N = capacity.shape[0]
    desired_f = jnp.asarray(desired).astype(jnp.float32)

    def cond(c):
        used, coll, placed, assign, stuck, waves = c
        return (placed < count) & ~stuck & (waves < max_waves)

    def body(c):
        used, coll, placed, assign, stuck, waves = c
        # ONE [N, M] scoring grid per wave (bulk_wave_grid, shared with
        # the node-sharded kernel): m=1 ("place one more now") is the
        # wave-start score, m=2 each node's own "+1" world (scoring is
        # row-independent, so this evaluates the post-placement score of
        # every node at once), and the leading columns give the per-node
        # fill runs.
        ms, fits_m, score_m = bulk_wave_grid(
            capacity, used, demand, feasible, affinity, has_affinity,
            desired_f, penalty, coll, spread_algorithm, fill_grid)

        fits = fits_m[:, 0]
        cur = jnp.where(fits, score_m[:, 0], -jnp.inf)
        any_fit = jnp.any(fits)
        s_star = jnp.max(jnp.where(fits_m[:, 1], score_m[:, 1], -jnp.inf))

        strict = fits & (cur > s_star)
        top2 = jax.lax.top_k(cur, 2)[0]
        tie = fits & (cur == top2[0])
        wave = jnp.where(jnp.any(strict), strict, tie)

        second = jnp.where(cur == top2[0], top2[1], top2[0])   # [N]
        run = bulk_run_lengths(ms, fits_m, score_m, second)

        # greedy-order the wave's runs (score desc, stable -> row asc
        # among ties) and cap cumulatively at the remaining count
        base = jnp.where(wave, run, 0)
        remaining = count - placed
        order = jnp.argsort(jnp.where(wave, -cur, jnp.inf))
        base_sorted = base[order]
        prefix = jnp.cumsum(base_sorted) - base_sorted
        alloc_sorted = jnp.clip(remaining - prefix, 0, base_sorted)
        per_node = jnp.zeros(N, jnp.int32).at[order].set(alloc_sorted)

        used = used + per_node[:, None].astype(jnp.float32) * demand
        coll = coll + per_node
        assign = assign + per_node
        placed = placed + jnp.sum(per_node)
        stuck = ~any_fit
        return (used, coll, placed, assign, stuck, waves + 1)

    c0 = (used0, coll0, jnp.int32(0), jnp.zeros(N, jnp.int32),
          jnp.array(False), jnp.int32(0))
    used_f, coll_f, placed, assign, _, waves = \
        jax.lax.while_loop(cond, body, c0)
    return used_f, coll_f, assign, placed, waves


def _bulk_tail(capacity, used_f, coll_f, feasible, affinity, has_affinity,
               desired, penalty, demand, spread_algorithm: bool):
    """Final scores + eval/exhaustion counts after a wavefront run."""
    final_scores, fits_f = _bulk_scores(capacity, used_f, demand, feasible,
                                        affinity, has_affinity, desired,
                                        penalty, coll_f, spread_algorithm)
    n_eval = jnp.sum(feasible).astype(jnp.int32)
    n_exh = jnp.sum(feasible & ~fits_f).astype(jnp.int32)
    return final_scores, n_eval, n_exh


@functools.partial(jax.jit,
                   static_argnames=("spread_algorithm", "max_waves",
                                    "fill_grid"))
def place_bulk_jit(capacity: jax.Array,    # f32[N, R]
                   used0: jax.Array,       # f32[N, R]
                   feasible: jax.Array,    # bool[N]
                   affinity: jax.Array,    # f32[N]
                   has_affinity: bool,
                   desired: jax.Array,     # i32 scalar (tg count)
                   penalty: jax.Array,     # bool[N]
                   coll0: jax.Array,       # i32[N] existing co-placements
                   demand: jax.Array,      # f32[R]
                   count: jax.Array,       # i32 scalar: instances to place
                   spread_algorithm: bool = False,
                   max_waves: int = 65536,
                   fill_grid: int = _FILL_GRID):
    """Single-eval wavefront placement (see `_bulk_loop` for semantics).
    Reference; not on the serving path: the engine dispatches
    `place_bulk_batch_donate_jit`, and the tests hold that to sequential
    calls of this.

    Returns one packed f32[N, R+3] leaf (one D2H round trip): cols [0,R)
    used, col R assign, col R+1 scores, col R+2 scalars in rows 0-2.
    Integers are value-encoded (exact below 2^24); bitcast encodings
    become denormals that TPU hardware flushes to zero."""
    used_f, coll_f, assign, placed, waves = _bulk_loop(
        capacity, used0, feasible, affinity, has_affinity, desired,
        penalty, coll0, demand, count, spread_algorithm, max_waves,
        fill_grid)
    final_scores, n_eval, n_exh = _bulk_tail(
        capacity, used_f, coll_f, feasible, affinity, has_affinity,
        desired, penalty, demand, spread_algorithm)
    as_f = lambda x: x.astype(jnp.float32)
    scalars = jnp.zeros(capacity.shape[0], jnp.float32) \
        .at[0].set(as_f(placed)).at[1].set(as_f(n_eval)) \
        .at[2].set(as_f(n_exh)).at[3].set(as_f(waves))
    return jnp.concatenate([used_f, as_f(assign)[:, None],
                            final_scores[:, None], scalars[:, None]],
                           axis=-1)


# --- batched bulk transport (same packed single-leaf scheme as
# place_batch_packed_jit: heavy = per-eval node-axis tensors, content-
# addressed device-side; light = per-eval scalars + sparse deltas) -------

def pack_bulk_heavy(feasible, affinity, penalty, coll0) -> np.ndarray:
    """f32[4N]: one bulk eval's node-axis tensors."""
    return np.concatenate([
        np.asarray(feasible, np.float32),
        np.asarray(affinity, np.float32),
        np.asarray(penalty, np.float32),
        np.asarray(coll0, np.float32)])


def bulk_heavy_digest(feasible, affinity, penalty, coll0) -> bytes:
    """Content fingerprint of one bulk request's node-axis tensors.
    All-zero fields (the common fresh-job case: no affinities, no
    penalties, no existing co-placements) hash as a 1-byte marker, and
    bools hash bit-packed — hashing dominated the device-cache HIT path
    at C2M-1M rates otherwise."""
    import hashlib
    h = hashlib.blake2b(digest_size=16)
    h.update(np.packbits(np.asarray(feasible, bool)).tobytes())
    # tag bytes frame each variable-length segment: without them,
    # (full||marker) and (marker||full) byte streams could collide
    for tag, a in ((b"\x01", affinity), (b"\x02", coll0)):
        if np.any(a):
            h.update(tag + b"F")
            h.update(np.ascontiguousarray(a).tobytes())
        else:
            h.update(tag + b"0")
    if np.any(penalty):
        h.update(b"\x03F")
        h.update(np.packbits(np.asarray(penalty, bool)).tobytes())
    else:
        h.update(b"\x030")
    return h.digest()


def bulk_light_len(R: int, D: int) -> int:
    return 3 + R + D * (R + 1)


def pack_bulk_light(has_affinity, desired, count, demand, deltas,
                    N: int, D: int) -> np.ndarray:
    R = demand.shape[0]
    out = np.empty(bulk_light_len(R, D), np.float32)
    out[0] = float(bool(has_affinity))
    out[1] = float(desired)
    out[2] = float(count)
    out[3:3 + R] = np.asarray(demand, np.float32)
    rows = np.full(D, N, np.float32)
    vals = np.zeros((D, R), np.float32)
    for d, (row, vec) in enumerate(deltas[:D]):
        rows[d] = row
        vals[d] = vec
    out[3 + R:3 + R + D] = rows
    out[3 + R + D:] = vals.ravel()
    return out


# sparse bulk output: assignments of a count<=SPARSE_CAP eval fit in
# SPARSE_CAP (row, count) pairs + the scores AT those rows.  A dense
# [N] assign+scores row is ~2N floats of D2H per eval (128 KB at 16K
# rows, x up to 512 evals per dispatch), all of which the host then
# has to scan for the few nonzero rows.
SPARSE_CAP = 128


def _place_bulk_batch(capacity: jax.Array,      # f32[N, R]
                      used0: jax.Array,         # f32[N, R] (device basis)
                      heavy: jax.Array,         # f32[E, 4N] (device, stacked
                      #   OUTSIDE jit: a 128-element tuple argument
                      #   costs ~0.4s/call in pjit arg processing)
                      dyn: jax.Array,           # f32[E*Ll] light blocks
                      D: int,
                      sparse_out: bool = False,
                      spread_algorithm: bool = False,
                      max_waves: int = 65536,
                      fill_grid: int = _FILL_GRID):
    """Chained batch of E wavefront bulk evals in ONE dispatch: a
    `lax.scan` over the eval axis carries the usage matrix, each step
    runs `_bulk_loop` (the O(waves) wavefront placement), so eval e+1
    scores against usage including eval e's placements — identical to
    sequential bulk processing but paying one transfer round trip per
    *batch*.  Each eval's sparse deltas (its own plan's stops /
    preplacements) are scoped to that eval only: they apply before its
    wavefront and are backed out of the carry after, matching the
    serialized bulk path where uncommitted stops of one eval are never
    visible to another (only *placements* chain forward, mirroring the
    engine's in-flight overlay).

    used0 is a DEVICE-RESIDENT basis (engine ships dirty rows only).
    Returns (packed, used_final, used_exact), the last two
    device-resident.  packed per eval: dense [2N+4] (assign[N],
    scores[N], placed/n_eval/n_exh/waves) or, with sparse_out,
    [3*SPARSE_CAP+4] (rows, counts, row_scores, scalars) — for count <=
    SPARSE_CAP only.

    Jitted below as `place_bulk_batch_donate_jit` (donate_argnums=(1,):
    the `used0` carry buffer is donated and the caller adopts
    `used_exact` as the new resident basis via
    world.loan_basis/adopt_basis — the carry never re-uploads).

    `used_exact` is an EXACT rank-1 reconstruction of the basis —
    `used0 + sum_e assign_e * demand_e`, one fused multiply-add per
    eval, the same op sequence as world.apply_rank1_host's scatters.
    The scan's own carry accumulates per-wave partial placements
    (multiple f32 adds per node), which drifts bitwise from the rank-1
    form; scoring must keep the drifted chain carry (placement parity
    with sequential `place_bulk_jit` calls), while the ADOPTED basis
    must stay bitwise in lockstep with the host snapshot that
    apply_rank1_host maintains — hence two carries."""
    N, R = capacity.shape
    E = heavy.shape[0]
    hstack = heavy
    light = dyn.reshape(E, -1)

    def eval_step(carry, hl):
        used, exact = carry
        h, l = hl
        feasible = h[:N] > 0.5
        affinity = h[N:2 * N]
        penalty = h[2 * N:3 * N] > 0.5
        coll0 = h[3 * N:].astype(jnp.int32)
        has_aff = l[0] > 0.5
        desired = l[1].astype(jnp.int32)
        count = l[2].astype(jnp.int32)
        demand = l[3:3 + R]
        delta_rows = l[3 + R:3 + R + D].astype(jnp.int32)
        delta_vals = l[3 + R + D:].reshape(D, R)
        delta_mat = jnp.zeros_like(used).at[delta_rows].add(
            delta_vals, mode="drop")
        used_f, coll_f, assign, placed, waves = _bulk_loop(
            capacity, used + delta_mat, feasible, affinity, has_aff,
            desired, penalty, coll0, demand, count, spread_algorithm,
            max_waves, fill_grid)
        scores, n_eval, n_exh = _bulk_tail(
            capacity, used_f, coll_f, feasible, affinity, has_aff,
            desired, penalty, demand, spread_algorithm)
        as_f = lambda x: x.astype(jnp.float32)
        scalars = jnp.stack([as_f(placed), as_f(n_eval), as_f(n_exh),
                             as_f(waves)])
        if sparse_out:
            # scatter-compaction, NOT top_k: a sort over the node axis
            # per chained eval (~4ms at 16K rows) would dominate the
            # whole wavefront.  Nonzero-assign rows get consecutive
            # slots via a prefix count; everything else lands in the
            # dropped overflow slot.
            mask = assign > 0
            pos = jnp.cumsum(mask) - 1
            tgt = jnp.where(mask, jnp.minimum(pos, SPARSE_CAP),
                            SPARSE_CAP)
            rows_o = jnp.full(SPARSE_CAP + 1, N, jnp.float32) \
                .at[tgt].set(jnp.arange(N, dtype=jnp.float32))
            counts_o = jnp.zeros(SPARSE_CAP + 1, jnp.float32) \
                .at[tgt].set(as_f(assign))
            scores_o = jnp.zeros(SPARSE_CAP + 1, jnp.float32) \
                .at[tgt].set(scores)
            # overflow slot holds junk from every masked-out row; the
            # sliced-off SPARSE_CAP+1 slot absorbs it
            out = jnp.concatenate([
                rows_o[:SPARSE_CAP], counts_o[:SPARSE_CAP],
                scores_o[:SPARSE_CAP], scalars])
        else:
            out = jnp.concatenate([as_f(assign), scores, scalars])
        return (used_f - delta_mat,
                exact + as_f(assign)[:, None] * demand), out

    (used_final, used_exact), packed = jax.lax.scan(
        eval_step, (used0, used0), (hstack, light))
    return packed, used_final, used_exact


place_bulk_batch_donate_jit = jax.jit(
    _place_bulk_batch,
    static_argnames=("D", "sparse_out", "spread_algorithm", "max_waves",
                     "fill_grid"),
    donate_argnums=(1,))

# Loan/adopt protocol for every donate_argnums site in this module
# (the donation-safety checker fails an undeclared donating jit).
_DONATE_PROTOCOL = {
    "place_bulk_batch_donate_jit":
        "arg 1 (used0) is the loaned usage basis: the engine takes it "
        "via world.loan_basis(), must not read it after dispatch, and "
        "adopts the exact carry via world.adopt_basis() — or "
        "invalidates the basis on a failed dispatch",
}


def unpack_bulk_batch(packed: np.ndarray, n_rows: int,
                      sparse: bool = False):
    """Host inverse of _place_bulk_batch's per-eval rows (both
    formats; sparse rows densify host-side — numpy, no transfer):
    returns (assign i32[E, N], scores f32[E, N], placed i32[E],
    n_eval i32[E], n_exh i32[E], waves i32[E]).  Dense scores default
    to -inf at unassigned rows in the sparse format (consumers only
    read scores at assigned rows)."""
    E, W = packed.shape
    s = np.rint(packed[:, -4:]).astype(np.int32)
    if sparse:
        rows = np.rint(packed[:, :SPARSE_CAP]).astype(np.int64)
        counts = np.rint(
            packed[:, SPARSE_CAP:2 * SPARSE_CAP]).astype(np.int32)
        rscores = packed[:, 2 * SPARSE_CAP:3 * SPARSE_CAP]
        assign = np.zeros((E, n_rows), np.int32)
        scores = np.full((E, n_rows), -np.inf, np.float32)
        e_idx = np.repeat(np.arange(E), SPARSE_CAP)
        r_idx = rows.ravel()
        c = counts.ravel()
        keep = c > 0
        assign[e_idx[keep], r_idx[keep]] = c[keep]
        scores[e_idx[keep], r_idx[keep]] = rscores.ravel()[keep]
        return assign, scores, s[:, 0], s[:, 1], s[:, 2], s[:, 3]
    N = (W - 4) // 2
    assign = np.rint(packed[:, :N]).astype(np.int32)
    scores = packed[:, N:2 * N]
    return assign, scores, s[:, 0], s[:, 1], s[:, 2], s[:, 3]


def unpack_bulk(packed: np.ndarray):
    """Host inverse of place_bulk_jit's packed leaf: returns
    (assign i32[N], placed, n_eval, n_exh, scores f32[N], waves,
    used f32[N,R]) — `used` stays last so `*_, used` callers survive
    field additions."""
    R = packed.shape[1] - 3
    used = packed[:, :R]
    assign = np.rint(packed[:, R]).astype(np.int32)
    scores = packed[:, R + 1]
    s = np.rint(packed[:4, R + 2]).astype(np.int32)
    return assign, int(s[0]), int(s[1]), int(s[2]), scores, int(s[3]), used


def place_eval(inp: PlaceInputs, spread_algorithm: bool = False) -> PlaceResult:
    """Convenience host wrapper returning numpy-backed results.
    Reference; not on the serving path: a lone eval is an E=1
    `place_batch_packed_jit` dispatch of the engine, and the tests hold
    that to this.

    All outputs come back in ONE single-leaf D2H transfer (the packed
    output array); the f32[N, R] `used` matrix stays device-resident (no
    caller reads it on host, so fetching it per eval is pure waste).
    """
    packed, used = place_eval_packed_jit(inp,
                                         spread_algorithm=spread_algorithm)
    node, score, fit_s, n_eval, n_exh, top_n, top_s = unpack_outputs(
        jax.device_get(packed))
    return PlaceResult(node=node, score=score, fit_score=fit_s,
                       nodes_evaluated=n_eval, nodes_exhausted=n_exh,
                       top_nodes=top_n, top_scores=top_s, used=used)


# every jit cache in this module, named for the recompile budget: a
# post-warmup growth of any of these is a shape-bucketing regression
recompile.register("place.eval_packed", place_eval_packed_jit)
recompile.register("place.eval", place_eval_jit)
recompile.register("place.batch_packed", place_batch_packed_jit)
recompile.register("place.bulk", place_bulk_jit)
recompile.register("place.bulk_batch_donate", place_bulk_batch_donate_jit)
