"""Preemption selection kernel (reference: scheduler/preemption.go —
PreemptForTaskGroup:198-265, basicResourceDistance:606-624,
scoreForTaskGroup:663-680, filterAndGroupPreemptibleAllocs:682-732).

For EVERY candidate node at once: given the node's preemptible allocations
(padded candidate axis A), greedily pick evictions — lowest priority tier
first, closest resource distance within a tier, distances recomputed as the
remaining ask shrinks — until the freed+remaining resources cover the ask.
The per-node greedy loop is a lax.scan over pick steps; nodes are vmapped,
so one kernel call answers "which nodes become feasible through preemption,
and what would each evict" for the whole cluster.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from nomad_tpu import tracing

BIG = jnp.float32(3.4e38)


def _distance(needed: jax.Array, res: jax.Array) -> jax.Array:
    """basicResourceDistance vectorized over candidates: Euclidean distance
    of (ask - candidate)/ask per dimension, dimensions with zero ask
    contribute 0."""
    ask = needed[None, :]
    coord = jnp.where(ask > 0.0, (ask - res) / jnp.maximum(ask, 1e-9), 0.0)
    return jnp.sqrt(jnp.sum(coord * coord, axis=-1))


def _node_preempt(cand_res, cand_prio, cand_valid, remaining, ask,
                  max_steps: int):
    """Greedy selection for ONE node.

    cand_res:   f32[A, R] resources of preemptible allocs
    cand_prio:  i32[A]    job priority of each candidate
    cand_valid: bool[A]
    remaining:  f32[R]    node capacity minus ALL current allocs
    ask:        f32[R]    the task group's demand
    -> (met: bool, picked: bool[A])
    """
    A = cand_res.shape[0]

    def step(state, _):
        picked, needed, avail, met = state
        open_ = cand_valid & ~picked
        # lowest priority tier among open candidates
        prio_masked = jnp.where(open_, cand_prio, jnp.int32(2**31 - 1))
        min_prio = jnp.min(prio_masked)
        tier = open_ & (cand_prio == min_prio)
        dist = _distance(needed, cand_res)
        dist = jnp.where(tier, dist, BIG)
        pick = jnp.argmin(dist)
        can_pick = jnp.any(tier) & ~met
        onehot = (jnp.arange(A) == pick) & can_pick
        picked = picked | onehot
        freed = jnp.sum(jnp.where(onehot[:, None], cand_res, 0.0), axis=0)
        avail = avail + freed
        needed = needed - freed
        met = met | jnp.all(avail >= ask)
        return (picked, needed, avail, met), None

    state0 = (jnp.zeros(A, bool), ask - jnp.zeros_like(ask), remaining,
              jnp.all(remaining >= ask))
    (picked, _, avail, met), _ = jax.lax.scan(
        step, state0, None, length=max_steps)
    return met, picked, avail


@functools.partial(jax.jit, static_argnames=("max_steps",))
def preempt_for_task_group(
    cand_res: jax.Array,       # f32[N, A, R]
    cand_prio: jax.Array,      # i32[N, A]
    cand_valid: jax.Array,     # bool[N, A]
    remaining: jax.Array,      # f32[N, R] capacity - all current usage
    ask: jax.Array,            # f32[R]
    max_steps: int = 16,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """-> (met bool[N], picked bool[N, A], avail_after f32[N, R]).  Serves
    nothing: the tests' oracle for `preempt_for_task_group_np`."""
    return jax.vmap(
        lambda r, p, v, rem: _node_preempt(r, p, v, rem, ask, max_steps)
    )(cand_res, cand_prio, cand_valid, remaining)


def net_priority(prios) -> float:
    """netPriority heuristic (rank preemption options; preemption.go:745-760):
    max priority + sum/max penalty."""
    if not prios:
        return 0.0
    mx = float(max(prios))
    if mx <= 0:
        return 0.0
    return mx + (float(sum(prios)) / mx)


def preemption_score(net_prio: float) -> float:
    """Logistic preemption score in (0,1), inflection at 2048
    (preemption.go:768-780)."""
    import math
    rate, origin = 0.0048, 2048.0
    return 1.0 / (1.0 + math.exp(rate * (net_prio - origin)))


def preempt_for_task_group_np(cand_res, cand_prio, cand_valid, remaining,
                              ask, max_steps: int = 16):
    """Numpy twin of preempt_for_task_group, and the one that serves
    (`Preemptor.find`, on a scheduler worker's thread): worker threads
    must not issue device work concurrently with the PlacementEngine's
    dispatcher (single-dispatch-thread discipline: one thread owns every
    launch and fetch, so the steady-state transfer guard and the
    donated-carry protocol have one place to hold).  Where the scan runs
    `max_steps` passes over every row, the host runs a pass only over the
    rows that can still pick, and none once no row can: a row that is met
    or out of open candidates is left as it is by every later pass, so
    the arrays returned are the scan's."""
    N, A, R = cand_res.shape
    picked = np.zeros((N, A), bool)
    needed = np.broadcast_to(ask, (N, R)).copy()
    avail = remaining.astype(np.float32).copy()
    met = np.all(avail >= ask, axis=-1)
    INT_MAX = np.int32(2**31 - 1)
    BIGF = np.float32(3.4e38)
    live = np.arange(N)
    for _ in range(max_steps):
        open_ = cand_valid[live] & ~picked[live]
        can_pick = open_.any(axis=1) & ~met[live]
        live, open_ = live[can_pick], open_[can_pick]
        if not len(live):
            break
        with tracing.span("sched.preempt_pass"):
            res, prio = cand_res[live], cand_prio[live]
            min_prio = np.where(open_, prio, INT_MAX).min(axis=1)
            tier = open_ & (prio == min_prio[:, None])
            askp = needed[live][:, None, :]                   # [n, 1, R]
            coord = np.where(askp > 0.0,
                             (askp - res) / np.maximum(askp, 1e-9), 0.0)
            dist = np.sqrt((coord * coord).sum(axis=-1))      # [n, A]
            pick = np.where(tier, dist, BIGF).argmin(axis=1)
            picked[live, pick] = True
            freed = res[np.arange(len(live)), pick]
            avail[live] += freed
            needed[live] -= freed
            met[live] = np.all(avail[live] >= ask, axis=-1)
    return met, picked, avail
