"""Placement engine behavior tests (dense analog of scheduler/rank_test.go,
feasible_test.go, spread_test.go cases)."""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from nomad_tpu import mock
from nomad_tpu.encode import ClusterMatrix
from nomad_tpu.ops import place as P
from nomad_tpu.parallel.engine import get_engine
from nomad_tpu.scheduler.stack import DenseStack
from nomad_tpu.structs.config import SchedulerConfiguration
from nomad_tpu.structs.job import Affinity, Constraint, Operand, Spread, SpreadTarget


def build_world(n_nodes=4, **node_overrides):
    cm = ClusterMatrix()
    nodes = [mock.node(**node_overrides) for _ in range(n_nodes)]
    for nd in nodes:
        cm.upsert_node(nd)
    return cm, nodes


def run_place(cm, job, count=None, allocs_by_tg=None, config=None, penalty=None):
    stack = DenseStack(cm, config)
    groups = [stack.compile_group(job, tg) for tg in job.task_groups]
    slots = []
    for gi, g in enumerate(groups):
        slots += [gi] * (count if count is not None else g.tg.count)
    inp = stack.build_inputs(job, groups, slots, allocs_by_tg or {}, penalty_nodes=penalty)
    eng = get_engine()
    res, ticket = eng.place(cm, inp, spread_algorithm=stack.spread_algorithm)
    eng.complete(ticket)
    return res, inp, slots


def test_basic_placement_fills_all_slots():
    cm, nodes = build_world(4)
    j = mock.job()
    j.task_groups[0].count = 4
    res, inp, slots = run_place(cm, j)
    sel = res.node[:len(slots)]
    assert (sel >= 0).all()
    # anti-affinity should spread the 4 placements over the 4 nodes
    assert len(set(sel.tolist())) == 4


def test_constraint_filters_nodes():
    cm, nodes = build_world(4)
    special = mock.node()
    special.attributes["rack"] = "r1"
    cm.upsert_node(special)
    j = mock.job()
    j.task_groups[0].count = 1
    j.constraints.append(Constraint("${attr.rack}", "r1", Operand.EQ))
    res, _, slots = run_place(cm, j)
    assert res.node[0] == cm.row_of[special.id]


def test_infeasible_yields_minus_one():
    cm, nodes = build_world(2)
    j = mock.job()
    j.constraints.append(Constraint("${attr.rack}", "nope", Operand.EQ))
    res, _, _ = run_place(cm, j, count=1)
    assert res.node[0] == -1
    assert res.nodes_evaluated[0] == 0


def test_resource_exhaustion_sequential_coupling():
    """Placements within one eval consume proposed capacity."""
    cm, nodes = build_world(1)
    j = mock.job()
    j.task_groups[0].tasks[0].resources.cpu = 3000   # node has 4000
    res, _, _ = run_place(cm, j, count=2)
    assert res.node[0] >= 0
    assert res.node[1] == -1                          # second no longer fits
    assert res.nodes_exhausted[1] == 1


def test_binpack_prefers_loaded_node():
    cm, nodes = build_world(2)
    j0 = mock.job()
    a = mock.alloc_for(j0, nodes[0].id)               # 500 MHz on node 0
    cm.upsert_alloc(a)
    j = mock.job()
    res, _, _ = run_place(cm, j, count=1)
    assert res.node[0] == cm.row_of[nodes[0].id]      # binpack packs onto loaded


def test_spread_algorithm_prefers_empty_node():
    cm, nodes = build_world(2)
    j0 = mock.job()
    cm.upsert_alloc(mock.alloc_for(j0, nodes[0].id))
    j = mock.job()
    cfg = SchedulerConfiguration(scheduler_algorithm="spread")
    res, _, _ = run_place(cm, j, count=1, config=cfg)
    assert res.node[0] == cm.row_of[nodes[1].id]


def test_rescheduling_penalty_avoids_previous_node():
    cm, nodes = build_world(2)
    j = mock.job()
    res, _, _ = run_place(cm, j, count=1,
                          penalty={"web": {nodes[0].id}})
    assert res.node[0] == cm.row_of[nodes[1].id]


def test_affinity_attracts():
    cm, nodes = build_world(3)
    target = mock.node()
    target.attributes["rack"] = "fast"
    cm.upsert_node(target)
    j = mock.job()
    j.affinities.append(Affinity("${attr.rack}", "fast", Operand.EQ, weight=100))
    res, _, _ = run_place(cm, j, count=1)
    assert res.node[0] == cm.row_of[target.id]


def test_negative_affinity_repels():
    cm, nodes = build_world(1)
    bad = mock.node()
    bad.attributes["rack"] = "slow"
    cm.upsert_node(bad)
    j = mock.job()
    j.affinities.append(Affinity("${attr.rack}", "slow", Operand.EQ, weight=-100))
    res, _, _ = run_place(cm, j, count=1)
    assert res.node[0] == cm.row_of[nodes[0].id]


def test_targeted_spread_follows_percentages():
    cm = ClusterMatrix()
    r1 = [mock.node() for _ in range(2)]
    r2 = [mock.node() for _ in range(2)]
    for n in r1:
        n.attributes["rack"] = "r1"
        cm.upsert_node(n)
    for n in r2:
        n.attributes["rack"] = "r2"
        cm.upsert_node(n)
    j = mock.job()
    j.task_groups[0].count = 4
    j.task_groups[0].spreads = [Spread("${attr.rack}", 100,
                                       (SpreadTarget("r1", 75), SpreadTarget("r2", 25)))]
    res, _, slots = run_place(cm, j)
    rows_r1 = {cm.row_of[n.id] for n in r1}
    placed_r1 = sum(1 for s in res.node[:4].tolist() if s in rows_r1)
    assert placed_r1 == 3                      # 75% of 4


def test_even_spread_balances():
    cm = ClusterMatrix()
    nodes = []
    for dc in ("dc1", "dc1", "dc2", "dc2"):
        n = mock.node(datacenter=dc)
        nodes.append(n)
        cm.upsert_node(n)
    j = mock.job()
    j.datacenters = ["dc1", "dc2"]
    j.task_groups[0].count = 4
    j.task_groups[0].spreads = [Spread("${node.datacenter}", 100, ())]
    res, _, _ = run_place(cm, j)
    dcs = [nodes_dc for nodes_dc in res.node[:4].tolist()]
    dc_of_row = {cm.row_of[n.id]: n.datacenter for n in nodes}
    counts = {}
    for r in dcs:
        counts[dc_of_row[r]] = counts.get(dc_of_row[r], 0) + 1
    assert counts == {"dc1": 2, "dc2": 2}


def test_distinct_hosts():
    cm, nodes = build_world(3)
    j = mock.job()
    j.constraints.append(Constraint(operand=Operand.DISTINCT_HOSTS))
    existing = mock.alloc_for(j, nodes[0].id)
    res, _, _ = run_place(cm, j, count=1, allocs_by_tg={"web": [existing]})
    assert res.node[0] != cm.row_of[nodes[0].id]


def test_score_meta_topk():
    cm, nodes = build_world(4)
    j = mock.job()
    res, _, _ = run_place(cm, j, count=1)
    assert (res.top_scores[0, 1:] <= res.top_scores[0, 0]).all()


def test_version_constraint():
    cm = ClusterMatrix()
    old = mock.node()
    old.attributes["nomad.version"] = "0.4.0"
    new = mock.node()
    new.attributes["nomad.version"] = "1.2.3"
    cm.upsert_node(old)
    cm.upsert_node(new)
    j = mock.job()
    j.constraints.append(Constraint("${attr.nomad.version}", ">= 1.0.0", Operand.VERSION))
    res, _, _ = run_place(cm, j, count=1)
    assert res.node[0] == cm.row_of[new.id]


def test_regex_constraint():
    cm = ClusterMatrix()
    a = mock.node(name="web-01")
    b = mock.node(name="db-01")
    cm.upsert_node(a)
    cm.upsert_node(b)
    j = mock.job()
    j.constraints.append(Constraint("${node.unique.name}", "^web-", Operand.REGEX))
    res, _, _ = run_place(cm, j, count=1)
    assert res.node[0] == cm.row_of[a.id]


# --- the slot loop's bound (ops.place._scan_slots) ------------------------
#
# The jitted entry points run an eval's slot loop to its last active slot.
# They are held, byte for byte over the whole padded [S, 5 + 2 * TOP_K]
# block and the final usage, to the loop they replaced: the full
# `lax.scan` over arange(S), kept here and sharing nothing with the helper.


@functools.partial(jax.jit, static_argnames=("spread_algorithm",))
def _full_scan(inp, spread_algorithm=False):
    S = inp.demand.shape[0]
    carry, outs = jax.lax.scan(
        functools.partial(P._place_step, inp, spread_algorithm),
        P.place_carry0(inp, inp.used), jnp.arange(S))
    return P._pack_outputs(*outs), carry[0]


@pytest.fixture(scope="module")
def bound_world():
    cm = ClusterMatrix()
    # room for a full 128 bucket under distinct_hosts (160 hosts) and
    # distinct_property (16 racks of at most 10)
    for i in range(160):
        nd = mock.node()
        nd.attributes["rack"] = f"r{i % 16}"
        cm.upsert_node(nd)
    return cm


def _bound_inputs(cm, kind, S, active):
    """One group's PlaceInputs with the slot axis at S: every slot
    carries the group's demand, so an inactive one is a real ask switched
    off."""
    job = mock.job()
    tg = job.task_groups[0]
    if kind == "spread":
        tg.spreads = [Spread("${attr.rack}", 100, ())]
    elif kind == "distinct":
        job.constraints.append(Constraint("", "", Operand.DISTINCT_HOSTS))
        job.constraints.append(
            Constraint("${attr.rack}", "10", Operand.DISTINCT_PROPERTY))
    stack = DenseStack(cm)
    groups = [stack.compile_group(job, tg)]
    inp = stack.build_inputs(job, groups, [0], {})
    return dataclasses.replace(inp, demand=np.tile(inp.demand[:1], (S, 1)),
                               slot_tg=np.zeros(S, np.int32),
                               slot_active=np.asarray(active, bool))


def _active(S, n, layout):
    on = np.arange(S) < n
    if layout == "holes":
        on[[1, n // 2]] = False
    return on


_BOUND_CASES = [
    pytest.param(kind, S, n, layout, id=f"{kind}-S{S}-n{n}-{layout}")
    for kind in ("plain", "spread", "distinct")
    for S in (16, 128)
    for n in (0, 1, S // 3, S - 1, S)
    for layout in ("prefix", "holes")
    if layout == "prefix" or n >= 3
] + [pytest.param("spread", 1024, 300, "holes", id="spread-S1024-n300-holes")]


@pytest.mark.parametrize("kind,S,n,layout", _BOUND_CASES)
def test_bounded_slot_loop_is_the_full_scan(bound_world, kind, S, n, layout):
    inp = _bound_inputs(bound_world, kind, S, _active(S, n, layout))
    want_packed, want_used = jax.device_get(_full_scan(inp))
    placed = want_packed[:, 0] >= 0
    assert not placed[~np.asarray(inp.slot_active)].any()
    if n:
        assert placed[n - 1]        # the slot behind the holes is placed

    packed, used = jax.device_get(P.place_eval_packed_jit(inp))
    assert packed.tobytes() == want_packed.tobytes()
    assert used.tobytes() == want_used.tobytes()

    res = P.place_eval_jit(inp)
    repacked = jax.device_get(P._pack_outputs(
        res.node, res.score, res.fit_score, res.nodes_evaluated,
        res.nodes_exhausted, res.top_nodes, res.top_scores))
    assert repacked.tobytes() == want_packed.tobytes()
    assert jax.device_get(res.used).tobytes() == want_used.tobytes()


def test_batch_pads_run_no_step_and_chain_like_single_evals(bound_world):
    """E = 8: three live evals and five inert pads (a light block of
    zeros, as the engine pads) chain as three sequential single evals."""
    S, D, E = 16, 4, 8
    live = [_bound_inputs(bound_world, "spread", S, _active(S, n, "prefix"))
            for n in (5, 1, 16)]
    used = np.asarray(live[0].used, np.float32)
    want = []
    for inp in live:
        packed, used = jax.device_get(_full_scan(
            dataclasses.replace(inp, used=used)))
        want.append(packed)
    assert (np.concatenate(want)[:, 0] >= 0).sum() == 5 + 1 + 16

    heavy = [jnp.asarray(P.pack_heavy(inp)) for inp in live]
    lights = [P.pack_light(inp, [], D, S) for inp in live]
    lights += [np.zeros_like(lights[0])] * (E - len(live))
    packed, used_final = jax.device_get(P.place_batch_packed_jit(
        jnp.asarray(live[0].capacity), jnp.asarray(live[0].used),
        tuple(heavy + [heavy[0]] * (E - len(live))),
        jnp.asarray(np.concatenate(lights)),
        P.heavy_dims(live[0]) + (S, D)))
    for e, w in enumerate(want):
        assert packed[e].tobytes() == w.tobytes()
    assert used_final.tobytes() == used.tobytes()
    # a pad eval's rows are what S inactive steps write
    inert, _ = jax.device_get(_full_scan(
        _bound_inputs(bound_world, "spread", S, np.zeros(S, bool))))
    for e in range(len(live), E):
        assert packed[e].tobytes() == inert.tobytes()
