"""Placement engine behavior tests (dense analog of scheduler/rank_test.go,
feasible_test.go, spread_test.go cases)."""
import numpy as np
import pytest

from nomad_tpu import mock
from nomad_tpu.encode import ClusterMatrix
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
