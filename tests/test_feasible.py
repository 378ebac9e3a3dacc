"""Constraint-program semantics (regressions from review findings included)."""
import numpy as np

from nomad_tpu import mock
from nomad_tpu.encode import ClusterMatrix
from nomad_tpu.scheduler import feasible as fz
from nomad_tpu.scheduler.stack import DenseStack
from nomad_tpu.scheduler.version import version_matches
from nomad_tpu.structs.job import Constraint, Operand, Task, TaskGroup
from nomad_tpu.structs.resources import DeviceRequest, NodeDevice


def test_version_matching_semantics():
    assert version_matches("1.2.3", ">= 1.0.0, < 2.0.0")
    assert not version_matches("2.0.1", ">= 1.0.0, < 2.0.0")
    assert version_matches("1.4.9", "~> 1.4")
    assert not version_matches("2.0.0", "~> 1.4")
    assert version_matches("1.4.5", "~> 1.4.3")
    assert not version_matches("1.5.0", "~> 1.4.3")
    # semver: prerelease only matches prerelease constraints
    assert not version_matches("1.3.0-beta1", ">= 0.6.1", semver=True)
    assert version_matches("1.3.0-beta1", ">= 1.3.0-beta1", semver=True)
    assert version_matches("1.3.0-beta1", ">= 0.6.1")  # plain version mode


def test_swapped_version_operands():
    """Literal version on the left, column carrying the spec on the right."""
    cm = ClusterMatrix()
    n = mock.node()
    n.attributes["allowed"] = ">= 1.0"
    cm.upsert_node(n)
    mask = fz.constraint_mask(cm, Constraint("1.2.3", "${attr.allowed}", Operand.VERSION))
    assert mask[cm.row_of[n.id]]
    mask = fz.constraint_mask(cm, Constraint("0.5.0", "${attr.allowed}", Operand.VERSION))
    assert not mask[cm.row_of[n.id]]


def test_neq_against_missing_column():
    cm = ClusterMatrix()
    n = mock.node()
    cm.upsert_node(n)
    r = cm.row_of[n.id]
    # nil != found-value -> True (reference checkConstraint "!=")
    assert fz.constraint_mask(cm, Constraint("${attr.kernel.name}", "${attr.never}", Operand.NEQ))[r]
    # nil == nil -> equal -> NEQ False
    assert not fz.constraint_mask(cm, Constraint("${attr.nope}", "${attr.never}", Operand.NEQ))[r]
    # EQ with a missing side is never satisfied
    assert not fz.constraint_mask(cm, Constraint("${attr.kernel.name}", "${attr.never}", Operand.EQ))[r]


def test_is_set_operators():
    cm = ClusterMatrix()
    n = mock.node()
    cm.upsert_node(n)
    r = cm.row_of[n.id]
    assert fz.constraint_mask(cm, Constraint("${attr.kernel.name}", "", Operand.ATTRIBUTE_IS_SET))[r]
    assert not fz.constraint_mask(cm, Constraint("${attr.zzz}", "", Operand.ATTRIBUTE_IS_SET))[r]
    assert fz.constraint_mask(cm, Constraint("${attr.zzz}", "", Operand.ATTRIBUTE_IS_NOT_SET))[r]


def test_set_contains():
    cm = ClusterMatrix()
    n = mock.node()
    n.attributes["features"] = "avx,sse4,aes"
    cm.upsert_node(n)
    r = cm.row_of[n.id]
    assert fz.constraint_mask(cm, Constraint("${attr.features}", "avx,aes", Operand.SET_CONTAINS))[r]
    assert not fz.constraint_mask(cm, Constraint("${attr.features}", "avx,foo", Operand.SET_CONTAINS))[r]
    assert fz.constraint_mask(cm, Constraint("${attr.features}", "foo,aes", Operand.SET_CONTAINS_ANY))[r]


def test_device_caps_cleared_on_reregister():
    cm = ClusterMatrix()
    n = mock.node()
    n.node_resources.devices = [NodeDevice("nvidia", "gpu", "t4", ["i0", "i1"])]
    cm.upsert_node(n)
    req = DeviceRequest(name="gpu", count=1)
    assert fz.device_mask(cm, [req])[cm.row_of[n.id]]
    n.node_resources.devices = []
    cm.upsert_node(n)
    assert not fz.device_mask(cm, [req])[cm.row_of[n.id]]


def test_tg_level_distinct_hosts_scoped_to_group():
    cm = ClusterMatrix()
    node = mock.node()
    cm.upsert_node(node)
    j = mock.job()
    j.task_groups.append(TaskGroup(name="b", count=1, tasks=[Task(name="b", driver="exec")]))
    j.task_groups[0].constraints.append(Constraint(operand=Operand.DISTINCT_HOSTS))
    st = DenseStack(cm)
    groups = [st.compile_group(j, tg) for tg in j.task_groups]
    b_alloc = mock.alloc_for(j, node.id)
    b_alloc.task_group = "b"
    from nomad_tpu.ops.place import distinct_open

    def open_to_web(inp):
        # the existing allocations are where the kernel's carry starts
        # (PlaceInputs.hosts_taken), not a mask over `feasible`
        assert inp.feasible[0, cm.row_of[node.id]]
        return bool(distinct_open(inp, 0, inp.hosts_taken,
                                  inp.prop_counts)[cm.row_of[node.id]])

    inp = st.build_inputs(j, groups, [0], {"b": [b_alloc]})
    # a group-level constraint on "web" must not collide with "b"'s alloc
    assert open_to_web(inp)
    # but a job-level one must
    j2 = mock.job()
    j2.task_groups.append(TaskGroup(name="b", count=1, tasks=[Task(name="b", driver="exec")]))
    j2.constraints.append(Constraint(operand=Operand.DISTINCT_HOSTS))
    groups2 = [st.compile_group(j2, tg) for tg in j2.task_groups]
    inp2 = st.build_inputs(j2, groups2, [0], {"b": [b_alloc]})
    assert not open_to_web(inp2)
