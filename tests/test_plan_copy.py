"""A plan's own copy of an allocation it stops or evicts: a new record
whose fields are the stored record's own objects, with the fields the
method sets set on the copy and the job dropped (reference
Plan.AppendStoppedAlloc: `*newAlloc = *alloc`).  The record a snapshot
may still hold is left exactly as it was, and nothing is deep-copied.
"""
import copy
import dataclasses

import pytest

from nomad_tpu import mock
from nomad_tpu.structs import AllocClientStatus, AllocDesiredStatus
from nomad_tpu.structs.alloc import (Allocation, AllocMetric,
                                     DesiredTransition, RescheduleEvent,
                                     RescheduleTracker, TaskState)
from nomad_tpu.structs.plan import Plan

# the fields an allocation holds by reference: shared with the plan's copy
PARTS = ("allocated_resources", "task_states", "metrics",
         "desired_transition", "reschedule_tracker", "deployment_status",
         "preempted_allocations")


def _record() -> Allocation:
    """A stored allocation with every part filled in."""
    a = mock.alloc()
    a.client_status = AllocClientStatus.RUNNING
    a.task_states = {"web": TaskState(state="running", started_at=12.5,
                                      events=[{"type": "Started"}])}
    a.metrics = AllocMetric(nodes_evaluated=3, scores={"n.class": 0.5},
                            score_meta=[{"node_id": a.node_id,
                                         "scores": {"binpack": 0.5},
                                         "norm_score": 0.5}])
    a.desired_transition = DesiredTransition(migrate=True)
    a.reschedule_tracker = RescheduleTracker(
        events=[RescheduleEvent(reschedule_time=3.0, prev_alloc_id="p")])
    a.deployment_status = {"healthy": True, "canary": False}
    a.preempted_allocations = ["x", "y"]
    a.create_index, a.modify_index = 7, 9
    a.comparable_resources()            # the memo a stored record carries
    return a


def _stop(plan, a):
    plan.append_stopped_alloc(a, "alloc not needed due to job update")
    return plan.node_update, {
        "desired_status": AllocDesiredStatus.STOP,
        "desired_description": "alloc not needed due to job update"}


def _stop_lost(plan, a):
    plan.append_stopped_alloc(a, "alloc was lost since its node is down",
                              client_status=AllocClientStatus.LOST)
    return plan.node_update, {
        "desired_status": AllocDesiredStatus.STOP,
        "desired_description": "alloc was lost since its node is down",
        "client_status": AllocClientStatus.LOST}


def _stop_followup(plan, a):
    plan.append_stopped_alloc(a, "alloc is being rescheduled",
                              followup_eval_id="eval-17")
    return plan.node_update, {
        "desired_status": AllocDesiredStatus.STOP,
        "desired_description": "alloc is being rescheduled",
        "followup_eval_id": "eval-17"}


def _evict(plan, a):
    plan.append_preempted_alloc(a, "alloc-99")
    return plan.node_preemptions, {
        "desired_status": AllocDesiredStatus.EVICT,
        "preempted_by_allocation": "alloc-99",
        "desired_description": "Preempted by alloc ID alloc-99"}


METHODS = [_stop, _stop_lost, _stop_followup, _evict]


@pytest.mark.parametrize("method", METHODS, ids=lambda m: m.__name__[1:])
def test_the_plans_entry_is_its_own_record_with_the_fields_set(method):
    a = _record()
    entries, sets = method(Plan(), a)
    (entry,) = entries[a.node_id]
    assert entry is not a
    assert entry.job is None
    for name, value in sets.items():
        assert getattr(entry, name) == value, name
    # every field the method does not set is the record's own
    for f in dataclasses.fields(Allocation):
        if f.name not in sets and f.name != "job":
            assert getattr(entry, f.name) == getattr(a, f.name), f.name
    for name in PARTS:
        assert getattr(entry, name) is getattr(a, name), name


@pytest.mark.parametrize("method", METHODS, ids=lambda m: m.__name__[1:])
def test_the_record_reads_as_it_did(method):
    """Every field of the record, its job included, equals a deep copy
    taken beforehand; the statuses the method sets land on the copy."""
    a = _record()
    before = copy.deepcopy(a)
    job = a.job
    entries, sets = method(Plan(), a)
    assert a == before
    assert a.job is job and a.job == before.job
    assert a.desired_status == AllocDesiredStatus.RUN
    assert a.client_status == AllocClientStatus.RUNNING
    assert a.preempted_by_allocation == "" and a.followup_eval_id == ""
    # and the copy's later life does not reach it: the store restores
    # the job and stamps the indexes on the entry (_insert_alloc)
    (entry,) = entries[a.node_id]
    entry.job, entry.modify_index = job, 11
    assert a == before and a.modify_index == 9


@pytest.mark.parametrize("method", METHODS, ids=lambda m: m.__name__[1:])
def test_no_part_is_deep_copied(method):
    """A job, resources and metrics that refuse to be deep-copied go
    through: 10,000 evictions of a fleet job copied 10,000 jobs."""
    a = _record()

    def refuse(self, memo):
        raise AssertionError(f"deep copy of a {type(self).__name__}")

    for part in (a.job, a.allocated_resources, a.metrics,
                 a.desired_transition, a.reschedule_tracker,
                 a.task_states["web"]):
        cls = type(part)
        guarded = type(cls.__name__, (cls,), {"__deepcopy__": refuse})
        part.__class__ = guarded
    with pytest.raises(AssertionError, match="deep copy of a Job"):
        a.copy()                        # the guard guards: copy() is deep
    entries, _ = method(Plan(), a)
    (entry,) = entries[a.node_id]
    assert entry.job is None and entry.metrics is a.metrics


def test_copy_is_still_deep_and_copy_shallow_is_not():
    a = _record()
    deep, shallow = a.copy(), a.copy_shallow()
    assert deep == a and shallow == a
    assert deep is not a and shallow is not a
    assert type(shallow) is Allocation
    for name in PARTS + ("job",):
        assert getattr(deep, name) is not getattr(a, name), name
        assert getattr(shallow, name) is getattr(a, name), name
    # the memo of comparable_resources() is keyed by the resources'
    # identity, which the shallow copy shares: it stays right
    assert shallow.comparable_resources() is a.comparable_resources()
    shallow.desired_status = AllocDesiredStatus.STOP
    assert a.desired_status == AllocDesiredStatus.RUN
