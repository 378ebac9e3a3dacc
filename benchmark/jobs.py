"""Job shapes, built from the parameters a traffic file gives.

Copies of `bench._batch_job`, `_c2m_job` and `_service_job` with every
field that decides placement written out here, so that a change to the
program's test fixtures cannot change the benchmark's traffic.
"""
from __future__ import annotations


def build(shape: dict, job_id: str, namespace: str = "default"):
    """One job of `shape`: kind (batch|service), groups x count task
    groups of one task (cpu MHz / memory MB, no disk, no ports), optional
    rack spread and datacenter affinity, priority, datacenters."""
    from nomad_tpu.structs import (
        Job, JobStatus, JobType, ReschedulePolicy, Task, TaskGroup)
    from nomad_tpu.structs.job import Affinity, Constraint, Operand, Spread
    from nomad_tpu.structs.resources import Resources
    service = shape["kind"] == "service"
    groups = []
    for g in range(shape.get("groups", 1)):
        tg = TaskGroup(
            name=f"g{g}", count=shape["count"],
            tasks=[Task(name="web", driver="exec",
                        config={"command": "/bin/date"},
                        resources=Resources(cpu=shape["cpu"],
                                            memory_mb=shape["memory_mb"]))],
            reschedule_policy=(ReschedulePolicy.default_service() if service
                               else ReschedulePolicy.default_batch()))
        tg.ephemeral_disk.size_mb = 0
        if shape.get("spread"):
            tg.spreads = [Spread("${attr.rack}", shape["spread"], ())]
        if shape.get("affinity_dc"):
            tg.affinities = [Affinity("${node.datacenter}",
                                      shape["affinity_dc"], "=",
                                      shape.get("affinity_weight", 50))]
        groups.append(tg)
    return Job(
        id=job_id, name=job_id, namespace=namespace,
        type=JobType.SERVICE if service else JobType.BATCH,
        priority=shape.get("priority", 50),
        datacenters=list(shape["datacenters"]),
        constraints=[Constraint("${attr.kernel.name}", "linux", Operand.EQ)],
        task_groups=groups, status=JobStatus.PENDING)
