"""Job shapes of `system-10k`: `preempt-10k`'s, and the system job.

`kind: "system"` is one allocation on every node in scope: one group,
one task (cpu MHz / memory MB, no disk, no ports), the priority stated,
no `count` (the scheduler gives a system job its count: the nodes that
meet it).  The scope is the shape's datacenters and, with `rack`, the
nodes whose `${attr.rack}` equals it.  Every other kind is
`preempt-10k`'s.
"""
from benchmark.preempt import jobs as preempt


def build(shape: dict, job_id: str, namespace: str = "default"):
    if shape["kind"] != "system":
        return preempt.build(shape, job_id, namespace)
    if "priority" not in shape:
        raise ValueError(f"job {job_id}: a shape of system-10k states "
                         f"its priority")
    from nomad_tpu.structs import Job, JobStatus, JobType, Task, TaskGroup
    from nomad_tpu.structs.job import Constraint, Operand
    from nomad_tpu.structs.resources import Resources
    tg = TaskGroup(
        name="g0", count=1,
        tasks=[Task(name="web", driver="exec",
                    config={"command": "/bin/date"},
                    resources=Resources(cpu=shape["cpu"],
                                        memory_mb=shape["memory_mb"]))])
    tg.ephemeral_disk.size_mb = 0
    constraints = [Constraint("${attr.kernel.name}", "linux", Operand.EQ)]
    if shape.get("rack"):
        constraints.append(Constraint("${attr.rack}", shape["rack"],
                                      Operand.EQ))
    return Job(
        id=job_id, name=job_id, namespace=namespace, type=JobType.SYSTEM,
        priority=shape["priority"], datacenters=list(shape["datacenters"]),
        constraints=constraints, task_groups=[tg], status=JobStatus.PENDING)
