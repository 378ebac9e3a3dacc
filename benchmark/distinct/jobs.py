"""Job shapes of `distinct-10k`: `c2m-10k`'s, and a shape with a
`task_groups` list names its groups and gives each its own size, and
carries `constraint` blocks under the job's or a group's `constraints`
key, written as `gpu-asks.json` writes a device's: `{"attribute",
"operator", "value"}`.  `{"operator": "distinct_hosts"}` is
`constraint { operator = "distinct_hosts" value = "true" }`;
`{"attribute": "${attr.rack}", "operator": "distinct_property", "value":
"3"}` is `constraint { distinct_property = "${attr.rack}" value = "3" }`.
A shape without `task_groups` (the preload's) is `c2m-10k`'s as it is.
"""
from benchmark import jobs as c2m


def build(shape: dict, job_id: str, namespace: str = "default"):
    groups = shape.get("task_groups")
    if not groups:
        return c2m.build(shape, job_id, namespace)
    from nomad_tpu.structs.job import Constraint

    def blocks(holder: dict) -> list:
        return [Constraint(c.get("attribute", ""), c.get("value", ""),
                           c["operator"])
                for c in holder.get("constraints", ())]

    job = c2m.build(dict(shape, groups=len(groups), count=groups[0]["count"],
                         cpu=groups[0]["cpu"],
                         memory_mb=groups[0]["memory_mb"]),
                    job_id, namespace)
    job.constraints = job.constraints + blocks(shape)
    for tg, g in zip(job.task_groups, groups):
        tg.name, tg.count = g["name"], g["count"]
        tg.tasks[0].resources.cpu = g["cpu"]
        tg.tasks[0].resources.memory_mb = g["memory_mb"]
        tg.constraints = blocks(g)
    return job
