"""Job shapes of `devices-10k`: `c2m-10k`'s, and a shape with a `device`
entry asks every task for instances as the job specification's `device`
block does: `{"name", "count", "constraints": [{"attribute", "operator",
"value"}], "affinities": [{..., "weight"}]}`."""
from benchmark import jobs as c2m


def build(shape: dict, job_id: str, namespace: str = "default"):
    from nomad_tpu.structs.job import Affinity, Constraint
    from nomad_tpu.structs.resources import DeviceRequest
    job = c2m.build(shape, job_id, namespace)
    ask = shape.get("device")
    if ask:
        for tg in job.task_groups:
            for task in tg.tasks:
                task.resources.devices = [DeviceRequest(
                    name=ask["name"], count=ask["count"],
                    constraints=[Constraint(c["attribute"], c["value"],
                                            c["operator"])
                                 for c in ask.get("constraints", ())],
                    affinities=[Affinity(a["attribute"], a["value"],
                                         a["operator"], a["weight"])
                                for a in ask.get("affinities", ())])]
    return job
