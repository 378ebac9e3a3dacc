"""Test-only second world, its job shapes: `c2m-10k`'s, and a shape
that names a `zone` is constrained to the nodes that carry it."""
from benchmark import jobs as c2m


def build(shape: dict, job_id: str, namespace: str = "default"):
    from nomad_tpu.structs.job import Constraint, Operand
    job = c2m.build(shape, job_id, namespace)
    if shape.get("zone"):
        job.constraints.append(
            Constraint("${attr.zone}", shape["zone"], Operand.EQ))
    return job
