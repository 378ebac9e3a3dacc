"""Job shapes of `preempt-10k`: `c2m-10k`'s, with the priority stated.
Every job of this configuration stands in a tier (the fillers at 20, 35
and 45, the services at Nomad's default 50), and which tier decides what
it may evict and what may evict it, so a shape that leaves the priority
to a default is refused."""
from benchmark import jobs as c2m


def build(shape: dict, job_id: str, namespace: str = "default"):
    if "priority" not in shape:
        raise ValueError(f"job {job_id}: a shape of preempt-10k states "
                         f"its priority")
    return c2m.build(shape, job_id, namespace)
