"""Job shapes of `ports-10k`: `c2m-10k`'s, and a shape with a `network`
entry gives every task group the job specification's group `network`
block: `{"ports": [{"label"}, {"label", "static": 8080}, {"label",
"static_of": [8080, 8443, 9090, 9443]}]}`.  A port without a value is a
dynamic one (`port "http" {}`); `static` is `port "https" { static = 8080
}`; `static_of` takes the value at the job's ordinal (the digits of its
id) modulo the list's length, so that the jobs of one shape ask for
different well-known ports.  `asked_ports` is that rule alone and imports
nothing: the reference's `JobSpec` records what was sent by it."""
import re

from benchmark import jobs as c2m

_ORDINAL = re.compile(r"\d+")


def asked_ports(shape: dict, job_id: str) -> list:
    """[(label, static value or 0 for a dynamic port)] of one task group
    of the job, in the block's order."""
    found = _ORDINAL.search(job_id)
    ordinal = int(found.group()) if found else 0
    out = []
    for port in (shape.get("network") or {}).get("ports", ()):
        value = port.get("static", 0)
        if "static_of" in port:
            value = port["static_of"][ordinal % len(port["static_of"])]
        out.append((port["label"], int(value)))
    return out


def build(shape: dict, job_id: str, namespace: str = "default"):
    from nomad_tpu.structs.resources import NetworkPort, NetworkResource
    job = c2m.build(shape, job_id, namespace)
    asked = asked_ports(shape, job_id)
    if asked:
        for tg in job.task_groups:
            tg.networks = [NetworkResource(
                reserved_ports=[NetworkPort(label=label, value=value)
                                for label, value in asked if value],
                dynamic_ports=[NetworkPort(label=label)
                               for label, value in asked if not value])]
    return job
