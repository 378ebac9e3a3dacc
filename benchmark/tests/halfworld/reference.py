"""Test-only second world, its reference: `c2m-10k`'s comparison of
counts, capacity and reported scores, plus two `violations` of its own:
an allocation of a zoned shape on a node without the zone, and an
allocation the window left on one node that the node's own list, read
back by `readback`, does not hold.  `misplaced_jobs_share` is not
compared: its floor counts nodes the constraint rules out."""
from benchmark import reference as c2m

JobSpec = c2m.JobSpec
LIMITS = {k: c2m.LIMITS[k] for k in ("violations", "unexplained_jobs_share")}


def readback(get, records) -> dict:
    """The allocation list of one node, the one that holds the first
    finished job's first allocation, as `/v1/node/<id>/allocations`
    gives it once the window has closed."""
    for rec in records:
        if rec.done is not None and rec.stubs:
            node_id = rec.stubs[0]["NodeID"]
            return {"node_id": node_id,
                    "allocs": get(f"/v1/node/{node_id}/allocations")}
    return {"node_id": None, "allocs": []}


def compare(cl, specs: dict, stubs: list, full: list, completed: set,
            seen: dict) -> dict:
    verdict = c2m.compare(cl, specs, stubs, full, completed, LIMITS)
    listed = {a["id"] for a in seen["allocs"]}
    problems = []
    for s in stubs:
        if s["DesiredStatus"] != "run" or s["NodeID"] not in cl.index:
            continue
        if specs[s["JobID"]].shape.get("zone") \
                and not cl.zoned[cl.index[s["NodeID"]]]:
            problems.append(f"allocation {s['ID']} of {s['JobID']} on a "
                            f"node outside zone {cl.cfg['zone']}")
        if s["NodeID"] == seen["node_id"] and s["ID"] not in listed:
            problems.append(f"allocation {s['ID']} is not in its node's "
                            f"own list")
    count = verdict["compared"]["violations"]
    count["value"] += len(problems)
    verdict["correct"] = verdict["correct"] and not problems
    verdict["problems"] = (verdict["problems"] + problems)[:5]
    return verdict
