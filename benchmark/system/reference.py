"""The plain reference of `system-10k` and the comparison that decides
`correct`.

Float64 numpy; imports nothing of the program.  The cluster is
`benchmark.system.cluster.Cluster` (the seed), the jobs the traffic
file's, and from the program only its answers, read back over HTTP once
the window has closed.  Preemption's meaning (`search`, `score`, the
delta, what an eviction owes its node) is `preempt-10k`'s and imported
from `benchmark.preempt.reference`; ScoreFitBinPack, the limits and the
tolerances are `c2m-10k`'s (`benchmark.reference`).

What this module adds is the system job (scheduler/scheduler_system.go):

* its scope is every node of its datacenters that meets its constraints
  (here: a rack, or none), and a completed job runs exactly one
  allocation on each node of the scope and none elsewhere;
* it chooses no node, so nothing is ranked: where the ask does not fit,
  the one node is searched as `preempt-10k` searches every node, and
  what goes is held to the same rules;
* a placement reports the score of its one node: the mean of
  ScoreFitBinPack after the eviction and the evicted set's preemption
  score where it evicts, ScoreFitBinPack alone where it does not.

Two numbers are compared, under `c2m-10k`'s limits:

* `violations` (exact): a node in the scope of a completed system job
  with none of its allocations, or with two; one outside the scope; and
  every count `preempt-10k` makes, on every node: from the node's own
  list where `readback` read it (a seeded sample, and every node on
  which the cluster's list shows anything but one placement and one
  eviction), else from the cluster's list, which says what runs and what
  does not but not who evicted whom: there the one placement stands as
  the evictor of the one filler gone.
* `unexplained_jobs_share`: a placement's reported score against the
  reference's score of that node with that evicted set, under every
  usage the node can have shown.

There is no `misplaced_jobs_share`: a system job has no choice of node
to be held to; what it does choose, the evicted set, `violations` holds
exactly.
"""
from __future__ import annotations

import copy
import time

import numpy as np

from benchmark import reference as c2m
from benchmark.preempt import reference as pre
from benchmark.preempt.reference import DELTA, score, search
from benchmark.system import cluster as world

LIMITS = {k: c2m.LIMITS[k] for k in ("violations", "unexplained_jobs_share")}
NODES_SAMPLED = 256       # node lists read whatever the cluster's list says
NODES_READ = 2000         # node lists one readback makes at most
READBACK_S = 25.0         # and it stops reading them after this long


def scope(cl, shape: dict) -> np.ndarray:
    """bool[N]: the nodes a system job of `shape` runs on."""
    ok = np.isin(cl.dc, shape["datacenters"])
    if shape.get("rack"):
        ok &= cl.rack == int(shape["rack"][1:])
    return ok


class JobSpec(pre.JobSpec):
    """What the benchmark sent.  A system job's count is its scope's
    size in the cluster made last (`Cluster.made`: the seam hands a spec
    its shape alone)."""

    def __init__(self, job_id, namespace, shape, registered=0):
        self.system = shape["kind"] == "system"
        counted = shape
        if self.system:
            counted = dict(shape, groups=1, count=int(
                scope(world.Cluster.made, shape).sum()))
        super().__init__(job_id, namespace, counted, registered)
        self.shape = shape


# ------------------------------------------------------------- readback

def readback(get, records) -> dict:
    """{"allocs": [(id, job, node, desired status)] of every allocation
    the cluster lists, in all namespaces; "nodes": {node id: its
    allocation list} for `NODES_SAMPLED` nodes drawn from the seed and
    for every node on which the first list shows anything but one
    placement of the run and one allocation that no longer runs (at most
    `NODES_READ`, for at most `READBACK_S`); "seconds"}."""
    t0 = time.monotonic()
    cl = world.Cluster.made
    listed = get("/v1/allocations", {"namespace": "*"})
    ours = {rec.spec.id for rec in records}
    shows: dict = {}          # node -> [placements of the run, not running]
    for s in listed:
        n = shows.setdefault(s["NodeID"], [0, 0])
        if s["DesiredStatus"] != "run":
            n[1] += 1
        elif s["JobID"] in ours:
            n[0] += 1
    rng = np.random.default_rng([cl.seed, 0x5A3F1E])
    read = [cl.node_ids[r] for r in sorted(rng.choice(
        cl.n, size=min(NODES_SAMPLED, cl.n), replace=False))]
    sampled = set(read)
    read += [n for n in sorted(shows) if shows[n] != [1, 1]
             and n not in sampled][:NODES_READ]
    nodes = {}
    for n in read:
        if time.monotonic() - t0 > READBACK_S:
            break
        nodes[n] = get(f"/v1/node/{n}/allocations")
    return {"allocs": [(s["ID"], s["JobID"], s["NodeID"], s["DesiredStatus"])
                       for s in listed],
            "nodes": nodes, "seconds": time.monotonic() - t0}


def _from_the_list(cl, specs: dict, seen: dict, problems: list) -> dict:
    """{node id: a list in the shape of the node's own} for the nodes
    `readback` did not read, made from the cluster's list.  Such a node
    shows one placement of the run and one filler gone, and the one
    stands as the evictor of the other; or nothing of either.  Anything
    else on a node not read is a problem: it cannot be judged."""
    by_node: dict = {}
    for aid, job, node, status in seen.get("allocs", ()):
        if node not in seen["nodes"]:
            by_node.setdefault(node, []).append((aid, job, status))
    out = {}
    for node, entries in by_node.items():
        placed = [(a, j) for a, j, st in entries
                  if st == "run" and j in specs]
        gone = [a for a, _j, st in entries if st != "run"]
        if (len(placed), len(gone)) not in ((1, 1), (0, 0)):
            problems.append(f"node {node} shows {len(placed)} placement(s) "
                            f"and {len(gone)} allocation(s) gone, and was "
                            f"not read")
            continue
        by = placed[0][0] if placed else None
        out[node] = [
            {"id": a, "job_id": j, "desired_status": st, "create_index": 0,
             "name": "g0[0]",
             "preempted_by_allocation": by if st != "run" else None}
            for a, j, st in entries]
    return out


# -------------------------------------------------------- the comparison

def _coverage_problems(cl, specs: dict, live: list, completed: set) -> list:
    """A system job that was seen placed (the window's), or that runs
    its count (the warm pass's, which the harness saw placed), against
    its scope."""
    problems = []
    for spec in specs.values():
        if not spec.system:
            continue
        rows = [cl.index.get(s["NodeID"], -1) for s in live
                if s["JobID"] == spec.id]
        if spec.id not in completed and len(rows) != spec.allocs:
            continue
        if -1 in rows:
            problems.append(f"{rows.count(-1)} allocation(s) of {spec.id} "
                            f"on nodes the cluster does not have")
        count = np.bincount([r for r in rows if r >= 0], minlength=cl.n)
        inside = scope(cl, spec.shape)
        for what, at in (("has none", inside & (count == 0)),
                         ("has two or more", inside & (count > 1)),
                         ("is outside its scope and has one",
                          ~inside & (count > 0))):
            problems += [f"node #{r} {what} of {spec.id}'s allocations"
                         for r in np.flatnonzero(at)]
    return problems


def _wanted(cl, specs, a, me, history, row):
    """Every score the reference can give the placement `me` on `row`:
    under the preload's usage, what committed on the node long before
    the job was registered, and any of what committed since from jobs
    registered by this commit (`c2m-10k`'s rule, as `preempt-10k`
    applies it)."""
    fd = cl.filler_demand
    spec = specs[me.job]
    settled = cl.used0[row].copy()
    maybe, coll = [], 0
    for p in history:
        if p is me:
            continue
        net = specs[p.job].demand - len(p.evicted) * fd
        if p.index < spec.registered - c2m.STALE:
            settled += net
            coll += p.job == me.job
        elif specs[p.job].registered <= me.index:
            maybe.append(net)
    maybe = maybe[:pre._SUBSETS_MAX]
    prios = cl.pre_prio[me.evicted]
    for mask in range(1 << len(maybe)):
        util = settled + sum((m for k, m in enumerate(maybe)
                              if mask >> k & 1), np.zeros(2)) \
            - len(me.evicted) * fd + spec.demand
        if (util > cl.cap[row]).any():
            continue
        if me.evicted:
            yield score(cl.cap[row], util, prios)[0]
        elif spec.system:
            yield c2m.fit_score(cl.cap[row], util)
        else:
            yield c2m.total_score(c2m.fit_score(cl.cap[row], util), coll,
                                  spec.groups[a["task_group"]])


def compare(cl, specs: dict, stubs: list, full: list, completed: set,
            seen: dict, limits: dict = LIMITS) -> dict:
    """`stubs`, `full`, `completed` as `benchmark.reference.compare`
    takes them; `seen` is what `readback` returned."""
    problems: list = []
    nodes = _from_the_list(cl, specs, seen, problems)
    nodes.update(seen["nodes"])
    placed, stayed, more = pre._node_histories(cl, specs, {"nodes": nodes})
    problems += more
    problems += pre._eviction_problems(cl, specs, placed, stayed)
    if "allocs" in seen:          # the controls make no cluster-wide list
        problems += pre._listing_problems(
            cl, specs, {"allocs": seen["allocs"], "nodes": nodes})
    live = [s for s in stubs if s["DesiredStatus"] == "run"]
    problems += _coverage_problems(cl, specs, live, completed)
    # counts, names, datacenters of the services and every node's
    # capacity as c2m-10k has them, on the cluster with the evicted
    # fillers taken out and the system jobs' allocations put in (they
    # share one name, so they are not c2m-10k's to count)
    after = copy.copy(cl)
    after.used0 = cl.used0.copy()
    for row, history in placed.items():
        after.used0[row] -= sum(len(p.evicted) for p in history) \
            * cl.filler_demand
    services = {j: s for j, s in specs.items() if not s.system}
    for s in live:
        spec, row = specs.get(s["JobID"]), cl.index.get(s["NodeID"])
        if spec is not None and spec.system and row is not None:
            after.used0[row] += spec.demand
    base = c2m.compare(after, services,
                       [s for s in stubs if s["JobID"] in services], [],
                       completed & set(services),
                       {"violations": limits["violations"]})
    n_problems = base["compared"]["violations"]["value"] + len(problems)
    problems = base["problems"] + problems

    gaps, worst = [], None
    job_gaps: dict = {}
    for a in full:
        spec = specs.get(a["job_id"])
        row = cl.index.get(a["node_id"])
        if spec is None or row is None or a["desired_status"] != "run":
            continue
        history = placed.get(row, ())
        me = next((p for p in history if p.id == a["id"]), None)
        got = {m["node_id"]: m["norm_score"] for m in
               (a.get("metrics") or {}).get("score_meta", ())
               }.get(a["node_id"])
        gap = np.inf
        if me is not None and got is not None and np.isfinite(got):
            me.index = a["create_index"]
            gap = min((abs(float(w) - got) for w in
                       _wanted(cl, specs, a, me, history, row)),
                      default=np.inf)
        gaps.append(gap)
        job_gaps.setdefault(spec.id, []).append(gap)
        if worst is None or gap > worst[0]:
            worst = (gap, a["name"], a["node_id"], got)
    gaps = np.array(gaps)

    bad = [np.mean(np.array(v) > c2m.SCORE_TOL) > c2m.JOB_SHARE
           for v in job_gaps.values()]
    numbers = {"violations": n_problems,
               "unexplained_jobs_share": float(np.mean(bad)) if bad else 1.0}
    evicted = [len(p.evicted) for h in placed.values() for p in h]
    return {
        "correct": all(numbers[k] <= limits[k] for k in limits),
        "compared": {k: {"value": numbers[k], "limit": limits[k]}
                     for k in limits},
        "allocations_compared": int(gaps.size),
        "nodes_read": len(seen["nodes"]),
        "readback_s": round(seen.get("seconds", 0.0), 1),
        "placements": len(evicted),
        "evictions": int(sum(evicted)),
        "problems": problems[:5],
        "worst_score": worst,
        "gaps": gaps,
    }


# ------------------------------------------- the reference as a scheduler

def place_reference(cl, specs: list, precision: str = "float64",
                    delta: int = DELTA, highest_first: bool = False,
                    skip_every: int = 0, drop_rack: bool = False):
    """The reference put in the program's place: the system jobs `specs`
    in order, each on every node of its scope, evicting where the ask
    does not fit, every score rounded to `precision`, answers in the
    shape the HTTP API gives them.  `delta` and `highest_first` are the
    search's; `skip_every` leaves every so-manieth node of a scope out
    and `drop_rack` places a job of one rack on every rack (the faults
    the controls are for).  Returns (stubs, full, seen)."""
    q = c2m.quantizer(precision)
    fd = cl.filler_demand
    used = cl.used0.copy()
    prio = cl.pre_prio.reshape(cl.n, cl.per_node)
    res = np.broadcast_to(fd, (cl.n, cl.per_node, 2))
    alive = np.ones_like(prio, bool)
    stubs, full = [], []
    lists: dict = {}
    index = 1_000_000
    for spec in specs:
        if not spec.system:
            raise ValueError(f"{spec.id}: the controls place system jobs")
        shape = spec.shape
        if drop_rack:
            shape = {k: v for k, v in shape.items() if k != "rack"}
        on = scope(cl, shape)
        if skip_every:
            on[np.flatnonzero(on)[skip_every - 1::skip_every]] = False
        index += 2
        spec.registered = index - 1
        fits = ((used + spec.demand) <= cl.cap).all(axis=1)
        met, picked = search(cl.cap - used, spec.demand, res, prio, alive,
                             spec.priority, delta, highest_first)
        sc = np.where(fits, c2m.fit_score(cl.cap, used + spec.demand, q),
                      pre._scores(cl.cap, used, spec.demand, fd, prio,
                                  picked, q))
        name = f"{spec.id}.g0[0]"
        for r in np.flatnonzero(on & (fits | met)):
            aid = f"{spec.id}.g0.{r}"
            node = cl.node_ids[r]
            stubs.append({"ID": aid, "JobID": spec.id, "TaskGroup": "g0",
                          "NodeID": node, "Name": name,
                          "EvalID": f"eval-{spec.id}",
                          "DesiredStatus": "run", "ModifyIndex": index})
            full.append({"id": aid, "job_id": spec.id, "task_group": "g0",
                         "eval_id": f"eval-{spec.id}", "name": name,
                         "node_id": node, "desired_status": "run",
                         "create_index": index,
                         "metrics": {"score_meta": [{
                             "node_id": node,
                             "norm_score": round(float(sc[r]), 6)}]}})
            lists.setdefault(node, []).append(full[-1])
            for k in np.flatnonzero(picked[r]):
                lists[node].append({
                    "id": cl.pre_ids[r * cl.per_node + k],
                    "job_id": cl.job_ids[cl.pre_job[r * cl.per_node + k]],
                    "node_id": node, "desired_status": "evict",
                    "preempted_by_allocation": aid})
            alive[r] &= ~picked[r]
            used[r] += spec.demand - picked[r].sum() * fd
    for node, listed in lists.items():
        r = cl.index[node]
        listed.extend({"id": cl.pre_ids[r * cl.per_node + k],
                       "job_id": cl.job_ids[cl.pre_job[r * cl.per_node + k]],
                       "node_id": node, "desired_status": "run"}
                      for k in np.flatnonzero(alive[r]))
    return stubs, full, {"nodes": lists}


def controls(cl, specs: list) -> dict:
    """The reference in the program's place, held to `compare`, on the
    run's own jobs: the warm pass's rack job (the first system spec's
    shape on rack `r0`, as the traffic file has `pool`), then `specs`.
    `sound` (float32) has to pass; `control` (bfloat16, the step below
    the float32 the configuration states) must not, by its scores; nor
    `highest_first`, `node_skipped` (every hundredth node of a scope
    left out) and `scope_dropped` (the rack's job on every rack), by
    `violations`.  `delta_dropped` (any lower priority may go) is read
    and passes: the lowest tier goes first, every node keeps two
    fillers of tiers 20 / 35 and no node gives more than two, so tier
    45 is never reached; the delta is `preempt-10k.tiers`'s to hold."""
    first = next(s for s in specs if s.system)
    run = [JobSpec("c-pool", first.namespace, dict(first.shape, rack="r0"))]
    run += specs
    by_id = {s.id: s for s in run}
    out = {}
    for name, kw in (
            ("sound", dict(precision="float32")),
            ("control", dict(precision="bfloat16")),
            ("highest_first", dict(precision="float32",
                                   highest_first=True)),
            ("delta_dropped", dict(precision="float32", delta=1)),
            ("node_skipped", dict(precision="float32", skip_every=100)),
            ("scope_dropped", dict(precision="float32", drop_rack=True))):
        stubs, full, seen = place_reference(cl, run, **kw)
        out[name] = compare(cl, by_id, stubs, full, set(by_id), seen)
    return out
