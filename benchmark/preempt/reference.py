"""The plain reference of `preempt-10k` and the comparison that decides
`correct`.

Float64 numpy; imports nothing of the program.  Nodes, fillers and
their tiers come from `benchmark.preempt.cluster.Cluster` (the seed), the
jobs from the traffic file, and from the program only its answers: the
allocations read back over HTTP once the window has closed, the sampled
jobs' in full, the cluster's list of every allocation and, from the
node's side, the allocation list of every node that took a placement of
the run or lost a filler (`readback`), which is where an evicted filler
says who evicted it.  What is the same as in `c2m-10k` is
taken from `benchmark.reference`: ScoreFitBinPack, the limits and
tolerances, the rule on which commits a reported score may have seen.

What this module adds is preemption's meaning, after the upstream's
scheduler/preemption.go (PreemptForTaskGroup, basicResourceDistance,
filterAndGroupPreemptibleAllocs, the superset filter) and rank.go
(PreemptionScoringIterator), in `search`:

* an allocation may go only if its job's priority is at least
  `DELTA` below the asking job's;
* on a node, candidates go lowest priority tier first and, within a
  tier, closest first by the distance sqrt(sum(((needed - res) /
  needed)^2)) over the dimensions still needed, recomputed as the need
  shrinks, until what is free and what is freed cover the ask;
* then, largest first, a pick is dropped again if the rest still covers
  the ask (the superset filter);
* the placement scores the mean of ScoreFitBinPack after the eviction
  and the logistic score of the evicted set's net priority (max + sum /
  max; 1 / (1 + e^(0.0048 (x - 2048)))), and goes to the best node.

The three numbers compared are `c2m-10k`'s, under its limits:

* `violations` also counts a filler that is not listed or not on its
  node, an allocation that is neither a filler nor the run's, and, on
  every node read back (every node on which anything stopped running
  is): an evicted filler that no live placement of the run on its node
  names in `preempted_by_allocation`; one less than `DELTA` below the job that
  evicted it; one of a higher tier where a lower tier's filler stayed to
  the end and would have given the room; one whose room the rest of its
  set, with what was free when the plan committed, gave already.  A node
  over capacity is counted with the evicted fillers taken out.
* `unexplained_jobs_share` holds a placement's reported score to the
  reference's score of that node with that evicted set, under every
  usage the node can have shown (other placements of the run on it,
  each with its own evictions, seen or not as `c2m-10k`'s rule says).
* `misplaced_jobs_share` ranks an explained placement of a group's
  first plan against the best offer of the nodes the run never touched,
  whose state is the preload's and so known without timing.
"""
from __future__ import annotations

import copy

import numpy as np

from benchmark import reference as c2m

LIMITS = dict(c2m.LIMITS)
DELTA = 10                # preemption.go: a job evicts only 10 or more below
_SUBSETS_MAX = 10         # other placements on a node a score may have seen


# ------------------------------------------------------ preemption's meaning

def net_priority(prios):
    """max + sum / max of an evicted set's job priorities, over the last
    axis (a 0 stands for "not evicted"); 0 for an empty set."""
    prios = np.asarray(prios, np.float64)
    if prios.shape[-1] == 0:
        return np.zeros(prios.shape[:-1])
    top = prios.max(axis=-1)
    return np.where(top > 0, top + prios.sum(axis=-1) / np.maximum(top, 1),
                    0.0)


def logistic(net):
    return 1.0 / (1.0 + np.exp(0.0048 * (np.asarray(net, np.float64)
                                         - 2048.0)))


def search(free, ask, res, prio, alive, job_prio, delta=DELTA,
           highest_first=False):
    """Every node at once.  `free` [N, 2] is what each node has left,
    `ask` [2] the demand; `res` [N, A, 2], `prio` [N, A] and `alive`
    [N, A] are the node's candidates.  Returns (met bool[N], picked
    bool[N, A]): where the ask does not fit as it is and evicting
    `picked` makes it fit.  `delta` and `highest_first` are for the
    controls: which priorities may go, and which tier goes first."""
    free = np.asarray(free, np.float64)
    ask = np.asarray(ask, np.float64)
    res = np.asarray(res, np.float64)
    n, width = prio.shape
    may_go = alive & (job_prio - prio >= delta)
    tier_key = np.where(highest_first, -prio, prio).astype(np.float64)
    picked = np.zeros((n, width), bool)
    avail = free.copy()
    needed = np.broadcast_to(ask, free.shape).copy()
    fits = (free >= ask).all(axis=1)
    met = fits.copy()
    rows = np.arange(n)
    for _ in range(width):
        left = may_go & ~picked
        key = np.where(left, tier_key, np.inf)
        tier = left & (key == key.min(axis=1, keepdims=True))
        need = needed[:, None, :]
        with np.errstate(divide="ignore", invalid="ignore"):
            coord = np.where(need > 0.0, (need - res) / need, 0.0)
        dist = np.where(tier, np.sqrt((coord * coord).sum(axis=2)), np.inf)
        take = dist.argmin(axis=1)
        go = tier.any(axis=1) & ~met
        picked[rows[go], take[go]] = True
        freed = np.where(go[:, None], res[rows, take], 0.0)
        avail += freed
        needed -= freed
        met |= (avail >= ask).all(axis=1)
    met &= ~fits
    picked &= met[:, None]
    # the superset filter: largest first, drop what the rest covers
    size = np.where(picked, res.sum(axis=2), -np.inf)
    for k in np.argsort(-size, axis=1, kind="stable").T:
        on = picked[rows, k]
        rest = picked.copy()
        rest[rows, k] = False
        room = free + (res * rest[:, :, None]).sum(axis=1)
        drop = on & rest.any(axis=1) & (room >= ask).all(axis=1)
        picked[rows[drop], k[drop]] = False
    return met, picked


def score(cap, used_after, evicted_prios, q=c2m.exact):
    """(mean, binpack, preemption) of one preempting placement."""
    fit = c2m.fit_score(cap, used_after, q)
    pre = q(logistic(net_priority(evicted_prios)))
    return q((fit + pre) / 2.0), fit, pre


# ----------------------------------------------------------- job records

class JobSpec(c2m.JobSpec):
    """What the benchmark sent, with the tier it stands in."""

    def __init__(self, job_id, namespace, shape, registered=0):
        super().__init__(job_id, namespace, shape, registered)
        self.priority = shape["priority"]


# ------------------------------------------------------------- readback

def readback(get, records) -> dict:
    """{"allocs": [(id, job, node, desired status)] of every allocation
    the cluster lists, in all namespaces, once the window has closed;
    "nodes": {node id: its allocation list} as `/v1/node/<id>/allocations`
    gives it, for every node that holds an allocation the warm pass or the
    window left and for every node on which anything listed no longer
    runs: an eviction is read wherever it happened, also on a node whose
    placement the applier rejected; "seconds"}."""
    import time
    t0 = time.monotonic()
    listed = get("/v1/allocations", {"namespace": "*"})
    nodes = {s["NodeID"] for rec in records for s in rec.stubs
             if s["DesiredStatus"] == "run"}
    nodes |= {s["NodeID"] for s in listed if s["DesiredStatus"] != "run"}
    return {"allocs": [(s["ID"], s["JobID"], s["NodeID"], s["DesiredStatus"])
                       for s in listed],
            "nodes": {n: get(f"/v1/node/{n}/allocations")
                      for n in sorted(nodes)},
            "seconds": time.monotonic() - t0}


# -------------------------------------------------------- the comparison

class _Placed:
    """One placement of the run on one node, as the node's list has it."""
    __slots__ = ("id", "job", "index", "slot", "evicted")

    def __init__(self, a):
        self.id, self.job = a["id"], a["job_id"]
        self.index, self.slot = a["create_index"], c2m._slot(a["name"])
        self.evicted = []          # filler slots it names


def _node_histories(cl, specs: dict, seen: dict):
    """({row: [_Placed in commit order]}, {row: filler slots that stayed},
    problems) from the nodes' own lists."""
    problems, placed, stayed = [], {}, {}
    for node_id, listed in seen["nodes"].items():
        row = cl.index.get(node_id)
        if row is None:
            continue
        here = {a["id"]: _Placed(a) for a in listed
                if a["job_id"] in specs and a["desired_status"] == "run"}
        stay = []
        for a in listed:
            s = cl.filler.get(a["id"])
            if s is None:
                continue
            if a["desired_status"] == "run":
                stay.append(s)
            elif a.get("preempted_by_allocation") in here:
                here[a["preempted_by_allocation"]].evicted.append(s)
            else:
                problems.append(
                    f"filler {a['id']} on node #{row} is "
                    f"{a['desired_status']}, and no live placement of the "
                    f"run there names it")
        placed[row] = sorted(here.values(), key=lambda p: (p.index, p.slot))
        stayed[row] = stay
    return placed, stayed, problems


def _listing_problems(cl, specs: dict, seen: dict) -> list:
    """The cluster's own list against the preload: every filler is there,
    on its node, and runs unless its node was read (where
    `_node_histories` says who evicted it); nothing is listed but the
    fillers and the run's jobs.  The deployment leaves no node room for a
    filler (220 MHz free, 360 after two evictions for one placement), so
    a filler placed anew stands where the capacity check cannot see it."""
    problems = []
    fillers = set()
    for aid, job, node, status in seen["allocs"]:
        s = cl.filler.get(aid)
        if s is None:
            if job not in specs:
                problems.append(f"allocation {aid} of {job} on {node} is "
                                f"neither a filler nor the run's")
            continue
        fillers.add(s)
        if node != cl.node_ids[cl.pre_node[s]]:
            problems.append(f"filler {aid} is listed on {node}")
        elif status != "run" and node not in seen["nodes"]:
            problems.append(f"filler {aid} is {status} on a node not read")
    if len(fillers) != len(cl.filler):
        problems.append(f"{len(cl.filler) - len(fillers)} filler(s) of the "
                        f"preload are not listed")
    return problems


def _eviction_problems(cl, specs: dict, placed: dict, stayed: dict) -> list:
    problems = []
    fd = cl.filler_demand
    for row, history in placed.items():
        free = cl.cap[row] - cl.used0[row]
        low_stayed = min((cl.pre_prio[s] for s in stayed[row]),
                         default=np.inf)
        for p in history:
            spec = specs[p.job]
            prios = cl.pre_prio[p.evicted]
            for s, pr in zip(p.evicted, prios):
                if spec.priority - pr < DELTA:
                    problems.append(
                        f"filler of priority {pr} on node #{row} evicted "
                        f"for {spec.id} of priority {spec.priority}")
                elif low_stayed < pr:
                    # the fillers are of one size: the one that stayed
                    # would have given the same room
                    problems.append(
                        f"filler of priority {pr} on node #{row} evicted "
                        f"while one of {low_stayed} stayed")
            if p.evicted and (free + (len(p.evicted) - 1) * fd
                              >= spec.demand).all():
                problems.append(
                    f"{len(p.evicted)} eviction(s) on node #{row} for "
                    f"{spec.id} where one fewer gives the room")
            free = free + len(p.evicted) * fd - spec.demand
    return problems


def offers(cl, spec):
    """(met bool[N], score f64[N]) of `spec`'s ask on every node in the
    preload's state."""
    res = np.broadcast_to(cl.filler_demand, (cl.n, cl.per_node, 2))
    prio = cl.pre_prio.reshape(cl.n, cl.per_node)
    met, picked = search(cl.cap - cl.used0, spec.demand, res, prio,
                         np.ones_like(prio, bool), spec.priority)
    return met, _scores(cl.cap, cl.used0, spec.demand, cl.filler_demand,
                        prio, picked)


def _scores(cap, used, demand, fd, prio, picked, q=c2m.exact):
    """The preempting placement's score on every node, given its picks
    (of fillers of the one size `fd`)."""
    after = used - picked.sum(axis=1)[:, None] * fd + demand
    return score(cap, after, np.where(picked, prio, 0), q)[0]


def compare(cl, specs: dict, stubs: list, full: list, completed: set,
            seen: dict, limits: dict = LIMITS) -> dict:
    """`stubs`, `full`, `completed` as `benchmark.reference.compare`
    takes them; `seen` is what `readback` returned."""
    placed, stayed, problems = _node_histories(cl, specs, seen)
    problems += _eviction_problems(cl, specs, placed, stayed)
    if "allocs" in seen:          # the controls make no cluster-wide list
        problems += _listing_problems(cl, specs, seen)
    # counts, names, datacenters and capacity as c2m-10k has them, on the
    # cluster with the evicted fillers taken out
    after = copy.copy(cl)
    after.used0 = cl.used0.copy()
    for row, history in placed.items():
        after.used0[row] -= sum(len(p.evicted) for p in history) \
            * cl.filler_demand
    base = c2m.compare(after, specs, stubs, [], completed,
                       {"violations": limits["violations"]})
    n_problems = base["compared"]["violations"]["value"] + len(problems)
    problems = base["problems"] + problems

    touched = np.zeros(cl.n, bool)
    per_group: dict = {}      # (job, tg) -> [commit index]
    for s in stubs:
        if s["DesiredStatus"] == "run" and s["NodeID"] in cl.index:
            touched[cl.index[s["NodeID"]]] = True
            per_group.setdefault((s["JobID"], s["TaskGroup"]), []).append(
                s["ModifyIndex"])
    best: dict = {}           # shape -> the untouched nodes' offers

    def untouched_offers(spec):
        key = id(spec.shape)              # a mix's specs share its shapes
        if key not in best:
            met, sc = offers(cl, spec)
            best[key] = sc[met & ~touched & np.isin(cl.dc, sorted(spec.dcs))]
        return best[key]

    fd = cl.filler_demand
    gaps, regrets, worst = [], [], None
    job_gaps: dict = {}
    job_regrets: dict = {}
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
        gap, sel = np.inf, -np.inf
        if me is not None and got is not None and np.isfinite(got):
            # usage before this placement: the preload, what committed
            # on the node long before the job was registered, and any of
            # what committed since from jobs registered by this commit
            settled = cl.used0[row].copy()
            maybe = []
            coll = 0
            for p in history:
                if p is me:
                    continue
                net = specs[p.job].demand - len(p.evicted) * fd
                if p.index < spec.registered - c2m.STALE:
                    settled += net
                    coll += p.job == me.job
                elif specs[p.job].registered <= me.index:
                    maybe.append(net)
            maybe = maybe[:_SUBSETS_MAX]
            prios = cl.pre_prio[me.evicted]
            for mask in range(1 << len(maybe)):
                before = settled + sum(
                    (m for k, m in enumerate(maybe) if mask >> k & 1),
                    np.zeros(2))
                util = before - len(me.evicted) * fd + spec.demand
                if (util > cl.cap[row]).any():
                    continue
                if me.evicted:
                    want = score(cl.cap[row], util, prios)[0]
                else:
                    want = c2m.total_score(
                        c2m.fit_score(cl.cap[row], util), coll,
                        spec.groups[a["task_group"]])
                if abs(want - got) <= c2m.SCORE_TOL:
                    sel = max(sel, float(want))
                gap = min(gap, abs(float(want) - got))
        gaps.append(gap)
        job_gaps.setdefault(spec.id, []).append(gap)
        if worst is None or gap > worst[0]:
            worst = (gap, a["name"], got)
        first = min(per_group.get((spec.id, a["task_group"]), [0]))
        if gap <= c2m.SCORE_TOL and a["create_index"] == first:
            # a group's first plan, against the nodes nothing touched;
            # what later plans placed again may be what the applier
            # rejected of this one: that many best offers are left out
            retried = sum(i > first for i in
                          per_group[(spec.id, a["task_group"])])
            open_ = untouched_offers(spec)
            if open_.size > retried:
                top = np.partition(open_, -1 - retried)[-1 - retried]
                regrets.append(float(top - sel))
                job_regrets.setdefault(spec.id, []).append(regrets[-1])
    gaps, regrets = np.array(gaps), np.array(regrets)

    def jobs_over(per_job: dict, tol: float) -> float:
        bad = [np.mean(np.array(v) > tol) > c2m.JOB_SHARE
               for v in per_job.values()]
        return float(np.mean(bad)) if bad else 0.0

    numbers = {
        "violations": n_problems,
        "unexplained_jobs_share": jobs_over(job_gaps, c2m.SCORE_TOL)
        if job_gaps else 1.0,
        "misplaced_jobs_share": jobs_over(job_regrets, c2m.REGRET_TOL),
    }
    evicted = [len(p.evicted) for h in placed.values() for p in h]
    return {
        "correct": all(numbers[k] <= limits[k] for k in limits),
        "compared": {k: {"value": numbers[k], "limit": limits[k]}
                     for k in limits},
        "allocations_compared": int(gaps.size),
        "placements_ranked": int(regrets.size),
        "nodes_read": len(seen["nodes"]),
        "readback_s": round(seen.get("seconds", 0.0), 1),
        "evictions": int(sum(evicted)),
        "evicting_placements": int(np.count_nonzero(evicted)),
        "problems": problems[:5],
        "worst_score": worst,
        "gaps": gaps, "regrets": regrets,
    }


# ------------------------------------------- the reference as a scheduler

def place_reference(cl, specs: list, precision: str = "float64",
                    hide_better_half: bool = False, delta: int = DELTA,
                    highest_first: bool = False):
    """The reference put in the program's place: `specs` in order, each
    group's slots to the best nodes of one search over the cluster as the
    jobs before it left it (one node a slot, as one round of the
    program's search gives them), every score rounded to `precision`,
    answers in the shape the HTTP API gives them.  `hide_better_half`
    blinds the choice to the nodes whose offer in the preload's state is
    at or above the median, or below it by less than the comparison
    forgives (twice `REGRET_TOL`: most nodes of one memory size tie to
    within the evicted tiers' 4e-6), so that what is left is worse:
    right scores, wrong choice.  `delta` and `highest_first` are the
    search's.  Returns (stubs, full, seen)."""
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
        feasible = np.isin(cl.dc, sorted(spec.dcs))
        if hide_better_half:
            met0, offer0 = offers(cl, spec)
            feasible &= ~(met0 & (offer0 > np.median(offer0[met0])
                                  - 2 * c2m.REGRET_TOL))
        index += 1
        spec.registered = index
        for tg, count in spec.groups.items():
            index += 1
            met, picked = search(cl.cap - used, spec.demand, res, prio,
                                 alive, spec.priority, delta, highest_first)
            sc = np.where(met & feasible,
                          _scores(cl.cap, used, spec.demand, fd, prio,
                                  picked, q), -np.inf)
            order = np.argsort(-sc, kind="stable")[:count]
            for i, r in enumerate(order[np.isfinite(sc[order])]):
                aid = f"{spec.id}.{tg}.{i}"
                name = f"{spec.id}.{tg}[{i}]"
                node = cl.node_ids[r]
                stubs.append({"ID": aid, "JobID": spec.id, "TaskGroup": tg,
                              "NodeID": node, "Name": name,
                              "EvalID": f"eval-{spec.id}",
                              "DesiredStatus": "run", "ModifyIndex": index})
                full.append({"id": aid, "job_id": spec.id, "task_group": tg,
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
    """The reference in the program's place, held to `compare`: `sound`
    (float32) has to pass; `control` (bfloat16, the step below the
    float32 the configuration states) and `half_hidden` (right scores, a
    choice blind to the better half of the nodes) must not, nor
    `highest_first` (of the tiers that may go, the highest first) and
    `delta_dropped` (any lower priority may go)."""
    by_id = {s.id: s for s in specs}
    out = {}
    for name, kw in (
            ("sound", dict(precision="float32")),
            ("control", dict(precision="bfloat16")),
            ("half_hidden", dict(precision="float32",
                                 hide_better_half=True)),
            ("highest_first", dict(precision="float32",
                                   highest_first=True)),
            ("delta_dropped", dict(precision="float32", delta=1))):
        stubs, full, seen = place_reference(cl, specs, **kw)
        out[name] = compare(cl, by_id, stubs, full, set(by_id), seen)
    return out
