"""The plain reference and the comparison that decides `correct`.

Float64 numpy; imports nothing of the program and takes nothing the
program made: the cluster comes from `cluster.Cluster` (the seed), the
jobs from the traffic file, and from the program only its answers, the
allocations read back over HTTP once the window has closed.

What is compared (each number beside a limit of its own, `LIMITS`):

* `violations`: exact.  A completed job has a group off its count, an
  allocation name twice, an allocation on a node outside the job's
  datacenters or unknown, or a node whose allocations (preload + every
  live allocation, summed here) exceed its cpu or memory.
* `unexplained_jobs_share`: the share of the sampled jobs of which more
  than `JOB_SHARE` of the allocations carry a reported score that the
  reference cannot explain.  The device reports, for
  the chosen node, its float32 score rounded to 6 places
  (`metrics.score_meta`); the reference scores that node in float64.  The
  score depends on the node's usage when the scheduler looked, and which
  concurrent plans it had seen is queue timing.  So the reference takes
  every usage the node can have had.  The job was scored after it was
  registered (raft index R, which the register call returned to the
  client) and before its plan committed (index C, the allocation's
  `create_index`).  So: the preload, plus everything committed on the
  node more than `STALE` indexes before R (it was in the state store,
  and in the device's copy of it, which was seen to trail the store by
  25 indexes in a loaded rehearsal), plus any number of the
  allocations that committed later than that and whose own job was
  registered by C (they may have been committed, in flight, or not yet
  scored), a small lattice (per demand shape, 0..count).  The gap is the least over that lattice and over the
  two places a score is reported from (the bulk wavefront reports the
  node's score after the group's run, the chained scan the score of the
  placement itself).  An allocation is explained when the gap is at most
  `SCORE_TOL`.  Nothing here asks which of two concurrent plans committed
  first or which kernel served the eval.  One more thing moves a node's
  usage in sound runs: when the applier rejects part of a plan, the
  rejected placements sat in the engine's in-flight overlay until then,
  and whatever was scored meanwhile saw usage that never reached the
  state store.  The lattice therefore also holds up to `GHOST` such
  allocations of each shape the mix sends.  A share of jobs and not a
  maximum over allocations, because a stall can put a whole plan in front
  of a state that none of this rebuilds (one 1,200-allocation job was
  scored against a device copy 25 indexes old), and one such job must
  not decide the run: what a lower precision does, it does to every job.
* `misplaced_jobs_share`: the share of the sampled jobs of which more
  than `JOB_SHARE` of the explained first placements on a node, in a
  group's first plan, lie more than `REGRET_TOL` below the best score
  that any node could have offered at that moment which the group never
  used and which ends with room for `GHOST` + 1 more of them (so that no
  uncommitted usage can have made it look full; its usage only grows, and
  binpack scores grow with usage, so its score at the preload's usage is
  a floor).  A placement on a worse node than that has regret above 0.
  Where later plans of the same eval placed `r` of the group's
  allocations again (the applier rejected that many of this plan's), the
  `r` best such nodes are left out: they may be the rejected ones.
"""
from __future__ import annotations

import re

import numpy as np

# set from readings, PERF.md section 2 ("How `correct` is decided")
LIMITS = {"violations": 0, "unexplained_jobs_share": 0.25,
          "misplaced_jobs_share": 0.25}
JOB_SHARE = 0.10       # a job is unexplained (misplaced) above this share
SCORE_TOL = 5e-6
REGRET_TOL = 5e-5
STALE = 64              # raft indexes by which the device's view may trail
GHOST = 8               # uncommitted allocations of each shape a node may have shown
_LATTICE_MAX = 200_000
_NAME_INDEX = re.compile(r"\[(\d+)\]$")


def exact(x):
    return x


def quantizer(name: str):
    """Rounding applied after every arithmetic step of the scoring stack:
    `float64` (none), `float32`, `bfloat16`.  The lower ones are what the
    control computes in."""
    if name == "float64":
        return exact
    if name == "float32":
        return lambda x: np.asarray(x, np.float32).astype(np.float64)
    if name == "bfloat16":
        import ml_dtypes
        return lambda x: np.asarray(x, np.float32).astype(
            ml_dtypes.bfloat16).astype(np.float64)
    raise ValueError(f"unknown precision {name!r}")


# ------------------------------------------------------------ the scores

def fit_score(cap, util, q=exact):
    """ScoreFitBinPack (nomad/structs/funcs.go, the 10^x form) over
    [..., 2] cpu/memory, normalised to [0, 1]."""
    frac = q(1.0 - q(util / cap))
    total = q(np.power(10.0, frac).sum(axis=-1))
    return q(np.clip(q(20.0 - total), 0.0, 18.0) / 18.0)


def spread_boost(counts, rack):
    """Even spread over the rack attribute (scheduler/spread.go, no
    targets): `counts[r]` allocations of the group per rack so far."""
    placed = counts > 0
    if not placed.any():
        return np.zeros(np.shape(rack))
    minc = counts[placed].min()
    maxc = counts[placed].max()
    cur = counts[rack]
    at_min = (-1.0 if minc == maxc else (maxc - minc) / minc)
    return np.where(cur != minc, (minc - cur) / minc, at_min)


def total_score(fit, coll, desired, aff=0.0, boost=0.0, q=exact):
    """rank.go's normalisation: the mean over the scorers that spoke
    (binpack always; job anti-affinity when the group already has an
    allocation on the node; affinity and spread when non-zero)."""
    fit, coll, aff, boost = np.broadcast_arrays(
        np.asarray(fit, np.float64), np.asarray(coll, np.float64),
        np.asarray(aff, np.float64), np.asarray(boost, np.float64))
    has_coll = coll > 0
    total = q(fit + np.where(has_coll, q(-(coll + 1.0) / max(desired, 1)), 0.0))
    total = q(total + aff)
    total = q(total + boost)
    n = 1.0 + has_coll + (aff != 0.0) + (boost != 0.0)
    return q(total / n)


# ----------------------------------------------------------- job records

class JobSpec:
    """What the benchmark sent: enough to score the job's placements.
    `registered` is the raft index the register call returned."""

    def __init__(self, job_id, namespace, shape, registered=0):
        self.id, self.namespace, self.shape = job_id, namespace, shape
        self.registered = registered
        self.groups = {f"g{g}": shape["count"]
                       for g in range(shape.get("groups", 1))}
        self.demand = np.array([shape["cpu"], shape["memory_mb"]], np.float64)
        self.dcs = set(shape["datacenters"])
        self.spread = bool(shape.get("spread"))
        self.aff_dc = shape.get("affinity_dc")
        self.allocs = sum(self.groups.values())


def _affinity(cl, spec, rows):
    if not spec.aff_dc:
        return np.zeros(np.shape(rows))
    return (cl.dc[rows] == spec.aff_dc).astype(np.float64)


def _slot(name: str) -> int:
    m = _NAME_INDEX.search(name)
    return int(m.group(1)) if m else 0


# -------------------------------------------------------- the comparison

def _lattice(free: dict) -> np.ndarray:
    """Every usage [P, 2] that `free` can add: per demand shape any count
    from 0 to the number given."""
    out = np.zeros((1, 2))
    for dem, cnt in free.items():
        axis = np.arange(cnt + 1)[:, None] * np.array(dem)[None, :]
        out = (out[:, None, :] + axis[None, :, :]).reshape(-1, 2)
        if len(out) > _LATTICE_MAX:
            raise RuntimeError(f"usage lattice of {len(out)} points")
    return out


def compare(cl, specs: dict, stubs: list, full: list, completed: set,
            limits: dict = LIMITS) -> dict:
    """`stubs`: every allocation the warm pass and the window left, as
    the HTTP list gives them ({ID, JobID, TaskGroup, NodeID, EvalID, Name,
    DesiredStatus, ModifyIndex}).  `full`: the sampled jobs' allocations
    with `metrics`.  `completed`: ids of jobs the client saw placed.
    Returns the numbers compared, each beside its limit, and `correct`."""
    problems = []
    live = [s for s in stubs if s["DesiredStatus"] == "run"]
    used = cl.used0.copy()
    per_node: dict = {}       # row -> [(commit, its job's register, demand, id)]
    per_group: dict = {}      # (job, tg) -> [(row, eval, commit index)]
    names = set()
    for s in live:
        spec = specs.get(s["JobID"])
        row = cl.index.get(s["NodeID"])
        if spec is None or row is None:
            problems.append(f"allocation {s['ID']}: unknown job or node")
            continue
        if cl.dc[row] not in spec.dcs:
            problems.append(f"allocation {s['ID']} of {spec.id} in "
                            f"{cl.dc[row]}, outside {sorted(spec.dcs)}")
        if (s["JobID"], s["Name"]) in names:
            problems.append(f"allocation name {s['Name']} twice")
        names.add((s["JobID"], s["Name"]))
        used[row] += spec.demand
        per_node.setdefault(row, []).append(
            (s["ModifyIndex"], spec.registered, tuple(spec.demand), s["ID"]))
        per_group.setdefault((s["JobID"], s["TaskGroup"]), []).append(
            (row, s["EvalID"], s["ModifyIndex"]))
    over = np.flatnonzero((used > cl.cap).any(axis=1))
    if over.size:
        problems.append(f"{over.size} node(s) over capacity, e.g. #{over[0]}: "
                        f"{used[over[0]].tolist()} > {cl.cap[over[0]].tolist()}")
    for jid in completed:
        spec = specs[jid]
        for tg, want in spec.groups.items():
            got = len(per_group.get((jid, tg), ()))
            if got != want:
                problems.append(f"job {jid} group {tg}: {got} of {want}")

    room: dict = {}           # demand -> (bool[N] room at the end, floor)

    def room_for(spec):
        key = tuple(spec.demand)
        if key not in room:
            room[key] = (((used + (GHOST + 1) * spec.demand)
                          <= cl.cap).all(axis=1),
                         fit_score(cl.cap, cl.used0 + spec.demand))
        return room[key]

    gaps, regrets, worst = [], [], None
    job_gaps: dict = {}
    job_regrets: dict = {}
    ghosts = _lattice({dem: GHOST for dem in
                       sorted({tuple(sp.demand) for sp in specs.values()})})
    plans: dict = {}          # one plan's allocations of one group
    for a in full:
        if a["desired_status"] == "run":
            plans.setdefault((a["job_id"], a["task_group"], a["eval_id"],
                              a["create_index"]), []).append(a)
    for (jid, tg, ev, index), allocs in plans.items():
        spec = specs[jid]
        allocs.sort(key=lambda a: _slot(a["name"]))
        d, desired = spec.demand, spec.groups[tg]
        rows = [cl.index[a["node_id"]] for a in allocs]
        ids = {a["id"] for a in allocs}
        mine: dict = {}
        for r in rows:
            mine[r] = mine.get(r, 0) + 1
        earlier: dict = {}    # row -> the group's allocations already there
        retried = 0           # placed again later: this plan's rejected part
        for r, _e, idx in per_group.get((jid, tg), ()):
            if idx < index:
                earlier[r] = earlier.get(r, 0) + 1
            elif idx > index:
                retried += 1
        has_room, floor = room_for(spec)
        unused = np.isin(cl.dc, sorted(spec.dcs)) & has_room
        unused[list(mine)] = False
        unused[list(earlier)] = False
        aff_all = _affinity(cl, spec, np.arange(cl.n))
        counts = np.zeros(cl.cfg["racks"])
        if spec.spread:
            for r, n in earlier.items():
                counts[cl.rack[r]] += n
        seen: dict = {}
        for a, row in zip(allocs, rows):
            got = {m["node_id"]: m["norm_score"] for m in
                   (a.get("metrics") or {}).get("score_meta", ())
                   }.get(a["node_id"])
            k_before = seen.get(row, 0)
            seen[row] = k_before + 1
            c0 = earlier.get(row, 0)
            cap = cl.cap[row]
            settled = cl.used0[row].copy()
            free: dict = {}
            for idx, reg, dem, aid in per_node[row]:
                if aid in ids:
                    continue
                if idx < spec.registered - STALE:
                    settled += dem
                elif reg <= index:
                    free[dem] = free.get(dem, 0) + 1
            lat = (settled + _lattice(free)[:, None, :]
                   + ghosts[None, :, :]).reshape(-1, 2)
            aff = aff_all[row]
            boost = spread_boost(counts, cl.rack[row]) if spec.spread else 0.0
            # the placement itself (what the chained scan reports)
            u_pre = lat + (k_before + 1) * d
            pre = total_score(fit_score(cap, u_pre), c0 + k_before, desired,
                              aff, boost)
            pre = np.where((u_pre <= cap).all(axis=1), pre, -np.inf)
            # one more after the group's run (what the bulk kernel reports)
            u_post = lat + (mine[row] + 1) * d
            post = total_score(fit_score(cap, u_post), c0 + mine[row],
                               desired, aff)
            post = np.where((u_post <= cap).all(axis=1), post, -np.inf)
            both = np.concatenate([pre, post])
            if got is None:
                err = np.full(both.shape, np.inf)
            elif np.isinf(got):
                err = np.where(np.isinf(both), 0.0, np.inf)
            else:
                err = np.where(np.isfinite(both), np.abs(both - got), np.inf)
            gap = float(err.min())
            gaps.append(gap)
            job_gaps.setdefault(jid, []).append(gap)
            if worst is None or gap > worst[0]:
                worst = (gap, a["name"], got)
            if k_before == 0 and not earlier and gap <= SCORE_TOL \
                    and unused.any():
                # selection, for a group's first plan (what a retry after a
                # partial commit knew of the first plan's allocations is
                # timing): among the usages that explain the reported
                # score, the one kindest to the program
                ok = np.flatnonzero(err <= SCORE_TOL) % len(lat)
                sel = total_score(fit_score(cap, lat[ok] + d), c0, desired,
                                  aff, boost).max()
                b = spread_boost(counts, cl.rack) if spec.spread else 0.0
                offers = total_score(floor, 0, desired, aff_all, b)[unused]
                # the applier may have rejected up to `retried` nodes of
                # this plan: they look unused and were chosen
                if offers.size > retried:
                    best = np.partition(offers, -1 - retried)[-1 - retried]
                    regrets.append(float(best - sel))
                    job_regrets.setdefault(jid, []).append(regrets[-1])
            if spec.spread:
                counts[cl.rack[row]] += 1
    gaps, regrets = np.array(gaps), np.array(regrets)

    def jobs_over(per_job: dict, tol: float) -> float:
        bad = [np.mean(np.array(v) > tol) > JOB_SHARE
               for v in per_job.values()]
        return float(np.mean(bad)) if bad else 0.0

    numbers = {
        "violations": len(problems),
        "unexplained_jobs_share": jobs_over(job_gaps, SCORE_TOL)
        if job_gaps else 1.0,
        "misplaced_jobs_share": jobs_over(job_regrets, REGRET_TOL),
    }
    return {
        "correct": all(numbers[k] <= limits[k] for k in limits),
        "compared": {k: {"value": numbers[k], "limit": limits[k]}
                     for k in limits},
        "allocations_compared": int(gaps.size),
        "placements_ranked": int(regrets.size),
        "problems": problems[:5],
        "worst_score": worst,
        "gaps": gaps, "regrets": regrets,
    }


# ------------------------------------------- the reference as a scheduler

def better_half(cl, spec) -> np.ndarray:
    """bool[N]: of the nodes in the job's datacenters, the half that
    scores higher for the job's demand at the preload's usage."""
    rows = np.flatnonzero(np.isin(cl.dc, sorted(spec.dcs)))
    floor = fit_score(cl.cap[rows], cl.used0[rows] + spec.demand)
    out = np.zeros(cl.n, bool)
    out[rows[np.argsort(-floor, kind="stable")[: len(rows) // 2]]] = True
    return out


def place_reference(cl, specs: list, precision: str = "float64",
                    hide_better_half: bool = False):
    """The reference put in the program's place: sequential greedy
    placement of `specs` in order, every score computed with `precision`
    rounding, answers in the shape the HTTP API gives them.  In float32
    it stands in for a sound program; in bfloat16 it is the control, the
    step below the float32 the configuration states.  With
    `hide_better_half` its argmax does not see `better_half`'s nodes (an
    argmax over part of the nodes: every score reported is right, the
    choice is not).  Returns (stubs, full)."""
    q = quantizer(precision)
    used = cl.used0.copy()
    stubs, full = [], []
    index = 1_000_000
    for spec in specs:
        d = spec.demand
        feasible = np.isin(cl.dc, sorted(spec.dcs))
        if hide_better_half:
            feasible &= ~better_half(cl, spec)
        aff = _affinity(cl, spec, np.arange(cl.n))
        index += 1
        spec.registered = index
        for tg, count in spec.groups.items():
            index += 1
            coll = np.zeros(cl.n)
            counts = np.zeros(cl.cfg["racks"])
            rows, reported = [], []
            for _ in range(count):
                util = used + d
                fits = (util <= cl.cap).all(axis=1) & feasible
                boost = spread_boost(counts, cl.rack) if spec.spread else 0.0
                sc = total_score(fit_score(cl.cap, util, q), coll, count,
                                 aff, boost, q)
                sc = np.where(fits, sc, -np.inf)
                r = int(np.argmax(sc))
                if not np.isfinite(sc[r]):
                    break
                rows.append(r)
                reported.append(sc[r])
                used[r] += d
                coll[r] += 1
                counts[cl.rack[r]] += 1
            if not spec.spread:
                rr = np.array(rows)
                u = used[rr] + d
                post = total_score(fit_score(cl.cap[rr], u, q), coll[rr],
                                   count, aff[rr], 0.0, q)
                reported = np.where((u <= cl.cap[rr]).all(axis=1), post,
                                    -np.inf)
            for i, (r, s) in enumerate(zip(rows, reported)):
                aid = f"{spec.id}.{tg}.{i}"
                name = f"{spec.id}.{tg}[{i}]"
                stubs.append({"ID": aid, "JobID": spec.id, "TaskGroup": tg,
                              "NodeID": cl.node_ids[r], "Name": name,
                              "EvalID": f"eval-{spec.id}",
                              "DesiredStatus": "run", "ModifyIndex": index})
                full.append({"id": aid, "job_id": spec.id, "task_group": tg,
                             "eval_id": f"eval-{spec.id}", "name": name,
                             "node_id": cl.node_ids[r],
                             "desired_status": "run", "create_index": index,
                             "metrics": {"score_meta": [{
                                 "node_id": cl.node_ids[r],
                                 "norm_score": round(float(s), 6)}]}})
    return stubs, full


def controls(cl, specs: list) -> dict:
    """{"sound", "control", "half_hidden": verdict}: the reference in the
    program's place in float32, which has to pass; in bfloat16, the step
    below the float32 the configuration states, which must not; and in
    float32 with the better half of the nodes hidden from its argmax,
    which must not either."""
    by_id = {s.id: s for s in specs}
    out = {}
    for name, precision, hide in (("sound", "float32", False),
                                  ("control", "bfloat16", False),
                                  ("half_hidden", "float32", True)):
        stubs, full = place_reference(cl, specs, precision, hide)
        out[name] = compare(cl, by_id, stubs, full, set(by_id))
    return out
