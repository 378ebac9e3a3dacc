"""The plain reference of `distinct-10k` and the comparison that decides
`correct`.

Float64 numpy, Python sets and `re`; imports nothing of the program.  The
cluster and what a `constraint` block selects by (a node's class, name,
kernel version, features and rack) come from
`benchmark.distinct.cluster.Cluster` (the seed), the jobs from the traffic
file, and from the program only its answers: the allocations read back
over HTTP once the window has closed (node ids are in the job lists, so
nothing else is read).  What is the same as in `c2m-10k` is taken from
`benchmark.reference`: ScoreFitBinPack, job anti-affinity, the rules on
which usage a reported score may have seen (its docstring), the limits and
tolerances.  What this module adds is the `constraint` block's meaning,
after the upstream's scheduler/feasible.go (`checkConstraint`,
`DistinctHostsIterator`, `DistinctPropertyIterator`) and
scheduler/propertyset.go:

* a static constraint compares the node's value of `attribute` with
  `value` by `operator`: `=`, `!=`, `regexp` (a search, as Go's
  MatchString), `version` (comma-separated comparisons of dotted
  integers), `set_contains` (every comma-separated member); a node
  without the attribute meets none of these but `!=` (`meets`);
* `distinct_hosts` at the job's level: no node takes two allocations of
  the job, whichever group; at a group's level: of that group;
* `distinct_property` with limit `value` (1 where left out): the
  allocations of its scope (the job's or the group's, as above) on nodes
  of one value of `attribute` number at most the limit, and a node
  without the attribute takes none;
* both are held slot by slot inside one eval, in the order of the
  allocations' name indices, group after group in the job's order, and
  started from the job's earlier allocations; neither adds a score: the
  mean is binpack's and, where the group already has an allocation on
  the node, job anti-affinity's.

The three numbers compared are `c2m-10k`'s, under its limits:

* `violations` also counts, over every live allocation of every job of
  the run (`_constraint_problems`): one on a node that fails a static
  constraint of its job or group, two of one `distinct_hosts` scope on
  one node, one more than the limit of a `distinct_property` scope on
  one value, one on a node without the attribute;
* `unexplained_jobs_share` holds the reported norm score to the
  reference's, as `c2m-10k` does for a scan placement;
* `misplaced_jobs_share` replays each sampled plan slot by slot with the
  reference's own host and value counts and ranks each placement against
  the nodes that were open to its slot (static constraints met, host not
  taken, value under its limit) which the plan never used and which end
  with room.
"""
from __future__ import annotations

import re

import numpy as np

from benchmark import reference as c2m

LIMITS = dict(c2m.LIMITS)
_DISTINCT = ("distinct_hosts", "distinct_property")
_KERNEL = {"attribute": "${attr.kernel.name}", "operator": "=",
           "value": "linux"}          # what jobs.build gives every job


# ------------------------------------------------------- the constraints

def attribute(cl, target: str):
    """The nodes' values of an interpolated `target`, one string a node,
    None where the node has none (`resolveTarget`)."""
    known = {
        "${attr.kernel.name}": lambda: ["linux"] * cl.n,
        "${node.datacenter}": lambda: [str(d) for d in cl.dc],
        "${node.class}": lambda: [str(c) for c in cl.node_class],
        "${node.unique.name}": lambda: list(cl.name),
        "${attr.kernel.version}": lambda: [str(k) for k in cl.kernel],
        "${meta.features}": lambda: [str(f) for f in cl.features],
        "${attr.rack}": lambda: [f"r{r}" for r in cl.rack],
    }
    return known[target]() if target in known else [None] * cl.n


def _version(text: str):
    """Dotted integers, or None where `text` is no such version."""
    parts = text.strip().lstrip("v").split(".")
    return tuple(int(p) for p in parts) \
        if all(p.isdigit() for p in parts) else None


def version_meets(have: str, wanted: str) -> bool:
    """`have` against comma-separated comparisons (">= 5.4, < 6"),
    missing parts read as 0 (go-version's Constraint.Check)."""
    v = _version(have)
    if v is None:
        return False
    for clause in wanted.split(","):
        m = re.fullmatch(r"\s*(>=|<=|!=|=|>|<|)\s*(\S+)\s*", clause)
        w = _version(m.group(2)) if m else None
        if w is None:
            return False
        n = max(len(v), len(w))
        a, b = v + (0,) * (n - len(v)), w + (0,) * (n - len(w))
        if not {">=": a >= b, "<=": a <= b, "!=": a != b, "=": a == b,
                "": a == b, ">": a > b, "<": a < b}[m.group(1)]:
            return False
    return True


def meets(operator: str, have, wanted: str) -> bool:
    """One node's value `have` (None: it has none) against `wanted`."""
    if operator == "!=":
        return have != wanted
    if have is None:
        return False
    if operator in ("=", "==", "is"):
        return have == wanted
    if operator == "regexp":
        return re.search(wanted, have) is not None
    if operator == "version":
        return version_meets(have, wanted)
    if operator == "set_contains":
        members = {s.strip() for s in have.split(",")}
        return all(s.strip() in members for s in wanted.split(","))
    raise ValueError(f"the reference does not read operator {operator!r}")


class JobSpec:
    """What the benchmark sent: enough to place and to score the job.
    Its allocations are scan slots (a `distinct_*` constraint keeps a
    group off the bulk path), which is what `spread` tells the harness's
    roofline count.  `registered` is the raft index the register call
    returned."""

    def __init__(self, job_id, namespace, shape, registered=0):
        self.id, self.namespace, self.shape = job_id, namespace, shape
        self.registered = registered
        self.dcs = set(shape["datacenters"])
        self.spread = True
        self.groups, self.demand = {}, {}
        self.static, self.hosts, self.props = {}, {}, {}
        job_level = list(shape.get("constraints", ()))
        for g in shape["task_groups"]:
            name = g["name"]
            self.groups[name] = g["count"]
            self.demand[name] = np.array([g["cpu"], g["memory_mb"]],
                                         np.float64)
            own = list(g.get("constraints", ()))
            self.static[name] = [_KERNEL] + [
                c for c in job_level + own if c["operator"] not in _DISTINCT]
            # a scope is the job's ("job") or the group's (its name)
            self.hosts[name] = (
                "job" if any(c["operator"] == "distinct_hosts"
                             for c in job_level)
                else name if any(c["operator"] == "distinct_hosts"
                                 for c in own) else None)
            self.props[name] = [
                (scope, c["attribute"], int(c.get("value") or 1))
                for scope, cs in (("job", job_level), (name, own))
                for c in cs if c["operator"] == "distinct_property"]
        self.allocs = sum(self.groups.values())


class World:
    """The cluster as the constraints of one run's jobs see it: a mask of
    the nodes each group may use (`static`), and a property's values as
    indices (`values`: -1 where the node lacks the attribute)."""

    def __init__(self, cl):
        self.cl = cl
        self._static: dict = {}
        self._values: dict = {}
        self._columns: dict = {}

    def _column(self, target: str):
        if target not in self._columns:
            self._columns[target] = attribute(self.cl, target)
        return self._columns[target]

    def static(self, spec, tg: str) -> np.ndarray:
        """bool[N]: the job's datacenters and every static constraint of
        the job and the group, each read once a distinct value."""
        key = (tuple(sorted(spec.dcs)), tuple(
            (c["attribute"], c["operator"], c["value"])
            for c in spec.static[tg]))
        if key not in self._static:
            ok = np.isin(self.cl.dc, sorted(spec.dcs))
            for target, operator, wanted in key[1]:
                column = self._column(target)
                verdict = {v: meets(operator, v, wanted) for v in set(column)}
                ok &= np.array([verdict[v] for v in column], bool)
            self._static[key] = ok
        return self._static[key]

    def values(self, target: str) -> np.ndarray:
        if target not in self._values:
            column = self._column(target)
            index = {v: i for i, v in enumerate(
                sorted({v for v in column if v is not None}))}
            self._values[target] = np.array(
                [-1 if v is None else index[v] for v in column], np.int64)
        return self._values[target]


class Scopes:
    """One job's `distinct_*` state: which rows each hosts scope holds,
    how many allocations each property scope has on each value."""

    def __init__(self, world: World, spec: JobSpec):
        self.world, self.spec = world, spec
        self.taken: dict = {}          # hosts scope -> set of rows
        self.counts: dict = {}         # (scope, attribute, limit) -> int[V]

    def _count(self, prop) -> np.ndarray:
        if prop not in self.counts:
            values = self.world.values(prop[1])
            self.counts[prop] = np.zeros(max(int(values.max()) + 1, 1),
                                         np.int64)
        return self.counts[prop]

    def open(self, tg: str) -> np.ndarray:
        """bool[N]: the nodes both constraints leave to the group now."""
        ok = np.ones(self.world.cl.n, bool)
        scope = self.spec.hosts[tg]
        if scope is not None and self.taken.get(scope):
            ok[list(self.taken[scope])] = False
        for prop in self.spec.props[tg]:
            values = self.world.values(prop[1])
            ok &= (values >= 0) & (self._count(prop)[values] < prop[2])
        return ok

    def why_not(self, tg: str, row: int) -> list:
        """The constraints that close `row` to one more of the group."""
        out = []
        scope = self.spec.hosts[tg]
        if scope is not None and row in self.taken.get(scope, ()):
            out.append(f"distinct_hosts of {scope}: node #{row} holds one")
        for prop in self.spec.props[tg]:
            v = self.world.values(prop[1])[row]
            if v < 0:
                out.append(f"distinct_property {prop[1]}: node #{row} has "
                           "no such attribute")
            elif self._count(prop)[v] >= prop[2]:
                out.append(f"distinct_property {prop[1]} of {prop[0]}: "
                           f"value #{v} holds its limit of {prop[2]}")
        return out

    def take(self, tg: str, row: int) -> None:
        scope = self.spec.hosts[tg]
        if scope is not None:
            self.taken.setdefault(scope, set()).add(row)
        for prop in self.spec.props[tg]:
            v = self.world.values(prop[1])[row]
            if v >= 0:
                self._count(prop)[v] += 1


# -------------------------------------------------------- the comparison

def _constraint_problems(world: World, specs: dict, live: list) -> list:
    """Every live allocation of every job of the run, in commit order."""
    cl = world.cl
    problems = []
    scopes: dict = {}
    for s in sorted(live, key=lambda s: (s["ModifyIndex"],
                                         c2m._slot(s["Name"]))):
        spec, row, tg = specs[s["JobID"]], cl.index[s["NodeID"]], \
            s["TaskGroup"]
        if tg not in spec.groups:
            problems.append(f"allocation {s['ID']}: {spec.id} has no group "
                            f"{tg}")
            continue
        if not world.static(spec, tg)[row]:
            problems.append(f"allocation {s['ID']} of {spec.id}.{tg} on node "
                            f"#{row} ({cl.name[row]}), which fails a "
                            "constraint of the job or the group")
        state = scopes.setdefault(spec.id, Scopes(world, spec))
        problems += [f"allocation {s['ID']} of {spec.id}.{tg}: {why}"
                     for why in state.why_not(tg, row)]
        state.take(tg, row)
    return problems


def compare(cl, specs: dict, stubs: list, full: list, completed: set,
            limits: dict = LIMITS) -> dict:
    """`stubs`, `full`, `completed` as `benchmark.reference.compare`
    takes them."""
    world = World(cl)
    problems = []
    live = []
    used = cl.used0.copy()
    per_node: dict = {}       # row -> [(commit, its job's register, demand, id)]
    per_job: dict = {}        # job -> [(row, group, eval, commit index)]
    names = set()
    for s in stubs:
        if s["DesiredStatus"] != "run":
            continue
        spec = specs.get(s["JobID"])
        row = cl.index.get(s["NodeID"])
        if spec is None or row is None:
            problems.append(f"allocation {s['ID']}: unknown job or node")
            continue
        if (s["JobID"], s["Name"]) in names:
            problems.append(f"allocation name {s['Name']} twice")
        names.add((s["JobID"], s["Name"]))
        live.append(s)
        dem = spec.demand.get(s["TaskGroup"], np.zeros(2))
        used[row] += dem
        per_node.setdefault(row, []).append(
            (s["ModifyIndex"], spec.registered, tuple(dem), s["ID"]))
        per_job.setdefault(s["JobID"], []).append(
            (row, s["TaskGroup"], s["EvalID"], s["ModifyIndex"]))
    over = np.flatnonzero((used > cl.cap).any(axis=1))
    if over.size:
        problems.append(f"{over.size} node(s) over capacity, e.g. #{over[0]}: "
                        f"{used[over[0]].tolist()} > {cl.cap[over[0]].tolist()}")
    for jid in completed:
        for tg, want in specs[jid].groups.items():
            got = sum(1 for _r, g, _e, _i in per_job.get(jid, ()) if g == tg)
            if got != want:
                problems.append(f"job {jid} group {tg}: {got} of {want}")
    problems += _constraint_problems(world, specs, live)

    views: dict = {}          # the ask -> (nodes that end with room, floor)

    def view_of(spec, tg):
        d = spec.demand[tg]
        key = (tuple(d), id(world.static(spec, tg)))
        if key not in views:
            room = ((used + (c2m.GHOST + 1) * d) <= cl.cap).all(axis=1) \
                & world.static(spec, tg)
            views[key] = (room, c2m.fit_score(cl.cap, cl.used0 + d))
        return views[key]

    gaps, regrets, worst = [], [], None
    job_gaps: dict = {}
    job_regrets: dict = {}
    ghosts = c2m._lattice({dem: c2m.GHOST for dem in sorted(
        {tuple(d) for sp in specs.values() for d in sp.demand.values()})})
    plans: dict = {}          # one plan's allocations, all its groups
    for a in full:
        if a["desired_status"] == "run":
            plans.setdefault((a["job_id"], a["eval_id"], a["create_index"]),
                             []).append(a)
    for (jid, _ev, index), allocs in plans.items():
        spec = specs[jid]
        order = list(spec.groups)
        allocs.sort(key=lambda a: (order.index(a["task_group"]),
                                   c2m._slot(a["name"])))
        rows = [cl.index[a["node_id"]] for a in allocs]
        ids = {a["id"] for a in allocs}
        before: dict = {}             # row -> what the plan has put there
        # the job's earlier allocations are where the counts start; the
        # later ones are what the applier made this plan place again
        state = Scopes(world, spec)
        earlier: dict = {}            # group -> {row: allocations there}
        retried: dict = {}
        for r, tg, _e, idx in per_job.get(jid, ()):
            if idx < index:
                state.take(tg, r)
                at = earlier.setdefault(tg, {})
                at[r] = at.get(r, 0) + 1
            elif idx > index:
                retried[tg] = retried.get(tg, 0) + 1
        untouched = np.ones(cl.n, bool)
        untouched[rows] = False
        for at in earlier.values():
            untouched[list(at)] = False
        seen_rows: dict = {}
        for a, row in zip(allocs, rows):
            tg = a["task_group"]
            d, desired = spec.demand[tg], spec.groups[tg]
            got = {m["node_id"]: m["norm_score"] for m in
                   (a.get("metrics") or {}).get("score_meta", ())
                   }.get(a["node_id"])
            k_before = seen_rows.get((tg, row), 0)
            seen_rows[(tg, row)] = k_before + 1
            c0 = earlier.get(tg, {}).get(row, 0)
            cap = cl.cap[row]
            settled = cl.used0[row].copy()
            free: dict = {}
            for idx, reg, dem, aid in per_node[row]:
                if aid in ids:
                    continue
                if idx < spec.registered - c2m.STALE:
                    settled += dem
                elif reg <= index:
                    free[dem] = free.get(dem, 0) + 1
            # with what the plan's own earlier slots put on the node (a
            # group's own, or another's where no distinct_hosts parts them)
            lat = (settled + before.get(row, 0.0)
                   + c2m._lattice(free)[:, None, :]
                   + ghosts[None, :, :]).reshape(-1, 2)
            u = lat + d
            pre = c2m.total_score(c2m.fit_score(cap, u), c0 + k_before,
                                  desired)
            pre = np.where((u <= cap).all(axis=1), pre, -np.inf)
            if got is None or not np.isfinite(got):
                err = np.full(pre.shape, np.inf)
            else:
                err = np.where(np.isfinite(pre), np.abs(pre - got), np.inf)
            gap = float(err.min())
            gaps.append(gap)
            job_gaps.setdefault(jid, []).append(gap)
            if worst is None or gap > worst[0]:
                worst = (gap, a["name"], got)
            if k_before == 0 and not earlier and gap <= c2m.SCORE_TOL:
                has_room, floor = view_of(spec, tg)
                offers = c2m.total_score(floor, 0, desired)[
                    has_room & untouched & state.open(tg)]
                again = retried.get(tg, 0)
                if offers.size > again:
                    ok = np.flatnonzero(err <= c2m.SCORE_TOL)
                    sel = c2m.total_score(c2m.fit_score(cap, lat[ok] + d),
                                          c0, desired).max()
                    best = np.partition(offers, -1 - again)[-1 - again]
                    regrets.append(float(best - sel))
                    job_regrets.setdefault(jid, []).append(regrets[-1])
            state.take(tg, row)
            before[row] = before.get(row, 0.0) + d
    gaps, regrets = np.array(gaps), np.array(regrets)

    def jobs_over(per: dict, tol: float) -> float:
        bad = [np.mean(np.array(v) > tol) > c2m.JOB_SHARE
               for v in per.values()]
        return float(np.mean(bad)) if bad else 0.0

    numbers = {
        "violations": len(problems),
        "unexplained_jobs_share": jobs_over(job_gaps, c2m.SCORE_TOL)
        if job_gaps else 1.0,
        "misplaced_jobs_share": jobs_over(job_regrets, c2m.REGRET_TOL),
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

def better_half(cl, d, feasible) -> np.ndarray:
    """bool[N]: of the nodes the group may use, the half that scores
    higher for it at the preload's usage."""
    rows = np.flatnonzero(feasible)
    floor = c2m.fit_score(cl.cap[rows], cl.used0[rows] + d)
    out = np.zeros(cl.n, bool)
    out[rows[np.argsort(-floor, kind="stable")[: len(rows) // 2]]] = True
    return out


def place_reference(cl, specs: list, precision: str = "float64",
                    hide_better_half: bool = False) -> list:
    """The reference put in the program's place: sequential greedy
    placement of `specs`, a job's groups in its order in one plan, every
    slot on the best-scoring node that its constraints leave open then,
    every score rounded to `precision`.  Returns the placements, one
    record each, for `answers` to give the shape the HTTP API gives."""
    q = c2m.quantizer(precision)
    world = World(cl)
    used = cl.used0.copy()
    placed = []
    index = 1_000_000
    for spec in specs:
        index += 2
        spec.registered = index - 1
        state = Scopes(world, spec)
        for tg, want in spec.groups.items():
            d = spec.demand[tg]
            static = world.static(spec, tg)
            if hide_better_half:
                static = static & ~better_half(cl, d, static)
            coll = np.zeros(cl.n)
            for i in range(want):
                util = used + d
                fits = (util <= cl.cap).all(axis=1) & static & state.open(tg)
                sc = np.where(fits, c2m.total_score(
                    c2m.fit_score(cl.cap, util, q), coll, want, q=q), -np.inf)
                r = int(np.argmax(sc))
                if not np.isfinite(sc[r]):
                    break
                used[r] += d
                coll[r] += 1
                state.take(tg, r)
                placed.append({"spec": spec, "tg": tg, "slot": i, "row": r,
                               "score": float(sc[r]), "index": index})
    return placed


def answers(cl, placed: list):
    """(stubs, full) of `placed`, in the shape the HTTP API gives them."""
    stubs, full = [], []
    for p in placed:
        spec, tg, r = p["spec"], p["tg"], p["row"]
        aid = f"{spec.id}.{tg}.{p['slot']}"
        name = f"{spec.id}.{tg}[{p['slot']}]"
        stubs.append({"ID": aid, "JobID": spec.id, "TaskGroup": tg,
                      "NodeID": cl.node_ids[r], "Name": name,
                      "EvalID": f"eval-{spec.id}", "DesiredStatus": "run",
                      "ModifyIndex": p["index"]})
        full.append({
            "id": aid, "job_id": spec.id, "task_group": tg,
            "eval_id": f"eval-{spec.id}", "name": name,
            "node_id": cl.node_ids[r], "desired_status": "run",
            "create_index": p["index"],
            "metrics": {"score_meta": [{
                "node_id": cl.node_ids[r],
                "norm_score": round(p["score"], 6)}]}})
    return stubs, full


# ----------------------------------------------------- this world's faults

def _fault(cl, placed: list, name: str) -> list:
    """A copy of `placed` with one allocation moved, as a program that
    drops one rule of the `constraint` block would place it: to a node
    with room for it, in the job's datacenters, that every rule but the
    dropped one leaves open."""
    out = [dict(p) for p in placed]
    world = World(cl)
    used = cl.used0.copy()
    for p in out:
        used[p["row"]] += p["spec"].demand[p["tg"]]

    def rows_of(spec, tg=None) -> np.ndarray:
        return np.array([p["row"] for p in out if p["spec"] is spec
                         and tg in (None, p["tg"])], np.int64)

    def move(p, target: np.ndarray, static: bool = True) -> bool:
        spec, tg = p["spec"], p["tg"]
        ok = target & ((used + spec.demand[tg]) <= cl.cap).all(axis=1) \
            & np.isin(cl.dc, sorted(spec.dcs))
        if static:
            ok &= world.static(spec, tg)
        ok[p["row"]] = False
        if not ok.any():
            return False
        p["row"] = int(np.flatnonzero(ok)[0])
        return True

    def held(rows) -> np.ndarray:
        mask = np.zeros(cl.n, bool)
        mask[rows] = True
        return mask

    for p in out:
        spec, tg = p["spec"], p["tg"]
        if name == "two_on_a_host":
            # a job of one group under distinct_hosts: onto a node that
            # holds another of its allocations
            if spec.hosts[tg] is not None and len(spec.groups) == 1 \
                    and not spec.props[tg] \
                    and move(p, held(rows_of(spec))):
                return out
        elif name == "cross_group":
            # job-level distinct_hosts, two groups: onto a node of the
            # other group's
            if spec.hosts[tg] == "job" and tg == list(spec.groups)[0] \
                    and len(spec.groups) > 1 \
                    and move(p, held(rows_of(spec, list(spec.groups)[1]))):
                return out
        elif name == "rack_over_limit":
            # into a rack that holds its limit, on a node the job has not
            for scope, target, limit in spec.props[tg]:
                if spec.hosts[tg] == "job" or scope == "job":
                    continue          # one rule broken, not two
                values = world.values(target)
                count = np.bincount(values[rows_of(spec, tg)],
                                    minlength=int(values.max()) + 1)
                full = (count >= limit)[values] \
                    & (values != values[p["row"]])
                if move(p, full & ~held(rows_of(spec))):
                    return out
        elif name == "constraint_dropped":
            # a job held to a node class: onto a node of another class
            if any(c["attribute"] == "${node.class}"
                   for c in spec.static[tg]) \
                    and move(p, ~world.static(spec, tg)
                             & ~held(rows_of(spec)), static=False):
                return out
    raise ValueError(f"fault {name!r} found nothing to break")


FAULTS = ("two_on_a_host", "cross_group", "rack_over_limit",
          "constraint_dropped")


def controls(cl, specs: list) -> dict:
    """The reference in the program's place, held to `compare`: `sound`
    (float32) has to pass; `control` (bfloat16, the step below the
    float32 the configuration states) and `half_hidden` (right scores, an
    argmax blind to the better half of the nodes) must not, as in
    `c2m-10k`; nor must `sound`'s answers with one allocation moved as a
    program without one rule would place it: `two_on_a_host` (one of a
    `distinct_hosts` job onto a node that holds another of the job),
    `cross_group` (one of the first group of a job-level `distinct_hosts`
    onto a node of its second group's), `rack_over_limit` (one of a
    group-level `distinct_property` into a rack at its limit),
    `constraint_dropped` (one of a job held to a node class onto a node
    of another)."""
    by_id = {s.id: s for s in specs}

    def held_to(placed):
        stubs, full = answers(cl, placed)
        return compare(cl, by_id, stubs, full, set(by_id))

    sound = place_reference(cl, specs, "float32")
    out = {"sound": held_to(sound),
           "control": held_to(place_reference(cl, specs, "bfloat16")),
           "half_hidden": held_to(place_reference(cl, specs, "float32",
                                                  hide_better_half=True))}
    for name in FAULTS:
        out[name] = held_to(_fault(cl, sound, name))
    return out
