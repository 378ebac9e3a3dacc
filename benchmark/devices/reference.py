"""The plain reference of `devices-10k` and the comparison that decides
`correct`.

Float64 numpy; imports nothing of the program.  The cluster and its GPU
fleet come from `benchmark.devices.cluster.Cluster` (the seed), the jobs
from the traffic file, and from the program only its answers: the
allocations read back over HTTP once the window has closed, the sampled
jobs' in full and, from the node's side, the allocation lists of a
sample of the nodes the window touched (`readback`).  What is the same
as in `c2m-10k` is taken from `benchmark.reference`: ScoreFitBinPack, job
anti-affinity, the rules on which usage a reported score may have seen
(its docstring), the limits and tolerances.  What this module adds is the
`device` block's meaning, after the upstream's scheduler/feasible.go
(DeviceChecker, nodeDeviceMatches, resolveDeviceTarget,
checkAttributeConstraint), scheduler/device.go (AssignDevice) and
scheduler/rank.go (BinPackIterator.Next):

* an attribute or a literal parses to a number with an optional unit, a
  bool or a string (`parse`); two compare when they are of one kind and
  one unit base (`holds`);
* a group is admitted for an ask when it answers to the ask's name and
  passes every constraint (`admits`); of the admitted groups with `count`
  free instances the one whose matched affinity weights over the sum of
  |weight| is highest is taken (`assign`);
* the node's `devices` score is the matched weights of all asks over the
  sum of |weight| of all asks' affinities, and rank.go appends it to the
  scores it averages `if totalDeviceAffinityWeight != 0`: a score of zero
  is appended too (unlike node affinity, which is left out at zero), so
  the mean's divisor counts it whenever the asks carry affinities
  (`total_score`).

The three numbers compared are `c2m-10k`'s, under its limits:

* `violations` also counts, for every allocation read back: other than
  `count` instance ids for an ask, an id its node's group does not have,
  a group that fails the ask's name or a constraint, and an id held by
  two live allocations of a sampled node (the preload's holders
  included);
* `unexplained_jobs_share` holds the reported norm score and the
  reported `devices` score (`score_meta[].scores.devices`) to the
  reference's;
* `misplaced_jobs_share` ranks a first placement against the nodes the
  group never used that end with room and with free instances of an
  admitted group.
"""
from __future__ import annotations

import re

import numpy as np

from benchmark import reference as c2m

LIMITS = dict(c2m.LIMITS)
NODES_READ = 3000         # allocation lists `readback` reads, at most
_SINGLES = 48             # of them, nodes with one allocation of the window


# ----------------------------------------------- the device block's meaning

_UNITS = {"MHz": ("hertz", 1e6), "GHz": ("hertz", 1e9), "mW": ("watt", 1e-3),
          "W": ("watt", 1.0), "kW": ("watt", 1e3), "MW": ("watt", 1e6),
          "GW": ("watt", 1e9), "kB": ("byte", 1e3), "kB/s": ("byterate", 1e3)}
for _i, _p in enumerate("KMGTPE", start=1):
    _UNITS[_p + "iB"] = ("byte", float(1024 ** _i))
    _UNITS[_p + "B"] = ("byte", float(1000 ** _i))
    _UNITS[_p + "iB/s"] = ("byterate", float(1024 ** _i))
    _UNITS[_p + "B/s"] = ("byterate", float(1000 ** _i))
_NUMBER = re.compile(r"^[+-]?(\d+\.?\d*([eE][+-]?\d+)?|\.\d+([eE][+-]?\d+)?)$")


def parse(value):
    """(kind, unit base, value): ("num", "byte", 1.7e10) for "16 GiB"."""
    if isinstance(value, bool):
        return ("bool", "", value)
    if isinstance(value, (int, float)):
        return ("num", "", float(value))
    text = str(value)
    for unit in sorted(_UNITS, key=len, reverse=True):
        if text.endswith(unit) and _NUMBER.match(text[:-len(unit)].strip()):
            base, mult = _UNITS[unit]
            return ("num", base, float(text[:-len(unit)].strip()) * mult)
    if _NUMBER.match(text):
        return ("num", "", float(text))
    if text in ("true", "false"):
        return ("bool", "", text == "true")
    return ("str", "", text)


def _version(text: str):
    return tuple(int(p) for p in re.findall(r"\d+", text.split("-")[0]))


def _version_holds(subject: str, spec: str) -> bool:
    for part in spec.split(","):
        m = re.match(r"^\s*(>=|<=|!=|=|>|<|~>)?\s*(\S+)\s*$", part)
        if not m:
            return False
        op, want = m.group(1) or "=", _version(m.group(2))
        have = _version(subject)
        width = max(len(want), len(have))
        a = have + (0,) * (width - len(have))
        b = want + (0,) * (width - len(want))
        if op == "~>":
            ok = a >= b and a[:len(want) - 1] == want[:-1]
        else:
            ok = {"=": a == b, "!=": a != b, ">": a > b, ">=": a >= b,
                  "<": a < b, "<=": a <= b}[op]
        if not ok:
            return False
    return True


def holds(operator: str, left, right) -> bool:
    """One operator on two parsed sides; None is a side that was not
    found (an attribute the group does not have)."""
    if operator in ("!=", "not"):
        if left is None and right is None:
            return False
        if left is None or right is None:
            return True
        return left[:2] == right[:2] and left[2] != right[2]
    if operator == "is_set":
        return left is not None
    if operator == "is_not_set":
        return left is None
    if left is None or right is None:
        return False
    if operator in ("=", "==", "is", "<", "<=", ">", ">="):
        if left[:2] != right[:2]:
            return False
        if left[0] == "bool":
            return operator in ("=", "==", "is") and left[2] == right[2]
        a, b = left[2], right[2]
        return {"=": a == b, "==": a == b, "is": a == b, "<": a < b,
                "<=": a <= b, ">": a > b, ">=": a >= b}[operator]
    if operator in ("version", "semver"):
        if right[0] != "str" or left[1]:
            return False
        if left[0] == "num" and float(left[2]).is_integer():
            return _version_holds(str(int(left[2])), right[2])
        return left[0] == "str" and _version_holds(left[2], right[2])
    if left[0] != "str" or right[0] != "str":
        return False
    have = {p.strip() for p in left[2].split(",")}
    want = {p.strip() for p in right[2].split(",")}
    if operator == "regexp":
        return re.search(right[2], left[2]) is not None
    if operator in ("set_contains", "set_contains_all"):
        return want <= have
    if operator == "set_contains_any":
        return bool(want & have)
    return False


def _side(target: str, group: dict):
    """A side of a constraint on `group` ({vendor, type, model,
    attributes}): a literal, or what `${device...}` names."""
    if not target.startswith("${"):
        return parse(target)
    names = {"${device.vendor}": "vendor", "${device.type}": "type",
             "${device.model}": "model"}
    if target in names:
        return ("str", "", group[names[target]])
    m = re.match(r"^\$\{device\.attr\.(.+)\}$", target)
    if m and m.group(1) in group["attributes"]:
        return parse(group["attributes"][m.group(1)])
    return None


def _rule_holds(rule: dict, group: dict) -> bool:
    return holds(rule["operator"], _side(rule["attribute"], group),
                 _side(rule["value"], group))


def answers_to(group: dict, name: str) -> bool:
    """An ask names `type`, `vendor/type` or `vendor/type/model`."""
    parts = name.split("/", 2)
    have = [group["vendor"], group["type"], group["model"]]
    return {1: have[1:2], 2: have[:2], 3: have}[len(parts)] == parts


def admits(group: dict, ask: dict, name_only: bool = False) -> bool:
    return answers_to(group, ask["name"]) and (name_only or all(
        _rule_holds(c, group) for c in ask.get("constraints", ())))


def weights(group: dict, ask: dict):
    """(sum of the matched affinities' weights, sum of |weight|)."""
    rules = ask.get("affinities", ())
    return (float(sum(a["weight"] for a in rules if _rule_holds(a, group))),
            float(sum(abs(a["weight"]) for a in rules)))


def assign(groups: list, free: list, ask: dict):
    """AssignDevice on one node: (index of the group taken, its matched
    weights), or None.  `groups` in group-id order; among equals the
    first."""
    best = None
    for k, (group, room) in enumerate(zip(groups, free)):
        if room < ask["count"] or not admits(group, ask):
            continue
        matched, total = weights(group, ask)
        choice = matched / total if total else 0.0
        if best is None or choice > best[0]:
            best = (choice, k, matched)
    return None if best is None else best[1:]


def devices_score(groups: list, free: list, asks: list):
    """One placement on one node: (the `devices` score or None when the
    asks carry no affinities, the group index taken per ask), or None
    when an ask finds no group.  `free` is updated."""
    taken, matched, total = [], 0.0, 0.0
    for ask in asks:
        got = assign(groups, free, ask)
        if got is None:
            return None
        free[got[0]] -= ask["count"]
        taken.append(got[0])
        matched += got[1]
        total += weights(groups[got[0]], ask)[1]
    return (matched / total if total else None), taken


def total_score(fit, coll, desired, dev=None, q=c2m.exact):
    """rank.go's mean over the scorers that spoke: binpack always, job
    anti-affinity when the group already has an allocation on the node,
    `devices` whenever the asks carry affinities (`dev` not None), a
    zero included."""
    fit, coll = np.broadcast_arrays(np.asarray(fit, np.float64),
                                    np.asarray(coll, np.float64))
    has_coll = coll > 0
    total = q(fit + np.where(has_coll,
                             q(-(coll + 1.0) / max(desired, 1)), 0.0))
    n = 1.0 + has_coll
    if dev is not None:
        total = q(total + dev)
        n = n + 1.0
    return q(total / n)


# ----------------------------------------------------------- job records

class JobSpec(c2m.JobSpec):
    """What the benchmark sent.  Its allocations are scan slots (a device
    ask keeps a group off the bulk path), which is what `spread` tells
    the harness's roofline count."""

    def __init__(self, job_id, namespace, shape, registered=0):
        super().__init__(job_id, namespace, shape, registered)
        self.ask = shape.get("device")
        self.spread = True


def _fleet_view(cl, spec, name_only=False, no_affinity=False):
    """(admitted bool[N], devices score f64[N] or None) of `spec`'s ask
    on every node of the cluster (one group a node)."""
    ok = np.zeros(len(cl.groups) + 1, bool)
    dev = np.zeros(len(cl.groups) + 1)
    total = 0.0
    for m, g in enumerate(cl.groups):
        ok[m] = admits(g, spec.ask, name_only)
        matched, total = weights(g, spec.ask)
        dev[m] = matched / total if total else 0.0
    if no_affinity or not total:
        return ok[cl.model], None
    return ok[cl.model], dev[cl.model]


# ------------------------------------------------------------- readback

def readback(get, records) -> dict:
    """{"nodes": {node id: its allocation list}, "seconds"}: the lists as
    `/v1/node/<id>/allocations` gives them once the window has closed,
    for every node that took two or more of the allocations the warm
    pass and the window left and `_SINGLES` of those that took one, in
    the order of their ids (which are drawn from the seed), `NODES_READ`
    at most."""
    import time
    t0 = time.monotonic()
    took: dict = {}
    for rec in records:
        for s in rec.stubs:
            if s["DesiredStatus"] == "run":
                took[s["NodeID"]] = took.get(s["NodeID"], 0) + 1
    many = sorted(n for n, k in took.items() if k >= 2)
    one = sorted(n for n, k in took.items() if k == 1)[:_SINGLES]
    nodes = one + many[:NODES_READ - len(one)]
    lists = {n: get(f"/v1/node/{n}/allocations") for n in nodes}
    return {"nodes": lists, "seconds": time.monotonic() - t0}


# -------------------------------------------------------- the comparison

def _device_problems(cl, specs: dict, full: list, seen: dict) -> list:
    """Instance accounting, from both sides: the sampled jobs'
    allocations and the sampled nodes' own lists."""
    problems = []
    allocs = {a["id"]: a for a in full}
    for node_id, listed in seen["nodes"].items():
        holder: dict = {}
        for a in listed:
            if a["desired_status"] != "run":
                continue
            allocs.setdefault(a["id"], a)
            for tr in a["allocated_resources"]["tasks"].values():
                for d in tr.get("devices") or ():
                    for inst in d["device_ids"]:
                        if inst in holder and holder[inst] != a["id"]:
                            problems.append(
                                f"instance {inst} held by {holder[inst]} "
                                f"and {a['id']}")
                        holder[inst] = a["id"]
    for a in allocs.values():
        spec = specs.get(a["job_id"])
        row = cl.index.get(a["node_id"])
        if spec is None or row is None or a["desired_status"] != "run" \
                or not spec.ask:
            continue
        got = [d for tr in a["allocated_resources"]["tasks"].values()
               for d in tr.get("devices") or ()]
        if len(got) != 1 or len(set(got[0]["device_ids"])) \
                != spec.ask["count"]:
            problems.append(f"allocation {a['id']} of {spec.id}: "
                            f"{[len(d['device_ids']) for d in got]} "
                            f"instance(s) for count {spec.ask['count']}")
            continue
        if cl.model[row] < 0:
            problems.append(f"allocation {a['id']}: instances on node "
                            f"#{row}, which has no group")
            continue
        group = cl.groups[cl.model[row]]
        mine = {cl.instance_id(row, k) for k in range(cl.instances[row])}
        if (got[0]["vendor"], got[0]["type"], got[0]["name"]) != \
                (group["vendor"], group["type"], group["model"]) \
                or not set(got[0]["device_ids"]) <= mine:
            problems.append(f"allocation {a['id']}: instances "
                            f"{got[0]['device_ids']} are not node #{row}'s")
        if not admits(group, spec.ask):
            problems.append(f"allocation {a['id']} of {spec.id} on a "
                            f"{group['model']}, which its ask rules out")
    return problems


def compare(cl, specs: dict, stubs: list, full: list, completed: set,
            seen: dict, limits: dict = LIMITS) -> dict:
    """`stubs`, `full`, `completed` as `benchmark.reference.compare`
    takes them; `seen` is what `readback` returned."""
    base = c2m.compare(cl, specs, stubs, [], completed,
                       {"violations": limits["violations"]})
    problems = base["problems"] + _device_problems(cl, specs, full, seen)
    n_problems = (base["compared"]["violations"]["value"]
                  + len(problems) - len(base["problems"]))

    live = [s for s in stubs if s["DesiredStatus"] == "run"
            and s["JobID"] in specs and s["NodeID"] in cl.index]
    used = cl.used0.copy()
    taken = cl.held.sum(axis=1).astype(np.int64)   # instances in use
    per_node: dict = {}
    per_group: dict = {}
    for s in live:
        spec, row = specs[s["JobID"]], cl.index[s["NodeID"]]
        used[row] += spec.demand
        taken[row] += spec.ask["count"] if spec.ask else 0
        per_node.setdefault(row, []).append(
            (s["ModifyIndex"], spec.registered, tuple(spec.demand), s["ID"]))
        per_group.setdefault((s["JobID"], s["TaskGroup"]), []).append(
            (row, s["EvalID"], s["ModifyIndex"]))

    views: dict = {}          # shape -> (admitted, dev, has room, floor)

    def view_of(spec):
        key = id(spec.shape)              # a mix's specs share its shapes
        if key not in views:
            ok, dev = _fleet_view(cl, spec)
            room = ((used + (c2m.GHOST + 1) * spec.demand)
                    <= cl.cap).all(axis=1) \
                & (cl.instances - taken >= spec.ask["count"])
            views[key] = (ok, dev, room,
                          c2m.fit_score(cl.cap, cl.used0 + spec.demand))
        return views[key]

    gaps, regrets, worst = [], [], None
    job_gaps: dict = {}
    job_regrets: dict = {}
    ghosts = c2m._lattice({dem: c2m.GHOST for dem in
                           sorted({tuple(sp.demand)
                                   for sp in specs.values()})})
    plans: dict = {}
    for a in full:
        if a["desired_status"] == "run":
            plans.setdefault((a["job_id"], a["task_group"], a["eval_id"],
                              a["create_index"]), []).append(a)
    for (jid, tg, _ev, index), allocs in plans.items():
        spec = specs[jid]
        allocs.sort(key=lambda a: c2m._slot(a["name"]))
        d, desired = spec.demand, spec.groups[tg]
        rows = [cl.index[a["node_id"]] for a in allocs]
        ids = {a["id"] for a in allocs}
        earlier: dict = {}
        retried = 0
        for r, _e, idx in per_group.get((jid, tg), ()):
            if idx < index:
                earlier[r] = earlier.get(r, 0) + 1
            elif idx > index:
                retried += 1
        admitted, dev, has_room, floor = view_of(spec)
        unused = np.isin(cl.dc, sorted(spec.dcs)) & has_room & admitted
        unused[rows] = False
        unused[list(earlier)] = False
        offers = total_score(floor, 0, desired, dev)[unused]
        seen_rows: dict = {}
        for a, row in zip(allocs, rows):
            meta = {m["node_id"]: m for m in
                    (a.get("metrics") or {}).get("score_meta", ())
                    }.get(a["node_id"]) or {}
            got = meta.get("norm_score")
            got_dev = (meta.get("scores") or {}).get("devices")
            k_before = seen_rows.get(row, 0)
            seen_rows[row] = k_before + 1
            c0 = earlier.get(row, 0)
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
            lat = (settled + c2m._lattice(free)[:, None, :]
                   + ghosts[None, :, :]).reshape(-1, 2)
            here = None if dev is None else dev[row]
            u = lat + (k_before + 1) * d
            pre = total_score(c2m.fit_score(cap, u), c0 + k_before, desired,
                              here)
            pre = np.where((u <= cap).all(axis=1), pre, -np.inf)
            if got is None or not np.isfinite(got):
                err = np.full(pre.shape, np.inf)
            else:
                err = np.where(np.isfinite(pre), np.abs(pre - got), np.inf)
            # the reported `devices` score is part of what is explained
            if (here is None) != (got_dev is None) or (
                    here is not None
                    and abs(here - got_dev) > c2m.SCORE_TOL):
                err = np.full(pre.shape, np.inf)
            gap = float(err.min())
            gaps.append(gap)
            job_gaps.setdefault(jid, []).append(gap)
            if worst is None or gap > worst[0]:
                worst = (gap, a["name"], got, got_dev)
            if k_before == 0 and not earlier and gap <= c2m.SCORE_TOL \
                    and offers.size > retried:
                ok = np.flatnonzero(err <= c2m.SCORE_TOL)
                sel = total_score(c2m.fit_score(cap, lat[ok] + d), c0,
                                  desired, here).max()
                best = np.partition(offers, -1 - retried)[-1 - retried]
                regrets.append(float(best - sel))
                job_regrets.setdefault(jid, []).append(regrets[-1])
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
    return {
        "correct": all(numbers[k] <= limits[k] for k in limits),
        "compared": {k: {"value": numbers[k], "limit": limits[k]}
                     for k in limits},
        "allocations_compared": int(gaps.size),
        "placements_ranked": int(regrets.size),
        "nodes_read": len(seen["nodes"]),
        "readback_s": round(seen.get("seconds", 0.0), 1),
        "problems": problems[:5],
        "worst_score": worst,
        "gaps": gaps, "regrets": regrets,
    }


# ------------------------------------------- the reference as a scheduler

def better_half(cl, spec, admitted, dev) -> np.ndarray:
    """bool[N]: of the nodes the job may use, the half that scores
    higher for it at the preload's usage."""
    rows = np.flatnonzero(np.isin(cl.dc, sorted(spec.dcs)) & admitted)
    floor = total_score(c2m.fit_score(cl.cap[rows],
                                      cl.used0[rows] + spec.demand), 0, 1,
                        None if dev is None else dev[rows])
    out = np.zeros(cl.n, bool)
    out[rows[np.argsort(-floor, kind="stable")[: len(rows) // 2]]] = True
    return out


def place_reference(cl, specs: list, precision: str = "float64",
                    hide_better_half: bool = False,
                    name_only: bool = False, no_affinity: bool = False):
    """The reference put in the program's place: sequential greedy
    placement of `specs`, every score rounded to `precision`, instances
    handed out lowest free index first, answers in the shape the HTTP API
    gives them.  `name_only` admits a group by its name alone and
    `no_affinity` leaves the `devices` scorer out: the program as it was
    before it read the ask's constraints and affinities.  Returns
    (stubs, full, seen)."""
    q = c2m.quantizer(precision)
    used = cl.used0.copy()
    width = cl.held.shape[1]
    busy = cl.held | (np.arange(width)[None, :] >= cl.instances[:, None])
    stubs, full = [], []
    index = 1_000_000
    for spec in specs:
        d, count = spec.demand, spec.ask["count"]
        admitted, dev = _fleet_view(cl, spec, name_only, no_affinity)
        feasible = np.isin(cl.dc, sorted(spec.dcs)) & admitted
        if hide_better_half:
            feasible &= ~better_half(cl, spec, admitted, dev)
        index += 1
        spec.registered = index
        for tg, want in spec.groups.items():
            index += 1
            coll = np.zeros(cl.n)
            for i in range(want):
                util = used + d
                fits = (util <= cl.cap).all(axis=1) & feasible \
                    & ((~busy).sum(axis=1) >= count)
                fit = c2m.fit_score(cl.cap, util, q)
                sc = np.where(fits, total_score(fit, coll, want, dev, q),
                              -np.inf)
                r = int(np.argmax(sc))
                if not np.isfinite(sc[r]):
                    break
                taken = np.flatnonzero(~busy[r])[:count]
                busy[r, taken] = True
                used[r] += d
                coll[r] += 1
                group = cl.groups[cl.model[r]]
                scores = {"binpack": round(float(fit[r]), 6)}
                if dev is not None:
                    scores["devices"] = round(float(q(dev[r])), 6)
                aid = f"{spec.id}.{tg}.{i}"
                name = f"{spec.id}.{tg}[{i}]"
                stubs.append({"ID": aid, "JobID": spec.id, "TaskGroup": tg,
                              "NodeID": cl.node_ids[r], "Name": name,
                              "EvalID": f"eval-{spec.id}",
                              "DesiredStatus": "run", "ModifyIndex": index})
                full.append({
                    "id": aid, "job_id": spec.id, "task_group": tg,
                    "eval_id": f"eval-{spec.id}", "name": name,
                    "node_id": cl.node_ids[r], "desired_status": "run",
                    "create_index": index,
                    "allocated_resources": {"tasks": {"web": {"devices": [{
                        "vendor": group["vendor"], "type": group["type"],
                        "name": group["model"],
                        "device_ids": [cl.instance_id(r, int(k))
                                       for k in taken]}]}}},
                    "metrics": {"score_meta": [{
                        "node_id": cl.node_ids[r],
                        "norm_score": round(float(sc[r]), 6),
                        "scores": scores}]}})
    lists: dict = {}
    for a in full:
        lists.setdefault(a["node_id"], []).append(a)
    for (row, _k, idx), aid in zip(cl.holders, cl.holder_ids):
        if cl.node_ids[row] in lists:
            g = cl.groups[cl.model[row]]
            lists[cl.node_ids[row]].append({
                "id": aid, "job_id": "preload-gpu", "desired_status": "run",
                "node_id": cl.node_ids[row],
                "allocated_resources": {"tasks": {"web": {"devices": [{
                    "vendor": g["vendor"], "type": g["type"],
                    "name": g["model"],
                    "device_ids": [cl.instance_id(row, i)
                                   for i in idx]}]}}}})
    return stubs, full, {"nodes": lists}


def controls(cl, specs: list) -> dict:
    """The reference in the program's place, held to `compare`: `sound`
    (float32) has to pass; `control` (bfloat16, the step below the
    float32 the configuration states), `half_hidden` (right scores, an
    argmax blind to the better half of the nodes), `constraint_dropped`
    (a group admitted by its name alone) and `affinity_dropped` (no
    `devices` scorer) must not: the last two are the program as it was
    before it read the ask's constraints and affinities."""
    by_id = {s.id: s for s in specs}
    out = {}
    for name, kw in (
            ("sound", dict(precision="float32")),
            ("control", dict(precision="bfloat16")),
            ("half_hidden", dict(precision="float32",
                                 hide_better_half=True)),
            ("constraint_dropped", dict(precision="float32",
                                        name_only=True)),
            ("affinity_dropped", dict(precision="float32",
                                      no_affinity=True))):
        stubs, full, seen = place_reference(cl, specs, **kw)
        out[name] = compare(cl, by_id, stubs, full, set(by_id), seen)
    return out
