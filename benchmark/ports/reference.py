"""The plain reference of `ports-10k` and the comparison that decides
`correct`.

Float64 numpy and Python sets; imports nothing of the program.  The
cluster, its port ranges and the ports its preload holds come from
`benchmark.ports.cluster.Cluster` (the seed), the jobs from the traffic
file, and from the program only its answers: the allocations read back
over HTTP once the window has closed, the sampled jobs' in full and, from
the node's side, the allocation lists of the nodes the run's jobs landed
on (`readback`).  What is the same as in `c2m-10k` is taken from
`benchmark.reference`: ScoreFitBinPack, job anti-affinity, the rules on
which usage a reported score may have seen (its docstring), the limits
and tolerances.  What this module adds is the group `network` block's
meaning, after the upstream's nomad/structs/network.go (NetworkIndex:
SetNode, AddAllocs, AssignPorts) and scheduler/rank.go (BinPackIterator's
network step):

* a node offers its own dynamic range [`lo`, `hi`] less its reserved
  ports; a static port is free on a node while no live allocation holds
  its value; a dynamic ask is met while the range has a free value;
* a node on which an asked static port is held, or fewer dynamic values
  are free than asked, is exhausted for the group: it is not scored
  (`feasible_for`);
* ports add no score: the mean is binpack's and, where the group already
  has an allocation on the node, job anti-affinity's.

The three numbers compared are `c2m-10k`'s, under its limits:

* `violations` also counts (`_port_problems`): from every live
  allocation of the run (the job lists, so every one of them): one on a
  node whose preload holds its static port, two of the run with one
  static port on one node, a node whose dynamic range is asked for more
  values than it has; from every allocation read back in full: labels
  other than the block's, a static value other than the one asked, a
  dynamic value outside its node's own range or among the node's
  reserved ports, a group's ports told differently by `shared_ports` and
  `shared_networks`; from every node list read: a value held by two live
  allocations, and a value of the run's that the preload holds by the
  seed.  Which free value a dynamic ask got is not compared;
* `unexplained_jobs_share` holds the reported norm score to the
  reference's, as `c2m-10k` does for a scan placement;
* `misplaced_jobs_share` ranks a first placement against the nodes the
  group never used that end with room, with the asked static ports free
  and with dynamic values to spare.
"""
from __future__ import annotations

import copy

import numpy as np

from benchmark import reference as c2m
from benchmark.ports.jobs import asked_ports

LIMITS = dict(c2m.LIMITS)
NODES_READ = 3000         # allocation lists `readback` reads, at most


class JobSpec(c2m.JobSpec):
    """What the benchmark sent.  Its allocations are scan slots (a port
    ask keeps a group off the bulk path), which is what `spread` tells
    the harness's roofline count."""

    def __init__(self, job_id, namespace, shape, registered=0):
        super().__init__(job_id, namespace, shape, registered)
        self.asked = asked_ports(shape, job_id)     # [(label, static or 0)]
        self.statics = [v for _label, v in self.asked if v]
        self.dynamic = sum(1 for _label, v in self.asked if not v)
        self.spread = True


def feasible_for(cl, spec, static_free, dyn_free) -> np.ndarray:
    """bool[N]: the job's datacenters, every asked static port free
    (`static_free` bool[N, len(cl.statics)]) and dynamic values enough
    (`dyn_free` int[N])."""
    ok = np.isin(cl.dc, sorted(spec.dcs)) & (dyn_free >= spec.dynamic)
    for v in spec.statics:
        ok &= static_free[:, cl.statics.index(v)]
    return ok


# ------------------------------------------------------------- readback

def readback(get, records) -> dict:
    """{"nodes": {node id: its allocation list}, "seconds"}: the lists as
    `/v1/node/<id>/allocations` gives them once the window has closed,
    for the nodes the warm pass's and the window's allocations landed
    on: those that took two or more first, then those that took one,
    each in the order of their ids (which are drawn from the seed),
    `NODES_READ` at most."""
    import time
    t0 = time.monotonic()
    took: dict = {}
    for rec in records:
        for s in rec.stubs:
            if s["DesiredStatus"] == "run":
                took[s["NodeID"]] = took.get(s["NodeID"], 0) + 1
    nodes = (sorted(n for n, k in took.items() if k >= 2)
             + sorted(n for n, k in took.items() if k == 1))[:NODES_READ]
    lists = {n: get(f"/v1/node/{n}/allocations") for n in nodes}
    return {"nodes": lists, "landed_on": len(took),
            "seconds": time.monotonic() - t0}


# -------------------------------------------------------- the comparison

def _held(a: dict):
    """([(label, value)] the allocation holds, each port once; whether
    its two tellings of the group's ports agree).  A group `network`
    block is told twice, flat in `shared_ports` and inside
    `shared_networks`; a task's networks are the older form."""
    res = a.get("allocated_resources") or {}

    def of(nets):
        return [(p["label"], p["value"]) for n in nets or ()
                for p in (n.get("reserved_ports") or [])
                + (n.get("dynamic_ports") or [])]

    flat = [(p["label"], p["value"]) for p in res.get("shared_ports") or ()]
    nested = of(res.get("shared_networks"))
    agree = not flat or not nested or sorted(flat) == sorted(nested)
    tasks = [pv for tr in (res.get("tasks") or {}).values()
             for pv in of(tr.get("networks"))]
    return tasks + (flat or nested), agree


def _port_problems(cl, specs: dict, stubs: list, full: list,
                   seen: dict) -> list:
    problems = []
    # ---- every live allocation of the run: where it may not be
    static_by: dict = {}              # (row, value) -> allocation id
    dyn_left = cl.dyn_free0.copy()
    for s in stubs:
        spec = specs.get(s["JobID"])
        row = cl.index.get(s["NodeID"])
        if spec is None or row is None or s["DesiredStatus"] != "run":
            continue
        dyn_left[row] -= spec.dynamic
        for v in spec.statics:
            if cl.static_held[row, cl.statics.index(v)]:
                problems.append(
                    f"allocation {s['ID']} of {spec.id} on node #{row}, "
                    f"whose preload holds its static port {v}")
            other = static_by.setdefault((row, v), s["ID"])
            if other != s["ID"]:
                problems.append(f"static port {v} on node #{row} asked by "
                                f"{other} and {s['ID']}")
    for row in np.flatnonzero(dyn_left < 0):
        problems.append(f"node #{row}: {-dyn_left[row]} more dynamic "
                        f"port(s) asked than its range "
                        f"{cl.lo[row]}-{cl.hi[row]} has free")
    # ---- every allocation read in full: what it holds
    allocs = {a["id"]: a for a in full}
    for node_id, listed in seen["nodes"].items():
        row = cl.index.get(node_id)
        holder: dict = {}
        for a in listed:
            if a["desired_status"] != "run":
                continue
            allocs.setdefault(a["id"], a)
            for _label, value in _held(a)[0]:
                if holder.setdefault(value, a["id"]) != a["id"]:
                    problems.append(f"port {value} on node #{row} held by "
                                    f"{holder[value]} and {a['id']}")
                elif row is not None and a["job_id"] in specs \
                        and value in cl.held[row]:
                    problems.append(f"port {value} of {a['id']} is taken "
                                    f"on node #{row} before the run")
    for a in allocs.values():
        spec = specs.get(a["job_id"])
        row = cl.index.get(a["node_id"])
        if spec is None or row is None or a["desired_status"] != "run":
            continue
        held, agree = _held(a)
        if not agree:
            problems.append(f"allocation {a['id']}: shared_ports and "
                            "shared_networks tell different ports")
        if sorted(label for label, _v in held) \
                != sorted(label for label, _v in spec.asked):
            problems.append(f"allocation {a['id']} of {spec.id} holds "
                            f"{sorted(held)}, asked {spec.asked}")
            continue
        asked = dict(spec.asked)
        for label, value in held:
            if asked[label]:
                if value != asked[label]:
                    problems.append(f"allocation {a['id']}: static port "
                                    f"{label} is {value}, asked "
                                    f"{asked[label]}")
            elif not cl.lo[row] <= value <= cl.hi[row] \
                    or value in cl.reserved:
                problems.append(f"allocation {a['id']}: dynamic port "
                                f"{label} = {value} outside node #{row}'s "
                                f"{cl.lo[row]}-{cl.hi[row]} or reserved")
    return problems


def compare(cl, specs: dict, stubs: list, full: list, completed: set,
            seen: dict, limits: dict = LIMITS) -> dict:
    """`stubs`, `full`, `completed` as `benchmark.reference.compare`
    takes them; `seen` is what `readback` returned."""
    base = c2m.compare(cl, specs, stubs, [], completed,
                       {"violations": limits["violations"]})
    port_problems = _port_problems(cl, specs, stubs, full, seen)
    problems = base["problems"] + port_problems
    n_problems = base["compared"]["violations"]["value"] + len(port_problems)

    live = [s for s in stubs if s["DesiredStatus"] == "run"
            and s["JobID"] in specs and s["NodeID"] in cl.index]
    used = cl.used0.copy()
    static_free = ~cl.static_held          # at the end of the run
    dyn_left = cl.dyn_free0.copy()
    per_node: dict = {}
    per_group: dict = {}
    for s in live:
        spec, row = specs[s["JobID"]], cl.index[s["NodeID"]]
        used[row] += spec.demand
        dyn_left[row] -= spec.dynamic
        for v in spec.statics:
            static_free[row, cl.statics.index(v)] = False
        per_node.setdefault(row, []).append(
            (s["ModifyIndex"], spec.registered, tuple(spec.demand), s["ID"]))
        per_group.setdefault((s["JobID"], s["TaskGroup"]), []).append(
            (row, s["EvalID"], s["ModifyIndex"]))

    views: dict = {}          # the ask -> (nodes that end open, floor)

    def view_of(spec):
        key = (tuple(spec.demand), tuple(spec.asked), tuple(sorted(spec.dcs)))
        if key not in views:
            # room for GHOST + 1 more of them at the end, in cpu, memory
            # and dynamic values, so that no uncommitted usage can have
            # made the node look full; its static ports free to the end
            margin = copy.copy(spec)
            margin.dynamic = (c2m.GHOST + 1) * spec.dynamic
            room = ((used + (c2m.GHOST + 1) * spec.demand)
                    <= cl.cap).all(axis=1) \
                & feasible_for(cl, margin, static_free, dyn_left)
            views[key] = (room,
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
        has_room, floor = view_of(spec)
        unused = has_room.copy()
        unused[rows] = False
        unused[list(earlier)] = False
        offers = c2m.total_score(floor, 0, desired)[unused]
        seen_rows: dict = {}
        for a, row in zip(allocs, rows):
            got = {m["node_id"]: m["norm_score"] for m in
                   (a.get("metrics") or {}).get("score_meta", ())
                   }.get(a["node_id"])
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
            u = lat + (k_before + 1) * d
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
            if k_before == 0 and not earlier and gap <= c2m.SCORE_TOL \
                    and offers.size > retried:
                ok = np.flatnonzero(err <= c2m.SCORE_TOL)
                sel = c2m.total_score(c2m.fit_score(cap, lat[ok] + d), c0,
                                      desired).max()
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
        "nodes_landed_on": seen.get("landed_on", len(seen["nodes"])),
        "readback_s": round(seen.get("seconds", 0.0), 1),
        "problems": problems[:5],
        "worst_score": worst,
        "gaps": gaps, "regrets": regrets,
    }


# ------------------------------------------- the reference as a scheduler

def better_half(cl, spec, feasible) -> np.ndarray:
    """bool[N]: of the nodes the job may use, the half that scores higher
    for it at the preload's usage."""
    rows = np.flatnonzero(feasible)
    floor = c2m.fit_score(cl.cap[rows], cl.used0[rows] + spec.demand)
    out = np.zeros(cl.n, bool)
    out[rows[np.argsort(-floor, kind="stable")[: len(rows) // 2]]] = True
    return out


def place_reference(cl, specs: list, precision: str = "float64",
                    hide_better_half: bool = False) -> list:
    """The reference put in the program's place: sequential greedy
    placement of `specs`, every score rounded to `precision`, a static
    port as asked and a dynamic one the lowest free value of the node's
    own range.  Returns the placements, one record each, for `answers`
    to give the shape the HTTP API gives them."""
    q = c2m.quantizer(precision)
    used = cl.used0.copy()
    held = [set(h) for h in cl.held]
    static_free = ~cl.static_held
    dyn_free = cl.dyn_free0.copy()
    placed = []
    index = 1_000_000
    for spec in specs:
        d = spec.demand
        hidden = None
        index += 1
        spec.registered = index
        for tg, want in spec.groups.items():
            index += 1
            coll = np.zeros(cl.n)
            for i in range(want):
                feasible = feasible_for(cl, spec, static_free, dyn_free)
                if hide_better_half:
                    if hidden is None:
                        hidden = better_half(cl, spec, feasible)
                    feasible &= ~hidden
                util = used + d
                fits = (util <= cl.cap).all(axis=1) & feasible
                sc = np.where(fits, c2m.total_score(
                    c2m.fit_score(cl.cap, util, q), coll, want, q=q), -np.inf)
                r = int(np.argmax(sc))
                if not np.isfinite(sc[r]):
                    break
                ports = []            # [(label, value, static)]
                for label, asked in spec.asked:
                    if asked:
                        value = asked
                        static_free[r, cl.statics.index(value)] = False
                    else:
                        value = next(p for p in range(cl.lo[r], cl.hi[r] + 1)
                                     if p not in held[r])
                        dyn_free[r] -= 1
                    held[r].add(value)
                    ports.append((label, value, bool(asked)))
                used[r] += d
                coll[r] += 1
                placed.append({"spec": spec, "tg": tg, "slot": i, "row": r,
                               "ports": ports, "score": float(sc[r]),
                               "index": index})
    return placed


def answers(cl, placed: list):
    """(stubs, full, seen) of `placed`, in the shape the HTTP API gives
    them: a group's ports flat in `shared_ports` and inside
    `shared_networks`; `seen` lists every node a placement is on, the
    preload's allocations there included."""
    stubs, full = [], []
    lists: dict = {}
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
            "allocated_resources": _resources(p["ports"]),
            "metrics": {"score_meta": [{
                "node_id": cl.node_ids[r],
                "norm_score": round(p["score"], 6)}]}})
        lists.setdefault(cl.node_ids[r], []).append(full[-1])
    for i, row in enumerate(cl.pre_node):
        if cl.node_ids[row] in lists:
            lists[cl.node_ids[row]].append({
                "id": cl.pre_ids[i], "job_id": "preload",
                "desired_status": "run", "node_id": cl.node_ids[row],
                "allocated_resources": _resources(cl.pre_ports[i])})
    return stubs, full, {"nodes": lists}


def _resources(ports: list) -> dict:
    """`allocated_resources` of an allocation that holds `ports`
    ([(label, value, static)])."""
    def port(label, value):
        return {"label": label, "value": value, "to": 0,
                "host_network": "default"}
    net = {"reserved_ports": [port(label, v) for label, v, s in ports if s],
           "dynamic_ports": [port(label, v) for label, v, s in ports
                             if not s]}
    return {"tasks": {"web": {"networks": []}},
            "shared_networks": [net] if ports else [],
            "shared_ports": net["reserved_ports"] + net["dynamic_ports"]}


# ----------------------------------------------------- this world's faults

def _fault(cl, placed: list, name: str) -> list:
    """A copy of `placed` with one answer wrong, as a program that drops
    one rule of the `network` block would give it."""
    out = [dict(p, ports=list(p["ports"])) for p in placed]

    def rewrite(static: bool, value_for) -> bool:
        """The first port of that kind, given `value_for(placement, its
        value)`; False where no placement has one or no value is found."""
        for p in out:
            for k, (label, v, is_static) in enumerate(p["ports"]):
                if is_static == static:
                    new = value_for(p, v)
                    if new is not None:
                        p["ports"][k] = (label, new, is_static)
                        return True
        return False

    if name == "duplicate_port":
        # one dynamic port rewritten to a value a neighbour holds
        theirs: dict = {}
        for i, row in enumerate(cl.pre_node):
            theirs.setdefault(int(row), []).extend(
                v for _l, v, static in cl.pre_ports[i] if not static)
        if rewrite(False, lambda p, _v: next(iter(theirs.get(p["row"], ())),
                                             None)):
            return out
    if name == "static_moved" and rewrite(True, lambda _p, v: v + 1):
        return out
    if name == "dynamic_out_of_range" and rewrite(
            False, lambda p, _v: int(cl.hi[p["row"]]) + 1):
        return out
    if name == "mask_dropped":
        # one allocation moved to a node whose preload holds its static
        # port, with room for it, where the run placed nothing
        taken = {p["row"] for p in out}
        for p in out:
            spec = p["spec"]
            if not spec.statics:
                continue
            k = cl.statics.index(spec.statics[0])
            ok = cl.static_held[:, k] & np.isin(cl.dc, sorted(spec.dcs)) \
                & ((cl.used0 + spec.demand) <= cl.cap).all(axis=1) \
                & (cl.dyn_free0 >= spec.dynamic)
            ok[list(taken)] = False
            p["row"] = int(np.flatnonzero(ok)[0])
            return out
    raise ValueError(f"fault {name!r} found nothing to break")


FAULTS = ("duplicate_port", "static_moved", "dynamic_out_of_range",
          "mask_dropped")


def controls(cl, specs: list) -> dict:
    """The reference in the program's place, held to `compare`: `sound`
    (float32) has to pass; `control` (bfloat16, the step below the
    float32 the configuration states) and `half_hidden` (right scores,
    an argmax blind to the better half of the nodes) must not, as in
    `c2m-10k`; nor must `sound`'s answers with one rule of the `network`
    block broken once: `duplicate_port` (one port of one allocation
    rewritten to a neighbour's on its node), `static_moved` (a static
    port answered with another value), `dynamic_out_of_range`,
    `mask_dropped` (an allocation moved to a node that held its static
    port)."""
    by_id = {s.id: s for s in specs}

    def held_to(placed):
        stubs, full, seen = answers(cl, placed)
        return compare(cl, by_id, stubs, full, set(by_id), seen)

    sound = place_reference(cl, specs, "float32")
    out = {"sound": held_to(sound),
           "control": held_to(place_reference(cl, specs, "bfloat16")),
           "half_hidden": held_to(place_reference(cl, specs, "float32",
                                                  hide_better_half=True))}
    for name in FAULTS:
        out[name] = held_to(_fault(cl, sound, name))
    return out
