"""Host-side device semantics and accounting (reference:
nomad/structs/devices.go DeviceAccounter, scheduler/device.go
deviceAllocator.AssignDevice, scheduler/feasible.go nodeDeviceMatches /
resolveDeviceTarget / checkAttributeConstraint, plugins/shared/structs
Attribute and its units).

This module is the scalar meaning of a `device` block: how an attribute
with a unit parses and compares, whether one device group passes an
ask's name and constraints, how many affinity weights it matches, and
which group and instance ids a placement takes (`assign_device_instances`).
The same predicates run over all rows at once in
`nomad_tpu.scheduler.feasible.device_fit`, which reads the matrix's
per-group attribute columns and calls `check_attribute` once per distinct
value; the kernel (`ops/place.py`) scores the result as the `devices`
scorer.  Instance ids stay host-side bookkeeping, and
`device_accounter_fits` is the applier's exclusivity check.
"""
from __future__ import annotations

import re
from functools import lru_cache
from typing import Dict, List, Optional, Tuple

from nomad_tpu.scheduler.version import version_matches

# unit -> (base, multiplier into the base): plugins/shared/structs/units.go.
# Two attributes compare only when their bases agree.
_UNITS: Dict[str, Tuple[str, float]] = {}
for _i, _p in enumerate(("K", "M", "G", "T", "P", "E"), start=1):
    _UNITS[f"{_p}iB"] = ("byte", float(2 ** (10 * _i)))
    _UNITS[f"{_p}B"] = ("byte", float(10 ** (3 * _i)))
    _UNITS[f"{_p}iB/s"] = ("byterate", float(2 ** (10 * _i)))
    _UNITS[f"{_p}B/s"] = ("byterate", float(10 ** (3 * _i)))
_UNITS["kB"] = ("byte", 1e3)
_UNITS["kB/s"] = ("byterate", 1e3)
_UNITS.update({"MHz": ("hertz", 1e6), "GHz": ("hertz", 1e9),
               "mW": ("watt", 1e-3), "W": ("watt", 1.0), "kW": ("watt", 1e3),
               "MW": ("watt", 1e6), "GW": ("watt", 1e9)})
_BY_LENGTH = sorted(_UNITS, key=len, reverse=True)
_BOOLS = {"1": True, "t": True, "T": True, "TRUE": True, "true": True,
          "True": True, "0": False, "f": False, "F": False, "FALSE": False,
          "false": False, "False": False}

# a parsed attribute: ("num", base, value in the base) | ("bool", "", b)
# | ("str", "", s)
Attr = Tuple[str, str, object]


@lru_cache(maxsize=4096)
def _parse_str(s: str) -> Attr:
    """psstructs.ParseAttribute: a number with an optional known unit,
    else a bool, else the string itself."""
    unit, numeric = "", s
    if s and s[-1].isalpha():
        unit = next((u for u in _BY_LENGTH if s.endswith(u)), "")
        if unit:
            numeric = s[:-len(unit)].strip()
    try:
        value = float(int(numeric, 10))
    except ValueError:
        try:
            value = float(numeric)
        except ValueError:
            value = None
    if value is not None and numeric.strip() == numeric and numeric:
        base, mult = _UNITS[unit] if unit else ("", 1.0)
        return ("num", base, value * mult)
    if s in _BOOLS:
        return ("bool", "", _BOOLS[s])
    return ("str", "", s)


def parse_attribute(value) -> Attr:
    """A node device's attribute value (str, bool, int or float as the
    fingerprint gave it) or a constraint's literal, parsed."""
    if isinstance(value, bool):
        return ("bool", "", value)
    if isinstance(value, (int, float)):
        return ("num", "", float(value))
    return _parse_str(str(value))


def _compare(a: Attr, b: Attr) -> Optional[int]:
    """Attribute.Compare: -1, 0, 1, or None where the two do not compare
    (different kinds, different unit bases, one with a unit and one
    without; bools compare for equality only)."""
    if a[0] != b[0] or a[1] != b[1]:
        return None
    if a[0] == "bool":
        return 0 if a[2] == b[2] else 1
    return (a[2] > b[2]) - (a[2] < b[2])


def _as_string(a: Optional[Attr]) -> Optional[str]:
    return a[2] if a is not None and a[0] == "str" else None


def _set_of(s: str) -> set:
    return {p.strip() for p in s.split(",")}


def check_attribute(operand: str, lval: Optional[Attr],
                    rval: Optional[Attr]) -> bool:
    """checkAttributeConstraint (feasible.go): one operator on two
    resolved sides, None where a side was not found.  A device affinity
    is matched by the same rule (checkAttributeAffinity)."""
    lfound, rfound = lval is not None, rval is not None
    if operand in ("distinct_hosts", "distinct_property"):
        return True
    if operand in ("!=", "not"):
        if not (lfound or rfound):
            return False
        if lfound != rfound:
            return True
        v = _compare(lval, rval)
        return v is not None and v != 0
    if operand in ("<", "<=", ">", ">=", "=", "==", "is"):
        if not (lfound and rfound):
            return False
        v = _compare(lval, rval)
        if v is None:
            return False
        if lval[0] == "bool" and operand not in ("=", "==", "is"):
            return False
        return {"=": v == 0, "==": v == 0, "is": v == 0, "<": v < 0,
                "<=": v <= 0, ">": v > 0, ">=": v >= 0}[operand]
    if operand == "is_set":
        return lfound
    if operand == "is_not_set":
        return not lfound
    if not (lfound and rfound):
        return False
    if operand in ("version", "semver"):
        subject = (str(int(lval[2])) if lval[0] == "num" and not lval[1]
                   and float(lval[2]).is_integer() else _as_string(lval))
        spec = _as_string(rval)
        return subject is not None and spec is not None and \
            version_matches(subject, spec, semver=operand == "semver")
    ls, rs = _as_string(lval), _as_string(rval)
    if ls is None or rs is None:
        return False
    if operand == "regexp":
        try:
            return re.search(rs, ls) is not None
        except re.error:
            return False
    if operand in ("set_contains", "set_contains_all"):
        return _set_of(rs) <= _set_of(ls)
    if operand == "set_contains_any":
        return bool(_set_of(rs) & _set_of(ls))
    return False


def device_target(target: str):
    """resolveDeviceTarget, first half: what a side of a device
    constraint names.  -> ("lit", Attr) | ("vendor" | "type" | "model",
    None) | ("attr", key) | ("unknown", None)."""
    if not target.startswith("${"):
        return "lit", parse_attribute(target)
    if target in ("${device.vendor}", "${device.type}", "${device.model}"):
        return target[len("${device."):-1], None
    if target.startswith("${device.attr.") and target.endswith("}"):
        return "attr", target[len("${device.attr."):-1]
    return "unknown", None


def _resolve(target: str, dev) -> Optional[Attr]:
    kind, arg = device_target(target)
    if kind == "lit":
        return arg
    if kind == "attr":
        v = dev.attributes.get(arg)
        return None if v is None else parse_attribute(v)
    if kind == "unknown":
        return None
    return ("str", "", {"vendor": dev.vendor, "type": dev.type,
                        "model": dev.name}[kind])


def group_passes(dev, request) -> bool:
    """nodeDeviceMatches: the group answers to the ask's name and passes
    every one of its constraints."""
    if not dev.matches(request.name):
        return False
    return all(check_attribute(c.operand, _resolve(c.ltarget, dev),
                               _resolve(c.rtarget, dev))
               for c in request.constraints)


def affinity_weights(dev, request) -> Tuple[float, float]:
    """(sum of the weights of the ask's affinities this group matches,
    sum of |weight| over all of them): AssignDevice's sumMatchedWeights
    and totalWeight."""
    matched = total = 0.0
    for a in request.affinities:
        total += abs(float(a.weight))
        if check_attribute(a.operand, _resolve(a.ltarget, dev),
                           _resolve(a.rtarget, dev)):
            matched += float(a.weight)
    return matched, total


def _collect_node_devices(node) -> Dict[str, Tuple[object, set]]:
    """device-group id -> (NodeDevice, set(free instance ids))."""
    out = {}
    for dev in node.node_resources.devices:
        out[dev.id] = (dev, set(dev.instance_ids))
    return out


def _used_instances(allocs) -> Dict[str, set]:
    used: Dict[str, set] = {}
    for alloc in allocs:
        if alloc.terminal_status():
            continue
        for tr in alloc.allocated_resources.tasks.values():
            for d in tr.devices:
                gid = f"{d['vendor']}/{d['type']}/{d['name']}"
                used.setdefault(gid, set()).update(d.get("device_ids", []))
    return used


def device_accounter_fits(node, allocs) -> bool:
    """True iff no device instance is claimed twice and all claimed
    instances exist on the node (reference DeviceAccounter.AddAllocs
    returning collision=false)."""
    groups = _collect_node_devices(node)
    claimed: Dict[str, set] = {}
    for alloc in allocs:
        if alloc.terminal_status():
            continue
        for tr in alloc.allocated_resources.tasks.values():
            for d in tr.devices:
                gid = f"{d['vendor']}/{d['type']}/{d['name']}"
                if gid not in groups:
                    return False
                have = groups[gid][1]
                got = claimed.setdefault(gid, set())
                for inst in d.get("device_ids", []):
                    if inst in got or inst not in have:
                        return False
                    got.add(inst)
    return True


def assign_device_instances(node, allocs, request, extra_used=None
                            ) -> Tuple[Optional[dict], float]:
    """deviceAllocator.AssignDevice (scheduler/device.go:32-131): of the
    node's groups that pass `group_passes` and have `request.count` free
    healthy instances, the one whose matched affinity weights over the
    total are highest (the first such in group-id order; the reference's
    order among equals is a map's).  Returns ({vendor, type, name,
    device_ids}, that group's sum of matched weights) or (None, 0.0).
    `extra_used` ({group id -> set(instance ids)}) carries grants already
    made to other requests of the same in-flight allocation, so two tasks
    in one group never share an instance.
    """
    import random as _random
    used = _used_instances(allocs)
    for gid, ids in (extra_used or {}).items():
        used.setdefault(gid, set()).update(ids)
    best = None
    for dev in sorted(node.node_resources.devices, key=lambda d: d.id):
        if not group_passes(dev, request):
            continue
        free = [i for i in dev.healthy_ids()
                if i not in used.get(dev.id, set())]
        if len(free) < max(request.count, 1):
            continue
        matched, total = affinity_weights(dev, request)
        choice = matched / total if total else 0.0
        if best is None or choice > best[0]:
            best = (choice, matched, dev, free)
    if best is None:
        return None, 0.0
    _choice, matched, dev, free = best
    # random choice among free instances: concurrent evals that
    # cannot see each other's in-flight assignments would all
    # deterministically take the first-free ids and collide at
    # the applier; random picks make them disjoint with high
    # probability (the applier still enforces exclusivity)
    picked = _random.sample(free, request.count)
    return {"vendor": dev.vendor, "type": dev.type, "name": dev.name,
            "device_ids": picked}, matched
