"""Feasibility: constraint programs over the dense attribute columns.

Host twin of the device constraint kernel; semantics mirror
scheduler/feasible.go:740-940 (resolveTarget/checkConstraint and the
operator table at :806-841).  Every function returns a bool[N] mask over
ClusterMatrix rows — vectorized numpy over hash/ordinal code columns for
=, !=, <, <=, >, >=, is_set; regex/version/semver/set_contains evaluate a
Python predicate over *distinct* values only and scatter (the analog of the
reference's "escaped" constraint fallback, context.go:252-420).
"""
from __future__ import annotations

import re
from dataclasses import dataclass
from functools import lru_cache
from typing import List, Optional

import numpy as np

from nomad_tpu.encode.attrs import AttrTable, hash_code
from nomad_tpu.encode.matrixizer import ClusterMatrix
from nomad_tpu.structs.job import Constraint, Operand
from nomad_tpu.structs.resources import device_id_matches
from nomad_tpu.scheduler import devices as dv
from nomad_tpu.scheduler.version import version_matches


@lru_cache(maxsize=4096)
def _compiled_regex(pattern: str) -> Optional["re.Pattern"]:
    try:
        return re.compile(pattern)
    except re.error:
        return None


def _set_contains_all(lval: str, rval: str) -> bool:
    have = {s.strip() for s in lval.split(",")}
    return all(s.strip() in have for s in rval.split(","))


def _set_contains_any(lval: str, rval: str) -> bool:
    have = {s.strip() for s in lval.split(",")}
    return any(s.strip() in have for s in rval.split(","))


def _ordered_mask(col, op: str, literal: str) -> np.ndarray:
    """Lexical <,<=,>,>= against a literal via ordinal codes
    (checkLexicalOrder semantics: plain string comparison)."""
    ords = col.ordinals()
    i, exact = col.ordinal_of(literal)
    found = ords >= 0
    if op == Operand.LT:
        return found & (ords < i)
    if op == Operand.LTE:
        return found & ((ords < i) | (exact & (ords == i)))
    if op == Operand.GT:
        return found & ((ords > i) if exact else (ords >= i))
    if op == Operand.GTE:
        return found & (ords >= i)
    raise ValueError(op)


_MISSING = object()   # a referenced column that no node materializes


def _resolve_side(cm: ClusterMatrix, target: str):
    """-> (column | None, literal | None, missing: bool).  Mirrors
    resolveTarget (feasible.go:769-802): non-interpolated targets are
    literals; unresolvable or never-seen columns are 'missing' (nil)."""
    col_name = AttrTable.target_to_column(target)
    if col_name is None:
        return None, target, False
    if col_name == "__unresolvable__":
        return None, None, True
    col = cm.attrs.columns.get(col_name)
    if col is None:
        return None, None, True
    return col, None, False


def constraint_mask(cm: ClusterMatrix, c: Constraint) -> np.ndarray:
    """bool[N] satisfaction mask for one constraint over all rows."""
    n = cm.n_rows
    op = c.operand
    # equality aliases (reference checkConstraint, feasible.go:808-814:
    # "=", "==" and "is" are one operator; "!=" and "not" likewise)
    if op in ("==", "is"):
        op = Operand.EQ
    elif op == "not":
        op = Operand.NEQ

    # distinct_hosts / distinct_property are not node-static; handled by the
    # stack against proposed allocations (checkConstraint returns true here,
    # feasible.go:809-813)
    if op in (Operand.DISTINCT_HOSTS, Operand.DISTINCT_PROPERTY):
        return np.ones(n, dtype=bool)

    lcol, llit, lmissing = _resolve_side(cm, c.ltarget)
    rcol, rlit, rmissing = _resolve_side(cm, c.rtarget)

    # ---- a side is nil on every row: collapse to a scalar per-row check
    if lmissing or rmissing:
        if lmissing and rmissing:
            return np.full(n, _scalar_check(op, None, None), dtype=bool)
        col, lit, col_is_lhs = (rcol, rlit, False) if lmissing else (lcol, llit, True)
        if col is None:
            v = lit
            res = _scalar_check(op, v, None) if col_is_lhs else _scalar_check(op, None, v)
            return np.full(n, res, dtype=bool)
        vals = col.values
        if col_is_lhs:
            return np.array([_scalar_check(op, v, None) for v in vals], dtype=bool)
        return np.array([_scalar_check(op, None, v) for v in vals], dtype=bool)

    # ---- both literals: scalar result broadcast
    if lcol is None and rcol is None:
        return np.full(n, _scalar_check(op, llit, rlit), dtype=bool)

    # ---- column vs column (rare): compare decoded values row-wise
    if lcol is not None and rcol is not None:
        lv, rv = lcol.values, rcol.values
        return np.array([_scalar_check(op, lv[i], rv[i]) for i in range(n)],
                        dtype=bool)

    # ---- column vs literal (the common case)
    swapped = lcol is None               # literal on the left, column right
    col = rcol if swapped else lcol
    lit = llit if swapped else rlit
    if swapped and op in (Operand.LT, Operand.LTE, Operand.GT, Operand.GTE):
        op = {Operand.LT: Operand.GT, Operand.LTE: Operand.GTE,
              Operand.GT: Operand.LT, Operand.GTE: Operand.LTE}[op]

    found = col.hash_codes != 0
    if op == Operand.EQ:
        return found & (col.hash_codes == hash_code(lit))
    if op == Operand.NEQ:
        # no found requirement: nil != literal is true (feasible.go:822)
        return col.hash_codes != hash_code(lit)
    if op in (Operand.LT, Operand.LTE, Operand.GT, Operand.GTE):
        return _ordered_mask(col, op, lit)
    if op == Operand.ATTRIBUTE_IS_SET:
        return found.copy()
    if op == Operand.ATTRIBUTE_IS_NOT_SET:
        return ~found
    # For the host-escape operators the *semantic* lhs/rhs matters: lVal is
    # the subject (version string / haystack), rVal the spec (constraint /
    # pattern / needle list) — checkConstraint (feasible.go:828-838).
    if op == Operand.VERSION:
        if swapped:   # literal is the version, column holds the spec
            return col.host_mask(lambda spec: version_matches(lit, spec))
        return col.host_mask(lambda v: version_matches(v, lit))
    if op == Operand.SEMVER:
        if swapped:
            return col.host_mask(lambda spec: version_matches(lit, spec, semver=True))
        return col.host_mask(lambda v: version_matches(v, lit, semver=True))
    if op == Operand.REGEX:
        if swapped:   # column holds the pattern, literal is the subject
            return col.host_mask(
                lambda pat: (rx := _compiled_regex(pat)) is not None
                and rx.search(lit) is not None)
        rx = _compiled_regex(lit)
        return col.host_mask(lambda v: rx is not None and rx.search(v) is not None)
    if op in (Operand.SET_CONTAINS, Operand.SET_CONTAINS_ALL):
        if swapped:
            return col.host_mask(lambda v: _set_contains_all(lit, v))
        return col.host_mask(lambda v: _set_contains_all(v, lit))
    if op == Operand.SET_CONTAINS_ANY:
        if swapped:
            return col.host_mask(lambda v: _set_contains_any(lit, v))
        return col.host_mask(lambda v: _set_contains_any(v, lit))
    return np.zeros(n, dtype=bool)   # unknown operator -> infeasible


def _scalar_check(op: str, lval: Optional[str], rval: Optional[str]) -> bool:
    lfound, rfound = lval is not None, rval is not None
    if op in ("=", "==", "is", Operand.EQ):
        return lfound and rfound and lval == rval
    if op in ("!=", "not", Operand.NEQ):
        return lval != rval
    if op in (Operand.LT, Operand.LTE, Operand.GT, Operand.GTE):
        if not (lfound and rfound):
            return False
        return {"<": lval < rval, "<=": lval <= rval,
                ">": lval > rval, ">=": lval >= rval}[op]
    if op == Operand.ATTRIBUTE_IS_SET:
        return lfound
    if op == Operand.ATTRIBUTE_IS_NOT_SET:
        return not lfound
    if op == Operand.VERSION:
        return lfound and rfound and version_matches(lval, rval)
    if op == Operand.SEMVER:
        return lfound and rfound and version_matches(lval, rval, semver=True)
    if op == Operand.REGEX:
        rx = _compiled_regex(rval) if rfound else None
        return lfound and rx is not None and rx.search(lval) is not None
    if op in (Operand.SET_CONTAINS, Operand.SET_CONTAINS_ALL):
        return lfound and rfound and _set_contains_all(lval, rval)
    if op == Operand.SET_CONTAINS_ANY:
        return lfound and rfound and _set_contains_any(lval, rval)
    return False


def more_than_an_equality(constraints: List[Constraint]) -> bool:
    """Whether `constraints_mask` has more to do than one hash compare
    over the rows (the `${attr.kernel.name} = linux` every job carries):
    several constraints, or one whose operator walks the column's values."""
    return len(constraints) > 1 or any(
        c.operand not in (Operand.EQ, "==", "is", Operand.NEQ, "not")
        for c in constraints)


def constraints_mask(cm: ClusterMatrix, constraints: List[Constraint]) -> np.ndarray:
    mask = np.ones(cm.n_rows, dtype=bool)
    for c in constraints:
        mask &= constraint_mask(cm, c)
    return mask


def driver_mask(cm: ClusterMatrix, drivers: List[str]) -> np.ndarray:
    """DriverChecker (feasible.go:452): node must have each driver detected
    and healthy — encoded as the attr.driver.<name> column being set."""
    mask = np.ones(cm.n_rows, dtype=bool)
    for d in drivers:
        col = cm.attrs.columns.get(f"attr.driver.{d}")
        mask &= (col.hash_codes != 0) if col is not None else False
    return mask


def csi_volume_mask(cm: ClusterMatrix, snapshot, namespace: str,
                    job_id: str, volumes) -> np.ndarray:
    """CSIVolumeChecker (feasible.go:212-358), dense: the volume-level
    gates (exists, schedulable, free claims — with the same-job
    write-claim exception) are scalars broadcast over the mask; the
    node-level gates (healthy node plugin, MaxVolumes) use the
    fingerprint column and one bulk claim-count pass."""
    reqs = [r for r in volumes.values() if r.type == "csi"]
    if not reqs:
        return np.ones(cm.n_rows, dtype=bool)
    if snapshot is None:
        return np.zeros(cm.n_rows, dtype=bool)
    mask = np.ones(cm.n_rows, dtype=bool)
    counts = snapshot._store.csi_volume_counts_by_node() \
        if hasattr(snapshot, "_store") else {}
    for req in reqs:
        vol = snapshot.csi_volume_by_id(namespace, req.source)
        if vol is None:
            return np.zeros(cm.n_rows, dtype=bool)
        if req.read_only:
            if not (vol.read_schedulable() and vol.has_free_read_claims()):
                return np.zeros(cm.n_rows, dtype=bool)
        else:
            if not vol.write_schedulable():
                return np.zeros(cm.n_rows, dtype=bool)
            if not vol.has_free_write_claims():
                # blocking write claims owned by this very job are fine
                # (feasible.go:336-358); GC'd or foreign claims block
                for alloc_id in vol.write_claims:
                    a = snapshot.allocs.get(alloc_id) \
                        if hasattr(snapshot, "allocs") else None
                    if a is None or a.namespace != namespace \
                            or a.job_id != job_id:
                        return np.zeros(cm.n_rows, dtype=bool)
        # node plugin healthy (fingerprint column)
        col = cm.attrs.columns.get(f"csiplugin.{vol.plugin_id}")
        if col is None:
            return np.zeros(cm.n_rows, dtype=bool)
        mask &= col.hash_codes == hash_code("1")
        # MaxVolumes per node plugin
        plug = snapshot.csi_plugin_by_id(vol.plugin_id)
        if plug is not None:
            for node_id, row in cm.row_of.items():
                info = plug.nodes.get(node_id)
                if info is None:
                    continue
                maxv = info.get("max_volumes", 0)
                if maxv and counts.get(node_id, {}).get(
                        vol.plugin_id, 0) >= maxv:
                    mask[row] = False
    return mask


@dataclass
class DeviceFit:
    """What a task group's device asks come to on every row."""
    capable: np.ndarray     # bool[N] DeviceChecker: groups that pass have
    #                         the healthy instances, whoever holds them
    place_cap: np.ndarray   # i32[N] placements the free instances allow
    score: np.ndarray       # f32[N] the `devices` score of the next one
    has_score: bool         # the asks' affinity weights do not sum to 0
    multi_level: bool       # on some row the score changes inside the cap


def _device_side(cm: ClusterMatrix, gid: str, target: str):
    """One side of a device constraint on group `gid`: (codes i32[N] into
    cm.device_attr_values, None) for `${device.attr.<key>}`, else
    (None, the one parsed value every row has, or None: not found)."""
    kind, arg = dv.device_target(target)
    if kind == "lit":
        return None, arg
    if kind == "attr":
        col = cm.device_attr_codes.get(gid, {}).get(arg)
        return (None, None) if col is None else (col, None)
    if kind == "unknown":
        return None, None
    vendor, dtype, name = gid.split("/")
    return None, ("str", "", {"vendor": vendor, "type": dtype,
                              "model": name}[kind])


def device_check_mask(cm: ClusterMatrix, gid: str, ltarget: str,
                      rtarget: str, operand: str) -> np.ndarray:
    """bool[N]: devices.check_attribute for one constraint (or affinity)
    of a device ask on group `gid`, evaluated once per distinct pair of
    values and gathered over the rows."""
    lcol, lval = _device_side(cm, gid, ltarget)
    rcol, rval = _device_side(cm, gid, rtarget)
    n = cm.n_rows
    if lcol is None and rcol is None:
        return np.full(n, dv.check_attribute(operand, lval, rval), bool)
    values = cm.device_attr_values
    m = len(values)
    key = ((lcol.astype(np.int64) if lcol is not None else 0) * m
           + (rcol if rcol is not None else 0))
    uniq, inverse = np.unique(key, return_inverse=True)

    def side(col, val, code):
        if col is None:
            return val
        return None if code == 0 else dv.parse_attribute(values[code])
    verdict = np.array([dv.check_attribute(
        operand, side(lcol, lval, int(k) // m), side(rcol, rval, int(k) % m))
        for k in uniq], bool)
    return verdict[inverse]


def device_fit(cm: ClusterMatrix, requests, extra_used=None) -> DeviceFit:
    """DeviceChecker (feasible.go:1192-1295) and AssignDevice
    (device.go:32-131) for all rows at once.  Per ask, a group is
    admitted on a row when it answers to the name, passes every one of
    `req.constraints` there and has a healthy instance; a placement gives
    each ask the admitted group with `count` free instances whose matched
    `req.affinities` weigh most, and the row's `devices` score is the
    matched weights of all asks over the sum of |weight| (rank.go:
    appended whenever that sum is not 0, a zero included).  Free counts
    are committed usage plus the engine's in-flight overlay plus
    `extra_used` ({gid: i32[N]}, what this eval has granted so far).

    Rows where every ask admits at most one group (a fleet with one card
    model a node) are array work: the score is constant and the cap a
    division.  A row where an ask admits several groups is walked
    placement by placement; if its score changes on the way (`multi_level`)
    the kernel's constant score is right only for the next placement and
    the scheduler places such an eval one slot at a time."""
    from nomad_tpu.parallel.engine import get_engine
    eng = get_engine()
    n = cm.n_rows
    free: dict = {}

    def free_of(gid):
        if gid not in free:
            f = cm.device_caps[gid].astype(np.int64) \
                - cm.device_used.get(gid, 0)
            inflight = eng.device_overlay(cm, gid)
            if inflight is not None and inflight.shape[0] == n:
                f = f - inflight
            if extra_used and gid in extra_used:
                f = f - extra_used[gid]
            free[gid] = f
        return free[gid]

    asks = []       # (count, total |weight|, [(gid, admitted, matched)])
    total_w = 0.0
    for req in requests:
        tw = sum(abs(float(a.weight)) for a in req.affinities)
        total_w += tw
        admitted = []
        for gid in sorted(cm.device_caps):
            if not device_id_matches(*gid.split("/"), req.name):
                continue
            ok = cm.device_caps[gid] > 0
            for c in req.constraints:
                ok = ok & device_check_mask(cm, gid, c.ltarget, c.rtarget,
                                            c.operand)
            w = np.zeros(n)
            for a in req.affinities:
                w += float(a.weight) * device_check_mask(
                    cm, gid, a.ltarget, a.rtarget, a.operand)
            admitted.append((gid, ok, w))
        asks.append((max(int(req.count), 1), tw, admitted))

    capable = np.ones(n, bool)
    several = np.zeros(n, bool)
    need: dict = {}                      # gid -> instances one placement takes
    matched = np.zeros(n)
    for count, _tw, admitted in asks:
        k = np.zeros(n, np.int64)
        for gid, ok, w in admitted:
            k += ok
            need[gid] = need.get(gid, 0) + count * ok
            matched += np.where(ok, w, 0.0)
        capable &= k >= 1
        several |= k >= 2
    cap = np.full(n, 2**30, np.int64)
    for gid, per in need.items():
        asked = per > 0
        capable &= ~asked | (cm.device_caps[gid] >= per)
        cap = np.where(asked, np.minimum(cap, free_of(gid)
                                         // np.maximum(per, 1)), cap)
    cap = np.where(capable, np.clip(cap, 0, 2**30), 0)
    score = matched / total_w if total_w else np.zeros(n)

    multi_level = False
    for row in np.flatnonzero(several):
        row = int(row)
        capable[row] = bool(_device_levels(
            asks, row, {g: int(cm.device_caps[g][row]) for g in need},
            total_w, limit=1))
        levels = _device_levels(
            asks, row, {g: int(free_of(g)[row]) for g in need}, total_w)
        cap[row] = len(levels)
        score[row] = levels[0] if levels else 0.0
        multi_level |= any(v != levels[0] for v in levels)
    return DeviceFit(capable=capable, place_cap=cap.astype(np.int32),
                     score=score.astype(np.float32),
                     has_score=total_w != 0.0, multi_level=multi_level)


def _device_levels(asks, row: int, free: dict, total_w: float,
                   limit: int = 2**30) -> list:
    """The `devices` score of each successive placement on one row, until
    an ask finds no admitted group with room (AssignDevice repeated over
    its own grants): the walk for rows with several admitted groups."""
    levels = []
    while len(levels) < limit:
        matched = 0.0
        for count, tw, admitted in asks:
            best = None
            for gid, ok, w in admitted:
                if ok[row] and free[gid] >= count:
                    choice = w[row] / tw if tw else 0.0
                    if best is None or choice > best[0]:
                        best = (choice, gid, w[row])
            if best is None:
                return levels
            free[best[1]] -= count
            matched += best[2]
        levels.append(matched / total_w if total_w else 0.0)
    return levels


def host_volume_mask(cm: ClusterMatrix, volumes) -> np.ndarray:
    """HostVolumeChecker (feasible.go:133): every requested host volume must
    exist; a read-only node volume only satisfies read-only requests."""
    mask = np.ones(cm.n_rows, dtype=bool)
    for req in volumes.values():
        if req.type != "host":
            continue
        col = cm.attrs.columns.get(f"hostvol.{req.source}")
        if col is None:
            return np.zeros(cm.n_rows, dtype=bool)
        present = col.hash_codes != 0
        if req.read_only:
            mask &= present
        else:
            mask &= col.hash_codes == hash_code("rw")
    return mask


def device_mask(cm: ClusterMatrix, requests,
                include_usage: bool = True) -> np.ndarray:
    """DeviceChecker (feasible.go:1192): every device ask finds a group
    that answers to its name, passes its constraints and has the
    instances; with `include_usage`, free ones (`device_fit`)."""
    fit = device_fit(cm, requests)
    return fit.place_cap > 0 if include_usage else fit.capable
