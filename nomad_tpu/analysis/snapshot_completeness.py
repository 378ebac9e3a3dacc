"""snapshot-completeness: every replicated table survives the
snapshot/restore round trip, rebuilt by the SAME constructors apply uses.

A raft snapshot is the only state a late-joining (or compacted) replica
ever sees: a table the FSM apply cone mutates but snapshot() never
persists silently diverges the replica from the log, and a table
restore() rebuilds through different code than the apply path rebuilds
it (PR 5's aliasing bug, PR 13's quota-usage rebuild) diverges the
*bytes* even when the values agree.  This checker cross-references four
cones over the shared interprocedural core (common.walk_cone):

  apply cone      FSM `apply` + `_apply_*`  -> store-table mutations
  snapshot cone   FSM `snapshot`            -> persisted attrs + the
                                               string record keys
  restore cone    FSM `restore`             -> rebuilt attrs + the
                                               record keys read back

against the store's declarations:

  _LOCK_PROTECTED      the replicated-table universe
  _SNAPSHOT_DERIVED    {table: builder method} — derived indexes that
                       are rebuilt, not persisted; restore MUST route
                       every row through the named builder, and an
                       incremental builder (one that adds rows in
                       place) must also be reachable from the apply
                       cone, so apply and restore share one constructor
  _SNAPSHOT_EPHEMERAL  caches that legitimately die with the process

and reports:

  - write-only tables   mutated under apply, never persisted/derived
  - persist-only        persisted but never restored
  - restore-only        restored but never persisted (and not derived)
  - record-key drift    snapshot record keys vs the keys restore reads
  - inline rebuilds     restore mutating a derived index outside its
                        builder (resetting to an empty container, by
                        assignment or by `.clear()`, is the one legal
                        inline form)
  - builder drift       a declared builder missing, unreachable from
                        restore, or incremental yet unreachable from
                        apply (rows rebuilt through a constructor the
                        apply path never uses)
"""
from __future__ import annotations

import ast
from typing import Dict, List, Optional, Set, Tuple

from nomad_tpu.analysis.common import (
    Corpus, Finding, FuncInfo, Mutation, attr_mutations, call_name,
    class_attr_types, class_decl, class_methods, decl_str_dict, dotted,
    enclosing_def_line, index_functions, is_empty_ctor, literal_strs,
    resolve_fsm_stores, store_bases, walk_cone,
)

CHECKER = "snapshot-completeness"


def _cone(index, seeds, store_cls: str, attr_types, universe: Set[str]):
    """Walk a cone, returning ({func key}, [(fi, chain, [Mutation])],
    {attr -> (sf, Mutation, chain)} first-mutation sites) restricted to
    the table universe."""
    keys: Set[str] = set()
    visits = []
    first: Dict[str, Tuple] = {}
    for fi, chain in walk_cone(index, seeds, CHECKER):
        keys.add(fi.key)
        bases = store_bases(fi, store_cls, attr_types)
        muts = [m for m in attr_mutations(fi.node, bases)
                if m.attr in universe] if bases else []
        visits.append((fi, chain, muts))
        for m in muts:
            first.setdefault(m.attr, (fi.sf, m, chain))
    return keys, visits, first


def _referenced_attrs(fi: FuncInfo, bases: Set[str],
                      universe: Set[str]) -> Set[str]:
    out: Set[str] = set()
    for node in ast.walk(fi.node):
        if isinstance(node, ast.Attribute) and node.attr in universe:
            b = dotted(node.value)
            if b is not None and b in bases:
                out.add(node.attr)
    return out


def _record_keys(fi: FuncInfo) -> Dict[str, int]:
    """String keys of dict literals built in the snapshot fn -> line."""
    out: Dict[str, int] = {}
    for node in ast.walk(fi.node):
        if isinstance(node, ast.Dict):
            for k in node.keys:
                if isinstance(k, ast.Constant) and isinstance(k.value, str):
                    out.setdefault(k.value, k.lineno)
    return out


def _blob_names(fi: FuncInfo) -> Set[str]:
    """Local names bound to the deserialized snapshot record
    (`data = pickle.loads(blob)` and aliases)."""
    names: Set[str] = set()
    for node in ast.walk(fi.node):
        if isinstance(node, ast.Assign) and len(node.targets) == 1 and \
                isinstance(node.targets[0], ast.Name) and \
                isinstance(node.value, ast.Call):
            callee = call_name(node.value)
            if callee in ("loads", "load"):
                names.add(node.targets[0].id)
    # aliases of the record dict
    changed = True
    while changed:
        changed = False
        for node in ast.walk(fi.node):
            if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                    and isinstance(node.targets[0], ast.Name) \
                    and isinstance(node.value, ast.Name) \
                    and node.value.id in names \
                    and node.targets[0].id not in names:
                names.add(node.targets[0].id)
                changed = True
    return names


def _read_keys(fi: FuncInfo, blob_names: Set[str]) -> Dict[str, int]:
    """Record keys the restore fn reads: `data["k"]`, `data.get("k")`,
    `"k" in data` -> line."""
    out: Dict[str, int] = {}

    def is_blob(expr: ast.AST) -> bool:
        return isinstance(expr, ast.Name) and expr.id in blob_names

    for node in ast.walk(fi.node):
        if isinstance(node, ast.Subscript) and is_blob(node.value):
            sl = node.slice
            if isinstance(sl, ast.Constant) and isinstance(sl.value, str):
                out.setdefault(sl.value, node.lineno)
        elif isinstance(node, ast.Call) and \
                isinstance(node.func, ast.Attribute) and \
                node.func.attr == "get" and is_blob(node.func.value):
            if node.args and isinstance(node.args[0], ast.Constant) and \
                    isinstance(node.args[0].value, str):
                out.setdefault(node.args[0].value, node.lineno)
        elif isinstance(node, ast.Compare) and \
                len(node.ops) == 1 and \
                isinstance(node.ops[0], (ast.In, ast.NotIn)) and \
                is_blob(node.comparators[0]) and \
                isinstance(node.left, ast.Constant) and \
                isinstance(node.left.value, str):
            out.setdefault(node.left.value, node.lineno)
    return out


def run(corpus: Corpus) -> List[Finding]:
    findings: List[Finding] = []
    files = corpus.py
    index = index_functions(files)
    attr_types = class_attr_types(files)

    for pair in resolve_fsm_stores(files, attr_types):
        fsm_sf, fsm_cls = pair.fsm_sf, pair.fsm_cls
        store_cls_name = pair.store_cls.name
        universe = pair.tables
        if not universe:
            continue
        derived = decl_str_dict(
            class_decl(pair.store_cls, "_SNAPSHOT_DERIVED"))
        eph_decl = class_decl(pair.store_cls, "_SNAPSHOT_EPHEMERAL")
        ephemeral = literal_strs(eph_decl) if eph_decl is not None else set()
        methods = class_methods(fsm_cls)
        snap_fn = methods.get("snapshot")
        restore_fn = methods.get("restore")
        store_methods = class_methods(pair.store_cls)

        def fi_of(sf, cls, fn) -> FuncInfo:
            return FuncInfo(sf, fn, f"{cls.name}.{fn.name}")

        def report(sf, line: int, msg: str,
                   chain: Tuple[str, ...] = ()) -> None:
            if not sf.allowed(CHECKER, line, enclosing_def_line(sf, line)):
                findings.append(Finding(CHECKER, sf.rel, line, msg, chain))

        # ---- apply cone: every table the log can mutate
        apply_seeds = [fi_of(fsm_sf, fsm_cls, fn)
                       for name, fn in methods.items()
                       if name == "apply" or name.startswith("_apply_")]
        apply_keys, _apply_visits, apply_first = _cone(
            index, apply_seeds, store_cls_name, attr_types, universe)

        # ---- snapshot cone: persisted attrs + record keys
        persisted: Set[str] = set()
        snap_keys: Dict[str, int] = {}
        snap_line = fsm_cls.lineno
        if snap_fn is not None:
            snap_line = snap_fn.lineno
            for fi, _chain in walk_cone(
                    index, [fi_of(fsm_sf, fsm_cls, snap_fn)], CHECKER):
                bases = store_bases(fi, store_cls_name, attr_types)
                if bases:
                    persisted |= _referenced_attrs(fi, bases, universe)
                for k, ln in _record_keys(fi).items():
                    snap_keys.setdefault(k, ln)

        # ---- restore cone: rebuilt attrs + record keys read back
        restored: Set[str] = set()
        restore_keys: Dict[str, int] = {}
        restore_line = fsm_cls.lineno
        restore_visits = []
        restore_cone_keys: Set[str] = set()
        if restore_fn is not None:
            restore_line = restore_fn.lineno
            restore_cone_keys, restore_visits, restore_first = _cone(
                index, [fi_of(fsm_sf, fsm_cls, restore_fn)],
                store_cls_name, attr_types, universe)
            restored = set(restore_first)
            for fi, _chain, _muts in restore_visits:
                blobs = _blob_names(fi)
                if blobs:
                    for k, ln in _read_keys(fi, blobs).items():
                        restore_keys.setdefault(k, ln)

        # ---- write-only tables: mutated under apply, never persisted
        for attr in sorted(apply_first):
            if attr in persisted or attr in derived or attr in ephemeral:
                continue
            sf, m, chain = apply_first[attr]
            report(sf, m.line,
                   f"store table `{attr}` is mutated in the FSM apply "
                   f"cone but never persisted by snapshot() and not "
                   f"declared in _SNAPSHOT_DERIVED/_SNAPSHOT_EPHEMERAL "
                   f"(write-only replication state)", chain)

        # ---- persist-only / restore-only tables
        if snap_fn is not None and restore_fn is not None:
            for attr in sorted(persisted - restored - ephemeral):
                report(fsm_sf, snap_line,
                       f"snapshot() persists store table `{attr}` but "
                       f"restore() never rebuilds it (lost on every "
                       f"snapshot install)")
            for attr in sorted(restored - persisted
                               - set(derived) - ephemeral):
                report(fsm_sf, restore_line,
                       f"restore() rebuilds store table `{attr}` which "
                       f"snapshot() never persists (restore-only table: "
                       f"replicas that install the snapshot invent state "
                       f"the leader never had)")

            # ---- record-key drift between persist and restore
            for k in sorted(set(snap_keys) - set(restore_keys)):
                report(fsm_sf, snap_keys[k],
                       f"snapshot record key '{k}' is never read back "
                       f"by restore()")
            for k in sorted(set(restore_keys) - set(snap_keys)):
                report(fsm_sf, restore_keys[k],
                       f"restore() reads record key '{k}' that "
                       f"snapshot() never writes")

        # ---- derived indexes: restore must route rows through the
        # declared builder; resetting to an empty container is the one
        # legal inline mutation
        for fi, chain, muts in restore_visits:
            in_builder = fi.cls == store_cls_name and \
                fi.node.name in derived.values()
            if in_builder:
                continue
            via = {c.rsplit(".", 1)[-1] for c in chain}
            for m in muts:
                if m.attr not in derived:
                    continue
                if derived[m.attr] in via:
                    # reached through the declared builder (a helper it
                    # delegates to) — still the shared constructor
                    continue
                if m.kind == "assign" and is_empty_ctor(m.node.value):
                    continue
                if m.kind == "method" and m.node.func.attr == "clear":
                    continue        # the same reset, of a table kept in place
                report(fi.sf, m.line,
                       f"derived index `{m.attr}` rebuilt inline in the "
                       f"restore path; route rows through "
                       f"`{derived[m.attr]}` so apply and restore share "
                       f"one constructor", chain)

        # ---- builder declarations: exist, reachable from restore, and
        # (when incremental) shared with the apply path
        decl_node = class_decl(pair.store_cls, "_SNAPSHOT_DERIVED")
        decl_line = getattr(decl_node, "lineno", pair.store_cls.lineno)
        for attr, builder in sorted(derived.items()):
            fn = store_methods.get(builder)
            if fn is None:
                report(pair.store_sf, decl_line,
                       f"_SNAPSHOT_DERIVED maps `{attr}` to "
                       f"`{builder}`, which is not a method of "
                       f"{store_cls_name}")
                continue
            bkey = f"{pair.store_sf.rel}::{store_cls_name}.{builder}"
            if restore_fn is not None and bkey not in restore_cone_keys:
                report(pair.store_sf, fn.lineno,
                       f"derived-index builder `{builder}` (for "
                       f"`{attr}`) is never called from the restore "
                       f"path")
            own = [m for m in attr_mutations(fn, {"self"})
                   if m.attr == attr]
            incremental = any(m.kind != "assign" for m in own)
            if incremental and apply_seeds and bkey not in apply_keys:
                report(pair.store_sf, fn.lineno,
                       f"incremental builder `{builder}` rebuilds "
                       f"`{attr}` row-by-row in restore but is never "
                       f"called from the FSM apply cone (restore uses a "
                       f"constructor apply never uses)")
    return findings
