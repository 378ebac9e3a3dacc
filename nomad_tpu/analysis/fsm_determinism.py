"""fsm-determinism: no nondeterminism in the raft FSM apply cone.

Replicas (and log replay onto a restored snapshot) must produce
byte-identical state from the same log entries, so everything reachable
from `NomadFSM.apply` may depend ONLY on the log payload and the current
store state.  This checker walks the shared interprocedural cone
(common.walk_cone) from the FSM's apply/restore methods and flags:

- wall-clock reads (`time.time`, `monotonic`, `perf_counter`, datetime
  now/utcnow, and the span primitive `tracing.span` / `tracing.record`)
- entropy (`random.*` draws, `uuid4`/`uuid1`, `os.urandom`) — including
  transitively, e.g. a helper that formats uuids
- iteration over unordered sets (set literals / `set()` constructions),
  whose order varies across processes when hash randomization differs

Resolution is by bare callee name over every def in the corpus — an
over-approximation (receiver types are unknown), kept honest by the
allow escape hatch (see common): an allowed call line is neither
flagged nor traversed, so leader-local side effects (broker enqueue,
heartbeat timers) can be fenced off explicitly at the FSM boundary.
"""
from __future__ import annotations

import ast
from typing import List, Optional, Set, Tuple

from nomad_tpu.analysis.common import (
    Corpus, Finding, FuncInfo, dotted, enclosing_def_line,
    find_fsm_classes, index_functions, walk_cone,
)

CHECKER = "fsm-determinism"

_WALLCLOCK_ATTRS = {"time", "time_ns", "monotonic", "monotonic_ns",
                    "perf_counter", "perf_counter_ns"}
_TIME_MODULES = {"time", "_time", "_t"}
_DATETIME_ATTRS = {"now", "utcnow", "today"}
_RANDOM_FNS = {"random", "randint", "randrange", "choice", "choices",
               "shuffle", "sample", "uniform", "gauss", "normalvariate",
               "expovariate", "betavariate", "getrandbits", "randbytes"}
_ENTROPY_NAMES = {"uuid4", "uuid1", "urandom", "token_hex", "token_bytes"}


def _sink(call: ast.Call) -> Optional[str]:
    """Nondeterminism description if this call is a sink, else None."""
    f = call.func
    if isinstance(f, ast.Attribute):
        base = dotted(f.value)
        if f.attr in _WALLCLOCK_ATTRS and base in _TIME_MODULES:
            return f"wall-clock read `{base}.{f.attr}()`"
        if f.attr in _DATETIME_ATTRS and base and \
                base.split(".")[-1] in ("datetime", "date"):
            return f"wall-clock read `{base}.{f.attr}()`"
        if f.attr in ("span", "record") and base == "tracing":
            # the span primitive reads the clock in __enter__/__exit__,
            # which the bare-name call graph cannot follow
            return f"wall-clock read via `tracing.{f.attr}()`"
        if f.attr in _ENTROPY_NAMES:
            return f"entropy source `.{f.attr}()`"
        if f.attr in _RANDOM_FNS and base is not None and \
                base.split(".")[-1] == "random":
            return f"entropy source `{base}.{f.attr}()`"
    elif isinstance(f, ast.Name):
        if f.id in _ENTROPY_NAMES:
            return f"entropy source `{f.id}()`"
    return None


def _is_set_expr(expr: ast.AST, local_sets: Set[str]) -> bool:
    if isinstance(expr, ast.Set):
        return True
    if isinstance(expr, ast.Call) and isinstance(expr.func, ast.Name) \
            and expr.func.id in ("set", "frozenset"):
        return True
    if isinstance(expr, ast.Name) and expr.id in local_sets:
        return True
    return False


def run(corpus: Corpus) -> List[Finding]:
    findings: List[Finding] = []
    index = index_functions(corpus.py)

    seeds: List[FuncInfo] = []
    for sf, cls in find_fsm_classes(corpus.py):
        for item in cls.body:
            if isinstance(item, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                    and (item.name == "apply" or item.name == "restore"
                         or item.name.startswith("_apply_")):
                seeds.append(FuncInfo(sf, item, f"{cls.name}.{item.name}"))

    reported: Set[Tuple[str, int]] = set()
    # sink calls are findings, not edges: their internals (stdlib) are
    # not part of the cone
    cone = walk_cone(index, seeds, CHECKER,
                     prune=lambda call: _sink(call) is not None)
    for fi, chain in cone:
        sf = fi.sf

        # names bound to set() expressions in this function, for the
        # unordered-iteration check
        local_sets: Set[str] = set()
        for node in ast.walk(fi.node):
            if isinstance(node, ast.Assign) and _is_set_expr(node.value, set()):
                for tgt in node.targets:
                    if isinstance(tgt, ast.Name):
                        local_sets.add(tgt.id)

        for node in ast.walk(fi.node):
            if isinstance(node, ast.Call):
                line = node.lineno
                if sf.allowed(CHECKER, line,
                              enclosing_def_line(sf, line)):
                    continue
                sink = _sink(node)
                if sink is not None:
                    key = (sf.rel, line)
                    if key not in reported:
                        reported.add(key)
                        findings.append(Finding(
                            CHECKER, sf.rel, line,
                            f"{sink} reachable from FSM apply", chain))
            elif isinstance(node, (ast.For, ast.comprehension)):
                it = node.iter
                line = getattr(node, "lineno",
                               getattr(it, "lineno", None)) or it.lineno
                if sf.allowed(CHECKER, line,
                              enclosing_def_line(sf, line)):
                    continue
                if _is_set_expr(it, local_sets):
                    key = (sf.rel, line)
                    if key not in reported:
                        reported.add(key)
                        findings.append(Finding(
                            CHECKER, sf.rel, line,
                            "iteration over an unordered set in the FSM "
                            "apply cone (order varies across replicas)",
                            chain))
    return findings
