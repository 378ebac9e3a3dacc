"""Shared infrastructure for the invariant linter suite.

Everything here is stdlib-only (ast + tokenize + re): the analyzers
parse source trees, they never import the code under analysis, so
`python -m nomad_tpu.analysis` runs in a bare interpreter with no
jax/numpy.

Suppression grammar (checked on the finding's line and on the line of
the enclosing `def`); every allow must state its reason after the
closing paren (the allow-audit satellite reports reasonless and unused
allows):

    ... code ...   # analysis: allow(checker-name) — why this is safe
    ... code ...   # analysis: allow(checker-a, checker-b) — reason
    ... code ...   # analysis: allow(*) — reason

A suppressed call site is also removed from call-graph traversal, so an
allowed edge does not leak findings from the functions behind it.
Allow comments are extracted from real COMMENT tokens (tokenize), never
from docstrings or string literals, so documentation that *quotes* the
grammar does not create suppressions.

The interprocedural core shared by the cone-walking checkers
(fsm-determinism, snapshot-completeness, canonical-form, wait-graph):

    index_functions    bare-name -> every def with that name
    walk_cone          BFS over the bare-name call graph with allow
                       pruning, the EDGE_DENYLIST, and the importable
                       edge filter
    find_fsm_classes   classes shaped like a raft FSM (apply + _apply_*)
    class_attr_types   per-class `self.attr` -> constructed/annotated
                       class name (receiver resolution)
    container_kinds    per-class `self.attr` -> container constructor
                       kind from __init__ (set/dict/defaultdict/...)
    lock_alloc_sites   per-class lock attr -> `file.py:line` allocation
                       site, the SAME naming the runtime
                       LockOrderRecorder uses, so the static wait-graph
                       and the runtime corpus share one node namespace
    attr_mutations     def-use: every mutation of `<base>.<attr>` in a
                       function body (assign/subscript/augassign/del/
                       mutator-method)
    expand_aliases     local names bound to a tracked base
                       (`s = self.store` makes `s._tbl.add(...)` a
                       store-table mutation)
"""
from __future__ import annotations

import ast
import io
import re
import tokenize
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, Iterable, Iterator, List, Optional, Sequence, Set, Tuple

ALLOW_RE = re.compile(
    r"#\s*analysis:\s*allow\(([^)]*)\)[ \t]*(?:[—:–-]+[ \t]*)?(.*)")

# directories never scanned, wherever the root points
EXCLUDED_PARTS = {"__pycache__", ".git", "build", ".jax_cache"}


@dataclass
class Finding:
    """One invariant violation."""
    checker: str
    path: str               # repo-relative (or root-relative) posix path
    line: int
    message: str
    chain: Tuple[str, ...] = ()   # call chain for transitive findings

    def to_dict(self) -> dict:
        d = {"checker": self.checker, "path": self.path,
             "line": self.line, "message": self.message}
        if self.chain:
            d["chain"] = list(self.chain)
        return d

    def render(self) -> str:
        s = f"{self.path}:{self.line}: [{self.checker}] {self.message}"
        if self.chain:
            s += f"  (via {' -> '.join(self.chain)})"
        return s


class SourceFile:
    """A parsed python source file plus its allow-comment map."""

    def __init__(self, path: Path, rel: str, text: str):
        self.path = path
        self.rel = rel
        self.text = text
        self.tree = ast.parse(text, filename=str(path))
        # dotted module name from the root-relative path:
        # nomad_tpu/state/store.py -> nomad_tpu.state.store
        mod = rel[:-3] if rel.endswith(".py") else rel
        mod = mod.replace("/", ".")
        if mod.endswith(".__init__"):
            mod = mod[: -len(".__init__")]
        self.module = mod
        self._imports: Optional[Set[str]] = None
        # line -> set of checker names allowed ("*" = all)
        self.allow: Dict[int, Set[str]] = {}
        # line -> the stated reason text ("" when missing)
        self.allow_reason: Dict[int, str] = {}
        # line -> checkers that actually consulted-and-matched the allow
        # during this corpus' lifetime (fed to the allow-audit)
        self.allow_used: Dict[int, Set[str]] = {}
        for ln, names, reason in _scan_allow_comments(text):
            self.allow[ln] = names
            self.allow_reason[ln] = reason

    @property
    def imports(self) -> Set[str]:
        """Dotted names this module imports (absolute and resolved
        relative), including `from pkg import sub` as `pkg.sub`."""
        if self._imports is None:
            out: Set[str] = set()
            pkg = self.module if self.rel.endswith("__init__.py") \
                else self.module.rpartition(".")[0]
            for node in ast.walk(self.tree):
                if isinstance(node, ast.Import):
                    for alias in node.names:
                        out.add(alias.name)
                elif isinstance(node, ast.ImportFrom):
                    base = node.module or ""
                    if node.level:
                        parts = pkg.split(".") if pkg else []
                        parts = parts[: len(parts) - (node.level - 1)] \
                            if node.level > 1 else parts
                        base = ".".join(parts + ([base] if base else []))
                    if base:
                        out.add(base)
                    for alias in node.names:
                        if base:
                            out.add(f"{base}.{alias.name}")
                        else:
                            out.add(alias.name)
            self._imports = out
        return self._imports

    def allowed(self, checker: str, *lines: Optional[int]) -> bool:
        for ln in lines:
            if ln is None:
                continue
            names = self.allow.get(ln)
            if names and ("*" in names or checker in names):
                self.allow_used.setdefault(ln, set()).add(checker)
                return True
        return False


def _scan_allow_comments(
        text: str) -> Iterator[Tuple[int, Set[str], str]]:
    """(line, names, reason) for every `# analysis: allow(...)` COMMENT
    token.  Docstrings and string literals quoting the grammar are NOT
    suppressions — only real comments count."""
    try:
        tokens = list(tokenize.generate_tokens(io.StringIO(text).readline))
    except (tokenize.TokenError, IndentationError):
        return
    for tok in tokens:
        if tok.type != tokenize.COMMENT:
            continue
        m = ALLOW_RE.search(tok.string)
        if not m:
            continue
        names = {p.strip() for p in m.group(1).split(",") if p.strip()}
        if names:
            yield tok.start[0], names, m.group(2).strip()


@dataclass
class Corpus:
    """The file set one analysis run operates on."""
    root: Path
    py: List[SourceFile] = field(default_factory=list)
    cpp: List[Tuple[Path, str, str]] = field(default_factory=list)  # (path, rel, text)
    # merged runtime lock-order corpus (LockOrderRecorder.dump JSON),
    # fed to the wait-graph checker when provided
    lock_corpus: Optional[dict] = None


def _is_excluded(rel: Path) -> bool:
    return any(part in EXCLUDED_PARTS for part in rel.parts)


def load_corpus(root: Path, include_tests: bool = False) -> Corpus:
    """Load every .py/.cpp under `root`.

    When `root` looks like the repo checkout (contains a `nomad_tpu`
    package), only `nomad_tpu/` and `native/` are scanned so the test
    fixtures' seeded violations never pollute a repo run.  Any other
    root (a fixture dir) is scanned wholesale.
    """
    root = Path(root).resolve()
    corpus = Corpus(root=root)
    if (root / "nomad_tpu").is_dir() and not include_tests:
        search_roots = [root / "nomad_tpu", root / "native"]
    else:
        search_roots = [root]
    seen: Set[Path] = set()
    for sr in search_roots:
        if not sr.exists():
            continue
        for p in sorted(sr.rglob("*.py")):
            rel = p.relative_to(root)
            if _is_excluded(rel) or p in seen:
                continue
            seen.add(p)
            try:
                text = p.read_text()
            except (OSError, UnicodeDecodeError):
                continue
            try:
                corpus.py.append(SourceFile(p, rel.as_posix(), text))
            except SyntaxError:
                continue
        for p in sorted(sr.rglob("*.cpp")):
            rel = p.relative_to(root)
            if _is_excluded(rel) or p in seen:
                continue
            seen.add(p)
            try:
                corpus.cpp.append((p, rel.as_posix(), p.read_text()))
            except (OSError, UnicodeDecodeError):
                continue
    return corpus


# ------------------------------------------------------------------ AST utils

def call_name(call: ast.Call) -> Optional[str]:
    """Bare callee name: `f(...)` -> 'f', `a.b.f(...)` -> 'f'."""
    f = call.func
    if isinstance(f, ast.Name):
        return f.id
    if isinstance(f, ast.Attribute):
        return f.attr
    return None


def dotted(expr: ast.AST) -> Optional[str]:
    """'a.b.c' for a Name/Attribute chain, else None."""
    parts: List[str] = []
    node = expr
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def decorator_names(fn: ast.AST) -> List[str]:
    """Dotted names of each decorator (call decorators yield the callee,
    so `@functools.partial(jax.jit, ...)` yields 'functools.partial')."""
    out = []
    for dec in getattr(fn, "decorator_list", []):
        target = dec.func if isinstance(dec, ast.Call) else dec
        name = dotted(target)
        if name:
            out.append(name)
    return out


@dataclass
class FuncInfo:
    """A function definition located in the corpus."""
    sf: SourceFile
    node: ast.AST                  # FunctionDef / AsyncFunctionDef
    qualname: str                  # Class.method or module-level name

    @property
    def key(self) -> str:
        return f"{self.sf.rel}::{self.qualname}"

    @property
    def cls(self) -> Optional[str]:
        """Enclosing class name, None for module-level defs."""
        if "." in self.qualname:
            return self.qualname.rsplit(".", 1)[0]
        return None


def index_functions(files: Sequence[SourceFile]) -> Dict[str, List[FuncInfo]]:
    """name -> every def with that bare name, package-wide.  The static
    call graph resolves calls by bare name (receiver types are unknown),
    which over-approximates: good for an invariant cone, where missing an
    edge is worse than following a spurious one."""
    index: Dict[str, List[FuncInfo]] = {}
    for sf in files:
        for node in ast.walk(sf.tree):
            if isinstance(node, ast.ClassDef):
                for item in node.body:
                    if isinstance(item, (ast.FunctionDef, ast.AsyncFunctionDef)):
                        index.setdefault(item.name, []).append(
                            FuncInfo(sf, item, f"{node.name}.{item.name}"))
            elif isinstance(node, ast.Module):
                for item in node.body:
                    if isinstance(item, (ast.FunctionDef, ast.AsyncFunctionDef)):
                        index.setdefault(item.name, []).append(
                            FuncInfo(sf, item, item.name))
    return index


def enclosing_def_line(sf: SourceFile, lineno: int) -> Optional[int]:
    """Line of the innermost def containing `lineno` (for def-level
    allow comments)."""
    best: Optional[int] = None
    best_span = None
    for node in ast.walk(sf.tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            end = getattr(node, "end_lineno", node.lineno)
            if node.lineno <= lineno <= end:
                span = end - node.lineno
                if best_span is None or span < best_span:
                    best, best_span = node.lineno, span
    return best


# --------------------------------------------------- interprocedural core

# bare names whose edges are never followed: dict/list/str methods that
# collide with ubiquitous helper names and cannot reach replicated state
EDGE_DENYLIST = {
    "get", "items", "keys", "values", "append", "extend", "pop",
    "popleft", "add", "discard", "remove", "clear", "update",
    "setdefault", "sort", "sorted", "join", "split", "strip",
    "startswith", "endswith", "encode", "decode", "format", "index",
    "count", "insert", "reverse", "lower", "upper", "replace",
}


def importable(src: SourceFile, dst: SourceFile) -> bool:
    """Edge filter: a module can only call into modules it imports (or
    itself).  Prunes bare-name collisions like `subprocess.run` matching
    `Worker.run` — the native module never imports the worker."""
    if src is dst:
        return True
    dst_mod = dst.module
    return any(imp == dst_mod or imp.startswith(dst_mod + ".")
               for imp in src.imports)


def walk_cone(index: Dict[str, List[FuncInfo]],
              seeds: Sequence[FuncInfo], checker: str,
              prune=None) -> Iterator[Tuple[FuncInfo, Tuple[str, ...]]]:
    """BFS over the bare-name call graph from `seeds`, yielding each
    reachable def ONCE with the shortest call chain that reached it.

    Edges are pruned by: `# analysis: allow(<checker>)` on the call line
    or the enclosing def line (the suppression fences the whole subtree),
    the EDGE_DENYLIST, the importable() module filter, and an optional
    `prune(call_node) -> bool` (e.g. sink calls whose internals are not
    part of the cone)."""
    visited: Set[str] = set()
    queue: List[Tuple[FuncInfo, Tuple[str, ...]]] = [
        (fi, (fi.qualname,)) for fi in seeds]
    while queue:
        fi, chain = queue.pop(0)
        if fi.key in visited:
            continue
        visited.add(fi.key)
        yield fi, chain
        sf = fi.sf
        for node in ast.walk(fi.node):
            if not isinstance(node, ast.Call):
                continue
            line = node.lineno
            if sf.allowed(checker, line, enclosing_def_line(sf, line)):
                continue
            if prune is not None and prune(node):
                continue
            callee = call_name(node)
            if callee is None or callee in EDGE_DENYLIST:
                continue
            for target in index.get(callee, ()):
                if target.key not in visited and importable(sf, target.sf):
                    queue.append((target, chain + (target.qualname,)))


def find_fsm_classes(
        files: Sequence[SourceFile]) -> List[Tuple[SourceFile, ast.ClassDef]]:
    """Classes shaped like the raft FSM: an `apply` plus `_apply_*`
    dispatch methods."""
    out = []
    for sf in files:
        for node in ast.walk(sf.tree):
            if isinstance(node, ast.ClassDef):
                names = {i.name for i in node.body
                         if isinstance(i, (ast.FunctionDef,
                                           ast.AsyncFunctionDef))}
                if "apply" in names and any(n.startswith("_apply_")
                                            for n in names):
                    out.append((sf, node))
    return out


def find_class(files: Sequence[SourceFile],
               name: str) -> Optional[Tuple[SourceFile, ast.ClassDef]]:
    for sf in files:
        for node in ast.walk(sf.tree):
            if isinstance(node, ast.ClassDef) and node.name == name:
                return sf, node
    return None


def class_methods(cls: ast.ClassDef) -> Dict[str, ast.AST]:
    return {i.name: i for i in cls.body
            if isinstance(i, (ast.FunctionDef, ast.AsyncFunctionDef))}


def _annotation_name(ann: ast.AST) -> Optional[str]:
    """Bare class name from a parameter annotation (`StateStore`,
    `"StateStore"`, `state.StateStore`, `Optional[StateStore]`)."""
    if isinstance(ann, ast.Constant) and isinstance(ann.value, str):
        return ann.value.strip("'\"").split(".")[-1] or None
    if isinstance(ann, ast.Subscript):
        return _annotation_name(ann.slice)
    name = dotted(ann)
    if name:
        return name.split(".")[-1]
    return None


def class_attr_types(
        files: Sequence[SourceFile]) -> Dict[str, Dict[str, str]]:
    """class name -> {self-attr: bare class name} inferred from method
    bodies: `self.x = ClassName(...)` and `self.x = param` where the
    parameter is annotated `param: ClassName`.  First binding wins."""
    out: Dict[str, Dict[str, str]] = {}
    for sf in files:
        for node in ast.walk(sf.tree):
            if not isinstance(node, ast.ClassDef):
                continue
            attrs = out.setdefault(node.name, {})
            for item in node.body:
                if not isinstance(item, (ast.FunctionDef,
                                         ast.AsyncFunctionDef)):
                    continue
                ann: Dict[str, str] = {}
                for a in item.args.args + item.args.kwonlyargs:
                    if a.annotation is not None:
                        t = _annotation_name(a.annotation)
                        if t:
                            ann[a.arg] = t
                for st in ast.walk(item):
                    if not (isinstance(st, ast.Assign)
                            and len(st.targets) == 1):
                        continue
                    tgt = st.targets[0]
                    if not (isinstance(tgt, ast.Attribute)
                            and isinstance(tgt.value, ast.Name)
                            and tgt.value.id == "self"):
                        continue
                    v = st.value
                    if isinstance(v, ast.Call):
                        n = dotted(v.func)
                        if n:
                            attrs.setdefault(tgt.attr, n.split(".")[-1])
                    elif isinstance(v, ast.Name) and v.id in ann:
                        attrs.setdefault(tgt.attr, ann[v.id])
    return out


_CONTAINER_CTORS = {"set", "frozenset", "dict", "defaultdict", "list",
                    "deque", "OrderedDict", "Counter"}


def container_kinds(cls: ast.ClassDef) -> Dict[str, str]:
    """self-attr -> container constructor kind, from `__init__` assigns:
    `self._x = set()` -> 'set', `= defaultdict(list)` -> 'defaultdict',
    `= {}` -> 'dict', `= []` -> 'list', `= {...}` (literal) -> 'dict'."""
    out: Dict[str, str] = {}
    init = class_methods(cls).get("__init__")
    if init is None:
        return out
    for st in ast.walk(init):
        if not (isinstance(st, ast.Assign) and len(st.targets) == 1):
            continue
        tgt = st.targets[0]
        if not (isinstance(tgt, ast.Attribute)
                and isinstance(tgt.value, ast.Name)
                and tgt.value.id == "self"):
            continue
        v = st.value
        kind = None
        if isinstance(v, ast.Dict):
            kind = "dict"
        elif isinstance(v, (ast.List, ast.ListComp)):
            kind = "list"
        elif isinstance(v, (ast.Set, ast.SetComp)):
            kind = "set"
        elif isinstance(v, ast.Call):
            n = dotted(v.func)
            if n and n.split(".")[-1] in _CONTAINER_CTORS:
                kind = n.split(".")[-1]
        if kind:
            out.setdefault(tgt.attr, kind)
    return out


_LOCK_CTORS = {"Lock", "RLock", "Condition", "Semaphore",
               "BoundedSemaphore"}


def lock_alloc_sites(
        files: Sequence[SourceFile]) -> Dict[Tuple[str, str], str]:
    """(class name, self-attr) -> `file.py:line` for every lock the
    class allocates (`self._lock = threading.RLock()` and friends).

    The naming deliberately matches the runtime LockOrderRecorder's
    `_alloc_site` (basename:lineno, threading frames skipped): a
    `threading.Condition()` wrapping nothing allocates its own RLock at
    the Condition() call line, while `Condition(self._lock)` aliases the
    wrapped lock's site — so the static wait-graph and the runtime
    corpus agree on node names and their edges merge."""
    sites: Dict[Tuple[str, str], str] = {}
    wraps: Dict[Tuple[str, str], Tuple[str, str]] = {}
    for sf in files:
        base = sf.rel.rsplit("/", 1)[-1]
        for node in ast.walk(sf.tree):
            if not isinstance(node, ast.ClassDef):
                continue
            for item in node.body:
                if not isinstance(item, (ast.FunctionDef,
                                         ast.AsyncFunctionDef)):
                    continue
                for st in ast.walk(item):
                    if not (isinstance(st, ast.Assign)
                            and len(st.targets) == 1):
                        continue
                    tgt = st.targets[0]
                    if not (isinstance(tgt, ast.Attribute)
                            and isinstance(tgt.value, ast.Name)
                            and tgt.value.id == "self"
                            and isinstance(st.value, ast.Call)):
                        continue
                    n = dotted(st.value.func)
                    ctor = n.split(".")[-1] if n else None
                    if ctor not in _LOCK_CTORS:
                        continue
                    key = (node.name, tgt.attr)
                    if ctor == "Condition" and st.value.args:
                        inner = st.value.args[0]
                        if isinstance(inner, ast.Attribute) and \
                                isinstance(inner.value, ast.Name) and \
                                inner.value.id == "self":
                            wraps[key] = (node.name, inner.attr)
                            continue
                    sites[key] = f"{base}:{st.lineno}"
    for key, target in wraps.items():
        sites[key] = sites.get(target, f"{target[0]}.{target[1]}")
    return sites


# ----------------------------------------------------- def-use helpers

# container methods that mutate their receiver in place
MUTATOR_METHODS = {"add", "append", "appendleft", "extend", "insert",
                   "discard", "remove", "clear", "update", "setdefault",
                   "pop", "popleft", "popitem"}


def _subscript_root(expr: ast.AST) -> ast.AST:
    node = expr
    while isinstance(node, ast.Subscript):
        node = node.value
    return node


def _base_attr(expr: ast.AST, bases: Set[str]) -> Optional[str]:
    """`<base>.<attr>` (possibly under Subscript chains) -> attr when
    the dotted base is tracked, else None."""
    node = _subscript_root(expr)
    if isinstance(node, ast.Attribute):
        b = dotted(node.value)
        if b is not None and b in bases:
            return node.attr
    return None


def _recv_attr(recv: ast.AST, bases: Set[str]) -> Optional[str]:
    """Receiver resolution for mutator-method calls, one chain level
    deep: `self._t.add(x)`, `self._t[k].add(x)`, and
    `self._t.setdefault(k, set()).add(x)`."""
    attr = _base_attr(recv, bases)
    if attr is not None:
        return attr
    if isinstance(recv, ast.Call) and isinstance(recv.func, ast.Attribute) \
            and recv.func.attr in ("setdefault", "get"):
        return _base_attr(recv.func.value, bases)
    return None


@dataclass
class Mutation:
    """One write to `<base>.<attr>` inside a function body."""
    attr: str
    line: int
    kind: str        # assign | subscript | augassign | del | method
    node: ast.AST    # the mutating statement/call


def attr_mutations(fn_node: ast.AST,
                   bases: Set[str]) -> List[Mutation]:
    """Every mutation of `<base>.<attr>` (base in `bases`, e.g.
    {'self'} or {'self.store', 's'}) in `fn_node`'s body:

    - `base.attr = v`               assign (wholesale rebind)
    - `base.attr[k] = v`            subscript
    - `base.attr[k] += v` etc.      augassign
    - `del base.attr[k]`            del
    - `base.attr.add(v)` etc.       method (incl. one-level chains via
                                    setdefault/get)
    """
    out: List[Mutation] = []
    for node in ast.walk(fn_node):
        if isinstance(node, ast.Assign):
            for tgt in node.targets:
                if isinstance(tgt, ast.Attribute):
                    b = dotted(tgt.value)
                    if b is not None and b in bases:
                        out.append(Mutation(tgt.attr, node.lineno,
                                            "assign", node))
                elif isinstance(tgt, (ast.Subscript, ast.Tuple)):
                    tgts = tgt.elts if isinstance(tgt, ast.Tuple) else [tgt]
                    for t in tgts:
                        attr = _base_attr(t, bases)
                        if attr is not None and isinstance(t, ast.Subscript):
                            out.append(Mutation(attr, node.lineno,
                                                "subscript", node))
        elif isinstance(node, ast.AugAssign):
            attr = _base_attr(node.target, bases)
            if attr is not None:
                out.append(Mutation(attr, node.lineno, "augassign", node))
        elif isinstance(node, ast.Delete):
            for tgt in node.targets:
                attr = _base_attr(tgt, bases)
                if attr is not None:
                    out.append(Mutation(attr, node.lineno, "del", node))
        elif isinstance(node, ast.Call):
            f = node.func
            if isinstance(f, ast.Attribute) and f.attr in MUTATOR_METHODS:
                attr = _recv_attr(f.value, bases)
                if attr is not None:
                    out.append(Mutation(attr, node.lineno, "method", node))
    return out


def expand_aliases(fn_node: ast.AST, bases: Set[str]) -> Set[str]:
    """`bases` plus every local name bound to a tracked base
    (`s = self.store` adds 's'), to a fixpoint."""
    out = set(bases)
    changed = True
    while changed:
        changed = False
        for node in ast.walk(fn_node):
            if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                    and isinstance(node.targets[0], ast.Name):
                d = dotted(node.value)
                if d is not None and d in out and \
                        node.targets[0].id not in out:
                    out.add(node.targets[0].id)
                    changed = True
    return out


def literal_strs(node: ast.AST) -> Set[str]:
    """Every string constant inside a literal expression (tuple/set/
    frozenset/dict-keys declarations like _LOCK_PROTECTED)."""
    out: Set[str] = set()
    for n in ast.walk(node):
        if isinstance(n, ast.Constant) and isinstance(n.value, str):
            out.add(n.value)
    return out


def module_decl(sf: "SourceFile", name: str) -> Optional[ast.AST]:
    """The value expression of a module-level `name = <literal>`
    declaration, else None (the module-scope twin of class_decl)."""
    for item in sf.tree.body:
        if isinstance(item, ast.Assign):
            for tgt in item.targets:
                if isinstance(tgt, ast.Name) and tgt.id == name:
                    return item.value
        elif isinstance(item, ast.AnnAssign):
            if isinstance(item.target, ast.Name) and \
                    item.target.id == name and item.value is not None:
                return item.value
    return None


def class_decl(cls: ast.ClassDef, name: str) -> Optional[ast.AST]:
    """The value expression of a class-level `name = <literal>`
    declaration, else None."""
    for item in cls.body:
        if isinstance(item, ast.Assign):
            for tgt in item.targets:
                if isinstance(tgt, ast.Name) and tgt.id == name:
                    return item.value
        elif isinstance(item, ast.AnnAssign):
            if isinstance(item.target, ast.Name) and \
                    item.target.id == name and item.value is not None:
                return item.value
    return None


def decl_str_dict(expr: Optional[ast.AST]) -> Dict[str, str]:
    """{str: str} from a dict literal declaration, tolerating non-str
    entries (skipped)."""
    out: Dict[str, str] = {}
    if isinstance(expr, ast.Dict):
        for k, v in zip(expr.keys, expr.values):
            if isinstance(k, ast.Constant) and isinstance(k.value, str) \
                    and isinstance(v, ast.Constant) \
                    and isinstance(v.value, str):
                out[k.value] = v.value
    return out


# ------------------------------------------- FSM / store pair resolution

@dataclass
class FsmStorePair:
    """One raft FSM class and the lock-protected store it replicates."""
    fsm_sf: SourceFile
    fsm_cls: ast.ClassDef
    store_sf: SourceFile
    store_cls: ast.ClassDef

    @property
    def tables(self) -> Set[str]:
        """The replicated-table universe: the store's _LOCK_PROTECTED."""
        decl = class_decl(self.store_cls, "_LOCK_PROTECTED")
        return literal_strs(decl) if decl is not None else set()


def resolve_fsm_stores(files: Sequence[SourceFile],
                       attr_types: Dict[str, Dict[str, str]]
                       ) -> List[FsmStorePair]:
    """Pair every FSM class with its store: the FSM attr (usually
    `self.store`) whose inferred type is a corpus class declaring
    `_LOCK_PROTECTED`."""
    out: List[FsmStorePair] = []
    for fsm_sf, fsm_cls in find_fsm_classes(files):
        for _attr, type_name in attr_types.get(fsm_cls.name, {}).items():
            hit = find_class(files, type_name)
            if hit is None:
                continue
            store_sf, store_cls = hit
            if class_decl(store_cls, "_LOCK_PROTECTED") is not None:
                out.append(FsmStorePair(fsm_sf, fsm_cls,
                                        store_sf, store_cls))
                break
    return out


def store_bases(fi: FuncInfo, store_cls_name: str,
                attr_types: Dict[str, Dict[str, str]]) -> Set[str]:
    """Dotted base expressions through which `fi`'s body can reach the
    store: `self` inside the store class itself, `self.<attr>` for attrs
    typed as the store, parameters annotated with the store class, and
    local aliases of any of those (`s = self.store`)."""
    bases: Set[str] = set()
    if fi.cls == store_cls_name:
        bases.add("self")
    for attr, t in attr_types.get(fi.cls or "", {}).items():
        if t == store_cls_name:
            bases.add(f"self.{attr}")
    args = fi.node.args
    for a in args.args + args.kwonlyargs:
        if a.annotation is not None and \
                _annotation_name(a.annotation) == store_cls_name:
            bases.add(a.arg)
    if not bases:
        return bases
    return expand_aliases(fi.node, bases)


def receiver_classes(fi: FuncInfo,
                     attr_types: Dict[str, Dict[str, str]]
                     ) -> Dict[str, str]:
    """Dotted base expression -> class name for every way `fi`'s body
    can name an object of known class: `self`, `self.<attr>` for typed
    attrs, annotated parameters, and local aliases of each."""
    out: Dict[str, str] = {}
    if fi.cls is not None:
        out["self"] = fi.cls
    for attr, t in attr_types.get(fi.cls or "", {}).items():
        out[f"self.{attr}"] = t
    args = fi.node.args
    for a in args.args + args.kwonlyargs:
        if a.annotation is not None:
            t = _annotation_name(a.annotation)
            if t is not None:
                out.setdefault(a.arg, t)
    for base, cls in list(out.items()):
        for alias in expand_aliases(fi.node, {base}):
            out.setdefault(alias, cls)
    return out


def resolve_call_targets(fi: FuncInfo, call: ast.Call,
                         index: Dict[str, List[FuncInfo]],
                         bases: Dict[str, str],
                         corpus_classes: Optional[Set[str]] = None
                         ) -> List[FuncInfo]:
    """Precise-when-possible call resolution (used by wait-graph, where
    a spurious edge manufactures a deadlock report; the invariant-cone
    checkers keep walk_cone's over-approximation instead, where a
    MISSED edge is the dangerous direction):

    - `self.m()` / `<typed base>.m()` -> that class's `m` when it has
      one; a known class with no methods in the corpus is EXTERNAL
      (threading.Thread, stdlib) and resolves to nothing; a corpus
      class missing the method (inheritance) falls back to the
      bare-name importable set
    - `<unknown receiver>.m()` -> bare-name importable set MINUS the
      enclosing class's own `m` (a foreign receiver is not `self`)
    """
    callee = call_name(call)
    if callee is None or callee in EDGE_DENYLIST:
        return []
    f = call.func
    candidates = index.get(callee, ())
    if isinstance(f, ast.Attribute):
        b = dotted(f.value)
        cls = bases.get(b) if b is not None else None
        if cls is not None:
            typed = [t for t in candidates if t.cls == cls]
            if typed:
                return typed
            if corpus_classes is not None and cls not in corpus_classes:
                return []
            return [t for t in candidates if importable(fi.sf, t.sf)]
        return [t for t in candidates
                if t.cls != fi.cls and importable(fi.sf, t.sf)]
    return [t for t in candidates if importable(fi.sf, t.sf)]


def is_empty_ctor(expr: ast.AST) -> bool:
    """A fresh-empty container expression: `{}`, `[]`, `set()`,
    `dict()`, `list()`, `deque()`, `defaultdict(factory)` — the legal
    'reset' shape for a derived index before its builder repopulates
    it row by row."""
    if isinstance(expr, ast.Dict):
        return not expr.keys
    if isinstance(expr, ast.List):
        return not expr.elts
    if isinstance(expr, ast.Call):
        n = dotted(expr.func)
        ctor = n.split(".")[-1] if n else None
        if ctor in ("set", "dict", "list", "deque", "OrderedDict",
                    "Counter"):
            return not expr.args and not expr.keywords
        if ctor == "defaultdict":
            return True    # args are the default factory, not contents
    return False
