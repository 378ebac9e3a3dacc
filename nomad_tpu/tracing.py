"""One span primitive for the placement spine, and its three sinks.

`span(name)` (a context manager) and `record(name, start, end)` (for an
interval that began on another thread, or whose two clock readings the
caller took itself) are the only way the program times a layer
boundary.  One call feeds, from one pair of `time.perf_counter()` reads:

* **the counter, always**: `count` and `total` of the span go to
  `telemetry.global_metrics` as the Sample ``nomad.<name>`` (`/v1/metrics`).
  A span that had child spans on its own thread also records
  ``nomad.self.<name>``: its duration less what its direct children
  covered.  `span(name, cpu=True)` also reads `time.thread_time()` at
  both ends and records ``nomad.cpu.<name>``: the milliseconds of the
  span in which its thread was running.  The wall time less that is the
  time a "working" thread waited to run (the GIL, mostly).  A thread
  clock read is a system call, so only the few call sites whose CPU
  share a metric reads ask for it.
* **the profiler's clock**: while a `jax.profiler` session runs, the span
  is a `TraceAnnotation` in the `/host:CPU` lines of the trace, beside
  the device ops.  Each thread shows a FLAT sequence named by its
  innermost open span (an enclosing span is cut into the pieces of its
  self time), and a `wait=True` span (blocked on another thread's work:
  a future, a queue, a commit) is not annotated: what is on the
  profiler's timeline is work.  With no session the cost is one flag
  test; jax is never imported from here, so a process that does not
  schedule does not pay for it.
* **a Dapper span, when sampled**: with a `Tracer` installed and a
  sampled context bound to the thread (or passed as `ctx`), the same
  call adds a `Span` with its parent to the `SpanStore` and binds the
  child context for the span's duration.

A `Tracer` makes one sampling decision at ingress; sampled requests get a
trace context — ``{"t": trace_id, "s": parent_span_id, "b": 1}`` — that
rides RPC args end-to-end under the reserved key `TRACE_KEY`.  Absence of
the key IS the unsampled state.  The tracer is installed process-wide
(`install()`) or picked up from the environment at import:

    NOMAD_TPU_TRACE=1 NOMAD_TPU_TRACE_SAMPLE=0.01 nomad agent ...

**The collector's pauses** are spans too: while an agent runs
(`watch_gc`), every collection is ``gc.collect.gen<n>`` on the thread
that collects, a child of whatever span was open there, so it leaves
that span's self time and is a piece of its own on the profiler's
timeline (an idle gap under a collection reads ``gc.collect.gen2``, not
the victim's name).  A collection may start while its thread holds the
registry's or the tracer's lock, so the callback takes no lock: the
counter and the Dapper span are written by the next `span` or `record`
to end, on any thread.

No clock reading reaches the store or a log payload, so replicas replay
to byte-identical state (see nomad_tpu.analysis.fsm_determinism).  A
span under the FSM's apply (`store.plan_write`, `store.plan_notify`,
`store.bucket_copy`) is allowed line by line there: its duration goes
to the three sinks above and to nothing a replica stores
(`tests/test_span_metrics.py` holds two replicas fed one log to
byte-identical state).  Trace context never rides in log payloads.
Durations come from `time.perf_counter()`; the wall clock is read only
for a Dapper span's display start.

Dapper spans land in a bounded ring `SpanStore` per server
(`store_for(node)`), queried through `/v1/traces` +
`/v1/traces/<trace_id>` and exportable as Chrome-trace JSON
(`chrome_trace()`) for Perfetto.
"""
from __future__ import annotations

import gc
import random
import sys
import threading
import time
from collections import deque
from time import perf_counter, thread_time
from typing import Any, Dict, List, Optional

from nomad_tpu import knobs
from nomad_tpu.analysis import race
from nomad_tpu.telemetry import global_metrics

# reserved RPC-args key the context rides under; handlers pop it before
# dispatch so endpoint code never sees it in its own args
TRACE_KEY = "_trace"


class Span:
    __slots__ = ("trace_id", "span_id", "parent_id", "name",
                 "start", "duration", "node", "attrs")

    def __init__(self, trace_id: str, span_id: str, parent_id: str,
                 name: str, start: float, duration: float = 0.0,
                 node: str = "", attrs: Optional[Dict[str, Any]] = None):
        self.trace_id = trace_id
        self.span_id = span_id
        self.parent_id = parent_id
        self.name = name
        self.start = start
        self.duration = duration
        self.node = node
        self.attrs = attrs or {}

    def to_dict(self) -> Dict[str, Any]:
        return {"trace_id": self.trace_id, "span_id": self.span_id,
                "parent_id": self.parent_id, "name": self.name,
                "start": self.start, "duration": self.duration,
                "node": self.node, "attrs": self.attrs}


class SpanStore:
    """Bounded ring of finished spans for one server.  Shared by every
    request thread on that server, so the ring is lock-guarded and traced
    by the happens-before detector like the event broker's queues."""

    _LOCK_NAME = "_lock"
    _LOCK_PROTECTED = frozenset({"_spans"})
    _RACE_TRACED = {"_spans": "_lock"}

    def __init__(self, limit: int = 4096):
        self._lock = threading.Lock()
        self._spans: deque = deque(maxlen=limit)

    def add(self, span: Span) -> None:
        with self._lock:
            race.write("SpanStore._spans", self)
            self._spans.append(span)

    def snapshot(self, trace_id: Optional[str] = None) -> List[Span]:
        with self._lock:
            race.read("SpanStore._spans", self)
            if trace_id is None:
                return list(self._spans)
            return [s for s in self._spans if s.trace_id == trace_id]

    def __len__(self) -> int:
        with self._lock:
            race.read("SpanStore._spans", self)
            return len(self._spans)


class Tracer:
    """Process-wide trace plane: sampling, span-id allocation, per-node
    span stores, and the propose-time side tables that let the broker
    wait and the raft pipeline be timed without touching the FSM cone."""

    # evals noted at propose time but never dequeued (leadership churn,
    # failed applies) must not leak; the table is bounded and evicts
    # oldest-first
    _NOTE_LIMIT = 4096

    def __init__(self, sample_rate: float = 1.0, seed: int = 0,
                 store_limit: int = 4096):
        self.sample_rate = float(sample_rate)
        self.store_limit = int(store_limit)
        self._rng = random.Random(seed)
        self._lock = threading.Lock()
        self._stores: Dict[str, SpanStore] = {}
        # eval_id -> ctx: written at propose time (outside the FSM),
        # taken at broker dequeue and again by the dequeuing worker
        self._eval_notes: Dict[str, dict] = {}

    # ------------------------------------------------------------- sampling

    def _new_id(self) -> str:
        with self._lock:
            return "%016x" % self._rng.getrandbits(64)

    def new_context(self) -> Optional[dict]:
        """One sampling decision at ingress; None means unsampled and the
        request proceeds with zero further tracing work anywhere."""
        with self._lock:
            if self._rng.random() >= self.sample_rate:
                return None
            return {"t": "%016x" % self._rng.getrandbits(64),
                    "s": "", "b": 1}

    # ------------------------------------------------------------- spans

    def start(self, ctx: dict, name: str, node: str = "") -> Span:
        return Span(trace_id=ctx["t"], span_id=self._new_id(),
                    parent_id=ctx.get("s", ""), name=name,
                    start=time.time(), node=node)

    def finish(self, span: Span,
               duration: Optional[float] = None) -> None:
        span.duration = max(0.0, time.time() - span.start) \
            if duration is None else duration
        self.store_for(span.node).add(span)

    def emit(self, ctx: dict, name: str, start: float, end: float,
             node: str = "", **attrs) -> Span:
        """Record a finished span from externally captured timestamps
        (observe-time emission for work that already happened)."""
        span = Span(trace_id=ctx["t"], span_id=self._new_id(),
                    parent_id=ctx.get("s", ""), name=name, start=start,
                    duration=max(0.0, end - start), node=node,
                    attrs=attrs or None)
        self.store_for(node).add(span)
        return span

    @staticmethod
    def child_ctx(ctx: dict, span: Span) -> dict:
        return {"t": ctx["t"], "s": span.span_id, "b": 1}

    # ------------------------------------------------------------- stores

    def store_for(self, node: str) -> SpanStore:
        with self._lock:
            st = self._stores.get(node)
            if st is None:
                st = self._stores[node] = SpanStore(self.store_limit)
            return st

    def spans(self, trace_id: Optional[str] = None) -> List[Span]:
        with self._lock:
            stores = list(self._stores.values())
        out: List[Span] = []
        for st in stores:
            out.extend(st.snapshot(trace_id))
        out.sort(key=lambda s: s.start)
        return out

    def traces(self) -> List[Dict[str, Any]]:
        """Trace summaries, newest first: root span name, start, total
        duration (max span end - min span start), span count, nodes."""
        by_id: Dict[str, List[Span]] = {}
        for s in self.spans():
            by_id.setdefault(s.trace_id, []).append(s)
        out = []
        for tid, spans in by_id.items():
            start = min(s.start for s in spans)
            end = max(s.start + s.duration for s in spans)
            roots = [s for s in spans if not s.parent_id]
            out.append({
                "trace_id": tid,
                "root": roots[0].name if roots else spans[0].name,
                "start": start,
                "duration": end - start,
                "spans": len(spans),
                "nodes": sorted({s.node for s in spans}),
            })
        out.sort(key=lambda t: t["start"], reverse=True)
        return out

    # ------------------------------------------------------------- notes

    def note_eval(self, eval_id: str, ctx: dict) -> None:
        """Propose-time note: the eval was created under `ctx`.  The
        FSM's leader hook enqueues the eval inside the apply cone, so
        the context crosses the broker here instead of in the payload."""
        with self._lock:
            while len(self._eval_notes) >= self._NOTE_LIMIT:
                self._eval_notes.pop(next(iter(self._eval_notes)))
            self._eval_notes[eval_id] = ctx

    def take_eval_note(self, eval_id: str) -> Optional[dict]:
        with self._lock:
            return self._eval_notes.pop(eval_id, None)


# ===================================================================== export

def chrome_trace(spans: List[Dict[str, Any]]) -> Dict[str, Any]:
    """Chrome-trace (Trace Event Format) JSON for Perfetto / chrome://
    tracing: one complete ("X") event per span, one process row per
    node, timestamps in microseconds."""
    pids: Dict[str, int] = {}
    events: List[Dict[str, Any]] = []
    for s in spans:
        node = s.get("node") or "-"
        pid = pids.get(node)
        if pid is None:
            pid = pids[node] = len(pids) + 1
            events.append({"name": "process_name", "ph": "M", "pid": pid,
                           "tid": 0, "args": {"name": node}})
        ev = {"name": s["name"], "ph": "X", "pid": pid, "tid": 0,
              "ts": s["start"] * 1e6, "dur": s["duration"] * 1e6,
              "args": {"trace_id": s["trace_id"],
                       "span_id": s["span_id"],
                       "parent_id": s["parent_id"]}}
        attrs = s.get("attrs")
        if attrs:
            ev["args"].update(attrs)
        events.append(ev)
    return {"traceEvents": events, "displayTimeUnit": "ms"}


# ===================================================================== module

# the installed tracer, or None: the Dapper sink is off unless this is
# set, and then only sampled contexts allocate spans
active: Optional[Tracer] = None

_tls = threading.local()


def install(tracer: Optional[Tracer]) -> Optional[Tracer]:
    global active
    prev = active
    active = tracer
    return prev


def uninstall() -> Optional[Tracer]:
    return install(None)


def current() -> Optional[dict]:
    """The trace context bound to this thread, or None (unsampled)."""
    return getattr(_tls, "ctx", None)


def bind(ctx: Optional[dict]) -> Optional[dict]:
    """Bind `ctx` as this thread's current trace context; returns the
    previous binding so callers can restore it in a finally block."""
    prev = getattr(_tls, "ctx", None)
    _tls.ctx = ctx
    return prev


def note_evals(eval_ids, ctx: Optional[dict] = None) -> None:
    """Propose-time: these evals are created under `ctx`, or this
    thread's sampled context (no-op when unsampled)."""
    tracer = active
    if tracer is not None:
        ctx = ctx or current()
        if ctx is not None:
            for eval_id in eval_ids:
                tracer.note_eval(eval_id, ctx)


def take_eval_ctx(eval_id: str) -> Optional[dict]:
    """The sampled context noted for this eval, or None."""
    tracer = active
    return tracer.take_eval_note(eval_id) if tracer is not None else None


# ====================================================================== spans

_TraceMe = None


def _annotate(name: str, load: bool = True):
    """An entered profiler annotation, or None when no profiler session
    runs (or jax was never imported by this process).  `load=False`
    never imports: the collector's callback may run in the middle of
    jax's own import."""
    global _TraceMe
    tm = _TraceMe
    if tm is None:
        if not load or "jax" not in sys.modules:
            return None
        from jax.profiler import TraceAnnotation as tm
        _TraceMe = tm
    if not tm.is_enabled():
        return None
    ann = tm(name)
    ann.__enter__()
    return ann


class span:
    """``with tracing.span("plan.evaluate"): ...`` — see the module
    docstring for the three sinks.  `wait=True` marks time blocked on
    another thread's work.  `cpu=True` also records the thread's CPU
    time as ``nomad.cpu.<name>``.  `ctx` starts the Dapper span under that
    context instead of the thread's own (ingress, contexts that crossed
    a queue); `node` and `attrs` go to the Dapper span only, and `attrs`
    may be filled while the span is open.  After exit `seconds` holds
    the duration."""

    __slots__ = ("name", "wait", "cpu", "ctx", "node", "attrs", "seconds",
                 "_t0", "_c0", "_children", "_ann", "_dapper", "_prev")

    def __init__(self, name: str, wait: bool = False,
                 ctx: Optional[dict] = None, node: str = "",
                 cpu: bool = False, **attrs):
        self.name = name
        self.wait = wait
        self.cpu = cpu
        self.ctx = ctx
        self.node = node
        self.attrs = attrs
        self.seconds = 0.0
        self._children = None       # seconds its direct children covered
        self._ann = self._dapper = None

    def __enter__(self) -> "span":
        stack = getattr(_tls, "stack", None)
        if stack is None:
            stack = _tls.stack = []
        elif stack and stack[-1]._ann is not None:
            # flat timeline: the enclosing span's piece ends here
            stack[-1]._ann.__exit__(None, None, None)
            stack[-1]._ann = None
        stack.append(self)
        tracer = active
        if tracer is not None:
            ctx = self.ctx or getattr(_tls, "ctx", None)
            if ctx is not None:
                self._dapper = tracer.start(ctx, self.name, self.node)
                self._prev = bind(tracer.child_ctx(ctx, self._dapper))
        if not self.wait:
            self._ann = _annotate(self.name)
        if self.cpu:
            self._c0 = thread_time()
        self._t0 = perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        dur = self.seconds = perf_counter() - self._t0
        if self.cpu:
            ran = thread_time() - self._c0
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
        stack = _tls.stack
        stack.pop()
        if _gc_done:
            _flush_gc()
        global_metrics.add_sample("nomad." + self.name, dur * 1e3)
        if self.cpu:
            global_metrics.add_sample("nomad.cpu." + self.name, ran * 1e3)
        if self._children is not None:
            global_metrics.add_sample(
                "nomad.self." + self.name,
                max(0.0, dur - self._children) * 1e3)
        if stack:
            parent = stack[-1]
            parent._children = (parent._children or 0.0) + dur
            if not parent.wait:
                parent._ann = _annotate(parent.name)
        sp = self._dapper
        if sp is not None:
            bind(self._prev)
            if self.wait:
                self.attrs["wait"] = True
            sp.attrs = self.attrs
            tracer = active
            if tracer is not None:
                tracer.finish(sp, dur)
        return False


def record(name: str, start: float, end: float, wait: bool = False,
           ctx: Optional[dict] = None, node: str = "", **attrs) -> None:
    """Observe-time form of `span` for an interval that began on another
    thread or before this one took the work over (a queue wait, a
    request's submit-to-resolve): `start` and `end` are
    `time.perf_counter()` readings.  Counted like a span; never on the
    profiler's timeline (it is over), never a child of the thread's open
    span; a Dapper span under `ctx` when one is given."""
    if _gc_done:
        _flush_gc()
    _record(name, start, end, wait, ctx, node, attrs)


def _record(name: str, start: float, end: float, wait: bool,
            ctx: Optional[dict], node: str, attrs: dict) -> None:
    dur = max(0.0, end - start)
    global_metrics.add_sample("nomad." + name, dur * 1e3)
    tracer = active
    if tracer is not None and ctx is not None:
        if wait:
            attrs["wait"] = True
        wall = time.time() - (perf_counter() - start)
        tracer.emit(ctx, name, wall, wall + dur, node=node, **attrs)


# ================================================================ collector

_GC_NAMES = ("gc.collect.gen0", "gc.collect.gen1", "gc.collect.gen2")
# the collection under way (one at a time in a process) and the finished
# ones whose counter and Dapper span are still to be written
_gc_open: Optional[tuple] = None
_gc_done: deque = deque()
_gc_watchers = 0
_gc_lock = threading.Lock()


def _on_gc(phase: str, info: dict) -> None:
    """`gc.callbacks` entry: the collection as a span of the collecting
    thread.  Takes no lock and touches neither the registry nor the
    tracer (see the module docstring)."""
    global _gc_open
    stack = getattr(_tls, "stack", None)
    parent = stack[-1] if stack else None
    if phase == "start":
        cut = parent is not None and parent._ann is not None
        if cut:
            parent._ann.__exit__(None, None, None)
            parent._ann = None
        name = _GC_NAMES[info["generation"]]
        _gc_open = (name, _annotate(name, False), cut, perf_counter())
    elif _gc_open is not None:
        end = perf_counter()
        (name, ann, cut, start), _gc_open = _gc_open, None
        if ann is not None:
            ann.__exit__(None, None, None)
        if parent is not None:
            parent._children = (parent._children or 0.0) + (end - start)
            if cut:
                parent._ann = _annotate(parent.name, False)
        _gc_done.append((name, start, end, getattr(_tls, "ctx", None)))


def _flush_gc() -> None:
    while True:
        try:
            name, start, end, ctx = _gc_done.popleft()
        except IndexError:
            return
        _record(name, start, end, False, ctx, "", {})


def watch_gc(on: bool) -> None:
    """An agent's start (`on`) and shutdown: the collector's callback is
    registered while at least one agent of the process runs, once."""
    global _gc_watchers
    with _gc_lock:
        _gc_watchers += 1 if on else -1
        if on and _gc_watchers == 1:
            gc.callbacks.append(_on_gc)
        elif not on and _gc_watchers == 0:
            gc.callbacks.remove(_on_gc)
            _flush_gc()


if knobs.get_bool("NOMAD_TPU_TRACE"):
    active = Tracer(sample_rate=knobs.get_float("NOMAD_TPU_TRACE_SAMPLE"))
