"""Tracing tests: the span primitive's three sinks (counter, profiler
annotation, Dapper span), self time, context propagation end to end
through the spine, raft span attribution, federation-hop survival,
span-store bounds, sampling, and Chrome-trace export.  This file is also
the CI `tracing` leg's payload — it must stay green under
NOMAD_TPU_RACE=1."""
import gc
import io
import json
import threading
import time

import pytest

from nomad_tpu import mock, tracing
from nomad_tpu.telemetry import global_metrics
from nomad_tpu.tracing import TRACE_KEY, Tracer, chrome_trace


def _wait(cond, timeout=20.0, interval=0.05):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if cond():
            return True
        time.sleep(interval)
    return False


@pytest.fixture
def tracer():
    t = Tracer(sample_rate=1.0, seed=42)
    tracing.install(t)
    yield t
    tracing.uninstall()


def _assert_causal(spans):
    """Every non-root span's parent must be another span in the trace."""
    ids = {s.span_id for s in spans}
    roots = [s for s in spans if not s.parent_id]
    assert roots, [s.name for s in spans]
    for s in spans:
        if s.parent_id:
            assert s.parent_id in ids, (s.name, s.parent_id)


# ------------------------------------------------------------- unit layer


def test_sample_rate_zero_is_silent():
    t = Tracer(sample_rate=0.0, seed=3)
    assert all(t.new_context() is None for _ in range(100))
    assert t.traces() == []


def test_sampling_rate_is_honored():
    t = Tracer(sample_rate=0.25, seed=11)
    hits = sum(t.new_context() is not None for _ in range(4000))
    assert 800 < hits < 1200, hits


def test_uninstalled_guard_is_none():
    assert tracing.active is None
    assert tracing.current() is None


def test_span_store_ring_is_bounded():
    t = Tracer(sample_rate=1.0, seed=1, store_limit=64)
    ctx = t.new_context()
    for i in range(500):
        t.emit(ctx, f"s{i}", float(i), float(i) + 1.0, node="n1")
    assert len(t.store_for("n1")) == 64
    # the ring keeps the newest spans
    names = {s.name for s in t.spans(ctx["t"])}
    assert "s499" in names and "s0" not in names


def test_span_store_concurrent_add_and_snapshot(tracer):
    """Hammer one store from writers while snapshotting — the shape the
    race detector (NOMAD_TPU_RACE=1) audits via SpanStore._RACE_TRACED."""
    ctx = tracer.new_context()
    stop = threading.Event()

    def writer():
        while not stop.is_set():
            tracer.emit(ctx, "w", 0.0, 1.0, node="n")

    threads = [threading.Thread(target=writer) for _ in range(4)]
    for th in threads:
        th.start()
    try:
        for _ in range(50):
            tracer.spans(ctx["t"])
            tracer.traces()
    finally:
        stop.set()
        for th in threads:
            th.join()
    assert len(tracer.store_for("n")) <= tracer.store_limit


def test_eval_note_table_is_bounded():
    t = Tracer(sample_rate=1.0, seed=2)
    ctx = t.new_context()
    for i in range(t._NOTE_LIMIT + 100):
        t.note_eval(f"ev-{i}", ctx)
    assert len(t._eval_notes) == t._NOTE_LIMIT
    # oldest evicted first, newest retrievable
    assert t.take_eval_note("ev-0") is None
    assert t.take_eval_note(f"ev-{t._NOTE_LIMIT + 99}") is not None


def test_chrome_trace_export_shape():
    t = Tracer(sample_rate=1.0, seed=5)
    ctx = t.new_context()
    root = t.start(ctx, "root", "n1")
    child = t.start(t.child_ctx(ctx, root), "child", "n2")
    t.finish(child)
    t.finish(root)
    doc = chrome_trace([s.to_dict() for s in t.spans(ctx["t"])])
    assert doc["displayTimeUnit"] == "ms"
    evs = doc["traceEvents"]
    assert all(isinstance(e["pid"], int) for e in evs)
    meta = [e for e in evs
            if e.get("ph") == "M" and e["name"] == "process_name"]
    assert {m["args"]["name"] for m in meta} == {"n1", "n2"}
    xs = [e for e in evs if e["ph"] == "X"]
    assert len(xs) == 2
    assert all("ts" in e and "dur" in e and
               e["args"]["trace_id"] == ctx["t"] for e in xs)
    json.dumps(doc)     # must be JSON-serializable as-is


# ---------------------------------------------------- the span primitive


class _FakeTraceMe:
    """Stands in for jax.profiler.TraceAnnotation with a session on:
    records (thread, name, "B"/"E") in order."""
    log: list = []

    def __init__(self, name):
        self.name = name

    @staticmethod
    def is_enabled():
        return True

    def __enter__(self):
        _FakeTraceMe.log.append((threading.get_ident(), self.name, "B"))

    def __exit__(self, *exc):
        _FakeTraceMe.log.append((threading.get_ident(), self.name, "E"))


@pytest.fixture
def annotations(monkeypatch):
    _FakeTraceMe.log = []
    monkeypatch.setattr(tracing, "_TraceMe", _FakeTraceMe)
    return _FakeTraceMe.log


def _sample(name):
    return {s["Name"]: s
            for s in global_metrics.snapshot()["Samples"]}.get(name)


def _count(name):
    s = _sample(name)
    return s["count"] if s else 0


def test_one_call_feeds_three_sinks(tracer, annotations):
    """Counter, profiler annotation and Dapper span from one `span()`;
    the Dapper span hangs under the bound context and the child context
    is bound only while the span is open."""
    n0 = _count("nomad.t3.work")
    ctx = tracer.new_context()
    prev = tracing.bind(ctx)
    try:
        with tracing.span("t3.work", node="n1", shard=7) as sp:
            inner = tracing.current()
            time.sleep(0.01)
        assert tracing.current() is ctx
    finally:
        tracing.bind(prev)
    assert _count("nomad.t3.work") == n0 + 1
    assert [(n, k) for _t, n, k in annotations] == \
        [("t3.work", "B"), ("t3.work", "E")]
    (got,) = tracer.spans(ctx["t"])
    assert (got.name, got.node, got.parent_id) == ("t3.work", "n1", "")
    assert got.attrs == {"shard": 7}
    assert inner == {"t": ctx["t"], "s": got.span_id, "b": 1}
    assert 0.009 < sp.seconds == got.duration < 0.5


def test_self_time_is_duration_less_direct_children(annotations):
    """`nomad.self.<name>` = the span less what its direct children
    covered (a grandchild counts once, through its parent), only for a
    span that had children, and under a prefix the harness's
    `nomad.worker.invoke_scheduler.` sum cannot pick up."""
    name = "tself.outer"
    with tracing.span(name) as outer:
        time.sleep(0.01)
        with tracing.span("tself.child") as c1:
            with tracing.span("tself.grandchild"):
                time.sleep(0.01)
        with tracing.span("tself.waited", wait=True) as c2:
            time.sleep(0.01)
    self_s = _sample("nomad.self." + name)
    assert self_s["count"] == 1
    want_ms = (outer.seconds - c1.seconds - c2.seconds) * 1e3
    assert abs(self_s["mean"] - want_ms) < 1e-6
    assert 9.0 < self_s["mean"] < outer.seconds * 1e3 - 19.0
    assert _sample("nomad.self.tself.grandchild") is None    # a leaf
    assert _sample("nomad.self.tself.child")["count"] >= 1
    assert _sample("nomad." + name + ".self") is None   # a prefix
    # the profiler's timeline of this thread is flat: the outer span is
    # cut into the pieces of its self time, the wait shows nothing
    assert [(n, k) for _t, n, k in annotations] == [
        (name, "B"), (name, "E"),
        ("tself.child", "B"), ("tself.child", "E"),
        ("tself.grandchild", "B"), ("tself.grandchild", "E"),
        ("tself.child", "B"), ("tself.child", "E"),
        (name, "B"), (name, "E"),
        (name, "B"), (name, "E")]


def test_wait_span_is_counted_and_not_annotated(tracer, annotations):
    n0 = _count("nomad.twait.blocked")
    ctx = tracer.new_context()
    with tracing.span("twait.blocked", wait=True, ctx=ctx):
        time.sleep(0.005)
    assert _count("nomad.twait.blocked") == n0 + 1
    assert annotations == []
    (got,) = tracer.spans(ctx["t"])
    assert got.attrs == {"wait": True}


def test_record_counts_and_never_nests(tracer, annotations):
    """The observe-time form: counted, a Dapper span only under an
    explicit context, no annotation, and not a child of the open span."""
    ctx = tracer.new_context()
    n0 = _count("nomad.trec.queue_wait")
    with tracing.span("trec.outer"):
        t1 = time.perf_counter()
        tracing.record("trec.queue_wait", t1 - 0.25, t1, wait=True)
        tracing.record("trec.queue_wait", t1 - 0.5, t1, wait=True,
                       ctx=ctx, node="n2", depth=3)
    s = _sample("nomad.trec.queue_wait")
    assert s["count"] == n0 + 2 and abs(s["max"] - 500.0) < 1e-6
    assert _sample("nomad.self.trec.outer") is None
    assert [n for _t, n, _k in annotations] == ["trec.outer"] * 2
    (got,) = tracer.spans(ctx["t"])
    assert got.node == "n2" and got.attrs == {"wait": True, "depth": 3}
    assert abs(got.duration - 0.5) < 1e-9
    assert abs(got.start - (time.time() - 0.5)) < 0.2


def test_spans_nest_per_thread():
    """Stacks are per thread: a span open on one thread is not the
    parent of a span on another."""
    name = "tthread.outer"
    done = threading.Event()

    def other():
        with tracing.span("tthread.other"):
            time.sleep(0.005)
        done.set()

    with tracing.span(name):
        th = threading.Thread(target=other)
        th.start()
        assert done.wait(5.0)
        th.join(5.0)
    assert not th.is_alive()
    assert _sample("nomad.self." + name) is None


def _total_ms(name):
    s = _sample(name)
    return s["mean"] * s["count"] if s else 0.0


def test_cpu_span_records_thread_time_beside_wall_time():
    """`cpu=True`: `nomad.cpu.<name>` holds the part of the span its
    thread was running (a sleep is none of it), never more than the
    wall time; a span without the flag writes no such Sample."""
    with tracing.span("tcpu.busy", cpu=True) as busy:
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < 0.02:
            pass
    with tracing.span("tcpu.asleep", cpu=True) as asleep:
        time.sleep(0.02)
    with tracing.span("tcpu.plain"):
        pass
    for sp, least in ((busy, 5.0), (asleep, 0.0)):
        cpu = _sample("nomad.cpu." + sp.name)
        assert cpu["count"] == 1
        assert least <= cpu["mean"] <= sp.seconds * 1e3 + 1.0
    assert _sample("nomad.cpu.tcpu.asleep")["mean"] < 10.0
    assert _sample("nomad.tcpu.plain")["count"] == 1
    assert _sample("nomad.cpu.tcpu.plain") is None


def test_collection_is_a_child_span_of_the_collecting_thread(annotations):
    """While an agent runs, a collection is `gc.collect.gen<n>` on the
    thread that collects: one Sample, out of the open span's self time,
    a piece of its own on the flat timeline; after the agent's shutdown
    `gc.callbacks` is as it was found."""
    from nomad_tpu.agent import Agent, AgentConfig

    found = list(gc.callbacks)
    a = Agent(AgentConfig(http_port=0, num_schedulers=1))
    a.start()
    try:
        assert gc.callbacks.count(tracing._on_gc) == 1
        gc.disable()        # no collection but the one asked for
        try:
            with tracing.span("tgc.before"):
                pass        # a span's end writes the pauses before it
            n0 = _count("nomad.gc.collect.gen2")
            me = threading.get_ident()
            del annotations[:]
            with tracing.span("tgc.outer") as outer:
                gc.collect(2)
            mine = [(n, k) for t, n, k in annotations if t == me]
        finally:
            gc.enable()
        pause = _sample("nomad.gc.collect.gen2")
        assert pause["count"] == n0 + 1
        self_ms = _sample("nomad.self.tgc.outer")
        assert self_ms["count"] == 1
        assert self_ms["mean"] <= outer.seconds * 1e3 - pause["p50"] * 0.5
        assert mine == [("tgc.outer", "B"), ("tgc.outer", "E"),
                        ("gc.collect.gen2", "B"), ("gc.collect.gen2", "E"),
                        ("tgc.outer", "B"), ("tgc.outer", "E")]
    finally:
        a.stop()
    assert tracing._on_gc not in gc.callbacks
    assert [c for c in gc.callbacks if c in found] == found


def test_many_pending_pauses_are_written_by_one_span_end():
    """Pauses wait for the next span or record to end, however many: a
    stretch with collections and no span (a comparison after the window)
    leaves thousands, and one span's end writes them all."""
    t = time.perf_counter()
    n0 = _count("nomad.gc.collect.gen0")
    tracing._gc_done.extend(
        ("gc.collect.gen0", t, t + 1e-4, None) for _ in range(5000))
    with tracing.span("tgc.flush"):
        pass
    assert not tracing._gc_done
    assert _count("nomad.gc.collect.gen0") == n0 + 5000


def test_two_watchers_register_the_callback_once():
    found = list(gc.callbacks)
    tracing.watch_gc(True)
    tracing.watch_gc(True)
    assert gc.callbacks == found + [tracing._on_gc]
    tracing.watch_gc(False)
    assert gc.callbacks == found + [tracing._on_gc]
    tracing.watch_gc(False)
    assert gc.callbacks == found


def _store_with_allocs(n_before):
    """A store with a node, a job and `n_before` of its allocations."""
    from nomad_tpu.scheduler.testing import Harness
    h = Harness()
    node, job = mock.node(), mock.job()
    h.store.upsert_node(h.next_index(), node)
    h.store.upsert_job(h.next_index(), job)
    h.store.upsert_allocs(h.next_index(), [
        mock.alloc_for(job, node.id, index=i) for i in range(n_before)])
    return h, node, job


def test_plan_write_closes_before_the_first_watcher_hears():
    """`store.plan_write` is the write alone: its Sample is counted when
    the first `_notify` of the commit arrives, `store.plan_notify` not
    yet; after the call both are."""
    from nomad_tpu.state.store import AppliedPlanResults
    h, node, job = _store_with_allocs(0)
    seen = []
    h.store.watch(lambda table, obj: seen.append(
        (table, _count("nomad.store.plan_write"),
         _count("nomad.store.plan_notify"))))
    w0 = _count("nomad.store.plan_write")
    n0 = _count("nomad.store.plan_notify")
    h.store.upsert_plan_results(h.next_index(), AppliedPlanResults(
        allocs_to_place=[mock.alloc_for(job, node.id, index=i)
                         for i in range(3)], plan_id="p-write"))
    assert seen == [("allocs", w0 + 1, n0)] * 3
    assert _count("nomad.store.plan_write") == w0 + 1
    assert _count("nomad.store.plan_notify") == n0 + 1
    assert _count("nomad.cpu.store.plan_write") >= 1


def test_bucket_copy_count_is_the_stores_counter():
    """One `store.bucket_copy` for each bucket `store.stats` counts as
    copied: a commit after a snapshot copies the buckets it writes into,
    a second commit in the same generation copies none of them again."""
    from nomad_tpu.state.store import AppliedPlanResults
    h, node, job = _store_with_allocs(4096)
    fresh = iter(range(4096, 8192))

    def commit(tag):
        c0 = _count("nomad.store.bucket_copy")
        s0 = h.store.stats["buckets_copied"]
        h.store.upsert_plan_results(h.next_index(), AppliedPlanResults(
            allocs_to_place=[mock.alloc_for(job, node.id, index=next(fresh))
                             for _ in range(8)], plan_id=tag))
        return (_count("nomad.store.bucket_copy") - c0,
                h.store.stats["buckets_copied"] - s0)

    h.store.snapshot()
    spans, counted = commit("p-copy-1")
    assert spans == counted >= 1
    spans, counted = commit("p-copy-2")
    assert spans == counted
    h.store.snapshot()
    spans, counted = commit("p-copy-3")
    assert spans == counted >= 1


def test_system_eval_pieces_sum_under_system_place():
    """One system eval over a small full world: one `sched.system_settle`
    a task group, one interval an eval for the loop's two pieces, and
    settle + build + evict copies + the search inside `system_place`."""
    import copy

    from test_preempt_cell import _world
    h, *_ = _world(7, 48)
    job = mock.system_job(priority=50)
    second = copy.deepcopy(job.task_groups[0])
    second.name = "web2"
    job.task_groups.append(second)
    for tg in job.task_groups:
        tg.tasks[0].resources.cpu = 1500
        tg.tasks[0].resources.memory_mb = 1500
    h.store.upsert_job(h.next_index(), job)
    ev = mock.eval(job_id=job.id, type="system", priority=50,
                   triggered_by="job-register")
    h.store.upsert_evals(h.next_index(), [ev])
    names = ("sched.system_place", "sched.system_settle",
             "sched.system_build_alloc", "sched.system_evict_copy",
             "sched.preempt_find")
    c0 = {n: _count("nomad." + n) for n in names}
    t0 = {n: _total_ms("nomad." + n) for n in names}
    h.process("system", ev)
    moved = {n: _count("nomad." + n) - c0[n] for n in names}
    ms = {n: _total_ms("nomad." + n) - t0[n] for n in names}
    assert 1 <= moved.pop("sched.preempt_find") <= 2    # one a group that asks
    assert moved == {"sched.system_place": 1, "sched.system_settle": 2,
                     "sched.system_build_alloc": 1,
                     "sched.system_evict_copy": 1}
    assert h.plans and h.plans[0].node_preemptions
    assert ms["sched.system_evict_copy"] > 0.0
    assert ms["sched.system_build_alloc"] > 0.0
    assert sum(ms[n] for n in names[1:]) <= ms["sched.system_place"]
    assert _count("nomad.cpu.sched.system_place") >= 1


def test_profiler_session_shows_flat_work_spans(tmp_path):
    """A real `jax.profiler` session on the CPU, read back with
    ProfileData: the program's names are in /host:CPU, a wait span is
    not, and no two of the program's annotations of one thread overlap."""
    import glob

    import jax
    from jax.profiler import ProfileData

    def work(tag):
        for _ in range(3):
            with tracing.span(f"tprof.outer.{tag}"):
                time.sleep(0.002)
                with tracing.span("tprof.child"):
                    time.sleep(0.002)
                with tracing.span("tprof.blocked", wait=True):
                    time.sleep(0.002)
                time.sleep(0.002)

    jax.profiler.start_trace(str(tmp_path))
    try:
        threads = [threading.Thread(target=work, args=(t,))
                   for t in ("a", "b")]
        for th in threads:
            th.start()
        for th in threads:
            th.join(20.0)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                            / "*.xplane.pb"))
    names, lines = set(), 0
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:CPU"):
            continue
        for line in plane.lines:
            mine = sorted((e.start_ns, e.start_ns + e.duration_ns, e.name)
                          for e in line.events
                          if e.name.startswith("tprof."))
            if not mine:
                continue
            lines += 1
            names |= {n for _s, _e, n in mine}
            for (_s0, e0, n0), (s1, _e1, n1) in zip(mine, mine[1:]):
                assert e0 <= s1, (n0, n1, e0, s1)
    assert lines == 2
    assert names == {"tprof.outer.a", "tprof.outer.b", "tprof.child"}


def test_profiler_session_shows_a_collection_by_name(tmp_path):
    """A real `jax.profiler` session: a full collection inside an open
    span is `gc.collect.gen2` in the thread's /host:CPU line, between
    two pieces of the span it fell in and overlapping neither."""
    import glob

    import jax
    from jax.profiler import ProfileData

    with tracing.span("tprofgc.warm"):
        pass            # resolves the annotation class: a callback never imports
    tracing.watch_gc(True)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with tracing.span("tprofgc.outer"):
            time.sleep(0.002)
            gc.collect(2)
            time.sleep(0.002)
    finally:
        jax.profiler.stop_trace()
        tracing.watch_gc(False)
    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                            / "*.xplane.pb"))
    found = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:CPU"):
            continue
        for line in plane.lines:
            mine = sorted((e.start_ns, e.start_ns + e.duration_ns, e.name)
                          for e in line.events
                          if e.name in ("tprofgc.outer", "gc.collect.gen2"))
            if any(n == "tprofgc.outer" for _s, _e, n in mine):
                found.append(mine)
    (mine,) = found
    names = [n for _s, _e, n in mine]
    assert names[0] == names[-1] == "tprofgc.outer"
    assert "gc.collect.gen2" in names
    for (_s0, e0, n0), (s1, _e1, n1) in zip(mine, mine[1:]):
        assert e0 <= s1, (n0, n1, e0, s1)


# ------------------------------------------------- one job on a dev agent

# every span of README's table that one registered job on a dev agent
# opens (raft.append / raft.commit need a raft node: asserted in
# test_cluster_plan_submit_trace_has_raft_spans; engine.warmup is set-up)
SPINE_SPANS = (
    "http.put.jobs", "http.park", "rpc.Job.Register", "broker.wait",
    "worker.invoke_scheduler.service", "worker.invoke_scheduler.batch",
    "sched.reconcile", "sched.feasible", "sched.materialise",
    "sched.wait_engine", "engine.queue_wait", "engine.idle",
    "engine.stack", "engine.put", "engine.device_get", "engine.resolve",
    "engine.dispatch", "plan.submit", "plan.queue_wait", "plan.evaluate",
    "plan.commit", "worker.settle_wait", "raft.fsm_apply",
    "native.validate_plan",
    "native.scatter_add_rank1", "native.expand_pairs",
    "native.format_uuids")


# the spans with `cpu=True`: each also writes `nomad.cpu.<name>`
CPU_SPANS = (
    "sched.reconcile", "sched.feasible", "sched.materialise",
    "sched.system_diff", "sched.system_place", "plan.evaluate",
    "plan.flatten", "store.plan_write", "engine.stack", "engine.put",
    "engine.resolve")

# what the fixture's second stage adds (a system job that evicts, the
# read that confirms it, a collection of each generation) and the
# commit's phases, which every plan opens
LATER_SPANS = (
    "plan.flatten", "store.plan_write", "store.plan_notify",
    "store.bucket_copy", "rpc.Job.Allocations",
    "worker.invoke_scheduler.system", "sched.system_diff",
    "sched.system_place", "sched.system_settle",
    "sched.system_build_alloc", "sched.system_evict_copy",
    "sched.preempt_find", "gc.collect.gen0", "gc.collect.gen1",
    "gc.collect.gen2") + tuple("cpu." + n for n in CPU_SPANS)


@pytest.mark.parametrize("name", SPINE_SPANS + LATER_SPANS)
def test_span_is_in_v1_metrics_after_one_job(spine_metrics, name):
    moved = spine_metrics["later"].get("nomad." + name, {"count": 0})[
        "count"] - spine_metrics["before"].get("nomad." + name, 0)
    assert moved >= 1, sorted(spine_metrics["later"])


def test_system_job_of_the_fixture_evicted(spine_metrics):
    """The second stage is what it says: every node got the system
    job's allocation, and some took their room by eviction."""
    allocs = spine_metrics["fleet_allocs"]
    assert len({a["NodeID"] for a in allocs}) == 3
    assert spine_metrics["evicted"] >= 1


def test_invoke_scheduler_count_is_evals_processed(spine_metrics):
    """The accepted `invoke_scheduler_ms` sums every Sample under
    `nomad.worker.invoke_scheduler.`: one per eval, nothing else."""
    under = {n: s["count"] - spine_metrics["before"].get(n, 0)
             for n, s in spine_metrics["samples"].items()
             if n.startswith("nomad.worker.invoke_scheduler.")}
    assert sum(under.values()) == spine_metrics["processed"] == 2, under
    assert {n for n, moved in under.items() if moved} == {
        "nomad.worker.invoke_scheduler.service",
        "nomad.worker.invoke_scheduler.batch"}
    # their self time is counted too, under a prefix that sum never sees
    for kind in ("service", "batch"):
        n = f"nomad.self.worker.invoke_scheduler.{kind}"
        assert spine_metrics["samples"][n]["count"] \
            - spine_metrics["before"].get(n, 0) == 1


@pytest.mark.parametrize("name", ["broker.wait", "plan.queue_wait",
                                  "engine.queue_wait",
                                  "worker.settle_wait"])
def test_queue_waits_are_counted_with_no_tracer(spine_metrics, name):
    """One per eval / plan / engine request / deferred eval (each of the
    two evals submits one plan, so each is deferred once), sampled or
    not."""
    moved = spine_metrics["samples"]["nomad." + name]["count"] \
        - spine_metrics["before"].get("nomad." + name, 0)
    assert moved == 2


# ------------------------------------------------------ dev agent (HTTP)


def test_dev_agent_http_chain_and_api(tracer):
    """HTTP ingress starts the root span; the context rides the RPC args
    through scheduler invoke, plan submit, and the dev-mode apply; the
    trace is served back over /v1/traces and exports via ?format=chrome.
    Flipping the sample rate to 0 silences new requests entirely."""
    from nomad_tpu.agent import Agent, AgentConfig
    from nomad_tpu.api import ApiClient

    a = Agent(AgentConfig(http_port=0, num_schedulers=2,
                          heartbeat_ttl=60.0))
    a.start()
    try:
        for _ in range(3):
            a.server.register_node(mock.node())
        api = ApiClient(a.http_addr)
        j = mock.job()
        api.jobs.register(j)
        a.server.wait_for_idle(10.0)

        reg = _wait_trace(tracer, "http.put.jobs",
                          {"plan.submit", "raft.fsm_apply"})
        spans = tracer.spans(reg["trace_id"])
        names = {s.name for s in spans}
        for want in ("http.put.jobs", "rpc.Job.Register",
                     "broker.wait", "plan.submit", "plan.queue_wait",
                     "plan.evaluate", "raft.fsm_apply"):
            assert want in names, (want, sorted(names))
        assert any(n.startswith("worker.invoke_scheduler.")
                   for n in names), sorted(names)
        assert len(spans) >= 6
        _assert_causal(spans)

        # the trace API serves what the store holds
        listed = api.operator.traces()
        assert any(t["trace_id"] == reg["trace_id"] for t in listed)
        got = api.operator.trace(reg["trace_id"])
        assert len(got["spans"]) == len(spans)
        doc = api.operator.trace_chrome(reg["trace_id"])
        assert len([e for e in doc["traceEvents"]
                    if e["ph"] == "X"]) == len(spans)

        # CLI: list, show, export
        from nomad_tpu.command.cli import main as cli_main
        out = io.StringIO()
        assert cli_main(["-address", a.http_addr, "operator", "trace"],
                        out=out) == 0
        assert reg["trace_id"] in out.getvalue()
        out = io.StringIO()
        assert cli_main(["-address", a.http_addr, "operator", "trace",
                         reg["trace_id"]], out=out) == 0
        assert "plan.submit" in out.getvalue()

        # sampling off: new requests produce no new traces
        tracer.sample_rate = 0.0
        before = len(tracer.traces())
        api.nodes.list()
        api.jobs.register(mock.job())
        a.server.wait_for_idle(10.0)
        time.sleep(0.2)
        assert len(tracer.traces()) == before
    finally:
        a.stop()


def _wait_trace(tracer, root_name, want_names, timeout=15.0):
    """Wait until a trace rooted at `root_name` contains `want_names`
    (spans land asynchronously as observe-time emission catches up)."""
    deadline = time.time() + timeout
    last = None
    while time.time() < deadline:
        for t in tracer.traces():
            if t["root"] == root_name:
                last = t
                names = {s.name for s in tracer.spans(t["trace_id"])}
                if want_names <= names:
                    return t
        time.sleep(0.1)
    raise AssertionError(
        f"no trace rooted at {root_name!r} grew spans {want_names}; "
        f"last={last}")


# --------------------------------------------------- 3-server raft spine


def test_cluster_plan_submit_trace_has_raft_spans(tracer):
    """The acceptance trace: one sampled register on a real 3-server
    raft spine shows the causally-linked chain rpc -> broker wait ->
    scheduler invoke -> plan submit/evaluate -> raft append (WAL+fsync
    window) -> commit -> fsm apply."""
    from nomad_tpu.core.cluster import Cluster

    c = Cluster(n=3)
    c.start()
    try:
        leader = c.leader(10.0)
        for _ in range(3):
            leader.register_node(mock.node())
        ctx = tracer.new_context()
        j = mock.job()
        j.task_groups[0].count = 2
        leader.endpoints.handle("Job.Register",
                                {"job": j, TRACE_KEY: ctx})
        assert _wait(lambda: len(
            leader.store.allocs_by_job("default", j.id)) == 2, 30)
        assert _wait(lambda: {"raft.fsm_apply", "plan.submit"} <=
                     {s.name for s in tracer.spans(ctx["t"])}, 10)

        spans = tracer.spans(ctx["t"])
        names = {s.name for s in spans}
        for want in ("rpc.Job.Register", "broker.wait", "plan.submit",
                     "plan.queue_wait", "plan.evaluate", "raft.append",
                     "raft.commit", "raft.fsm_apply"):
            assert want in names, (want, sorted(names))
        assert len(spans) >= 6
        _assert_causal(spans)
        # all spans share the one trace id; raft spans carry the index
        assert {s.trace_id for s in spans} == {ctx["t"]}
        assert any(s.name == "raft.append" and
                   s.attrs and "index" in s.attrs for s in spans)
        # the counters move for every entry, sampled or not
        for name in ("raft.append", "raft.commit", "raft.fsm_apply"):
            assert _count("nomad." + name) >= 1, name
    finally:
        c.stop()


# ------------------------------------------------------------ federation


def test_federation_hop_preserves_trace_id(tracer):
    """A forwarded RPC keeps its trace context across the WAN hop: the
    remote region's rpc span lands under the SAME trace_id, attributed
    to the remote server."""
    from nomad_tpu.core.cluster import FederatedCluster
    from nomad_tpu.core.server import ServerConfig
    from nomad_tpu.raft import RaftConfig

    fc = FederatedCluster(
        regions=("global", "west"), n=1,
        config=ServerConfig(num_schedulers=2, heartbeat_ttl=60.0),
        raft_config=RaftConfig(heartbeat_interval=0.02,
                               election_timeout=0.1))
    fc.start()
    fc.wait_federated(20.0)
    try:
        g = fc.leader("global", 10.0)
        w = fc.leader("west", 10.0)
        w.register_node(mock.node())
        ctx = tracer.new_context()
        j = mock.job()
        j.region = "west"
        j.task_groups[0].count = 1
        g.endpoints.handle("Job.Register", {"job": j, TRACE_KEY: ctx})
        assert _wait(lambda: any(
            s.name == "rpc.Job.Register"
            for s in tracer.spans(ctx["t"])), 10)
        assert _wait(lambda: w.name in {
            s.node for s in tracer.spans(ctx["t"])
            if s.name == "rpc.Job.Register"}, 10)
        spans = tracer.spans(ctx["t"])
        rpc_spans = [s for s in spans if s.name == "rpc.Job.Register"]
        # the ingress dispatch on global AND the forwarded handling on
        # west both land under the SAME trace id
        assert {s.node for s in rpc_spans} == {g.name, w.name}
        assert {s.trace_id for s in spans} == {ctx["t"]}
        # the register landed where it was routed
        assert _wait(lambda: w.store.job_by_id("default", j.id) is not None, 10)
        assert g.store.job_by_id("default", j.id) is None
    finally:
        fc.stop()


def test_spans_carry_no_token_material(tracer, monkeypatch):
    """Multi-tenant guarantee: ACL secrets never land in span names,
    nodes, or attrs — whether the token arrives via the X-Nomad-Token
    header or the ?token= query fallback.  A leaked secret in the trace
    plane would hand every operator with read access to /v1/traces a
    management credential."""
    import json as _json

    from nomad_tpu.agent import Agent, AgentConfig
    from nomad_tpu.api import ApiClient

    monkeypatch.setenv("NOMAD_TPU_ACL", "1")
    a = Agent(AgentConfig(http_port=0, num_schedulers=2,
                          heartbeat_ttl=60.0))
    a.start()
    try:
        a.server.register_node(mock.node())
        boot = a.server.bootstrap_acl()
        secret = boot.secret_id
        api = ApiClient(a.http_addr, token=secret)
        j = mock.job()
        j.task_groups[0].count = 1
        api.jobs.register(j)
        a.server.wait_for_idle(10.0)
        # query-param token path (the header-less fallback)
        bare = ApiClient(a.http_addr)
        bare.get(f"/v1/jobs?token={secret}")
        bare.put(f"/v1/namespaces?token={secret}",
                 {"Name": "traced-ns"})
        assert _wait(lambda: len(tracer.spans()) > 5)

        blob = _json.dumps([s.to_dict() for s in tracer.spans()])
        assert secret not in blob
        # accessor ids are not secrets, but the secret must not appear
        # in any recorded eval notes either
        assert all(secret not in str(v)
                   for v in tracer._eval_notes.values())
    finally:
        a.stop()
