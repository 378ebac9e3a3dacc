"""Benchmark runner (BASELINE.json scenarios).

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...},
which names the device it ran on ("platform", "device_kind",
"device_count" as jax reports them).  A scenario that raises, a headline
that does not finish, or a failed gate exits non-zero.
Headline: the north-star C2M-1M shape at its ACTUAL size — 10K nodes /
1M allocations (10,000 jobs x 10 task groups x count 10) through the
FULL server spine: job register -> eval broker -> 48 concurrent
scheduler workers -> batched device dispatch (PlacementEngine) -> plan
queue -> batched pipelined applier -> state store.  vs_baseline compares
against the north-star C2M rate (1M allocs / 30 s = 33,333 allocs/s on a
v5e-8; this runs on whatever `jax.devices()` reports, and says so).

`--smoke` runs the same shape shrunk to seconds (small world) for CI —
tests/test_commit_pipeline.py invokes it so commit-path throughput
regressions fail tier-1 instead of only showing up in BENCH_r*.json.

Supplementary numbers (other BASELINE.json scenarios, kernel-only rate at
C2M node scale) go to stderr so the driver still sees a single JSON line
on stdout.
"""
import json
import os
import sys
import tempfile
import threading
import time
import traceback


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def device_info() -> dict:
    """The device this process runs on, as jax reports it.  Merged into
    every JSON line so a number can never be read without its device."""
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform,
            "device_kind": devs[0].device_kind,
            "device_count": len(devs)}


def _on_devices() -> str:
    d = device_info()
    return f"on {d['device_count']} x {d['device_kind']} ({d['platform']})"


# per-scenario plan.submit/plan.evaluate latency summaries, folded into
# the stdout BENCH JSON so the latency trajectory (ROADMAP item 3) is
# regression-gatable, not just logged
_PLAN_STATS: dict = {}

# per-scenario steady-state purity report (transfer guard + recompile
# budget + world re-upload watch), folded into the BENCH JSON; any
# violation fails the --smoke leg.  NOMAD_TPU_BENCH_GUARD=0 opts out.
_STEADY_STATE: dict = {}

# per-scenario engine-stats snapshot taken before the server stops; the
# --smoke fused-path gate reads it after the run (fused dispatch means
# one device dispatch per wave group: bulk_parts == bulk_groups)
_ENGINE_SNAP: dict = {}


class _SteadyGate:
    """Arms the steady-state dispatch discipline around a measured
    window, AFTER warmup: jax's transfer guard flips to "disallow" (any
    implicit host<->device transfer raises inside the dispatch loop),
    the recompile budget snapshots every registered kernel's jit cache
    (post-warmup growth is a shape-bucketing regression), and
    DeviceWorld stats are diffed (a full [N, R] re-upload after the
    epoch's first means the scatter path leaked).  Results land in
    `_STEADY_STATE[scenario]`."""

    def __init__(self, scenario: str):
        self.scenario = scenario
        self.enabled = \
            os.environ.get("NOMAD_TPU_BENCH_GUARD", "1") != "0"
        self._guard = None
        self._eng = None

    def __enter__(self):
        if not self.enabled:
            return self
        from nomad_tpu.analysis import recompile, transfer_purity
        from nomad_tpu.parallel.engine import get_engine
        self._eng = get_engine()
        self.budget = recompile.Budget()
        self._world0 = self._eng.world_stats()
        self._eng0 = dict(self._eng.stats)
        self._guard = transfer_purity.steady_state_guard()
        self._guard.__enter__()
        return self

    def __exit__(self, exc_type, exc, tb):
        if self._guard is not None:
            self._guard.__exit__(exc_type, exc, tb)
        if not self.enabled or exc_type is not None:
            return False
        from nomad_tpu.telemetry import global_metrics
        rep = self.budget.report()
        wstats = self._eng.world_stats()
        reuploads = wstats.get("steady_reuploads", 0) - \
            self._world0.get("steady_reuploads", 0)
        violations = self.budget.violations()
        if reuploads > 0:
            violations.append(
                f"{reuploads} full world re-upload(s) during the "
                f"measured window (steady state must scatter rows only)")
        estats = dict(self._eng.stats)
        donated = estats.get("donated_carries", 0) - \
            self._eng0.get("donated_carries", 0)
        bulk_parts = estats.get("bulk_parts", 0) - \
            self._eng0.get("bulk_parts", 0)
        adopts = wstats.get("basis_adopts", 0) - \
            self._world0.get("basis_adopts", 0)
        if bulk_parts > 0 and (donated <= 0 or adopts <= 0):
            violations.append(
                f"{bulk_parts} bulk dispatch(es) "
                f"produced donated_carries={donated} basis_adopts={adopts} "
                f"(steady state must keep the usage basis resident via "
                f"donated carries, not re-download + re-upload it)")
        self.budget.publish(global_metrics)
        _STEADY_STATE[self.scenario] = {
            "transfer_guard": "disallow",
            "recompiled": rep["recompiled"],
            "compile_events": rep["compile_events"],
            "steady_reuploads": reuploads,
            "donated_carries": donated,
            "basis_adopts": adopts,
            "world": wstats,
            "violations": violations,
        }
        log(f"{self.scenario} steady-state: "
            f"compiles={rep['compile_events']} reuploads={reuploads} "
            f"violations={violations or 'none'}")
        return False


def _log_plan_submit(scenario: str) -> dict:
    """Per-scenario p50/p99 plan-submit latency (the BASELINE.json metric
    is evals/sec + p99 plan-submit; reference metric nomad.nomad.plan.submit).
    Resets the series so scenarios don't pollute each other."""
    from nomad_tpu.telemetry import global_metrics
    s = global_metrics.take_sample("nomad.plan.submit")
    ev = global_metrics.take_sample("nomad.plan.evaluate")

    def _ms(m):
        return {"p50": round(m["p50"], 2), "p99": round(m["p99"], 2),
                "mean": round(m["mean"], 2), "max": round(m["max"], 2),
                "count": m["count"]}
    _PLAN_STATS[scenario] = {"submit_ms": _ms(s), "evaluate_ms": _ms(ev)}
    log(f"{scenario}: plan.submit p50 {s['p50']:.1f} / p99 {s['p99']:.1f} ms "
        f"(mean {s['mean']:.1f} ms, n={s['count']}); "
        f"plan.evaluate p50 {ev['p50']:.1f} / p99 {ev['p99']:.1f} ms")
    return _PLAN_STATS[scenario]


def _wait_allocs(store, jobs, want, timeout=300.0):
    t0 = time.time()
    while time.time() - t0 < timeout:
        placed = sum(len(store.allocs_by_job("default", j.id)) for j in jobs)
        if placed >= want:
            return placed
        time.sleep(0.01)
    return sum(len(store.allocs_by_job("default", j.id)) for j in jobs)


def _require_complete(scenario: str, placed: int, want: int) -> None:
    """A scenario that did not place exactly what it asked for has no
    rate to report: raise, so the run exits non-zero."""
    if placed < want:
        raise RuntimeError(
            f"{scenario} INCOMPLETE: {placed}/{want} allocs before the "
            f"deadline")
    if placed > want:
        raise RuntimeError(
            f"{scenario} OVER-PLACED: {placed} allocs for {want} asked")


def bench_e2e_spine(n_nodes=1000, n_jobs=50, count=100, workers=48):
    """configs[1]: 1K nodes / 5K batch allocs, binpack, through the spine."""
    from nomad_tpu import mock
    from nomad_tpu.core.server import Server, ServerConfig

    s = Server(ServerConfig(num_schedulers=workers, heartbeat_ttl=3600.0,
                            gc_interval=3600.0))
    s.start()
    t0 = time.time()
    for _ in range(n_nodes):
        s.register_node(mock.node())
    log(f"world build ({n_nodes} nodes): {time.time()-t0:.2f}s")

    # deterministic kernel warm: compile EVERY E-bucket variant of both
    # dispatch kernels for the run's shapes (organic warming depends on
    # queue timing and can leave a bucket to compile mid-measurement);
    # warmup discards results, so the measured world stays empty
    t0 = time.time()
    wj = mock.batch_job()
    wj.task_groups[0].count = count
    _warm_engine(s, scan_job=wj, bulk_job=wj)
    log(f"warm: {time.time()-t0:.2f}s")

    jobs = []
    t0 = time.time()
    for _ in range(n_jobs):
        j = mock.batch_job()
        j.task_groups[0].count = count
        jobs.append(j)
        s.register_job(j)
    placed = _wait_allocs(s.store, jobs, n_jobs * count)
    dt = time.time() - t0

    from nomad_tpu.parallel.engine import get_engine
    log(f"engine stats: {get_engine().stats}")
    s.stop()
    log(f"e2e spine: placed {placed} allocs in {dt:.2f}s "
        f"({placed/dt:.0f} allocs/s, {n_jobs/dt:.1f} evals/s, "
        f"{workers} workers)")
    _log_plan_submit("e2e_spine")
    _require_complete("e2e_spine", placed, n_jobs * count)
    return placed / dt


def _batch_job(count, cpu=100, mem=64):
    from nomad_tpu import mock
    j = mock.batch_job()
    tg = j.task_groups[0]
    tg.count = count
    tg.tasks[0].resources.cpu = cpu
    tg.tasks[0].resources.memory_mb = mem
    tg.ephemeral_disk.size_mb = 0
    return j


def _c2m_job(groups=10, count=10):
    """The C2M-1M job shape: `groups` task groups x `count`, allocs sized
    so a 10K-node cluster holds 1M of them (30 cpu / 60 mb each)."""
    from nomad_tpu import mock
    j = mock.batch_job()
    base = j.task_groups[0]
    base.count = count
    base.tasks[0].resources.cpu = 30
    base.tasks[0].resources.memory_mb = 60
    base.ephemeral_disk.size_mb = 0
    tgs = []
    for k in range(groups):
        tg = base.copy() if k else base
        tg.name = f"g{k}"
        tgs.append(tg)
    j.task_groups = tgs
    return j


def _service_job(count, cpu=100, mem=64, spread=True, priority=None):
    from nomad_tpu import mock
    from nomad_tpu.structs.job import Affinity, Spread
    j = mock.job()
    tg = j.task_groups[0]
    tg.count = count
    tg.tasks[0].resources.cpu = cpu
    tg.tasks[0].resources.memory_mb = mem
    tg.ephemeral_disk.size_mb = 0
    if spread:
        tg.spreads = [Spread("${attr.rack}", 50, ())]
        tg.affinities = [Affinity("${node.datacenter}", "dc1", "=", 50)]
    if priority is not None:
        j.priority = priority
    return j


def _server(workers=8):
    from nomad_tpu.core.server import Server, ServerConfig
    s = Server(ServerConfig(num_schedulers=workers, heartbeat_ttl=3600.0,
                            gc_interval=3600.0))
    s.start()
    return s


def _fill_nodes(s, n, racks=50, node_fn=None):
    from nomad_tpu import mock
    for i in range(n):
        node = mock.node()
        node.attributes["rack"] = f"r{i % racks}"
        if node_fn:
            node_fn(node, i)
        s.store.upsert_node(s.next_index(), node)


def _warm_engine(s, scan_job=None, bulk_job=None):
    """Precompile every E-bucket kernel variant for THIS server's matrix
    shapes (engine.warmup) so XLA compiles never land inside a measured
    window — compiles are shape-keyed, so each world size needs its own
    warm."""
    import numpy as np

    from nomad_tpu.parallel.engine import get_engine
    from nomad_tpu.scheduler.stack import DenseStack
    eng = get_engine()
    cm = s.store.matrix
    inputs = None
    bulk = None
    if scan_job is not None:
        st = DenseStack(cm)
        groups = [st.compile_group(scan_job, tg)
                  for tg in scan_job.task_groups]
        count = max(scan_job.task_groups[0].count, 1)
        inputs = st.build_inputs(scan_job, groups, [0] * count, {})
    if bulk_job is not None:
        st = DenseStack(cm)
        g = st.compile_group(bulk_job, bulk_job.task_groups[0])
        N = cm.n_rows
        bulk = dict(
            feasible=g.feasible, affinity=g.affinity.astype(np.float32),
            has_affinity=bool(g.has_affinity),
            desired=max(bulk_job.task_groups[0].count, 1),
            penalty=np.zeros(N, bool), coll0=np.zeros(N, np.int32),
            demand=g.demand.astype(np.float32),
            count=bulk_job.task_groups[0].count)
    eng.warmup(cm, inputs=inputs, bulk=bulk)


def bench_dev_agent_sim():
    """configs[0]: 1 service job, 3 task groups, 5-node dev-agent sim —
    end-to-end registration->placement latency."""
    from nomad_tpu import mock
    s = _server(workers=2)
    try:
        _fill_nodes(s, 5)
        lat = []
        for trial in range(6):
            j = mock.job()
            tgs = []
            for k in range(3):
                tg = j.task_groups[0].copy() if k else j.task_groups[0]
                tg.name = f"g{k}"
                tg.count = 2
                tgs.append(tg)
            j.task_groups = tgs
            t0 = time.time()
            s.register_job(j)
            placed = _wait_allocs(s.store, [j], 6, timeout=30)
            lat.append(time.time() - t0)
            assert placed == 6, placed
        lat.sort()
        log(f"dev-agent sim: p50 register->placed latency "
            f"{lat[len(lat)//2]*1000:.0f} ms (6 allocs, 3 tgs, 5 nodes)")
        _log_plan_submit("dev_agent")
        return lat[len(lat)//2]
    finally:
        s.stop()


def bench_c2m(n_nodes=10000, n_batch=96, batch_count=1000,
              n_service=40, service_count=100, workers=48):
    """configs[2]: C2M — 10K nodes / 100K allocs, mixed service+batch,
    spread + node-affinity scoring, through the full spine."""
    s = _server(workers=workers)
    try:
        t0 = time.time()
        _fill_nodes(s, n_nodes)
        log(f"C2M world build ({n_nodes} nodes): {time.time()-t0:.1f}s")
        _warm_engine(s, scan_job=_service_job(service_count),
                     bulk_job=_batch_job(batch_count))
        w1, w2 = _batch_job(100), _service_job(50)
        s.register_job(w1)
        s.register_job(w2)
        _wait_allocs(s.store, [w1, w2], 150, timeout=300)
        log(f"C2M warm done: {time.time()-t0:.1f}s")

        jobs = [_batch_job(batch_count) for _ in range(n_batch)] + \
               [_service_job(service_count) for _ in range(n_service)]
        want = n_batch * batch_count + n_service * service_count
        t0 = time.time()
        for j in jobs:
            s.register_job(j)
        placed = _wait_allocs(s.store, jobs, want, timeout=600)
        dt = time.time() - t0
        log(f"C2M spine: {placed}/{want} allocs in {dt:.1f}s "
            f"({placed/dt:.0f} allocs/s)")
        _log_plan_submit("c2m")
        _require_complete("c2m", placed, want)
        return placed / dt
    finally:
        s.stop()


def bench_c2m_1m(n_nodes=10000, n_jobs=10000, groups_per_job=10,
                 group_count=10, workers=48, deadline_s=3600.0,
                 scenario="c2m_1m"):
    """The north-star C2M at its ACTUAL size (BASELINE.json configs[2] /
    north_star): 1M allocations over 100K task groups on 10K nodes,
    through the full spine.  10,000 jobs x 10 task groups x count 10;
    allocs sized so the cluster holds them (30 cpu / 60 mb each)."""
    s = _server(workers=workers)
    try:
        t0 = time.time()
        _fill_nodes(s, n_nodes)
        log(f"{scenario} world build ({n_nodes} nodes): "
            f"{time.time()-t0:.1f}s")

        def make_job():
            return _c2m_job(groups_per_job, group_count)

        t0 = time.time()
        _warm_engine(s, scan_job=make_job(), bulk_job=make_job())
        wj = make_job()
        s.register_job(wj)
        _wait_allocs(s.store, [wj], groups_per_job * group_count,
                     timeout=300)
        log(f"{scenario} warm: {time.time()-t0:.1f}s")

        want = n_jobs * groups_per_job * group_count
        base_allocs = len(s.store._allocs)
        t0 = time.time()
        # measured window runs under the steady-state purity gate: the
        # warm epoch's world is resident, so from here on the dispatch
        # loop must scatter rows, never re-ship or recompile
        with _SteadyGate(scenario):
            for _ in range(n_jobs):
                s.register_job(make_job())
            reg_dt = time.time() - t0
            log(f"{scenario} registered {n_jobs} jobs in {reg_dt:.1f}s")
            deadline = time.time() + deadline_s
            placed = 0
            while time.time() < deadline:
                placed = len(s.store._allocs) - base_allocs
                if placed >= want:
                    break
                failed = sum(w.stats["failed"] for w in s.workers)
                if failed:
                    # a kernel the device refuses fails the eval; its
                    # allocs never appear, so waiting only burns the
                    # deadline (the reason is in the worker's log)
                    raise RuntimeError(
                        f"{scenario}: {failed} eval(s) FAILED after "
                        f"{placed}/{want} allocs")
                time.sleep(0.2 if deadline_s < 600 else 1.0)
        dt = time.time() - t0
        log(f"{scenario} spine: {placed}/{want} allocs in {dt:.1f}s "
            f"({placed/dt:.0f} allocs/s {_on_devices()}; "
            f"{n_jobs * groups_per_job} task groups)")
        if s.applier.stats.get("coalesced"):
            log(f"{scenario} applier stats: {s.applier.stats}")
        from nomad_tpu.parallel.engine import get_engine
        eng = get_engine()
        log(f"{scenario} engine stats: {eng.stats}")
        _ENGINE_SNAP[scenario] = dict(eng.stats)
        _log_plan_submit(scenario)
        return placed / dt, placed, want
    finally:
        s.stop()


def bench_smoke(workers=8):
    """The C2M-1M shape shrunk to CI scale: a small world that finishes
    in seconds, exercising the identical commit pipeline (bulk kernel ->
    native materialization -> plan queue -> batched applier -> store).
    Returns allocs/s; tests assert a generous floor so only real
    commit-path regressions trip it."""
    return bench_c2m_1m(n_nodes=128, n_jobs=30, groups_per_job=5,
                        group_count=4, workers=workers, deadline_s=240.0,
                        scenario="smoke")


# ns per tracing.span() with the profiler off and no tracer installed:
# 1,930-2,400 measured in this sandbox (CPU, 10^6 and 3x10^5 loops,
# PR 25); the bound is three times the median so that a CI host loaded
# by six test workers does not trip it
_SPAN_NS_BOUND = 6000.0


def _smoke_trace_checks() -> dict:
    """Tracing leg of --smoke: (1) with the profiler off and no tracer
    installed, one `tracing.span()` (the primitive every layer boundary
    opens: two clock reads, one flag test, one counter update) must stay
    under `_SPAN_NS_BOUND` — "nil" against a multi-ms plan submit;
    (2) a fully sampled run through the real spine must produce causally
    linked spans that export as well-formed Chrome-trace JSON (the file
    Perfetto loads)."""
    from nomad_tpu import mock, tracing

    out = {"disabled_overhead_ns_per_op": None, "spans": 0,
           "perfetto_file": "", "perfetto_events": 0, "violations": []}
    if tracing.active is not None:
        tracing.uninstall()
    n = 200_000
    t0 = time.perf_counter()
    for _ in range(n):
        with tracing.span("bench.smoke_probe"):
            pass
    per_ns = (time.perf_counter() - t0) / n * 1e9
    out["disabled_overhead_ns_per_op"] = round(per_ns, 1)
    if per_ns > _SPAN_NS_BOUND:
        out["violations"].append(
            f"tracing.span() with everything off costs {per_ns:.0f} ns/op "
            f"(> {_SPAN_NS_BOUND:.0f} ns)")

    tracing.install(tracing.Tracer(sample_rate=1.0, seed=7))
    s = _server(workers=4)
    try:
        tracer = tracing.active
        for _ in range(32):
            s.register_node(mock.node())
        j = mock.batch_job()
        j.task_groups[0].count = 8
        # bench drives the server directly (no HTTP front), so open the
        # root span the agent's HTTP layer would normally start
        ctx = tracer.new_context()
        with tracing.span("bench.register_job", ctx=ctx, node=s.name):
            s.register_job(j)
        _wait_allocs(s.store, [j], 8, timeout=60)
        time.sleep(0.2)     # let the applier's observe-time spans land
        spans = tracer.spans(ctx["t"])
        out["spans"] = len(spans)
        names = {sp.name for sp in spans}
        for want_name in ("bench.register_job", "plan.submit",
                          "plan.evaluate", "raft.fsm_apply"):
            if want_name not in names:
                out["violations"].append(
                    f"sampled run missing span {want_name!r} "
                    f"(got {sorted(names)})")
        doc = tracing.chrome_trace([sp.to_dict() for sp in spans])
        evs = doc.get("traceEvents", [])
        out["perfetto_events"] = len(evs)
        if not any(e.get("ph") == "X" and "ts" in e and "dur" in e
                   for e in evs):
            out["violations"].append("chrome trace has no X events")
        path = os.path.join(tempfile.gettempdir(),
                            "nomad_tpu_smoke_trace.json")
        with open(path, "w") as f:
            json.dump(doc, f)
        with open(path) as f:
            reloaded = json.load(f)
        if reloaded.get("displayTimeUnit") != "ms" or \
                len(reloaded.get("traceEvents", [])) != len(evs):
            out["violations"].append("perfetto file did not round-trip")
        else:
            out["perfetto_file"] = path
    finally:
        s.stop()
        tracing.uninstall()
    log(f"trace checks: {out['disabled_overhead_ns_per_op']} ns/op "
        f"disabled; {out['spans']} spans sampled; "
        f"{out['perfetto_events']} perfetto events")
    return out


def bench_serving_plane(n_watchers=1200, n_blockers=12, idle_samples=200,
                        busy_samples=400, scenario="serving_plane"):
    """Serving-plane scenario: N concurrent event watchers (bounded
    broker subscriptions) plus follower blocking queries over HTTP on a
    3-server cluster, while a commit spine registers jobs through the
    leader.  Reports follower lease-read p50/p99 idle vs busy and the
    broker's drop/eviction counters; the hard invariant is that no
    subscriber queue ever exceeds its bound (zero unbounded growth)."""
    from nomad_tpu import mock
    from nomad_tpu.agent.http import HTTPServer
    from nomad_tpu.core.cluster import Cluster

    class _Shim:
        """agent surface for a per-server HTTP listener"""

        def __init__(self, server):
            self.server = server

        def rpc(self, method, args, consistency=None):
            return self.server.rpc_leader(method, args)

    c = Cluster(3)
    c.start()
    stop = threading.Event()
    threads = []
    http = None
    try:
        leader = c.leader()
        follower = c.followers()[0]
        deadline = time.time() + 30.0
        while not leader.raft.lease_valid() and time.time() < deadline:
            time.sleep(0.02)

        # watchers: bounded subscriptions on the follower's broker —
        # subscriptions are objects, not threads, so >=1K of them is
        # cheap; a small consumer pool drains them round-robin
        subs = [follower.event_broker.subscribe({"*": ["*"]}, max_queue=64)
                for _ in range(n_watchers)]
        consumed = [0] * 4

        def drain(slot, chunk):
            while not stop.is_set():
                idle = True
                for sub in chunk:
                    while True:
                        ev = sub.next(timeout=0.0)
                        if ev is None:
                            break
                        idle = False
                        consumed[slot] += 1
                if idle:
                    time.sleep(0.005)

        for k in range(4):
            t = threading.Thread(target=drain, args=(k, subs[k::4]),
                                 daemon=True)
            t.start()
            threads.append(t)

        # follower blocking queries through the real HTTP path
        # (?index&wait): each loop parks on the follower's store index
        # and must wake with a reply index >= the one it gave
        http = HTTPServer(_Shim(follower), port=0)
        http.start()
        wakeups = [0] * n_blockers
        block_errs = [0]

        def blocker(slot):
            import urllib.request
            while not stop.is_set():
                idx = follower.store.latest_index
                url = (f"http://127.0.0.1:{http.port}/v1/jobs"
                       f"?index={idx}&wait=300ms")
                try:
                    with urllib.request.urlopen(url, timeout=15.0) as r:
                        got = int(r.headers["X-Nomad-Index"])
                        r.read()
                    if got < idx:
                        block_errs[0] += 1
                    wakeups[slot] += 1
                except Exception:       # noqa: BLE001
                    if not stop.is_set():
                        block_errs[0] += 1

        for k in range(n_blockers):
            t = threading.Thread(target=blocker, args=(k,), daemon=True)
            t.start()
            threads.append(t)

        def sample(n):
            lats = []
            for _ in range(n):
                t0 = time.perf_counter()
                follower.read("Job.List", {}, consistency="default")
                lats.append(time.perf_counter() - t0)
            lats.sort()
            return lats

        # idle baseline: watchers + blockers attached, no commit spine
        # (a short discarded warmup absorbs first-read cold paths so the
        # idle p99 is a real steady-state denominator)
        sample(20)
        idle = sample(idle_samples)

        # commit spine on the leader (register -> eval -> schedule ->
        # raft commit -> store apply -> broker publish on every server)
        def spine():
            while not stop.is_set():
                j = mock.batch_job()
                j.task_groups[0].count = 10
                try:
                    leader.register_job(j)
                except Exception:       # noqa: BLE001
                    pass
                time.sleep(0.002)

        t = threading.Thread(target=spine, daemon=True)
        t.start()
        threads.append(t)
        time.sleep(0.3)                 # let the spine reach the broker
        busy = sample(busy_samples)

        stop.set()
        for t in threads:
            t.join(10.0)

        st = follower.event_broker.stats()
        max_q = max((s["queue_len"] for s in st["subs"]), default=0)
        bounded = all(s["queue_len"] <= s["max_queue"] for s in st["subs"])
        result = {
            "watchers": n_watchers,
            "blockers": n_blockers,
            "events_consumed": sum(consumed),
            "blocking_wakeups": sum(wakeups),
            "blocking_errors": block_errs[0],
            "read_p50_idle_ms": round(idle[len(idle) // 2] * 1000, 3),
            "read_p99_idle_ms": round(idle[int(len(idle) * .99)] * 1000, 3),
            "read_p50_busy_ms": round(busy[len(busy) // 2] * 1000, 3),
            "read_p99_busy_ms": round(busy[int(len(busy) * .99)] * 1000, 3),
            "dropped": sum(s["dropped"] for s in st["subs"]),
            "evictions": sum(s["evictions"] for s in st["subs"]),
            "max_queue_len": max_q,
            "bounded": bounded,
            "lease_reads": True,
        }
        log(f"{scenario}: {n_watchers} watchers / {n_blockers} blockers; "
            f"read p50/p99 idle {result['read_p50_idle_ms']}/"
            f"{result['read_p99_idle_ms']} ms, busy "
            f"{result['read_p50_busy_ms']}/{result['read_p99_busy_ms']} ms; "
            f"consumed {result['events_consumed']} events, "
            f"{result['blocking_wakeups']} blocking wakeups, "
            f"dropped {result['dropped']} (evictions "
            f"{result['evictions']}), max queue {max_q}, "
            f"bounded={bounded}")
        return result
    finally:
        stop.set()
        if http is not None:
            http.stop()
        c.stop()


def bench_scan_spread(n_nodes=10000, n_jobs=60, count=100, workers=48):
    """The SCAN path at C2M shape: spread+affinity service jobs (the
    workload class the bulk wavefront excludes — spreads are active), so
    every placement goes through place_batch_packed_jit's chained
    lax.scan.  Reports allocs/s + batched_evals so the path's coverage
    is visible (VERDICT r4 weak #4)."""
    from nomad_tpu.parallel.engine import get_engine
    s = _server(workers=workers)
    try:
        t0 = time.time()
        _fill_nodes(s, n_nodes)
        log(f"scan-spread world build ({n_nodes} nodes): "
            f"{time.time()-t0:.1f}s")
        _warm_engine(s, scan_job=_service_job(count))
        w = _service_job(50)
        s.register_job(w)
        _wait_allocs(s.store, [w], 50, timeout=300)

        eng = get_engine()
        base_batched = eng.stats["batched_evals"]
        jobs = [_service_job(count) for _ in range(n_jobs)]
        want = n_jobs * count
        t0 = time.time()
        for j in jobs:
            s.register_job(j)
        placed = _wait_allocs(s.store, jobs, want, timeout=600)
        dt = time.time() - t0
        batched = eng.stats["batched_evals"] - base_batched
        log(f"scan-spread: {placed}/{want} spread-service allocs in "
            f"{dt:.1f}s ({placed/dt:.0f} allocs/s, "
            f"batched_evals={batched})")
        log(f"scan-spread engine stats: {eng.stats}")
        _log_plan_submit("scan_spread")
        _require_complete("scan_spread", placed, want)
        return placed / dt
    finally:
        s.stop()


def bench_device_constrained(n_nodes=10000, n_jobs=20, count=100,
                             warm_count=50):
    """configs[3]: 10K nodes, half with GPU device groups; jobs with
    device requests and job anti-affinity."""
    from nomad_tpu.structs.resources import DeviceRequest, NodeDevice
    s = _server(workers=8)
    try:
        def node_fn(node, i):
            if i % 2 == 0:
                node.node_resources.devices = [NodeDevice(
                    vendor="nvidia", type="gpu", name="a100",
                    instance_ids=[f"gpu-{i}-0", f"gpu-{i}-1"])]
        t0 = time.time()
        _fill_nodes(s, n_nodes, node_fn=node_fn)
        log(f"device world build: {time.time()-t0:.1f}s")
        warm = _batch_job(warm_count)
        warm.task_groups[0].tasks[0].resources.devices = [
            DeviceRequest(name="gpu", count=1)]
        s.register_job(warm)
        _wait_allocs(s.store, [warm], warm_count, timeout=300)

        jobs = []
        for _ in range(n_jobs):
            j = _batch_job(count)
            j.task_groups[0].tasks[0].resources.devices = [
                DeviceRequest(name="gpu", count=1)]
            jobs.append(j)
        want = n_jobs * count
        t0 = time.time()
        for j in jobs:
            s.register_job(j)
        placed = _wait_allocs(s.store, jobs, want, timeout=300)
        dt = time.time() - t0
        log(f"device-constrained: {placed}/{want} GPU allocs in {dt:.1f}s "
            f"({placed/dt:.0f} allocs/s)")
        _log_plan_submit("device")
        _require_complete("device", placed, want)
        return placed / dt
    finally:
        s.stop()


def bench_preemption_heavy(n_nodes=10000, workers=48, n_service=10,
                           service_count=50):
    """configs[4]: 10K nodes at ~95% utilization of low-priority work;
    high-priority service jobs must preempt across priority tiers."""
    s = _server(workers=workers)
    try:
        cfg = s.store.scheduler_config
        cfg.preemption_config.service_scheduler_enabled = True
        cfg.preemption_config.batch_scheduler_enabled = True
        _fill_nodes(s, n_nodes)
        # fill to ~95%: nodes are 4000cpu/8192mb; 9 allocs x 420cpu = 94.5%
        fillers = [_batch_job(n_nodes * 3, cpu=420, mem=850)
                   for _ in range(3)]
        fillers_prio = []
        for i, j in enumerate(fillers):
            j.priority = 20 + i * 10
            fillers_prio.append(j)
            s.register_job(j)
        _wait_allocs(s.store, fillers, n_nodes * 9, timeout=600)

        jobs = [_service_job(service_count, cpu=420, mem=850, spread=False,
                             priority=90) for _ in range(n_service)]
        want = n_service * service_count
        t0 = time.time()
        for j in jobs:
            s.register_job(j)
        placed = _wait_allocs(s.store, jobs, want, timeout=300)
        dt = time.time() - t0
        preempted = sum(
            1 for a in s.store._allocs.values()
            if a.desired_status == "evict")
        log(f"preemption-heavy: {placed}/{want} high-prio allocs in "
            f"{dt:.1f}s ({placed/dt:.0f} allocs/s, {preempted} preempted)")
        _log_plan_submit("preemption")
        _require_complete("preemption", placed, want)
        return placed / dt
    finally:
        s.stop()


def bench_kernel_c2m_scale():
    """Kernel-only: one dense placement scan at 10K-node scale."""
    from nomad_tpu import mock
    from nomad_tpu.encode import ClusterMatrix
    from nomad_tpu.parallel.engine import get_engine
    from nomad_tpu.scheduler.stack import DenseStack

    cm = ClusterMatrix(initial_rows=16384)
    t0 = time.time()
    for i in range(10000):
        n = mock.node()
        n.attributes["rack"] = f"r{i % 50}"
        cm.upsert_node(n)
    log(f"world build (10000 nodes): {time.time()-t0:.2f}s")

    job = mock.job()
    job.task_groups[0].count = 1024
    stack = DenseStack(cm)
    groups = [stack.compile_group(job, tg) for tg in job.task_groups]
    inp = stack.build_inputs(job, groups, [0] * 1024, {})

    eng, alg = get_engine(), stack.spread_algorithm
    res, ticket = eng.place(cm, inp, spread_algorithm=alg)  # compile + run
    eng.complete(ticket)
    t0 = time.time()
    res, ticket = eng.place(cm, inp, spread_algorithm=alg)
    dt = time.time() - t0
    eng.complete(ticket)
    placed = int((res.node[:1024] >= 0).sum())
    log(f"kernel: {placed} placements over 10K nodes in {dt:.3f}s "
        f"({placed/dt:.0f} placements/s {_on_devices()})")
    return placed / dt


def bench_kernel_100k_nodes(n_nodes=100_000, waves=12, per_wave=8,
                            count=512,
                            out_path="BENCH_kernel_100k_nodes.json"):
    """100K-node world on the serving mesh: the shape a single-host
    round-trip budget cannot reach (re-uploading f32[131072, R] every
    wave).  The world uploads ONCE into the device-resident DeviceWorld,
    then `waves` dispatches of `per_wave` concurrent bulk evals (batched
    into one chained device call each) place allocs whose commits flow
    back as rank-1 scatters — steady state ships zero world bytes.
    Emits its own trajectory JSON (p50/p99 per-wave dispatch latency,
    engine stats) to `out_path` and returns the parsed dict."""
    import numpy as np

    from nomad_tpu import mock
    from nomad_tpu.encode import ClusterMatrix
    from nomad_tpu.parallel.engine import PlacementEngine
    from nomad_tpu.scheduler.stack import DenseStack

    cm = ClusterMatrix(initial_rows=131072)
    t0 = time.time()
    for i in range(n_nodes):
        n = mock.node()
        n.attributes["rack"] = f"r{i % 200}"
        cm.upsert_node(n)
    log(f"kernel_100k world build ({n_nodes} nodes, {cm.n_rows} padded "
        f"rows): {time.time()-t0:.1f}s")

    job = mock.batch_job()
    job.task_groups[0].count = count
    st = DenseStack(cm)
    g = st.compile_group(job, job.task_groups[0])
    N = cm.n_rows
    demand = np.zeros(cm.used.shape[1], np.float32)
    dm = np.asarray(g.demand, np.float32)
    demand[:min(len(dm), len(demand))] = dm[:len(demand)]
    bulk = dict(feasible=g.feasible,
                affinity=g.affinity.astype(np.float32),
                has_affinity=bool(g.has_affinity), desired=count,
                penalty=np.zeros(N, bool), coll0=np.zeros(N, np.int32),
                demand=g.demand.astype(np.float32), count=count)

    # max_batch bounds which E-bucket variants warm at this row count
    # (each compile stages f32[E, 4N]; per_wave is all we dispatch)
    eng = PlacementEngine(max_batch=per_wave)
    try:
        t0 = time.time()
        eng.warmup(cm, bulk=bulk)
        log(f"kernel_100k warm: {time.time()-t0:.1f}s")

        lat_s = []
        placed_total = 0
        t_run = time.time()
        for _ in range(waves):
            t0 = time.time()
            futs = [eng.place_bulk_begin(cm, **bulk)
                    for _ in range(per_wave)]
            results = [f.result() for f in futs]
            lat_s.append(time.time() - t0)
            for assign, placed, _ev, _ex, _scores, ticket in results:
                placed_total += int(placed)
                rows = np.flatnonzero(assign)
                for r_ in rows:
                    cm.used[r_] += assign[r_] * demand
                if ticket is not None:
                    eng.complete(ticket)
        dt = time.time() - t_run

        lat_ms = sorted(v * 1000.0 for v in lat_s)
        p50 = lat_ms[len(lat_ms) // 2]
        p99 = lat_ms[min(len(lat_ms) - 1, int(len(lat_ms) * 0.99))]
        stats = {k: (round(v, 4) if isinstance(v, float) else v)
                 for k, v in eng.stats.items()}
        traj = {
            "metric": "kernel_100k_nodes_allocs_per_sec",
            "value": round(placed_total / dt, 1),
            "unit": "allocs/s",
            "n_nodes": n_nodes, "padded_rows": int(N),
            **device_info(),
            "waves": waves, "evals_per_wave": per_wave, "count": count,
            "placed": placed_total,
            "p50_ms": round(p50, 2), "p99_ms": round(p99, 2),
            "engine_stats": stats,
        }
        with open(out_path, "w") as f:
            json.dump(traj, f, indent=2)
            f.write("\n")
        log(f"kernel_100k_nodes: {placed_total} allocs in {dt:.1f}s "
            f"({placed_total/dt:.0f} allocs/s; wave p50 {p50:.0f} ms / "
            f"p99 {p99:.0f} ms {_on_devices()})")
        log(f"kernel_100k engine stats: {eng.stats}")
        return traj
    finally:
        eng.stop()


def main():
    target = 1_000_000 / 30.0       # north-star C2M rate (v5e-8)

    if "--fleet-soak" in sys.argv:
        # 10K-agent fleet cells (nomad_tpu/scenarios.py FleetSoakShape):
        # batched heartbeats, drain/churn storms, and the blank-join
        # gate with a leader hard-kill mid-snapshot-stream.  Minutes per
        # cell at full size; the CI leg shrinks the fleet via
        # NOMAD_TPU_FLEET_AGENTS.  A NOMAD_TPU_CHAOS env spec overrides
        # the schedule (cells collapse to (fleet_soak, env)).
        from nomad_tpu.scenarios import FLEET_CELLS, run_matrix
        seed = 1
        if "--seed" in sys.argv:
            seed = int(sys.argv[sys.argv.index("--seed") + 1])
        summary = run_matrix(FLEET_CELLS, seed=seed, log=log)
        print(json.dumps({
            "metric": "fleet_soak",
            **device_info(),
            "seed": seed,
            "agents": int(os.environ.get("NOMAD_TPU_FLEET_AGENTS",
                                         "10000")),
            "cells": len(summary["cells"]),
            "passed": summary["passed"],
            "failed": summary["failed"],
            "per_cell": [{
                "shape": t.get("shape"), "schedule": t.get("schedule"),
                "converged": t["convergence"].get("converged"),
                "convergence_time_s":
                    t["convergence"].get("convergence_time_s"),
                "notes": t.get("notes"),
            } for t in summary["cells"]],
        }), flush=True)
        sys.exit(0 if summary["ok"] else 1)

    if "--matrix" in sys.argv:
        # chaos scenario matrix: workload shapes x phased chaos
        # schedules on a real 3-server cluster, each cell gated on
        # post-chaos convergence invariants (nomad_tpu/scenarios.py).
        # `--matrix --smoke` runs the curated CI subset; `--seed N`
        # picks the chaos seed; a NOMAD_TPU_CHAOS env spec overrides
        # the schedule for every cell.
        from nomad_tpu.scenarios import ALL_CELLS, SMOKE_CELLS, run_matrix
        seed = 1
        if "--seed" in sys.argv:
            seed = int(sys.argv[sys.argv.index("--seed") + 1])
        cells = SMOKE_CELLS if "--smoke" in sys.argv else ALL_CELLS
        summary = run_matrix(cells, seed=seed, log=log)
        print(json.dumps({
            "metric": "scenario_matrix",
            **device_info(),
            "seed": seed,
            "cells": len(summary["cells"]),
            "passed": summary["passed"],
            "failed": summary["failed"],
            "per_cell": [{
                "shape": t.get("shape"), "schedule": t.get("schedule"),
                "converged": t["convergence"].get("converged"),
                "convergence_time_s":
                    t["convergence"].get("convergence_time_s"),
                "allocs_per_sec": t.get("allocs_per_sec"),
                "plan_submit_ms": t.get("plan_submit_ms"),
            } for t in summary["cells"]],
        }), flush=True)
        sys.exit(0 if summary["ok"] else 1)

    if "--smoke" in sys.argv:
        # CI leg: the same shape in seconds (tier-1 invokes this)
        rate, placed, want = bench_smoke()
        steady = _STEADY_STATE.get("smoke", {})
        # serving-plane leg rides the smoke run: >=1K watchers +
        # follower blocking queries on a 3-server cluster while the
        # spine commits.  Hard-fails on unbounded subscriber queues or
        # busy read p99 blowing past 2x idle (5 ms floor absorbs CI
        # scheduler jitter on shared CPU runners).
        serving = bench_serving_plane(
            n_watchers=1024, n_blockers=8,
            idle_samples=150, busy_samples=300)
        # per-scenario regression gate: the spread / device / preemption
        # shapes shrunk to seconds, their plan.submit p99 capped.  The
        # cap is generous (it catches order-of-magnitude regressions in
        # a scenario's placement path, not CI-runner jitter) and
        # env-overridable for slow runners.
        p99_cap_ms = float(os.environ.get("NOMAD_TPU_SMOKE_P99_MS", "750"))
        scenario_violations = []
        for name, fn in (
                ("scan_spread", lambda: bench_scan_spread(
                    n_nodes=256, n_jobs=6, count=20, workers=8)),
                ("device", lambda: bench_device_constrained(
                    n_nodes=256, n_jobs=4, count=25, warm_count=10)),
                ("preemption", lambda: bench_preemption_heavy(
                    n_nodes=96, workers=8, n_service=2,
                    service_count=12))):
            fn()
            p99 = _PLAN_STATS.get(name, {}).get(
                "submit_ms", {}).get("p99", 0.0)
            if p99 > p99_cap_ms:
                scenario_violations.append(
                    f"{name}: plan.submit p99 {p99} ms > "
                    f"cap {p99_cap_ms} ms")
        # fused-path leg (r15): the smoke spine must have run every bulk
        # wave group as ONE device dispatch, and the donating bulk
        # kernel must be registered with the recompile budget and warm
        # before the gate (its cache populated by warmup, not the
        # measured window).  The sharded twin is only checkable on a
        # multi-device host.
        fused_violations = []
        snap = _ENGINE_SNAP.get("smoke", {})
        groups = snap.get("bulk_groups", 0)
        parts = snap.get("bulk_parts", 0)
        if groups <= 0:
            fused_violations.append(
                "no bulk wave groups dispatched (fused path unused)")
        elif parts != groups:
            fused_violations.append(
                f"fused path inactive: {parts} device dispatches for "
                f"{groups} wave groups (expected 1 per wave)")
        from nomad_tpu.analysis import recompile as _recompile
        kernel_sizes = _recompile.cache_sizes()
        want_kernels = ["place.bulk_batch_donate"]
        if device_info()["device_count"] > 1:
            want_kernels.append("sharded.bulk")
        for k in want_kernels:
            if kernel_sizes.get(k) is None:
                fused_violations.append(
                    f"kernel {k!r} missing a recompile.register entry")
            elif kernel_sizes[k] < 1:
                fused_violations.append(
                    f"kernel {k!r} registered but never warmed "
                    f"(cache empty after the run)")
        # tracing leg: disabled guards must be free, sampled run must
        # export a well-formed Perfetto file (r12)
        trace_checks = _smoke_trace_checks()
        print(json.dumps({
            "metric": "c2m_smoke_allocs_per_sec",
            "value": round(rate, 1),
            "unit": "allocs/s",
            "vs_baseline": round(rate / target, 4),
            **device_info(),
            "placed": placed,
            "want": want,
            "plan_latency_ms": _PLAN_STATS,
            "steady_state": steady,
            "serving_plane": serving,
            "fused": {"bulk_groups": groups, "bulk_parts": parts,
                      "kernels": {k: kernel_sizes.get(k)
                                  for k in want_kernels},
                      "violations": fused_violations},
            "tracing": trace_checks,
        }), flush=True)
        if placed < want:
            log(f"smoke INCOMPLETE: {placed}/{want} before deadline")
            sys.exit(1)
        if steady.get("violations"):
            log("steady-state violations:", steady["violations"])
            sys.exit(1)
        if fused_violations:
            for v in fused_violations:
                log("fused gate:", v)
            sys.exit(1)
        if trace_checks["violations"]:
            for v in trace_checks["violations"]:
                log("tracing gate:", v)
            sys.exit(1)
        if scenario_violations:
            for v in scenario_violations:
                log("scenario gate:", v)
            sys.exit(1)
        if not serving["bounded"]:
            log("serving_plane: subscriber queue exceeded its bound")
            sys.exit(1)
        p99_cap = max(2 * serving["read_p99_idle_ms"], 5.0)
        if serving["read_p99_busy_ms"] > p99_cap:
            log(f"serving_plane: busy read p99 "
                f"{serving['read_p99_busy_ms']} ms exceeds cap "
                f"{p99_cap:.1f} ms (2x idle, 5 ms floor)")
            sys.exit(1)
        return

    if "--100k" in sys.argv:
        # the 100K-node device-resident scenario, alone (own trajectory
        # JSON; the stdout line mirrors it for the driver)
        traj = bench_kernel_100k_nodes()
        print(json.dumps(traj), flush=True)
        return

    # headline: the REAL north-star number — C2M-1M at full size.
    # Every scenario runs even when an earlier one failed (an hour-long
    # run should report all it can), but any failure is named in the
    # JSON and makes the process exit non-zero.
    failures = []

    def run(name, fn):
        try:
            return fn()
        except Exception as e:          # noqa: BLE001 — recorded, exits 1
            log(f"scenario {name} FAILED:\n{traceback.format_exc()}")
            failures.append(f"{name}: {type(e).__name__}: {e}")
            return None

    rate = 0.0
    headline = run("c2m_1m", bench_c2m_1m)
    if headline is not None:
        rate, placed, want = headline
        if placed < want:
            failures.append(
                f"c2m_1m INCOMPLETE: {placed}/{want} before deadline")
        for v in _STEADY_STATE.get("c2m_1m", {}).get("violations", ()):
            failures.append(f"c2m_1m steady-state gate: {v}")
    run("kernel_c2m_scale", bench_kernel_c2m_scale)
    run("kernel_100k_nodes", bench_kernel_100k_nodes)
    serving = run("serving_plane", bench_serving_plane) or {}

    if os.environ.get("BENCH_ALL") == "1":
        # the full BASELINE.json scenario suite (tens of minutes)
        for name, fn in (("e2e_spine", bench_e2e_spine),
                         ("dev_agent", bench_dev_agent_sim),
                         ("c2m", bench_c2m),
                         ("scan_spread", bench_scan_spread),
                         ("device", bench_device_constrained),
                         ("preemption", bench_preemption_heavy)):
            run(name, fn)

    print(json.dumps({
        "metric": "c2m_1m_allocs_per_sec_10knodes_1mallocs",
        "value": round(rate, 1),
        "unit": "allocs/s",
        "vs_baseline": round(rate / target, 4),
        **device_info(),
        "plan_latency_ms": _PLAN_STATS,
        "steady_state": _STEADY_STATE,
        "serving_plane": serving,
        "failures": failures,
    }), flush=True)
    if failures:
        for f in failures:
            log("FAILED:", f)
        sys.exit(1)


if __name__ == "__main__":
    main()
