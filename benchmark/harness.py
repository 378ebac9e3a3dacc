"""One run of one cell: set-up, warm-up, the measured window, readback,
the comparison with the plain reference, and the result line.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric is a file found by the name `BENCHMARK.json` gives it
(`configs/`, `traffic/`, `metrics/` + `readers/`), and the modules that
make a configuration's deployment (its cluster, its job shapes, its
plain reference) by the names its own file gives them (`world_module`);
this file knows none of them by name.
"""
from __future__ import annotations

import importlib
import json
import os
import shutil
import statistics
import sys
import threading
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
TRACE_S = 5.0          # the profiler traces this much of the window
# what a configuration's file need not name: the modules `c2m-10k` runs
WORLD_DEFAULTS = {"cluster": "cluster", "jobs": "jobs"}


class Refused(RuntimeError):
    """The run cannot be made as the cell asks; exit non-zero, no line."""


def say(*a) -> None:
    print(*a, file=sys.stderr, flush=True)


def load_benchmark() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


PER_LAYER_MAX = 128     # the driver refuses a longer `per_layer`


def check_benchmark(bench: dict) -> None:
    """`per_layer` as PERF.md section 3 rules it: a quantity is one entry,
    named by its metric file's base name, and lists its cells.  `Refused`
    with the entry's name, before anything starts, so that a list the
    driver would refuse (or read nothing from) is heard of here."""
    entries = bench["per_layer"]
    if len(entries) > PER_LAYER_MAX:
        raise Refused(f"per_layer has {len(entries)} entries, "
                      f"{PER_LAYER_MAX} at most: the first one over is "
                      f"{entries[PER_LAYER_MAX]['name']!r}")
    cells = {w["name"] for w in bench["workloads"]}
    moved = {m["name"]: set(m.get("workloads", cells))
             for m in bench["end_to_end"]}
    seen, split = set(), {}
    for m in entries:
        name = m["name"]
        if name in seen:
            raise Refused(f"per_layer names {name!r} twice")
        seen.add(name)
        listed = m.get("workloads")
        if not isinstance(listed, list) or not listed:
            raise Refused(f"per_layer {name!r} lists no workloads")
        if m["moves"] not in moved:
            raise Refused(f"per_layer {name!r} moves {m['moves']!r}, "
                          "which is no end-to-end metric")
        for cell in listed:
            if cell not in cells:
                raise Refused(f"per_layer {name!r} lists {cell!r}, "
                              "which is no workload")
            if cell not in moved[m["moves"]]:
                raise Refused(f"per_layer {name!r} lists {cell!r}, which "
                              f"does not report {m['moves']!r}")
        path = _metric_file(name)
        if path is None:
            raise Refused(f"per_layer {name!r} has no metric file under "
                          "benchmark/metrics")
        with open(path) as f:
            reader = json.load(f).get("reader")
        try:
            importlib.import_module(f"benchmark.readers.{reader}")
        except ImportError as e:
            raise Refused(f"per_layer {name!r}: reader {reader!r} of "
                          f"{os.path.basename(path)}: {e}") from e
        # a name without a file of its own is a copy of its base name's
        base = os.path.basename(path)[:-len(".json")]
        split.setdefault((base, m["moves"]), []).append(name)
    for (base, moves), names in split.items():
        if len(names) > 1:
            extra = next(n for n in names if n != base)
            other = next(n for n in names if n != extra)
            raise Refused(
                f"per_layer {extra!r} reads {base!r}'s metric file and moves "
                f"{moves!r}, as {other!r} does: one entry lists the cells "
                "of both, or the copy brings a metric file of its own")


def load_config(name: str) -> dict:
    with open(os.path.join(HERE, "configs", f"{name}.json")) as f:
        return json.load(f)


def world_module(cfg: dict, key: str):
    """The module the configuration's file names under `key`
    (`reference`, `cluster` or `jobs`): a dotted name under this package.
    PERF.md section 4 has what the harness takes from each."""
    name = cfg.get(key, WORLD_DEFAULTS.get(key))
    if not name:
        raise Refused(f"configuration {cfg.get('name')!r} names no "
                      f"{key!r} module")
    try:
        return importlib.import_module(f"benchmark.{name}")
    except ModuleNotFoundError as e:
        raise Refused(f"configuration {cfg.get('name')!r}: {key} module "
                      f"benchmark.{name}: {e}") from e


def device_check(chips: int, require_tpu: bool) -> dict:
    import jax
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    if require_tpu and dev["platform"] != "tpu":
        raise Refused(f"no accelerator: jax reports {dev}")
    if require_tpu and dev["count"] != chips:
        raise Refused(f"the cell asks for {chips} chip(s), jax reports {dev}")
    return dev


class Compiles:
    """Counts jax's backend compiles (fires once per compile, never on a
    cache hit) and persistent-cache hits and misses."""

    def __init__(self):
        import jax
        self.count = self.hits = self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._ev)

    def _dur(self, name, _secs, **_kw) -> None:
        if name == "/jax/core/compile/backend_compile_duration":
            self.count += 1

    def _ev(self, name, **_kw) -> None:
        if name == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif name == "/jax/compilation_cache/cache_misses":
            self.misses += 1


# ------------------------------------------------------------- set-up

def warm_classes(server, mix: dict, build) -> None:
    """`engine.warmup` for the shape classes this cell's traffic can
    reach and no others (as `bench._warm_engine` builds its samples): per
    class a scan sample of `scan_slots` slots of the shape's job (made by
    `build`, the configuration's jobs module) and, with `bulk`, the bulk
    variant grid for its first group."""
    from nomad_tpu.parallel.engine import get_engine
    from nomad_tpu.scheduler.stack import DenseStack
    eng = get_engine()
    cm = server.store.matrix
    for cls in mix["warm"]["classes"]:
        job = build(mix["shapes"][cls["shape"]], "warm-sample")
        st = DenseStack(cm)
        groups = [st.compile_group(job, tg) for tg in job.task_groups]
        inputs = st.build_inputs(job, groups, [0] * cls["scan_slots"], {})
        bulk = None
        if cls.get("bulk"):
            g = groups[0]
            bulk = dict(
                feasible=g.feasible, affinity=g.affinity.astype(np.float32),
                has_affinity=bool(g.has_affinity),
                desired=max(job.task_groups[0].count, 1),
                penalty=np.zeros(cm.n_rows, bool),
                coll0=np.zeros(cm.n_rows, np.int32),
                demand=g.demand.astype(np.float32),
                count=job.task_groups[0].count)
        eng.warmup(cm, inputs=inputs, bulk=bulk)


def check_preload(api, cl, seed: int) -> None:
    """A few nodes drawn from the seed: the allocations the HTTP API
    lists on them add up to the reference's own preload sum."""
    rng = np.random.default_rng([int(seed), 0x9E10AD])
    for row in rng.choice(cl.n, size=min(4, cl.n), replace=False):
        got = np.zeros(2)
        for a in api.get(f"/v1/node/{cl.node_ids[row]}/allocations"):
            for tr in a["allocated_resources"]["tasks"].values():
                got += [tr["cpu_shares"], tr["memory_mb"]]
        if not np.array_equal(got, cl.used0[row]):
            raise Refused(f"preload on node #{row}: the API lists "
                          f"{got.tolist()}, the seed gives "
                          f"{cl.used0[row].tolist()}")


# -------------------------------------------------------------- facts

def snapshot(agent, api) -> dict:
    """Every counter the per-layer readers may difference, flat."""
    from nomad_tpu.parallel.engine import get_engine
    eng = get_engine()
    out = {}
    for k, v in dict(eng.stats).items():
        if isinstance(v, (int, float)):
            out[f"engine.{k}"] = float(v)
    for k, v in eng.world_stats().items():
        out[f"world.{k}"] = float(v)
    for k, v in dict(agent.server.applier.stats).items():
        if isinstance(v, (int, float)):
            out[f"applier.{k}"] = float(v)
    out["workers.processed"] = float(sum(
        w.stats["processed"] for w in agent.server.workers))
    inv_t = inv_c = 0.0
    for s in api.system.metrics().get("Samples", ()):
        total = s["mean"] * s["count"]
        out[f"telemetry.{s['Name']}.count"] = float(s["count"])
        out[f"telemetry.{s['Name']}.total_ms"] = total
        if s["Name"].startswith("nomad.worker.invoke_scheduler."):
            inv_t += total
            inv_c += s["count"]
    out["telemetry.invoke_scheduler.total_ms"] = inv_t
    out["telemetry.invoke_scheduler.count"] = inv_c
    return out


def difference(after: dict, before: dict, prefix: str = "") -> dict:
    return {prefix + k: v - before.get(k, 0.0) for k, v in after.items()}


def percentile(values: list, p: float) -> float:
    """Nearest-rank percentile of all values (p in 0..100)."""
    s = sorted(values)
    return s[min(len(s) - 1, max(0, int(np.ceil(p / 100.0 * len(s))) - 1))]


def memory_peak() -> int:
    import jax
    peak = 0
    for d in jax.devices():
        st = d.memory_stats() or {}
        peak = max(peak, int(st.get("peak_bytes_in_use", 0)))
    return peak


# ------------------------------------------------------------ the run

def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             t_start: float, n_nodes: int | None = None,
             require_tpu: bool = True) -> dict:
    """Drive one run and return the result line as a dict.  `n_nodes`
    and `require_tpu=False` are for the tests under benchmark/tests,
    which drive the same code at a small size on the CPU."""
    bench = load_benchmark()
    check_benchmark(bench)
    cell = next((w for w in bench["workloads"] if w["name"] == workload), None)
    if cell is None:
        raise Refused(f"no workload {workload!r} in BENCHMARK.json")
    dev = device_check(cell["chips"], require_tpu)
    say(f"platform={dev['platform']} device_kind={dev['kind']} "
        f"device_count={dev['count']}")

    from benchmark import traffic, trace_reduce
    cfg = load_config(cell["config"])
    reference, cluster, jobs = (world_module(cfg, key) for key in
                                ("reference", "cluster", "jobs"))
    mix = traffic.load(cell["traffic"])
    compiles = Compiles()

    from nomad_tpu.agent.agent import Agent, AgentConfig
    from nomad_tpu.api.client import ApiClient
    from nomad_tpu.parallel.engine import get_engine
    t0 = time.monotonic()
    cl = cluster.Cluster(cfg, seed, n_nodes)
    agent = Agent(AgentConfig(http_port=0, num_schedulers=cfg["schedulers"],
                              heartbeat_ttl=3600.0))
    wrote = cl.install(agent)
    t_install = time.monotonic() - t0
    agent.start()
    drv = None
    try:
        api = ApiClient(agent.http_addr, timeout=120.0)
        sc = api.operator.scheduler_get_configuration()
        sc.preemption_config.service_scheduler_enabled = \
            cfg["preemption"]["service"]
        sc.preemption_config.batch_scheduler_enabled = \
            cfg["preemption"]["batch"]
        api.operator.scheduler_set_configuration(sc)
        check_preload(api, cl, seed)
        eng = get_engine()
        rows = int(agent.server.store.matrix.n_rows)
        say(f"world: {wrote}, {rows} padded rows, install "
            f"{t_install:.1f}s, ready {time.monotonic() - t0:.1f}s after "
            f"{t0 - t_start:.1f}s of imports and device start")

        t1 = time.monotonic()
        warm_classes(agent.server, mix, jobs.build)
        say(f"engine.warmup: {time.monotonic() - t1:.1f}s, cache hits "
            f"{compiles.hits} misses {compiles.misses}")

        span = None
        if trace:
            import jax
            span = jax.profiler.TraceAnnotation
        drv = traffic.Driver(agent.http_addr, mix, seed, reference.JobSpec,
                             jobs.build, span)
        t1 = time.monotonic()
        warm = drv.warm_pass(mix["warm"]["jobs"],
                             mix.get("clients", 4))
        bad = [r for r in warm if r.done is None]
        if bad:
            raise Refused(f"warm pass: {len(bad)} job(s) not placed, e.g. "
                          f"{bad[0].spec.id}: {bad[0].failed}")
        say(f"warm pass: {len(warm)} jobs in {time.monotonic() - t1:.1f}s")
        if eng.world_stats().get("full_uploads", 0) < 1:
            raise Refused("the device-resident world was never uploaded")

        before = snapshot(agent, api)
        c0 = compiles.count
        trace_dir = os.path.join(ROOT, ".bench_trace",
                                 f"{workload}-{seed}")
        setup_s = time.monotonic() - t_start
        traced = None
        if trace:
            traced = _Tracer(trace_dir, agent, api, min(TRACE_S, seconds))
            threading.Thread(target=traced.run, daemon=True).start()
        win = drv.window(seed, seconds)
        if traced is not None:
            traced.join()
        after = snapshot(agent, api)
        window_s = win["end"] - win["t0"]
        recs = win["records"]
        say(f"window: {len(recs)} jobs sent in {window_s:.1f}s, drained "
            f"after {win['drained'] - win['end']:.1f}s")

        peak = memory_peak()

        # -------- metrics of the window (the client's clock)
        done = [r for r in recs if r.done is not None]
        in_window = [r for r in done if r.done <= win["end"]]
        lat_ms = [((r.done if r.done is not None else win["drained"])
                   - r.due) * 1e3 for r in recs]
        late_ms = [(r.sent - r.due) * 1e3 for r in recs if r.sent is not None]
        facts = difference(after, before)
        facts.update({
            "window.seconds": window_s,
            "compiles.count": float(compiles.count - c0),
            "client.jobs_attempted": float(len(recs)),
            "client.jobs_completed": float(len(done)),
            "client.allocs_completed": float(
                sum(r.spec.allocs for r in in_window)),
            "client.register_count": float(
                sum(1 for r in recs if r.rtt is not None)),
            "client.register_rtt_ms_sum": sum(
                r.rtt * 1e3 for r in recs if r.rtt is not None),
            "shape.rows": float(rows), "shape.resource_dims": 4.0,
            "device.kind": dev["kind"],
        })
        if lat_ms:
            facts["client.p50_ms"] = statistics.median(lat_ms)
            facts["client.p95_ms"] = percentile(lat_ms, 95)
        if late_ms and mix["arrivals"] == "open":
            facts["client.late_p95_ms"] = percentile(late_ms, 95)
        say(f"client: p50 {facts.get('client.p50_ms')} ms, p95 "
            f"{facts.get('client.p95_ms')} ms, generator late p95 "
            f"{facts.get('client.late_p95_ms')} ms, "
            f"{sum(lat_ms) / 1e3 / window_s:.2f} jobs in the system on "
            f"average (the latencies' sum over the window)")
        end_to_end = {
            "job_placed_p50_ms": facts.get("client.p50_ms"),
            "allocs_per_s": facts["client.allocs_completed"] / window_s,
            "setup_s": setup_s,
        }

        # -------- readback and the comparison, outside the window
        t1 = time.monotonic()
        specs = {r.spec.id: r.spec for r in list(warm) + recs}
        stubs = [s for r in list(warm) + recs for s in _final_stubs(drv, r)]
        sample = _sample(done, mix["sample_jobs"], seed)
        full = []
        for r in sample:
            for ev in sorted({s["EvalID"] for s in r.stubs}):
                full.extend(a for a in
                            api.get(f"/v1/evaluation/{ev}/allocations")
                            if a["job_id"] == r.spec.id)
        say(f"readback: {len(stubs)} stubs, {len(full)} allocations of "
            f"{len(sample)} sampled jobs in {time.monotonic() - t1:.1f}s")
        # what else the configuration's reference wants to have read:
        # its own GETs, after the window and the memory reading
        more = ()
        if hasattr(reference, "readback"):
            more = (reference.readback(api.get, list(warm) + recs),)
        t1 = time.monotonic()
        verdict = reference.compare(cl, specs, stubs, full,
                                    {r.spec.id for r in done}, *more)
        say(f"comparison: {time.monotonic() - t1:.1f}s; " + "; ".join(
            f"{k} {_brief(v)}" for k, v in verdict.items()
            if k not in ("correct", "compared")))

        if trace:
            facts.update(traced.facts(trace_reduce,
                                      _kernels(bench, workload), recs))
    finally:
        if drv is not None:
            drv.close()
        agent.stop()

    failed = [r for r in recs if r.done is None]
    if failed:
        say(f"failed: {len(failed)} job(s), e.g. {failed[0].spec.id}: "
            f"{failed[0].failed}")
    device = {"platform": dev["platform"], "kind": dev["kind"],
              "count": dev["count"], "memory_peak_bytes": peak}
    line = {"correct": bool(verdict["correct"]) and bool(recs),
            "attempted": len(recs), "failed": len(failed)}
    if trace:
        line["metrics"] = _per_layer(bench, workload, facts)
        device["busy_s"] = facts.get("trace.busy_s", 0.0)
        device["window_s"] = facts.get("trace.window_s", 0.0)
        line["device"] = device
        line["breakdown"] = {"device_ops": traced.reduced["device_ops"],
                             "idle_gaps": traced.reduced["idle_gaps"]}
    else:
        line["metrics"] = {
            m["name"]: {"value": end_to_end[m["name"]], "unit": m["unit"]}
            for m in bench["end_to_end"]
            if workload in m.get("workloads", [workload])
            and end_to_end.get(m["name"]) is not None}
        line["device"] = device
    line["compared"] = verdict["compared"]
    for k, v in verdict["compared"].items():
        say(f"compared {k}: {v['value']!r} limit {v['limit']!r}")
    say(f"correct: {line['correct']}")
    return line


def _brief(v):
    """A verdict's entry for the log: an array as its size, median and
    largest value."""
    if isinstance(v, np.ndarray):
        return f"n={v.size}" + (f" p50={np.median(v):.3g} max={v.max():.3g}"
                                if v.size else "")
    return v


def _final_stubs(drv, rec) -> list:
    """A finished job's allocation list as its client last read it; an
    unfinished job's, read again now."""
    if rec.done is not None:
        return rec.stubs
    try:
        return drv.apis[rec.spec.namespace].jobs.allocations(rec.spec.id)
    except Exception:                     # noqa: BLE001 - refused job
        return rec.stubs


def _sample(done: list, k: int, seed: int) -> list:
    """`k` finished jobs drawn from the seed, the largest among them."""
    if not done:
        return []
    rng = np.random.default_rng([int(seed), 0x5A3F1E])
    big = max(done, key=lambda r: r.spec.allocs)
    rest = [r for r in done if r is not big]
    pick = rng.choice(len(rest), size=min(k - 1, len(rest)), replace=False)
    return [big] + [rest[i] for i in sorted(pick)]


def _metric_file(name: str):
    """The metric file of a per-layer entry: `metrics/<name>.json`, or the
    file of its base name, which a quantity split by what it moves
    (`x.backlog`, `x`) shares.  None where there is none."""
    for stem in (name, name.rpartition(".")[0]):
        path = os.path.join(HERE, "metrics", f"{stem}.json")
        if stem and os.path.exists(path):
            return path
    return None


def _metric_specs(bench: dict, workload: str):
    """(entry of BENCHMARK.json, its metric file) for the cell's
    per-layer metrics."""
    for m in bench["per_layer"]:
        if workload in m.get("workloads", [workload]):
            with open(_metric_file(m["name"])) as f:
                yield m, json.load(f)


def _kernels(bench: dict, workload: str) -> dict:
    """The compiled programs whose device time the cell's metrics read,
    {key: a stable part of the program's name in the trace}; each metric
    file names its own."""
    out = {}
    for _m, spec in _metric_specs(bench, workload):
        out.update(spec.get("kernels", {}))
    return out


def _per_layer(bench: dict, workload: str, facts: dict) -> dict:
    """Each per-layer metric of this cell through its own reader; a
    reader that finds nothing to read leaves the metric out."""
    out = {}
    for m, spec in _metric_specs(bench, workload):
        reader = importlib.import_module(f"benchmark.readers.{spec['reader']}")
        value = reader.read(facts, spec)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


class _Tracer:
    """Traces the first `seconds` of the window with the profiler, on a
    thread of its own, and keeps the counters of exactly that stretch."""

    def __init__(self, log_dir, agent, api, seconds):
        shutil.rmtree(log_dir, ignore_errors=True)
        self.dir, self.agent, self.api = log_dir, agent, api
        self.seconds = seconds
        self.done = threading.Event()
        self.reduced = {"device_ops": [], "idle_gaps": []}

    def run(self) -> None:
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        time.sleep(0.5)
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.before = snapshot(self.agent, self.api)
        self.t0 = time.monotonic()
        time.sleep(self.seconds)
        self.t1 = time.monotonic()
        self.after = snapshot(self.agent, self.api)
        jax.profiler.stop_trace()
        self.done.set()

    def join(self) -> None:
        self.done.wait()

    def facts(self, trace_reduce, kernels, recs) -> dict:
        red = trace_reduce.reduce_trace(trace_reduce.find_trace(self.dir),
                                        kernels)
        shutil.rmtree(self.dir, ignore_errors=True)
        self.reduced = red
        inside = [r for r in recs if r.done is not None
                  and self.t0 <= r.done <= self.t1]
        out = difference(self.after, self.before, "trace.")
        out.update({
            "trace.window_s": self.t1 - self.t0,
            "trace.busy_s": red["busy_s"],
            "trace.allocs_placed": float(sum(r.spec.allocs for r in inside)),
            "trace.scan_slots": float(sum(
                r.spec.allocs for r in inside if r.spec.spread)),
        })
        for k, v in red["kernel_s"].items():
            if v > 0:
                out[f"trace.kernel_s.{k}"] = v
        return out
