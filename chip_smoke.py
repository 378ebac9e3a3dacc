#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the placement spine runs on the chip.

One process.  It starts the agent a user would start (`Agent`, server mode,
HTTP API on an ephemeral port), registers a 10,000-node cluster through
`Node.Register`, warms the placement engine, and submits jobs through
`ApiClient` over HTTP so that every kernel family on the serving path runs
at full node width: the batched bulk wavefront with donated carries (C2M
shaped jobs, 10 groups x count 10), the multi-wave dense bulk output (one
batch job above SPARSE_CAP), the chained scan (spread + affinity service
jobs), the device-instance path, preemption, and single placements.  The
mix runs twice: once to finish warming, once under the steady-state gate
(`bench._SteadyGate`: transfer guard "disallow", no compile, no world
re-upload, donated carries, one device dispatch per bulk wave group).

Allocations are read back over HTTP and held to a plain numpy check written
here, independent of the code under test: every group at its count, no node
over capacity, device allocs on GPU nodes with distinct instances, spread
jobs covering most racks, single placements at the binpack optimum.

It fails, and never falls back: no TPU, no native library, a FAILED eval, a
gate violation, or (with several devices) a mesh that did not engage all
exit non-zero.  The last line of stdout is one JSON object
`{"ok": true, "device": {...}}`; the line before it is the run's summary.

Run it on the chip through the chip tool, from the root of a checkout:

    python3 chip_smoke.py
"""
from __future__ import annotations

import json
import logging
import sys
import time
import traceback

import numpy as np

N_NODES = 10_000      # BASELINE.json configs[2], the C2M node count
N_RACKS = 50          # -> 16,384 padded rows on the device
POOL_NODES = 16       # nodes set aside per preemption pool (dc2, dc3)
BIG_COUNT = 1_200     # > SPARSE_CAP: dense bulk output, several waves
SPREAD_COUNT = 100
# spread is a score term, not a guarantee: a 100-alloc job over 50 racks
# covered 50/50 racks in every CPU rehearsal of this script (2,048 and
# 10,000 nodes; 1, 4 and 8 devices); the bound leaves room for ties that
# the TPU's pow breaks differently
SPREAD_MIN_RACKS = 40
BINPACK_TOL = 1e-4    # normalized (score / 18) units
# a second tenant: the engine bins bulk evals into the mesh's wave lanes by
# namespace, so on several chips two namespaces score side by side
NAMESPACE_B = "smoke-b"
WAIT_S = 300.0


class SmokeFailure(RuntimeError):
    """A check of this script failed; the process exits non-zero."""


def say(*a) -> None:
    print(*a, flush=True)


# ------------------------------------------------------------------ device

def device_check() -> dict:
    """The device as jax reports it.  Anything but a TPU is a failure:
    with libtpu installed and no chip, jax falls back to the CPU with a
    warning, and the spine would carry on there without saying so."""
    import bench
    dev = bench.device_info()
    if dev["platform"] != "tpu":
        raise SmokeFailure(
            f"no accelerator: jax.devices()[0].platform == "
            f"{dev['platform']!r} ({dev['device_kind']}, "
            f"{dev['device_count']} device(s)); "
            f"chip_smoke.py runs on a TPU only")
    return dev


class _CacheEvents:
    """Counts jax's persistent-compilation-cache hits and misses."""

    def __init__(self):
        import jax
        self.hits = self.misses = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, name, **_kw) -> None:
        if name == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif name == "/jax/compilation_cache/cache_misses":
            self.misses += 1


# ------------------------------------------------------------------- world

def make_nodes(n_nodes: int):
    """`n_nodes` mock nodes over N_RACKS racks; every even node carries a
    2-instance GPU group; the last 2*POOL_NODES nodes form the two
    preemption pools (dc2, dc3), everything else is dc1."""
    from nomad_tpu import mock
    from nomad_tpu.structs.node import compute_node_class
    from nomad_tpu.structs.resources import NodeDevice

    nodes = []
    for i in range(n_nodes):
        n = mock.node()
        n.attributes["rack"] = f"r{i % N_RACKS}"
        if i >= n_nodes - POOL_NODES:
            n.datacenter = "dc3"
        elif i >= n_nodes - 2 * POOL_NODES:
            n.datacenter = "dc2"
        if i % 2 == 0:
            n.node_resources.devices = [NodeDevice(
                vendor="nvidia", type="gpu", name="a100",
                instance_ids=[f"gpu-{i}-0", f"gpu-{i}-1"])]
        n.computed_class = compute_node_class(n)
        nodes.append(n)
    return nodes


class World:
    """What this script knows about the cluster without asking the code
    under test: the nodes it registered, and the usage it recomputes from
    allocations read back over HTTP."""

    def __init__(self, nodes):
        self.index = {n.id: i for i, n in enumerate(nodes)}
        self.dc = np.array([n.datacenter for n in nodes])
        self.rack = np.array([n.attributes["rack"] for n in nodes])
        self.gpu_ids = [{x for d in n.node_resources.devices
                         for x in d.instance_ids} for n in nodes]
        self.cap = np.array(
            [[n.node_resources.cpu.cpu_shares
              - n.reserved_resources.cpu_shares,
              n.node_resources.memory_mb - n.reserved_resources.memory_mb]
             for n in nodes], np.float64)
        self.used = np.zeros_like(self.cap)
        self.gpu_taken = [set() for _ in nodes]

    def add(self, node_id: str, demand) -> int:
        i = self.index.get(node_id)
        if i is None:
            raise SmokeFailure(f"alloc on unknown node {node_id}")
        self.used[i] += demand
        return i

    def check_capacity(self) -> None:
        over = np.flatnonzero((self.used > self.cap).any(axis=1))
        if over.size:
            i = int(over[0])
            raise SmokeFailure(
                f"{over.size} node(s) over capacity, e.g. node #{i}: used "
                f"{self.used[i].tolist()} > capacity {self.cap[i].tolist()}")


# -------------------------------------------------------------------- jobs

def _demand(job) -> dict:
    """{task group: f64[2] (cpu, mem)} of a job this script built."""
    return {tg.name: np.array(
        [sum(t.resources.cpu for t in tg.tasks),
         sum(t.resources.memory_mb for t in tg.tasks)], np.float64)
        for tg in job.task_groups}


def c2m_job(namespace: str = "default"):
    """The C2M-1M job shape: 10 task groups x count 10 (30 cpu / 60 MB)."""
    import bench
    j = bench._c2m_job()
    j.namespace = namespace
    return j


def device_job(count: int = 4):
    import bench
    from nomad_tpu.structs.resources import DeviceRequest
    j = bench._batch_job(count)
    j.task_groups[0].tasks[0].resources.devices = [
        DeviceRequest(name="gpu", count=1)]
    return j


def pool_jobs(dc: str):
    """(filler, preemptor) for one preemption pool: priority-20 batch work
    that fills every pool node (9 x 420 cpu of 4000), then a priority-90
    service job whose 8 allocs fit nowhere without evicting."""
    import bench
    filler = bench._batch_job(POOL_NODES * 9, cpu=420, mem=850)
    filler.priority = 20
    filler.datacenters = [dc]
    high = bench._service_job(POOL_NODES // 2, cpu=420, mem=850,
                              spread=False, priority=90)
    high.datacenters = [dc]
    return filler, high


# --------------------------------------------------------------- the drive

class Driver:
    """Submits jobs and reads results back through ApiClient only."""

    def __init__(self, address: str, world: World):
        from nomad_tpu.api.client import ApiClient
        self.apis = {ns: ApiClient(address, namespace=ns, timeout=60.0)
                     for ns in ("default", NAMESPACE_B)}
        self.api = self.apis["default"]
        self.world = world
        self.placed = 0

    def submit(self, jobs) -> None:
        for j in jobs:
            self.apis[j.namespace].jobs.register(j)

    def _running(self, job):
        return [a for a in self.apis[job.namespace].jobs.allocations(job.id)
                if a["DesiredStatus"] == "run"]

    def _fail_on_failed_eval(self, job) -> None:
        for ev in self.apis[job.namespace].jobs.evaluations(job.id):
            if ev.status == "failed":
                raise SmokeFailure(
                    f"eval {ev.id} of job {job.id} FAILED: "
                    f"{ev.status_description}")

    def wait(self, jobs, timeout: float = WAIT_S) -> dict:
        """Block until every group of every job runs exactly its count;
        returns {job id: [alloc stubs]}.  The first FAILED eval raises at
        once, with its status_description."""
        want = {j.id: {tg.name: tg.count for tg in j.task_groups}
                for j in jobs}
        done: dict = {}
        deadline = time.time() + timeout
        while True:
            for j in jobs:
                if j.id in done:
                    continue
                self._fail_on_failed_eval(j)
                allocs = self._running(j)
                got: dict = {}
                for a in allocs:
                    got[a["TaskGroup"]] = got.get(a["TaskGroup"], 0) + 1
                over = {g: c for g, c in got.items()
                        if c > want[j.id].get(g, 0)}
                if over:
                    raise SmokeFailure(
                        f"job {j.id}: groups over their count: {over} "
                        f"(want {want[j.id]})")
                if got == want[j.id]:
                    done[j.id] = allocs
            if len(done) == len(jobs):
                return done
            if time.time() > deadline:
                missing = {j.id: want[j.id] for j in jobs
                           if j.id not in done}
                raise SmokeFailure(
                    f"timed out after {timeout:.0f}s waiting for "
                    f"{len(missing)} job(s), e.g. {list(missing.items())[:2]}")
            time.sleep(0.05)

    def account(self, job, allocs) -> list:
        """Add a finished job's allocations to the recomputed usage;
        returns the node index of each alloc."""
        demand = _demand(job)
        rows = [self.world.add(a["NodeID"], demand[a["TaskGroup"]])
                for a in allocs]
        self.placed += len(allocs)
        return rows

    def run_and_account(self, jobs) -> dict:
        self.submit(jobs)
        done = self.wait(jobs)
        return {j.id: self.account(j, done[j.id]) for j in jobs}


def check_spread(world: World, job, rows) -> int:
    racks = len({world.rack[i] for i in rows})
    if racks < min(SPREAD_MIN_RACKS, len(rows)):
        raise SmokeFailure(
            f"spread job {job.id}: {len(rows)} allocs cover only {racks} "
            f"of {N_RACKS} racks (bound {SPREAD_MIN_RACKS})")
    return racks


def check_devices(api, world: World, allocs) -> None:
    """Device allocs sit on GPU nodes and hold instance ids nobody else
    on that node holds."""
    for stub in allocs:
        a = api.allocations.info(stub["ID"])
        i = world.index[a.node_id]
        ids = [x for tr in a.allocated_resources.tasks.values()
               for d in tr.devices for x in d["device_ids"]]
        if len(ids) != 1:
            raise SmokeFailure(
                f"device alloc {a.id}: wanted 1 gpu instance, got {ids}")
        if not set(ids) <= world.gpu_ids[i]:
            raise SmokeFailure(
                f"device alloc {a.id} on node #{i}: instances {ids} are "
                f"not that node's ({sorted(world.gpu_ids[i]) or 'no gpu'})")
        if set(ids) & world.gpu_taken[i]:
            raise SmokeFailure(
                f"device alloc {a.id} on node #{i}: instance {ids} is "
                f"already assigned to another alloc")
        world.gpu_taken[i].update(ids)


def binpack_scores(world: World, demand) -> np.ndarray:
    """Reference ScoreFitBinPack (nomad/structs/funcs.go, the 10^x form)
    over the dc1 nodes, normalized to [0, 1]; -inf where the demand does
    not fit.  float64 numpy on the usage this script recomputed."""
    util = world.used + demand
    fits = (util <= world.cap).all(axis=1) & (world.dc == "dc1")
    free = 1.0 - util / world.cap
    total = np.power(10.0, free).sum(axis=1)
    score = np.clip(20.0 - total, 0.0, 18.0) / 18.0
    return np.where(fits, score, -np.inf)


def run_single(drv: Driver, cpu: int, mem: int) -> float:
    """One alloc on the known world: the node the device chose must score
    within BINPACK_TOL of the numpy maximum (a tolerance, not identity:
    the TPU's pow is not bit-equal to the host's, so ties break
    differently).  Returns the gap."""
    import bench
    job = bench._batch_job(1, cpu=cpu, mem=mem)
    scores = binpack_scores(drv.world, _demand(job)["web"])
    rows = drv.run_and_account([job])[job.id]
    gap = float(scores.max() - scores[rows[0]])
    if not gap <= BINPACK_TOL:
        raise SmokeFailure(
            f"single alloc of job {job.id} on node #{rows[0]} scores "
            f"{scores[rows[0]]:.6f}; the best feasible node scores "
            f"{scores.max():.6f} (gap {gap:.2e} > {BINPACK_TOL})")
    return gap


def run_mix(drv: Driver, pool_dc: str, big_count: int) -> dict:
    """One pass of the whole job mix; raises on the first failed check."""
    import bench
    api, world = drv.api, drv.world
    c2m = [c2m_job(ns) for ns in ("default", NAMESPACE_B) * 2]
    big = bench._batch_job(big_count)
    spread = [bench._service_job(SPREAD_COUNT) for _ in range(2)]
    dev = [device_job() for _ in range(2)]
    filler, high = pool_jobs(pool_dc)

    # everything that can run concurrently goes in together, so the
    # engine batches evals of several jobs into one dispatch
    first = c2m + [big] + spread + dev + [filler]
    t0 = time.time()
    drv.submit(first)
    done = drv.wait(first)
    rows = {j.id: drv.account(j, done[j.id]) for j in first}
    racks = [check_spread(world, j, rows[j.id]) for j in spread]
    for j in dev:
        check_devices(api, world, done[j.id])
    world.check_capacity()
    t1 = time.time()

    # the pool is full of priority-20 work now: the priority-90 job only
    # fits by evicting it
    pool = np.flatnonzero(world.dc == pool_dc)
    if not (world.used[pool, 0] + 420 > world.cap[pool, 0]).all():
        raise SmokeFailure(f"pool {pool_dc} is not full before preemption: "
                           f"{world.used[pool].tolist()}")
    drv.submit([high])
    high_allocs = drv.wait([high])[high.id]
    after = api.jobs.allocations(filler.id)
    evicted = [a for a in after if a["DesiredStatus"] == "evict"]
    if len(evicted) < len(high_allocs):
        raise SmokeFailure(
            f"{len(high_allocs)} priority-90 allocs placed on a full pool "
            f"but only {len(evicted)} priority-20 allocs were evicted")
    fd = _demand(filler)["web"]
    for a in evicted:
        world.used[world.index[a["NodeID"]]] -= fd
    high_rows = drv.account(high, high_allocs)
    if not set(high_rows) <= set(pool.tolist()):
        raise SmokeFailure(f"priority-90 allocs left pool {pool_dc}")
    world.check_capacity()
    t2 = time.time()

    gaps = [run_single(drv, 500, 1000), run_single(drv, 250, 4000)]
    world.check_capacity()
    # host seconds per phase, readback and checks included (a CPU figure
    # on a CPU run; never a device metric)
    return {"spread_racks": racks, "evicted": len(evicted),
            "binpack_gap": max(gaps),
            "phase_s": {"mix": round(t1 - t0, 2),
                        "preempt": round(t2 - t1, 2),
                        "single": round(time.time() - t2, 2)}}


def warm(server, cache: _CacheEvents) -> dict:
    """engine.warmup for every shape class the mix can dispatch, as
    bench._warm_engine does.  Which of them a run reaches depends on
    queue timing, so all are warmed: the bulk variant grid, and one scan
    class per job shape of the mix, because any job can reach the scan
    path.  A group with a single slot left (the retry after a partial
    commit) is below the scheduler's bulk threshold and scans with all of
    its job's groups compiled.  The classes: spread services (one group,
    51 spread values, up to 100 slots), one-group jobs without spread
    (device jobs, the preemptor, single placements, the remainder of a
    batch job; 16 slots at most), and the C2M job (ten groups, at most
    one slot each)."""
    import bench
    h0, m0 = cache.hits, cache.misses
    t0 = time.time()
    bench._warm_engine(server, scan_job=bench._service_job(SPREAD_COUNT))
    bench._warm_engine(server, scan_job=device_job())
    bench._warm_engine(server, scan_job=c2m_job(), bulk_job=c2m_job())
    hits, misses = cache.hits - h0, cache.misses - m0
    state = "cold" if hits == 0 else \
        ("cached" if misses == 0 else "partly cached")
    return {"warmup_s": round(time.time() - t0, 2), "state": state,
            "cache_hits": hits, "cache_misses": misses}


def check_gate(steady: dict, before: dict, after: dict, n_devices: int,
               mesh) -> dict:
    """The steady-state window's verdict, from bench._SteadyGate's report
    and the engine's own counters."""
    d = {k: after.get(k, 0) - before.get(k, 0)
         for k in ("bulk_groups", "bulk_parts", "donated_carries",
                   "sharded_evals", "bulk_evals", "batched_evals",
                   "single_evals", "overlap_chained")}
    d["wave_lanes"] = after.get("wave_lanes", 0)
    # compiles after warmup and world re-uploads are violations the gate
    # reports itself
    problems = list(steady.get("violations", ()))
    if steady.get("transfer_guard") != "disallow":
        problems.append("the steady-state gate did not run")
    if d["donated_carries"] <= 0:
        problems.append("no donated carry in the window")
    if d["bulk_groups"] <= 0 or d["bulk_parts"] != d["bulk_groups"]:
        problems.append(f"bulk_parts {d['bulk_parts']} != bulk_groups "
                        f"{d['bulk_groups']} (one dispatch per wave group)")
    if n_devices > 1:
        if mesh is None:
            problems.append(f"{n_devices} devices visible but the engine "
                            f"built no serving mesh for this world")
        if d["sharded_evals"] <= 0 or d["wave_lanes"] <= 0:
            problems.append(
                f"{n_devices} devices visible but the mesh did not engage "
                f"(sharded_evals {d['sharded_evals']}, wave_lanes "
                f"{d['wave_lanes']})")
    if problems:
        raise SmokeFailure("steady-state gate: " + "; ".join(problems))
    return d


def run(n_nodes: int = N_NODES, big_count: int = BIG_COUNT) -> dict:
    """Drive the spine once on whatever platform jax is on and check what
    comes out; returns the summary.  `main` refuses anything but a TPU
    before calling this; the tier-1 test calls it on the CPU at 2,048
    nodes, with a big batch job small enough to leave every rack of so
    small a cluster some room (spread cannot cover a rack that is full)."""
    import jax

    import bench
    from nomad_tpu import native
    from nomad_tpu.agent.agent import Agent, AgentConfig
    from nomad_tpu.parallel.engine import get_engine

    cache = _CacheEvents()
    lib = native._load()
    if not native.NATIVE_AVAILABLE:
        raise SmokeFailure("native library did not load")
    say(f"native library: {lib._name}")
    faults0 = native.breaker.stats["failures"]

    agent = Agent(AgentConfig(http_port=0, num_schedulers=8,
                              heartbeat_ttl=3600.0))
    agent.start()
    try:
        server = agent.server
        eng = get_engine()

        t0 = time.time()
        nodes = make_nodes(n_nodes)
        for n in nodes:
            server.register_node(n)
        cm = server.store.matrix
        world = World(nodes)
        say(f"world: {n_nodes} nodes registered in {time.time() - t0:.1f}s, "
            f"{cm.n_rows} padded rows, {N_RACKS} racks, "
            f"{sum(1 for g in world.gpu_ids if g)} gpu nodes")
        drv = Driver(agent.http_addr, world)
        api = drv.api
        api.namespaces.register(NAMESPACE_B)
        got = len(api.nodes.list())
        if got != n_nodes:
            raise SmokeFailure(f"/v1/nodes lists {got} of {n_nodes} nodes")

        cfg = api.operator.scheduler_get_configuration()
        cfg.preemption_config.service_scheduler_enabled = True
        cfg.preemption_config.batch_scheduler_enabled = True
        api.operator.scheduler_set_configuration(cfg)

        mesh = eng._mesh_for(cm.n_rows)
        mesh_shape = dict(mesh.shape) if mesh is not None else None
        cache_dir = jax.config.jax_compilation_cache_dir
        say(f"mesh: {mesh_shape}; compile cache: {cache_dir}")
        warmup = warm(server, cache)
        say(f"warmup: {warmup}")

        t0 = time.time()
        first = run_mix(drv, "dc2", big_count)
        say(f"warm pass: {drv.placed} allocs in {time.time() - t0:.1f}s "
            f"{first}")

        before = dict(eng.stats)
        placed0, t0 = drv.placed, time.time()
        with bench._SteadyGate("chip_smoke"):
            second = run_mix(drv, "dc3", big_count)
        steady_s = time.time() - t0
        say(f"steady pass: {drv.placed - placed0} allocs in "
            f"{steady_s:.1f}s {second}")
        steady = bench._STEADY_STATE.get("chip_smoke", {})
        counters = check_gate(steady, before, dict(eng.stats),
                              len(jax.devices()), mesh)

        failed = [e for a in drv.apis.values()
                  for e in a.evaluations.list() if e.status == "failed"]
        if failed:
            raise SmokeFailure(
                f"{len(failed)} FAILED eval(s), e.g. {failed[0].id}: "
                f"{failed[0].status_description}")
        # a native fault below the breaker's threshold ran its numpy
        # twin without opening it: that is a fallback too
        faults = native.breaker.stats["failures"] - faults0
        if native.breaker.open or faults:
            raise SmokeFailure(
                f"{faults} native call(s) faulted during the run "
                f"(breaker open: {native.breaker.open})")
        say(f"engine stats: {eng.stats}")
        say(f"world stats: {eng.world_stats()}")
        return {
            "nodes": n_nodes, "padded_rows": int(cm.n_rows),
            "allocs": drv.placed, "mesh": mesh_shape,
            "cache_dir": cache_dir, "warmup": warmup,
            "warm_pass": first, "steady_pass": second,
            "steady_pass_s": round(steady_s, 2),
            "steady_gate": {
                "transfer_guard": steady.get("transfer_guard"),
                "compile_events": steady.get("compile_events"),
                "steady_reuploads": steady.get("steady_reuploads"),
                **counters},
        }
    finally:
        agent.stop()


def main() -> int:
    # the server logs a failed eval's traceback and the native layer its
    # faults; without a handler on the root logger they reach nobody
    logging.basicConfig(level=logging.WARNING, stream=sys.stderr)
    t0 = time.time()
    try:
        dev = device_check()
        say(f"platform={dev['platform']} device_kind={dev['device_kind']} "
            f"device_count={dev['device_count']}")
        summary = run()
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    except Exception:                   # noqa: BLE001 — reported, exits 1
        print(f"chip_smoke: FAILED:\n{traceback.format_exc()}",
              file=sys.stderr, flush=True)
        return 1
    summary = {"device": dev, **summary,
               "wall_s": round(time.time() - t0, 1), "claim": None}
    say(json.dumps(summary))
    say(json.dumps({"ok": True, "device": {
        "platform": dev["platform"], "kind": dev["device_kind"],
        "count": dev["device_count"]}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
