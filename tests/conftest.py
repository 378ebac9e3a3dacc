"""Test configuration: force an 8-device virtual CPU platform so
multi-chip sharding paths are exercised without TPU hardware (matches the
driver's dryrun_multichip environment).

The environment variables below are set before jax is imported, so they
decide the platform; tests never reach an accelerator.
"""
import os
import sys

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"
# the persistent compile cache is for serving: a CPU test run must not
# leave (or load) XLA:CPU AOT executables that another host, with other
# CPU features, would pick up from the same directory
os.environ.setdefault("NOMAD_TPU_JAX_CACHE", "0")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import pytest


@pytest.fixture(scope="session", autouse=True)
def _lock_order_guard(request):
    """NOMAD_TPU_LOCK_ORDER=1 wraps every lock allocated during the run
    and fails the session if the acquisition graph has a cycle (latent
    deadlock).  Off by default: the wrapper adds per-acquire overhead.

    The observed acquisition graph is dumped (LockOrderRecorder.dump,
    the corpus format the static wait-graph checker merges via
    `python -m nomad_tpu.analysis --lock-corpus`) to
    NOMAD_TPU_LOCK_ORDER_DUMP when set, and always on a failing
    session so CI failures keep the interleaving evidence."""
    if os.environ.get("NOMAD_TPU_LOCK_ORDER", "0") in ("", "0"):
        yield
        return
    from nomad_tpu.analysis.lock_order import LockOrderRecorder
    rec = LockOrderRecorder().install()
    yield
    rec.uninstall()
    cycles = rec.cycles()
    dump = os.environ.get("NOMAD_TPU_LOCK_ORDER_DUMP", "")
    if not dump and (cycles or request.session.testsfailed):
        dump = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "..", "lock-order-corpus.json")
    if dump:
        rec.dump(dump)
    assert not cycles, "\n" + rec.render_cycles()


@pytest.fixture(scope="session", autouse=True)
def _race_guard():
    """NOMAD_TPU_RACE=1 installs the happens-before detector for the
    whole run: every lock is clock-carrying, every race.read/race.write
    hook in production code is checked, and the session fails on any
    unordered access pair or lock-order cycle.  Off by default (vector
    clocks cost more than the plain lock-order recorder)."""
    if os.environ.get("NOMAD_TPU_RACE", "0") in ("", "0"):
        yield
        return
    from nomad_tpu.analysis import race as race_mod
    from nomad_tpu.analysis.race import RaceDetector
    det = RaceDetector().install()
    prev, race_mod.active = race_mod.active, det
    yield
    race_mod.active = prev
    det.uninstall()
    assert det.races == [], "\n" + det.render_races()
    assert det.cycles() == [], "\n" + det.render_cycles()


@pytest.fixture(scope="session")
def spine_metrics():
    """One dev agent, no tracer installed: a service job (scan path) and
    a batch job (bulk path) registered over HTTP and placed, then one
    blocking query.  Returns what `/v1/metrics` served afterwards, as
    {"samples": {name: summary}, "prometheus": text, "processed": evals
    the workers acked}; "later" holds the samples after a second stage (a
    system job that evicts, `jobs.allocations`, three collections)."""
    import time
    import urllib.request

    from nomad_tpu import mock, tracing
    from nomad_tpu.agent import Agent, AgentConfig
    from nomad_tpu.api import ApiClient

    assert tracing.active is None
    a = Agent(AgentConfig(http_port=0, num_schedulers=2,
                          heartbeat_ttl=60.0))
    a.start()
    try:
        for _ in range(3):
            a.server.register_node(mock.node())
        api = ApiClient(a.http_addr)
        before = {s["Name"]: s["count"]
                  for s in api.system.metrics()["Samples"]}
        api.jobs.register(mock.job())
        batch = mock.batch_job()
        batch.task_groups[0].count = 4
        api.jobs.register(batch)
        assert a.server.wait_for_idle(30.0)
        api.get("/v1/jobs?index=1&wait=10ms")
        time.sleep(0.2)     # the commit thread's spans close after idle
        got = {
            "samples": {s["Name"]: s
                        for s in api.system.metrics()["Samples"]},
            "before": before,
            "prometheus": urllib.request.urlopen(
                a.http_addr + "/v1/metrics?format=prometheus",
                timeout=10).read().decode(),
            "processed": sum(w.stats["processed"]
                             for w in a.server.workers),
        }
        # a second stage, after the counts above are taken: a system job
        # that has to evict on the nodes the two jobs filled, the read a
        # client confirms a job by, and one collection of each generation
        import gc
        fleet = mock.system_job()
        fleet.task_groups[0].tasks[0].resources.cpu = 3000
        api.jobs.register(fleet)
        assert a.server.wait_for_idle(30.0)
        got["fleet_allocs"] = api.jobs.allocations(fleet.id)
        got["evicted"] = sum(x["DesiredStatus"] == "evict"
                             for x in api.get("/v1/allocations"))
        for generation in (0, 1, 2):
            gc.collect(generation)
        time.sleep(0.2)
        api.jobs.allocations(fleet.id)      # a span closes: pauses counted
        got["later"] = {s["Name"]: s
                        for s in api.system.metrics()["Samples"]}
    finally:
        # stopped before the first test reads it: no thread of this
        # agent outlives the fixture's set-up
        a.stop()
    return got
