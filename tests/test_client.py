"""Client (node agent) tests — fingerprint, drivers, task/alloc runners,
restart policies, state recovery, and the full server+client data plane
(reference analogs: client/client_test.go, taskrunner tests,
drivers/mock/driver_test.go)."""
import os
import time

import pytest

from nomad_tpu import mock
from nomad_tpu.client import Client, ClientConfig
from nomad_tpu.client.allocdir import AllocDir
from nomad_tpu.client.drivers import (
    DriverRegistry,
    ExitResult,
    MockDriver,
    RawExecDriver,
    TaskHandle,
)
from nomad_tpu.client.fingerprint import fingerprint_node
from nomad_tpu.client.state import ClientStateDB
from nomad_tpu.client.taskenv import build_task_env, interpolate
from nomad_tpu.client.taskrunner import RestartTracker
from nomad_tpu.core.server import Server, ServerConfig
from nomad_tpu.structs import Job, Node, RestartPolicy, Task, TaskGroup


def _wait(cond, timeout=10.0, interval=0.05):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if cond():
            return True
        time.sleep(interval)
    return False


# ------------------------------------------------------------ units

def test_fingerprint_node():
    n = Node(id="n1", name="test")
    fingerprint_node(n, {"raw_exec": {"detected": True, "healthy": True}})
    assert n.attributes["kernel.name"] == "linux"
    assert int(n.attributes["cpu.numcores"]) >= 1
    assert n.node_resources.memory_mb > 0
    assert n.node_resources.cpu.cpu_shares > 0
    assert n.attributes["driver.raw_exec"] == "1"


def test_taskenv_interpolation():
    alloc = mock.alloc()
    alloc.name = "web.fe[2]"
    task = alloc.job.task_groups[0].tasks[0]
    task.env = {"LISTEN": "${NOMAD_ALLOC_INDEX}",
                "WHO": "${meta.owner}"}
    task.meta = {"owner": "ops"}
    node = mock.node()
    env = build_task_env(alloc, task, node, "/tmp/x")
    assert env["NOMAD_ALLOC_INDEX"] == "2"
    assert env["NOMAD_TASK_NAME"] == task.name
    assert env["LISTEN"] == "2"
    assert env["WHO"] == "ops"
    assert interpolate("${attr.kernel.name}", env, node) == "linux"
    assert interpolate("${unknown.thing}", env, node) == "${unknown.thing}"


def test_restart_tracker_fail_mode():
    rt = RestartTracker(RestartPolicy(attempts=2, interval_s=300.0,
                                      delay_s=1.0, mode="fail"))
    assert rt.next(ExitResult(exit_code=1), now=100.0) == ("restart", 1.0)
    assert rt.next(ExitResult(exit_code=1), now=101.0) == ("restart", 1.0)
    assert rt.next(ExitResult(exit_code=1), now=102.0) == ("fail", None)
    # new window resets the budget
    v, _ = rt.next(ExitResult(exit_code=1), now=500.0)
    assert v == "restart"


def test_restart_tracker_delay_mode():
    rt = RestartTracker(RestartPolicy(attempts=1, interval_s=100.0,
                                      delay_s=5.0, mode="delay"))
    assert rt.next(ExitResult(exit_code=1), now=0.0) == ("restart", 5.0)
    verdict, delay = rt.next(ExitResult(exit_code=1), now=10.0)
    assert verdict == "restart"
    assert delay >= 90.0           # waits out the window


def test_mock_driver_run_for():
    drv = MockDriver()
    task = Task(name="t", driver="mock_driver",
                config={"run_for": 0.1, "exit_code": 0})
    h = TaskHandle(driver="mock_driver", task_name="t")
    drv.start_task(h, task, {}, "/tmp")
    res = drv.wait_task(h)
    assert res.successful()


def test_mock_driver_exit_code_and_kill():
    drv = MockDriver()
    task = Task(name="t", config={"run_for": 0.05, "exit_code": 3})
    h = TaskHandle()
    drv.start_task(h, task, {}, "/tmp")
    assert drv.wait_task(h).exit_code == 3
    # long-running task killed
    task2 = Task(name="t2", config={"run_for": 60})
    h2 = TaskHandle()
    drv.start_task(h2, task2, {}, "/tmp")
    drv.stop_task(h2)
    res = drv.wait_task(h2)
    assert res.signal == 9


def test_raw_exec_driver(tmp_path):
    drv = RawExecDriver()
    ad = AllocDir(str(tmp_path), "a1")
    ad.build()
    task_dir = ad.build_task_dir("sh")
    task = Task(name="sh", driver="raw_exec",
                config={"command": "/bin/sh",
                        "args": ["-c", "echo hello-$FOO; exit 0"]})
    h = TaskHandle()
    drv.start_task(h, task, {"FOO": "bar"}, task_dir)
    res = drv.wait_task(h)
    assert res.successful()
    # the detached logmon pump drains the pipe asynchronously
    path = os.path.join(ad.logs_dir(), "sh.stdout")
    deadline = time.time() + 5.0
    out = ""
    while time.time() < deadline and "hello-bar" not in out:
        out = open(path).read()
        time.sleep(0.05)
    assert "hello-bar" in out


def test_raw_exec_stop(tmp_path):
    drv = RawExecDriver()
    ad = AllocDir(str(tmp_path), "a2")
    ad.build()
    task_dir = ad.build_task_dir("sleeper")
    task = Task(name="sleeper", driver="raw_exec",
                config={"command": "/bin/sleep", "args": ["60"]})
    h = TaskHandle()
    drv.start_task(h, task, {}, task_dir)
    t0 = time.time()
    drv.stop_task(h, timeout_s=2.0)
    res = drv.wait_task(h)
    assert time.time() - t0 < 5.0
    assert not res.successful()


def test_client_state_db(tmp_path):
    db = ClientStateDB(str(tmp_path / "state.db"))
    db.put_alloc("a1", {"job_id": "j"})
    h = TaskHandle(driver="raw_exec", task_name="t", pid=1234)
    db.put_task_state("a1", "t", "running", False, 2, h)
    assert db.get_allocs()["a1"]["job_id"] == "j"
    st, failed, restarts, got = db.get_task_states("a1")["t"]
    assert (st, failed, restarts) == ("running", False, 2)
    assert got.pid == 1234
    db.delete_alloc("a1")
    assert db.get_allocs() == {}
    db.close()


# ------------------------------------------------------------ E2E

@pytest.fixture
def cluster(tmp_path):
    """Dev server + one real client wired over the in-proc RPC."""
    server = Server(ServerConfig(num_schedulers=2, heartbeat_ttl=30.0))
    server.start()
    client = Client(
        ClientConfig(node_name="c1", data_dir=str(tmp_path / "client"),
                     watch_interval=0.05),
        rpc=server.endpoints.handle)
    client.start()
    yield server, client
    client.stop()
    server.stop()


def _batch_job(command="/bin/true", args=None, **cfg):
    job = Job(id=f"batch-{time.time_ns()}", name="batch", type="batch",
              task_groups=[TaskGroup(name="g", count=1, tasks=[
                  Task(name="t", driver="raw_exec",
                       config={"command": command,
                               "args": args or [], **cfg})])])
    job.canonicalize()
    return job


def test_e2e_batch_job_completes(cluster, tmp_path):
    server, client = cluster
    out_file = tmp_path / "proof.txt"
    job = _batch_job("/bin/sh", ["-c", f"echo done > {out_file}"])
    server.register_job(job)
    assert _wait(lambda: [
        a for a in server.store.allocs_by_job("default", job.id)
        if a.client_status == "complete"], 15.0), \
        [(a.client_status, a.task_states) for a in
         server.store.allocs_by_job("default", job.id)]
    assert out_file.read_text().strip() == "done"
    allocs = server.store.allocs_by_job("default", job.id)
    ts = allocs[0].task_states["t"]
    assert ts.state == "dead" and not ts.failed
    assert any(e["type"] == "Started" for e in ts.events)


def test_e2e_service_job_runs_and_stops(cluster):
    server, client = cluster
    job = Job(id="svc-e2e", name="svc", type="service",
              task_groups=[TaskGroup(name="g", count=2, tasks=[
                  Task(name="t", driver="mock_driver",
                       config={"run_for": 0})])])
    job.canonicalize()
    server.register_job(job)
    assert _wait(lambda: len([
        a for a in server.store.allocs_by_job("default", job.id)
        if a.client_status == "running"]) == 2, 15.0)
    # job stop: clients should kill tasks, allocs go complete
    server.deregister_job("default", job.id)
    assert _wait(lambda: all(
        a.client_terminal_status()
        for a in server.store.allocs_by_job("default", job.id)), 15.0), \
        [(a.desired_status, a.client_status)
         for a in server.store.allocs_by_job("default", job.id)]


def test_e2e_the_store_keeps_no_live_task_state(cluster):
    """In process the server stores the objects the client hands it, and
    a plan's copy of a record shares the record's parts: so an update
    carries the task states as they read, not the runners' own objects,
    which the runners go on writing."""
    server, client = cluster
    job = Job(id="svc-alias", name="svc", type="service",
              task_groups=[TaskGroup(name="g", count=1, tasks=[
                  Task(name="t", driver="mock_driver",
                       config={"run_for": 0})])])
    job.canonicalize()
    server.register_job(job)
    assert _wait(lambda: [
        a for a in server.store.allocs_by_job("default", job.id)
        if a.client_status == "running"
        and a.task_states["t"].state == "running"], 15.0)
    (held,) = server.store.allocs_by_job("default", job.id)
    ts = held.task_states["t"]
    events = list(ts.events)
    runner = client.alloc_runners[held.id].task_runners["t"]
    assert ts is not runner.state and ts.events is not runner.state.events
    server.deregister_job("default", job.id)
    assert _wait(lambda: all(
        a.client_terminal_status()
        for a in server.store.allocs_by_job("default", job.id)), 15.0)
    assert runner.state.state == "dead"
    (now,) = server.store.allocs_by_job("default", job.id)
    assert now.task_states["t"].state == "dead"
    # what a reader held from before the stop reads as it did
    assert held.client_status == "running"
    assert ts.state == "running" and ts.events == events


def test_e2e_failed_task_restarts_then_reschedules(cluster):
    server, client = cluster
    job = Job(id="fail-e2e", name="f", type="batch",
              task_groups=[TaskGroup(
                  name="g", count=1,
                  restart_policy=RestartPolicy(attempts=1, interval_s=300.0,
                                               delay_s=0.05, mode="fail"),
                  tasks=[Task(name="t", driver="raw_exec",
                              config={"command": "/bin/false"})])])
    job.canonicalize()
    job.task_groups[0].reschedule_policy.attempts = 0
    job.task_groups[0].reschedule_policy.unlimited = False
    server.register_job(job)
    assert _wait(lambda: [
        a for a in server.store.allocs_by_job("default", job.id)
        if a.client_status == "failed"], 15.0)
    a = [x for x in server.store.allocs_by_job("default", job.id)
         if x.client_status == "failed"][0]
    assert a.task_states["t"].restarts == 1
    assert a.task_states["t"].failed


def test_e2e_node_fingerprint_visible(cluster):
    server, client = cluster
    assert _wait(lambda: server.store.node_by_id(client.node.id)
                 is not None, 5.0)
    n = server.store.node_by_id(client.node.id)
    assert n.status == "ready"
    assert n.attributes.get("driver.raw_exec") == "1"


def test_client_restart_recovery(tmp_path):
    """A client restart recovers a still-running raw_exec task from the
    state DB (reference: persisted task handles + RecoverTask)."""
    server = Server(ServerConfig(num_schedulers=2, heartbeat_ttl=30.0))
    server.start()
    data_dir = str(tmp_path / "client")
    client = Client(ClientConfig(node_name="c1", data_dir=data_dir,
                                 watch_interval=0.05),
                    rpc=server.endpoints.handle)
    client.start()
    try:
        job = _batch_job("/bin/sleep", ["30"])
        server.register_job(job)
        assert _wait(lambda: [
            a for a in server.store.allocs_by_job("default", job.id)
            if a.client_status == "running"], 15.0)
        # hard-stop the client without killing tasks (simulated crash):
        client._stop.set()
        time.sleep(0.3)
        pid = next(iter(client.alloc_runners.values())) \
            .task_runners["t"].handle.pid
        client.state_db.close()

        c2 = Client(ClientConfig(node_name="c1", data_dir=data_dir,
                                 watch_interval=0.05),
                    rpc=server.endpoints.handle)
        c2.start()
        try:
            assert c2.num_allocs() == 1
            ar = next(iter(c2.alloc_runners.values()))
            assert _wait(lambda: ar.client_status == "running", 5.0)
            tr = ar.task_runners["t"]
            assert tr.handle.pid == pid
            os.kill(pid, 15)       # the recovered task exiting is seen
            assert _wait(lambda: tr.state.state == "dead", 10.0)
        finally:
            c2.stop()
    finally:
        server.stop()
        import signal as _sig
        try:
            os.kill(pid, _sig.SIGKILL)
        except ProcessLookupError:
            pass


def test_stop_after_client_disconnect(tmp_path):
    """heartbeatstop (client/heartbeatstop.go:158): a disconnected client
    stops allocs whose group sets stop_after_client_disconnect once the
    deadline passes the last successful heartbeat."""
    server = Server(ServerConfig(num_schedulers=2, heartbeat_ttl=2.0))
    server.start()
    rpc_ok = {"v": True}

    def gated_rpc(method, args):
        if not rpc_ok["v"]:
            raise ConnectionError("network partitioned")
        return server.endpoints.handle(method, args)

    client = Client(
        ClientConfig(node_name="c-dc", data_dir=str(tmp_path / "c"),
                     watch_interval=0.05),
        rpc=gated_rpc)
    client.start()
    try:
        job = Job(id="svc-dc", name="svc", type="service",
                  task_groups=[TaskGroup(
                      name="g", count=1,
                      stop_after_client_disconnect_s=1.0,
                      tasks=[Task(name="t", driver="mock_driver",
                                  config={"run_for": 0})])])
        job.canonicalize()
        server.register_job(job)
        assert _wait(lambda: any(
            ar.client_status == "running"
            for ar in client.alloc_runners.values()), 15.0)

        # partition the client from the server
        rpc_ok["v"] = False
        assert _wait(lambda: any(
            ar.client_status == "lost"
            for ar in client.alloc_runners.values()), 15.0), \
            [(ar.client_status, ar.client_description)
             for ar in client.alloc_runners.values()]
        ar = next(iter(client.alloc_runners.values()))
        assert "client disconnect" in ar.client_description
        assert all(tr.state.state == "dead"
                   for tr in ar.task_runners.values())
    finally:
        rpc_ok["v"] = True
        client.stop()
        server.stop()


# ------------------------------------------------------------ exec driver

def _exec_task(command, args=None, cpu=100, mem=64):
    from nomad_tpu.structs.resources import Resources
    return Task(name="e", driver="exec",
                config={"command": command, "args": args or []},
                resources=Resources(cpu=cpu, memory_mb=mem))


def test_exec_driver_runs_in_cgroup(tmp_path):
    from nomad_tpu.client.drivers import ExecDriver, TaskHandle

    drv = ExecDriver()
    task_dir = tmp_path / "task"
    task_dir.mkdir()
    h = TaskHandle(driver="exec", task_name="e")
    task = _exec_task("/bin/sh", ["-c", "cat /proc/self/cgroup > out.txt"])
    drv.start_task(h, task, {}, str(task_dir))
    res = drv.wait_task(h)
    assert res.exit_code == 0
    cg = (task_dir / "out.txt").read_text()
    if os.access("/sys/fs/cgroup/memory", os.W_OK):
        assert "nomad_tpu" in cg, cg
    stats = drv.inspect_task(h)
    assert stats["cgroup"] == os.access("/sys/fs/cgroup/memory", os.W_OK)
    drv.destroy_task(h)


def test_exec_driver_stop_and_signal(tmp_path):
    from nomad_tpu.client.drivers import ExecDriver, TaskHandle

    drv = ExecDriver()
    task_dir = tmp_path / "task"
    task_dir.mkdir()
    h = TaskHandle(driver="exec", task_name="e")
    drv.start_task(h, _exec_task("/bin/sleep", ["300"]), {}, str(task_dir))
    t0 = time.time()
    done = {}

    def waiter():
        done["res"] = drv.wait_task(h)

    import threading
    t = threading.Thread(target=waiter, daemon=True)
    t.start()
    time.sleep(0.2)
    drv.stop_task(h, timeout_s=2.0)
    t.join(10.0)
    assert "res" in done and done["res"].signal in (15, 9)
    assert time.time() - t0 < 10
    drv.destroy_task(h)


def test_exec_driver_reattach_after_driver_restart(tmp_path):
    """The executor process outlives the driver object: a brand-new
    driver instance recovers the task from the handle's socket path and
    still observes its exit (go-plugin reattach semantics)."""
    from nomad_tpu.client.drivers import ExecDriver, TaskHandle

    drv1 = ExecDriver()
    task_dir = tmp_path / "task"
    task_dir.mkdir()
    h = TaskHandle(driver="exec", task_name="e")
    proof = task_dir / "done.txt"
    drv1.start_task(
        h, _exec_task("/bin/sh", ["-c", f"sleep 0.5; echo ok > {proof}"]),
        {}, str(task_dir))
    del drv1                          # "client restart"

    drv2 = ExecDriver()
    assert drv2.recover_task(h), "reattach over the socket failed"
    res = drv2.wait_task(h)
    assert res.exit_code == 0
    assert proof.read_text().strip() == "ok"
    drv2.destroy_task(h)
    assert not drv2.recover_task(h)
