"""Device plugin health stream (VERDICT r3 item 8): the client's device
fingerprint loop updates per-instance health, unhealthy instances carry no
scheduling capacity, and allocations holding a dead instance reschedule
onto healthy hardware."""
import time

import pytest

from nomad_tpu import mock
from nomad_tpu.core.server import Server, ServerConfig
from nomad_tpu.structs.resources import DeviceRequest, NodeDevice


def _wait(cond, timeout=30.0):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if cond():
            return True
        time.sleep(0.05)
    return False


def test_unhealthy_instances_excluded_from_capacity_and_assignment():
    from nomad_tpu.encode import ClusterMatrix
    from nomad_tpu.scheduler.devices import assign_device_instances

    n = mock.node()
    n.node_resources.devices = [NodeDevice(
        vendor="nvidia", type="gpu", name="a100",
        instance_ids=["g0", "g1"], unhealthy_ids=["g0"])]
    cm = ClusterMatrix()
    row = cm.upsert_node(n)
    assert int(cm.device_caps["nvidia/gpu/a100"][row]) == 1

    got, _weight = assign_device_instances(
        n, [], DeviceRequest(name="gpu", count=1))
    assert got["device_ids"] == ["g1"]
    assert assign_device_instances(
        n, [], DeviceRequest(name="gpu", count=2)) == (None, 0.0)


def test_device_death_reschedules_allocs():
    """A re-registration marking an instance unhealthy migrates the alloc
    holding it; the replacement lands on a node with healthy devices."""
    s = Server(ServerConfig(num_schedulers=2, heartbeat_ttl=3600.0,
                            gc_interval=3600.0))
    s.start()
    try:
        nodes = []
        for i in range(2):
            n = mock.node()
            n.node_resources.devices = [NodeDevice(
                vendor="nvidia", type="gpu", name="a100",
                instance_ids=[f"n{i}-g0"])]
            nodes.append(n)
            s.register_node(n)

        j = mock.batch_job()
        tg = j.task_groups[0]
        tg.count = 1
        tg.tasks[0].resources.devices = [DeviceRequest(name="gpu", count=1)]
        s.register_job(j)

        def live():
            return [a for a in s.store.allocs_by_job("default", j.id)
                    if not a.terminal_status()
                    and not a.desired_transition.should_force_reschedule()]
        assert _wait(lambda: len(live()) == 1)
        a0 = live()[0]
        victim = next(n for n in nodes if n.id == a0.node_id)
        survivor = next(n for n in nodes if n.id != a0.node_id)

        # the device fingerprint now reports the held instance unhealthy
        victim.node_resources.devices[0].unhealthy_ids = list(
            victim.node_resources.devices[0].instance_ids)
        s.register_node(victim)

        def rescheduled():
            allocs = live()
            return (len(allocs) == 1 and allocs[0].id != a0.id
                    and allocs[0].node_id == survivor.id)
        assert _wait(rescheduled, timeout=30), \
            [(a.id[:8], a.node_id[:8], a.desired_status)
             for a in s.store.allocs_by_job("default", j.id)]
    finally:
        s.stop()


def test_client_device_monitor_pushes_health():
    """The client's fingerprint loop re-registers the node when device
    health changes."""
    from nomad_tpu.client.client import Client, ClientConfig

    s = Server(ServerConfig(num_schedulers=1, heartbeat_ttl=3600.0,
                            gc_interval=3600.0))
    s.start()
    devices = [NodeDevice(vendor="amd", type="gpu", name="mi300",
                          instance_ids=["d0", "d1"])]
    c = Client(ClientConfig(node_name="dev-client",
                            device_fingerprint=lambda: devices,
                            device_poll_interval=0.1),
               rpc=s.rpc_leader)
    c.start()
    try:
        def caps():
            node = s.store.node_by_id(c.node.id)
            if node is None or not node.node_resources.devices:
                return None
            return len(node.node_resources.devices[0].healthy_ids())
        assert _wait(lambda: caps() == 2)
        devices[0] = NodeDevice(vendor="amd", type="gpu", name="mi300",
                                instance_ids=["d0", "d1"],
                                unhealthy_ids=["d1"])
        assert _wait(lambda: caps() == 1, timeout=15)
    finally:
        c.stop()
        s.stop()
