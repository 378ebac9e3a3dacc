"""Client core (reference: client/client.go:169 — node registration
(:1602), heartbeating with TTL jitter, blocking-query allocation watching
(:2056), runAllocs diff (:2286), state restore, and alloc GC (gc.go)).

The client speaks to servers through an `rpc(method, args)` callable —
in-process for the dev agent, or a TCP transport client in a cluster —
the same boundary as the reference's msgpack-RPC.
"""
from __future__ import annotations

import logging
import os
import tempfile
import threading
import time
import uuid
from dataclasses import dataclass, field, replace
from typing import Callable, Dict, List, Optional

from nomad_tpu.client.allocrunner import AllocRunner
from nomad_tpu.client.drivers import DriverRegistry
from nomad_tpu.client.fingerprint import fingerprint_node
from nomad_tpu.client.state import ClientStateDB
from nomad_tpu.structs import Node
from nomad_tpu.structs.alloc import AllocClientStatus, AllocDesiredStatus
from nomad_tpu.structs.node import NodeStatus

log = logging.getLogger(__name__)


@dataclass
class ClientConfig:
    node_name: str = "client-1"
    datacenter: str = "dc1"
    node_class: str = ""
    region: str = "global"
    data_dir: str = ""                     # default: tempdir
    drivers: List[str] = field(
        default_factory=lambda: ["mock_driver", "raw_exec", "exec", "mock"])
    meta: Dict[str, str] = field(default_factory=dict)
    max_allocs_gc: int = 50                # GC threshold (gc.go)
    watch_interval: float = 0.2
    # device plugin fingerprint stream (reference plugins/device/
    # device.go:25-37): a callable returning the CURRENT [NodeDevice]
    # list (with per-instance health); polled periodically, node
    # re-registers on change so the servers see device health updates
    device_fingerprint: Optional[Callable[[], list]] = None
    device_poll_interval: float = 1.0
    # device plugin specs (client/devicemanager): each dict builds a
    # FakeDevicePlugin (vendor/type/name + count|instance_ids) that the
    # node fingerprints and the client reserves instances from
    device_plugins: List[dict] = field(default_factory=list)


class Client:
    def __init__(self, config: ClientConfig,
                 rpc: Callable[[str, dict], object]):
        self.config = config
        self.rpc = rpc
        self.registry = DriverRegistry(config.drivers)
        self.data_dir = config.data_dir or tempfile.mkdtemp(
            prefix="nomad-client-")
        self.alloc_dir_root = os.path.join(self.data_dir, "allocs")
        self.state_db = ClientStateDB(
            os.path.join(self.data_dir, "client_state.db"))
        from nomad_tpu.client.devices import (DeviceManager,
                                              FakeDevicePlugin)
        self.device_manager = DeviceManager(
            [FakeDevicePlugin(s) for s in config.device_plugins])
        self.node = self._build_node()
        self.alloc_runners: Dict[str, AllocRunner] = {}
        self._ar_lock = threading.Lock()
        self._stop = threading.Event()
        self._threads: List[threading.Thread] = []
        self._heartbeat_ttl = 10.0
        self._pending_updates: Dict[str, object] = {}
        self._updates_lock = threading.Lock()
        self._last_alloc_index = 0

    # ------------------------------------------------------------ node

    def _build_node(self) -> Node:
        node = Node(
            id=str(uuid.uuid4()),
            name=self.config.node_name,
            datacenter=self.config.datacenter,
            node_class=self.config.node_class,
            status=NodeStatus.INIT,
        )
        node.meta = dict(self.config.meta)
        fingerprint_node(node, self.registry.fingerprints())
        node.node_resources.devices = self.device_manager.fingerprint()
        from nomad_tpu.structs.node import compute_node_class
        node.computed_class = compute_node_class(node)
        return node

    # ------------------------------------------------------------ lifecycle

    def start(self) -> None:
        self._restore()
        if self.config.device_fingerprint is not None:
            # seed the device set so the FIRST registration already
            # carries the fingerprint
            self._apply_device_fingerprint(register=False)
        resp = self.rpc("Node.Register", {"node": self.node})
        self._heartbeat_ttl = resp.get("heartbeat_ttl", 10.0)
        self.node.status = NodeStatus.READY
        self.rpc("Node.UpdateStatus",
                 {"node_id": self.node.id, "status": "ready"})
        loops = [(self._heartbeat_loop, "hb"),
                 (self._heartbeat_stop_loop, "hb-stop"),
                 (self._watch_allocations, "alloc-watch"),
                 (self._update_pusher, "alloc-update"),
                 (self._log_janitor_loop, "log-janitor")]
        if self.config.device_fingerprint is not None:
            loops.append((self._device_monitor_loop, "device-fp"))
        for target, name in loops:
            t = threading.Thread(target=target, daemon=True,
                                 name=f"client-{name}")
            t.start()
            self._threads.append(t)

    # -------------------------------------------------------- device health

    def _device_snapshot(self):
        return [(d.id, tuple(d.instance_ids), tuple(sorted(d.unhealthy_ids)))
                for d in self.node.node_resources.devices]

    def _apply_device_fingerprint(self, register: bool = True) -> bool:
        """Poll the device fingerprint stream; on change, update the node
        and (optionally) re-register so servers see the new health."""
        try:
            devices = self.config.device_fingerprint()
        except Exception:                       # noqa: BLE001
            return False
        before = self._device_snapshot()
        self.node.node_resources.devices = \
            self.device_manager.fingerprint() + list(devices)
        changed = self._device_snapshot() != before
        if changed and register:
            # delta path first: the fingerprint change rides the
            # leader's batched write path (one NodeFingerprintBatch
            # entry per flush tick across the whole fleet) instead of
            # a full Node.Register raft entry per change.  Fall back
            # to re-register if the server doesn't know us (or is too
            # old to know the RPC).
            try:
                resp = self.rpc("Node.UpdateFingerprint", {
                    "node_id": self.node.id,
                    "devices": list(self.node.node_resources.devices)})
                if resp.get("known", False):
                    return changed
            except Exception:                   # noqa: BLE001
                pass
            try:
                self.rpc("Node.Register", {"node": self.node})
            except Exception:                   # noqa: BLE001
                pass
        return changed

    def _log_janitor_loop(self) -> None:
        """Rotate oversized task log files written by direct-append
        drivers (logmon.rotate_copytruncate; the exec executor rotates
        its own in-process)."""
        from nomad_tpu.client.logmon import (DEFAULT_MAX_FILE_SIZE,
                                             DEFAULT_MAX_FILES,
                                             rotate_copytruncate)
        import os as _os
        while not self._stop.wait(10.0):
            with self._ar_lock:
                runners = list(self.alloc_runners.values())
            for ar in runners:
                tg = ar.task_group()
                for task in (tg.tasks if tg else []):
                    # only direct-append drivers: the exec executor owns
                    # its rotation in-process, and racing it would
                    # clobber fragments
                    if task.driver != "raw_exec":
                        continue
                    try:
                        lcfg = (task.config or {}).get("logs") or {}
                        max_size = int(lcfg.get("max_file_size_mb", 0)) \
                            * 1024 * 1024 or DEFAULT_MAX_FILE_SIZE
                        max_files = int(lcfg.get("max_files", 0)) \
                            or DEFAULT_MAX_FILES
                        logs_dir = ar.alloc_dir.logs_dir()
                        for kind in ("stdout", "stderr"):
                            rotate_copytruncate(
                                _os.path.join(logs_dir,
                                              f"{task.name}.{kind}"),
                                max_size, max_files)
                    except Exception:                # noqa: BLE001
                        continue    # one bad logs config must not kill
                                    # rotation for the whole node

    def _device_monitor_loop(self) -> None:
        while not self._stop.is_set():
            if self._stop.wait(self.config.device_poll_interval):
                return
            self._apply_device_fingerprint()

    def stop(self) -> None:
        self._stop.set()
        for t in self._threads:
            t.join(2.0)
        with self._ar_lock:
            runners = list(self.alloc_runners.values())
        for ar in runners:
            ar.stop(0.5)
        self.state_db.close()

    # ------------------------------------------------------------ heartbeat

    def _heartbeat_loop(self) -> None:
        """registerAndHeartbeat (client.go:1602): beat at ~TTL/2 with
        jitter; re-register on unknown-node errors."""
        import random
        while not self._stop.is_set():
            wait = self._heartbeat_ttl * (0.45 + 0.1 * random.random())
            if self._stop.wait(wait):
                return
            try:
                resp = self.rpc("Node.UpdateStatus",
                                {"node_id": self.node.id,
                                 "heartbeat": True})
                self._heartbeat_ttl = resp.get("heartbeat_ttl",
                                               self._heartbeat_ttl)
                self._disconnected_since = None
            except Exception:                       # noqa: BLE001
                # server unreachable: keep trying; the server marks us
                # down/disconnected on TTL expiry (heartbeat.go:135)
                if getattr(self, "_disconnected_since", None) is None:
                    self._disconnected_since = time.time()
                log.debug("heartbeat failed", exc_info=True)
                try:
                    self.rpc("Node.Register", {"node": self.node})
                except Exception:                   # noqa: BLE001
                    pass

    def _heartbeat_stop_loop(self) -> None:
        """heartbeatstop (client/heartbeatstop.go:158): while the client
        cannot reach a server, allocations whose task group sets
        stop_after_client_disconnect are stopped locally once that
        duration elapses past the last successful heartbeat."""
        while not self._stop.is_set():
            if self._stop.wait(1.0):
                return
            since = getattr(self, "_disconnected_since", None)
            if since is None:
                continue
            behind = time.time() - since
            with self._ar_lock:
                runners = list(self.alloc_runners.values())
            for ar in runners:
                tg = ar.task_group()
                if tg is None or tg.stop_after_client_disconnect_s is None:
                    continue
                if behind <= tg.stop_after_client_disconnect_s:
                    continue
                if ar.client_status in ("complete", "failed", "lost"):
                    continue
                log.info("stopping alloc %s: client disconnected > %.0fs",
                         ar.alloc.id[:8], tg.stop_after_client_disconnect_s)
                ar.stop_for_disconnect()

    # ------------------------------------------------------------ allocs

    def _watch_allocations(self) -> None:
        """Blocking-query watch (client.go:2056 watchAllocations →
        Node.GetClientAllocs)."""
        while not self._stop.is_set():
            try:
                resp = self.rpc("Node.GetClientAllocs",
                                {"node_id": self.node.id,
                                 "min_index": self._last_alloc_index,
                                 "timeout": 2.0})
            except Exception:                       # noqa: BLE001
                if self._stop.wait(1.0):
                    return
                continue
            if resp is None:
                continue
            self._last_alloc_index = resp.get("index",
                                              self._last_alloc_index)
            self._run_allocs(resp.get("allocs") or [])
            self._stop.wait(self.config.watch_interval)

    def _run_allocs(self, allocs) -> None:
        """Diff assigned vs running (client.go:2286 runAllocs)."""
        assigned = {a.id: a for a in allocs}
        with self._ar_lock:
            existing = dict(self.alloc_runners)
        # removed (GC'd server-side): destroy
        for alloc_id, ar in existing.items():
            if alloc_id not in assigned:
                ar.destroy()
                with self._ar_lock:
                    self.alloc_runners.pop(alloc_id, None)
        for alloc_id, alloc in assigned.items():
            ar = existing.get(alloc_id)
            if ar is None:
                if alloc.server_terminal_status() or \
                        alloc.client_terminal_status():
                    continue
                self._start_alloc(alloc)
            else:
                self._update_alloc(ar, alloc)
        self._maybe_gc()

    def _start_alloc(self, alloc) -> None:
        alloc = alloc.copy() if hasattr(alloc, "copy") else alloc
        if alloc.job is None:
            try:
                alloc.job = self.rpc("Job.GetJob",
                                     {"namespace": alloc.namespace,
                                      "job_id": alloc.job_id})
            except Exception:                       # noqa: BLE001
                pass
        prev_dir = None
        if alloc.previous_allocation:
            with self._ar_lock:
                prev = self.alloc_runners.get(alloc.previous_allocation)
            if prev is not None:
                prev_dir = prev.alloc_dir
        ar = AllocRunner(alloc, self.registry, self.alloc_dir_root,
                         node=self.node, on_update=self._on_alloc_update,
                         state_db=self.state_db,
                         prev_alloc_dir=prev_dir, rpc=self.rpc,
                         device_manager=self.device_manager)
        with self._ar_lock:
            self.alloc_runners[alloc.id] = ar
        self.state_db.put_alloc(alloc.id, {
            "namespace": alloc.namespace, "job_id": alloc.job_id,
            "task_group": alloc.task_group, "name": alloc.name,
            "eval_id": alloc.eval_id,
            "deployment_id": alloc.deployment_id})
        ar.run()

    def _update_alloc(self, ar: AllocRunner, alloc) -> None:
        if alloc.desired_status in (AllocDesiredStatus.STOP,
                                    AllocDesiredStatus.EVICT) and \
                ar.client_status in (AllocClientStatus.PENDING,
                                     AllocClientStatus.RUNNING):
            ar.alloc.desired_status = alloc.desired_status
            ar.stop()
            return
        # in-place update: new job version and/or deployment membership
        # without a task restart (alloc_runner.go Update)
        new_version = (alloc.job is not None and ar.alloc.job is not None
                       and alloc.job.version != ar.alloc.job.version)
        if new_version or alloc.deployment_id != ar.alloc.deployment_id:
            # copy before the runner aliases/mutates it: with in-process
            # RPC the server hands us live store objects (_start_alloc
            # copies for the same reason)
            ar.update(alloc.copy() if hasattr(alloc, "copy") else alloc)
        ar.alloc.desired_transition = alloc.desired_transition

    def _maybe_gc(self) -> None:
        """Destroy oldest terminal allocrunners over the cap (gc.go)."""
        with self._ar_lock:
            terminal = [(aid, ar) for aid, ar in self.alloc_runners.items()
                        if ar.client_status in (AllocClientStatus.COMPLETE,
                                                AllocClientStatus.FAILED)]
            excess = len(self.alloc_runners) - self.config.max_allocs_gc
        if excess > 0:
            for aid, ar in terminal[:excess]:
                ar.destroy()
                with self._ar_lock:
                    self.alloc_runners.pop(aid, None)

    # ------------------------------------------------------------ updates

    def _on_alloc_update(self, ar: AllocRunner) -> None:
        """Queue a client-status push (allocSync batching,
        client.go allocSync / Node.UpdateAlloc)."""
        u = ar.alloc.copy()
        u.client_status = ar.client_status
        u.client_description = ar.client_description
        # the states as they read now, not the runners' live objects: with
        # in-process RPC the store keeps what it is handed, and a stored
        # allocation's parts are never written in place
        u.task_states = {n: replace(s, events=list(s.events))
                         for n, s in ar.task_states().items()}
        u.job = None                        # strip for wire size
        if ar.deployment_healthy is not None:
            u.deployment_status = {"healthy": ar.deployment_healthy,
                                   "timestamp": time.time()}
        with self._updates_lock:
            self._pending_updates[u.id] = u

    def _update_pusher(self) -> None:
        while not self._stop.wait(0.2):
            self.push_updates()
        self.push_updates()

    def push_updates(self) -> None:
        with self._updates_lock:
            updates = list(self._pending_updates.values())
            self._pending_updates.clear()
        if not updates:
            return
        try:
            self.rpc("Node.UpdateAlloc", {"allocs": updates})
        except Exception:                           # noqa: BLE001
            with self._updates_lock:
                for u in updates:
                    self._pending_updates.setdefault(u.id, u)

    # ------------------------------------------------------------ restore

    def _restore(self) -> None:
        """Recover alloc runners persisted by a previous process
        (client.go restoreState; drivers RecoverTask)."""
        saved = self.state_db.get_allocs()
        for alloc_id, summary in saved.items():
            try:
                alloc = self.rpc("Alloc.GetAlloc", {"alloc_id": alloc_id})
            except Exception:                       # noqa: BLE001
                alloc = None
            if alloc is None or alloc.terminal_status():
                self.state_db.delete_alloc(alloc_id)
                continue
            if alloc.job is None:
                alloc.job = self.rpc("Job.GetJob",
                                     {"namespace": alloc.namespace,
                                      "job_id": alloc.job_id})
            ar = AllocRunner(alloc, self.registry, self.alloc_dir_root,
                             node=self.node,
                             on_update=self._on_alloc_update,
                             state_db=self.state_db, rpc=self.rpc,
                             device_manager=self.device_manager)
            with self._ar_lock:
                self.alloc_runners[alloc.id] = ar
            ar.restore()

    # ------------------------------------------------------------ stats

    def num_allocs(self) -> int:
        with self._ar_lock:
            return len(self.alloc_runners)
