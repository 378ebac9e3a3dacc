"""Agent: embeds a Server and/or Client plus the HTTP API
(reference: command/agent/agent.go — setupServer/setupClient; `-dev`
mode runs both in one process with in-memory Raft).
"""
from __future__ import annotations

import threading
from dataclasses import dataclass, field
from typing import List, Optional

from nomad_tpu import tracing
from nomad_tpu.core.server import Server, ServerConfig


@dataclass
class AgentConfig:
    name: str = "agent-1"
    region: str = "global"
    datacenter: str = "dc1"
    server_enabled: bool = True
    client_enabled: bool = False
    dev_mode: bool = True
    http_host: str = "127.0.0.1"
    http_port: int = 4646                 # reference default port
    # address other nodes should use to reach this agent's HTTP API
    # (reference `advertise { http = ... }`); defaults to a best-effort
    # guess — REQUIRED for cross-node alloc fs/logs when binding 0.0.0.0
    http_advertise: Optional[str] = None
    num_schedulers: int = 4
    enabled_schedulers: List[str] = field(
        default_factory=lambda: ["service", "batch", "system", "sysbatch"])
    heartbeat_ttl: float = 10.0
    data_dir: Optional[str] = None
    acl_enabled: bool = False
    node_pool_drivers: List[str] = field(
        default_factory=lambda: ["mock", "raw_exec"])


class Agent:
    """One process: server (control plane) + optional client (node agent)
    + HTTP API.  `-dev` = both, in-memory (command/agent/command.go)."""

    def __init__(self, config: Optional[AgentConfig] = None):
        self.config = config or AgentConfig()
        self.server: Optional[Server] = None
        self.client = None
        self.http: Optional["HTTPServer"] = None
        self._lock = threading.Lock()
        self._watching_gc = False
        # in-process log ring feeding /v1/agent/monitor (reference
        # command/agent/monitor/monitor.go: a log broker the HTTP monitor
        # endpoint streams from)
        import collections
        import logging

        self.log_ring = collections.deque(maxlen=2048)  # (seq, line)
        self._log_seq = 0
        self._log_cv = threading.Condition()

        agent = self

        class _RingHandler(logging.Handler):
            def emit(self, record):
                try:
                    line = self.format(record)
                except Exception:               # noqa: BLE001
                    return
                with agent._log_cv:
                    agent._log_seq += 1
                    agent.log_ring.append((agent._log_seq, line))
                    agent._log_cv.notify_all()

        handler = _RingHandler()
        handler.setFormatter(logging.Formatter(
            "%(asctime)s [%(levelname)s] %(name)s: %(message)s"))
        logging.getLogger("nomad_tpu").addHandler(handler)
        logging.getLogger("nomad_tpu").setLevel(logging.INFO)
        self._log_handler = handler

        if self.config.server_enabled:
            self.server = Server(
                ServerConfig(
                    num_schedulers=self.config.num_schedulers,
                    enabled_schedulers=self.config.enabled_schedulers,
                    heartbeat_ttl=self.config.heartbeat_ttl,
                    data_dir=self.config.data_dir,
                    region=self.config.region),
                name=self.config.name)
            if self.config.acl_enabled:
                self.server.enable_acl()
        if self.config.client_enabled:
            try:
                from nomad_tpu.client import Client, ClientConfig
            except ImportError as e:
                raise RuntimeError(
                    "client_enabled requires the nomad_tpu.client "
                    "package") from e
            if self.server is None:
                raise ValueError("remote-server client requires rpc target")
            self.client = Client(
                ClientConfig(node_name=self.config.name + "-client",
                             datacenter=self.config.datacenter,
                             drivers=list(self.config.node_pool_drivers)),
                rpc=self.server.endpoints.handle)

    def start(self) -> None:
        if not self._watching_gc:
            # the collector's pauses as `gc.collect.gen<n>` spans
            self._watching_gc = True
            tracing.watch_gc(True)
        if self.server is not None:
            self.server.start()
        if self.client is not None:
            self.client.start()
        from nomad_tpu.agent.http import HTTPServer
        self.http = HTTPServer(self, host=self.config.http_host,
                               port=self.config.http_port)
        self.http.start()
        if self.client is not None:
            # advertise this agent's HTTP address on the node so servers
            # can forward fs/log reads (Node.HTTPAddr)
            self.client.node.http_addr = self._advertise_addr()
            try:
                self.client.rpc("Node.Register",
                                {"node": self.client.node})
            except Exception:               # noqa: BLE001
                pass

    def _advertise_addr(self) -> str:
        if self.config.http_advertise:
            return self.config.http_advertise
        host = self.http.host
        if host in ("0.0.0.0", "::", ""):
            # wildcard bind is unreachable from other nodes — guess the
            # primary interface address (advertise { http } overrides)
            import socket
            try:
                s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
                s.connect(("10.255.255.255", 1))
                host = s.getsockname()[0]
                s.close()
            except OSError:
                host = "127.0.0.1"
        return f"{host}:{self.http.port}"

    def stop(self) -> None:
        if self.http is not None:
            self.http.stop()
        if self.client is not None:
            self.client.stop()
        if self.server is not None:
            self.server.stop()
        if self._watching_gc:
            self._watching_gc = False
            tracing.watch_gc(False)

    @property
    def http_addr(self) -> str:
        return f"http://{self.http.host}:{self.http.port}"

    def rpc(self, method: str, args: dict,
            consistency: Optional[str] = None):
        """In-process RPC into the embedded server (the agent's RPC
        client; reference command/agent/agent.go RPC passthrough).

        With `consistency` set and a read method, the request is served
        from THIS server's store at a gate-established read point
        (follower reads) instead of forwarding to the leader."""
        if self.server is None:
            raise RuntimeError("agent has no server")
        if consistency is not None:
            from nomad_tpu.serving.gate import READ_METHODS
            if method in READ_METHODS:
                result, _ctx = self.server.read(method, args, consistency)
                return result
        return self.server.rpc_leader(method, args)
