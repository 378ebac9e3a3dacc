"""Server: the control-plane spine wired together.

Reference analog: nomad/server.go + leader.go establishLeadership — state
store, eval broker, blocked evals, plan queue, the serialized plan-apply
loop, N scheduler workers, heartbeats and the periodic dispatcher.

Two consensus modes, mirroring the reference's raftInmem vs raft-boltdb:
 - dev (raft=None): single server, writes apply straight through the
   NomadFSM under a lock (the '-dev agent' in-memory Raft).
 - cluster: writes go through `RaftNode.apply` and every member's FSM
   replays them; leadership elections drive establish/revoke of the
   leader-only subsystems (nomad/leader.go:277,1099).
"""
from __future__ import annotations

import itertools
import logging
import os
import pickle
import threading
import time as _time
import uuid
from typing import Dict, List, Optional

from nomad_tpu import knobs, tracing
from nomad_tpu.core.blocked import BlockedEvals
from nomad_tpu.core.broker import FAILED_QUEUE, EvalBroker
from nomad_tpu.core.core_gc import CoreScheduler
from nomad_tpu.core.deployments import DeploymentWatcher
from nomad_tpu.core.drainer import NodeDrainer
from nomad_tpu.core.events import EventBroker
from nomad_tpu.core.heartbeat import HeartbeatBatcher, HeartbeatTracker
from nomad_tpu.core.periodic import PeriodicDispatcher
from nomad_tpu.core.plan_apply import PlanApplier
from nomad_tpu.core.plan_queue import PlanQueue
from nomad_tpu.core.secrets import SecretsProvider
from nomad_tpu.serving.gate import ReadGate
from nomad_tpu.core.worker import Worker
from nomad_tpu.raft import (
    ConfigurationInFlightError,
    DurableMeta,
    FileSnapshotStore,
    LogStore,
    MessageType,
    NomadFSM,
    NotLeaderError,
    RaftNode,
)
from nomad_tpu.state import StateStore
from nomad_tpu.structs import (
    Evaluation,
    EvalStatus,
    Job,
    JobType,
    Node,
)
from nomad_tpu.structs.evaluation import EvalTrigger

log = logging.getLogger(__name__)


class ServerConfig:
    def __init__(self, num_schedulers: int = 4,
                 enabled_schedulers: Optional[List[str]] = None,
                 heartbeat_ttl: float = 10.0,
                 heartbeat_batch_interval: float = 0.05,
                 gc_interval: float = 300.0,
                 data_dir: Optional[str] = None,
                 region: str = "global",
                 failed_eval_followup_delay: float = 60.0,
                 integrity_interval: float = 2.0,
                 integrity_full_every: int = 4):
        self.num_schedulers = num_schedulers
        self.enabled_schedulers = enabled_schedulers or \
            ["service", "batch", "system", "sysbatch"]
        self.heartbeat_ttl = heartbeat_ttl
        # flush cadence of the leader's heartbeat/node-status coalescer
        # (one NodeHeartbeatBatch raft entry per flush);
        # NOMAD_TPU_HEARTBEAT_BATCH_MS overrides
        self.heartbeat_batch_interval = knobs.get_float(
            "NOMAD_TPU_HEARTBEAT_BATCH_MS",
            default=heartbeat_batch_interval * 1000.0) / 1000.0
        self.gc_interval = gc_interval
        self.data_dir = data_dir
        self.region = region
        self.failed_eval_followup_delay = failed_eval_followup_delay
        # replica-integrity plane: STATE_CHECKPOINT proposal cadence
        # (seconds; <= 0 disables) and the every-Nth full digest walk;
        # NOMAD_TPU_INTEGRITY_INTERVAL / _FULL_EVERY override
        self.integrity_interval = knobs.get_float(
            "NOMAD_TPU_INTEGRITY_INTERVAL", default=integrity_interval)
        self.integrity_full_every = max(1, knobs.get_int(
            "NOMAD_TPU_INTEGRITY_FULL_EVERY",
            default=integrity_full_every))


class Server:
    # wait-graph (nomad_tpu.analysis)
    _LOCK_BLOCKING_OK = {
        "_leader_lock": "establish/revoke are serialized on the raft "
                        "leadership dispatcher thread and no "
                        "raft-internal thread takes this lock, so the "
                        "commit barrier inside establishLeadership is "
                        "a bounded stall (its own timeout), never a "
                        "cycle — mirrors the reference leaderLoop",
    }

    def __init__(self, config: Optional[ServerConfig] = None,
                 name: str = "server-1",
                 peers: Optional[List[str]] = None,
                 raft_transport=None,
                 raft_config=None,
                 membership=None,
                 raft_join: bool = False,
                 wan_pool=None):
        self.config = config or ServerConfig()
        self.name = name
        self.store = StateStore()
        self.broker = EvalBroker()
        self.broker.node_name = name     # span attribution (tracing)
        self.blocked_evals = BlockedEvals(self.broker)
        self.plan_queue = PlanQueue()
        self.applier = PlanApplier(self.store, commit_fn=self._commit_plan)
        self.applier.node_name = name
        # PreemptionEvals are created by the applier AFTER the raft apply
        # returns (reference plan_apply.go applyPlan) — creating them from
        # inside the FSM's state-change watcher would re-enter the raft
        # write path under its own lock and deadlock the commit
        self.applier.on_preempted = self._create_preemption_evals
        self.workers: List[Worker] = []
        self.remote_workers: List[Worker] = []
        # dev-mode wave-aligned dequeue front (set at leadership)
        self.eval_feeder = None
        self._raft_lock = threading.Lock()     # serializes indexed writes
        self._stop = threading.Event()
        self._leader_stop = threading.Event()
        self._leader_lock = threading.Lock()
        self._plan_thread: Optional[threading.Thread] = None
        self._threads: List[threading.Thread] = []
        self.event_broker = EventBroker()
        self.heartbeats = HeartbeatTracker(self, ttl=self.config.heartbeat_ttl)
        self.heartbeat_batch = HeartbeatBatcher(
            self, interval=self.config.heartbeat_batch_interval)
        self.deployment_watcher = DeploymentWatcher(self)
        from nomad_tpu.core.volumes import VolumeWatcher
        self.volume_watcher = VolumeWatcher(self)
        # Vault-shaped secrets (core/secrets.py): leases are leader-local
        # like the reference's external-Vault client state, not raft state
        self.secrets = SecretsProvider()
        self.drainer = NodeDrainer(self)
        self.periodic = PeriodicDispatcher(self)
        self.core_scheduler = CoreScheduler(self)
        self.store.watch(self.blocked_evals.watch_state)
        self.store.watch(self.event_broker.watch_state)
        self.store.watch(self._on_state_change)
        self.leader = False
        self._established = False
        # deny-by-default token enforcement on HTTP/RPC mutation paths
        # (reference: `acl { enabled = true }` agent config)
        if knobs.get_bool("NOMAD_TPU_ACL"):
            self.acl_enabled = True

        self.fsm = NomadFSM(self.store, hooks=self)
        self.raft: Optional[RaftNode] = None
        self._transport = raft_transport
        from nomad_tpu.rpc.endpoints import Endpoints
        self.endpoints = Endpoints(self)
        # overload plane: per-namespace admission (off unless the env
        # knobs set limits) + leader brownout classification (always
        # on — level 0 until the raft signals cross the thresholds)
        from nomad_tpu.admission import AdmissionGate, BrownoutMonitor
        self.admission = AdmissionGate()
        self.brownout = BrownoutMonitor(self)
        # consistency-mode read gate: every server (leader or follower)
        # serves reads from its LOCAL store once the gate establishes a
        # read point (serving/gate.py)
        self.serving_gate = ReadGate(self)
        self.membership = membership   # LAN gossip (core.membership)
        # multi-region federation (nomad/serf.go WAN pool + nomad/rpc.go
        # forwardRegion): servers discover other regions over a second
        # SWIM instance (wan_pool, channel "wan") tagged with region +
        # leader-ness, and the router forwards RPCs to the remote
        # region's current leader.  `_region_peers` remains as the
        # static route table for in-process federation (dev mode).
        self.region = self.config.region
        self._region_peers: Dict[str, object] = {}
        self.wan_pool = wan_pool
        from nomad_tpu.federation import RegionRouter
        self.region_router = RegionRouter(self)
        if raft_transport is not None:
            raft_transport.register(f"rpc:{name}", self.endpoints.handle)
            data_dir = self.config.data_dir
            log_store = snapshots = meta = None
            if data_dir:
                sdir = os.path.join(data_dir, name)
                os.makedirs(sdir, exist_ok=True)
                log_store = LogStore(os.path.join(sdir, "raft.log"))
                snapshots = FileSnapshotStore(os.path.join(sdir, "snapshots"))
                # term + vote on stable storage: without this a restarted
                # server can grant a second vote in the same term
                meta = DurableMeta(os.path.join(sdir, "raft_meta.json"))
            self.raft = RaftNode(
                name, peers or [name], raft_transport, self.fsm,
                config=raft_config, log_store=log_store, snapshots=snapshots,
                meta=meta,
                on_leader=self._establish_leadership,
                on_follower=self._revoke_leadership,
                join=raft_join)
        # autopilot (reference nomad/autopilot.go): the leader promotes
        # caught-up non-voters after a stabilization window and, when
        # gossip runs, adds ALIVE members / removes LEFT ones / reaps
        # FAILED ones out of the raft configuration
        self._autopilot_interval = knobs.get_float(
            "NOMAD_TPU_AUTOPILOT_INTERVAL")
        self._autopilot_stabilization = knobs.get_float(
            "NOMAD_TPU_AUTOPILOT_STABILIZATION")
        self._autopilot_lag = knobs.get_int("NOMAD_TPU_AUTOPILOT_LAG")
        self._autopilot_reap_after = knobs.get_float(
            "NOMAD_TPU_AUTOPILOT_REAP_AFTER")
        self._nonvoter_since: Dict[str, float] = {}
        self._failed_since: Dict[str, float] = {}

    # ------------------------------------------------------------- writes

    def apply(self, msg_type: str, payload: dict) -> int:
        """The single write path: a (type, payload) log entry applied via
        the FSM — through Raft when clustered, directly in dev mode
        (reference raft.Apply → nomadFSM.Apply).  On a follower the write
        forwards to the leader over RPC (reference forwardLeader,
        nomad/rpc.go)."""
        try:
            return self.apply_local(msg_type, payload)
        except NotLeaderError:
            return self.rpc_leader("Raft.Apply",
                                   {"msg_type": msg_type, "payload": payload})

    def apply_local(self, msg_type: str, payload: dict) -> int:
        """Apply on THIS server (no forwarding) — the Raft.Apply endpoint
        target; raises NotLeaderError if a follower is asked directly."""
        if self.raft is not None:
            return self.raft.apply(msg_type, payload)
        with self._raft_lock:
            index = self.store.latest_index + 1
            # dev mode (no raft): the span opens around the FSM call,
            # never under it — the FSM does not read the clock
            with tracing.span("raft.fsm_apply", node=self.name,
                              msg_type=msg_type, index=index):
                self.fsm.apply(index, msg_type, payload)
        return index

    def rpc_leader(self, method: str, args: dict):
        """Invoke an RPC on the leader: short-circuits locally when this
        server is the leader (or in dev mode), else rides the transport
        (reference: rpc.go forward + helper/pool)."""
        if self.raft is None or self.raft.is_leader:
            return self.endpoints.handle(method, args)
        leader = self.raft.leader_id
        if leader is None or leader == self.name or self._transport is None:
            # leader == self.name while not is_leader = stale self-pointer
            # during a transition; forwarding would recurse into ourselves
            from nomad_tpu.rpc.endpoints import RpcError
            raise RpcError("no_leader", "no cluster leader")
        # the transport hop leaves this thread: re-attach the sampled
        # trace context and re-encode the remaining deadline budget so
        # the leader inherits both (reserved-key contract, rpc/reserved)
        from nomad_tpu.rpc import reserved
        return self._transport.call(self.name, f"rpc:{leader}", method,
                                    reserved.restamp(args))

    # ------------------------------------------------------------- reads

    def read(self, method: str, args: dict,
             consistency: str = "default", timeout: float = 5.0):
        """Serve a read RPC from THIS server's store at a gate-established
        read point; returns (result, ReadContext).  This is the follower-
        read path: nothing here touches the leader beyond what the
        consistency mode requires (zero rounds for a valid lease, one
        forwarded ReadIndex RPC otherwise, nothing at all for stale)."""
        ctx = self.serving_gate.begin_read(consistency, timeout)
        return self.endpoints.handle(method, args), ctx

    # ------------------------------------------------------------- regions

    def federate(self, other: "Server") -> None:
        """Two-way in-process federation (reference: WAN serf join,
        nomad/serf.go — each region learns a route to the other's
        servers).  Transitive routes propagate so a three-region mesh
        needs only pairwise joins."""
        self._region_peers[other.region] = other
        other._region_peers[self.region] = self
        for r, p in list(other._region_peers.items()):
            if r not in (self.region,) and r not in self._region_peers:
                self._region_peers[r] = p
        for r, p in list(self._region_peers.items()):
            if r not in (other.region,) and r not in other._region_peers:
                other._region_peers[r] = p

    def federate_name(self, region: str, server_name: str) -> None:
        """Static transport-based federation route: RPCs for `region` may
        forward to `server_name` over the shared transport.  The WAN
        gossip pool supersedes this once members are discovered; the
        static entry remains a seed/fallback."""
        self._region_peers[region] = server_name

    def regions(self) -> List[str]:
        """Known regions, sorted and deduped, always including ours:
        WAN-pool-discovered regions plus static federation routes."""
        regs = {self.region, *self._region_peers}
        if self.wan_pool is not None:
            regs.update(self.wan_pool.regions())
        return sorted(regs)

    def rpc_region(self, region: str, method: str, args: dict):
        """Route an RPC to the right region's leader (reference
        nomad/rpc.go:21 forwardRegion).  Local region short-circuits;
        remote regions go through the federation router (known-leader
        hints, bounded retry over remote churn, Unreachable fail-fast
        when the region is dark)."""
        # app-level forwards (job.region routing, leader handoffs) build
        # fresh args: re-attach this thread's sampled trace context AND
        # re-encode the remaining deadline budget so both survive the
        # hop like they do the _forward_hops path (before restamp() the
        # budget silently vanished here and the remote region served
        # the request unbounded)
        from nomad_tpu.rpc import reserved
        return self.region_router.route(region, method,
                                        reserved.restamp(args))

    def enqueue_plan(self, plan):
        """Plan-queue enqueue gated on the submitting worker still holding
        its eval lease (reference planner token check, plan_endpoint.go):
        if the lease expired (auto-nack) or moved to another worker, this
        plan is from a superseded scheduling pass and must not commit."""
        if plan.eval_id and plan.eval_token:
            if self.broker.outstanding(plan.eval_id) != plan.eval_token:
                from nomad_tpu.rpc.endpoints import RpcError
                raise RpcError(
                    "stale_eval_token",
                    f"eval {plan.eval_id}: lease expired or reassigned")
        return self.plan_queue.enqueue(plan)

    def _commit_plan(self, applied) -> int:
        """Commit applier output through the raft write path.  `applied`
        is one AppliedPlanResults or a LIST of them — the applier
        coalesces adjacent plans from the queue into one log entry (one
        raft apply, one index) and the FSM fans the batch out to the
        store under a single lock acquisition.

        Deliberately NOT leader-forwarded (apply_local, not apply): the
        eval-token gate runs at enqueue time against THIS server's
        broker, so a plan stranded in the applier when leadership moves
        must fail with NotLeaderError — forwarding it would commit a
        deposed leader's plan on the new leader, whose broker may have
        already redelivered the eval and committed a competing plan
        (double placement).  The failed future nacks the eval and it
        reschedules under the new leader's gate."""
        return self.apply_local(MessageType.APPLY_PLAN_RESULTS,
                                {"results": applied})

    def next_index(self) -> int:
        with self._raft_lock:
            return self.store.latest_index + 1

    # ------------------------------------------------------------- lifecycle

    def start(self) -> None:
        # placements can fail against transient in-flight over-reservation
        # (the engine overlay's double-count window); once the overlay
        # drains, give blocked evals another chance
        from nomad_tpu.parallel.engine import get_engine
        get_engine().on_drain = lambda: self.blocked_evals.unblock_all(
            self.store.latest_index)
        if self.membership is not None:
            self.membership.start()
        if self.wan_pool is not None:
            self.wan_pool.start()
        if self.raft is not None:
            # every server runs schedulers against its replicated snapshot,
            # RPCing the leader for dequeue/ack/plan-submit (reference:
            # workers run on all servers, nomad/worker.go:81-85)
            from nomad_tpu.core.worker import RemoteWorker
            for i in range(self.config.num_schedulers):
                w = RemoteWorker(self, i, self.config.enabled_schedulers)
                w.start()
                self.remote_workers.append(w)
            self.raft.start()
        else:
            self._establish_leadership()

    def _establish_leadership(self) -> None:
        """establishLeadership (reference nomad/leader.go:277-357)."""
        with self._leader_lock:
            if self._established:
                return
            self._established = True
            self.leader = True
            if self.wan_pool is not None:
                # leadership rides the WAN tags: remote regions route to
                # us once the re-tag gossips out (nomad/serf.go member
                # tags carrying raft leadership)
                self.wan_pool.set_leader(True)
            self._leader_stop = threading.Event()
            stop = self._leader_stop
            self.broker.set_enabled(True)
            # fairness knobs live in replicated SchedulerConfiguration;
            # a fresh leader's broker must adopt the committed values
            # (later changes arrive via the FSM's scheduler-config hook)
            self.broker.set_fair_config(self.store.scheduler_config)
            self.blocked_evals.set_enabled(True)
            self.plan_queue.set_enabled(True)
            self._plan_thread = threading.Thread(
                target=self.applier.run_loop, args=(self.plan_queue, stop),
                name="plan-apply", daemon=True)
            self._plan_thread.start()
            if self.raft is None:
                # dev mode: local workers; in cluster mode RemoteWorkers
                # already run on every member (started in start()).  The
                # wave feeder aligns the pool's dequeues: one broker lock
                # pass drains a whole ready wave so the engine coalesces
                # full-wave dispatch batches (NOMAD_TPU_WAVE caps it).
                from nomad_tpu.core.broker import EvalWaveFeeder
                wave_n = knobs.get_int(
                    "NOMAD_TPU_WAVE",
                    default=self.config.num_schedulers)
                self.eval_feeder = EvalWaveFeeder(self.broker, wave_n)
                for i in range(self.config.num_schedulers):
                    w = Worker(self, i, self.config.enabled_schedulers)
                    w.start()
                    self.workers.append(w)
            if self.raft is not None:
                # barrier before reading the store: a fresh leader may
                # still be replaying committed entries, and restoring
                # evals from a stale view would drop the tail of them
                self.raft.barrier(5.0)
            self._restore_evals()
            t = threading.Thread(target=self._failed_eval_reaper,
                                 args=(stop,), name="eval-reaper", daemon=True)
            t.start()
            self._threads.append(t)
            dup_t = threading.Thread(target=self._dup_blocked_reaper,
                                     args=(stop,), name="dup-blocked-reaper",
                                     daemon=True)
            dup_t.start()
            self._threads.append(dup_t)
            self.heartbeat_batch.start()
            self.heartbeats.start()
            # initializeHeartbeatTimers (leader.go:347): nodes registered
            # under a previous leader get timers on the new one, so a node
            # that died around the failover still expires
            for node in self.store.nodes():
                if not node.terminal_status():
                    self.heartbeats.heartbeat(node.id)
            self.deployment_watcher.start()
            self.volume_watcher.start()
            self.drainer.start()
            self.periodic.start()
            gc_t = threading.Thread(target=self._gc_loop, args=(stop,),
                                    name="core-gc", daemon=True)
            gc_t.start()
            self._threads.append(gc_t)
            if self.raft is not None:
                ap_t = threading.Thread(target=self._autopilot_loop,
                                        args=(stop,), name="autopilot",
                                        daemon=True)
                ap_t.start()
                self._threads.append(ap_t)
                if self.config.integrity_interval > 0:
                    it_t = threading.Thread(target=self._integrity_loop,
                                            args=(stop,), name="integrity",
                                            daemon=True)
                    it_t.start()
                    self._threads.append(it_t)

    # ------------------------------------------------------------- integrity

    def _integrity_loop(self, stop: threading.Event) -> None:
        """Leader-side STATE_CHECKPOINT proposer (Paxos-Made-Live
        log-stamped checksums): one checkpoint entry per interval, every
        `integrity_full_every`-th a full digest walk, plus an immediate
        full walk whenever a mismatch at an incremental checkpoint
        escalates.  The entry is stamped at PROPOSE time — the FSM never
        reads the clock — and applies as a deterministic no-op; the raft
        apply loop computes the digest at its log position."""
        interval = self.config.integrity_interval
        full_every = self.config.integrity_full_every
        seq = 0
        last = _time.monotonic()
        while not stop.wait(min(0.05, interval / 4.0)):
            raft = self.raft
            if raft is None or not raft.is_leader:
                continue
            escalated = raft.integrity.escalation_pending()
            if not escalated and _time.monotonic() - last < interval:
                continue
            seq += 1
            full = escalated or (seq % full_every == 0)
            if escalated:
                raft.integrity.take_escalation()
            last = _time.monotonic()
            try:
                self.apply_local(MessageType.STATE_CHECKPOINT, {
                    "seq": seq, "full": full,
                    "proposed_at": _time.time()})
            except Exception:                       # noqa: BLE001
                # deposed mid-propose or transient quorum loss: the
                # next tick retries (seq gaps are fine — the digest
                # protocol keys on log index, not seq)
                log.debug("integrity checkpoint propose failed",
                          exc_info=True)

    # ------------------------------------------------------------- autopilot

    def _autopilot_loop(self, stop: threading.Event) -> None:
        self._nonvoter_since.clear()
        self._failed_since.clear()
        while not stop.wait(self._autopilot_interval):
            try:
                self._autopilot_tick()
            except Exception:                       # noqa: BLE001
                log.debug("autopilot tick failed", exc_info=True)

    def _autopilot_tick(self) -> None:
        """One autopilot pass (leader only): promote stabilized
        non-voters; with gossip running, add ALIVE members to the
        configuration as non-voters, remove LEFT ones immediately, and
        reap FAILED ones after the reap window.  Membership changes are
        serialized by raft's one-in-flight rule — a conflict just means
        the next tick retries."""
        raft = self.raft
        if raft is None or not raft.is_leader:
            self._nonvoter_since.clear()
            self._failed_since.clear()
            return
        cfg = raft.configuration()
        now = _time.monotonic()
        for nv in cfg["nonvoters"]:
            if raft.server_healthy(nv, lag=self._autopilot_lag):
                since = self._nonvoter_since.setdefault(nv, now)
                if now - since >= self._autopilot_stabilization:
                    self._autopilot_change(raft.add_server, nv, voter=True)
                    self._nonvoter_since.pop(nv, None)
            else:
                # health flap: the stabilization window starts over
                self._nonvoter_since[nv] = now
        if self.membership is None:
            return
        in_cfg = set(cfg["voters"]) | set(cfg["nonvoters"])
        members = {m["name"]: m for m in self.membership.member_list()}
        for mname, m in members.items():
            if m["status"] == "alive" and mname not in in_cfg:
                self._autopilot_change(raft.add_server, mname)
            elif m["status"] == "left" and mname in in_cfg \
                    and mname != self.name:
                self._autopilot_change(raft.remove_server, mname)
            elif m["status"] == "failed" and mname in in_cfg \
                    and mname != self.name:
                since = self._failed_since.setdefault(mname, now)
                if now - since >= self._autopilot_reap_after:
                    self._autopilot_change(raft.remove_server, mname)
                    self._failed_since.pop(mname, None)
        for mname in list(self._failed_since):
            if members.get(mname, {}).get("status") != "failed":
                del self._failed_since[mname]

    def _autopilot_change(self, op, server: str, **kw) -> None:
        try:
            op(server, timeout=5.0, **kw)
        except (NotLeaderError, ConfigurationInFlightError):
            pass        # deposed or a change in flight: next tick retries
        except Exception:                           # noqa: BLE001
            log.debug("autopilot %s(%s) failed", op.__name__, server,
                      exc_info=True)

    def _revoke_leadership(self) -> None:
        """revokeLeadership (reference nomad/leader.go:1099-1132)."""
        with self._leader_lock:
            if not self._established:
                return
            self._established = False
            self.leader = False
            if self.wan_pool is not None:
                self.wan_pool.set_leader(False)
            self._leader_stop.set()
            self.heartbeats.stop()
            self.heartbeat_batch.stop()
            self.deployment_watcher.stop()
            self.volume_watcher.stop()
            self.drainer.stop()
            self.periodic.stop()
            for w in self.workers:
                w.stop()
            for w in self.workers:
                w.join(1.0)
            self.workers = []
            if self.eval_feeder is not None:
                self.eval_feeder.close()
                self.eval_feeder = None
            self.plan_queue.set_enabled(False)
            self.broker.set_enabled(False)
            self.blocked_evals.set_enabled(False)
            if self._plan_thread:
                self._plan_thread.join(1.0)
                self._plan_thread = None

    def stop(self) -> None:
        # graceful leave: a leader hands off BEFORE saying goodbye, so
        # followers elect a successor in milliseconds instead of waiting
        # out an election timeout of silence (transfer_leadership returns
        # False fast when no viable target exists)
        if self.raft is not None and self.raft.is_leader:
            try:
                self.raft.transfer_leadership()
            except Exception:                      # noqa: BLE001
                pass
        if self.membership is not None:
            try:
                self.membership.leave()
            except Exception:                      # noqa: BLE001
                pass
            self.membership = None
        if self.wan_pool is not None:
            # graceful goodbye on the WAN too: remote regions see LEFT
            # (and reap into a tombstone) instead of suspecting a failure
            try:
                self.wan_pool.leave()
            except Exception:                      # noqa: BLE001
                pass
            self.wan_pool = None
        self._stop.set()
        for w in self.remote_workers:
            w.stop()
        self._revoke_leadership()
        for w in self.remote_workers:
            w.join(1.0)
        self.remote_workers = []
        if self.raft is not None:
            self.raft.stop()

    def crash(self) -> None:
        """Hard-kill (power loss) simulation: threads stop, but nothing
        flushes — the raft WAL loses its unsynced tail (and may keep a
        torn record under chaos `disk.torn_write`).  The durability soak
        restarts a crashed server from the same data_dir and asserts no
        committed state was lost."""
        self._stop.set()
        for w in self.remote_workers:
            w.stop()
        self._revoke_leadership()
        for w in self.remote_workers:
            w.join(1.0)
        self.remote_workers = []
        if self.raft is not None:
            self.raft.crash()
        if self.wan_pool is not None:
            # no goodbye: remote regions must detect the failure through
            # the WAN failure detector, not a graceful LEFT
            self.wan_pool.stop()
            self.wan_pool = None
        if self._transport is not None:
            self._transport.deregister(f"rpc:{self.name}")

    # ------------------------------------------------------------- snapshots

    def save_snapshot(self, path: str) -> None:
        """Operator snapshot save (reference `nomad operator snapshot save`,
        helper/snapshot/)."""
        blob = self.fsm.snapshot()
        with open(path, "wb") as fh:
            pickle.dump({"index": self.store.latest_index,
                         "data": blob}, fh)

    def restore_snapshot(self, path: str) -> None:
        """Operator snapshot restore: replace state wholesale.  Dev-mode
        only — a clustered member restoring locally would diverge from its
        peers; clustered restore must flow through Raft's InstallSnapshot
        (the reference's operator restore goes through raft.Restore)."""
        if self.raft is not None:
            raise RuntimeError(
                "restore_snapshot on a clustered server would diverge "
                "from peers; restore the whole cluster from the snapshot "
                "via fresh data dirs instead")
        with open(path, "rb") as fh:
            rec = pickle.load(fh)
        self.fsm.restore(rec["data"])

    def _restore_evals(self) -> None:
        """On leadership: re-enqueue non-terminal evals (leader.go:572)."""
        for ev in self.store.evals():
            if ev.should_enqueue():
                self.broker.enqueue(ev.copy())
            elif ev.should_block():
                self.blocked_evals.block(ev.copy())
        # the missed-unblock indexes died with the old leader: a node that
        # recovered just before the failover is invisible to this tracker,
        # so a restored eval would block forever on its stale snapshot.
        # Give every restored eval one clean re-evaluation; the still
        # infeasible ones re-block with a fresh snapshot_index that this
        # leader's capacity watch covers.
        self.blocked_evals.unblock_once(self.store.latest_index)

    def _failed_eval_reaper(self, stop: threading.Event) -> None:
        """Mark dead-lettered evals failed and create follow-ups
        (leader.go:842-884)."""
        while not stop.is_set() and not self._stop.is_set():
            ev, token = self.broker.dequeue([FAILED_QUEUE], timeout=0.2)
            if ev is None:
                continue
            updated = ev.copy()
            updated.status = EvalStatus.FAILED
            updated.status_description = "maximum attempts reached"
            self.update_eval(updated)
            follow = Evaluation(
                namespace=ev.namespace, priority=ev.priority, type=ev.type,
                job_id=ev.job_id, triggered_by=EvalTrigger.FAILED_FOLLOW_UP,
                status=EvalStatus.PENDING,
                wait_until=_time.time() +
                self.config.failed_eval_followup_delay)
            self.create_evals([follow])
            self.broker.ack(ev.id, token)

    def _dup_blocked_reaper(self, stop: threading.Event) -> None:
        """Cancel duplicate blocked evals in the store (reference
        reapDupBlockedEvaluations, leader.go:815): the tracker keeps one
        blocked eval per job and drops the rest, but the dropped ones
        would otherwise sit BLOCKED in replicated state forever."""
        while not stop.wait(0.2):
            if self._stop.is_set():
                return
            for ev in self.blocked_evals.get_duplicates():
                cancelled = ev.copy()
                cancelled.status = EvalStatus.CANCELLED
                cancelled.status_description = \
                    "existing blocked evaluation exists for this job"
                try:
                    self.update_eval(cancelled)
                except Exception:               # noqa: BLE001
                    pass                        # deposed mid-write: drop

    def _gc_loop(self, stop: threading.Event) -> None:
        """Leader periodic GC timers (reference leader.go:782-810 core-job
        eval scheduling, here invoked directly)."""
        while not stop.wait(self.config.gc_interval):
            if self._stop.is_set():
                return
            try:
                self.core_scheduler.process("force-gc")
            except Exception:               # noqa: BLE001
                import logging
                logging.getLogger(__name__).exception("core gc")

    # ------------------------------------------------------------- watches

    def _on_state_change(self, table: str, obj) -> None:
        # alloc terminations free capacity: unblock that node's class
        if table == "allocs":
            a = obj
            if a.terminal_status():
                node = self.store.node_by_id(a.node_id)
                if node is not None:
                    self.blocked_evals.unblock(node.computed_class,
                                               self.store.latest_index)

    # ------------------------------------------------------------- API ops
    # (these are what the RPC endpoints call; reference nomad/job_endpoint.go,
    #  node_endpoint.go, eval_endpoint.go)

    def _create_preemption_evals(self, preempted) -> None:
        """One reschedule eval per job whose allocs were preempted
        (reference CreatePreemptionEvals, plan_apply.go:204+)."""
        seen = set()
        evals = []
        for a in preempted:
            key = (a.namespace, a.job_id)
            if key in seen:
                continue
            seen.add(key)
            job = a.job or self.store.job_by_id(a.namespace, a.job_id)
            if job is None or job.stopped():
                continue
            evals.append(Evaluation(
                namespace=a.namespace, priority=job.priority,
                type=job.type, job_id=job.id,
                triggered_by=EvalTrigger.PREEMPTION,
                status=EvalStatus.PENDING))
        if evals:
            self.create_evals(evals)

    def update_eval(self, ev: Evaluation) -> None:
        # timestamps ride in the log payload: the FSM must not read the
        # clock, or replicas/replay diverge (see nomad_tpu.analysis)
        ev.modify_time = _time.time()
        if not ev.create_time:
            ev.create_time = ev.modify_time
        self.apply(MessageType.EVAL_UPDATE, {"evals": [ev]})

    def create_evals(self, evals: List[Evaluation]) -> None:
        # pending evals are enqueued / blocked by the FSM's leader hook
        # (reference: fsm eval apply with the broker attached)
        now = _time.time()
        copies = []
        for e in evals:
            c = e.copy()
            c.modify_time = now
            if not c.create_time:
                c.create_time = now
            copies.append(c)
        # propose-time trace note: the broker enqueue happens inside the
        # FSM apply cone where nothing may stamp the clock, so a sampled
        # context crosses the broker through the tracer's note table
        # (see EvalBroker._pick_locked)
        tracing.note_evals(c.id for c in copies)
        self.apply(MessageType.EVAL_UPDATE, {"evals": copies})

    def register_job(self, job: Job) -> Evaluation:
        """Job.Register (nomad/job_endpoint.go:81): upsert + eval.  A job
        whose region is not ours forwards to that region's servers
        (job_endpoint.go forward via rpc.go forwardRegion); a region
        nobody has heard of is rejected outright — silently committing
        it locally (or forwarding it in a loop) would strand the job."""
        if job.multiregion is not None and job.multiregion.regions \
                and "multiregion.rollout" not in job.meta:
            return self._register_multiregion(job)
        if job.region and job.region != self.region:
            known = self.regions()
            if job.region not in known:
                from nomad_tpu.rpc.endpoints import RpcError
                raise RpcError(
                    "unknown_region",
                    f"job {job.id!r} submitted to unknown region "
                    f"{job.region!r} (known regions: "
                    f"{', '.join(known)})")
            resp = self.rpc_region(job.region, "Job.Register",
                                   {"job": job})
            return Evaluation(
                id=resp["eval_id"], namespace=job.namespace,
                job_id=job.id, type=job.type,
                triggered_by=EvalTrigger.JOB_REGISTER,
                status=EvalStatus.PENDING)
        ns = job.namespace or "default"
        if self.store.namespace(ns) is None:
            # same shape as the unknown-region rejection above: naming
            # the known set makes the typo obvious to the submitter
            from nomad_tpu.rpc.endpoints import RpcError
            known = sorted(n.name for n in self.store.namespaces())
            raise RpcError(
                "unknown_namespace",
                f"job {job.id!r} submitted to unknown namespace "
                f"{ns!r} (known namespaces: {', '.join(known)})")
        if not job.submit_time:
            job.submit_time = _time.time()   # propose-time, rides the log
        index = self.apply(MessageType.JOB_REGISTER, {"job": job})
        # when the write was forwarded, the leader mutated a pickled copy;
        # pull the committed indexes back onto the caller's object so the
        # eval (and the RPC response) carries the real job_modify_index
        self.store.wait_for_index(index)
        stored = self.store.job_by_id(job.namespace, job.id)
        if stored is not None:
            job.create_index = stored.create_index
            job.modify_index = stored.modify_index
            job.job_modify_index = stored.job_modify_index
            job.version = stored.version
        ev = Evaluation(
            namespace=job.namespace, priority=job.priority, type=job.type,
            job_id=job.id, triggered_by=EvalTrigger.JOB_REGISTER,
            status=EvalStatus.PENDING,
            job_modify_index=job.job_modify_index)
        ev.modify_index = job.modify_index
        if not job.is_periodic() and not job.is_parameterized():
            self.create_evals([ev])
        return ev

    def _register_multiregion(self, job: Job) -> Evaluation:
        """Expand a `multiregion` job into per-region copies and start
        the sequential rollout at the FIRST listed region (reference
        nomad/job_endpoint.go multiregion Register: later regions only
        deploy after the previous region's deployment is healthy — the
        deployment watcher kicks region N+1 when region N succeeds)."""
        regions = [r.name for r in job.multiregion.regions]
        known = self.regions()
        unknown = [r for r in regions if r not in known]
        if unknown:
            from nomad_tpu.rpc.endpoints import RpcError
            raise RpcError(
                "unknown_region",
                f"multiregion job {job.id!r} names unknown region(s) "
                f"{', '.join(repr(r) for r in unknown)} (known regions: "
                f"{', '.join(known)})")
        rollout = uuid.uuid4().hex
        first = job.multiregion_copy(regions[0], rollout)
        return self.register_job(first)

    def deregister_job(self, namespace: str, job_id: str, purge: bool = False) -> Optional[Evaluation]:
        job = self.store.job_by_id(namespace, job_id)
        if job is None:
            return None
        self.apply(MessageType.JOB_DEREGISTER,
                   {"namespace": namespace, "job_id": job_id, "purge": purge})
        self.blocked_evals.untrack(namespace, job_id)
        ev = Evaluation(
            namespace=namespace, priority=job.priority, type=job.type,
            job_id=job_id, triggered_by=EvalTrigger.JOB_DEREGISTER,
            status=EvalStatus.PENDING)
        self.create_evals([ev])
        return ev

    def scale_job(self, namespace: str, job_id: str, group: str,
                  count: Optional[int] = None, message: str = "",
                  error: bool = False, meta: Optional[dict] = None
                  ) -> Optional[Evaluation]:
        """Job.Scale (reference nomad/job_endpoint.go:967): adjust one
        task group's count within its scaling-policy bounds by
        registering the updated job (which creates the eval that
        reschedules), and record a ScalingEvent either way (error=True
        events are autoscaler annotations that never change counts)."""
        import time as _t

        from nomad_tpu.structs.job import ScalingEvent
        job = self.store.job_by_id(namespace, job_id)
        if job is None:
            raise ValueError(f"job {job_id!r} not found")
        tg = job.lookup_task_group(group)
        if tg is None:
            raise ValueError(
                f"task group {group!r} does not exist in job")
        prev = tg.count
        ev = None
        if count is not None and not error:
            if tg.scaling is not None and tg.scaling.enabled:
                if count < tg.scaling.min:
                    raise ValueError(
                        f"group count was less than scaling policy "
                        f"minimum: {count} < {tg.scaling.min}")
                if tg.scaling.max and count > tg.scaling.max:
                    raise ValueError(
                        f"group count was greater than scaling policy "
                        f"maximum: {count} > {tg.scaling.max}")
            new_job = job.copy()
            new_job.lookup_task_group(group).count = int(count)
            ev = self.register_job(new_job)
        event = ScalingEvent(
            time=_t.time(), previous_count=prev, count=count,
            message=message, error=error,
            eval_id=ev.id if ev is not None else "", meta=meta or {})
        self.apply(MessageType.SCALING_EVENT,
                   {"namespace": namespace, "job_id": job_id,
                    "group": group, "event": event})
        return ev

    def job_scale_status(self, namespace: str, job_id: str) -> Optional[dict]:
        """Job.ScaleStatus (job_endpoint.go:2038): desired vs placed vs
        healthy per group + the scaling-event log."""
        job = self.store.job_by_id(namespace, job_id)
        if job is None:
            return None
        allocs = self.store.allocs_by_job(namespace, job_id)
        events = self.store.scaling_events_by_job(namespace, job_id)
        groups = {}
        for tg in job.task_groups:
            live = [a for a in allocs if a.task_group == tg.name
                    and not a.terminal_status()]
            healthy = sum(1 for a in live if (a.deployment_status or {})
                          .get("healthy") is True)
            unhealthy = sum(1 for a in live if (a.deployment_status or {})
                            .get("healthy") is False)
            groups[tg.name] = {
                "desired": tg.count, "placed": len(live),
                "running": sum(1 for a in live
                               if a.client_status == "running"),
                "healthy": healthy, "unhealthy": unhealthy,
                "events": events.get(tg.name, []),
            }
        return {"job_id": job_id, "namespace": namespace,
                "job_modify_index": job.modify_index,
                "job_stopped": job.stopped(), "task_groups": groups}

    def set_job_stability(self, namespace: str, job_id: str, version: int,
                          stable: bool) -> None:
        self.apply(MessageType.JOB_STABILITY,
                   {"namespace": namespace, "job_id": job_id,
                    "version": version, "stable": stable})

    def register_node(self, node: Node) -> None:
        """Node.Register (nomad/node_endpoint.go:79).  The leader's FSM
        hook starts the TTL timer.  A re-registration whose device
        fingerprint marks instances unhealthy (the device plugin health
        stream, plugins/device/device.go:25-37) migrates the allocations
        holding those instances — dead hardware must not keep serving."""
        prev = self.store.node_by_id(node.id)
        if prev is not None and prev.secret_id and node.secret_id \
                and prev.secret_id != node.secret_id:
            # reference node_endpoint.go:141 — a re-registration may not
            # rotate another node's identity out from under it
            raise ValueError(f"node secret ID does not match: {node.id}")
        newly_bad: set = set()
        if prev is not None:
            prev_bad = {i for d in prev.node_resources.devices
                        for i in d.unhealthy_ids}
            now_bad = {i for d in node.node_resources.devices
                       for i in d.unhealthy_ids}
            newly_bad = now_bad - prev_bad
        self.apply(MessageType.NODE_REGISTER, {"node": node})
        if newly_bad:
            self._migrate_device_allocs(node.id, newly_bad)

    def _migrate_device_allocs(self, node_id: str, bad_ids: set) -> None:
        """DesiredTransition(force_reschedule) + eval for every alloc on
        the node holding a now-unhealthy device instance: the reconciler
        replaces it, and the replacement lands on healthy hardware
        because unhealthy instances carry no capacity."""
        from nomad_tpu.structs.alloc import DesiredTransition
        doomed = []
        for a in self.store.allocs_by_node(node_id):
            if a.terminal_status():
                continue
            held = {i for tr in a.allocated_resources.tasks.values()
                    for d in tr.devices
                    for i in d.get("device_ids", ())}
            if held & bad_ids:
                doomed.append(a)
        if not doomed:
            return
        for a in doomed:
            u = a.copy() if hasattr(a, "copy") else a
            # force_reschedule: migrate only moves allocs on DRAINING
            # nodes; a dead device on a healthy node needs the
            # unconditional replace path (the `nomad alloc stop` flow)
            u.desired_transition = DesiredTransition(force_reschedule=True)
            self.apply(MessageType.ALLOC_UPDATE_DESIRED_TRANSITION,
                       {"allocs": [u]})
        evs = []
        for (ns, job_id) in {(a.namespace, a.job_id) for a in doomed}:
            job = self.store.job_by_id(ns, job_id)
            if job is None:
                continue
            evs.append(Evaluation(
                namespace=ns, priority=job.priority, type=job.type,
                job_id=job_id, triggered_by=EvalTrigger.NODE_UPDATE,
                status=EvalStatus.PENDING))
        if evs:
            self.create_evals(evs)

    def node_heartbeat(self, node_id: str) -> float:
        """Node.UpdateStatus heartbeat path: reset TTL; a down node
        re-heartbeating is brought back to ready (init->ready handled by
        client re-registration).  TTL timers are leader-local soft state,
        so follower-received heartbeats forward (heartbeat.go:56)."""
        if self.raft is not None and not self.raft.is_leader:
            resp = self.rpc_leader("Node.UpdateStatus",
                                   {"node_id": node_id, "heartbeat": True})
            return resp["heartbeat_ttl"]
        node = self.store.node_by_id(node_id)
        if node is not None:
            if node.status in ("down", "disconnected"):
                # revival rides the heartbeat batch when it runs: one
                # coalesced FSM entry per flush tick, not one per node
                if self.heartbeat_batch.running:
                    self.heartbeat_batch.note(node_id, "ready")
                else:
                    self.update_node_status(node_id, "ready")
            elif self.heartbeat_batch.running:
                # periodic liveness stamp (rate-limited to half-TTL per
                # node inside the batcher) so a failed-over leader sees
                # reasonably fresh status_updated_at values
                self.heartbeat_batch.stamp(node_id, node.status)
        return self.heartbeats.heartbeat(node_id)

    def node_heartbeats(self, node_ids: List[str]) -> float:
        """Batched heartbeat for fleet-scale agent drivers: one
        forwarded RPC re-arms many TTLs; each node still takes the real
        node_heartbeat path (revival, liveness stamp, TTL wheel)."""
        if self.raft is not None and not self.raft.is_leader:
            resp = self.rpc_leader("Node.BatchHeartbeat",
                                   {"node_ids": list(node_ids)})
            return resp["heartbeat_ttl"]
        ttl = self.config.heartbeat_ttl
        for nid in node_ids:
            ttl = self.node_heartbeat(nid)
        return ttl

    def node_update_fingerprint(self, node_id: str, update: dict) -> dict:
        """Node.UpdateFingerprint: a device/attribute re-fingerprint
        DELTA from a registered client.  Rides the heartbeat batcher's
        coalesced write path (one NodeFingerprintBatch raft entry per
        flush tick) instead of a full Node.Register per change; an
        unknown node returns known=False so the client falls back to a
        full re-register."""
        if self.raft is not None and not self.raft.is_leader:
            args = dict(update)
            args["node_id"] = node_id
            return self.rpc_leader("Node.UpdateFingerprint", args)
        if self.store.node_by_id(node_id) is None:
            return {"known": False}
        payload = {k: v for k, v in update.items()
                   if k in ("devices", "attributes")}
        payload["node_id"] = node_id
        if self.heartbeat_batch.running:
            self.heartbeat_batch.note_fingerprint(node_id, payload)
        else:
            self.apply(MessageType.NODE_FINGERPRINT_BATCH,
                       {"updates": [payload]})
        return {"known": True}

    def update_node_status(self, node_id: str, status: str) -> List[Evaluation]:
        """Node.UpdateStatus: transition + evals for affected jobs."""
        self.apply(MessageType.NODE_UPDATE_STATUS,
                   {"node_id": node_id, "status": status,
                    "updated_at": _time.time()})
        return self.create_node_evals(node_id)

    def create_node_evals(self, node_id: str) -> List[Evaluation]:
        """Evaluate all jobs with allocs on the node plus system jobs
        (reference createNodeEvals, node_endpoint.go)."""
        evals = []
        seen = set()
        for a in self.store.allocs_by_node(node_id):
            job = a.job or self.store.job_by_id(a.namespace, a.job_id)
            if job is None or job.id in seen:
                continue
            seen.add(job.id)
            evals.append(Evaluation(
                namespace=a.namespace, priority=job.priority, type=job.type,
                job_id=job.id, triggered_by=EvalTrigger.NODE_UPDATE,
                node_id=node_id, status=EvalStatus.PENDING,
                modify_index=self.store.latest_index))
        for job in self.store.jobs():
            if job.type in (JobType.SYSTEM, JobType.SYSBATCH) \
                    and job.id not in seen and not job.stopped():
                seen.add(job.id)
                evals.append(Evaluation(
                    namespace=job.namespace, priority=job.priority,
                    type=job.type, job_id=job.id,
                    triggered_by=EvalTrigger.NODE_UPDATE, node_id=node_id,
                    status=EvalStatus.PENDING,
                    modify_index=self.store.latest_index))
        if evals:
            self.create_evals(evals)
        return evals

    # ------------------------------------------------------------- ACL

    acl_enabled = False

    def enable_acl(self) -> None:
        """Turn on ACL enforcement (reference acl block in agent config)."""
        self.acl_enabled = True

    def resolve_token(self, secret_id: str):
        """SecretID -> compiled ACL (reference nomad/acl.go ResolveToken).
        Anonymous (empty) tokens get the 'anonymous' policy if present."""
        from nomad_tpu.acl import ACL, parse_policy
        if not secret_id:
            anon = self.store.acl_policy("anonymous")
            if anon is None:
                return None
            return ACL(policies=[anon])
        token = self.store.acl_token_by_secret(secret_id)
        if token is None:
            return None
        if token.type == "management":
            return ACL(management=True)
        policies = [self.store.acl_policy(p) for p in token.policies]
        return ACL(policies=[p for p in policies if p is not None])

    def bootstrap_acl(self):
        """One-time management token mint (reference ACL.Bootstrap).
        The uniqueness invariant is enforced inside the replicated FSM
        apply (a losing concurrent bootstrap is dropped there), so after
        the commit we verify our token actually landed."""
        from nomad_tpu.acl import ACLToken
        t = ACLToken(name="Bootstrap Token", type="management",
                     global_=True)
        index = self.apply(MessageType.ACL_TOKEN_UPSERT,
                           {"token": t, "bootstrap": True})
        self.store.wait_for_index(index)
        if self.store.acl_token(t.accessor_id) is None:
            raise RuntimeError("ACL already bootstrapped")
        return t

    def upsert_acl_policy(self, name: str, description: str, rules: str):
        from nomad_tpu.acl import parse_policy
        policy = parse_policy(name, rules, description)
        self.apply(MessageType.ACL_POLICY_UPSERT, {"policy": policy})
        return policy

    def delete_acl_policy(self, name: str) -> None:
        self.apply(MessageType.ACL_POLICY_DELETE, {"name": name})

    def acl_policies(self):
        return self.store.acl_policies()

    def acl_policy(self, name: str):
        return self.store.acl_policy(name)

    def create_acl_token(self, name: str = "", type_: str = "client",
                         policies=None):
        from nomad_tpu.acl import ACLToken
        t = ACLToken(name=name, type=type_, policies=list(policies or []))
        self.apply(MessageType.ACL_TOKEN_UPSERT, {"token": t})
        return t

    def delete_acl_token(self, accessor_id: str) -> None:
        self.apply(MessageType.ACL_TOKEN_DELETE,
                   {"accessor_id": accessor_id})

    def acl_tokens(self):
        return self.store.acl_tokens()

    def acl_token(self, accessor_id: str):
        return self.store.acl_token(accessor_id)

    def acl_token_by_secret(self, secret_id: str):
        return self.store.acl_token_by_secret(secret_id)

    # ------------------------------------------------------------- namespaces

    def namespaces(self):
        return self.store.namespaces()

    def namespace(self, name: str):
        return self.store.namespace(name)

    def upsert_namespace(self, name: str, description: str = "",
                         quota: str = "") -> None:
        if quota and self.store.quota_spec(quota) is None:
            raise ValueError(f"quota spec {quota!r} does not exist")
        self.apply(MessageType.NAMESPACE_UPSERT,
                   {"name": name, "description": description,
                    "quota": quota})

    def delete_namespace(self, name: str) -> None:
        self.apply(MessageType.NAMESPACE_DELETE, {"name": name})

    # ------------------------------------------------------------- quotas

    def upsert_quota_spec(self, spec) -> None:
        self.apply(MessageType.QUOTA_SPEC_UPSERT, {"spec": spec})

    def delete_quota_spec(self, name: str) -> None:
        # propose-time guard mirrors the FSM's authoritative check so the
        # caller gets the error without burning a log entry
        for ns in self.store.namespaces():
            if ns.quota == name:
                raise ValueError(
                    f"quota {name!r} referenced by namespace {ns.name!r}")
        self.apply(MessageType.QUOTA_SPEC_DELETE, {"name": name})

    def quota_specs(self):
        return self.store.quota_specs()

    def quota_spec(self, name: str):
        return self.store.quota_spec(name)

    def quota_usage(self, namespace: str):
        return self.store.quota_usage(namespace)

    def quota_usages(self):
        return self.store.quota_usages()

    # ------------------------------------------------------------- helpers

    def wait_for_idle(self, timeout: float = 10.0) -> bool:
        """Testing/bench helper: wait until no evals are queued or in
        flight."""
        deadline = _time.time() + timeout
        while _time.time() < deadline:
            if (self.broker.ready_count() == 0
                    and self.broker.unacked_count() == 0
                    and self.plan_queue.depth() == 0):
                return True
            _time.sleep(0.01)
        return False
