"""Raft consensus node (reference: vendored hashicorp/raft as wired in
nomad/server.go:107-111 — elections, log replication, commit, snapshot
install, log compaction).

A compact, threaded Raft: follower/candidate/leader states with randomized
election timeouts, AppendEntries consistency checks, majority commit, an
apply loop feeding the NomadFSM, and a streamed, resumable, CRC-framed
InstallSnapshot (dissertation §7 offset/done framing) for followers that
fell behind a compaction — chunk transfers run on their own threads, off
the replication tick, and resume from the follower's acked offset across
drops, restarts and leader changes.  Designed for in-process clusters over
InMemTransport (the reference's raftInmem test mode) — the production
transport boundary is the same `call(dst, method, args)` surface.

Dynamic membership (Raft §4.1, single-server changes): the cluster
configuration — voters plus catch-up non-voters — is itself replicated
as `RaftConfiguration` log entries carried in the WAL and snapshots.
Each entry holds the complete resulting configuration, takes effect on
APPEND (not commit), and only one change may be in flight at a time, so
quorum arithmetic is always computed against the latest appended
configuration and a half-replicated AddVoter already raises the commit
bar.  `add_server`/`remove_server` are the leader-side API; a blank
server boots with `join=True` (empty configuration, never campaigns)
and learns the membership from the entries or snapshot the leader
streams it.  Leadership transfer (`transfer_leadership` → TimeoutNow,
§3.10) fences new proposals, brings the target current, and tells it to
campaign immediately — transfer votes bypass pre-vote and leader
stickiness so the handoff completes in milliseconds.
"""
from __future__ import annotations

import concurrent.futures
import logging
import os
import pickle
import queue
import random
import threading
import time
import zlib
from typing import Callable, Dict, List, Optional, Tuple

from nomad_tpu import chaos, knobs, tracing
from nomad_tpu.analysis import race
from nomad_tpu.raft.integrity import IntegrityTracker
from nomad_tpu.raft.log import LogEntry, LogStore
from nomad_tpu.raft.meta import DurableMeta, MetaPersistError
from nomad_tpu.raft.snapshot import ChunkSink, FileSnapshotStore
from nomad_tpu.raft.transport import InMemTransport, Unreachable
from nomad_tpu.state import digest as state_digest
from nomad_tpu.telemetry import global_metrics
from nomad_tpu.utils import requires_lock

log = logging.getLogger(__name__)

FOLLOWER, CANDIDATE, LEADER = "follower", "candidate", "leader"

# InstallSnapshot stream frame size (Raft dissertation §7 offset/done
# framing); NOMAD_TPU_SNAP_CHUNK overrides
SNAP_CHUNK_DEFAULT = 256 * 1024

# frames of snapshot blob a sender buffers off disk per peer stream
# (NOMAD_TPU_SNAP_WINDOW overrides): peak sender memory per stream is
# window * chunk, independent of snapshot size
SNAP_WINDOW_DEFAULT = 8

# log entry type carrying a full cluster configuration (Raft §4.1);
# dispatched as a no-op by the FSM — the raft layer consumes it on append
CONFIGURATION_MSG = "RaftConfiguration"

# log entry type carrying an integrity checkpoint (Paxos-Made-Live
# log-stamped state checksums): a no-op for the FSM — the apply loop
# computes the per-table digest when the entry applies, so every
# replica stamps the SAME log position
STATE_CHECKPOINT_MSG = "StateCheckpointRequest"

# entry types the fsm.apply_skip chaos point never skips: skipping a
# no-op cannot create state divergence, and skipping the checkpoint
# itself would blind the very detector the drill is exercising
_APPLY_SKIP_EXEMPT = frozenset({
    "Noop", STATE_CHECKPOINT_MSG, CONFIGURATION_MSG})


class NotLeaderError(Exception):
    def __init__(self, leader: Optional[str] = None):
        super().__init__(f"not the leader (leader={leader})")
        self.leader = leader


class ConfigurationInFlightError(Exception):
    """A membership change is already appended but not yet committed.
    Raft §4.1 allows exactly one configuration change in flight at a
    time; retry once the pending entry commits."""


class _ReadBatch:
    """One leadership-confirmation round shared by every reader that
    joined before its probes went out (reference raft ReadOnlyQueue
    batching): the first reader runs the heartbeat quorum round,
    concurrent readers wait on `event`.  Each reader captures its OWN
    commit index at arrival — the shared round only proves leadership,
    and it proves it for all of them because every probe ack happens
    after the last joiner's capture."""

    __slots__ = ("ok", "event")

    def __init__(self):
        self.ok = False             # quorum confirmed leadership at our term
        self.event = threading.Event()


class RaftConfig:
    def __init__(self,
                 heartbeat_interval: float = 0.05,
                 election_timeout: float = 0.2,
                 snapshot_threshold: int = 2048,
                 max_append_entries: int = 128,
                 lease_clock_skew: float = 0.25):
        self.heartbeat_interval = heartbeat_interval
        self.election_timeout = election_timeout
        self.snapshot_threshold = snapshot_threshold
        self.max_append_entries = max_append_entries
        # leader-lease safety margin: a lease anchored at a quorum ack
        # round lasts election_timeout * (1 - skew).  Stickiness means a
        # new leader needs a full election_timeout of quorum silence
        # first, so with any skew > 0 a deposed leader's lease expires
        # strictly before a successor can win — even with clocks drifting
        # by up to `lease_clock_skew` of the timeout (reference
        # consul/nomad LeaderLeaseTimeout < ElectionTimeout).
        self.lease_clock_skew = lease_clock_skew


class RaftNode:
    # membership configuration tables: every access happens under
    # `self._lock` (lexical `with`, or @requires_lock helpers whose
    # callers hold it); `_apply_cv` is a Condition over the same RLock
    _LOCK_NAME = "_lock"
    _LOCK_ALIASES = ("_apply_cv",)
    _LOCK_PROTECTED = frozenset({"_voters", "_nonvoters"})
    _RACE_TRACED = {"_voters": "_lock"}
    # wait-graph (nomad_tpu.analysis)
    _LOCK_BLOCKING_OK = {
        "_lock": "raft persist-before-respond: term/vote/log entries "
                 "must hit disk under the state lock before any RPC "
                 "reply or role transition (election/RPC timeouts "
                 "bound the stall)",
    }

    def __init__(self, name: str, peers: List[str],
                 transport: InMemTransport, fsm,
                 config: Optional[RaftConfig] = None,
                 log_store: Optional[LogStore] = None,
                 snapshots: Optional[FileSnapshotStore] = None,
                 meta: Optional[DurableMeta] = None,
                 on_leader: Optional[Callable[[], None]] = None,
                 on_follower: Optional[Callable[[], None]] = None,
                 join: bool = False):
        self.name = name
        # Cluster configuration (Raft §4.1): `_voters` take part in
        # elections/quorum/leases; `_nonvoters` only receive replication
        # while they catch up.  A joining server starts with an EMPTY
        # configuration — it never campaigns and learns the membership
        # from the leader's log/snapshot.  `peers` stays the replication
        # target list (everyone but us) for compatibility.
        self._initial_voters = [] if join else sorted(set(peers) | {name})
        self._voters: List[str] = list(self._initial_voters)
        self._nonvoters: List[str] = []
        self._config_index = 0
        self._snap_config: Optional[dict] = None
        self.peers = [p for p in self._voters if p != name]
        self.transport = transport
        self.fsm = fsm
        self.config = config or RaftConfig()
        self.log = log_store or LogStore()
        self.snapshots = snapshots
        self.meta = meta
        self.on_leader = on_leader
        self.on_follower = on_follower

        self._lock = threading.RLock()
        self.state = FOLLOWER
        # term + vote come back from stable storage (Raft Figure 2): a
        # restarted node that voted this term must still remember it
        self.term = meta.term if meta is not None else 0
        self.voted_for: Optional[str] = \
            meta.voted_for if meta is not None else None
        self.leader_id: Optional[str] = None
        self.commit_index = 0
        self.last_applied = 0
        self._last_snapshot_index = 0
        self._last_snap_term = 0
        # outbound snapshot streams (leader): peer -> worker thread, so
        # the chunk loop runs OFF the replication tick and heartbeats to
        # healthy peers never queue behind a catch-up transfer; plus a
        # bounded-backoff table for peers whose installs keep failing
        self._snap_streams: Dict[str, threading.Thread] = {}
        self._snap_backoff: Dict[str, Tuple[int, float]] = {}
        # inbound chunk stream (follower): at most one partial sink at a
        # time, keyed by snapshot identity so a new leader resuming the
        # SAME snapshot continues where the dead one stopped
        self._snap_rx: Optional[ChunkSink] = None
        self._next_index: Dict[str, int] = {}
        self._match_index: Dict[str, int] = {}
        self._futures: Dict[int, concurrent.futures.Future] = {}
        # tracing side table (guarded by _lock): log index -> sampled
        # trace context, noted at propose time on the proposing node so
        # the apply thread can emit the fsm-apply span at observe time.
        # Context never rides in log payloads (FSM byte-identity).
        self._trace_notes: Dict[int, dict] = {}
        self._last_contact = time.monotonic()
        # autopilot health inputs: when the leader last successfully
        # replicated to each peer (append ack or snapshot install)
        self._peer_contact: Dict[str, float] = {}
        # leadership transfer: while set, apply() refuses new proposals
        # and points callers at the target (it will be leader in ms)
        self._transfer_target: Optional[str] = None
        # leader lease (read path): _ack_round_start[peer] is the send
        # time of the last append round that peer successfully acked; the
        # lease anchors at the majority-th newest of those (self counts as
        # "now") and extends election_timeout * (1 - lease_clock_skew)
        self._ack_round_start: Dict[str, float] = {}
        self._lease_until = 0.0
        self._read_batch: Optional[_ReadBatch] = None
        # one confirmation round in flight at a time: while it runs, the
        # next batch stays open and accumulates joiners (their captured
        # indexes all precede that batch's probes)
        self._round_lock = threading.Lock()
        self.read_rounds = 0        # confirmation rounds run (telemetry)
        self._stop = threading.Event()
        # commit advancement wakes the ticker (hashicorp/raft's per-peer
        # notify channel): followers learn the new commit index on an
        # immediate round instead of waiting out the heartbeat interval,
        # which is what keeps follower read-index waits short under load
        self._commit_event = threading.Event()
        self._apply_cv = threading.Condition(self._lock)
        self._fsm_lock = threading.Lock()   # serializes fsm.apply/restore
        # leadership transitions execute strictly in order through one
        # dispatcher thread (an unordered establish/revoke pair would leave
        # a follower running leader-only subsystems)
        self._leadership_q: "queue.Queue[str]" = queue.Queue()
        self._threads: List[threading.Thread] = []

        # replica-integrity plane: per-table digest cache fed by FSM
        # apply hooks, checkpoint vote state (leader), quarantine flag
        self.integrity = IntegrityTracker(self)
        if hasattr(fsm, "dirty_hook"):
            fsm.dirty_hook = self.integrity.note_dirty

        # restart recovery: restore the snapshot (committed state only).
        # The persisted log tail is NOT replayed into the FSM here — those
        # entries may be uncommitted and could be truncated by a new
        # leader; they apply normally once a leader advances commit_index
        # (its post-election no-op commits the whole prefix).
        if self.snapshots is not None:
            rec = self.snapshots.latest_full()
            if rec is not None:
                self.fsm.restore(rec["data"])
                self.last_applied = rec["index"]
                self.commit_index = rec["index"]
                self._last_snapshot_index = rec["index"]
                self._last_snap_term = rec["term"]
                self._snap_config = rec.get("config")

        # entries already in the WAL at boot are recovery replay, not
        # live traffic: the divergence chaos points skip them (an armed
        # fsm.apply_skip firing inside replay would corrupt whichever
        # early entry happens to re-apply first, and two churn restarts
        # replaying the same prefix could then manufacture a corrupt
        # MAJORITY that outvotes the one still-healthy replica)
        self._boot_log_end = self.log.last_index

        # the configuration is part of replicated state: recover the
        # latest one from snapshot / log tail / durable meta — an
        # uncommitted config entry in the WAL is still effective (§4.1,
        # effective on append survives restart)
        with self._lock:
            self._recompute_config(include_meta=True)

        transport.register(name, self._handle_rpc)

    # ------------------------------------------------------------- lifecycle

    def start(self) -> None:
        for target, nm in ((self._run_ticker, "raft-tick"),
                           (self._run_apply, "raft-apply"),
                           (self._run_leadership, "raft-leadership")):
            t = threading.Thread(target=target,
                                 name=f"{nm}-{self.name}", daemon=True)
            t.start()
            self._threads.append(t)

    def stop(self) -> None:
        self._stop.set()
        self._commit_event.set()      # unblock a ticker mid-wait
        with self._apply_cv:
            self._apply_cv.notify_all()
        self.transport.deregister(self.name)
        for t in self._threads:
            t.join(1.0)
        self.log.close()

    def crash(self) -> None:
        """Hard-kill (power loss) simulation for durability soaks: threads
        stop and the WAL loses its unsynced tail — possibly tearing the
        record being appended (chaos `disk.torn_write`).  The meta and
        snapshot files are left exactly as last durably written; restart
        by constructing a fresh node over the same paths."""
        self._stop.set()
        self._commit_event.set()
        with self._apply_cv:
            self._apply_cv.notify_all()
        self.transport.deregister(self.name)
        for t in self._threads:
            t.join(1.0)
        self.log.simulate_crash()

    # --------------------------------------------------------- stable meta

    def _persist_meta(self) -> bool:
        """Write (term, voted_for) to stable storage; True on success.
        Callers gate durability-critical actions (granting a vote,
        launching a candidacy) on the result."""
        if self.meta is None:
            return True
        try:
            self.meta.persist(self.term, self.voted_for)
            return True
        except MetaPersistError:
            log.warning("raft: %s could not persist term/vote; refusing "
                        "the action that required it", self.name,
                        exc_info=True)
            return False

    # ----------------------------------------------------- configuration

    @requires_lock("_lock")
    def _quorum(self) -> int:
        """Votes/acks needed for a majority of the CURRENT voter set."""
        return len(self._voters) // 2 + 1 if self._voters else 1

    @requires_lock("_lock")
    def _sole_voter(self) -> bool:
        """True when we are the only voter (non-voters may still exist):
        commit, leases and reads need no network round."""
        return self._voters == [self.name]

    @requires_lock("_lock")
    def _set_config(self, voters, nonvoters, index: int) -> None:
        """Adopt a configuration (effective on append).  Recomputes the
        replication target list and prunes per-peer state for servers
        that left; best-effort mirrors the config into durable meta as a
        recovery belt alongside WAL + snapshot carriage."""
        race.write("RaftNode._voters", self)
        self._voters = sorted(set(voters))
        self._nonvoters = sorted(set(nonvoters) - set(voters))
        self._config_index = index
        self.peers = sorted((set(self._voters) | set(self._nonvoters))
                            - {self.name})
        live = set(self.peers)
        for table in (self._next_index, self._match_index,
                      self._ack_round_start, self._peer_contact):
            for k in list(table):
                if k != self.name and k not in live:
                    table.pop(k, None)
        if self.state == LEADER:
            nxt = self.log.last_index + 1
            for p in self.peers:
                self._next_index.setdefault(p, nxt)
                self._match_index.setdefault(p, 0)
        if self.meta is not None:
            try:
                self.meta.persist_config(
                    {"voters": list(self._voters),
                     "nonvoters": list(self._nonvoters), "index": index})
            except MetaPersistError:
                # WAL + snapshot still carry the config; meta is a
                # recovery convenience, not the durability anchor
                log.warning("raft: %s could not mirror configuration to "
                            "meta", self.name, exc_info=True)

    @requires_lock("_lock")
    def _recompute_config(self, include_meta: bool = False) -> None:
        """Rebuild the effective configuration from what storage actually
        holds: the newest of (initial static config, snapshot config,
        config entries still in the log[, durable-meta mirror]).  Used at
        boot and after a follower truncates a conflicting suffix that may
        have carried the configuration it was running."""
        best = {"voters": list(self._initial_voters), "nonvoters": [],
                "index": 0}
        for cand in ((self._snap_config,
                      self.meta.config if include_meta
                      and self.meta is not None else None)):
            if cand and cand.get("index", 0) >= best["index"]:
                best = cand
        for e in self.log.entries_of_type(CONFIGURATION_MSG):
            if e.index >= best["index"]:
                best = {"voters": list(e.payload["voters"]),
                        "nonvoters": list(e.payload["nonvoters"]),
                        "index": e.index}
        self._set_config(best["voters"], best.get("nonvoters", []),
                         best.get("index", 0))

    @requires_lock("_lock")
    def _config_at(self, index: int) -> Optional[dict]:
        """The configuration as of log `index` (for snapshot carriage):
        the newest config entry at or below it, else the snapshot's own
        config, else the initial static config."""
        best = self._snap_config
        for e in self.log.entries_of_type(CONFIGURATION_MSG):
            if e.index <= index and (best is None
                                     or e.index >= best.get("index", 0)):
                best = {"voters": list(e.payload["voters"]),
                        "nonvoters": list(e.payload["nonvoters"]),
                        "index": e.index}
        if best is None and self._initial_voters:
            best = {"voters": list(self._initial_voters), "nonvoters": [],
                    "index": 0}
        return best

    def configuration(self) -> dict:
        """Operator view of the replicated membership (the
        `/v1/operator/raft/configuration` payload)."""
        with self._lock:
            race.read("RaftNode._voters", self)
            return {"voters": list(self._voters),
                    "nonvoters": list(self._nonvoters),
                    "index": self._config_index,
                    "leader": self.leader_id,
                    "term": self.term}

    def add_server(self, server: str, voter: bool = False,
                   timeout: float = 10.0) -> int:
        """AddVoter / AddNonvoter (leader only).  New servers normally
        join as non-voters and are promoted (`voter=True` on an existing
        non-voter) once the autopilot health gate passes; adding straight
        to voter is allowed but raises the quorum bar immediately."""
        with self._lock:
            if self.state != LEADER:
                raise NotLeaderError(self.leader_id)
            voters, nonvoters = set(self._voters), set(self._nonvoters)
            if voter:
                if server in voters:
                    return self._config_index
                voters.add(server)
                nonvoters.discard(server)
            else:
                if server in voters or server in nonvoters:
                    return self._config_index
                nonvoters.add(server)
        return self._append_config(sorted(voters), sorted(nonvoters),
                                   timeout)

    def remove_server(self, server: str, timeout: float = 10.0) -> int:
        """RemoveServer (leader only).  Removing the leader itself is
        transfer-then-demote: hand leadership off first, then let the
        caller retry against the successor (which performs the actual
        removal) — the deposed leader never has to commit its own
        removal under a quorum it no longer anchors.  If no transfer
        target exists the leader commits its own removal and steps down
        once the entry applies (Raft §4.2.2)."""
        with self._lock:
            self_removal = self.state == LEADER and server == self.name
        if self_removal and self.transfer_leadership():
            with self._lock:
                raise NotLeaderError(self.leader_id)
        with self._lock:
            if self.state != LEADER:
                raise NotLeaderError(self.leader_id)
            voters, nonvoters = set(self._voters), set(self._nonvoters)
            if server not in voters and server not in nonvoters:
                return self._config_index
            if voters == {server}:
                raise ValueError("cannot remove the last voter")
            voters.discard(server)
            nonvoters.discard(server)
        return self._append_config(sorted(voters), sorted(nonvoters),
                                   timeout)

    def _append_config(self, voters: List[str], nonvoters: List[str],
                       timeout: float) -> int:
        """Append one configuration entry and wait for it to commit.
        Enforces the §4.1 one-change-in-flight rule; the new config is
        effective the moment the entry is appended, BEFORE it commits."""
        with self._lock:
            if self.state != LEADER:
                raise NotLeaderError(self.leader_id)
            if self._transfer_target is not None:
                raise NotLeaderError(self._transfer_target)
            if self._config_index > self.commit_index:
                raise ConfigurationInFlightError(
                    f"configuration change at index {self._config_index} "
                    f"is not yet committed (commit={self.commit_index})")
            if chaos.active is not None \
                    and chaos.should("raft.config_conflict"):
                raise ConfigurationInFlightError(
                    "chaos: injected configuration conflict")
            index = self.log.last_index + 1
            self.log.append(LogEntry(index, self.term, CONFIGURATION_MSG,
                                     {"voters": list(voters),
                                      "nonvoters": list(nonvoters)}))
            self._set_config(voters, nonvoters, index)
            self._match_index[self.name] = index
            fut: concurrent.futures.Future = concurrent.futures.Future()
            self._futures[index] = fut
            self._advance_commit()     # sole-voter configs commit locally
        self._replicate_all()
        fut.result(timeout=timeout)
        return index

    def server_healthy(self, server: str, lag: int = 16) -> bool:
        """Autopilot promotion gate (leader only): we heard an ack from
        the server within one election timeout AND its log is within
        `lag` entries of ours — the stabilization window the caller
        enforces on top makes a flapping server re-earn both."""
        with self._lock:
            if self.state != LEADER:
                return False
            fresh = (time.monotonic() - self._peer_contact.get(server, 0.0)
                     < self.config.election_timeout)
            caught = self._match_index.get(server, 0) \
                >= self.log.last_index - lag
        if self.integrity.peer_divergent(server):
            # a digest-convicted replica is never promoted, whatever its
            # log position — it re-earns health via verified repair
            return False
        return fresh and caught

    # ----------------------------------------------------------- transfer

    def transfer_leadership(self, target: Optional[str] = None,
                            timeout: Optional[float] = None) -> bool:
        """Graceful handoff (Raft §3.10 / TimeoutNow).  Fences new
        proposals, brings the target fully current, then tells it to
        campaign immediately — its RequestVote carries `transfer: True`,
        bypassing pre-vote and leader stickiness, so the handoff lands in
        milliseconds instead of an election timeout.  Returns True once
        we observe our own deposition (the successor's higher term);
        False re-arms normal proposal service."""
        if timeout is None:
            timeout = self.config.election_timeout * 3
        deadline = time.monotonic() + timeout
        with self._lock:
            if self.state != LEADER:
                raise NotLeaderError(self.leader_id)
            candidates = [v for v in self._voters if v != self.name]
            if target is None:
                if not candidates:
                    return False
                target = max(candidates,
                             key=lambda p: self._match_index.get(p, 0))
            elif target not in candidates:
                raise ValueError(f"transfer target {target!r} is not a "
                                 f"voter")
            self._transfer_target = target
            term = self.term
        try:
            while True:
                with self._lock:
                    if self.state != LEADER or self.term != term:
                        return False
                    caught = self._match_index.get(target, 0) \
                        >= self.log.last_index
                if caught:
                    break
                if time.monotonic() >= deadline:
                    return False
                try:
                    self._replicate_one(target)
                except Unreachable:
                    return False     # target gone: resume normal duty
                except Exception:                   # noqa: BLE001
                    log.warning("raft: %s transfer catch-up to %s failed",
                                self.name, target, exc_info=True)
                time.sleep(0.002)
            if chaos.active is not None and chaos.should("transfer.timeout"):
                # injected: the TimeoutNow never reaches the target; the
                # caller falls back to a normal election timeout
                return False
            try:
                resp = self.transport.call(self.name, target, "timeout_now",
                                           {"term": term,
                                            "leader": self.name})
            except Exception:                       # noqa: BLE001
                return False
            if not resp.get("success"):
                return False
            # success manifests as our own deposition: the target's
            # higher-term RequestVote (or its first heartbeat) steps us
            # down; wait out the deadline for it
            while time.monotonic() < deadline:
                with self._lock:
                    if self.state != LEADER or self.term != term:
                        return True
                time.sleep(0.002)
            return False
        finally:
            with self._lock:
                if self._transfer_target == target:
                    self._transfer_target = None

    # ------------------------------------------------------------- public

    @property
    def is_leader(self) -> bool:
        with self._lock:
            return self.state == LEADER

    def apply(self, msg_type: str, payload,
              timeout: float = 10.0) -> int:
        """Append + replicate + commit + FSM-apply one entry; returns its
        log index (reference raft.Apply)."""
        with self._lock:
            if self.state != LEADER:
                raise NotLeaderError(self.leader_id)
            if self._transfer_target is not None:
                # transferring: stop taking proposals so the target can
                # catch up to a FIXED last_index; it will be leader in ms
                raise NotLeaderError(self._transfer_target)
            index = self.log.last_index + 1
            # The local propose path must have the same wire-faithful copy
            # semantics as a forwarded RPC (InMemTransport pickles args and
            # results): the leader's log entry is a private copy, so later
            # caller-side mutation of the proposal can never alias FSM state.
            entry = LogEntry(index, self.term, msg_type,
                             pickle.loads(pickle.dumps(payload)))
            # propose-time: the WAL append (including its fsync) is a
            # span, and the index->context note lets _run_apply open the
            # fsm-apply span under the proposer's sampled context
            # without touching the payload
            with tracing.span("raft.append", node=self.name, index=index):
                self.log.append(entry)
            tctx = tracing.current()
            if tctx is not None:
                if len(self._trace_notes) > 1024:
                    self._trace_notes.clear()   # leadership-churn strays
                self._trace_notes[index] = tctx
            self._match_index[self.name] = index
            fut: concurrent.futures.Future = concurrent.futures.Future()
            self._futures[index] = fut
            self._advance_commit()    # sole-voter clusters commit locally
        # replicate + quorum commit + local FSM apply wait
        with tracing.span("raft.commit", wait=True, node=self.name,
                          index=index):
            self._replicate_all()
            fut.result(timeout=timeout)
        return index

    def proposal_depth(self) -> int:
        """In-flight proposal count (appended, not yet applied) — the
        brownout monitor's overload signal.  A bare len() read: the
        sampled signal tolerates staleness, so no lock is taken."""
        return len(self._futures)

    def barrier(self, timeout: float = 10.0) -> None:
        """Flush the log and wait for it to apply locally (best-effort).

        On a leader this pushes a no-op through the full append/commit/
        apply path (hashicorp/raft Barrier): when it returns, every entry
        committed before the call has been applied — including prior-term
        entries that only BECOME committed via a new-term write.  The
        plain commit_index wait is not enough for a fresh leader: its
        commit_index can lag entries a deposed leader already replicated
        to a majority, and acting on pre-barrier state (e.g. restoring
        evals) would miss their effects."""
        if self.is_leader:
            try:
                self.apply("Noop", None, timeout=timeout)
                return   # future resolves only after local FSM apply
            except Exception:                       # noqa: BLE001
                pass     # deposed or timed out: fall back to local wait
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            with self._lock:
                if self.last_applied >= self.commit_index:
                    return
            time.sleep(0.005)

    # ------------------------------------------------------------- reads

    def read_index(self, timeout: float = 5.0,
                   lease_ok: bool = True) -> int:
        """Linearizable read point (Raft §6.4 ReadIndex + leader lease).

        On the leader: return commit_index after proving leadership — via
        a still-valid lease (zero network rounds, `lease_ok=True`) or one
        empty-AppendEntries quorum round shared by every reader that
        arrives while it runs.  `lease_ok=False` (the `?consistent` mode)
        always pays the round.  On a follower: raises NotLeaderError —
        the serving gate forwards to the leader, then waits locally via
        `wait_applied(index)`."""
        deadline = time.monotonic() + timeout
        with self._lock:
            if self.state != LEADER:
                raise NotLeaderError(self.leader_id)
            if chaos.should("read.lease_expire"):
                self._lease_until = 0.0
            if lease_ok and (self._sole_voter()
                             or time.monotonic() < self._lease_until):
                return self.commit_index
            if self._sole_voter():
                return self.commit_index   # single voter: trivially leader
            # every reader serves at the commit index as of ITS arrival
            # (etcd's readOnly queue): joining an in-flight batch must not
            # hand back an index captured before a write this caller may
            # already have seen acknowledged
            index = self.commit_index
            batch = self._read_batch
            runs_round = batch is None
            if runs_round:
                batch = self._read_batch = _ReadBatch()
            term = self.term
        if runs_round:
            # the round lock serializes confirmation rounds: while a prior
            # round runs, this batch stays published and keeps collecting
            # joiners, and every probe ack below lands strictly after each
            # joiner captured its index — the ordering that lets one
            # shared round confirm all of them
            locked = self._round_lock.acquire(
                timeout=max(0.0, deadline - time.monotonic()))
            try:
                with self._lock:
                    if self._read_batch is batch:
                        self._read_batch = None   # closed: probes start now
                    live = self.state == LEADER and self.term == term
                if live:
                    self._confirm_leadership(batch, term)
            finally:
                if locked:
                    self._round_lock.release()
                batch.event.set()
        else:
            batch.event.wait(max(0.0, deadline - time.monotonic()))
        if not batch.event.is_set():
            raise TimeoutError("raft: read_index confirmation timed out")
        if not batch.ok:
            with self._lock:
                raise NotLeaderError(self.leader_id)
        return index

    def _confirm_leadership(self, batch: _ReadBatch, term: int) -> None:
        """One empty heartbeat round: a majority acking at `term` proves no
        higher-term leader existed when each batched reader captured its
        index, so serving reads at those indexes is linearizable.
        Successful acks also refresh the lease, so a burst of
        `?consistent` reads leaves the default mode round-free."""
        chaos.maybe_delay("read.index_stall")
        self.read_rounds += 1
        start = time.monotonic()
        with self._lock:
            # leadership is proven by VOTERS only: a non-voter's ack says
            # nothing about who the electorate follows
            probe_peers = [v for v in self._voters if v != self.name]
            quorum = self._quorum()
            acks = 1 if self.name in self._voters else 0
        for peer in probe_peers:
            with self._lock:
                if self.state != LEADER or self.term != term:
                    return                          # deposed mid-round
            try:
                # prev_log_index=0 skips the consistency check — this is a
                # pure leadership probe, not replication — so it must also
                # carry leader_commit=0: a real commit index here would let
                # a follower still holding a divergent uncommitted tail
                # from a deposed leader commit its own conflicting entries
                # past the skipped check.  Commit propagation belongs to
                # replication rounds, which do carry prev_log_index.
                resp = self.transport.call(self.name, peer,
                                           "append_entries", {
                    "term": term, "leader": self.name,
                    "prev_log_index": 0, "prev_log_term": 0,
                    "entries": [], "leader_commit": 0})
            except Unreachable:
                continue
            except Exception:                       # noqa: BLE001
                log.warning("raft: %s read probe to %s failed",
                            self.name, peer, exc_info=True)
                continue
            with self._lock:
                if resp["term"] > self.term:
                    self._step_down(resp["term"])
                    return
                if self.state != LEADER or self.term != term:
                    return
                if resp.get("success"):
                    acks += 1
                    self._ack_round_start[peer] = start
                    self._refresh_lease()
        if acks >= quorum:
            batch.ok = True

    @requires_lock("_lock")
    def _refresh_lease(self) -> None:
        """Re-anchor the leader lease (call under self._lock, as leader).

        The lease is valid while a majority — counting ourselves as of
        "now" — acked an append round that started within
        election_timeout * (1 - lease_clock_skew): stickiness guarantees
        no successor can be elected until election_timeout after the
        quorum last heard from us, so the shortened window can never
        overlap a new leader's writes."""
        if self.name not in self._voters:
            return            # a non-voter leader-in-demotion holds no lease
        need = self._quorum() - 1                   # voter acks beyond self
        if need == 0:
            anchor = time.monotonic()
        else:
            starts = sorted((self._ack_round_start.get(v, 0.0)
                             for v in self._voters if v != self.name),
                            reverse=True)
            anchor = starts[need - 1]
        lease = anchor + self.config.election_timeout \
            * (1.0 - self.config.lease_clock_skew)
        if lease > self._lease_until:
            self._lease_until = lease

    def lease_valid(self) -> bool:
        with self._lock:
            return self.state == LEADER and (
                self._sole_voter()
                or time.monotonic() < self._lease_until)

    def wait_applied(self, index: int, timeout: float = 5.0) -> bool:
        """Block until last_applied >= index — the follower half of
        ReadIndex.  Waits on raft's own applied counter, not the store's
        latest_index: a read index can point at a Noop entry the store
        never sees."""
        deadline = time.monotonic() + timeout
        with self._apply_cv:
            while self.last_applied < index:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    return False
                self._apply_cv.wait(min(remaining, 0.05))
            return True

    def last_contact_ms(self) -> float:
        """Milliseconds since this node last heard from a leader (0 on the
        leader itself) — the X-Nomad-LastContact header value."""
        with self._lock:
            if self.state == LEADER:
                return 0.0
            return max(0.0, (time.monotonic() - self._last_contact) * 1e3)

    # ------------------------------------------------------------- ticker

    def _election_deadline(self) -> float:
        to = self.config.election_timeout
        return self._last_contact + to + random.uniform(0, to)

    def _run_ticker(self) -> None:
        while not self._stop.is_set():
            # backstop: the ticker is the only thread that heartbeats and
            # starts elections — if it dies, this node can never lead or
            # vote itself out of a wedge, so no exception may escape
            try:
                with self._lock:
                    state = self.state
                if state == LEADER:
                    self._replicate_all(heartbeat=True)
                    self._maybe_compact()
                    # sleep a heartbeat, or less if a commit advances
                    # (the next round propagates leader_commit at once)
                    self._commit_event.wait(self.config.heartbeat_interval)
                    self._commit_event.clear()
                else:
                    if time.monotonic() >= self._election_deadline():
                        self._run_election()
                    else:
                        self._stop.wait(self.config.heartbeat_interval / 2)
            except Exception:                       # noqa: BLE001
                log.exception("raft: %s ticker iteration failed", self.name)
                self._stop.wait(self.config.heartbeat_interval)

    # ------------------------------------------------------------- election

    def _run_election(self, transfer: bool = False) -> None:
        # Pre-vote round (the reference's preElectSelf): probe whether a
        # quorum WOULD vote for us before touching our real term.  A node
        # that is merely behind — restarting from its data_dir while the
        # leader streams it a snapshot — must not depose a healthy leader
        # just by timing out: without this, its inflated term leaks back
        # through append responses and forces an election it cannot win,
        # over and over, for as long as catch-up takes.  Pre-votes also
        # hit no disk, so an unwinnable election costs zero fsyncs.
        # `transfer=True` (TimeoutNow, §3.10) skips the pre-vote — the
        # outgoing leader explicitly asked us to campaign NOW, and its own
        # liveness is exactly what pre-vote/stickiness would hold against
        # us.
        with self._lock:
            if self.name not in self._voters:
                # non-voters (joining servers, demoted members) never
                # campaign; they wait for a leader to contact them
                self._last_contact = time.monotonic()
                return
            vote_peers = [v for v in self._voters if v != self.name]
            quorum = self._quorum()
            term = self.term + 1
            last_index = self.log.last_index
            last_term = self.log.last_term or self._snapshot_term()
        if not transfer:
            votes = 1
            for peer in vote_peers:
                try:
                    resp = self.transport.call(
                        self.name, peer, "request_vote", {
                            "term": term, "candidate": self.name,
                            "prevote": True, "last_log_index": last_index,
                            "last_log_term": last_term})
                except Unreachable:
                    continue
                except Exception:                   # noqa: BLE001
                    log.warning("raft: %s pre-vote call to %s failed",
                                self.name, peer, exc_info=True)
                    continue
                if resp.get("granted"):
                    votes += 1
            if votes < quorum:
                with self._lock:
                    # a quorum sees a live leader (or a better log); wait a
                    # full randomized timeout before probing again
                    self._last_contact = time.monotonic()
                return
        with self._lock:
            prev_term, prev_vote = self.term, self.voted_for
            if self.term + 1 != term or self.state == LEADER \
                    or self.name not in self._voters:
                return   # the world moved while we were pre-voting
            self.state = CANDIDATE
            self.term = term
            self.voted_for = self.name
            # the self-vote must hit stable storage before any peer can
            # count it — otherwise a crash-restart mid-election forgets
            # it and this node may vote for someone else in the same term
            if not self._persist_meta():
                self.state = FOLLOWER
                self.term, self.voted_for = prev_term, prev_vote
                return
            self.leader_id = None
            self._last_contact = time.monotonic()
            vote_peers = [v for v in self._voters if v != self.name]
            quorum = self._quorum()
            last_index = self.log.last_index
            last_term = self.log.last_term or self._snapshot_term()
        votes = 1
        for peer in vote_peers:
            try:
                resp = self.transport.call(self.name, peer, "request_vote", {
                    "term": term, "candidate": self.name,
                    "transfer": transfer,
                    "last_log_index": last_index, "last_log_term": last_term})
            except Unreachable:
                continue
            except Exception:                       # noqa: BLE001
                log.warning("raft: %s vote call to %s failed",
                            self.name, peer, exc_info=True)
                continue
            with self._lock:
                if resp["term"] > self.term:
                    self._step_down(resp["term"])
                    return
            if resp.get("granted"):
                votes += 1
        with self._lock:
            if self.state != CANDIDATE or self.term != term:
                return
            if votes >= quorum:
                self._become_leader()

    def _become_leader(self) -> None:
        self.state = LEADER
        self.leader_id = self.name
        # commit a no-op in the new term so prior-term entries become
        # committable immediately (hashicorp/raft's LogNoop on election)
        nxt = self.log.last_index + 1
        self.log.append(LogEntry(nxt, self.term, "Noop", None))
        for p in self.peers:
            self._next_index[p] = nxt
            self._match_index[p] = 0
        self._match_index[self.name] = self.log.last_index
        # a fresh leadership stint must re-earn its lease: ack times from
        # a previous term could anchor a lease the quorum never granted
        self._ack_round_start.clear()
        self._lease_until = 0.0
        self._advance_commit()        # sole-voter: the no-op commits now
        log.info("raft: %s became leader (term %d)", self.name, self.term)
        self._leadership_q.put("leader")

    def _step_down(self, term: int) -> None:
        was_leader = self.state == LEADER
        self.state = FOLLOWER
        if term > self.term:
            # adopting a NEW term resets the vote; an equal-term step-down
            # (e.g. a candidate seeing the elected leader's heartbeat)
            # must keep voted_for — clearing it would let this node vote
            # twice in one term.  Persist is best-effort here: a vote
            # granted later in this term re-persists term+vote atomically
            # before it is released.
            self.term = term
            self.voted_for = None
            self._persist_meta()
        if was_leader:
            # don't advertise ourselves as leader after deposition — a
            # stale self-pointing leader_id would make rpc_leader forward
            # to itself in a loop until the new leader's heartbeat arrives
            self.leader_id = None
        # a deposed (or term-bumped) node must never serve lease reads
        self._lease_until = 0.0
        self._ack_round_start.clear()
        self._last_contact = time.monotonic()
        if was_leader:
            for fut in self._futures.values():
                if not fut.done():
                    fut.set_exception(NotLeaderError(self.leader_id))
            self._futures.clear()
            self._leadership_q.put("follower")

    def _run_leadership(self) -> None:
        """Ordered establish/revoke dispatcher (the reference's leaderLoop
        consuming raft.LeaderCh, nomad/leader.go:66-120)."""
        while not self._stop.is_set():
            try:
                evt = self._leadership_q.get(timeout=0.1)
            except queue.Empty:
                continue
            try:
                if evt == "leader" and self.on_leader is not None:
                    self.on_leader()
                elif evt == "follower" and self.on_follower is not None:
                    self.on_follower()
            except Exception:                       # noqa: BLE001
                log.exception("leadership transition failed")

    def _snapshot_term(self) -> int:
        """Term of the newest installed snapshot: the candidate's
        last-log-term fallback once compaction has emptied the log — a
        fully-compacted node advertising term 0 could never win a
        (pre-)vote against peers comparing it to the snapshot's real
        term."""
        return self._last_snap_term

    # ------------------------------------------------------------- replicate

    def _replicate_all(self, heartbeat: bool = False) -> None:
        for peer in self.peers:
            try:
                self._replicate_one(peer)
            except Unreachable:
                continue
            except Exception:                       # noqa: BLE001
                # a peer mid-crash raises out of its own handler (closed
                # WAL, dying transport) straight into this thread over the
                # in-process transport; replication just retries next tick
                log.warning("raft: %s replicate to %s failed",
                            self.name, peer, exc_info=True)
                continue

    def _replicate_one(self, peer: str) -> None:
        with self._lock:
            if self.state != LEADER:
                return
            term = self.term
            nxt = self._next_index.get(peer, self.log.last_index + 1)
            if nxt < self.log.first_index and self.snapshots is not None:
                self._spawn_snapshot_stream(peer)
                return
            prev_index = nxt - 1
            prev_term = self.log.term_at(prev_index)
            if prev_index > 0 and prev_term == 0 \
                    and prev_index == self._last_snapshot_index:
                prev_term = self._last_snap_term
            entries = self.log.entries_from(
                nxt, self.config.max_append_entries)
            commit = self.commit_index
        round_start = time.monotonic()
        args = {
            "term": term, "leader": self.name,
            "prev_log_index": prev_index, "prev_log_term": prev_term,
            "entries": [(e.index, e.term, e.msg_type, e.payload)
                        for e in entries],
            "leader_commit": commit}
        bad_table = self.integrity.peer_divergent(peer)
        if bad_table:
            # convicted peer: the quarantine directive rides every
            # append until the repair stream digest-verifies
            args["integrity_quarantine"] = bad_table
        resp = self.transport.call(self.name, peer, "append_entries", args)
        with self._lock:
            if resp["term"] > self.term:
                self._step_down(resp["term"])
                return
            if self.state != LEADER or self.term != term:
                return
            if resp.get("success"):
                if entries:
                    self._match_index[peer] = entries[-1].index
                    self._next_index[peer] = entries[-1].index + 1
                self._advance_commit()
                # every successful append/heartbeat ack extends the
                # leader lease from the time the round was SENT (the
                # conservative anchor: leadership was proven as of then)
                self._ack_round_start[peer] = round_start
                self._peer_contact[peer] = time.monotonic()
                self._refresh_lease()
                self.integrity.observe_ack(peer, resp.get("integrity"))
            else:
                # consistency check failed: back off
                self._next_index[peer] = max(
                    1, min(nxt - 1, resp.get("last_index", nxt - 1) + 1))
                return
        # checkpoint vote + repair kicks run with no locks held — the
        # evaluation takes only the tracker's leaf lock, and a repair
        # spawn may force a snapshot (fsm lock)
        self._integrity_evaluate()

    def _integrity_evaluate(self) -> None:
        """Leader-side checkpoint vote (no locks held on entry): judge
        the newest checkpoint by majority, quarantine convicted peers
        (the directive rides their next append), kick anti-entropy
        repair streams, and — if WE lost the vote — quarantine our own
        reads and hand leadership off so the successor repairs us as a
        follower."""
        with self._lock:
            if self.state != LEADER:
                return
            race.read("RaftNode._voters", self)
            voters = list(self._voters) or [self.name]
            members = set(self._voters) | set(self._nonvoters) \
                | {self.name}
        actions = self.integrity.evaluate(voters, members=members)
        if actions["self_outlier"]:
            if not self.integrity.quarantined:
                self.integrity.quarantine(
                    "lost integrity majority vote as leader")
                threading.Thread(
                    target=self._integrity_step_aside,
                    name=f"raft-integrity-stepdown-{self.name}",
                    daemon=True).start()
            return
        if not actions["repair"]:
            return
        need_spawn = []
        now = time.monotonic()
        with self._lock:
            if self.state != LEADER:
                return
            for peer in actions["repair"]:
                t = self._snap_streams.get(peer)
                if t is not None and t.is_alive():
                    continue
                _, next_ok = self._snap_backoff.get(peer, (0, 0.0))
                if now < next_ok:
                    continue
                need_spawn.append(peer)
        if not need_spawn:
            return
        # a FRESH snapshot so the repair base (and its expected digest)
        # is at/above the judged checkpoint — the stream then rides the
        # ordinary chunked InstallSnapshot machinery with repair framing
        self.force_snapshot()
        with self._lock:
            if self.state != LEADER:
                return
            for peer in need_spawn:
                self._spawn_snapshot_stream(peer, repair=True)

    def _integrity_step_aside(self) -> None:
        """A leader convicted by its own integrity vote transfers
        leadership away (runs on a helper thread — transfer blocks on
        the target catching up)."""
        try:
            if not self.transfer_leadership():
                log.warning("raft: %s integrity step-aside could not "
                            "transfer leadership", self.name)
        except (NotLeaderError, ValueError):
            pass
        except Exception:                           # noqa: BLE001
            log.warning("raft: %s integrity step-aside failed",
                        self.name, exc_info=True)

    @requires_lock("_lock")
    def _spawn_snapshot_stream(self, peer: str,
                               repair: bool = False) -> None:
        """Kick off (or leave running) the chunked snapshot transfer to a
        lagging peer.  Called from the replication tick under `_lock`;
        only spawns the worker thread, so heartbeats to the remaining
        peers proceed immediately.  `repair=True` streams with integrity
        repair framing (see _send_snapshot)."""
        t = self._snap_streams.get(peer)
        if t is not None and t.is_alive():
            return
        _, next_ok = self._snap_backoff.get(peer, (0, 0.0))
        if time.monotonic() < next_ok:
            return      # bounded backoff after repeated install failures
        t = threading.Thread(target=self._send_snapshot,
                             args=(peer, repair),
                             name=f"raft-snap-{self.name}-{peer}",
                             daemon=True)
        self._snap_streams[peer] = t
        t.start()

    def _note_snap_failure(self, peer: str) -> None:
        """A snapshot stream attempt failed: count it and arm bounded
        exponential backoff so a follower that persistently fails to
        persist is not re-streamed the full blob every tick forever."""
        global_metrics.incr("raft.snapshot.send_fail")
        with self._lock:
            fails, _ = self._snap_backoff.get(peer, (0, 0.0))
            fails = min(fails + 1, 6)
            delay = min(2.0, 0.05 * (2 ** fails))
            self._snap_backoff[peer] = (fails, time.monotonic() + delay)

    def _send_snapshot(self, peer: str, repair: bool = False) -> None:
        """Streamed, resumable InstallSnapshot (dissertation §7).

        Runs on its own thread, off the replication tick.  The blob goes
        out in `NOMAD_TPU_SNAP_CHUNK`-byte frames, each carrying
        {offset, crc32, total, done, last_index, last_term, config};
        every ack returns the follower's next expected offset, which is
        the whole resume protocol — a dropped/duplicated/reordered frame
        re-syncs to the ack, a restarted follower acks 0, and a NEW
        leader streaming the same snapshot picks up at the offset the
        dead leader's stream reached.  The `done` frame adds the
        whole-stream CRC so the follower persists only a verified blob.

        `repair=True` is the anti-entropy channel for a digest-convicted
        peer: every frame carries ``repair: True`` (the follower's
        install bypasses the dup/skip-restore guards and rewinds
        last_applied to the snapshot index — entries above it re-apply
        onto the restored base), and the `done` frame carries the
        combined digest of the streamed blob so the follower can
        digest-verify its restored state before re-admitting itself.
        """
        stream = None
        try:
            chunk = max(1, knobs.get_int(
                "NOMAD_TPU_SNAP_CHUNK", default=SNAP_CHUNK_DEFAULT))
            window = max(1, knobs.get_int(
                "NOMAD_TPU_SNAP_WINDOW", default=SNAP_WINDOW_DEFAULT))
            # windowed read handle: frames come off the sidecar blob
            # file at most `window` chunks at a time, so N concurrent
            # peer streams cost N*window*chunk — not N whole blobs
            stream = self.snapshots.open_stream(window * chunk) \
                if self.snapshots else None
            if stream is None:
                return
            s_idx, s_term = stream.index, stream.term
            total = stream.total
            stream_crc = stream.stream_crc
            snap_config = stream.config
            expected_digest = None
            if repair:
                # expected digest of the streamed state, computed from
                # the SAME blob (one transient full read — repair only)
                rec = self.snapshots.latest_full()
                if rec is None or rec["index"] != s_idx:
                    # another snapshot landed between open and read:
                    # retry next tick with a consistent blob/digest pair
                    self._note_snap_failure(peer)
                    return
                expected_digest = state_digest.combine(
                    state_digest.blob_digests(rec["data"]))
            offset = 0
            stalls = drops = 0
            while True:
                with self._lock:
                    if self.state != LEADER or self._stop.is_set():
                        return
                    term = self.term
                if chaos.active is not None \
                        and chaos.should("snapshot.stream_abort"):
                    # sender dies mid-transfer (leader kill / stream
                    # teardown): the next replication tick restarts the
                    # stream, which resumes from the follower's ack
                    # rather than byte zero
                    return
                data = stream.read_at(offset, chunk)
                done = offset + len(data) >= total
                frame = {
                    "term": term, "leader": self.name,
                    "last_index": s_idx, "last_term": s_term,
                    "offset": offset, "total": total,
                    "crc32": zlib.crc32(data), "data": data, "done": done,
                    # configuration as of the snapshot index so a blank
                    # joiner learns the membership without any log prefix
                    "config": snap_config,
                }
                if repair:
                    frame["repair"] = True
                if done:
                    frame["stream_crc32"] = stream_crc
                    if repair:
                        frame["digest"] = expected_digest
                if chaos.active is not None \
                        and chaos.should("snapshot.chunk_drop"):
                    # frame lost in flight: re-probe the same offset — the
                    # follower's ack re-synchronizes the stream
                    drops += 1
                    if drops > 64:      # chaos armed at rate ~1.0
                        self._note_snap_failure(peer)
                        return
                    continue
                resp = self.transport.call(self.name, peer,
                                           "install_snapshot", frame)
                with self._lock:
                    if resp["term"] > self.term:
                        self._step_down(resp["term"])
                        return
                    if self.state != LEADER or self.term != term:
                        return
                if not resp.get("success"):
                    # follower could not persist/verify; back off instead
                    # of hammering it with the full stream every tick
                    self._note_snap_failure(peer)
                    return
                acked = resp.get("offset", offset + len(data))
                if done and acked >= total:
                    with self._lock:
                        if self.state != LEADER or self.term != term:
                            return
                        self._next_index[peer] = s_idx + 1
                        self._match_index[peer] = s_idx
                        self._peer_contact[peer] = time.monotonic()
                        self._snap_backoff.pop(peer, None)
                    if repair:
                        # verified True lifts the conviction; False
                        # keeps it (back off, then re-stream); absent
                        # (mixed-version follower that cannot verify)
                        # lifts it and lets the next checkpoint re-judge
                        verified = resp.get("verified")
                        self.integrity.repair_result(peer, verified)
                        if verified is False:
                            self._note_snap_failure(peer)
                    return
                if acked == offset:
                    # no progress (per-chunk CRC reject, or a done frame
                    # whose stream CRC failed when total == acked): give
                    # the link a rest after a few tries
                    stalls += 1
                    if stalls > 16:
                        self._note_snap_failure(peer)
                        return
                else:
                    stalls = 0
                    with self._lock:
                        # a moving stream is proof of contact: autopilot
                        # must not reap a peer mid-catch-up
                        self._peer_contact[peer] = time.monotonic()
                offset = min(max(acked, 0), total)
        except Unreachable:
            self._note_snap_failure(peer)
        except Exception:                           # noqa: BLE001
            log.warning("raft: %s snapshot stream to %s failed",
                        self.name, peer, exc_info=True)
            self._note_snap_failure(peer)
        finally:
            if stream is not None:
                stream.close()

    @requires_lock("_lock")
    def _advance_commit(self) -> None:
        """Majority-of-VOTERS match ⇒ commit (current-term entries only).

        The quorum is computed over the latest appended configuration —
        effective-on-append (§4.1) means a half-replicated AddVoter
        already raises the bar (2-of-4 can never commit), and a removed
        leader no longer counts itself.  Non-voters replicate but never
        advance commit."""
        race.read("RaftNode._voters", self)
        voters = self._voters or [self.name]
        matches = sorted(self._match_index.get(v, 0) for v in voters)
        quorum = len(voters) // 2 + 1
        majority = matches[len(voters) - quorum]
        if majority > self.commit_index \
                and self.log.term_at(majority) == self.term:
            self.commit_index = majority
            self._apply_cv.notify_all()
            self._commit_event.set()

    # ------------------------------------------------------------- apply

    def _run_apply(self) -> None:
        """One entry at a time: re-check state under the lock every step so
        a concurrently installed snapshot (which moves last_applied
        forward and compacts the log) can never be undone or spun on."""
        while not self._stop.is_set():
            with self._apply_cv:
                while self.last_applied >= self.commit_index \
                        and not self._stop.is_set():
                    self._apply_cv.wait(0.1)
                if self._stop.is_set():
                    return
                i = self.last_applied + 1
                e = self.log.get(i)
                if e is None:
                    if i <= self._last_snapshot_index:
                        # compacted: the snapshot already covers it
                        self.last_applied = i
                        continue
                    # not replicated yet; wait for it
                    self._apply_cv.wait(0.05)
                    continue
            with self._fsm_lock:
                with self._lock:
                    if i <= self.last_applied:   # snapshot raced us
                        continue
                    tctx = self._trace_notes.pop(i, None)
                # the span brackets the FSM call from outside: the FSM
                # itself never reads the clock
                with tracing.span("raft.fsm_apply", ctx=tctx,
                                  node=self.name, index=i,
                                  msg_type=e.msg_type):
                    try:
                        if chaos.active is not None \
                                and e.msg_type not in _APPLY_SKIP_EXEMPT \
                                and e.index > self._boot_log_end \
                                and chaos.should("fsm.apply_skip", self.name):
                            # injected divergence: the committed entry is
                            # silently NOT applied while last_applied still
                            # advances — the log says it happened, the state
                            # says it didn't.  Invisible to raft; only the
                            # integrity plane's digest checkpoints can tell.
                            log.warning("chaos: %s skipped fsm apply of %s "
                                        "at %d", self.name, e.msg_type,
                                        e.index)
                        else:
                            self.fsm.apply(e.index, e.msg_type, e.payload)
                        err = None
                    except Exception as exc:           # noqa: BLE001
                        log.exception("fsm apply failed at %d", e.index)
                        err = exc
                if err is None and chaos.active is not None \
                        and e.index > self._boot_log_end \
                        and chaos.should("store.bitflip", self.name):
                    # injected silent corruption: flip one replicated
                    # record post-apply — no index bump, no dirty mark,
                    # caught only by a full digest walk
                    store = getattr(self.fsm, "store", None)
                    if store is not None \
                            and hasattr(store, "chaos_bitflip"):
                        hit = store.chaos_bitflip(chaos.active.uniform())
                        log.warning("chaos: %s bitflipped %s after "
                                    "apply %d", self.name, hit, e.index)
                if err is None and e.msg_type == STATE_CHECKPOINT_MSG \
                        and hasattr(self.fsm, "snapshot_tables"):
                    # digest stamped here, under _fsm_lock, so the walk
                    # sees exactly the state at this log position
                    try:
                        self.integrity.on_checkpoint(e.index, e.payload)
                    except Exception:               # noqa: BLE001
                        log.exception("integrity checkpoint at %d "
                                      "failed", e.index)
                with self._lock:
                    self.last_applied = max(self.last_applied, i)
                    fut = self._futures.pop(i, None)
                    # wake wait_applied() readers (the cv shares _lock)
                    self._apply_cv.notify_all()
                    # §4.2.2: a leader that committed its own removal
                    # steps down once the config entry APPLIES — the
                    # future was popped above so the caller still gets
                    # its success before _step_down fails the rest
                    if e.msg_type == CONFIGURATION_MSG \
                            and self.state == LEADER \
                            and self.name not in self._voters:
                        log.info("raft: %s removed from configuration; "
                                 "stepping down", self.name)
                        self._step_down(self.term)
            if fut is not None and not fut.done():
                if err is None:
                    fut.set_result(i)
                else:
                    fut.set_exception(err)

    # ------------------------------------------------------------- compaction

    def _maybe_compact(self) -> None:
        if self.snapshots is None:
            return
        with self._lock:
            if self.last_applied - self._last_snapshot_index \
                    < self.config.snapshot_threshold:
                return
        self.force_snapshot()

    def force_snapshot(self) -> None:
        """Operator snapshot save (command/raft_tools analogue).  Holds the
        FSM lock so the blob is exactly the state at `last_applied` — a
        concurrent apply landing mid-snapshot would make restart replay
        non-idempotent entries (e.g. job version bumps) twice."""
        if self.snapshots is None:
            return
        with self._fsm_lock:
            with self._lock:
                applied = self.last_applied
                term = self.log.term_at(applied) or self._last_snap_term \
                    or self.term
                cfg = self._config_at(applied)
            blob = self.fsm.snapshot()
        with self._lock:
            try:
                self.snapshots.save(applied, term, blob, config=cfg)
            except Exception:                       # noqa: BLE001
                # incl. injected snapshot.partial_write: the save did NOT
                # land durably, so compacting the log here would orphan
                # the only copy of those entries; keep the log and retry
                # at the next snapshot threshold
                log.warning("raft: %s snapshot save failed; keeping log",
                            self.name, exc_info=True)
                return
            self._last_snapshot_index = applied
            self._last_snap_term = term
            self._snap_config = cfg
            self.log.compact(applied)

    # ------------------------------------------------------------- RPC

    def _handle_rpc(self, method: str, args: dict) -> dict:
        if method == "request_vote":
            return self._on_request_vote(args)
        if method == "append_entries":
            return self._on_append_entries(args)
        if method == "install_snapshot":
            return self._on_install_snapshot(args)
        if method == "timeout_now":
            return self._on_timeout_now(args)
        raise ValueError(method)

    def _on_timeout_now(self, a: dict) -> dict:
        """TimeoutNow (§3.10): the current leader asks us to campaign
        immediately.  The election runs on its own thread — campaigning
        inline would hold the transport handler while we call every
        voter back through it."""
        with self._lock:
            if a["term"] < self.term:
                return {"term": self.term, "success": False}
            if self.name not in self._voters:
                return {"term": self.term, "success": False}
            self._last_contact = time.monotonic()
        threading.Thread(target=self._transfer_campaign,
                         name=f"raft-transfer-{self.name}",
                         daemon=True).start()
        return {"term": self.term, "success": True}

    def _transfer_campaign(self) -> None:
        try:
            self._run_election(transfer=True)
        except Exception:                           # noqa: BLE001
            log.exception("raft: %s transfer campaign failed", self.name)

    def _on_request_vote(self, a: dict) -> dict:
        with self._lock:
            # leader stickiness (reference requestVote/requestPreVote):
            # while we are hearing from a live leader, refuse — and do NOT
            # adopt the candidate's term.  A partitioned or catching-up
            # node cannot depose a leader the quorum still follows.
            # Transfer votes (§3.10) bypass stickiness: the live leader
            # ITSELF asked this candidate to depose it.
            if not a.get("transfer") \
                    and self.leader_id is not None \
                    and self.leader_id != a["candidate"] \
                    and (time.monotonic() - self._last_contact
                         < self.config.election_timeout):
                return {"term": self.term, "granted": False}
            if a.get("prevote"):
                # would we vote for this candidate in that term?  No state
                # change, no disk: just an electability probe.
                my_last_term = self.log.last_term or self._last_snap_term
                granted = (a["term"] > self.term
                           and (a["last_log_term"] > my_last_term
                                or (a["last_log_term"] == my_last_term
                                    and a["last_log_index"]
                                    >= self.log.last_index)))
                return {"term": self.term, "granted": granted}
            if a["term"] > self.term:
                self._step_down(a["term"])
            granted = False
            if a["term"] == self.term \
                    and self.voted_for in (None, a["candidate"]):
                my_last_term = self.log.last_term or self._last_snap_term
                up_to_date = (
                    a["last_log_term"] > my_last_term
                    or (a["last_log_term"] == my_last_term
                        and a["last_log_index"] >= self.log.last_index))
                if up_to_date:
                    # grant only once the vote is on stable storage: a
                    # granted-then-forgotten vote is the two-leaders bug
                    self.voted_for = a["candidate"]
                    if self._persist_meta():
                        granted = True
                        self._last_contact = time.monotonic()
                    else:
                        self.voted_for = None
            return {"term": self.term, "granted": granted}

    def _on_append_entries(self, a: dict) -> dict:
        with self._lock:
            if a["term"] < self.term:
                return {"term": self.term, "success": False,
                        "last_index": self.log.last_index}
            if a["term"] > self.term or self.state != FOLLOWER:
                self._step_down(a["term"])   # single term-adoption path
            self.leader_id = a["leader"]
            self._last_contact = time.monotonic()
            prev_index = a["prev_log_index"]
            if prev_index > 0:
                local_term = self.log.term_at(prev_index)
                if local_term == 0 and prev_index == self._last_snapshot_index:
                    local_term = self._last_snap_term
                if local_term != a["prev_log_term"] \
                        and prev_index > self._last_snapshot_index:
                    return {"term": self.term, "success": False,
                            "last_index": min(self.log.last_index,
                                              prev_index - 1)}
            # collect the fresh suffix, then append with ONE group-commit
            # durability wait (raft requires entries durable before this
            # response ACKs them — the leader counts us toward commit)
            fresh: List[LogEntry] = []
            for (idx, term, msg_type, payload) in a["entries"]:
                if not fresh:
                    existing = self.log.get(idx)
                    if existing is not None and existing.term == term:
                        continue
                fresh.append(LogEntry(idx, term, msg_type, payload))
            self.log.append_batch(fresh)
            if fresh:
                if fresh[0].index <= self._config_index:
                    # the conflicting suffix we just truncated carried the
                    # configuration we were running; fall back to what
                    # storage still holds before adopting the new entries
                    self._recompute_config()
                for e in fresh:
                    if e.msg_type == CONFIGURATION_MSG:
                        # effective on append (§4.1), commit not required
                        self._set_config(e.payload["voters"],
                                         e.payload["nonvoters"], e.index)
            if a["leader_commit"] > self.commit_index:
                self.commit_index = min(a["leader_commit"],
                                        self.log.last_index)
                self._apply_cv.notify_all()
            if a.get("integrity_quarantine"):
                # the leader's majority vote convicted us: stop serving
                # stale/lease reads now, keep replicating and voting —
                # the repair snapshot stream is already on its way
                self.integrity.quarantine(
                    "leader divergence verdict (table %s)"
                    % a["integrity_quarantine"])
            resp = {"term": self.term, "success": True,
                    "last_index": self.log.last_index}
            rep = self.integrity.report()
            if rep is not None:
                # digest piggyback: {index, digest, per_table} of our
                # newest applied STATE_CHECKPOINT (absent before the
                # first one — the leader counts that as "unverified")
                resp["integrity"] = rep
            return resp

    def _on_install_snapshot(self, a: dict) -> dict:
        with self._lock:
            if a["term"] < self.term:
                return {"term": self.term, "success": False}
            if a["term"] > self.term or self.state != FOLLOWER:
                self._step_down(a["term"])   # single term-adoption path
            self.leader_id = a["leader"]
            self._last_contact = time.monotonic()
        if "offset" not in a:
            # monolithic install (seed protocol, kept for compatibility):
            # the whole blob arrives in one frame
            return self._install_snapshot_blob(a, a["data"])
        return self._on_snapshot_chunk(a)

    def _on_snapshot_chunk(self, a: dict) -> dict:
        """One frame of a chunked InstallSnapshot stream.

        Frames append to a temp file through a ChunkSink keyed by the
        snapshot identity (last_index, last_term, total); every ack
        carries our next expected offset, which is the whole resume
        protocol — a duplicated or reordered frame acks the current
        offset, a restarted follower (no sink) acks 0, and a restarted
        leader re-syncs off the first ack.  The sink deliberately
        survives leader/term changes: a new leader streaming the SAME
        snapshot resumes where the dead one stopped, while a different
        snapshot identity discards the partial sink cleanly.  On `done`
        the whole-stream CRC gates persist-before-accept."""
        key = (a["last_index"], a["last_term"], a["total"])
        with self._lock:
            if a["term"] < self.term:
                return {"term": self.term, "success": False, "offset": 0}
            sink = self._snap_rx
            if sink is not None and sink.key != key:
                # a different snapshot supersedes the partial stream
                sink.abort()
                sink = self._snap_rx = None
            if sink is None:
                if a["offset"] != 0:
                    # mid-stream frame with no sink (we restarted):
                    # tell the leader to resume from byte zero
                    return {"term": self.term, "success": True,
                            "offset": 0}
                try:
                    sink = self._snap_rx = ChunkSink(
                        self.snapshots.dir if self.snapshots is not None
                        else None, key)
                except OSError:
                    log.warning("raft: %s cannot open snapshot sink",
                                self.name, exc_info=True)
                    return {"term": self.term, "success": False,
                            "offset": 0}
            if a["offset"] != sink.offset:
                # dropped/duplicated/reordered frame: re-sync the leader
                # to where the stream actually is
                return {"term": self.term, "success": True,
                        "offset": sink.offset}
            if zlib.crc32(a["data"]) != a["crc32"]:
                # corrupt in flight: ask for the same offset again
                return {"term": self.term, "success": True,
                        "offset": sink.offset}
            try:
                sink.append(a["data"])
            except OSError:
                log.warning("raft: %s snapshot chunk append failed",
                            self.name, exc_info=True)
                self._snap_rx = None
                sink.abort()
                return {"term": self.term, "success": False, "offset": 0}
            if not a.get("done"):
                return {"term": self.term, "success": True,
                        "offset": sink.offset}
            # final frame: assemble + whole-stream verify, then hand the
            # blob to the monolithic install tail below (outside _lock —
            # it takes _fsm_lock first, same nesting as force_snapshot)
            self._snap_rx = None
            data = sink.finish()
            if sink.offset != a["total"] \
                    or sink.crc != a.get("stream_crc32", sink.crc):
                # the assembled bytes are not the leader's blob (e.g. a
                # resumed prefix from a dead leader whose snapshot bytes
                # differ): discard and restart from zero
                return {"term": self.term, "success": True, "offset": 0}
        resp = self._install_snapshot_blob(a, data)
        resp["offset"] = a["total"] if resp.get("success") else 0
        return resp

    def _install_snapshot_blob(self, a: dict, data: bytes) -> dict:
        """Persist-before-accept + restore of a complete snapshot blob —
        the tail of the install path, reached monolithically or when a
        chunk stream's `done` frame verifies."""
        with self._lock:
            if a["term"] < self.term:
                return {"term": self.term, "success": False}
            # Persist BEFORE accepting.  The snapshot stands in for log
            # entries the leader has already compacted away: if we restore
            # it in memory without a durable copy, later appends land past
            # a hole that exists only on disk, and the next restart replays
            # around the hole — committed state silently vanishes.  Reject
            # instead; the leader backs off and retries the install.
            if self.snapshots is not None:
                try:
                    self.snapshots.save(a["last_index"], a["last_term"],
                                        data, config=a.get("config"))
                except Exception:                   # noqa: BLE001
                    log.warning("raft: %s could not persist installed "
                                "snapshot; rejecting (leader retries)",
                                self.name, exc_info=True)
                    return {"term": self.term, "success": False}
        # fsm_lock outer, _lock inner (same nesting as force_snapshot):
        # last_applied must move in the same critical section as the
        # restore or the apply loop could re-apply a pre-snapshot entry
        # onto the restored state
        repair = bool(a.get("repair"))
        with self._fsm_lock:
            with self._lock:
                if repair:
                    if a["last_index"] < self._last_snapshot_index:
                        # a repair rewind below our own compaction point
                        # has no log tail left to replay through: reject
                        # so the leader retries with a fresher snapshot
                        return {"term": self.term, "success": False}
                    # anti-entropy repair bypasses both guards below:
                    # our state at these indexes is exactly what is
                    # suspected corrupt, so "already covered" means
                    # nothing — wipe and rebuild from the leader's blob
                    skip_restore = False
                else:
                    if a["last_index"] <= self._last_snapshot_index:
                        # duplicate/stale install: never regress the FSM
                        return {"term": self.term, "success": True}
                    # §7: if the apply loop already covered the
                    # snapshot's prefix via AppendEntries while the
                    # stream was in flight, the state ALREADY includes
                    # it (committed entries at an index are unique) —
                    # restoring would rewind the FSM past entries that
                    # will never re-apply.  Retain the state, still
                    # compact the now-redundant log prefix below.
                    skip_restore = a["last_index"] <= self.last_applied
            if repair:
                # a repair stream IS the divergence verdict (it can
                # outrun the quarantine directive riding our next
                # append): refuse local reads from here until the
                # restored state digest-verifies
                self.integrity.quarantine(
                    "anti-entropy repair in progress (leader divergence "
                    "verdict)")
            if not skip_restore:
                self.fsm.restore(data)
                self.integrity.note_restore()
                if chaos.active is not None \
                        and chaos.should("disk.silent_corrupt", self.name):
                    # injected silent disk corruption: the restored
                    # state differs from the streamed blob (a bad read
                    # that still unpickled) — digest verification below
                    # must refuse re-admission and the leader retries
                    store = getattr(self.fsm, "store", None)
                    if store is not None \
                            and hasattr(store, "chaos_bitflip"):
                        hit = store.chaos_bitflip(chaos.active.uniform())
                        log.warning("chaos: %s silent-corrupted %s on "
                                    "snapshot restore", self.name, hit)
            verified = None
            if repair and hasattr(self.fsm, "snapshot_tables"):
                # digest-verified re-admission: recompute the restored
                # state's digest and match the leader's expected one —
                # still under _fsm_lock, so the walk is quiescent
                verified = self.integrity.verify_restore(a.get("digest"))
            if repair and verified is None:
                # no digest to verify against (mixed-version leader):
                # the install itself was CRC-gated — do not brick the
                # replica behind a verdict nobody can verify; the next
                # checkpoint vote re-judges the restored state
                self.integrity.clear_quarantine(
                    "repair installed (no digest to verify)")
            with self._lock:
                self._last_snapshot_index = a["last_index"]
                self._last_snap_term = a["last_term"]
                self.log.compact(a["last_index"])
                if repair:
                    # rewind-and-replay: the restored blob IS the state
                    # at the snapshot index; committed entries above it
                    # re-apply onto the clean base (exactly-once writes
                    # are deduped by replicated state, e.g.
                    # _applied_plan_ids)
                    self.last_applied = a["last_index"]
                    self._apply_cv.notify_all()
                else:
                    self.last_applied = max(self.last_applied,
                                            a["last_index"])
                self.commit_index = max(self.commit_index, a["last_index"])
                cfg = a.get("config")
                if cfg:
                    self._snap_config = cfg
                    if cfg.get("index", 0) >= self._config_index:
                        # a blank joiner learns the membership here; an
                        # established follower only moves FORWARD (a log
                        # tail past the snapshot may hold a newer config)
                        self._set_config(cfg["voters"],
                                         cfg.get("nonvoters", []),
                                         cfg.get("index", 0))
                resp = {"term": self.term, "success": True}
                if repair:
                    resp["verified"] = verified
                return resp
