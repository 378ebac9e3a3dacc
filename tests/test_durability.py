"""Crash-safe durability tests: checksummed WAL, persisted term/vote,
hardened snapshots, client state DB recovery, and the seeded hard-kill /
restart soak (reference analogs: raft-boltdb's torture tests plus the
crash-consistency failure classes of Pillai et al., OSDI 2014).

Unit legs pin one contract each: WAL record framing + torn-tail repair,
mid-stream corruption refusal, legacy pickle migration, fsync policy
semantics under simulated power loss, corrupt-read retry, durable meta
round-trip + refusal paths, snapshot CRC fallback + reap floor +
partial-write injection, and ClientStateDB corruption/checkpoint
behavior.

The soak leg boots a data_dir-backed 3-server cluster under seeded disk
faults (torn writes, fsync failures, corrupt reads, partial snapshot
writes), hard-kills members mid-commit and restarts them from disk, then
asserts the safety properties: never two leaders in one term, exactly
the requested allocs per job (no committed plan lost or applied twice),
and byte-identical FSM state across all members.
"""
import json
import os
import pickle
import random
import signal
import threading
import time

import pytest

from nomad_tpu import chaos, mock
from nomad_tpu.chaos import ChaosError, ChaosRegistry
from nomad_tpu.client import Client, ClientConfig
from nomad_tpu.client.state import ClientStateDB
from nomad_tpu.core.cluster import Cluster
from nomad_tpu.core.server import Server, ServerConfig
from nomad_tpu.core.worker import TRANSIENT_ERRORS
from nomad_tpu.raft import (
    DurableMeta,
    FileSnapshotStore,
    InMemTransport,
    LogStore,
    MessageType,
    MetaPersistError,
    NomadFSM,
    RaftConfig,
    RaftNode,
    WALCorruptionError,
)
from nomad_tpu.raft.log import (
    LogEntry,
    WAL_MAGIC,
    encode_record,
    fsync_policy_from_env,
)
from nomad_tpu.state import StateStore
from nomad_tpu.structs import EvalStatus, Job, Task, TaskGroup

FAST = RaftConfig(heartbeat_interval=0.02, election_timeout=0.1)


def _wait(cond, timeout=10.0, interval=0.02):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if cond():
            return True
        time.sleep(interval)
    return False


@pytest.fixture(autouse=True)
def _no_leaked_chaos():
    chaos.uninstall()
    yield
    chaos.uninstall()


# ------------------------------------------------------------------ WAL


def test_wal_new_format_roundtrip(tmp_path):
    path = str(tmp_path / "raft.log")
    st = LogStore(path, fsync="always")
    for i in range(1, 6):
        st.append(LogEntry(i, 1, "Noop", {"i": i}))
    st.close()
    with open(path, "rb") as fh:
        assert fh.read(len(WAL_MAGIC)) == WAL_MAGIC
    st2 = LogStore(path, fsync="off")
    assert st2.last_index == 5
    assert st2.get(3).payload == {"i": 3}
    st2.close()


def test_wal_torn_tail_truncated_on_open(tmp_path):
    path = str(tmp_path / "raft.log")
    st = LogStore(path, fsync="always")
    for i in range(1, 4):
        st.append(LogEntry(i, 1, "Noop", i))
    st.close()
    good = os.path.getsize(path)
    # crash mid-append: a partial record past the last good one
    rec = encode_record(pickle.dumps(("entry", 4, 1, "Noop", 4)))
    with open(path, "ab") as fh:
        fh.write(rec[:-3])
    st2 = LogStore(path, fsync="off")
    assert st2.last_index == 3
    st2.close()
    assert os.path.getsize(path) == good     # tail truncated away
    # torn header variant (fewer bytes than a length prefix)
    with open(path, "ab") as fh:
        fh.write(b"\x05\x00")
    st3 = LogStore(path, fsync="off")
    assert st3.last_index == 3
    st3.close()
    assert os.path.getsize(path) == good


def test_wal_midstream_corruption_refuses_to_open(tmp_path):
    path = str(tmp_path / "raft.log")
    st = LogStore(path, fsync="always")
    for i in range(1, 4):
        st.append(LogEntry(i, 1, "Noop", "x" * 50))
    st.close()
    # flip a payload byte in the FIRST record: valid records follow, so
    # this is damaged committed history, not a torn tail
    with open(path, "r+b") as fh:
        fh.seek(len(WAL_MAGIC) + 8 + 2)
        b = fh.read(1)
        fh.seek(-1, os.SEEK_CUR)
        fh.write(bytes([b[0] ^ 0xFF]))
    with pytest.raises(WALCorruptionError, match="refusing"):
        LogStore(path, fsync="off")


def test_wal_legacy_pickle_migration(tmp_path):
    path = str(tmp_path / "raft.log")
    with open(path, "wb") as fh:
        for i in range(1, 5):
            pickle.dump(("entry", i, 1, "Noop", {"i": i}), fh,
                        protocol=pickle.HIGHEST_PROTOCOL)
        fh.write(b"\x80\x05\x03")            # truncated trailing record
    st = LogStore(path, fsync="off")
    assert st.last_index == 4
    st.close()
    assert os.path.exists(path + ".legacy")
    with open(path, "rb") as fh:
        assert fh.read(len(WAL_MAGIC)) == WAL_MAGIC
    # reopens as new-format (no second migration) with entries intact
    st2 = LogStore(path, fsync="off")
    assert st2.last_index == 4
    assert st2.get(2).payload == {"i": 2}
    st2.close()
    assert not os.path.exists(path + ".legacy.legacy")


def test_fsync_policy_env_parsing(monkeypatch):
    monkeypatch.delenv("NOMAD_TPU_FSYNC", raising=False)
    assert fsync_policy_from_env() == "batch"
    for pol in ("always", "batch", "off"):
        monkeypatch.setenv("NOMAD_TPU_FSYNC", pol)
        assert fsync_policy_from_env() == pol
    monkeypatch.setenv("NOMAD_TPU_FSYNC", "sometimes")
    with pytest.raises(ValueError, match="NOMAD_TPU_FSYNC"):
        fsync_policy_from_env()


def test_power_loss_respects_fsync_policy(tmp_path):
    """always/batch: append() returning means the record survives power
    loss.  off: page cache only, the crash loses it."""
    for pol, survives in (("always", True), ("batch", True), ("off", False)):
        path = str(tmp_path / f"wal-{pol}.log")
        st = LogStore(path, fsync=pol)
        st.append(LogEntry(1, 1, "Noop", "payload"))
        st.simulate_crash()
        st2 = LogStore(path, fsync="off")
        assert (st2.last_index == 1) is survives, pol
        st2.close()


def test_append_batch_group_commit_durable(tmp_path):
    path = str(tmp_path / "raft.log")
    st = LogStore(path, fsync="batch")
    st.append_batch([LogEntry(i, 1, "Noop", i) for i in range(1, 51)])
    st.simulate_crash()
    st2 = LogStore(path, fsync="off")
    assert st2.last_index == 50
    st2.close()


def test_corrupt_read_is_caught_and_retried(tmp_path):
    path = str(tmp_path / "raft.log")
    st = LogStore(path, fsync="always")
    for i in range(1, 6):
        st.append(LogEntry(i, 1, "Noop", i))
    st.close()
    # every record read is corrupted on its first attempt; the CRC catches
    # it and the retry (from pristine data) succeeds
    chaos.install(ChaosRegistry(seed=2, rates={"disk.corrupt_read": 1.0}))
    st2 = LogStore(path, fsync="off")
    assert st2.last_index == 5
    st2.close()


# ----------------------------------------------------------- durable meta


def test_meta_roundtrip_and_noop_persist(tmp_path):
    path = str(tmp_path / "raft_meta.json")
    m = DurableMeta(path)
    assert m.state() == (0, None)
    m.persist(3, "server-1")
    with open(path, "rb") as fh:
        before = fh.read()
    m.persist(3, "server-1")                 # unchanged: no rewrite
    with open(path, "rb") as fh:
        assert fh.read() == before
    m2 = DurableMeta(path)
    assert m2.state() == (3, "server-1")


def test_meta_corruption_refuses_to_load(tmp_path):
    path = str(tmp_path / "raft_meta.json")
    DurableMeta(path).persist(2, "b")
    with open(path, "r+b") as fh:
        fh.write(b"{garbage")
    with pytest.raises(MetaPersistError):
        DurableMeta(path)
    # a parseable file whose CRC does not cover its contents is just as
    # untrustworthy — it may advertise a vote the node never made
    with open(path, "w") as fh:
        json.dump({"v": 1, "term": 9, "voted_for": "evil", "crc": 1}, fh)
    with pytest.raises(MetaPersistError, match="crc mismatch"):
        DurableMeta(path)


def test_vote_refused_when_meta_fsync_fails(tmp_path):
    meta = DurableMeta(str(tmp_path / "raft_meta.json"))
    tr = InMemTransport()
    n = RaftNode("a", ["a", "b"], tr, NomadFSM(StateStore()),
                 config=FAST, meta=meta)
    req = {"term": 1, "candidate": "b",
           "last_log_index": 0, "last_log_term": 0}
    chaos.install(ChaosRegistry(seed=1, rates={"disk.fsync_fail": 1.0}))
    resp = n._on_request_vote(dict(req))
    chaos.uninstall()
    # an unpersistable vote must not be granted (it could be forgotten)
    assert not resp["granted"]
    assert n.voted_for is None
    resp = n._on_request_vote(dict(req))     # disk healthy again
    assert resp["granted"]
    assert DurableMeta(meta.path).state() == (1, "b")
    tr.deregister("a")


# -------------------------------------------------------------- snapshots


def test_snapshot_fallback_to_older_valid(tmp_path):
    snaps = FileSnapshotStore(str(tmp_path), retain=3)
    snaps.save(10, 1, b"old-state")
    newest = snaps.save(20, 2, b"new-state")
    with open(newest, "r+b") as fh:          # tear the newest snapshot
        fh.seek(-1, os.SEEK_END)
        fh.truncate()
    assert snaps.latest() == (10, 1, b"old-state")


def test_snapshot_reap_never_deletes_newest_valid(tmp_path):
    snaps = FileSnapshotStore(str(tmp_path), retain=0)
    snaps.save(1, 1, b"a")
    snaps.save(2, 1, b"b")
    # retention misconfigured to 0: the restart anchor must survive
    assert snaps.latest() == (2, 1, b"b")
    assert len(snaps._snap_names()) == 1


def test_snapshot_partial_write_fails_save_and_is_skipped(tmp_path):
    snaps = FileSnapshotStore(str(tmp_path), retain=2)
    snaps.save(5, 1, b"good")
    chaos.install(ChaosRegistry(
        seed=4, rates={"snapshot.partial_write": 1.0}))
    with pytest.raises(ChaosError):
        snaps.save(9, 1, b"torn-" * 100)
    chaos.uninstall()
    # the torn file landed under its final name; latest() skips it
    assert len(snaps._snap_names()) == 2
    assert snaps.latest() == (5, 1, b"good")


def test_snapshot_legacy_bare_pickle_readable(tmp_path):
    snaps = FileSnapshotStore(str(tmp_path))
    legacy = os.path.join(str(tmp_path),
                          "snapshot-0000000001-000000000007.snap")
    with open(legacy, "wb") as fh:
        pickle.dump({"index": 7, "term": 1, "data": b"seed"}, fh)
    assert snaps.latest() == (7, 1, b"seed")


def test_force_snapshot_failure_keeps_log(tmp_path):
    snaps = FileSnapshotStore(str(tmp_path / "snaps"))
    tr = InMemTransport()
    n = RaftNode("a", ["a"], tr, NomadFSM(StateStore()), config=FAST,
                 snapshots=snaps,
                 log_store=LogStore(str(tmp_path / "wal"), fsync="off"))
    n.start()
    try:
        assert _wait(lambda: n.is_leader, 3.0)
        for _ in range(5):
            n.apply(MessageType.NODE_REGISTER, {"node": mock.node()})
        chaos.install(ChaosRegistry(
            seed=1, rates={"snapshot.partial_write": 1.0}))
        n.force_snapshot()       # must not raise and must NOT compact —
        chaos.uninstall()        # the log is the only durable copy now
        assert n.log.first_index == 1
        assert n._last_snapshot_index == 0
        n.force_snapshot()       # healthy retry lands and compacts
        assert snaps.latest() is not None
        assert n.log.first_index > 1
    finally:
        chaos.uninstall()
        n.stop()


def test_install_snapshot_unpersistable_is_rejected(tmp_path):
    """A follower that cannot durably save an installed snapshot must
    refuse it outright: accepting in memory lets later appends land past
    a hole the leader already compacted away, and the next restart
    replays around the hole — committed entries silently vanish."""
    snaps = FileSnapshotStore(str(tmp_path / "snaps"))
    tr = InMemTransport()
    n = RaftNode("a", ["a", "b", "c"], tr, NomadFSM(StateStore()),
                 config=FAST, snapshots=snaps,
                 log_store=LogStore(str(tmp_path / "wal"), fsync="off"))
    donor = StateStore()
    donor_fsm = NomadFSM(donor)
    donor_fsm.apply(1, MessageType.NODE_REGISTER, {"node": mock.node()})
    blob = donor_fsm.snapshot()
    args = {"term": 1, "leader": "b", "last_index": 9, "last_term": 1,
            "data": blob}
    chaos.install(ChaosRegistry(
        seed=1, rates={"snapshot.partial_write": 1.0}))
    try:
        resp = n._on_install_snapshot(dict(args))
    finally:
        chaos.uninstall()
    assert resp["success"] is False
    assert n.last_applied == 0 and n.commit_index == 0
    assert n._last_snapshot_index == 0      # nothing accepted
    assert len(n.fsm.store.nodes()) == 0    # FSM untouched
    resp = n._on_install_snapshot(dict(args))   # healthy retry lands
    assert resp["success"] is True
    assert n.last_applied == 9 and n._last_snapshot_index == 9
    assert len(n.fsm.store.nodes()) == 1


def test_log_store_refuses_gapped_append(tmp_path):
    ls = LogStore(str(tmp_path / "wal"), fsync="off")
    ls.append(LogEntry(1, 1, "Noop", None))
    with pytest.raises(ValueError, match="non-contiguous"):
        ls.append(LogEntry(5, 1, "Noop", None))
    ls.close()


# ---------------------------------------------------------- client state


def test_client_db_corrupt_file_moved_aside(tmp_path):
    path = str(tmp_path / "client_state.db")
    with open(path, "wb") as fh:
        fh.write(b"this is not a sqlite database at all")
    db = ClientStateDB(path)                 # recovers instead of raising
    db.put_alloc("a1", {"x": 1})
    assert db.get_allocs() == {"a1": {"x": 1}}
    db.close()
    with open(path + ".corrupt", "rb") as fh:
        assert fh.read().startswith(b"this is not")


def test_client_db_wal_checkpoint_on_close(tmp_path):
    path = str(tmp_path / "client_state.db")
    db = ClientStateDB(path)
    db.put_alloc("a1", {"x": 1})
    db.close()
    wal = path + "-wal"
    assert (not os.path.exists(wal)) or os.path.getsize(wal) == 0
    db2 = ClientStateDB(path)
    assert db2.get_allocs() == {"a1": {"x": 1}}
    db2.close()


def test_client_db_survives_unclean_shutdown(tmp_path):
    path = str(tmp_path / "client_state.db")
    db = ClientStateDB(path)
    db.put_alloc("a1", {"x": 1})
    # crash: the connection is abandoned; the sqlite WAL sidecar holds
    # the write and the next open replays it
    db2 = ClientStateDB(path)
    assert db2.get_allocs() == {"a1": {"x": 1}}
    db2.close()
    db._db.close()


def _sleep_job():
    job = Job(id=f"batch-{time.time_ns()}", name="batch", type="batch",
              task_groups=[TaskGroup(name="g", count=1, tasks=[
                  Task(name="t", driver="raw_exec",
                       config={"command": "/bin/sleep", "args": ["30"]})])])
    job.canonicalize()
    return job


def test_client_crash_restart_recovers_task(tmp_path):
    """A hard-killed client (state DB never closed — the sqlite WAL
    sidecar is what the dead process leaves behind) restarts from its
    data_dir and re-attaches the still-running task."""
    server = Server(ServerConfig(num_schedulers=2, heartbeat_ttl=30.0))
    server.start()
    data_dir = str(tmp_path / "client")
    client = Client(ClientConfig(node_name="c1", data_dir=data_dir,
                                 watch_interval=0.05),
                    rpc=server.endpoints.handle)
    client.start()
    pid = None
    try:
        job = _sleep_job()
        server.register_job(job)
        assert _wait(lambda: [
            a for a in server.store.allocs_by_job("default", job.id)
            if a.client_status == "running"], 15.0)
        client._stop.set()                   # crash: no clean shutdown
        time.sleep(0.3)
        pid = next(iter(client.alloc_runners.values())) \
            .task_runners["t"].handle.pid

        c2 = Client(ClientConfig(node_name="c1", data_dir=data_dir,
                                 watch_interval=0.05),
                    rpc=server.endpoints.handle)
        c2.start()
        try:
            assert _wait(lambda: c2.num_allocs() == 1, 5.0)
            ar = next(iter(c2.alloc_runners.values()))
            assert _wait(lambda: ar.client_status == "running", 5.0)
            assert ar.task_runners["t"].handle.pid == pid
        finally:
            c2.stop()
            client.state_db.close()
    finally:
        server.stop()
        if pid is not None:
            try:
                os.kill(pid, signal.SIGKILL)
            except ProcessLookupError:
                pass


# ------------------------------------------------------------------- soak


DISK_RATES = {
    "disk.torn_write": 0.25,
    "disk.fsync_fail": 0.05,
    "disk.corrupt_read": 0.05,
    "snapshot.partial_write": 0.10,
}


def _canon(blob):
    """Canonicalize an FSM snapshot for equality: pickle memoizes shared
    object references, so two byte-different blobs can encode identical
    state (a replayed server shares objects differently than a
    snapshot-restored one).  Re-pickle each item standalone, order-free."""
    data = pickle.loads(blob)
    out = {}
    for key, val in sorted(data.items()):
        if isinstance(val, list):
            out[key] = sorted(pickle.dumps(v) for v in val)
        elif isinstance(val, dict):
            out[key] = {k: pickle.dumps(v) for k, v in sorted(val.items())}
        else:
            out[key] = pickle.dumps(val)
    return out


def _on_leader(cluster, fn, timeout=15.0):
    """Run fn(leader), retrying across leadership churn."""
    deadline = time.time() + timeout
    while True:
        try:
            return fn(cluster.leader(timeout=5.0))
        except TRANSIENT_ERRORS + (TimeoutError,):
            if time.time() >= deadline:
                raise
            time.sleep(0.05)


@pytest.mark.parametrize("seed", [1, 2, 3, 4, 5])
def test_durability_soak_kill_restart(seed, tmp_path):
    """Hard-kill members mid-commit under seeded disk faults, restart
    them from data_dir, and assert the safety properties hold: one leader
    per term, exactly-once plan application, identical FSM state."""
    reg = ChaosRegistry(seed=seed, rates=DISK_RATES)
    cfg = ServerConfig(num_schedulers=2, heartbeat_ttl=60.0,
                       failed_eval_followup_delay=0.3)
    cluster = Cluster(3, config=cfg,
                      raft_config=RaftConfig(heartbeat_interval=0.02,
                                             election_timeout=0.1),
                      data_dir=str(tmp_path))
    def _tune(s):
        # keep redelivery fast on every incarnation: restart() builds a
        # fresh Server, so the replacement reverts to the 60s production
        # defaults and a lease it holds would outlive the whole soak
        s.broker.nack_timeout = 1.0
        s.broker.initial_nack_delay = 0.05
        s.broker.subsequent_nack_delay = 0.1

    for s in cluster.servers:
        _tune(s)
    rng = random.Random(seed)

    # election-safety monitor: sample every member's (state, term) under
    # its lock for the whole run; two names in one term = safety broken
    leaders_by_term = {}
    stop_mon = threading.Event()

    def _monitor():
        while not stop_mon.is_set():
            for s in list(cluster.servers):
                r = s.raft
                if r is None:
                    continue
                with r._lock:
                    if r.state == "leader":
                        leaders_by_term.setdefault(
                            r.term, set()).add(s.name)
            time.sleep(0.005)

    mon = threading.Thread(target=_monitor, daemon=True)
    jobs = []

    def _add_job():
        j = mock.job()
        j.task_groups[0].count = 2
        jobs.append(j)
        _on_leader(cluster, lambda ld: ld.register_job(j))

    try:
        try:
            chaos.install(reg)
            cluster.start()
            mon.start()
            for _ in range(4):
                nd = mock.node()
                _on_leader(cluster, lambda ld, nd=nd: ld.register_node(nd))
            _add_job()
            for _ in range(2):
                _add_job()           # a commit in flight around the kill
                victim = cluster.servers[
                    rng.randrange(len(cluster.servers))]
                cluster.hard_kill(victim)
                time.sleep(0.2)
                for s in cluster.servers:
                    if s is not victim:      # exercise snapshot faults
                        s.raft.force_snapshot()
                _tune(cluster.restart(victim))
                try:
                    cluster.leader(timeout=10.0)
                except TimeoutError:
                    raftdump = "; ".join(
                        f"{s.name}(state={s.raft.state} term={s.raft.term} "
                        f"est={s._established} "
                        f"commit={s.raft.commit_index} "
                        f"applied={s.raft.last_applied} "
                        f"last_log={s.raft.log.last_index})"
                        for s in cluster.servers if s.raft is not None)
                    pytest.fail(
                        f"seed {seed}: no leader after restart of "
                        f"{victim.name}; {raftdump}; "
                        f"chaos fired: {dict(reg.stats)}")
        finally:
            chaos.uninstall()

        def converged():
            try:
                ld = cluster.leader(timeout=2.0)
            except TimeoutError:
                return False
            for j in jobs:
                live = [a for a in ld.store.allocs_by_job("default", j.id)
                        if not a.terminal_status()]
                if len(live) != j.task_groups[0].count:
                    return False
            if any(not EvalStatus.terminal(e.status)
                   for e in ld.store.evals()):
                return False
            return not ld.broker._unack and not ld.plan_queue._heap

        if not _wait(converged, timeout=30.0):
            # raft-level state first: "no leader" and "leader but stuck
            # work" need different triage, so dump both on the way out
            raftdump = "; ".join(
                f"{s.name}(state={s.raft.state} term={s.raft.term} "
                f"est={s._established} commit={s.raft.commit_index} "
                f"applied={s.raft.last_applied} "
                f"last_log={s.raft.log.last_index})"
                for s in cluster.servers if s.raft is not None)
            try:
                ld = cluster.leader(timeout=5.0)
            except TimeoutError:
                pytest.fail(f"seed {seed}: no leader after soak; {raftdump}; "
                            f"chaos fired: {dict(reg.stats)}")
            counts = {f"job{i}": len(
                [a for a in ld.store.allocs_by_job("default", j.id)
                 if not a.terminal_status()]) for i, j in enumerate(jobs)}
            evdump = "; ".join(
                f"{e.id[-8:]}(type={e.type} status={e.status} "
                f"trig={e.triggered_by})"
                for e in ld.store.evals()
                if not EvalStatus.terminal(e.status))
            pytest.fail(f"seed {seed}: no convergence; live={counts}; "
                        f"open evals: [{evdump}]; "
                        f"unacked={len(ld.broker._unack)} "
                        f"plan_heap={len(ld.plan_queue._heap)}; "
                        f"{raftdump}; chaos fired: {dict(reg.stats)}")

        # exactly-once across restarts: every job has its requested count,
        # never a duplicate placement from a replayed plan
        ld = cluster.leader()
        for j in jobs:
            live = [a for a in ld.store.allocs_by_job("default", j.id)
                    if not a.terminal_status()]
            assert len(live) == j.task_groups[0].count
            assert len({a.id for a in live}) == len(live)

        # identical FSM state on every member once all have applied
        # through the leader's index (barrier commits the whole prefix)
        ld.raft.barrier()
        assert cluster.wait_replication(ld.store.latest_index, timeout=10.0)
        assert _wait(lambda: all(
            s.raft.last_applied >= ld.raft.last_applied
            for s in cluster.servers), 10.0)
        blobs = {s.name: _canon(s.raft.fsm.snapshot())
                 for s in cluster.servers}
        ref = blobs[ld.name]
        for name, blob in blobs.items():
            assert blob == ref, f"seed {seed}: FSM divergence on {name}"

        # election safety held for the entire soak
        multi = {t: sorted(names) for t, names in leaders_by_term.items()
                 if len(names) > 1}
        assert not multi, \
            f"seed {seed}: two leaders in one term: {multi}"
        # the fault schedule actually bit (the soak isn't vacuous)
        assert sum(reg.stats.values()) > 0
    finally:
        stop_mon.set()
        mon.join(2.0)
        chaos.uninstall()
        cluster.stop()
