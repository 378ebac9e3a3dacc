"""Invariant linter suite tests: fixture corpus per checker (seeded
violations caught, allow-comment suppresses, clean tree passes), the CLI
contract, the runtime lock-order recorder, and FSM replay determinism
(the property the fsm-determinism checker exists to protect)."""
import copy
import json
import subprocess
import sys
import threading
from pathlib import Path

import pytest

from nomad_tpu import mock
from nomad_tpu.analysis import CHECKERS, run_all
from nomad_tpu.analysis.lock_order import LockOrderRecorder
from nomad_tpu.raft import MessageType, NomadFSM
from nomad_tpu.state import StateStore

FIXTURES = Path(__file__).resolve().parent / "fixtures" / "analysis"
REPO = Path(__file__).resolve().parent.parent

# (fixture dir, checker name, findings seeded in bad/)
CASES = [
    ("fsm_determinism", "fsm-determinism", 4),
    ("lock_discipline", "lock-discipline", 1),
    ("native_abi", "native-abi", 5),
    ("jax_purity", "jax-purity", 4),
    ("chaos_coverage", "chaos-coverage", 5),
    ("transfer_purity", "transfer-purity", 6),
    ("recompile", "recompile-budget", 2),
    ("race", "happens-before", 5),
    ("snapshot_completeness", "snapshot-completeness", 10),
    ("canonical_form", "canonical-form", 6),
    ("wait_graph", "wait-graph", 4),
    ("context_propagation", "context-propagation", 8),
    ("deadline_coverage", "deadline-coverage", 7),
    ("donation_safety", "donation-safety", 6),
    ("knob_registry", "knob-registry", 7),
    ("allow_audit", "allow-audit", 3),
]


# ------------------------------------------------------------ fixture corpus


@pytest.mark.parametrize("fixture,checker,n_bad", CASES,
                         ids=[c[1] for c in CASES])
def test_seeded_violations_caught(fixture, checker, n_bad):
    findings = run_all(FIXTURES / fixture / "bad", checkers=[checker])
    assert len(findings) == n_bad
    assert all(f.checker == checker for f in findings)
    assert all(f.line > 0 and f.message for f in findings)


@pytest.mark.parametrize("fixture,checker,n_bad", CASES,
                         ids=[c[1] for c in CASES])
def test_allow_comment_suppresses(fixture, checker, n_bad):
    assert run_all(FIXTURES / fixture / "allowed", checkers=[checker]) == []


@pytest.mark.parametrize("fixture,checker,n_bad", CASES,
                         ids=[c[1] for c in CASES])
def test_clean_tree_passes(fixture, checker, n_bad):
    assert run_all(FIXTURES / fixture / "clean", checkers=[checker]) == []


@pytest.mark.parametrize("fixture,checker,n_bad", CASES,
                         ids=[c[1] for c in CASES])
def test_allowed_corpus_is_audit_clean(fixture, checker, n_bad):
    """Every allowed-corpus suppression carries a reason and is consulted
    by the checker it names: run_all runs the whole suite before the
    audit, so a dead or reasonless allow would surface here."""
    assert run_all(FIXTURES / fixture / "allowed",
                   checkers=[checker, "allow-audit"]) == []


def test_transitive_findings_carry_call_chain():
    findings = run_all(FIXTURES / "fsm_determinism" / "bad",
                       checkers=["fsm-determinism"])
    transitive = [f for f in findings if len(f.chain) > 1]
    assert transitive, "expected the helper's entropy via a call chain"
    assert transitive[0].chain == ("MiniFSM._apply_job", "MiniFSM._stamp")


def test_repo_tree_is_clean():
    """The acceptance bar: the linters find nothing in the repo itself."""
    assert [f.render() for f in run_all(REPO)] == []


def test_unknown_checker_rejected():
    with pytest.raises(ValueError, match="unknown checker"):
        run_all(FIXTURES / "fsm_determinism" / "clean", checkers=["nope"])


def test_wait_graph_merges_runtime_corpus_into_cycle():
    """A runtime-observed edge opposite to a static one must close a
    cycle — the merged graph is the whole point of the shared corpus."""
    from nomad_tpu.analysis import wait_graph
    from nomad_tpu.analysis.common import load_corpus, lock_alloc_sites

    root = FIXTURES / "wait_graph" / "clean"
    corpus = load_corpus(root)
    sites = lock_alloc_sites(corpus.py)
    la, lb = sites[("Pair", "_la")], sites[("Pair", "_lb")]
    corpus.lock_corpus = {
        "format": "nomad-tpu-lock-order/1",
        "edges": [{"a": lb, "b": la, "thread": "t9", "held": [lb]}],
    }
    findings = wait_graph.run(corpus)
    assert len(findings) == 1
    msg = findings[0].message
    assert "lock-order cycle" in msg
    assert "[runtime: thread t9]" in msg and "[static:" in msg


def test_wait_graph_rejects_foreign_corpus_format():
    from nomad_tpu.analysis import wait_graph
    from nomad_tpu.analysis.common import load_corpus

    corpus = load_corpus(FIXTURES / "wait_graph" / "clean")
    corpus.lock_corpus = {"format": "bogus/9", "edges": []}
    findings = wait_graph.run(corpus)
    assert len(findings) == 1
    assert "format" in findings[0].message


# ------------------------------------------------------------------ the CLI


def _cli(*args):
    return subprocess.run(
        [sys.executable, "-m", "nomad_tpu.analysis", *args],
        capture_output=True, text=True, cwd=str(REPO))


def test_cli_exits_nonzero_on_findings():
    res = _cli("--root", str(FIXTURES / "lock_discipline" / "bad"),
               "--checker", "lock-discipline")
    assert res.returncode == 1
    assert "[lock-discipline]" in res.stdout


def test_cli_exits_zero_on_clean_tree():
    res = _cli("--root", str(FIXTURES / "lock_discipline" / "clean"),
               "--checker", "lock-discipline")
    assert res.returncode == 0


def test_cli_json_output():
    res = _cli("--root", str(FIXTURES / "native_abi" / "bad"),
               "--checker", "native-abi", "--json")
    assert res.returncode == 1
    doc = json.loads(res.stdout)
    assert len(doc["findings"]) == 5
    assert {f["checker"] for f in doc["findings"]} == {"native-abi"}
    assert all({"path", "line", "message"} <= set(f) for f in doc["findings"])


def test_cli_list_checkers():
    res = _cli("--list-checkers")
    assert res.returncode == 0
    assert res.stdout.split() == list(CHECKERS)
    assert len(CHECKERS) == 16


def test_cli_checkers_csv_and_json_counts():
    res = _cli("--root", str(FIXTURES / "wait_graph" / "bad"),
               "--checkers", "wait-graph,allow-audit", "--json")
    assert res.returncode == 1
    doc = json.loads(res.stdout)
    assert doc["checkers"] == ["wait-graph", "allow-audit"]
    assert doc["counts"]["wait-graph"] == 4
    assert doc["counts"]["allow-audit"] == 0
    assert len(doc["findings"]) == 4


def test_cli_baseline_ratchets_known_findings(tmp_path):
    """--baseline turns known debt into exit 0: a report generated from
    the same tree baselines every finding away."""
    root = str(FIXTURES / "knob_registry" / "bad")
    res = _cli("--root", root, "--checker", "knob-registry", "--json")
    assert res.returncode == 1
    baseline = tmp_path / "report.json"
    baseline.write_text(res.stdout)
    res2 = _cli("--root", root, "--checker", "knob-registry",
                "--baseline", str(baseline))
    assert res2.returncode == 0
    assert "0 new findings" in res2.stdout
    assert "(7 baselined)" in res2.stdout


def test_cli_baseline_fails_on_new_findings(tmp_path):
    """Findings not in the baseline still fail, and only they print."""
    root = str(FIXTURES / "knob_registry" / "bad")
    res = _cli("--root", root, "--checker", "knob-registry", "--json")
    doc = json.loads(res.stdout)
    doc["findings"] = [f for f in doc["findings"]
                       if "NOMAD_TPU_RAW_GET`" not in f["message"]]
    baseline = tmp_path / "report.json"
    baseline.write_text(json.dumps(doc))
    res2 = _cli("--root", root, "--checker", "knob-registry",
                "--baseline", str(baseline), "--json")
    assert res2.returncode == 1
    out = json.loads(res2.stdout)
    assert len(out["findings"]) == 1
    assert "NOMAD_TPU_RAW_GET" in out["findings"][0]["message"]
    assert out["baselined"] == 6


def test_cli_baseline_unreadable_is_usage_error(tmp_path):
    p = tmp_path / "nope.json"
    res = _cli("--root", str(FIXTURES / "lock_discipline" / "clean"),
               "--baseline", str(p))
    assert res.returncode == 2
    assert "--baseline" in res.stderr


def test_cli_lock_corpus_flag(tmp_path):
    from nomad_tpu.analysis.common import load_corpus, lock_alloc_sites

    root = FIXTURES / "wait_graph" / "clean"
    sites = lock_alloc_sites(load_corpus(root).py)
    corpus = {"format": "nomad-tpu-lock-order/1",
              "edges": [{"a": sites[("Pair", "_lb")],
                         "b": sites[("Pair", "_la")],
                         "thread": "t1", "held": []}]}
    p = tmp_path / "corpus.json"
    p.write_text(json.dumps(corpus))
    res = _cli("--root", str(root), "--checker", "wait-graph",
               "--lock-corpus", str(p))
    assert res.returncode == 1
    assert "lock-order cycle" in res.stdout


def test_cli_rejects_foreign_lock_corpus(tmp_path):
    p = tmp_path / "bogus.json"
    p.write_text('{"format": "other/1"}')
    res = _cli("--root", str(FIXTURES / "wait_graph" / "clean"),
               "--lock-corpus", str(p))
    assert res.returncode == 2
    assert "lock-order corpus" in res.stderr


def test_cli_runs_without_jax():
    """The analyzers are stdlib-only: a bare interpreter that cannot
    import jax must still run them (the CI analysis leg relies on it)."""
    code = ("import sys; sys.modules['jax'] = None; "
            "from nomad_tpu.analysis.__main__ import main; "
            "sys.exit(main(['--root', sys.argv[1]]))")
    res = subprocess.run(
        [sys.executable, "-c", code,
         str(FIXTURES / "lock_discipline" / "clean")],
        capture_output=True, text=True, cwd=str(REPO))
    assert res.returncode == 0, res.stderr


# ------------------------------------------------- runtime lock-order cycles


def _nest(outer, inner):
    with outer:
        with inner:
            pass


def _wrapped(rec, name):
    """A recorded lock over a raw _thread lock: invisible to any outer
    (session-level) recorder, so deliberately seeded cycles stay local."""
    import _thread

    from nomad_tpu.analysis.lock_order import _RecordingLock
    return _RecordingLock(_thread.allocate_lock(), name, rec)


def test_lock_order_recorder_flags_cycle():
    rec = LockOrderRecorder()
    a = _wrapped(rec, "lock-a")
    b = _wrapped(rec, "lock-b")
    _nest(a, b)
    t = threading.Thread(target=_nest, args=(b, a))
    t.start()
    t.join()
    cycles = rec.cycles()
    assert len(cycles) == 1
    rendered = rec.render_cycles()
    assert "lock-order cycle" in rendered and "lock-a" in rendered


def test_lock_order_recorder_consistent_order_is_clean():
    rec = LockOrderRecorder()
    a = _wrapped(rec, "lock-a")
    b = _wrapped(rec, "lock-b")
    c = _wrapped(rec, "lock-c")
    _nest(a, b)
    _nest(b, c)
    t = threading.Thread(target=_nest, args=(a, c))
    t.start()
    t.join()
    assert rec.cycles() == []


def test_lock_order_recorder_install_wraps_new_locks():
    from nomad_tpu.analysis.lock_order import _RecordingLock
    rec = LockOrderRecorder()
    with rec:
        assert isinstance(threading.Lock(), _RecordingLock)
        assert isinstance(threading.RLock(), _RecordingLock)


def test_lock_order_recorder_wraps_condition():
    """Condition() over a recorded RLock keeps the wait/notify protocol
    (the wrapper must delegate _release_save/_acquire_restore)."""
    rec = LockOrderRecorder()
    with rec:
        cv = threading.Condition(threading.RLock())
        hits = []

        def waiter():
            with cv:
                while not hits:
                    cv.wait(timeout=2.0)

        t = threading.Thread(target=waiter)
        t.start()
        with cv:
            hits.append(1)
            cv.notify_all()
        t.join()
    assert rec.cycles() == []


def test_lock_order_dump_load_roundtrip(tmp_path):
    """dump() writes the shared corpus format wait-graph consumes."""
    from nomad_tpu.analysis import load_lock_corpus
    from nomad_tpu.analysis.lock_order import LOCK_ORDER_FORMAT

    rec = LockOrderRecorder()
    a = _wrapped(rec, "store.py:10")
    b = _wrapped(rec, "wal.py:20")
    _nest(a, b)
    path = tmp_path / "corpus.json"
    rec.dump(path)
    data = load_lock_corpus(path)
    assert data["format"] == LOCK_ORDER_FORMAT
    assert len(data["edges"]) == 1
    edge = data["edges"][0]
    assert edge["a"] == "store.py:10" and edge["b"] == "wal.py:20"
    assert edge["thread"] and edge["held"] == ["store.py:10"]


def test_load_lock_corpus_rejects_foreign_json(tmp_path):
    from nomad_tpu.analysis import load_lock_corpus

    p = tmp_path / "x.json"
    p.write_text('{"what": 1}')
    with pytest.raises(ValueError, match="lock-order corpus"):
        load_lock_corpus(p)


def test_lock_order_recorder_uninstall_restores_factories():
    orig_lock, orig_rlock = threading.Lock, threading.RLock
    rec = LockOrderRecorder().install()
    rec.uninstall()
    assert threading.Lock is orig_lock and threading.RLock is orig_rlock


# ------------------------------------------- runtime happens-before detection


@pytest.fixture
def race_detector():
    """An installed RaceDetector wired to the module hooks, torn down
    even on assertion failure (a leaked detector corrupts every later
    test that allocates a lock)."""
    from nomad_tpu.analysis import race as race_mod
    from nomad_tpu.analysis.race import RaceDetector
    det = RaceDetector().install()
    prev, race_mod.active = race_mod.active, det
    try:
        yield race_mod, det
    finally:
        race_mod.active = prev
        det.uninstall()


def test_race_detector_flags_unlocked_writes(race_detector):
    race_mod, det = race_detector
    gate = threading.Barrier(2)

    def unlocked():
        gate.wait()
        for _ in range(100):
            race_mod.write("Demo._tbl", None)

    ts = [threading.Thread(target=unlocked) for _ in range(2)]
    [t.start() for t in ts]
    [t.join() for t in ts]
    assert det.races
    rendered = det.races[0].render()
    assert "Demo._tbl" in rendered and "unordered" in rendered


def test_race_detector_locked_writes_are_clean(race_detector):
    race_mod, det = race_detector
    lk = threading.Lock()       # allocated under install() -> wrapped
    gate = threading.Barrier(2)

    def locked():
        gate.wait()
        for _ in range(100):
            with lk:
                race_mod.write("Demo._tbl", None)

    ts = [threading.Thread(target=locked) for _ in range(2)]
    [t.start() for t in ts]
    [t.join() for t in ts]
    assert det.races == [], det.render_races()
    assert det.cycles() == []


def test_race_detector_fork_join_orders_accesses(race_detector):
    race_mod, det = race_detector
    race_mod.write("Demo._tbl", None)
    t = threading.Thread(target=lambda: race_mod.write("Demo._tbl", None))
    t.start()
    t.join()
    race_mod.write("Demo._tbl", None)
    assert det.races == [], det.render_races()


def test_race_detector_condition_handoff_is_clean(race_detector):
    """Producer writes under the condition, consumer reads after wait:
    the wrapped RLock's _release_save/_acquire_restore pair must carry
    the clocks through the wait."""
    race_mod, det = race_detector
    cv = threading.Condition(threading.RLock())
    ready = []

    def producer():
        with cv:
            race_mod.write("Demo._q", None)
            ready.append(1)
            cv.notify()

    def consumer():
        with cv:
            while not ready:
                cv.wait(timeout=5.0)
            race_mod.read("Demo._q", None)

    tc = threading.Thread(target=consumer)
    tp = threading.Thread(target=producer)
    tc.start()
    tp.start()
    tc.join()
    tp.join()
    assert det.races == [], det.render_races()


def test_race_detector_uninstall_restores_patches():
    from nomad_tpu.analysis.race import RaceDetector
    orig = (threading.Lock, threading.RLock,
            threading.Thread.start, threading.Thread.join)
    det = RaceDetector().install()
    det.uninstall()
    assert (threading.Lock, threading.RLock,
            threading.Thread.start, threading.Thread.join) == orig


def test_race_hooks_tolerate_missing_detector():
    """Production hooks must be safe (and near-free) with no detector
    installed — they run unconditionally on the hot path."""
    from nomad_tpu.analysis import race as race_mod
    race_mod.read("Demo._tbl", None)
    race_mod.write("Demo._tbl", None)


# ------------------------------------------------------ FSM replay determinism


def _fsm_log():
    """A log exercising the once-nondeterministic paths: job register
    (submit_time), eval update (create/modify times), deployment upsert,
    plan results, and a deregister.  Timestamps are pre-stamped the way
    the propose path does it now."""
    node = mock.node()
    job = mock.job(submit_time=1234.5)
    ev = mock.eval(job_id=job.id, create_time=10.0, modify_time=10.0)
    alloc = mock.alloc_for(job, node.id)
    return [
        (1, MessageType.NODE_REGISTER, {"node": node}),
        (2, MessageType.JOB_REGISTER, {"job": job}),
        (3, MessageType.EVAL_UPDATE, {"evals": [ev]}),
        (4, MessageType.ALLOC_UPDATE, {"allocs": [alloc]}),
        (5, MessageType.JOB_DEREGISTER,
         {"namespace": "default", "job_id": job.id, "purge": False}),
    ]


def _replay(log):
    fsm = NomadFSM(StateStore())
    for index, msg_type, payload in copy.deepcopy(log):
        fsm.apply(index, msg_type, payload)
    return fsm.snapshot()


def test_fsm_replay_is_byte_identical():
    log = _fsm_log()
    assert _replay(log) == _replay(log)


def test_snapshot_derived_builders_are_real_methods():
    """The _SNAPSHOT_DERIVED contract the snapshot-completeness checker
    enforces statically, asserted live: every declared builder exists
    and every derived table is in the replicated universe."""
    for table, builder in StateStore._SNAPSHOT_DERIVED.items():
        assert callable(getattr(StateStore, builder)), (table, builder)
        assert table in StateStore._LOCK_PROTECTED, table


def test_restore_rebuilds_derived_indexes_like_a_live_store():
    """A restored follower's derived indexes must equal a live
    survivor's — including the liveness index, which must NOT contain
    terminal allocs.  Apply and restore share the _index_*_locked
    builders, so the two paths cannot drift."""
    from nomad_tpu.structs import AllocClientStatus

    node = mock.node()
    job = mock.job(submit_time=1.0)
    live_a = mock.alloc_for(job, node.id)
    dead_a = mock.alloc_for(job, node.id, index=1,
                            client_status=AllocClientStatus.COMPLETE)
    # a system job's allocations share one name: the index's inner key,
    # the node, is what tells them apart
    node2 = mock.node()
    sys_job = mock.system_job(submit_time=1.0)
    live_s = mock.alloc_for(sys_job, node.id)
    dead_s = mock.alloc_for(sys_job, node2.id,
                            client_status=AllocClientStatus.FAILED)
    log = [
        (1, MessageType.NODE_REGISTER, {"node": node}),
        (2, MessageType.JOB_REGISTER, {"job": job}),
        (3, MessageType.ALLOC_UPDATE, {"allocs": [live_a, dead_a]}),
        (4, MessageType.NODE_REGISTER, {"node": node2}),
        (5, MessageType.JOB_REGISTER, {"job": sys_job}),
        (6, MessageType.ALLOC_UPDATE, {"allocs": [live_s, dead_s]}),
    ]
    live = NomadFSM(StateStore())
    for index, msg_type, payload in copy.deepcopy(log):
        live.apply(index, msg_type, payload)
    restored = NomadFSM(StateStore())
    # onto a used store: a holder the snapshot does not have must not
    # survive the restore
    restored.store.upsert_allocs(1, [mock.alloc_for(sys_job, node2.id)])
    restored.restore(live.snapshot())
    ls, rs = live.store, restored.store
    for table in ("_allocs_by_job", "_allocs_by_node", "_allocs_by_eval",
                  "_evals_by_job", "_services_by_alloc"):
        assert dict(getattr(ls, table)) == dict(getattr(rs, table)), table
    assert ls._live_names == rs._live_names
    # the two live allocations and nothing else: no terminal one, and
    # not the holder the restore wrote over
    assert rs._live_names == {
        ("default", job.id, live_a.name): {node.id: {live_a.id}},
        ("default", sys_job.id, live_s.name): {node.id: {live_s.id}}}
    assert set(ls._acl_by_secret) == set(rs._acl_by_secret)
    assert ls._applied_plan_ids_set == rs._applied_plan_ids_set


def test_fsm_replay_matches_snapshot_restore_roundtrip():
    """Replay onto a restored snapshot must agree with direct replay —
    the plan_id dedup ring and follower catch-up both rely on it.
    Compared after a loads/dumps normalization pass: raw snapshot bytes
    differ across a restore only in pickle's string-memoization layout
    (object identity of interned keys), not in state."""
    import pickle

    def canon(blob):
        return pickle.dumps(pickle.loads(blob))

    log = _fsm_log()
    blob = _replay(log)
    fsm = NomadFSM(StateStore())
    fsm.restore(blob)
    assert canon(fsm.snapshot()) == canon(blob)
