"""The 2-D ('node_shard','wave') serving mesh (PR 16): device-count
factorization, mesh-identity cache keys, donated usage-basis carries,
upload/compute overlap chaining, and laned-kernel placement parity with
the single-device engine."""
from concurrent.futures import Future

import numpy as np
import pytest

from nomad_tpu import mock
from nomad_tpu.encode import ClusterMatrix
from nomad_tpu.parallel.engine import PlacementEngine, _BulkRequest
from nomad_tpu.scheduler.stack import DenseStack


# ------------------------------------------------------------ mesh shapes

def test_wave_mesh_shape_factorizations(monkeypatch):
    from nomad_tpu.parallel import wave_mesh_shape
    monkeypatch.delenv("NOMAD_TPU_WAVE_SHARDS", raising=False)
    assert wave_mesh_shape(1) == (1, 1)
    assert wave_mesh_shape(2) == (2, 1)
    assert wave_mesh_shape(4) == (2, 2)
    assert wave_mesh_shape(8) == (4, 2)
    assert wave_mesh_shape(16) == (4, 4)
    with pytest.raises(ValueError):
        wave_mesh_shape(0)


def test_wave_mesh_shape_env_override(monkeypatch):
    from nomad_tpu.parallel import wave_mesh_shape
    monkeypatch.setenv("NOMAD_TPU_WAVE_SHARDS", "4")
    assert wave_mesh_shape(8) == (2, 4)
    # a wave extent that does not divide the device count falls back to
    # 1 rather than dropping devices from the mesh
    monkeypatch.setenv("NOMAD_TPU_WAVE_SHARDS", "3")
    assert wave_mesh_shape(8) == (8, 1)
    monkeypatch.setenv("NOMAD_TPU_WAVE_SHARDS", "1")
    assert wave_mesh_shape(8) == (8, 1)
    # explicit argument beats the env knob
    monkeypatch.setenv("NOMAD_TPU_WAVE_SHARDS", "4")
    assert wave_mesh_shape(8, wave_shards=2) == (4, 2)


def test_make_mesh_axis_names(monkeypatch):
    from nomad_tpu.parallel import make_mesh
    from nomad_tpu.parallel.sharded import make_serving_mesh, mesh_key
    monkeypatch.delenv("NOMAD_TPU_WAVE_SHARDS", raising=False)
    m = make_mesh()
    assert tuple(m.axis_names) == ("node_shard", "wave")
    assert dict(m.shape) == {"node_shard": 4, "wave": 2}
    # the serving mesh uses the same factorization -> same identity
    sm = make_serving_mesh()
    assert mesh_key(sm) == mesh_key(m)
    sm1 = make_serving_mesh(wave_shards=1)
    assert dict(sm1.shape) == {"node_shard": 8, "wave": 1}
    assert mesh_key(sm1) != mesh_key(sm)
    # explicit factor pair
    m2 = make_mesh(n_wave_shards=2, n_node_shards=4)
    assert dict(m2.shape) == {"node_shard": 4, "wave": 2}


# ------------------------------------------------------------- fixtures

def _world_cm(n_nodes, seed=3):
    rng = np.random.default_rng(seed)
    cm = ClusterMatrix(initial_rows=n_nodes)
    for i in range(n_nodes):
        n = mock.node()
        n.attributes["rack"] = f"r{i % 4}"
        n.node_resources.cpu.cpu_shares = int(rng.integers(3000, 9000))
        cm.upsert_node(n)
    return cm


def _group_fields(cm, count):
    bj = mock.batch_job()
    btg = bj.task_groups[0]
    btg.count = count
    btg.ephemeral_disk.size_mb = 0
    bg = DenseStack(cm).compile_group(bj, btg)
    return bg


def _bulk_req(cm, bg, count, wave_key, deltas=None, seed=None):
    N = cm.n_rows
    rng = np.random.default_rng(seed)
    feasible = bg.feasible.copy()
    if seed is not None:                  # random infeasible holes
        feasible &= rng.random(N) > 0.1
    return _BulkRequest(
        cm=cm, feasible=feasible,
        affinity=bg.affinity.astype(np.float32),
        has_affinity=bool(bg.has_affinity), desired=int(count),
        penalty=np.zeros(N, bool), coll0=np.zeros(N, np.int32),
        demand=bg.demand.astype(np.float32), count=int(count),
        deltas=list(deltas or []), spread_algorithm=False,
        future=Future(), wave_key=wave_key)


def _results(reqs):
    out = []
    for r in reqs:
        assign, placed, n_eval, n_exh, scores, ticket = \
            r.future.result(timeout=120)
        out.append((np.asarray(assign).copy(), int(placed),
                    np.asarray(scores).copy(), ticket))
    return out


# ------------------------------------------------- sharded cache identity

def test_bulk_kernel_cache_survives_mesh_recreation(monkeypatch):
    """The sharded kernel cache keys on mesh IDENTITY (axis layout +
    device ids), not the Mesh object: a re-created serving mesh must hit
    the compiled entries of its predecessor (zero recompiles), while a
    RESHAPED mesh (different wave extent) must miss."""
    from nomad_tpu.parallel import sharded as sh

    cm = _world_cm(256)
    N = cm.n_rows
    bg = _group_fields(cm, 6)

    def run_once():
        eng = PlacementEngine(shard_min_nodes=8)
        try:
            assert eng._mesh_for(N) is not None
            _a, p, *_rest, t = eng.place_bulk(
                cm, feasible=bg.feasible, affinity=bg.affinity,
                has_affinity=bg.has_affinity, desired=6,
                penalty=np.zeros(N, bool), coll0=np.zeros(N, np.int32),
                demand=bg.demand, count=6, wave_key="ns")
            assert p == 6
            eng.complete(t)
        finally:
            eng.stop()

    def n_bulk_entries():
        return sum(1 for k in sh._SERVING_FN_CACHE
                   if isinstance(k, tuple) and k and k[0] == "bulk")

    run_once()
    before = n_bulk_entries()
    assert before >= 1
    # fresh engine -> fresh Mesh object, same devices/axes -> cache HIT
    run_once()
    assert n_bulk_entries() == before
    # reshaped mesh (wave extent pinned to 1) -> different mesh_key -> MISS
    monkeypatch.setenv("NOMAD_TPU_WAVE_SHARDS", "1")
    run_once()
    assert n_bulk_entries() > before


# ------------------------------------------------------- donated carries

def test_donated_carry_invalidates_loaned_buffer():
    """donate_argnums actually donates: the loaned device basis buffer
    is dead after the kernel runs, the adopted carry is bitwise equal to
    the host snapshot (exact_out reconstruction), and steady state ships
    ZERO basis bytes (no scatters, no re-uploads)."""
    import jax

    cm = _world_cm(64)
    N = cm.n_rows
    bg = _group_fields(cm, 6)
    eng = PlacementEngine()            # N=64 < shard_min -> mesh off
    try:
        assert eng._mesh_for(N) is None
        world = eng._world(cm, N, None)
        loaned = []
        orig = world.loan_basis

        def spy():
            b = orig()
            loaned.append(b)
            return b

        world.loan_basis = spy
        tickets = []
        for i in range(3):
            _a, p, *_rest, t = eng.place_bulk(
                cm, feasible=bg.feasible, affinity=bg.affinity,
                has_affinity=bg.has_affinity, desired=6,
                penalty=np.zeros(N, bool), coll0=np.zeros(N, np.int32),
                demand=bg.demand, count=6, wave_key=f"ns-{i}")
            assert p == 6
            tickets.append(t)
        assert len(loaned) == 3
        assert all(b is not None and b.is_deleted() for b in loaned)
        assert eng.stats["donated_carries"] == 3
        ws = world.stats
        assert ws["basis_loans"] == 3 and ws["basis_adopts"] == 3
        # zero steady-state basis traffic: one epoch upload, then the
        # donated carry IS the next dispatch's basis
        assert ws["full_uploads"] == 1
        assert ws["rows_scattered"] == 0
        assert ws["steady_reuploads"] == 0
        # the adopted device carry is bitwise the host-side basis
        cap_dev, basis_dev = world.device_arrays()
        np.testing.assert_array_equal(
            np.asarray(jax.device_get(basis_dev)),
            eng._basis_for(cm)[:N])
        for t in tickets:
            eng.complete(t)
    finally:
        eng.stop()


# ------------------------------------------------ upload/compute overlap

@pytest.mark.parametrize("shard_min", [8, 1 << 30],
                         ids=["sharded", "single_device"])
def test_overlap_chained_matches_drained(shard_min):
    """A part dispatched while the previous one is still in flight
    (chained behind the donated carry) places exactly what a
    drain-first barrier would: the carry already holds the in-flight
    placements, bitwise."""
    cm = _world_cm(256, seed=11)
    N = cm.n_rows
    bg = _group_fields(cm, 7)

    def run(overlap):
        eng = PlacementEngine(shard_min_nodes=shard_min)
        try:
            parts = [[_bulk_req(cm, bg, 7, f"ns-{j}-{i}") for j in range(2)]
                     for i in range(3)]
            # direct dispatch: each part goes out while the previous is
            # still pending, deterministically exercising the chain;
            # the reference drains after every part
            for part in parts:
                eng._dispatch(part)
                if not overlap:
                    eng._drain_pending()
            eng._drain_pending()
            res = _results([r for part in parts for r in part])
            stats = dict(eng.stats)
            for *_r, t in res:
                eng.complete(t)
            return res, stats
        finally:
            eng.stop()

    chained, s_chained = run(overlap=True)
    drained, s_drained = run(overlap=False)
    assert s_chained["overlap_chained"] >= 1
    assert s_drained["overlap_chained"] == 0
    for (a1, p1, sc1, _t1), (a2, p2, sc2, _t2) in zip(chained, drained):
        assert p1 == p2 == 7
        np.testing.assert_array_equal(a1, a2)
        np.testing.assert_allclose(sc1, sc2, rtol=1e-5)


def test_chained_upload_opens_before_inflight_device_get_closes():
    """The overlap the engine's window deques used to time, asked of the
    spans: with a part in flight, the next part's `engine.put` (stack +
    dirty-row update + dispatch) opens and closes before the in-flight
    part's `engine.device_get` does, so the host prep rides under the
    device's work.  Drained after every part, every put is followed by
    its own device_get."""
    from nomad_tpu import tracing

    cm = _world_cm(64, seed=2)
    bg = _group_fields(cm, 4)

    def phases(overlap):
        tracer = tracing.Tracer(sample_rate=1.0, seed=9)
        prev = tracing.install(tracer)
        eng = PlacementEngine(shard_min_nodes=1 << 30)
        try:
            ctx = tracer.new_context()
            parts = [[_bulk_req(cm, bg, 7, f"ns-{j}-{i}") for j in range(2)]
                     for i in range(3)]
            for part in parts:
                for r in part:
                    r.ctx = ctx
                eng._dispatch(part)
                if not overlap:
                    eng._drain_pending()
            eng._drain_pending()
            for *_r, t in _results([r for part in parts for r in part]):
                eng.complete(t)
            spans = [s for s in tracer.spans(ctx["t"])
                     if s.name in ("engine.put", "engine.device_get")]
            assert all(s.duration >= 0 for s in spans)
            return [s.name for s in spans], dict(eng.stats)
        finally:
            eng.stop()
            tracing.install(prev)

    chained, stats = phases(overlap=True)
    assert stats["overlap_chained"] == 2
    assert chained == ["engine.put", "engine.put", "engine.device_get",
                       "engine.put", "engine.device_get",
                       "engine.device_get"]
    drained, stats = phases(overlap=False)
    assert stats["overlap_chained"] == 0
    assert drained == ["engine.put", "engine.device_get"] * 3


# ------------------------------------------------------ laned parity

@pytest.mark.parametrize("bucket", ["sparse", "dense"])
def test_laned_sharded_parity_with_single_device(bucket):
    """The 2-D laned dispatch — distinct wave_keys scored concurrently
    across the mesh's wave columns — places each lane exactly as the
    single-device engine chains that lane in isolation (lanes are blind
    within a dispatch by construction), covering the sparse (count <=
    SPARSE_CAP) and dense output buckets plus preemption delta rows."""
    cm = _world_cm(256, seed=17)
    N = cm.n_rows
    counts = [5, 9, 12, 7] if bucket == "sparse" else [140, 6, 130, 9]
    bgs = {c: _group_fields(cm, c) for c in set(counts)}
    # preemption rows on one request: usage freed on specific rows
    free = [(3, -bgs[counts[1]].demand.astype(np.float32) * 2.0),
            (17, -bgs[counts[1]].demand.astype(np.float32))]

    def build_reqs():
        reqs = []
        for i, c in enumerate(counts):
            reqs.append(_bulk_req(cm, bgs[c], c, wave_key=f"ns-{i % 3}",
                                  deltas=free if i == 1 else None,
                                  seed=100 + i))
        return reqs

    eng = PlacementEngine(shard_min_nodes=8)
    try:
        mesh = eng._mesh_for(N)
        assert mesh is not None and mesh.shape.get("wave", 1) == 2
        reqs = build_reqs()
        eng._dispatch(reqs)
        eng._drain_pending()
        sharded_res = _results(reqs)
        assert eng.stats["wave_lanes"] == 2
        assert eng.stats["lane_evals"] == len(counts)
        for *_r, t in sharded_res:
            eng.complete(t)
    finally:
        eng.stop()

    # reference: each lane in isolation through the single-device engine
    # (chained within the lane, blind to the other lane)
    bins, mapping = PlacementEngine._lane_bins(build_reqs(), 2)
    ref_by_slot = {}
    for lane, lane_reqs in enumerate(bins):
        if not lane_reqs:
            continue
        ref = PlacementEngine(shard_min_nodes=1 << 30)
        try:
            ref._dispatch(lane_reqs)
            ref._drain_pending()
            for slot, (a, p, sc, t) in enumerate(_results(lane_reqs)):
                ref_by_slot[(lane, slot)] = (a, p, sc)
                ref.complete(t)
        finally:
            ref.stop()

    for i, (a, p, sc, _t) in enumerate(sharded_res):
        ra, rp, rsc = ref_by_slot[mapping[i]]
        assert p == rp == counts[i]
        np.testing.assert_array_equal(a, ra)
        # the sparse output bucket materializes scores for assigned rows
        # only (-inf elsewhere); compare where a placement landed
        rows = a > 0
        np.testing.assert_allclose(sc[rows], rsc[rows], rtol=1e-5)


def test_single_wave_key_matches_pre_laned_semantics():
    """One distinct wave_key degenerates to a single active lane: the
    2-D dispatch chains ALL evals sequentially, identical to the
    single-device fused dispatch."""
    cm = _world_cm(256, seed=23)
    bgs = [_group_fields(cm, c) for c in (6, 6, 6)]

    def run(shard_min):
        eng = PlacementEngine(shard_min_nodes=shard_min)
        try:
            reqs = [_bulk_req(cm, bg, 6, wave_key="only") for bg in bgs]
            eng._dispatch(reqs)
            eng._drain_pending()
            res = _results(reqs)
            for *_r, t in res:
                eng.complete(t)
            return res
        finally:
            eng.stop()

    sharded = run(8)
    single = run(1 << 30)
    for (a1, p1, sc1, _), (a2, p2, sc2, _) in zip(sharded, single):
        assert p1 == p2 == 6
        np.testing.assert_array_equal(a1, a2)
        rows = a1 > 0           # sparse bucket: ref scores only at rows
        np.testing.assert_allclose(sc1[rows], sc2[rows], rtol=1e-5)
