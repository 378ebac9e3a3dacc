"""PlacementEngine batch-path parity: a chained batch dispatch must be
exactly equivalent to sequential single-eval processing (same node picks,
same scores), including sparse usage deltas, and concurrent callers must
coalesce through the public API without changing results."""
import threading

import numpy as np
import pytest

from nomad_tpu import mock
from nomad_tpu.encode import ClusterMatrix
from nomad_tpu.ops.place import place_eval
from nomad_tpu.parallel.engine import PlacementEngine, _Request
from nomad_tpu.scheduler.stack import DenseStack
from concurrent.futures import Future


def _world(n_nodes=16):
    cm = ClusterMatrix(initial_rows=n_nodes)
    for i in range(n_nodes):
        n = mock.node()
        n.attributes["rack"] = f"r{i % 4}"
        cm.upsert_node(n)
    return cm


def _request(cm, count=5, deltas=()):
    job = mock.batch_job()
    job.task_groups[0].count = count
    stack = DenseStack(cm)
    groups = [stack.compile_group(job, tg) for tg in job.task_groups]
    used = cm.used.copy()
    for row, vec in deltas:
        used[row] += vec
    inputs = stack.build_inputs(job, groups, [0] * count, {},
                                used_override=used)
    return _Request(cm=cm, inputs=inputs, deltas=list(deltas),
                    spread_algorithm=False, future=Future())


def _serial_reference(cm, reqs):
    """Sequential processing with the chained-usage semantics the batch
    kernel implements: each eval starts from the usage left by the last."""
    used = cm.used.copy()
    results = []
    for r in reqs:
        u = used.copy()
        for row, vec in r.deltas:
            u[row] += vec
        inp = r.inputs
        inp.used = u
        res = place_eval(inp, r.spread_algorithm)
        results.append(res)
        used = u
        for si in range(inp.demand.shape[0]):
            row = int(res.node[si])
            if row >= 0:
                used[row] += inp.demand[si]
    return results


def test_batch_matches_serial_chained():
    cm = _world()
    engine = PlacementEngine()
    try:
        reqs = [_request(cm, count=3) for _ in range(4)]
        expected = _serial_reference(cm, [_request(cm, count=3)
                                          for _ in range(4)])
        engine._dispatch(reqs)
        for r, exp in zip(reqs, expected):
            got, ticket = r.future.result(timeout=30)
            np.testing.assert_array_equal(got.node[:3], exp.node[:3])
            np.testing.assert_allclose(got.score[:3], exp.score[:3],
                                       rtol=1e-5)
            assert int(got.nodes_evaluated[0]) == int(exp.nodes_evaluated[0])
            engine.complete(ticket)
        assert engine.stats["batched_evals"] == 4
        # all tickets released -> overlay fully drained
        assert not engine._tickets and not engine._overlays
    finally:
        engine.stop()


def test_batch_applies_deltas():
    cm = _world(n_nodes=8)
    engine = PlacementEngine()
    try:
        # free a full node's worth on row 0, consume most of row 1
        free = np.array([-2000.0, -2000.0, 0.0, 0.0], np.float32)
        eat = np.array([3500.0, 7500.0, 0.0, 0.0], np.float32)
        reqs = [_request(cm, count=2, deltas=[(0, free)]),
                _request(cm, count=2, deltas=[(1, eat)])]
        expected = _serial_reference(
            cm, [_request(cm, count=2, deltas=[(0, free)]),
                 _request(cm, count=2, deltas=[(1, eat)])])
        engine._dispatch(reqs)
        for r, exp in zip(reqs, expected):
            got, ticket = r.future.result(timeout=30)
            np.testing.assert_array_equal(got.node[:2], exp.node[:2])
            np.testing.assert_allclose(got.score[:2], exp.score[:2],
                                       rtol=1e-5)
            engine.complete(ticket)
    finally:
        engine.stop()


def test_concurrent_callers_coalesce():
    cm = _world()
    engine = PlacementEngine()
    try:
        # hold the dispatcher busy with one request so the rest queue up
        # and form a batch
        n_callers = 6
        barrier = threading.Barrier(n_callers)
        results = [None] * n_callers
        errors = []

        tickets = []

        def call(i):
            try:
                r = _request(cm, count=3)
                barrier.wait()
                res, ticket = engine.place(cm, r.inputs, r.deltas,
                                           r.spread_algorithm)
                results[i] = res
                tickets.append(ticket)
            except Exception as e:              # noqa: BLE001
                errors.append(e)

        threads = [threading.Thread(target=call, args=(i,))
                   for i in range(n_callers)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        # in the real flow a ticket is released only after its plan
        # commits into cm.used; here nothing commits, so release at the
        # end to keep every in-flight contribution visible to later
        # batches
        for t_ in tickets:
            engine.complete(t_)
        assert not errors
        assert all(r is not None for r in results)
        # every caller placed all 3 allocs somewhere valid
        for r in results:
            assert (r.node[:3] >= 0).all()
        # chained usage: total demand across callers must fit --
        # reconstruct usage and check no node is over capacity
        total = cm.used.copy()
        demand = _request(cm, count=3).inputs.demand
        for r in results:
            for si in range(3):
                total[int(r.node[si])] += demand[si]
        assert (total <= cm.capacity + 1e-3).all()
    finally:
        engine.stop()


def test_packed_cache_hits_and_single_path():
    """The content-addressed device cache dedupes identical heavy blocks
    across evals (same job state -> hit -> zero bytes shipped) and the
    packed single-eval path matches the raw kernel."""
    cm = _world()
    engine = PlacementEngine()
    try:
        # single-eval path parity vs place_eval
        r = _request(cm, count=3)
        exp = place_eval(_request(cm, count=3).inputs, False)
        engine._dispatch([r])
        got, ticket = r.future.result(timeout=30)
        np.testing.assert_array_equal(got.node[:3], exp.node[:3])
        np.testing.assert_allclose(got.score[:3], exp.score[:3], rtol=1e-5)
        engine.complete(ticket)
        assert engine._cache.misses >= 1

        # identical-content batch: every heavy block after the first hits
        misses0 = engine._cache.misses
        reqs = [_request(cm, count=3) for _ in range(4)]
        engine._dispatch(reqs)
        for rq in reqs:
            _, t = rq.future.result(timeout=30)
            engine.complete(t)
        assert engine._cache.misses == misses0   # all heavy blocks cached
        assert engine._cache.hits >= 4
    finally:
        engine.stop()


def test_device_world_upload_never_aliases_host_snapshot():
    """Regression: on the CPU backend `jax.device_put` zero-copy aliases
    the numpy buffer, so uploading `_basis_last` itself let apply_rank1's
    NATIVE host scatter mutate the "device" array in place — the jitted
    scatter then added the delta again and the device basis drifted to
    snapshot + demand on every commit.  The upload must own its bytes."""
    import jax

    from nomad_tpu.parallel.world import DeviceWorld

    N, R = 16, 4
    world = DeviceWorld(mesh=None)
    capacity = np.full((N, R), 100.0, np.float32)
    world.update(capacity, np.zeros((N, R), np.float32))

    rows = np.array([0, 3], np.int32)
    demand = np.array([5.0, 2.0, 0.0, 0.0], np.float32)
    world.apply_rank1(rows, np.ones(2, np.int32), demand)

    _, basis_dev = world.device_arrays()
    got = np.asarray(jax.device_get(basis_dev)).copy()
    expect = np.zeros((N, R), np.float32)
    expect[rows] = demand
    np.testing.assert_array_equal(got, expect)
    np.testing.assert_array_equal(world.host_basis(), expect)


def test_engine_single_device_world_resident_across_evals():
    """The unsharded engine path keeps the world device-resident: the
    second eval's dispatch diffs clean against the post-commit snapshot
    (zero rows scattered, no second full upload) and placements match a
    from-scratch engine seeing the same committed state."""
    cm = ClusterMatrix()
    for _ in range(32):
        cm.upsert_node(mock.node())
    j = mock.batch_job()
    j.task_groups[0].count = 8
    st = DenseStack(cm)
    g = st.compile_group(j, j.task_groups[0])
    N = cm.n_rows
    demand = np.zeros(cm.used.shape[1], np.float32)
    dm = np.asarray(g.demand, np.float32)
    demand[:min(len(dm), len(demand))] = dm[:len(demand)]
    bulk = dict(feasible=g.feasible, affinity=g.affinity.astype(np.float32),
                has_affinity=bool(g.has_affinity), desired=8,
                penalty=np.zeros(N, bool), coll0=np.zeros(N, np.int32),
                demand=g.demand.astype(np.float32), count=8)

    def one_eval(eng):
        assign, placed, _e, _x, _s, ticket = eng.place_bulk(cm, **bulk)
        rows = np.flatnonzero(assign)
        for r in rows:
            cm.used[r] += assign[r] * demand
        if ticket is not None:
            eng.complete(ticket)
        return np.asarray(assign).copy()

    used0 = cm.used.copy()
    eng = PlacementEngine(shard_min_nodes=1 << 30)   # force single-device
    try:
        a1 = one_eval(eng)
        a2 = one_eval(eng)
        world = next(iter(eng._worlds.values()))
        assert world.stats["full_uploads"] == 1
        assert world.stats["rows_scattered"] == 0    # commits kept it clean
        assert world.stats["rank1_applies"] >= 1
    finally:
        eng.stop()

    committed = cm.used.copy()
    cm.used[:] = used0
    for r in np.flatnonzero(a1):
        cm.used[r] += a1[r] * demand
    fresh = PlacementEngine(shard_min_nodes=1 << 30)
    try:
        a2_fresh = one_eval(fresh)
    finally:
        fresh.stop()
    np.testing.assert_array_equal(a2, a2_fresh)
    np.testing.assert_array_equal(cm.used, committed)


def test_get_engine_has_no_off_switch(monkeypatch):
    """NOMAD_TPU_ENGINE=0 once made get_engine() return None and every
    caller carried a second scheduler behind `is None`; the variable now
    means nothing and the engine is always there."""
    from nomad_tpu.parallel.engine import get_engine

    monkeypatch.setenv("NOMAD_TPU_ENGINE", "0")
    assert isinstance(get_engine(), PlacementEngine)


# ------------------------------------------- the families serving reaches

_SERVED = {"place.batch_packed", "place.bulk_batch_donate"}


@pytest.fixture(scope="module")
def warmed():
    """One engine on one device, warmed for one scan class and one bulk
    class; yields (engine, matrix, bulk fields, the kernel families whose
    jit caches grew in the warm-up)."""
    from nomad_tpu import knobs
    from nomad_tpu.analysis import recompile

    cm = _world(64)
    N = cm.n_rows
    j = mock.batch_job()
    g = DenseStack(cm).compile_group(j, j.task_groups[0])
    bulk = dict(feasible=g.feasible, affinity=g.affinity.astype(np.float32),
                has_affinity=bool(g.has_affinity), desired=8,
                penalty=np.zeros(N, bool), coll0=np.zeros(N, np.int32),
                demand=g.demand.astype(np.float32), count=8)
    # a byte budget of eight evals' heavy blocks: the warm grid is the
    # bulk buckets 1 and 8, not all six
    with knobs.override("NOMAD_TPU_BULK_BYTES", 8 * 4 * N * 4):
        eng = PlacementEngine(shard_min_nodes=1 << 30)
    try:
        assert eng._mesh_for(N) is None and eng._bulk_chunk(N) == 8
        budget = recompile.Budget()
        eng.warmup(cm, inputs=_request(cm, count=5).inputs, bulk=bulk)
        grew = {k for k in budget.report()["recompiled"]
                if k.startswith(("place.", "sharded."))}
        yield eng, cm, bulk, grew
    finally:
        eng.stop()


def _bulk_reqs(cm, bulk, counts, deltas=()):
    from nomad_tpu.parallel.engine import _BulkRequest
    return [_BulkRequest(cm=cm, deltas=list(deltas), spread_algorithm=False,
                         future=Future(), wave_key=f"ns-{i}",
                         **dict(bulk, count=c, desired=c))
            for i, c in enumerate(counts)]


def _some_deltas(n):
    vec = np.array([1.0, 1.0, 0.0, 0.0], np.float32)
    return [(i % 64, vec) for i in range(n)]


@pytest.mark.parametrize("form", [
    "lone_scan", "scan_group", "bulk_sparse", "bulk_dense", "bulk_deltas",
    "bulk_delta_overflow"])
def test_serving_reaches_two_kernel_families(warmed, form):
    """Every form of traffic one device serves goes through
    `place.batch_packed` or `place.bulk_batch_donate`, at a shape the
    warm-up compiled: no other placement family grows in the warm-up, and
    no registered kernel compiles once it is over."""
    from nomad_tpu.analysis import recompile
    from nomad_tpu.ops.place import SPARSE_CAP
    from nomad_tpu.parallel.engine import _DELTA_BUCKET

    eng, cm, bulk, grew_warming = warmed
    assert grew_warming == _SERVED
    before = dict(eng.stats)
    budget = recompile.Budget()
    reqs = {
        "lone_scan": lambda: [_request(cm, count=3)],
        "scan_group": lambda: [_request(cm, count=3) for _ in range(3)],
        "bulk_sparse": lambda: _bulk_reqs(cm, bulk, [7, 5, 3]),
        "bulk_dense": lambda: _bulk_reqs(cm, bulk, [SPARSE_CAP + 2, 4]),
        "bulk_deltas": lambda: _bulk_reqs(cm, bulk, [7, 5],
                                          _some_deltas(2)),
        "bulk_delta_overflow": lambda: _bulk_reqs(
            cm, bulk, [6], _some_deltas(_DELTA_BUCKET + 1)),
    }[form]()
    eng._dispatch(reqs)
    eng._drain_pending()
    tickets = [r.future.result(timeout=60)[-1] for r in reqs]
    want = {"lone_scan": {"single_evals": 1},
            "scan_group": {"batched_evals": 3}}.get(
        form, {"bulk_groups": 1, "bulk_parts": 1, "donated_carries": 1,
               "bulk_evals": len(reqs)})
    for t in tickets:
        eng.complete(t)
    assert {k: eng.stats[k] - before[k] for k in want} == want
    assert budget.violations() == []


def test_scan_steps_counted_and_no_variant_added(warmed):
    """`scan_steps_run` over `scan_steps_bucket` says how far the slot
    loop's bound engages: each eval's last active slot + 1 against the
    E x S of its dispatch.  The bound is read on the device from
    `slot_active`, so another slot count is the same compiled kernel and
    the registry is what it was."""
    from nomad_tpu.analysis import recompile

    eng, cm, _bulk, _grew = warmed
    assert {k for k in recompile.cache_sizes() if k.startswith("place.")} \
        == {"place.eval_packed", "place.eval", "place.batch_packed",
            "place.bulk", "place.bulk_batch_donate"}
    budget = recompile.Budget()

    def steps(counts):
        before = dict(eng.stats)
        reqs = [_request(cm, count=c) for c in counts]
        eng._dispatch(reqs)
        for r in reqs:
            res, ticket = r.future.result(timeout=60)
            assert (res.node[:r.inputs.slot_active.sum()] >= 0).all()
            eng.complete(ticket)
        return tuple(eng.stats[k] - before[k]
                     for k in ("scan_steps_run", "scan_steps_bucket"))

    assert steps([5]) == (5, 16)
    assert steps([3, 5, 2]) == (3 + 5 + 2, 8 * 16)
    assert steps([7]) == (7, 16)
    assert budget.violations() == []
