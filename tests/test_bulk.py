"""Bulk wavefront kernel parity: for identical slots with spreads
inactive, place_bulk_jit must produce the same per-node assignment counts
as the sequential per-slot scan kernel (which is itself golden-tested
against the reference's semantics)."""
import numpy as np
import pytest

from nomad_tpu import mock
from nomad_tpu.encode import ClusterMatrix
from nomad_tpu.ops.place import place_bulk_jit, place_eval, unpack_bulk
from nomad_tpu.scheduler.stack import DenseStack


def _world(n_nodes, seed=0, heterogeneous=True):
    rng = np.random.default_rng(seed)
    cm = ClusterMatrix(initial_rows=n_nodes)
    for i in range(n_nodes):
        n = mock.node()
        if heterogeneous:
            n.node_resources.cpu.cpu_shares = int(rng.integers(2000, 8000))
            n.node_resources.memory_mb = int(rng.integers(4096, 16384))
        cm.upsert_node(n)
    return cm


def _run_both(cm, count, cpu=500, mem=256, existing=None):
    job = mock.batch_job()
    tg = job.task_groups[0]
    tg.count = count
    tg.tasks[0].resources.cpu = cpu
    tg.tasks[0].resources.memory_mb = mem
    tg.ephemeral_disk.size_mb = 0
    stack = DenseStack(cm)
    g = stack.compile_group(job, tg)
    allocs_by_tg = {tg.name: existing or []}

    # sequential scan
    inputs = stack.build_inputs(job, [g], [0] * count, allocs_by_tg)
    res = place_eval(inputs)
    scan_counts = np.zeros(cm.n_rows, np.int64)
    for si in range(count):
        row = int(res.node[si])
        if row >= 0:
            scan_counts[row] += 1

    # bulk wavefront
    import jax
    coll0 = np.zeros(cm.n_rows, np.int32)
    for a in allocs_by_tg[tg.name]:
        row = cm.row_of.get(a.node_id)
        if row is not None:
            coll0[row] += 1
    packed = place_bulk_jit(
        np.ascontiguousarray(cm.capacity),
        np.ascontiguousarray(cm.used.astype(np.float32)),
        g.feasible, g.affinity.astype(np.float32), bool(g.has_affinity),
        np.int32(max(tg.count, 1)), np.zeros(cm.n_rows, bool), coll0,
        g.demand.astype(np.float32), np.int32(count))
    assign, placed, n_eval, n_exh, scores, waves, used_f = unpack_bulk(
        jax.device_get(packed))
    return scan_counts, np.asarray(assign).astype(np.int64), int(placed)


@pytest.mark.parametrize("n_nodes,count,seed", [
    (8, 12, 1), (16, 40, 2), (32, 100, 3), (16, 7, 4),
])
def test_bulk_matches_scan(n_nodes, count, seed):
    cm = _world(n_nodes, seed=seed)
    scan, bulk, placed = _run_both(cm, count)
    assert placed == scan.sum() == count
    np.testing.assert_array_equal(bulk, scan)


def test_bulk_matches_scan_with_existing_collisions():
    cm = _world(8, seed=5, heterogeneous=False)
    job = mock.batch_job()
    nodes = list(cm.row_of)
    existing = [mock.alloc_for(job, node_id=nodes[0]),
                mock.alloc_for(job, node_id=nodes[0], index=1)]
    # the helper builds its own job; patch task_group names to match
    scan, bulk, placed = _run_both(cm, 20, existing=existing)
    np.testing.assert_array_equal(bulk, scan)


def test_bulk_overflow_partial_placement():
    """More instances than the cluster fits: bulk places what fits and
    reports the rest unplaced, like the scan."""
    cm = _world(4, seed=6, heterogeneous=False)
    scan, bulk, placed = _run_both(cm, 200, cpu=900, mem=2000)
    assert placed < 200
    assert placed == scan.sum()
    np.testing.assert_array_equal(bulk, scan)


def test_bulk_filling_regime():
    """Demand so small that anti-affinity is negligible vs fit gains:
    the filling regime (singleton + fill) must stay exact."""
    cm = _world(4, seed=7, heterogeneous=False)
    scan, bulk, placed = _run_both(cm, 64, cpu=50, mem=100)
    assert placed == 64
    np.testing.assert_array_equal(bulk, scan)


def test_generic_scheduler_uses_bulk_path():
    """End-to-end through the Harness: a large batch job exercises the
    bulk path and lands the same world as before."""
    from nomad_tpu.scheduler.testing import Harness

    h = Harness()
    for _ in range(16):
        h.store.upsert_node(h.next_index(), mock.node())
    job = mock.batch_job()
    tg = job.task_groups[0]
    tg.count = 600                  # >= BULK_MIN
    tg.tasks[0].resources.cpu = 50
    tg.tasks[0].resources.memory_mb = 100
    tg.ephemeral_disk.size_mb = 0
    h.store.upsert_job(h.next_index(), job)
    h.process("batch", mock.eval(job_id=job.id, type="batch"))
    allocs = h.store.allocs_by_job("default", job.id)
    assert len(allocs) == 600
    # usage actually committed and within capacity
    assert (h.store.matrix.used <= h.store.matrix.capacity + 1e-3).all()
    # placement metadata present
    assert allocs[0].metrics.nodes_evaluated > 0


def test_engine_bulk_batch_matches_serial():
    """Concurrent engine.place_bulk calls coalesce into one chained
    dispatch (place_bulk_batch_donate_jit) and must equal sequential bulk
    processing: each eval's placements land on usage that includes the
    previous eval's, and no node ends over capacity."""
    import threading

    import jax
    from nomad_tpu.ops.place import place_bulk_jit
    from nomad_tpu.parallel.engine import PlacementEngine

    cm = _world(32, heterogeneous=True)
    job = mock.batch_job()
    tg = job.task_groups[0]
    tg.count = 12
    tg.tasks[0].resources.cpu = 700
    tg.tasks[0].resources.memory_mb = 900
    tg.ephemeral_disk.size_mb = 0
    stack = DenseStack(cm)
    g = stack.compile_group(job, tg)
    N = cm.n_rows
    zero = np.zeros(N, np.int32)
    demand = g.demand.astype(np.float32)

    # serial chained reference with the raw kernel
    used = cm.used.astype(np.float32).copy()
    serial = []
    for _ in range(4):
        packed = place_bulk_jit(
            np.ascontiguousarray(cm.capacity),
            np.ascontiguousarray(used), g.feasible,
            g.affinity.astype(np.float32), bool(g.has_affinity),
            np.int32(12), np.zeros(N, bool), zero, demand, np.int32(12))
        assign, placed, *_ , used_f = unpack_bulk(jax.device_get(packed))
        serial.append((assign.copy(), placed))
        used = np.array(used_f)

    engine = PlacementEngine()
    try:
        results = [None] * 4
        barrier = threading.Barrier(4)

        def call(i):
            barrier.wait()
            results[i] = engine.place_bulk(
                cm, feasible=g.feasible, affinity=g.affinity,
                has_affinity=g.has_affinity, desired=12,
                penalty=np.zeros(N, bool), coll0=zero, demand=demand,
                count=12)

        threads = [threading.Thread(target=call, args=(i,))
                   for i in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert all(r is not None for r in results)
        # chained: total assignment counts equal the serial totals and
        # respect capacity
        total = np.zeros(N, np.int64)
        for assign, placed, n_eval, n_exh, scores, ticket in results:
            assert placed == 12
            total += assign
            engine.complete(ticket)
        serial_total = sum(a for a, _ in serial)
        np.testing.assert_array_equal(total, serial_total)
        over = cm.used + total[:, None] * demand[None, :]
        assert (over <= cm.capacity + 1e-3).all()
        assert engine.stats["bulk_evals"] >= 4
        assert not engine._tickets     # drained
    finally:
        engine.stop()


def test_engine_bulk_overflow_deltas_not_double_counted():
    """An eval with more deltas than the fixed slot bucket folds them
    into a private basis; the returned used matrix must count each delta
    exactly once (regression: the resolve path re-applied them)."""
    from nomad_tpu.parallel.engine import PlacementEngine, _DELTA_BUCKET

    cm = _world(128, heterogeneous=False)
    N = cm.n_rows
    demand = np.array([100.0, 64.0, 0.0, 0.0], np.float32)
    # one positive delta per row, more than the bucket holds
    n_d = _DELTA_BUCKET + 8
    vec = np.array([50.0, 10.0, 0.0, 0.0], np.float32)
    deltas = [(i, vec) for i in range(n_d)]

    engine = PlacementEngine()
    try:
        assign, placed, n_eval, n_exh, scores, ticket = \
            engine.place_bulk(
                cm, feasible=np.ones(N, bool),
                affinity=np.zeros(N, np.float32), has_affinity=False,
                desired=4, penalty=np.zeros(N, bool),
                coll0=np.zeros(N, np.int32), demand=demand, count=4,
                deltas=deltas)
        assert placed == 4
        # the in-flight overlay must carry the PLACEMENTS only — folded
        # deltas (this eval's private stops) never register there
        overlay = engine._overlays[id(cm)]
        expected = np.outer(assign.astype(np.float32), demand)
        np.testing.assert_allclose(overlay[:, :expected.shape[1]],
                                   expected, rtol=1e-6)
        engine.complete(ticket)
    finally:
        engine.stop()
