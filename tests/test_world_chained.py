"""A chained bulk dispatch brings the device's usage up to date by ADDING
the host's row changes to it (PR 52): the adopted carry is the only
place the pending dispatch's placements live, and a row set erased them
on exactly the rows a commit had just touched."""
import numpy as np
import pytest

from nomad_tpu.analysis import recompile
from nomad_tpu.parallel.engine import PlacementEngine
from nomad_tpu.parallel.world import ROW_BUCKETS, DeviceWorld, warm_scatter
from test_wave_mesh import _bulk_req, _group_fields, _results, _world_cm

R = 4
MESH = pytest.mark.parametrize("use_mesh", [False, True],
                               ids=["single_device", "mesh"])


def _mesh(use_mesh):
    if not use_mesh:
        return None
    from nomad_tpu.parallel.sharded import make_serving_mesh
    return make_serving_mesh()


def _device(world):
    import jax
    return np.asarray(jax.device_get(world.device_arrays()[1]))


def _pend(world, placed):
    """What a donated dispatch leaves behind before it resolves: its
    placements in the adopted carry and nowhere else."""
    import jax
    dev = world.loan_basis()
    world.adopt_basis(dev + jax.device_put(placed, dev.sharding))


@MESH
@pytest.mark.parametrize("n_dirty", [2, ROW_BUCKETS[-1] + 100],
                         ids=["one_bucket", "chunked"])
def test_chained_update_keeps_pending_placements(use_mesh, n_dirty):
    N = 64 if n_dirty == 2 else 2 * ROW_BUCKETS[-1]
    rng = np.random.default_rng(n_dirty)
    world = DeviceWorld(_mesh(use_mesh))
    capacity = np.full((N, R), 1e6, np.float32)
    host = rng.integers(0, 4000, (N, R)).astype(np.float32)
    world.update(capacity, host.copy())

    prows = np.array([3, 5], np.int32)
    pcounts = np.array([2, 7], np.int32)
    demand = np.array([500.0, 256.0, 0.0, 3.0], np.float32)
    placed = np.zeros((N, R), np.float32)
    placed[prows] = pcounts[:, None] * demand
    _pend(world, placed)

    # commits and releases land on row 5 (which the pending dispatch
    # placed on), row 9 and, chunked, on thousands more
    dirty = np.array([5, 9]) if n_dirty == 2 else \
        np.concatenate([[5, 9], rng.choice(
            np.arange(10, N), n_dirty - 2, replace=False)])
    host[dirty] += rng.integers(-300, 900, (dirty.size, R))
    world.update(capacity, host.copy(), force_scatter=True)

    np.testing.assert_array_equal(_device(world), host + placed)
    np.testing.assert_array_equal(world.host_basis(), host)
    assert world.stats["chained_rows_added"] == n_dirty
    assert world.stats["rows_scattered"] == n_dirty
    assert world.stats["full_uploads"] == 1

    # the pending dispatch resolves: device and snapshot, bit for bit
    world.apply_rank1_host(prows, pcounts, demand)
    assert np.array_equal(_device(world), world.host_basis())


@MESH
def test_unchained_update_still_sets_rows(use_mesh):
    """Without a pending dispatch the host is the truth: rows are set."""
    N = 64
    world = DeviceWorld(_mesh(use_mesh))
    capacity = np.full((N, R), 1e6, np.float32)
    host = np.arange(N * R, dtype=np.float32).reshape(N, R)
    world.update(capacity, host.copy())
    host[[5, 9]] += 17.0
    world.update(capacity, host.copy())
    np.testing.assert_array_equal(_device(world), host)
    assert world.stats["rows_scattered"] == 2
    assert world.stats["chained_rows_added"] == 0


@pytest.mark.parametrize("shard_min", [8, 1 << 30],
                         ids=["sharded", "single_device"])
def test_chained_part_counts_pending_placements_on_a_committed_row(
        shard_min):
    """Two bulk parts, the second launched while the first is pending; in
    between a commit lands on the row the first placed most on (the
    ticket of two in-flight allocations goes, one of them is committed).
    The second part may take the one slot that freed, not the room the
    first part's placements hold."""
    cm = _world_cm(64, seed=5)
    bg = _group_fields(cm, 40)
    d = np.zeros(cm.used.shape[1], np.float32)
    d[:len(bg.demand)] = bg.demand

    eng = PlacementEngine(shard_min_nodes=shard_min)
    try:
        probe = _bulk_req(cm, bg, 40, "probe")
        eng._dispatch([probe])
        eng._drain_pending()
        (a0, _p, _s, t0), = _results([probe])
        eng.complete(t0)
        row = int(np.argmax(a0))
        assert a0[row] >= 3

        held = eng.register_external(cm, [(row, 2 * d)])
        first, second = (_bulk_req(cm, bg, 40, k) for k in "ab")
        eng._dispatch([first])
        assert eng._pending is not None
        cm.used[row] += d
        eng.complete(held)
        eng._dispatch([second])
        eng._drain_pending()
        (a1, p1, _s1, t1), (a2, p2, _s2, t2) = _results([first, second])

        assert p1 == p2 == 40 and a1[row] >= 1
        assert a2[row] == 1
        basis = eng._basis_for(cm)           # cm.used + both tickets
        assert (basis <= cm.capacity).all(), \
            np.flatnonzero((basis > cm.capacity).any(axis=1))
        assert eng.stats["overlap_chained"] == 1
        assert eng.world_stats()["chained_rows_added"] == 1
        # resolved: the device is the host snapshot, which is the basis
        world = eng._world(cm, cm.n_rows, eng._mesh_for(cm.n_rows))
        np.testing.assert_array_equal(_device(world), world.host_basis())
        np.testing.assert_array_equal(world.host_basis(), basis)
        eng.complete(t1)
        eng.complete(t2)
    finally:
        eng.stop()


@MESH
def test_add_rows_registered_and_warmed(use_mesh):
    """`warm_scatter` leaves no compile for a first chained update, in
    any row bucket, and the recompile budget watches the add-rows pair."""
    mesh = _mesh(use_mesh)
    N = 2 * ROW_BUCKETS[-1]
    warm_scatter((N, R), mesh)
    assert ("sharded.serving_add_rows" if use_mesh else "world.add_rows") \
        in recompile.cache_sizes()

    world = DeviceWorld(mesh)
    capacity = np.full((N, R), 1e6, np.float32)
    host = np.zeros((N, R), np.float32)
    world.update(capacity, host.copy())
    budget = recompile.Budget()
    for n in (1, ROW_BUCKETS[0] + 1, ROW_BUCKETS[1] + 1,
              ROW_BUCKETS[2] + 1):
        host[:n] += 1.0
        world.update(capacity, host.copy(), force_scatter=True)
    assert budget.violations() == []
    np.testing.assert_array_equal(_device(world), host)
