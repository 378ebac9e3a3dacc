"""Multi-chip sharded placement: parity with the single-chip engine on the
8-device virtual CPU mesh."""
import numpy as np
import pytest

from nomad_tpu import mock
from nomad_tpu.encode import ClusterMatrix
from nomad_tpu.parallel import make_mesh, place_eval_batch_sharded, stack_inputs
from nomad_tpu.parallel.engine import get_engine
from nomad_tpu.scheduler.stack import DenseStack


def build_inputs(n_nodes=16, count=6, seed=0):
    cm = ClusterMatrix()
    rng = np.random.default_rng(seed)
    for i in range(n_nodes):
        n = mock.node()
        n.attributes["rack"] = f"r{i % 4}"
        cm.upsert_node(n)
    j = mock.job()
    j.task_groups[0].count = count
    st = DenseStack(cm)
    groups = [st.compile_group(j, tg) for tg in j.task_groups]
    inp = st.build_inputs(j, groups, [0] * count, {})
    return st, inp, count


def test_sharded_matches_single_chip():
    from nomad_tpu.ops.place import place_eval
    st, inp, count = build_inputs()
    single = place_eval(inp, st.spread_algorithm)

    mesh = make_mesh(n_wave_shards=2, n_node_shards=4)
    batch = stack_inputs([inp, inp])
    node, score, fit_s, n_eval, n_exh, top_i, top_s, used = \
        place_eval_batch_sharded(mesh, batch)

    for b in range(2):
        assert np.array_equal(np.asarray(node[b]), single.node), \
            (np.asarray(node[b]), single.node)
        np.testing.assert_allclose(np.asarray(score[b])[:count],
                                   single.score[:count], rtol=1e-5)
        assert np.array_equal(np.asarray(n_eval[b]), single.nodes_evaluated)
    # final usage matrices agree
    np.testing.assert_allclose(np.asarray(used[0]), single.used, rtol=1e-5)


def test_sharded_with_spread_and_affinity():
    from nomad_tpu.structs.job import Affinity, Operand, Spread
    cm = ClusterMatrix()
    for i in range(8):
        n = mock.node()
        n.attributes["rack"] = f"r{i % 2}"
        cm.upsert_node(n)
    j = mock.job()
    j.task_groups[0].count = 4
    j.task_groups[0].spreads = [Spread("${attr.rack}", 100, ())]
    j.affinities.append(Affinity("${attr.rack}", "r0", Operand.EQ, weight=20))
    st = DenseStack(cm)
    groups = [st.compile_group(j, tg) for tg in j.task_groups]
    inp = st.build_inputs(j, groups, [0] * 4, {})
    single, ticket = get_engine().place(
        cm, inp, spread_algorithm=st.spread_algorithm)
    get_engine().complete(ticket)

    mesh = make_mesh(n_wave_shards=1, n_node_shards=8)
    batch = stack_inputs([inp])
    node, score, *_ = place_eval_batch_sharded(mesh, batch)
    # the engine pads the slot axis to a canonical bucket; compare the
    # real slots
    assert np.array_equal(np.asarray(node[0]), single.node[:4])
    np.testing.assert_allclose(np.asarray(score[0])[:4], single.score[:4],
                               rtol=1e-5)


def _mixed_world(n_nodes, racks=8, seed=3):
    rng = np.random.default_rng(seed)
    cm = ClusterMatrix(initial_rows=n_nodes)
    for i in range(n_nodes):
        n = mock.node()
        n.attributes["rack"] = f"r{i % racks}"
        n.node_resources.cpu.cpu_shares = int(rng.integers(3000, 8000))
        cm.upsert_node(n)
    return cm


def _mixed_job(count):
    from nomad_tpu.structs.job import Affinity, Operand, Spread
    j = mock.job()
    tg = j.task_groups[0]
    tg.count = count
    tg.spreads = [Spread("${attr.rack}", 60, ())]
    j.affinities.append(Affinity("${attr.rack}", "r2", Operand.EQ,
                                 weight=40))
    return j


def test_sharded_scale_10k_nodes_mixed():
    """VERDICT r3 item 5: a 10K-node world with spreads + affinities
    active, a few hundred slots, through both the single-chip kernel and
    the 8-device sharded kernel — identical selections, scores, and
    spread-count carries."""
    from nomad_tpu.ops.place import place_eval

    cm = _mixed_world(10_000)
    assert cm.n_rows == 16384            # divides the 8-device mesh
    count = 200
    j = _mixed_job(count)
    st = DenseStack(cm)
    groups = [st.compile_group(j, tg) for tg in j.task_groups]
    inp = st.build_inputs(j, groups, [0] * count, {})

    single = place_eval(inp, st.spread_algorithm)

    mesh = make_mesh(n_wave_shards=1, n_node_shards=8)
    batch = stack_inputs([inp])
    node, score, fit_s, n_eval, n_exh, top_i, top_s, used = \
        place_eval_batch_sharded(mesh, batch, st.spread_algorithm)

    np.testing.assert_array_equal(np.asarray(node[0]), single.node)
    np.testing.assert_allclose(np.asarray(score[0])[:count],
                               single.score[:count], rtol=1e-5)
    np.testing.assert_allclose(np.asarray(fit_s[0])[:count],
                               single.fit_score[:count], rtol=1e-5)
    assert np.array_equal(np.asarray(n_eval[0]), single.nodes_evaluated)
    # spread-carry consistency: identical placements imply identical
    # per-rack distribution; verify against the selections directly
    racks = np.array([int(cm.attrs.columns["attr.rack"].values[r][1:])
                      for r in np.asarray(node[0])[:count]])
    single_racks = np.array(
        [int(cm.attrs.columns["attr.rack"].values[r][1:])
         for r in single.node[:count]])
    np.testing.assert_array_equal(racks, single_racks)
    # usage matrices agree (sharded returns the node-sharded final used)
    np.testing.assert_allclose(np.asarray(used[0]), np.asarray(single.used),
                               rtol=1e-5)


def test_engine_sharded_serving_parity():
    """The engine's multi-chip serving route (chained scan + bulk over
    the ('nodes',) mesh) must produce placements identical to the
    single-device engine paths."""
    from concurrent.futures import Future

    from nomad_tpu.ops.place import place_eval
    from nomad_tpu.parallel.engine import PlacementEngine, _Request

    cm = _mixed_world(1024)
    count = 12
    j = _mixed_job(count)
    st = DenseStack(cm)
    groups = [st.compile_group(j, tg) for tg in j.task_groups]
    inp = st.build_inputs(j, groups, [0] * count, {})
    single = place_eval(inp, st.spread_algorithm)

    eng = PlacementEngine(shard_min_nodes=8)
    try:
        assert eng._mesh_for(cm.n_rows) is not None
        reqs = [_Request(cm=cm, inputs=inp, deltas=[],
                         spread_algorithm=False, future=Future())
                for _ in range(2)]
        eng._dispatch(reqs)
        res, ticket = reqs[0].future.result(timeout=120)
        np.testing.assert_array_equal(np.asarray(res.node[:count]),
                                      single.node[:count])
        np.testing.assert_allclose(np.asarray(res.score[:count]),
                                   single.score[:count], rtol=1e-5)
        eng.complete(ticket)
        _, ticket1 = reqs[1].future.result(timeout=120)
        eng.complete(ticket1)   # drain the overlay before the bulk check
        assert eng.stats.get("sharded_evals", 0) >= 2

        # bulk wavefront through the mesh vs the single-device kernel
        import jax

        from nomad_tpu.ops.place import place_bulk_jit, unpack_bulk
        N = cm.n_rows
        bj = mock.batch_job()
        btg = bj.task_groups[0]
        btg.count = 30
        btg.ephemeral_disk.size_mb = 0
        bst = DenseStack(cm)
        bg = bst.compile_group(bj, btg)
        zero = np.zeros(N, np.int32)
        packed = place_bulk_jit(
            np.ascontiguousarray(cm.capacity),
            np.ascontiguousarray(cm.used.astype(np.float32)),
            bg.feasible, bg.affinity.astype(np.float32),
            bool(bg.has_affinity), np.int32(30), np.zeros(N, bool),
            zero, bg.demand.astype(np.float32), np.int32(30))
        ref_assign, ref_placed, *_ = unpack_bulk(jax.device_get(packed))

        assign, placed, n_eval, n_exh, scores, tkt = \
            eng.place_bulk(cm, feasible=bg.feasible,
                           affinity=bg.affinity, has_affinity=bg.has_affinity,
                           desired=30, penalty=np.zeros(N, bool),
                           coll0=zero, demand=bg.demand, count=30)
        np.testing.assert_array_equal(assign, ref_assign)
        assert placed == ref_placed == 30
        eng.complete(tkt)
    finally:
        eng.stop()


@pytest.mark.parametrize("in_flight", [50, 1])
def test_e2e_spine_sharded_matches_single_device(in_flight):
    """VERDICT r3 item 1 'done' criterion: a 1K-node / 5K-alloc world
    placed through the FULL Server spine on the 8-virtual-device mesh,
    with placements identical (same node rows) to the single-device
    engine.  One scheduler worker keeps eval processing order
    deterministic so the runs are comparable.

    With one job in flight the two runs agree job by job.  With all 50
    registered at once (deferred commits, chained waves) they agree on
    the cluster and not on the job: a dispatch that reads its basis
    between a plan's store write and the release of that plan's overlay
    tickets (`PlanApplier._post_commit`) counts the plan twice, and when
    that fills a block of nodes on paper the job goes to the next block
    and its successor takes the hole.  Which job meets that window is
    timing, on either engine (ROADMAP S19), so that case is held to what
    does not depend on it: every job whole, no node over its capacity,
    and the same number of allocations on every node."""
    import contextlib

    from nomad_tpu import knobs
    from nomad_tpu.core.server import Server, ServerConfig
    from nomad_tpu.parallel import engine as engine_mod

    @contextlib.contextmanager
    def one_device_engine():
        # the process-wide engine reads its mesh floor when it is made:
        # the single-device side gets an engine of its own, made under a
        # floor no cluster reaches
        with knobs.override("NOMAD_TPU_SHARD_MIN", 1 << 30):
            prev, engine_mod._engine = engine_mod._engine, None
            try:
                yield
            finally:
                engine_mod.get_engine().stop()
                engine_mod._engine = prev

    def run_spine(shard: bool):
        with contextlib.nullcontext() if shard else one_device_engine():
            s = Server(ServerConfig(num_schedulers=1,
                                    heartbeat_ttl=3600.0,
                                    gc_interval=3600.0))
            s.start()
            try:
                for i in range(1000):
                    n = mock.node()
                    n.attributes["rack"] = f"r{i % 8}"
                    s.register_node(n)
                assert s.store.matrix.n_rows == 1024
                import time
                deadline = time.time() + 240

                def wait_placed(js):
                    while time.time() < deadline:
                        placed = sum(
                            len(s.store.allocs_by_job("default", j.id))
                            for j in js)
                        if placed >= 100 * len(js):
                            break
                        time.sleep(0.002 if len(js) == 1 else 0.05)
                    return placed

                jobs = []
                for k in range(50):
                    j = mock.batch_job(id=f"spine-{k}")
                    j.task_groups[0].count = 100
                    jobs.append(j)
                    s.register_job(j)
                    if in_flight == 1:
                        wait_placed([j])
                want = 5000
                placed = wait_placed(jobs)
                rows = {}
                cm = s.store.matrix
                for j in jobs:
                    counts = {}
                    for a in s.store.allocs_by_job("default", j.id):
                        row = cm.row_of[a.node_id]
                        counts[row] = counts.get(row, 0) + 1
                    rows[j.id] = counts
                assert placed == want, placed
                assert (engine_mod.get_engine()._mesh_for(1024)
                        is not None) == shard
                assert (cm.used <= cm.capacity).all()
                return rows
            finally:
                s.stop()

    sharded = run_spine(shard=True)
    single = run_spine(shard=False)
    if in_flight == 1:
        assert sharded == single
        return

    def per_node(rows):
        total = {}
        for counts in rows.values():
            for row, c in counts.items():
                total[row] = total.get(row, 0) + c
        return total

    for rows in (sharded, single):
        assert all(sum(c.values()) == 100 for c in rows.values())
    assert per_node(sharded) == per_node(single)


def test_engine_sharded_c2m_scale_mixed_batch():
    """VERDICT r4 item 5: the engine's sharded serving paths at C2M
    node scale — N=10,240 (16,384 padded rows) sharded 8 ways — with a
    MIXED eval batch (small-count bulk, large-count bulk, spread scan),
    asserting placement parity with the single-device engine."""
    from concurrent.futures import Future

    from nomad_tpu.parallel.engine import PlacementEngine, _Request

    cm = _mixed_world(10_240)
    N = cm.n_rows
    assert N % 8 == 0

    # bulk groups: one small-count (sparse-output class), one large
    bj = mock.batch_job()
    btg = bj.task_groups[0]
    btg.count = 10
    btg.ephemeral_disk.size_mb = 0
    bst = DenseStack(cm)
    bg_small = bst.compile_group(bj, btg)
    bj2 = mock.batch_job()
    btg2 = bj2.task_groups[0]
    btg2.count = 200
    btg2.ephemeral_disk.size_mb = 0
    bg_large = DenseStack(cm).compile_group(bj2, btg2)

    # scan eval: spreads active
    count = 40
    sj = _mixed_job(count)
    st = DenseStack(cm)
    groups = [st.compile_group(sj, tg) for tg in sj.task_groups]
    scan_inp = st.build_inputs(sj, groups, [0] * count, {})

    zero = np.zeros(N, np.int32)

    def run(shard_min):
        eng = PlacementEngine(shard_min_nodes=shard_min)
        out = {}
        try:
            a1, p1, *_rest1, t1 = eng.place_bulk(
                cm, feasible=bg_small.feasible,
                affinity=bg_small.affinity,
                has_affinity=bg_small.has_affinity, desired=10,
                penalty=np.zeros(N, bool), coll0=zero,
                demand=bg_small.demand, count=10)
            eng.complete(t1)
            a2, p2, *_rest2, t2 = eng.place_bulk(
                cm, feasible=bg_large.feasible,
                affinity=bg_large.affinity,
                has_affinity=bg_large.has_affinity, desired=200,
                penalty=np.zeros(N, bool), coll0=zero,
                demand=bg_large.demand, count=200)
            eng.complete(t2)
            req = _Request(cm=cm, inputs=scan_inp, deltas=[],
                           spread_algorithm=False, future=Future())
            eng._dispatch([req])
            res, t3 = req.future.result(timeout=300)
            eng.complete(t3)
            out = {"a1": a1, "p1": p1, "a2": a2, "p2": p2,
                   "scan_nodes": np.asarray(res.node[:count]).copy(),
                   "scan_scores": np.asarray(res.score[:count]).copy(),
                   "sharded": eng.stats.get("sharded_evals", 0)}
        finally:
            eng.stop()
        return out

    sharded = run(shard_min=8)         # mesh active at this N
    single = run(shard_min=1 << 30)    # mesh disabled

    assert sharded["sharded"] >= 1
    assert single["sharded"] == 0
    assert sharded["p1"] == single["p1"] == 10
    assert sharded["p2"] == single["p2"] == 200
    np.testing.assert_array_equal(sharded["a1"], single["a1"])
    np.testing.assert_array_equal(sharded["a2"], single["a2"])
    np.testing.assert_array_equal(sharded["scan_nodes"],
                                  single["scan_nodes"])
    np.testing.assert_allclose(sharded["scan_scores"],
                               single["scan_scores"], rtol=1e-5)


@pytest.mark.parametrize("use_mesh", [False, True])
def test_device_world_parity_randomized(use_mesh):
    """Device-resident incremental state == from-scratch rebuild, bitwise,
    after a randomized interleaving of plan commits (rank-1 scatters),
    node joins/drains (row mutations), preemptions (negative counts), and
    a cluster epoch change (row-count growth -> full re-upload)."""
    import jax

    from nomad_tpu.parallel.sharded import make_serving_mesh
    from nomad_tpu.parallel.world import DeviceWorld

    rng = np.random.default_rng(7)
    N, R = 64, 4
    mesh = make_serving_mesh() if use_mesh else None
    world = DeviceWorld(mesh=mesh)

    capacity = rng.uniform(100, 1000, (N, R)).astype(np.float32)
    truth = np.zeros((N, R), np.float32)        # from-scratch reference
    world.update(capacity, truth.copy())

    def check():
        cap_dev, basis_dev = world.device_arrays()
        np.testing.assert_array_equal(
            np.asarray(jax.device_get(basis_dev)), truth)
        np.testing.assert_array_equal(
            np.asarray(jax.device_get(cap_dev)), capacity)
        np.testing.assert_array_equal(world.host_basis(), truth)

    for step in range(60):
        op = rng.integers(0, 4)
        if op == 0:                              # plan commit
            k = int(rng.integers(1, 9))
            rows = rng.choice(N, k, replace=False).astype(np.int32)
            counts = rng.integers(1, 4, k).astype(np.int32)
            demand = rng.uniform(0, 50, R).astype(np.float32)
            world.apply_rank1(rows, counts, demand)
            truth[rows] += counts[:, None].astype(np.float32) * demand
        elif op == 1:                            # preemption: reverse
            k = int(rng.integers(1, 5))
            rows = rng.choice(N, k, replace=False).astype(np.int32)
            demand = rng.uniform(0, 20, R).astype(np.float32)
            world.apply_rank1(rows, np.full(k, -1, np.int32), demand)
            truth[rows] -= demand
        elif op == 2:                            # node join/drain churn
            k = int(rng.integers(1, 6))
            rows = rng.choice(N, k, replace=False)
            capacity[rows] = rng.uniform(100, 1000, (k, R))
            truth[rows] = 0.0                    # drained node resets
            world.update(capacity, truth.copy())
        else:                                    # clean dispatch
            world.update(capacity, truth.copy())
        check()

    # epoch change: the padded row axis grows -> one full re-upload
    N2 = N * 2
    cap2 = rng.uniform(100, 1000, (N2, R)).astype(np.float32)
    cap2[:N] = capacity
    truth2 = np.zeros((N2, R), np.float32)
    truth2[:N] = truth
    if use_mesh:
        capacity, truth = cap2, truth2
        N = N2
    else:                                        # odd N fine unsharded
        capacity = cap2[: N2 - 3].copy()
        truth = truth2[: N2 - 3].copy()
        N = N2 - 3
    world.update(capacity, truth.copy())
    rows = rng.choice(N, 5, replace=False).astype(np.int32)
    demand = rng.uniform(0, 50, R).astype(np.float32)
    world.apply_rank1(rows, np.ones(5, np.int32), demand)
    truth[rows] += demand
    check()
    assert world.stats["full_uploads"] >= 2
    assert world.stats["rank1_applies"] >= 1


def test_mesh_key_survives_mesh_recreation():
    """`mesh_key` identifies re-created meshes as the same serving mesh
    (the `id(mesh)` keying bug: a new Mesh object could reuse a dead
    mesh's id and resurrect stale shardings)."""
    from nomad_tpu.parallel.engine import PlacementEngine
    from nomad_tpu.parallel.sharded import make_serving_mesh
    from nomad_tpu.parallel.world import mesh_key

    import jax

    m1 = make_serving_mesh()
    m2 = make_serving_mesh()
    assert mesh_key(m1) == mesh_key(m2)
    assert mesh_key(None) is None
    # the key DISCRIMINATES meshes over different device sets
    half = make_serving_mesh(jax.devices()[: len(jax.devices()) // 2])
    assert mesh_key(half) != mesh_key(m1)

    eng = PlacementEngine()
    try:
        arr = np.arange(16, dtype=np.float32).reshape(8, 2)
        from jax.sharding import NamedSharding, PartitionSpec as P
        a1 = eng._cache.sharded("t", m1, arr,
                                NamedSharding(m1, P("node_shard", None)))
        a2 = eng._cache.sharded("t", m2, arr,
                                NamedSharding(m2, P("node_shard", None)))
        assert a1 is a2                          # same content-address
    finally:
        eng.stop()
