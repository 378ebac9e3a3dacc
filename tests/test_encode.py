"""ClusterMatrix / AttrTable incremental-mirror tests."""
import copy

import numpy as np
import pytest

from nomad_tpu import mock
from nomad_tpu.encode import ClusterMatrix, RES_CPU, RES_MEM, pad_to_bucket
from nomad_tpu.encode.attrs import AttrTable, hash_code


def test_pad_to_bucket():
    assert pad_to_bucket(1) == 8
    assert pad_to_bucket(8) == 8
    assert pad_to_bucket(9) == 16
    assert pad_to_bucket(1000) == 1024


def test_upsert_node_and_grow():
    cm = ClusterMatrix()
    nodes = [mock.node() for _ in range(20)]  # forces growth past 8 and 16
    rows = [cm.upsert_node(n) for n in nodes]
    assert cm.n_rows == 32
    assert len(set(rows)) == 20
    r0 = cm.row_of[nodes[0].id]
    assert cm.capacity[r0, RES_CPU] == 4000
    assert cm.ready[r0]
    assert cm.attrs.column("node.datacenter").values[r0] == "dc1"


def test_alloc_usage_tracking():
    cm = ClusterMatrix()
    n = mock.node()
    cm.upsert_node(n)
    j = mock.job()
    a = mock.alloc_for(j, n.id)
    cm.upsert_alloc(a)
    r = cm.row_of[n.id]
    assert cm.used[r, RES_CPU] == 500
    assert cm.used[r, RES_MEM] == 256
    # terminal update removes usage
    a.client_status = "failed"
    cm.upsert_alloc(a)
    assert cm.used[r, RES_CPU] == 0


def test_node_removal_recycles_row():
    cm = ClusterMatrix()
    n1, n2 = mock.node(), mock.node()
    r1 = cm.upsert_node(n1)
    cm.remove_node(n1.id)
    assert not cm.ready[r1]
    r2 = cm.upsert_node(n2)
    assert r2 == r1  # recycled


def test_port_accounting():
    cm = ClusterMatrix()
    n = mock.node()
    n.reserved_resources.reserved_ports = [22, 80]
    cm.upsert_node(n)
    free = cm.static_ports_free([22])
    r = cm.row_of[n.id]
    assert not free[r]
    assert cm.static_ports_free([8080])[r]
    # dynamic port count excludes claims inside the dynamic range
    base_free = cm.free_dynamic_ports()[r]
    assert base_free == 12001
    j = mock.job()
    a = mock.alloc_for(j, n.id)
    from nomad_tpu.structs.resources import NetworkPort, NetworkResource
    a.allocated_resources.shared_ports = [NetworkPort(label="http", value=20005)]
    cm.upsert_alloc(a)
    assert cm.free_dynamic_ports()[r] == 12000
    assert not cm.static_ports_free([20005])[r]


# dynamic ranges a node registers with: the default, the narrowed one of
# `ports-10k` (hi on a word's last bit), one with neither end on a word
# boundary and one inside a single word
_RANGES = [(20000, 32000), (20000, 20031), (20005, 20100), (30000, 30010)]
# ports of every range's edges, a step outside them, the word boundaries
# near them, and some far from any range
_PORTS = sorted({p + d for lo, hi in _RANGES for p in (lo, hi)
                 for d in (-32, -1, 0, 1, 31, 32)}
                | {22, 80, 8080, 65535})


def _ported_node(rng, lo_hi=None):
    n = mock.node()
    lo, hi = lo_hi or _RANGES[rng.integers(len(_RANGES))]
    n.node_resources.min_dynamic_port = lo
    n.node_resources.max_dynamic_port = hi
    n.reserved_resources.reserved_ports = [
        int(p) for p in rng.choice(_PORTS, rng.integers(0, 4), replace=False)]
    return n


def _ported_alloc(node_id, ports):
    from nomad_tpu.structs.resources import NetworkPort
    a = mock.alloc_for(mock.job(), node_id)
    a.allocated_resources.shared_ports = [
        NetworkPort(label=f"p{i}", value=int(p)) for i, p in enumerate(ports)]
    return a


def _free_by_unpacked_bits(cm, row):
    bits = np.unpackbits(cm.port_words[row].view(np.uint8),
                         bitorder="little")
    lo, hi = int(cm.dyn_port_lo[row]), int(cm.dyn_port_hi[row])
    return (hi - lo + 1) - int(bits[lo:hi + 1].sum())


def _assert_column_is_recount(cm, what):
    got, want = cm.free_dynamic_ports(), cm._recount_free_dynamic_ports()
    assert got.dtype == np.int32 and got.shape == (cm.n_rows,), what
    assert np.array_equal(got, want), (what, np.flatnonzero(got != want))


@pytest.mark.parametrize("seed", range(10))
def test_free_dynamic_ports_column_follows_every_bit(seed):
    """The kept column against the recount from `port_words`, after every
    step of the named cases and then of a random walk over them."""
    rng = np.random.default_rng(seed)
    cm = ClusterMatrix()
    nodes, absent, allocs = {}, {}, {}

    def pick(pool):
        return pool[sorted(pool)[rng.integers(len(pool))]]

    def some_ports(node=None):
        ports = [int(p) for p in
                 rng.choice(_PORTS, rng.integers(1, 5), replace=False)]
        if node is not None and rng.random() < 0.5:
            # one another allocation of the node holds, or one it reserves
            held = [p for _v, ps, *_ in
                    cm._node_allocs.get(node.id, {}).values() for p in ps]
            held += node.reserved_resources.reserved_ports
            if held:
                ports.append(int(held[rng.integers(len(held))]))
        return ports

    def add_node(lo_hi=None):
        n = _ported_node(rng, lo_hi)
        cm.upsert_node(n)
        nodes[n.id] = n

    def reregister():
        n = copy.deepcopy(pick(nodes))
        other = _ported_node(rng)
        n.node_resources = other.node_resources
        n.reserved_resources = other.reserved_resources
        cm.upsert_node(n)
        nodes[n.id] = n

    def remove_node():
        n = pick(nodes)
        cm.remove_node(n.id)
        del nodes[n.id]
        absent[n.id] = n           # its allocations stay tracked

    def node_appears():
        n = pick(absent)
        cm.upsert_node(n)
        nodes[n.id] = absent.pop(n.id)

    def add_alloc(node=None, ports=None):
        n = node or pick(nodes)
        a = _ported_alloc(n.id, ports or some_ports(n))
        cm.upsert_alloc(a)
        allocs[a.id] = a

    def alloc_before_node():
        n = _ported_node(rng)
        absent[n.id] = n
        add_alloc(n)

    def update_alloc():
        a = copy.deepcopy(pick(allocs))
        if rng.random() < 0.3 and nodes:
            a.node_id = pick(nodes).id
        a.allocated_resources = _ported_alloc(
            a.node_id, some_ports(nodes.get(a.node_id))).allocated_resources
        cm.upsert_alloc(a)
        allocs[a.id] = a

    def terminal():
        a = copy.deepcopy(pick(allocs))
        a.client_status = "failed"
        cm.upsert_alloc(a)
        del allocs[a.id]

    def remove_alloc():
        cm.remove_alloc(allocs.pop(pick(allocs).id).id)

    # every named case once, in an order that does not depend on the seed
    add_node((20000, 32000))
    _assert_column_is_recount(cm, "default range")
    add_node((20000, 20031))
    narrowed = pick({k: v for k, v in nodes.items()
                     if v.node_resources.max_dynamic_port == 20031})
    row = cm.row_of[narrowed.id]
    base = int(cm.free_dynamic_ports()[row])
    assert base == 32 - sum(20000 <= p <= 20031 for p in
                            narrowed.reserved_resources.reserved_ports)
    add_alloc(narrowed, [19999, 20032, 8080])          # outside: no move
    assert cm.free_dynamic_ports()[row] == base
    free_in = [p for p in (20000, 20031, 20015) if p not in
               narrowed.reserved_resources.reserved_ports]
    add_alloc(narrowed, free_in)                       # lo, hi, inside
    assert cm.free_dynamic_ports()[row] == base - len(free_in)
    add_alloc(narrowed, free_in[:1])                   # held twice: no move
    assert cm.free_dynamic_ports()[row] == base - len(free_in)
    _assert_column_is_recount(cm, "narrowed node's allocations")
    for step in (alloc_before_node, node_appears, reregister, terminal,
                 update_alloc, remove_alloc, remove_node, add_node):
        step()
        _assert_column_is_recount(cm, step.__name__)
    while cm.n_rows < 32:                              # _grow, twice
        add_node()
        _assert_column_is_recount(cm, "grow")

    steps = [(add_node, 2, None), (reregister, 2, nodes),
             (remove_node, 1, nodes), (node_appears, 1, absent),
             (add_alloc, 6, nodes), (alloc_before_node, 1, None),
             (update_alloc, 2, allocs), (terminal, 2, allocs),
             (remove_alloc, 2, allocs)]
    weights = np.array([w for _f, w, _n in steps], dtype=float)
    for i in range(250):
        fn, _w, needs = steps[rng.choice(len(steps), p=weights / weights.sum())]
        if needs is not None and not needs:
            continue
        fn()
        _assert_column_is_recount(cm, (i, fn.__name__))
    # the oracle itself, against a count that shares none of its code
    for row in cm.row_of.values():
        assert cm.free_dynamic_ports()[row] == _free_by_unpacked_bits(cm, row)
    empty = np.ones(cm.n_rows, bool)
    empty[list(cm.row_of.values())] = False
    assert not cm.free_dynamic_ports()[empty].any()


def test_free_dynamic_ports_is_the_callers_array():
    cm = ClusterMatrix()
    n = mock.node()
    r = cm.upsert_node(n)
    got = cm.free_dynamic_ports()
    got //= 2
    np.minimum(got, 1, out=got)
    got[:] = -7
    assert cm.free_dynamic_ports()[r] == 12001
    cm.upsert_alloc(_ported_alloc(n.id, [20005]))
    assert cm.free_dynamic_ports()[r] == 12000
    _assert_column_is_recount(cm, "after a caller wrote into its copy")


def test_a_node_with_an_inverted_dynamic_range_registers_and_offers_none():
    cm = ClusterMatrix()
    rng = np.random.default_rng(0)
    n = _ported_node(rng, (30000, 20000))
    r = cm.upsert_node(n)
    cm.upsert_alloc(_ported_alloc(n.id, [20000, 25000, 30000]))
    assert cm.free_dynamic_ports()[r] == 0
    _assert_column_is_recount(cm, "inverted range")


def test_free_port_column_under_writers_and_a_lockless_reader():
    """The store's writers (serialised by its lock) against a reader that
    takes no lock, as the scheduler's `compile_group` does: more threads
    than cores, a short switch interval, and at the end the column is the
    recount; a lost update would leave it off by the ports it missed."""
    import os
    import sys
    import threading
    from nomad_tpu.state import StateStore

    store = StateStore()
    rng = np.random.default_rng(11)
    nodes = [_ported_node(rng, (20000, 20031)) for _ in range(4)]
    for i, n in enumerate(nodes, 1):
        n.reserved_resources.reserved_ports = [22]
        store.upsert_node(i, n)
    cm = store.matrix
    index = iter(range(100, 10**6))
    stop = threading.Event()
    seen_bad = []

    def writer(k):
        mine = np.random.default_rng(k)
        for _ in range(40):
            n = nodes[mine.integers(len(nodes))]
            a = _ported_alloc(n.id, 20000 + mine.choice(32, 3, replace=False))
            store.upsert_allocs(next(index), [a])
            if mine.random() < 0.5:
                a = copy.deepcopy(a)
                a.client_status = "complete"
                store.upsert_allocs(next(index), [a])

    def reader():
        while not stop.is_set():
            free = cm.free_dynamic_ports()
            if free.min() < 0 or free.max() > 32:
                seen_bad.append(free)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=writer, args=(k,))
                   for k in range(2 * (os.cpu_count() or 4))]
        watch = threading.Thread(target=reader)
        watch.start()
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
        stop.set()
        watch.join(10)
    finally:
        sys.setswitchinterval(old)
    assert not watch.is_alive() and not any(t.is_alive() for t in threads)
    assert not seen_bad
    _assert_column_is_recount(cm, "after the writers")
    assert (cm.free_dynamic_ports()[[cm.row_of[n.id] for n in nodes]]
            < 32).all()


def test_fsm_restore_ends_with_the_free_port_column_exact():
    from nomad_tpu.raft.fsm import MessageType, NomadFSM
    from nomad_tpu.state import StateStore

    rng = np.random.default_rng(5)
    wide, narrow = _ported_node(rng, (20000, 32000)), \
        _ported_node(rng, (20000, 20031))
    wide.reserved_resources.reserved_ports = [80]
    narrow.reserved_resources.reserved_ports = [22, 20001]
    held = [_ported_alloc(wide.id, [20000, 25000, 32000, 32001]),
            _ported_alloc(narrow.id, [20001, 20002, 20031, 20032]),
            _ported_alloc(narrow.id, [20002, 8080])]
    stopped = _ported_alloc(wide.id, [20007])
    stopped.client_status = "complete"
    live = NomadFSM(StateStore())
    for i, (kind, payload) in enumerate([
            (MessageType.NODE_REGISTER, {"node": wide}),
            (MessageType.NODE_REGISTER, {"node": narrow}),
            (MessageType.ALLOC_UPDATE, {"allocs": held + [stopped]})], 1):
        live.apply(i, kind, copy.deepcopy(payload))
    restored = NomadFSM(StateStore())
    restored.restore(live.snapshot())
    for fsm in (live, restored):
        cm = fsm.store.matrix
        _assert_column_is_recount(cm, fsm)
        free = cm.free_dynamic_ports()
        assert free[cm.row_of[wide.id]] == 12001 - 3
        assert free[cm.row_of[narrow.id]] == 32 - 3


# ------------------------------------------------------- the slot table
#
# `alloc_res` / `alloc_prio` / `alloc_live` / `alloc_ids`: the writers of
# `used` mark the rows they change and `candidates` lays those out again;
# `_recount_candidates` rebuilds what every row has to hold from
# `_node_allocs`.

_TABLE_JOBS = {p: mock.job(priority=p) for p in (20, 35, 45, 50, 70)}


def _table_alloc(rng, node_id):
    prio = (None, 20, 35, 45, 50, 70)[rng.integers(6)]
    a = mock.alloc_for(_TABLE_JOBS[prio or 50], node_id)
    if prio is None:
        a.job = None                       # reads as priority 50
    (task,) = a.allocated_resources.tasks.values()
    task.cpu_shares = int(rng.integers(1, 500))
    task.memory_mb = int(rng.integers(1, 900))
    a.allocated_resources.shared_disk_mb = int(rng.integers(0, 300))
    return a


def _assert_table_is_recount(cm, what):
    want = cm._recount_candidates()
    # what a search is handed: the rows' eligible prefixes
    handed = {p: cm.candidates(p) for p in (20, 40, 90)}
    assert not cm._stale_rows, what
    width = cm.alloc_live.shape[1]
    assert cm.alloc_res.shape == (cm.n_rows, width, 4), what
    assert cm.alloc_prio.shape == cm.alloc_live.shape == (cm.n_rows, width)
    assert len(cm.alloc_ids) == cm.n_rows
    assert width == pad_to_bucket(width, minimum=4)
    assert set(want) == set(cm.row_of.values())
    # a row names its live slots and no other, one with no node none
    assert [len(ids) for ids in cm.alloc_ids] == \
        cm.alloc_live.sum(axis=1).tolist(), what
    empty = np.ones(cm.n_rows, bool)
    empty[list(want)] = False
    assert not cm.alloc_live[empty].any(), what
    for row, allocs in want.items():
        slots = np.flatnonzero(cm.alloc_live[row])
        got = {cm.alloc_ids[row][s]: (tuple(cm.alloc_res[row, s].tolist()),
                                      int(cm.alloc_prio[row, s]))
               for s in slots}
        assert len(got) == len(slots) and got == allocs, (what, row)
        # a row fills from slot 0, lowest priority first, equals in the
        # order the node tracks them
        assert slots.tolist() == list(range(len(slots))), (what, row)
        assert list(cm.alloc_ids[row]) == sorted(
            allocs, key=lambda i: allocs[i][1]), (what, row)
    for max_prio, (res, prio, valid, ids) in handed.items():
        may_go = cm.alloc_live & (cm.alloc_prio <= max_prio)
        width = valid.shape[1]
        assert width == pad_to_bucket(int(may_go.sum(axis=1).max(initial=1)),
                                      minimum=4), what
        assert not may_go[:, width:].any(), what
        assert np.array_equal(valid, may_go[:, :width]), what
        assert (valid[:, :-1] >= valid[:, 1:]).all(), "a prefix of each row"
        assert np.array_equal(res, cm.alloc_res[:, :width]), what
        assert np.array_equal(prio, cm.alloc_prio[:, :width]), what
        assert ids == cm.alloc_ids and ids is not cm.alloc_ids, what
        assert {i for a in want.values() for i, (_v, p) in a.items()
                if p <= max_prio} == {ids[r][k] for r, k in
                                      zip(*np.nonzero(valid))}, what
        res[:], prio[:], valid[:], ids[:] = 7, 7, True, [()] * len(ids)
    assert not cm.alloc_live[empty].any(), "a caller wrote into its own"


# a kind of history -> how often each step is taken in its walk
_HISTORIES = {
    "allocations come and go": dict(
        add_node=1, add_alloc=8, remove_alloc=5, update_alloc=2),
    "clients report them terminal": dict(
        add_node=1, add_alloc=8, terminal=6),
    "nodes register again": dict(
        add_node=1, add_alloc=6, remove_alloc=2, reregister=5),
    "nodes leave and come back": dict(
        add_node=2, add_alloc=6, remove_alloc=2, terminal=1, remove_node=2,
        node_appears=2),
    "allocations arrive before their node": dict(
        add_alloc=4, alloc_before_node=4, node_appears=2, remove_alloc=2,
        terminal=1),
    "the rows grow": dict(add_node=8, add_alloc=8, remove_alloc=1),
    "the slots grow": dict(crowd=10, add_alloc=2, remove_alloc=6, add_node=1),
    "everything at once": dict(
        add_node=2, add_alloc=8, remove_alloc=3, update_alloc=2, terminal=3,
        reregister=2, remove_node=1, node_appears=1, alloc_before_node=1,
        crowd=2),
}


@pytest.mark.parametrize("history", sorted(_HISTORIES))
def test_the_slot_table_is_the_recount_after(history):
    """Some thousands of writes of one kind of history, and the table a
    view finds is the one rebuilt from `_node_allocs`: slot set for slot
    set on every row, though only the rows written since the last view
    were laid out again."""
    rng = np.random.default_rng([len(history), 0x51075])
    cm = ClusterMatrix()
    nodes, absent, allocs = {}, {}, {}

    def pick(pool):
        return pool[sorted(pool)[rng.integers(len(pool))]]

    def add_node():
        n = mock.node()
        cm.upsert_node(n)
        nodes[n.id] = n

    def reregister():
        n = copy.deepcopy(pick(nodes))
        n.node_resources.memory_mb += 1
        cm.upsert_node(n)
        nodes[n.id] = n

    def remove_node():
        n = pick(nodes)
        cm.remove_node(n.id)
        absent[n.id] = nodes.pop(n.id)     # its allocations stay tracked

    def node_appears():
        n = pick(absent)
        cm.upsert_node(n)
        nodes[n.id] = absent.pop(n.id)

    def add_alloc(node=None):
        a = _table_alloc(rng, (node or pick(nodes)).id)
        cm.upsert_alloc(a)
        allocs[a.id] = a

    def crowd():
        add_alloc(nodes[sorted(nodes)[0]])  # one node fills its slots

    def alloc_before_node():
        n = mock.node()
        absent[n.id] = n
        add_alloc(n)

    def update_alloc():
        old = pick(allocs)
        a = _table_alloc(rng, pick(nodes).id if rng.random() < 0.3
                         else old.node_id)
        a.id = old.id
        cm.upsert_alloc(a)
        allocs[a.id] = a

    def terminal():
        a = copy.copy(pick(allocs))
        a.client_status = "failed"
        cm.upsert_alloc(a)
        del allocs[a.id]

    def remove_alloc():
        cm.remove_alloc(allocs.pop(pick(allocs).id).id)

    steps = {f.__name__: (f, needs) for f, needs in [
        (add_node, None), (reregister, nodes), (remove_node, nodes),
        (node_appears, absent), (add_alloc, nodes), (crowd, nodes),
        (alloc_before_node, None), (update_alloc, allocs),
        (terminal, allocs), (remove_alloc, allocs)]}
    add_node()
    names = sorted(_HISTORIES[history])
    weights = np.array([_HISTORIES[history][k] for k in names], dtype=float)
    rows0, width0 = cm.n_rows, cm.alloc_live.shape[1]
    taken = 0
    for i in range(3000):
        fn, needs = steps[names[rng.choice(len(names),
                                           p=weights / weights.sum())]]
        if needs is not None and (not needs or (needs is allocs and not nodes)):
            continue
        fn()
        taken += 1
        if i % 100 == 0 or i < 40:
            _assert_table_is_recount(cm, (history, i, fn.__name__))
    _assert_table_is_recount(cm, (history, "the end"))
    assert taken > 2000
    # the oracle's own priorities, against the records
    for a in allocs.values():
        if a.node_id in nodes:
            row = cm.row_of[a.node_id]
            slot = cm.alloc_ids[row].index(a.id)
            assert cm.alloc_prio[row, slot] == (
                a.job.priority if a.job is not None else 50)
    if history == "the rows grow":
        assert cm.n_rows >= 4 * rows0
    if history == "the slots grow":
        assert cm.alloc_live.shape[1] >= 4 * width0


def test_fsm_restore_ends_with_the_slot_table_exact():
    from nomad_tpu.raft.fsm import MessageType, NomadFSM
    from nomad_tpu.state import StateStore

    rng = np.random.default_rng(9)
    nodes = [mock.node() for _ in range(3)]
    held = [_table_alloc(rng, nodes[i % 3].id) for i in range(14)]
    stopped = _table_alloc(rng, nodes[0].id)
    stopped.client_status = "complete"
    live = NomadFSM(StateStore())
    for i, (kind, payload) in enumerate(
            [(MessageType.NODE_REGISTER, {"node": n}) for n in nodes]
            + [(MessageType.ALLOC_UPDATE, {"allocs": held + [stopped]})], 1):
        live.apply(i, kind, copy.deepcopy(payload))
    restored = NomadFSM(StateStore())
    restored.restore(live.snapshot())
    for fsm in (live, restored):
        cm = fsm.store.matrix
        assert cm.lock is fsm.store._lock
        _assert_table_is_recount(cm, fsm)
        assert cm.alloc_live.sum() == len(held)
        assert stopped.id not in {i for ids in cm.alloc_ids for i in ids}


def test_attr_ordinals_lexical():
    t = AttrTable(4)
    col = t.column("attr.ver")
    for i, v in enumerate(["1.10", "1.9", None, "2.0"]):
        col.set(i, v)
    ords = col.ordinals()
    # lexical: "1.10" < "1.9" < "2.0"
    assert ords[0] < ords[1] < ords[3]
    assert ords[2] == -1
    r, exact = col.ordinal_of("1.9")
    assert exact and r == ords[1]


def test_hash_code_stable_nonzero():
    assert hash_code("x") == hash_code("x")
    assert hash_code("x") != hash_code("y")
    assert hash_code("") != 0


def test_dc_mask():
    cm = ClusterMatrix()
    a = mock.node(datacenter="dc1")
    b = mock.node(datacenter="dc2")
    cm.upsert_node(a)
    cm.upsert_node(b)
    m = cm.dc_mask(["dc2"])
    assert m[cm.row_of[b.id]] and not m[cm.row_of[a.id]]
