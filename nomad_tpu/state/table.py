"""Versioned tables: what the state store keeps its rows in, and what a
snapshot shares with it.

Reference: go-memdb's immutable radix trees (nomad/state/state_store.go
Snapshot:190), which give the reference O(1) snapshots.  A `Table` is
the store's own dict, read and iterated as any dict is, with a shadow
that snapshots share: a bucket list of fixed fan-out over small dicts.

- `Table.view()` hands out the bucket list as it stands and bumps the
  table's generation.  It copies nothing and allocates one object.
- A write goes to the dict and to the shadow, where it copies the piece
  it is about to change, and only if a view may still share it (the
  piece is stamped with an older generation): the bucket list once a
  generation, a bucket once a generation, and in an `IndexTable` the
  one id set it adds to or discards from.  A piece the table has copied
  is its own until the next `view()`.
- A piece that a view shares is never written again, so a view reads
  without a lock and answers, whatever is written later, with the
  membership and the object each key had when it was taken.  Old pieces
  go when the last view that holds them goes.

Why the dict stays: it is as old as the store, so the collector meets
it before the rows it holds and leaves them where they lie; rows held
only by buckets, which are always younger than their rows, are moved
behind them at every full collection, and a collector that walks the
heap out of order takes two to three times as long (measured, PR 33).
It also keeps the store's own reads at a dict's speed and order.

Writers are serialized by the store's lock; `view()` is called under it.
"""
from __future__ import annotations

from collections.abc import Mapping
from time import perf_counter
from typing import Dict
from zlib import crc32

from nomad_tpu import tracing

_EMPTY: dict = {}       # every bucket of a new table; never owned, never written

# Buckets a table, one number for all seven.  A write past a snapshot
# copies one bucket (rows / FANOUT entries) and, once a generation, the
# bucket list (FANOUT pointers), and a snapshot let go frees both, so the
# number trades the two.  At 10,000 nodes and 110,000 allocations a
# snapshot and the 3-allocation plan after it cost 459 / 210 / 187 / 250 /
# 673 us at 64 / 256 / 1,024 / 4,096 / 16,384 and a 1,200-allocation plan
# 30-36 ms at every one (CPU, collector off; PERF.md section 6, PR 33).
FANOUT = 1024


def _slot(key) -> int:
    """A key's bucket before masking.  Not `hash()`: str hashes are
    salted per process, and bucket order is a view's iteration order.
    Keys are ids (str) or (namespace, id) pairs, which go by their id."""
    if key.__class__ is tuple:
        key = key[-1]
    return crc32(key.encode())


class TableView(Mapping):
    """What a snapshot holds: a mapping over a bucket list that nothing
    writes any more.  `keys`, `values` and `items` are lists, in bucket
    order."""

    __slots__ = ("_root", "_mask", "_n")

    def __init__(self, root: list, mask: int, n: int):
        self._root = root
        self._mask = mask
        self._n = n

    def __getitem__(self, key):
        return self._root[_slot(key) & self._mask][key]

    def get(self, key, default=None):
        return self._root[_slot(key) & self._mask].get(key, default)

    def rows(self, ids) -> list:
        """The rows of `ids`, each a key this table holds: what an index
        table's id set is read for."""
        root, mask, slot = self._root, self._mask, _slot
        return [root[slot(i) & mask][i] for i in ids]

    def __contains__(self, key) -> bool:
        return key in self._root[_slot(key) & self._mask]

    def __len__(self) -> int:
        return self._n

    def __iter__(self):
        for b in self._root:
            yield from b

    def keys(self) -> list:
        return [k for b in self._root for k in b]

    def values(self) -> list:
        return [v for b in self._root for v in b.values()]

    def items(self) -> list:
        return [kv for b in self._root for kv in b.items()]


class Table(dict):
    """The store's side: a dict whose writes also go to the shadow that
    views share.  Written through `t[k] = v`, `del t[k]`, `pop` and
    `clear` alone.  `stats` is the owner's counter dict: every piece
    copied because a view shared it is counted there."""

    __slots__ = ("_root", "_mask", "_gen", "_root_gen", "_bgen", "_stats")

    def __init__(self, fanout: int, stats: Dict[str, int]):
        if fanout & (fanout - 1):
            raise ValueError(f"fan-out {fanout} is not a power of two")
        super().__init__()
        self._root = [_EMPTY] * fanout
        self._mask = fanout - 1
        self._gen = 0                   # bumped by view(): older pieces are shared
        self._root_gen = 0
        self._bgen = [-1] * fanout      # generation each bucket was copied in
        self._stats = stats

    def view(self) -> TableView:
        self._gen += 1
        return TableView(self._root, self._mask, len(self))

    def clear(self) -> None:
        """Empty the table; views keep what they hold."""
        super().clear()
        fanout = self._mask + 1
        self._root = [_EMPTY] * fanout
        self._root_gen = self._gen
        self._bgen = [-1] * fanout

    def _own(self, key) -> dict:
        """The shadow bucket of `key`, this table's alone to write."""
        i = _slot(key) & self._mask
        gen = self._gen
        if self._bgen[i] != gen:
            if self._root_gen != gen:
                self._root = list(self._root)
                self._root_gen = gen
                self._stats["roots_copied"] += 1
            old = self._root[i]
            self._bgen[i] = gen
            if old:
                t0 = perf_counter()
                self._root[i] = dict(old)
                tracing.record("store.bucket_copy", t0, perf_counter())  # analysis: allow(fsm-determinism, allow-audit) — under the FSM's apply at run time (`t[k] = v` is no call the static cone follows): a duration for the metrics registry, nothing a replica stores
                self._stats["buckets_copied"] += 1
            else:
                self._root[i] = {}
        return self._root[i]

    def __setitem__(self, key, value) -> None:
        super().__setitem__(key, value)
        self._own(key)[key] = value

    def __delitem__(self, key) -> None:
        super().__delitem__(key)
        del self._own(key)[key]

    def pop(self, key, default=None):
        if key not in self:
            return default
        del self._own(key)[key]
        return super().pop(key)

    def _unsupported(self, *args, **kwargs):
        raise TypeError("a Table is written by item, pop and clear alone")

    update = setdefault = popitem = __ior__ = _unsupported


class IndexTable(Table):
    """key -> the set of ids filed under it.  Ids go in and out through
    `add` and `discard`, which copy a set at most once a generation; a
    key whose set empties is dropped.  A reader gets the set itself and
    only reads it."""

    __slots__ = ("_fresh",)

    def __init__(self, fanout: int, stats: Dict[str, int]):
        super().__init__(fanout, stats)
        self._fresh: set = set()        # keys whose sets no view shares

    def view(self) -> TableView:
        self._fresh.clear()
        return super().view()

    def clear(self) -> None:
        self._fresh.clear()
        super().clear()

    def add(self, key, member) -> None:
        ids = self.get(key)
        if ids is None:
            self[key] = {member}
            self._fresh.add(key)
        elif member not in ids:
            self._own_ids(key, ids).add(member)

    def discard(self, key, member) -> None:
        ids = self.get(key)
        if ids is None or member not in ids:
            return
        if len(ids) == 1:
            del self[key]
            self._fresh.discard(key)
        else:
            self._own_ids(key, ids).discard(member)

    def _own_ids(self, key, ids: set) -> set:
        if key not in self._fresh:
            ids = self[key] = set(ids)
            self._fresh.add(key)
            self._stats["sets_copied"] += 1
        return ids
