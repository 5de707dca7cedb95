"""The whole-dispatch cut (`_cut_csr`) against the per-item loop it
replaced, kept here verbatim as a plain oracle.

`loop_cut_csr` is `_cut_csr` as `ops/resolver.py` had it while the cut ran a
`np.unique`, a gather and three `tolist`s an item (and step 8 of
`_assemble_key_deps` beside it ran the same body). The array cut has to hand
`make` the same four tuples an item -- rows, txn ids, offsets, value_idx,
Python ints in Python tuples -- and touch no other element of `out`.
"""
from __future__ import annotations

import numpy as np
import pytest

from accord_tpu.ops.resolver import _cut_csr, _sort_entries
from accord_tpu.primitives.deps import KeyDeps, RangeDeps
from accord_tpu.primitives.keyspace import Range
from accord_tpu.primitives.timestamp import Domain, TxnId, TxnKind


def loop_cut_csr(e_slot, e_rank, slot_off, by_rank, row_objects, make, out):
    first = np.flatnonzero(np.r_[True, e_slot[1:] != e_slot[:-1]])
    rows = row_objects(e_slot[first])
    bounds = np.searchsorted(e_slot, slot_off)
    row_at = np.searchsorted(first, bounds).tolist()
    bounds = bounds.tolist()
    for i in np.flatnonzero(np.diff(bounds)).tolist():
        a, b = bounds[i], bounds[i + 1]
        ra, rb = row_at[i], row_at[i + 1]
        uniq, inv = np.unique(e_rank[a:b], return_inverse=True)
        out[i] = make(tuple(rows[ra:rb]), tuple(by_rank[uniq].tolist()),
                      tuple((first[ra:rb] - a).tolist()) + (b - a,),
                      tuple(inv.tolist()))


def _four(*fields):
    return fields


def _row_names(slots):
    return [("row", u) for u in slots.tolist()]


def _by_rank(r):
    by_rank = np.empty(r, object)
    by_rank[:] = [("txn", i) for i in range(r)]
    return by_rank


def _plain(x):
    """The answer down to its element types: a numpy integer where the
    loop gave a Python int is a difference (the wire codec packs them)."""
    if isinstance(x, tuple):
        return tuple(_plain(e) for e in x)
    return type(x).__name__, x


def _both(slot_of_pair, rank_of_pair, slot_off, r, row_objects=_row_names,
          make=_four, by_rank=None):
    """Sort the pairs as the decode does, cut them both ways -> (loop's
    out, array's out); every untouched element stays the sentinel."""
    slot_off = np.asarray(slot_off, np.int64)
    by_rank = _by_rank(r) if by_rank is None else by_rank
    e_slot, e_rank = _sort_entries(np.asarray(slot_of_pair, np.int64),
                                   np.asarray(rank_of_pair, np.int64), r)
    want = ["untouched"] * (len(slot_off) - 1)
    got = list(want)
    if e_slot.size:     # the loop's callers never handed it an empty input
        loop_cut_csr(e_slot, e_rank, slot_off, by_rank, row_objects, make,
                     want)
    _cut_csr(e_slot, e_rank, slot_off, by_rank, row_objects, make, got)
    return want, got


def _assert_same(want, got):
    assert len(want) == len(got)
    for i, (w, g) in enumerate(zip(want, got)):
        assert _plain(w) == _plain(g), f"item {i}: loop {w!r} != array {g!r}"


def _random_dispatch(rng, n_items, r):
    """0-4 slots an item; a slot holds 0..min(r, 12) distinct ranks, a
    third of the slots none; ranks are drawn from a pool far smaller than
    the pairs, so items and an item's slots share them."""
    n_slots = rng.integers(0, 5, n_items)
    slot_off = np.concatenate(([0], np.cumsum(n_slots)))
    slots, ranks = [], []
    for s in range(int(slot_off[-1])):
        k = 0 if rng.integers(0, 3) == 0 else int(rng.integers(1, min(r, 12) + 1))
        ranks.append(rng.choice(r, size=k, replace=False))
        slots.append(np.full(k, s))
    if not slots:
        return [], [], slot_off
    order = rng.permutation(sum(len(x) for x in slots))
    return (np.concatenate(slots)[order], np.concatenate(ranks)[order],
            slot_off)


@pytest.mark.parametrize("n_items", [1, 2, 7, 1024])
@pytest.mark.parametrize("seed", [0, 5, 2**31 + 11])
def test_randomized_dispatch(seed, n_items):
    rng = np.random.default_rng(seed)
    r = 6 if n_items < 1024 else 40
    hit = 0
    for _ in range(8 if n_items < 1024 else 1):
        slot, rank, slot_off = _random_dispatch(rng, n_items, r)
        want, got = _both(slot, rank, slot_off, r)
        _assert_same(want, got)
        hit += sum(w != "untouched" for w in want)
    assert hit > 0, "the comparison was vacuous"


def test_empty_input():
    want, got = _both([], [], [0, 2, 2, 5], 9)
    assert got == want == ["untouched"] * 3
    # and no slot at all
    out = []
    _cut_csr(np.zeros(0, np.int64), np.zeros(0, np.int64),
             np.zeros(1, np.int64), _by_rank(3), _row_names, _four, out)
    assert out == []


def test_trailing_and_leading_empty_items():
    # items 0 and 1 own no slot, item 3's slots hold no pair, items 4-6 own
    # no slot: slot_off repeats its last value
    want, got = _both([0, 0, 1, 1, 1], [4, 2, 2, 0, 1],
                      [0, 0, 0, 2, 5, 5, 5, 5], 5)
    _assert_same(want, got)
    assert [w != "untouched" for w in want] == \
        [False, False, True, False, False, False, False]
    assert got[2] == ((("row", 0), ("row", 1)),
                      (("txn", 0), ("txn", 1), ("txn", 2), ("txn", 4)),
                      (0, 2, 5), (2, 3, 0, 1, 2))


def test_one_rank_shared_by_every_slot_of_an_item():
    # item 0: four slots, all holding rank 3 and nothing else; item 1: the
    # same rank beside others
    want, got = _both([0, 1, 2, 3, 4, 4, 5], [3, 3, 3, 3, 3, 1, 3],
                      [0, 4, 6], 7)
    _assert_same(want, got)
    assert got[0] == (tuple(("row", s) for s in range(4)), (("txn", 3),),
                      (0, 1, 2, 3, 4), (0, 0, 0, 0))
    assert got[1][1] == (("txn", 1), ("txn", 3))
    assert got[1][3] == (0, 1, 1)


def test_ranks_shared_across_items():
    # every item holds the same three ranks: the dictionaries must not
    # merge across the item boundary, nor `inv` count from another item's
    n, r = 50, 11
    slot = np.repeat(np.arange(2 * n), 3)
    rank = np.tile([10, 0, 5], 2 * n)
    want, got = _both(slot, rank, np.arange(n + 1) * 2, r)
    _assert_same(want, got)
    for g in got:
        assert g[1] == (("txn", 0), ("txn", 5), ("txn", 10))
        assert g[2] == (0, 3, 6) and g[3] == (0, 1, 2, 0, 1, 2)


def test_slots_present_in_the_middle_only():
    # an item whose first and last slots hold no pair: the rows are the
    # present slots, the offsets count from the item's first pair
    want, got = _both([1, 1, 2, 6], [2, 0, 1, 0], [0, 4, 8], 3)
    _assert_same(want, got)
    assert got[0][0] == (("row", 1), ("row", 2)) and got[0][2] == (0, 2, 3)
    assert got[1][0] == (("row", 6),) and got[1][2] == (0, 1)


def test_duplicate_pairs_are_cut_once():
    # _sort_entries dedupes (a txn in two arenas shares a rank): the cut
    # sees each (slot, rank) once
    want, got = _both([0, 0, 0, 1, 1], [2, 2, 1, 2, 2], [0, 2], 3)
    _assert_same(want, got)
    assert got[0][2] == (0, 2, 3) and got[0][3] == (0, 1, 1)


def test_rows_are_made_once_a_present_slot():
    calls = []

    def rows_of(slots):
        calls.append(slots.tolist())
        return _row_names(slots)

    _both([5, 5, 0, 3], [1, 0, 1, 1], [0, 2, 4, 6], 2, row_objects=rows_of)
    assert calls == [[0, 3, 5]] * 2     # once for the loop, once for the cut


def test_key_deps_and_range_deps_through_one_cut():
    """The two domains' objects from the same cut: KeyDeps over keys,
    RangeDeps over Ranges, real TxnIds in the dictionary; the fields equal
    the loop's and the objects compare equal."""
    rng = np.random.default_rng(7)
    r = 30
    tids = np.empty(r, object)
    tids[:] = [TxnId.create(1, 1000 + i, 1, TxnKind.WRITE,
                            Domain.KEY if i % 2 else Domain.RANGE)
               for i in range(r)]
    slot, rank, slot_off = _random_dispatch(rng, 40, r)
    n_slots = int(slot_off[-1])
    keys = [10 * s for s in range(n_slots)]
    ranges = [Range(10 * s, 10 * s + 7) for s in range(n_slots)]
    for make, rows, field in (
            (KeyDeps, lambda sl: [keys[u] for u in sl.tolist()], "keys"),
            (RangeDeps, lambda sl: [ranges[u] for u in sl.tolist()],
             "ranges")):
        want, got = _both(slot, rank, slot_off, r, row_objects=rows,
                          make=make, by_rank=tids)
        seen = 0
        for w, g in zip(want, got):
            if w == "untouched":
                assert g == "untouched"
                continue
            assert type(g) is make and g == w
            for f in (field, "txn_ids", "offsets", "value_idx"):
                assert _plain(getattr(g, f)) == _plain(getattr(w, f)), f
            assert g.offsets[-1] == len(g.value_idx)
            seen += 1
        assert seen > 10


def test_numpy_calls_do_not_grow_with_the_items(monkeypatch):
    """The cut's contract: the count of numpy calls a call makes does not
    depend on the number of items. Counted on the module's `np` functions
    (the array methods -- one `tolist` a lane -- are in the code to read)."""
    import accord_tpu.ops.resolver as res

    def count_calls(n_items):
        rng = np.random.default_rng(n_items)
        slot, rank, slot_off = _random_dispatch(rng, n_items, 9)
        e_slot, e_rank = _sort_entries(np.asarray(slot, np.int64),
                                       np.asarray(rank, np.int64), 9)
        counts = {"n": 0}

        class Counting:
            def __getattr__(self, name):
                attr = getattr(np, name)
                if not callable(attr) or isinstance(attr, type):
                    return attr

                def counted(*a, **k):
                    counts["n"] += 1
                    return attr(*a, **k)
                return counted

        with monkeypatch.context() as m:
            m.setattr(res, "np", Counting())
            out = [None] * n_items
            _cut_csr(e_slot, e_rank, np.asarray(slot_off, np.int64),
                     _by_rank(9), _row_names, _four, out)
        return counts["n"], sum(o is not None for o in out)

    (few, cut_few), (many, cut_many) = count_calls(3), count_calls(300)
    assert cut_few >= 1 and cut_many > 100
    assert few == many and few > 0
