"""The arena's sync scatters in place (PR 34): `arena_scatter`,
`arena_scatter_keys` and `kid_word_scatter` donate the lanes they rewrite.

The rule under test, and the only one: an array that `device_arrays()` or
`kid_arrays()` has returned is never donated (a staged plan may hold it);
every other array the arena holds is the sync's own and is donated to the
next scatter.

Load-bearing properties:
  1. every lane handed out before a later sync of many chunks is still
     readable afterwards and holds what it held;
  2. a plan staged in one tick, then registrations (and a prune) and a
     sync, then its launch and harvest: the exact answer, from the finalize
     lane and from the key lane, single group and fused;
  3. after a long sync, after compact()'s re-upload and after arena_grow
     each device lane equals one rebuilt from the host shadows;
  4. a sync of n chunks from handed-out lanes donates in all but its first
     step, from fresh lanes in all;
  5. with the sync's calls still queued at most two arrays of the bitmap's
     shape and two of the kid table's are alive;
  6. each program lowers under its old name (the benchmark sums device time
     by it), with its lanes donated;
  7. after warmup() a sync at the warmed tiers compiles nothing.
"""
from __future__ import annotations

import numpy as np
import pytest

from accord_tpu.ops import kernels
from accord_tpu.ops.resolver import BatchDepsResolver, warmup
from accord_tpu.primitives.keyspace import Keys
from accord_tpu.primitives.timestamp import Domain, TxnKind
from tests.test_fused_dispatch import (_attach, _far, _register_keys,
                                       _store_lo, _two_store_node)
from tests.test_local_engine import setup_store

BUCKETS, CAP = 128, 512
# rows a chunk: 64 where rows are narrow; 7 (so the 8-row tier) where each
# row holds 70 keys, because a chunk carries at most 512 key entries
TIERS = {8: dict(n=40, k=70), 64: dict(n=300, k=4)}
DONATING = ("arena_scatter", "arena_scatter_keys", "kid_word_scatter")


def _key_lists(rng, n, k, lo=0, domain=3000):
    return [sorted({lo + int(x) for x in rng.integers(0, domain, k)})
            for _ in range(n)]


def _arena(buckets=BUCKETS, cap=CAP, kid_cap=4096):
    cluster, node, store = setup_store()
    resolver = BatchDepsResolver(num_buckets=buckets, initial_cap=cap,
                                 kid_cap=kid_cap)
    store.deps_resolver = resolver
    return resolver, node, store, resolver._arena(store)


def _counts(resolver):
    return np.array([resolver.arena_upload_calls,
                     resolver.arena_scatters_donated])


def _from_shadows(arena):
    """The five lanes and the kid table as the host shadows give them."""
    bitmap = np.zeros((arena.cap, arena.num_buckets), np.float32)
    for row in range(arena.count):
        bitmap[row, arena.row_mods[row]] = 1.0
    kids = np.zeros((arena.kid_cap, arena.cap // 32), np.uint32)
    for key, words in arena.key_rows.items():
        kids[arena.kid_of[key]] = words
    return (bitmap, arena.ts, arena.exec_ts, arena.kinds, arena.valid), kids


def _assert_device_is_the_shadows(arena):
    lanes, kids = _from_shadows(arena)
    for name, dev, want in zip(("bitmaps", "ts", "exec_ts", "kinds", "valid"),
                               arena.device_arrays(), lanes):
        np.testing.assert_array_equal(np.asarray(dev), want, err_msg=name)
    np.testing.assert_array_equal(np.asarray(arena.kid_arrays()), kids)


# -- 1. what was handed out stays whole -----------------------------------------

@pytest.mark.parametrize("tier", sorted(TIERS))
def test_lanes_handed_out_outlive_a_later_sync_of_many_chunks(tier):
    rng = np.random.default_rng(tier)
    resolver, node, store, arena = _arena()
    first = _key_lists(rng, 60, 3)
    tids = _register_keys(store, node, first)
    held = [*arena.device_arrays(), arena.kid_arrays()]
    # a round's four plans each ask again, and find nothing dirty
    again = [*arena.device_arrays(), arena.kid_arrays()]
    assert all(a is b for a, b in zip(held, again))
    was = [np.asarray(a).copy() for a in held]
    before = _counts(resolver)
    # whole rows in many chunks, key-set deltas, valid flips, kid words
    _register_keys(store, node, _key_lists(rng, **TIERS[tier]))
    for t, ks in list(zip(tids, first))[:20]:
        resolver.on_prune(store, t, ks)
    now = [*arena.device_arrays(), arena.kid_arrays()]
    calls, donated = _counts(resolver) - before
    assert calls >= 8 and donated >= calls // 2
    for old, new, want in zip(held, now, was):
        assert old is not new and not old.is_deleted()
        np.testing.assert_array_equal(np.asarray(old), want)  # raises if donated
        assert not new.is_deleted()
    _assert_device_is_the_shadows(arena)


# -- 2. a staged plan launches on its own snapshot ------------------------------

def _stage_then_sync(cluster, stores, lane, seed):
    """Subjects enqueued and their plans staged (snapshots taken), then
    registrations in many chunks on every store (and, for the key lane, a
    prune of rows no subject touches, which breaks the finalize guards),
    then the sync, then the deferred launch and the harvest."""
    rng = np.random.default_rng(seed)
    node = stores[0].node
    resolver = BatchDepsResolver(num_buckets=BUCKETS, initial_cap=CAP)
    _attach(stores, node, resolver)
    resident, chaff = {}, {}
    for s in stores:
        lo = _store_lo(s)
        resident[s] = _key_lists(rng, 40, 2, lo=lo, domain=12)
        _register_keys(s, node, resident[s])
        chaff[s] = _key_lists(rng, 10, 2, lo=lo + 100, domain=40)
        chaff[s] = list(zip(_register_keys(s, node, chaff[s]), chaff[s]))
    far = _far(node)
    subs = []
    for s in stores:
        for i in range(4):
            tid = node.next_txn_id(TxnKind.WRITE, Domain.KEY)
            keys = Keys(resident[s][10 + i])
            subs.append((s, tid, keys, far,
                         resolver.enqueue_deps(s, tid, keys, far)))
    while not resolver._staged.get(id(node)):
        assert cluster.queue.process_one(), "stage never cut a plan"
    assert resolver.dispatches == 0
    plans = resolver._staged[id(node)]
    assert [len(p.groups) for p in plans] == [len(stores)]
    before = _counts(resolver)
    for s in stores:
        _register_keys(s, node, _key_lists(rng, 200, 4, lo=_store_lo(s) + 200))
        if lane == "key":
            for t, ks in chaff[s]:
                resolver.on_prune(s, t, ks)
        arena = resolver._arenas[id(s)]
        arena.device_arrays()
        arena.kid_arrays()
    calls, donated = _counts(resolver) - before
    assert donated >= calls // 2 > 0
    while not all(out.done for *_, out in subs):
        assert cluster.queue.process_one(), "harvest never fired"
    cluster.queue.drain(max_events=10_000)
    return resolver, subs


@pytest.mark.parametrize("lane", ("finalize", "key"))
@pytest.mark.parametrize("shape", ("single", "fused"))
def test_a_staged_plan_answers_from_its_own_snapshot(shape, lane):
    if shape == "single":
        cluster, _, store = setup_store()
        stores = [store]
    else:
        cluster, _, stores = _two_store_node()
    resolver, subs = _stage_then_sync(cluster, stores, lane, seed=len(shape))
    assert resolver.staged_dispatches == resolver.dispatches == 1
    assert resolver.host_fallbacks == 0
    if lane == "finalize":
        assert resolver.finalized_decodes >= len(stores)
        assert resolver.legacy_decodes == 0
    else:
        assert resolver.legacy_decodes >= len(stores)
    nonempty = 0
    for store, tid, keys, before, out in subs:
        host = store.host_calculate_deps(tid, keys, before)
        assert out.value() == host, f"subject {tid} ({store})"
        nonempty += bool(host.key_deps.all_txn_ids())
    assert nonempty == len(subs)


# -- 3. the device is the host shadows ------------------------------------------

@pytest.mark.parametrize("after", ("long_sync", "compaction", "growth"))
def test_device_lanes_equal_the_host_shadows(after):
    rng = np.random.default_rng(5)
    resolver, node, store, arena = _arena(cap=256)
    lists = _key_lists(rng, 200, 4)
    tids = _register_keys(store, node, lists)
    _assert_device_is_the_shadows(arena)  # hands every lane out
    for t, ks in list(zip(tids, lists))[:50 if after == "growth" else 150]:
        resolver.on_prune(store, t, ks)
    if after == "long_sync":
        _register_keys(store, node, _key_lists(rng, 50, 4))
        assert (arena.cap, arena.gen) == (256, 0)
    elif after == "compaction":
        _register_keys(store, node, _key_lists(rng, 100, 4))
        assert resolver.arena_compactions == 1 and arena.cap == 256
        assert arena._device is None and arena._kid_dev is None
    else:
        _register_keys(store, node, _key_lists(rng, 40, 4))
        arena.device_arrays()
        _register_keys(store, node, _key_lists(rng, 40, 4))
        # 206 live of 256: too many to compact onto half, so it grew
        assert resolver.arena_growths == 1 and arena.cap == 512
        assert resolver.arena_compactions == 0
    _assert_device_is_the_shadows(arena)
    # and once more from lanes that were handed out
    _register_keys(store, node, _key_lists(rng, 70, 4))
    _assert_device_is_the_shadows(arena)


# -- 4. how often a scatter donates ---------------------------------------------

@pytest.mark.parametrize("tier", sorted(TIERS))
def test_a_sync_donates_in_all_but_a_first_step_on_lent_lanes(tier):
    rng = np.random.default_rng(tier)
    resolver, node, store, arena = _arena()
    _register_keys(store, node, _key_lists(rng, 30, 2))
    arena.device_arrays()
    arena.kid_arrays()
    _register_keys(store, node, _key_lists(rng, **TIERS[tier]))
    chunks = len(list(arena._csr_chunks(sorted(arena._dirty_full))))
    words = -(-len(arena._dirty_kid_words) // 512)
    assert chunks >= 4 and words >= 3
    before = _counts(resolver)
    arena.device_arrays()
    assert list(_counts(resolver) - before) == [chunks, chunks - 1]
    before = _counts(resolver)
    arena.kid_arrays()
    assert list(_counts(resolver) - before) == [words, words - 1]
    # fresh lanes (what compact() leaves, and a first sync): the arena owns
    # the zeros it starts from, so every step donates
    arena._compact_onto([i for i in range(arena.count) if arena.key_sets[i]])
    assert arena._device is None and arena._kid_dev is None
    chunks = len(list(arena._csr_chunks(list(range(arena.count)))))
    before = _counts(resolver)
    arena.device_arrays()
    assert list(_counts(resolver) - before) == [chunks, chunks]
    before = _counts(resolver)
    arena.kid_arrays()
    calls, donated = _counts(resolver) - before
    assert calls == donated >= 3
    # one lane alone (scatter_rows, through deltas.flush_lane) never donates
    arena.exec_ts[3] += 1
    arena._dirty_ts.add(3)
    before = _counts(resolver)
    arena.device_arrays()
    assert list(_counts(resolver) - before) == [1, 0]


# -- 5. memory: the lent array and the sync's own, no more ----------------------

def test_a_sync_in_flight_holds_two_bitmaps_and_two_kid_tables():
    import jax
    rng = np.random.default_rng(9)

    def alive(shape):
        return [a.shape for a in jax.live_arrays()].count(shape)

    # shapes no other test of this file makes; whatever an earlier file of
    # this process left alive is counted first
    bitmap, kids = (1024, 64), (4096, 1024 // 32)
    base = alive(bitmap), alive(kids)
    resolver, node, store, arena = _arena(buckets=64, cap=1024)
    _register_keys(store, node, _key_lists(rng, 40, 4))
    held = (arena.device_arrays()[0], arena.kid_arrays())  # a staged plan's
    _register_keys(store, node, _key_lists(rng, 900, 4))
    arena.device_arrays()
    arena.kid_arrays()  # nothing waited for: the calls may still be queued
    assert resolver.arena_upload_calls > 20
    assert (held[0].shape, held[1].shape) == (bitmap, kids)
    assert (alive(bitmap) - base[0], alive(kids) - base[1]) == (2, 2)
    del held
    assert (alive(bitmap) - base[0], alive(kids) - base[1]) == (1, 1)


# -- 6. the programs keep their names -------------------------------------------

@pytest.mark.parametrize("program", DONATING)
def test_the_donating_programs_lower_under_their_old_names(program):
    cap, k, m, z = 64, 32, 8, 64
    bm = np.zeros((cap, k), np.float32)
    ts = np.zeros((cap, 3), np.int32)
    kd = np.zeros(cap, np.int32)
    vl = np.zeros(cap, bool)
    rows = np.zeros(m, np.int32)
    csr = (np.full(z, cap, np.int32), np.zeros(z, np.int32))
    args, lanes = {
        "arena_scatter": ((bm, ts, ts, kd, vl, rows, *csr, ts[:m], ts[:m],
                           kd[:m], vl[:m]), 5),
        "arena_scatter_keys": ((bm, rows, *csr), 1),
        "kid_word_scatter": ((np.zeros((16, cap // 32), np.uint32),
                              np.full(z, 16, np.int32), np.zeros(z, np.int32),
                              np.zeros(z, np.uint32)), 1),
    }[program]
    text = getattr(kernels, program).lower(*args).as_text()
    assert f"module @jit_{program} " in text
    # each lane it returns is an input it may overwrite
    assert text.count("tf.aliasing_output") \
        + text.count("jax.buffer_donor") == lanes


# -- 7. warmup covers the sync --------------------------------------------------

def test_after_warmup_a_sync_at_the_warmed_tiers_compiles_nothing():
    # as the live runner's warm_kernels asks, at shapes of this test's own
    buckets, cap, kid_cap = 32, 256, 512
    warmup(num_buckets=buckets, cap=cap, batch_tiers=(),
           scatter_tiers=(8, 64), nnz_tiers=(), store_tiers=(1,),
           out_tiers=(0,), range_out_tiers=(), kid_cap=kid_cap)
    programs = DONATING + ("arena_copy", "scatter_rows")
    warmed = {p: getattr(kernels, p)._cache_size() for p in programs}
    rng = np.random.default_rng(3)
    resolver, node, store, arena = _arena(buckets, cap, kid_cap)

    def sync():
        arena.device_arrays()
        arena.kid_arrays()

    for n, k in ((5, 2), (150, 4), (20, 70)):  # both row and both nnz tiers
        lists = _key_lists(rng, n, k, domain=400)
        tids = _register_keys(store, node, lists)
        sync()  # whole rows, from lent lanes (from fresh ones the first time)
        for t, ks in zip(tids, lists):
            resolver.on_prune(store, t, ks)
        sync()  # key sets, valid flags and kid words, from lent lanes
    assert resolver.arena_compactions == 0 and arena.cap == cap
    _register_keys(store, node, _key_lists(rng, 100, 4, domain=400))
    assert resolver.arena_compactions == 1 and arena.cap == cap
    sync()  # the re-upload, from fresh lanes
    assert resolver.arena_scatters_donated > 0
    assert {p: getattr(kernels, p)._cache_size() for p in programs} == warmed
