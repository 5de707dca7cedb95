"""Device-side dep finalization: the finalized-CSR harvest (exact key
filtering + segment compaction ON device) must answer bit-identically to
the legacy unpackbits decode -- which is itself tested bit-identical to
the host scans -- across randomized mixed key/range workloads, truncation
and prune churn, compaction landing between dispatch and harvest, and
fused multi-store dispatches. The finalized counters prove the fast path
actually ran: any nonzero legacy_decodes on a healthy run means the
kernels silently handed decode back to the host."""
from __future__ import annotations

import numpy as np

from accord_tpu.local.cfk import CfkStatus
from accord_tpu.ops.resolver import BatchDepsResolver
from accord_tpu.primitives.deps import Deps, KeyDeps
from accord_tpu.primitives.keyspace import Keys, Range, Ranges
from accord_tpu.primitives.timestamp import Domain, Timestamp, TxnId, TxnKind
from tests.test_fused_dispatch import (_attach, _far, _mixed_subjects,
                                       _register_keys,
                                       _register_mixed_per_store, _run_async,
                                       _store_lo, _two_store_node)
from tests.test_local_engine import setup_store
from tests.test_range_device_deps import _register_mixed, _subjects


def _assert_clean(resolver):
    assert resolver.host_fallbacks == 0
    assert resolver.range_fallbacks == 0
    assert resolver.finalize_fallbacks == 0


def test_finalized_vs_legacy_randomized_differential():
    """The load-bearing differential: same store state, same subjects,
    finalize_on_device=True vs =False must produce identical Deps (and both
    must equal the host scan). The counters prove which decode ran."""
    rng = np.random.default_rng(1234)
    _, node, store = setup_store()
    fin = BatchDepsResolver(num_buckets=128, initial_cap=128)
    assert fin.finalize_on_device  # the default IS the finalized path
    store.deps_resolver = fin
    _, tss = _register_mixed(store, node, rng)

    subs = _subjects(store, node, rng, tss, n=40)
    fin_res = [fin.resolve_one(store, tid, owned, before)
               for tid, owned, before in subs]
    assert fin.finalized_decodes > 0, "finalized path never engaged"
    assert fin.legacy_decodes == 0, "finalized run leaked into legacy decode"
    _assert_clean(fin)

    # a fresh resolver adopts the same store state; finalize off = the
    # legacy unpackbits decode, bit-identical by construction
    leg = BatchDepsResolver(num_buckets=128, initial_cap=128,
                            finalize_on_device=False)
    store.deps_resolver = leg
    leg_res = [leg.resolve_one(store, tid, owned, before)
               for tid, owned, before in subs]
    assert leg.finalized_decodes == 0
    assert leg.legacy_decodes > 0
    _assert_clean(leg)

    key_seen = range_seen = 0
    for (tid, owned, before), fd, ld in zip(subs, fin_res, leg_res):
        assert fd == ld, f"finalized vs legacy diverge on {tid}"
        host = store.host_calculate_deps(tid, owned, before)
        assert fd == host, f"finalized vs host diverge on {tid}"
        key_seen += bool(host.key_deps.all_txn_ids())
        range_seen += bool(host.range_deps.all_txn_ids())
    assert key_seen > 0 and range_seen > 0, "differential vacuous"


def test_finalized_truncation_and_prune():
    """Truncate half the range txns and prune keys off some key txns; the
    finalized path must keep answering exactly (the kid table and interval
    arena shrink with the churn) with no truncated id surviving in any
    answer and no fallback to legacy decode."""
    rng = np.random.default_rng(77)
    _, node, store = setup_store()
    resolver = BatchDepsResolver(num_buckets=128, initial_cap=128)
    store.deps_resolver = resolver
    rids, tss = _register_mixed(store, node, rng, n_key=40, n_range=30)

    arena = resolver._arenas[id(store)]
    for tid in rids[::2]:
        store.range_txns.pop(tid, None)
        store.range_index.remove(tid)
        resolver.on_truncate(store, tid)
    # prune one entry off several keys' cfks, mirrored into the arena the
    # way store._deregister does, so kid-table row masks and kseq move
    # mid-differential
    pruned = 0
    for key in sorted(store.cfks)[:8]:
        cfk = store.cfks[key]
        for t in sorted(cfk._infos)[:1]:
            cfk.remove(t)
            resolver.on_prune(store, t, (key,))
            pruned += 1
    assert pruned > 0

    nonempty = 0
    truncated = set(rids[::2])
    for tid, owned, before in _subjects(store, node, rng, tss, n=24):
        host = store.host_calculate_deps(tid, owned, before)
        dev = resolver.resolve_one(store, tid, owned, before)
        assert dev == host, f"subject {tid} after truncation/prune"
        assert not (set(dev.range_deps.all_txn_ids()) & truncated)
        nonempty += bool(host.key_deps.all_txn_ids()
                         or host.range_deps.all_txn_ids())
    assert nonempty > 0, "differential vacuous"
    assert resolver.finalized_decodes > 0
    assert resolver.legacy_decodes == 0
    _assert_clean(resolver)


def test_compaction_between_dispatch_and_harvest_falls_back_exactly():
    """Compact the key arena while a finalized call is in flight: the
    kseq/gen guard must reject the device CSR (its row ids predate the
    compaction) and the harvest must fall back to the legacy decode over
    the PINNED id snapshot -- still exact, still no host fallback."""
    rng = np.random.default_rng(55)
    cluster, node, store = setup_store()
    resolver = BatchDepsResolver(num_buckets=128, initial_cap=128)
    store.deps_resolver = resolver
    store.batch_window_ms = 0.5
    node.device_latency_ms = 50.0
    node.device_poll_ms = 1.0
    lo = 0

    # prunable chaff (disjoint keys) so compaction can reclaim rows, plus
    # live rows the in-flight subjects actually depend on
    chaff_keys = [sorted({lo + int(k) for k in rng.integers(100, 140, 2)})
                  for _ in range(50)]
    chaff = _register_keys(store, node, chaff_keys)
    live = [sorted({lo + int(k) for k in rng.integers(0, 12, 2)})
            for _ in range(30)]
    _register_keys(store, node, live)
    for t, ks in zip(chaff, chaff_keys):
        resolver.on_prune(store, t, ks)

    arena = resolver._arenas[id(store)]
    far = _far(node)
    subs = []
    for i in range(6):
        tid = node.next_txn_id(TxnKind.WRITE, Domain.KEY)
        keys = Keys(live[10 + i])
        subs.append((tid, keys, far,
                     resolver.enqueue_deps(store, tid, keys, far)))

    while resolver.dispatches < 1:
        assert cluster.queue.process_one(), "tick never fired"
    assert all(not out.done for *_, out in subs)

    gen0 = arena.gen
    assert arena.compact(), "compaction should reclaim the pruned chaff"
    assert arena.gen == gen0 + 1
    assert gen0 in arena.retired_ids  # in-flight pin forced a snapshot

    while not all(out.done for *_, out in subs):
        assert cluster.queue.process_one(), "harvest never fired"
    assert resolver.stale_harvests >= 1
    # the guard tripped: the finalized CSR was discarded for the stale
    # group and the legacy decode ran over the pinned snapshot instead
    assert resolver.finalize_fallbacks >= 1
    assert resolver.host_fallbacks == 0
    cluster.queue.drain(max_events=10_000)
    assert gen0 not in arena.retired_ids  # pin released on harvest

    nonempty = 0
    for tid, keys, before, out in subs:
        host = store.host_calculate_deps(tid, keys, before)
        assert out.value() == host, f"subject {tid} across compaction"
        nonempty += bool(host.key_deps.all_txn_ids())
    assert nonempty > 0, "differential vacuous"

    # and a healthy resolve afterwards goes straight back to finalized
    f0 = resolver.finalized_decodes
    tid = node.next_txn_id(TxnKind.WRITE, Domain.KEY)
    dev = resolver.resolve_one(store, tid, Keys(live[0]), _far(node))
    assert dev == store.host_calculate_deps(tid, Keys(live[0]), _far(node))
    assert resolver.finalized_decodes == f0 + 1


def test_range_compaction_in_flight_finalized_range_guard():
    """The range twin: truncating + compacting the INTERVAL arena while a
    finalized call is in flight must trip the rseq/rgen guard for key
    subjects' range deps and still answer exactly via the translated
    candidate decode."""
    rng = np.random.default_rng(29)
    cluster, node, store = setup_store()
    resolver = BatchDepsResolver(num_buckets=128, initial_cap=128)
    store.deps_resolver = resolver
    store.batch_window_ms = 0.5
    node.device_latency_ms = 50.0
    node.device_poll_ms = 1.0
    rids, _ = _register_mixed(store, node, rng, n_key=30, n_range=40)

    arena = resolver._arenas[id(store)]
    far = Timestamp(node.epoch, node.time_service.now_micros() + 50_000,
                    0, node.id)
    subs = []
    for i in range(8):
        owned = store.owned(Keys(sorted(
            {int(k) for k in rng.integers(0, 1 << 16, 8)})))
        tid = node.next_txn_id(TxnKind.WRITE, Domain.KEY)
        subs.append((tid, owned, far,
                     resolver.enqueue_deps(store, tid, owned, far)))

    while resolver.dispatches < 1:
        assert cluster.queue.process_one(), "tick never fired"

    for tid in rids[:20]:
        store.range_txns.pop(tid, None)
        store.range_index.remove(tid)
        resolver.on_truncate(store, tid)
    rgen0 = arena.ranges.gen
    assert arena.ranges.compact(), "compaction should reclaim rows"

    while not all(out.done for *_, out in subs):
        assert cluster.queue.process_one(), "harvest never fired"
    assert resolver.stale_harvests >= 1
    assert resolver.host_fallbacks == 0
    cluster.queue.drain(max_events=10_000)

    nonempty = 0
    truncated = set(rids[:20])
    for tid, owned, before, out in subs:
        host = store.host_calculate_deps(tid, owned, before)
        assert out.value() == host, f"subject {tid} across range compaction"
        got = set(out.value().key_deps.all_txn_ids())
        assert not (got & truncated)
        # range txns hit by a KEY subject land in key_deps (per-key
        # attribution); count them to prove the stab was exercised
        nonempty += any(t.domain == Domain.RANGE for t in got)
    assert nonempty > 0, "differential vacuous"


def test_fused_multi_store_finalized_differential():
    """Fused cross-store dispatches ride the finalized path end to end:
    each participating store's group materializes from its own device CSR
    slice, answers match both the legacy-decode resolver and the host
    scans, and no group leaks into legacy decode."""
    rng = np.random.default_rng(63)
    cluster, node, stores = _two_store_node()
    fin = BatchDepsResolver(num_buckets=128, initial_cap=128)
    _attach(stores, node, fin, latency=5.0)
    for s in stores:
        _register_mixed_per_store(s, node, rng)

    subs = []
    for wave_rng in (np.random.default_rng(3), np.random.default_rng(4)):
        wave = []
        for s in stores:
            wave.extend(_mixed_subjects(s, node, wave_rng, 9))
        subs.append(wave)

    fin_res = []
    for wave in subs:
        fin_res.extend(_run_async(cluster, fin, wave))
    assert fin.dispatches < 2 * fin.ticks, "fused path disengaged"
    assert fin.finalized_decodes >= 2, "both stores' groups should finalize"
    assert fin.legacy_decodes == 0
    _assert_clean(fin)

    leg = BatchDepsResolver(num_buckets=128, initial_cap=128,
                            finalize_on_device=False)
    leg_res = []
    for wave in subs:
        leg_res.extend(_run_async(cluster, leg, wave))
    assert leg.finalized_decodes == 0 and leg.legacy_decodes > 0

    key_seen = range_seen = 0
    for (store, tid, owned, before), fd, ld in zip(
            [x for wave in subs for x in wave], fin_res, leg_res):
        assert fd == ld, f"finalized vs legacy diverge on {tid}"
        host = store.host_calculate_deps(tid, owned, before)
        assert fd == host, f"finalized vs host diverge on {tid}"
        key_seen += bool(host.key_deps.all_txn_ids())
        range_seen += bool(host.range_deps.all_txn_ids())
    assert key_seen > 0 and range_seen > 0, "differential vacuous"


def test_packed_segment_compact_overflow_signal():
    """A nonzero-word count whose BIT total exceeds out_cap must surface as
    indptr[-1] > out_cap -- the exact total, computed from popcounts before
    any scatter can drop -- never as a silently truncated CSR that decodes
    as a plausible-but-short dep list."""
    import jax.numpy as jnp

    from accord_tpu.ops.kernels import _packed_segment_compact

    rng = np.random.default_rng(13)
    m = rng.integers(0, 1 << 32, (4, 8), dtype=np.uint64).astype(np.uint32)
    total = int(np.unpackbits(m.view(np.uint8)).sum())
    out_cap = 32
    assert total > out_cap  # dense random words: ~512 bits
    indptr, dep_rows = _packed_segment_compact(jnp.asarray(m), out_cap)
    indptr = np.asarray(indptr)
    assert indptr[-1] == total > out_cap, "overflow signal lost"
    # per-segment counts stay exact too (they come from the popcount pass)
    pops = [int(np.unpackbits(row.view(np.uint8)).sum()) for row in m]
    assert np.array_equal(np.diff(indptr), pops)

    # and under the cap the compaction is the ground-truth bit walk
    m2 = np.zeros((3, 2), np.uint32)
    m2[0, 0] = 0b1010001
    m2[1, 1] = 1 << 31
    indptr2, rows2 = _packed_segment_compact(jnp.asarray(m2), 32)
    indptr2, rows2 = np.asarray(indptr2), np.asarray(rows2)
    assert indptr2.tolist() == [0, 3, 4, 4]
    assert rows2[:4].tolist() == [0, 4, 6, 63]


def test_out_cap_overflow_bumps_tier_and_falls_back_exactly():
    """Force the hysteresis picker to pin an undersized out_cap (seed the
    lane with a tiny observed bound), then resolve a subject with more deps
    than the tier holds: the overflow must bump the ladder, the ONE
    overflowing group must decode bit-identically through the legacy
    fallback, and the next dispatch must finalize cleanly on the bumped
    tier."""
    rng = np.random.default_rng(17)
    _, node, store = setup_store()
    resolver = BatchDepsResolver(num_buckets=128, initial_cap=1024)
    store.deps_resolver = resolver

    hot = 7
    for i in range(300):
        ts = node.unique_now()
        tid = TxnId.create(ts.epoch, ts.hlc, ts.node, TxnKind.WRITE,
                           Domain.KEY)
        ks = {hot} | {int(k) for k in rng.integers(0, 1 << 16, 2)}
        store.register(tid, Keys(sorted(ks)), CfkStatus.WITNESSED, ts)

    arena = resolver._arenas[id(store)]
    pol = resolver._outcap(arena, "key")
    pol.observe(8, 8)  # fake a quiet dispatch: estimate pins the 256 tier
    assert not pol.cold

    far = Timestamp(node.epoch, node.time_service.now_micros() + 50_000,
                    0, node.id)
    tid = node.next_txn_id(TxnKind.WRITE, Domain.KEY)
    owned = store.owned(Keys([hot]))
    host = store.host_calculate_deps(tid, owned, far)
    assert len(host.key_deps.all_txn_ids()) >= 300  # > the 256 rung
    dev = resolver.resolve_one(store, tid, owned, far)
    assert dev == host, "overflow fallback diverged from the host scan"
    assert resolver.finalize_fallbacks == 1
    assert resolver.legacy_decodes == 1
    assert pol.current >= 2048, "overflow did not bump the pinned tier"
    assert resolver.outcap_tier_switches >= 1

    # steady state after the bump: straight back to the finalized path
    f0, ff0 = resolver.finalized_decodes, resolver.finalize_fallbacks
    tid2 = node.next_txn_id(TxnKind.WRITE, Domain.KEY)
    dev2 = resolver.resolve_one(store, tid2, owned, far)
    assert dev2 == store.host_calculate_deps(tid2, owned, far)
    assert resolver.finalized_decodes == f0 + 1
    assert resolver.finalize_fallbacks == ff0
    assert resolver.host_fallbacks == 0


def test_finalized_key_harvest_reads_back_indptr_rows_and_two_words(
        monkeypatch):
    """One finalized key lane's harvest fetches (indptr, dep_rows, bound,
    csum) and nothing else: readback_bytes grows by exactly indptr's and
    dep_rows' bytes and the two words, with no [out_cap, 3] txn-id lane
    (the decode reads txn ids off the host's arena lanes by row)."""
    rng = np.random.default_rng(29)
    _, node, store = setup_store()
    resolver = BatchDepsResolver(num_buckets=128, initial_cap=128)
    store.deps_resolver = resolver
    for _ in range(40):
        ts = node.unique_now()
        tid = TxnId.create(ts.epoch, ts.hlc, ts.node, TxnKind.WRITE,
                           Domain.KEY)
        ks = {int(k) for k in rng.integers(0, 12, 3)}
        store.register(tid, Keys(sorted(ks)), CfkStatus.WITNESSED, ts)
    reads = []
    read = resolver._read
    monkeypatch.setattr(resolver, "_read",
                        lambda dev: reads.append(read(dev)) or reads[-1])
    far = Timestamp(node.epoch, node.time_service.now_micros() + 50_000,
                    0, node.id)
    tid = node.next_txn_id(TxnKind.WRITE, Domain.KEY)
    owned = store.owned(Keys([3, 7]))
    before = resolver.readback_bytes
    dev = resolver.resolve_one(store, tid, owned, far)
    assert dev == store.host_calculate_deps(tid, owned, far)
    assert dev.key_deps.all_txn_ids(), "differential vacuous"
    assert resolver.finalized_decodes == 1 and resolver.legacy_decodes == 0
    (fetched,) = reads
    indptr, dep_rows, bound, csum = fetched
    assert indptr.ndim == dep_rows.ndim == 1
    assert bound.shape == csum.shape == ()
    assert resolver.readback_bytes - before \
        == indptr.nbytes + dep_rows.nbytes + 8


def test_device_bound_and_range_stab_randomized_differential():
    """The retired host residuals, differentially: the default resolver
    (device-computed out-cap bound + on-device range-subject stabbing) vs
    the legacy unpackbits decode (finalize_on_device=False) -- both
    bit-identical to the host scans over a randomized mixed workload with
    multi-piece range subjects, before AND after truncation/prune churn."""
    rng = np.random.default_rng(2718)
    _, node, store = setup_store()
    dev = BatchDepsResolver(num_buckets=128, initial_cap=128)
    leg = BatchDepsResolver(num_buckets=128, initial_cap=128,
                            finalize_on_device=False)
    store.deps_resolver = dev
    rids, tss = _register_mixed(store, node, rng)

    def sweep(subs):
        key_seen = range_seen = 0
        for tid, owned, before in subs:
            host = store.host_calculate_deps(tid, owned, before)
            for r in (dev, leg):
                store.deps_resolver = r
                got = r.resolve_one(store, tid, owned, before)
                assert got == host, f"{tid} diverged (bound/stab config)"
            key_seen += bool(host.key_deps.all_txn_ids())
            range_seen += bool(host.range_deps.all_txn_ids())
        assert key_seen > 0 and range_seen > 0, "differential vacuous"

    subs = _subjects(store, node, rng, tss, n=36)
    # the population includes multi-piece range subjects (the per-piece
    # segment lanes under test)
    assert any(not isinstance(o, Keys) and len(list(o)) > 1
               for _, o, _ in subs)
    sweep(subs)
    # the device path really decoded range subjects from the stab, with no
    # legacy decode and no guard trips
    assert dev.range_subject_device_decodes > 0
    assert dev.legacy_decodes == 0 and dev.finalize_fallbacks == 0
    # the range lane's out_cap is now fed by the DEVICE stab-count bound
    # riding back with each range_finalize_csr result: after the first
    # dispatch the policy is warm, so steady-state range sizing pays no
    # host entries*nvalid pass (and the differential above proves the
    # device-bound-sized caps never undersize the compaction)
    rpol = dev._outcap(dev._arenas[id(store)], "range")
    assert not rpol.cold, "range lane never observed a device stab bound"
    assert leg.legacy_decodes > 0 and leg.finalized_decodes == 0

    # truncate half the range txns + prune a few key entries, mirrored into
    # every resolver (store._deregister fans out the same way), then the
    # whole differential must keep holding on the shrunk arenas
    for tid in rids[::2]:
        store.range_txns.pop(tid, None)
        store.range_index.remove(tid)
        for r in (dev, leg):
            r.on_truncate(store, tid)
    pruned = 0
    for key in sorted(store.cfks)[:6]:
        cfk = store.cfks[key]
        for t in sorted(cfk._infos)[:1]:
            cfk.remove(t)
            for r in (dev, leg):
                r.on_prune(store, t, (key,))
            pruned += 1
    assert pruned > 0
    sweep(_subjects(store, node, rng, tss, n=24))
    for r in (dev, leg):
        assert r.host_fallbacks == 0
        assert r.range_fallbacks == 0


def test_finalized_truncation_output_cap_growth():
    """Dep lists wider than the first OUT_TIER must grow the output
    capacity tier, not truncate: one hot key touched by hundreds of txns
    answers exactly (indptr overflow would silently drop deps if out_cap
    were pinned to the smallest tier)."""
    rng = np.random.default_rng(91)
    _, node, store = setup_store()
    resolver = BatchDepsResolver(num_buckets=128, initial_cap=1024)
    store.deps_resolver = resolver

    hot = 7
    for i in range(300):
        ts = node.unique_now()
        tid = TxnId.create(ts.epoch, ts.hlc, ts.node, TxnKind.WRITE,
                           Domain.KEY)
        ks = {hot} | {int(k) for k in rng.integers(0, 1 << 16, 2)}
        store.register(tid, Keys(sorted(ks)), CfkStatus.WITNESSED, ts)

    far = Timestamp(node.epoch, node.time_service.now_micros() + 50_000,
                    0, node.id)
    tid = node.next_txn_id(TxnKind.WRITE, Domain.KEY)
    owned = store.owned(Keys([hot]))
    host = store.host_calculate_deps(tid, owned, far)
    assert len(host.key_deps.all_txn_ids()) >= 300
    dev = resolver.resolve_one(store, tid, owned, far)
    assert dev == host
    assert resolver.finalized_decodes == 1
    assert resolver.legacy_decodes == 0
    _assert_clean(resolver)


def _shuffled_key_store_with_covers(seed, n=60, keyspace=12):
    """A key-only store whose arena rows are NOT in TxnId order: the ids
    are minted in order and registered shuffled, so a row and its rank
    differ on nearly every row. A third of the txns then commit, and the
    later committed writes cover the committed deps below them on each of
    their keys. Attaches a finalizing resolver first. Returns (rng, node,
    store, resolver, tids in id order, their stamps, their key sets, the
    covered (key, txn) pairs)."""
    rng = np.random.default_rng(seed)
    _, node, store = setup_store()
    fin = BatchDepsResolver(num_buckets=128, initial_cap=128)
    store.deps_resolver = fin
    stamps = [node.unique_now() for _ in range(n)]
    tids = [TxnId.create(ts.epoch, ts.hlc, ts.node, TxnKind.WRITE, Domain.KEY)
            for ts in stamps]
    key_sets = [sorted({int(k) for k in
                        rng.integers(0, keyspace, 1 + int(rng.integers(0, 3)))})
                for _ in range(n)]
    for i in rng.permutation(n).tolist():
        store.register(tids[i], Keys(key_sets[i]), CfkStatus.WITNESSED,
                       stamps[i])
    committed = sorted(rng.choice(n, n // 3, replace=False).tolist())
    for i in committed:
        store.register(tids[i], Keys(key_sets[i]), CfkStatus.COMMITTED,
                       stamps[i], tids[i].as_timestamp())
    for i in committed[len(committed) // 2:]:
        per_key = {k: [tids[j] for j in range(i) if k in key_sets[j]]
                   for k in key_sets[i]}
        store.register_commit_cover(tids[i], tids[i].as_timestamp(),
                                    Deps(KeyDeps.of(per_key)))
    covered = {(k, t) for k, c in store.cfks.items() for t in c.covered}
    return rng, node, store, fin, tids, stamps, key_sets, covered


def _fields(deps):
    kd, rd = deps.key_deps, deps.range_deps
    return (kd.keys, kd.txn_ids, kd.offsets, kd.value_idx,
            rd.ranges, rd.txn_ids, rd.offsets, rd.value_idx)


def test_covered_elision_recovers_rows_from_ranks():
    """Step 7 of _assemble_key_deps reads the covered map by txn id, and
    since the pairs are sorted as (slot, rank) it recovers each pair's row
    as order[rank]. Rows registered out of TxnId order make a rank read as
    a row name another txn: the elision would then drop or keep the wrong
    dependency and the answer would leave the host scan's. One sync batch,
    covers present, one subject itself registered (and itself covered)."""
    rng, node, store, fin, tids, stamps, key_sets, covered = \
        _shuffled_key_store_with_covers(41)
    assert len(covered) >= 5, "no covers: the case is vacuous"
    arena = fin._arenas[id(store)]
    rank = arena.row_rank()[0]
    assert (rank != np.arange(rank.size)).sum() > rank.size // 2

    me = next(t for _, t in sorted(covered))
    far = _far(node)
    subs = [(me, store.owned(Keys(key_sets[tids.index(me)])), far)]
    for i in range(24):
        ks = {int(k) for k in rng.integers(0, 12, 1 + int(rng.integers(0, 4)))}
        before = far if i % 3 else stamps[int(rng.integers(20, len(stamps)))]
        subs.append((node.next_txn_id(TxnKind.WRITE, Domain.KEY),
                     store.owned(Keys(ks)), before))
    cuts0 = fin.array_cuts
    got = fin.resolve_batch(store, subs)
    assert fin.array_cuts == cuts0 + 1      # one group, one domain
    elided = 0
    for (tid, owned, before), deps in zip(subs, got):
        host = store.host_calculate_deps(tid, owned, before)
        assert deps == host, f"{tid}: {deps!r} != host scan {host!r}"
        assert tid not in deps.key_deps.all_txn_ids()
        for k in owned:
            gone = {t for kk, t in covered if kk == k} \
                - set(deps.key_deps.for_key(k))
            elided += len(gone)
    assert elided > 0, "no covered dependency was elided"
    assert fin.finalized_decodes > 0 and fin.legacy_decodes == 0
    _assert_clean(fin)

    # the legacy decode of the same batch, on the same store state: the
    # same objects field for field (both end in _assemble_key_deps)
    leg = BatchDepsResolver(num_buckets=128, initial_cap=128,
                            finalize_on_device=False)
    store.deps_resolver = leg
    leg_got = leg.resolve_batch(store, subs)
    assert leg.legacy_decodes > 0 and leg.finalized_decodes == 0
    assert leg.array_cuts == 1
    for (tid, _, _), fd, ld in zip(subs, got, leg_got):
        assert _fields(fd) == _fields(ld), f"legacy vs finalized on {tid}"
    _assert_clean(leg)
