"""Cross-store fused dispatch: a multi-store node's tick drains EVERY
store's pending items into one device call (store-id lane + per-group word
spans route results back), with generation pins isolating compaction per
store -- plus the field-granular arena deltas that ride the same PR.

Three load-bearing properties:
  1. one fused call per tick across stores, and compacting ONE store's
     arena mid-flight must not disturb the other store's pins or force a
     host fallback;
  2. fused dispatch decodes bit-identically to the host scan on a
     randomized mixed key/range workload, in fewer calls than stores x ticks;
  3. status-bump updates ship one int32 lane, not the full row --
     upload_bytes stays strictly below the full-row-equivalent baseline.
"""
from __future__ import annotations

import numpy as np

from accord_tpu.local.cfk import CfkStatus
from accord_tpu.ops.resolver import BatchDepsResolver
from accord_tpu.primitives.keyspace import Keys, Range, Ranges
from accord_tpu.primitives.timestamp import Domain, Timestamp, TxnId, TxnKind
from accord_tpu.sim.cluster import Cluster, ClusterConfig


def _two_store_node():
    cluster = Cluster(1, ClusterConfig(num_nodes=1, rf=1, num_shards=1,
                                       stores_per_node=2, progress=False))
    node = cluster.nodes[1]
    stores = node.command_stores.stores
    assert len(stores) == 2
    return cluster, node, stores


def _attach(stores, node, resolver, window=0.5, latency=50.0):
    for s in stores:
        s.deps_resolver = resolver
        s.batch_window_ms = window
    node.device_latency_ms = latency


def _store_lo(store):
    return min(int(r.start) for r in store.ranges)


def _register_keys(store, node, key_lists, status=CfkStatus.WITNESSED):
    tids = []
    for ks in key_lists:
        ts = node.unique_now()
        tid = TxnId.create(ts.epoch, ts.hlc, ts.node, TxnKind.WRITE,
                           Domain.KEY)
        store.register(tid, Keys(ks), status, ts)
        tids.append(tid)
    return tids


def _far(node):
    return Timestamp(node.epoch, node.time_service.now_micros() + 50_000,
                     0, node.id)


def test_fused_tick_with_per_store_compaction_in_flight():
    """Items from both stores ride ONE dispatch; compacting store A's arena
    while that call is in flight leaves store B's generation untouched, and
    every answer still decodes on the device path (no host fallback)."""
    rng = np.random.default_rng(23)
    cluster, node, stores = _two_store_node()
    resolver = BatchDepsResolver(num_buckets=128, initial_cap=128)
    _attach(stores, node, resolver)
    sa, sb = stores
    lo_a, lo_b = _store_lo(sa), _store_lo(sb)

    # store A: prunable chaff (disjoint keys) so compaction can reclaim
    # >= half its arena, plus live rows the subjects query
    chaff_keys = [sorted({lo_a + int(k) for k in rng.integers(100, 140, 2)})
                  for _ in range(50)]
    chaff = _register_keys(sa, node, chaff_keys)
    live_a = [sorted({lo_a + int(k) for k in rng.integers(0, 12, 2)})
              for _ in range(30)]
    _register_keys(sa, node, live_a)
    live_b = [sorted({lo_b + int(k) for k in rng.integers(0, 12, 2)})
              for _ in range(30)]
    _register_keys(sb, node, live_b)
    for t, ks in zip(chaff, chaff_keys):
        resolver.on_prune(sa, t, ks)

    arena_a = resolver._arenas[id(sa)]
    arena_b = resolver._arenas[id(sb)]
    assert arena_a is not arena_b

    far = _far(node)
    subs = []
    for i in range(4):
        tid = node.next_txn_id(TxnKind.WRITE, Domain.KEY)
        keys = Keys(live_a[10 + i])
        subs.append((sa, tid, keys, far,
                     resolver.enqueue_deps(sa, tid, keys, far)))
    for i in range(4):
        tid = node.next_txn_id(TxnKind.WRITE, Domain.KEY)
        keys = Keys(live_b[10 + i])
        subs.append((sb, tid, keys, far,
                     resolver.enqueue_deps(sb, tid, keys, far)))

    while resolver.dispatches < 1:
        assert cluster.queue.process_one(), "tick never fired"
    # the tentpole: both stores' items fused into one call
    assert resolver.ticks == 1
    assert resolver.dispatches == 1
    call = resolver._inflight[id(node)][0]
    assert len(call.groups) == 2
    assert {g.store for g in call.groups} == {sa, sb}
    assert all(not out.done for *_, out in subs)

    # compact store A mid-flight; store B's generations must not move
    gen_a0, gen_b0 = arena_a.gen, arena_b.gen
    assert arena_a.compact(), "compaction should reclaim the pruned chaff"
    assert arena_a.gen == gen_a0 + 1
    assert gen_a0 in arena_a.retired_ids  # pinned snapshot forced
    assert arena_b.gen == gen_b0
    assert not arena_b.retired_ids

    while not all(out.done for *_, out in subs):
        assert cluster.queue.process_one(), "harvest never fired"
    assert resolver.stale_harvests == 1
    assert resolver.host_fallbacks == 0
    cluster.queue.drain(max_events=10_000)
    assert gen_a0 not in arena_a.retired_ids  # pin released

    nonempty = 0
    for store, tid, keys, before, out in subs:
        host = store.host_calculate_deps(tid, keys, before)
        assert out.value() == host, f"subject {tid} ({store})"
        nonempty += bool(host.key_deps.all_txn_ids())
    assert nonempty > 0, "differential vacuous"


def _register_mixed_per_store(store, node, rng, n_key=25, n_range=15):
    lo = _store_lo(store)
    span = 4096
    for i in range(n_key):
        ts = node.unique_now()
        kind = TxnKind.WRITE if i % 3 else TxnKind.READ
        tid = TxnId.create(ts.epoch, ts.hlc, ts.node, kind, Domain.KEY)
        width = 20 if i % 9 == 0 else 1 + int(rng.integers(0, 4))
        keys = Keys(sorted({lo + int(k)
                            for k in rng.integers(0, span, width)}))
        store.register(tid, keys, CfkStatus.WITNESSED, ts)
    for i in range(n_range):
        ts = node.unique_now()
        kind = TxnKind.WRITE if i % 2 else TxnKind.READ
        tid = TxnId.create(ts.epoch, ts.hlc, ts.node, kind, Domain.RANGE)
        s = lo + int(rng.integers(0, span))
        store.register(tid, Ranges([Range(s, s + 1 + int(
            rng.integers(0, 1024)))]), CfkStatus.WITNESSED, ts)


def _mixed_subjects(store, node, rng, n):
    lo = _store_lo(store)
    span = 4096
    far = _far(node)
    subs = []
    for i in range(n):
        kind = TxnKind.WRITE if i % 2 else TxnKind.READ
        if i % 3 == 0:
            s = lo + int(rng.integers(0, span))
            owned = store.owned(
                Ranges([Range(s, s + 1 + int(rng.integers(0, 2048)))]))
            tid = node.next_txn_id(kind, Domain.RANGE)
        else:
            width = 1 + int(rng.integers(0, 4))
            owned = store.owned(Keys(sorted(
                {lo + int(k) for k in rng.integers(0, span, width)})))
            tid = node.next_txn_id(kind, Domain.KEY)
        subs.append((store, tid, owned, far))
    return subs


def _run_async(cluster, resolver, subs):
    outs = [resolver.enqueue_deps(store, tid, owned, before)
            for store, tid, owned, before in subs]
    cluster.queue.drain(max_events=100_000)
    assert all(o.done for o in outs)
    return [o.value() for o in outs]


def test_fused_vs_host_differential():
    """Randomized mixed key/range workload over two stores: the fused
    cross-store dispatch must decode bit-identically to the host scan,
    while issuing fewer device calls than store-count x ticks."""
    rng = np.random.default_rng(31)
    cluster, node, stores = _two_store_node()
    fused = BatchDepsResolver(num_buckets=128, initial_cap=128)
    _attach(stores, node, fused, latency=5.0)
    for s in stores:
        _register_mixed_per_store(s, node, rng)

    # interleave both stores' subjects, two waves (two fused ticks)
    subs = []
    for wave_rng in (np.random.default_rng(7), np.random.default_rng(8)):
        wave = []
        for s in stores:
            wave.extend(_mixed_subjects(s, node, wave_rng, 9))
        subs.append(wave)

    fused_res = []
    for wave in subs:
        fused_res.extend(_run_async(cluster, fused, wave))
    assert fused.ticks >= 2
    assert fused.dispatches < 2 * fused.ticks, "fused path disengaged"
    assert fused.host_fallbacks == 0 and fused.range_fallbacks == 0

    key_seen = range_seen = 0
    for (store, tid, owned, before), fd in zip(
            [x for wave in subs for x in wave], fused_res):
        host = store.host_calculate_deps(tid, owned, before)
        assert fd == host, f"fused vs host diverge on {tid}"
        key_seen += bool(host.key_deps.all_txn_ids())
        range_seen += bool(host.range_deps.all_txn_ids())
    assert key_seen > 0 and range_seen > 0, "differential vacuous"


def test_sharded_fused_two_store_differential():
    """The mesh-sharded resolver's fused cross-store dispatch must also
    decode bit-identically to the host scans on a mixed two-store workload
    (this exercises the per-store block concat in parallel/mesh.py, which
    must dodge the sharded-axis concatenate miscompile -- see
    _concat_lane_blocks)."""
    from accord_tpu.ops.resolver import ShardedBatchDepsResolver
    from accord_tpu.parallel.mesh import make_mesh
    rng = np.random.default_rng(41)
    cluster, node, stores = _two_store_node()
    res = ShardedBatchDepsResolver(mesh=make_mesh(), num_buckets=128,
                                   initial_cap=128)
    _attach(stores, node, res, latency=5.0)
    for s in stores:
        _register_mixed_per_store(s, node, rng)
    subs = []
    for s in stores:
        subs.extend(_mixed_subjects(s, node, np.random.default_rng(9), 9))
    outs = _run_async(cluster, res, subs)
    assert res.dispatches < 2 * res.ticks, "fused path disengaged"
    assert res.host_fallbacks == 0 and res.range_fallbacks == 0
    key_seen = range_seen = 0
    for (store, tid, owned, before), dv in zip(subs, outs):
        host = store.host_calculate_deps(tid, owned, before)
        assert dv == host, f"sharded fused diverges from host on {tid}"
        key_seen += bool(host.key_deps.all_txn_ids())
        range_seen += bool(host.range_deps.all_txn_ids())
    assert key_seen > 0 and range_seen > 0, "differential vacuous"


def test_field_granular_upload_accounting():
    """A status bump re-registration dirties only the exec-ts lane: the next
    device sync ships the int32 lane (upload_bytes_by_field['ts']) instead
    of full rows, and total upload_bytes stays strictly below the
    full-row-equivalent baseline."""
    from tests.test_local_engine import setup_store
    rng = np.random.default_rng(13)
    _, node, store = setup_store()
    resolver = BatchDepsResolver(num_buckets=128, initial_cap=128)
    store.deps_resolver = resolver

    key_lists = [sorted({int(k) for k in rng.integers(0, 64, 3)})
                 for _ in range(30)]
    tids = _register_keys(store, node, key_lists)

    def probe():
        tid = node.next_txn_id(TxnKind.WRITE, Domain.KEY)
        keys = Keys(key_lists[int(rng.integers(0, len(key_lists)))])
        far = _far(node)
        dev = resolver.resolve_one(store, tid, keys, far)
        assert dev == store.host_calculate_deps(tid, keys, far)

    probe()  # initial full upload
    by0 = dict(resolver.upload_bytes_by_field)
    ub0 = resolver.upload_bytes
    eq0 = resolver.upload_bytes_full_equiv
    assert by0["full"] > 0
    assert ub0 == eq0  # full uploads ARE the baseline

    # status bumps: same keys, later witnessed_at -> exec-ts lane only
    for tid, ks in list(zip(tids, key_lists))[:10]:
        store.register(tid, Keys(ks), CfkStatus.COMMITTED, node.unique_now())
    # and a couple of invalidations -> valid lane only
    for tid, ks in list(zip(tids, key_lists))[10:13]:
        store.register(tid, Keys(ks), CfkStatus.INVALIDATED,
                       node.unique_now())

    probe()  # granular delta upload
    by1 = dict(resolver.upload_bytes_by_field)
    assert by1["full"] == by0["full"], "bump re-uploaded full rows"
    assert by1["ts"] > by0["ts"]
    assert by1["valid"] > by0["valid"]
    # the delta cost strictly undercuts what full-row chunks would have paid
    granular = resolver.upload_bytes - ub0
    baseline = resolver.upload_bytes_full_equiv - eq0
    assert 0 < granular < baseline
    assert resolver.upload_bytes < resolver.upload_bytes_full_equiv
