"""The staged tick pipeline: while device call N is in flight, the host
runs the NEXT tick's preaccept + encode (stage_host) so the host phases
hide inside the device window, and call N launches at the top of the next
tick event (stage_dispatch).

Four load-bearing properties:
  1. the staged tick decodes bit-identically to the host scan on a
     randomized mixed key/range two-store workload, and every launch comes
     off the encode-ahead stage;
  2. a preaccept that raises inside stage_host fails ONLY its own
     AsyncResult -- batchmates complete and the pipeline stays live;
  3. compaction landing BETWEEN encode-ahead (plan cut, pins taken) and
     the deferred launch is absorbed by the plan-time generation pin: the
     harvest translates rows on the device path, no host fallback;
  4. Node.shutdown() drains both stages -- staged (encode-ahead) plans AND
     in-flight calls -- so no enqueued AsyncResult strands.
"""
from __future__ import annotations

import numpy as np

from accord_tpu.local import commands
from accord_tpu.ops.resolver import BatchDepsResolver
from accord_tpu.primitives.keyspace import Keys
from tests.test_fused_dispatch import (
    _attach, _mixed_subjects, _register_mixed_per_store, _run_async,
    _two_store_node)
from tests.test_local_engine import mk_txn, setup_store
from tests.test_ops import _preaccept_population


def test_staged_tick_vs_host_differential():
    """Randomized mixed key/range workload over two stores in three waves:
    the staged pipeline must decode bit-identically to the host scan --
    and the deferred-launch path must actually engage."""
    rng = np.random.default_rng(61)
    cluster, node, stores = _two_store_node()
    staged = BatchDepsResolver(num_buckets=128, initial_cap=128)
    _attach(stores, node, staged, latency=5.0)
    for s in stores:
        _register_mixed_per_store(s, node, rng)

    waves = []
    for seed in (11, 12, 13):
        wave_rng = np.random.default_rng(seed)
        wave = []
        for s in stores:
            wave.extend(_mixed_subjects(s, node, wave_rng, 8))
        waves.append(wave)

    results = []
    for wave in waves:
        results.extend(_run_async(cluster, staged, wave))
    # every launch came from the encode-ahead stage, none from drain's
    # serial encode+launch
    assert staged.staged_dispatches > 0
    assert staged.staged_dispatches == staged.dispatches
    assert staged.host_fallbacks == 0 and staged.range_fallbacks == 0

    key_seen = range_seen = 0
    for (store, tid, owned, before), got in zip(
            [x for wave in waves for x in wave], results):
        host = store.host_calculate_deps(tid, owned, before)
        assert got == host, f"staged vs host diverge on {tid}"
        key_seen += bool(host.key_deps.all_txn_ids())
        range_seen += bool(host.range_deps.all_txn_ids())
    assert key_seen > 0 and range_seen > 0, "differential vacuous"


def test_staged_preaccept_exception_isolation(monkeypatch):
    """One poisoned preaccept inside stage_host fails only its own
    AsyncResult; every batchmate still completes with host-identical
    (outcome, witnessed, deps), and the NEXT batch through the same
    resolver proceeds normally (the pipeline did not wedge)."""
    cluster, node, store = setup_store()
    resolver = BatchDepsResolver(num_buckets=128, initial_cap=128)
    store.deps_resolver = resolver
    store.batch_window_ms = 0.5
    node.device_latency_ms = 5.0

    txns = []
    for i in range(6):
        txn = mk_txn([2 * i, 2 * i + 1], value=i)
        tid = node.next_txn_id(txn.kind, txn.domain)
        txns.append((tid, txn.slice(store.ranges, include_query=False),
                     node.compute_route(txn)))
    bad_tid = txns[2][0]

    real = commands.preaccept

    def poisoned(store_, txn_id, txn, route, ballot=None):
        if txn_id == bad_tid:
            raise RuntimeError("poisoned preaccept")
        if ballot is None:
            return real(store_, txn_id, txn, route)
        return real(store_, txn_id, txn, route, ballot)

    monkeypatch.setattr(commands, "preaccept", poisoned)

    outs = [store.submit_preaccept(tid, partial, route)
            for tid, partial, route in txns]
    cluster.queue.drain(max_events=100_000)

    assert all(o.done for o in outs)
    bad = outs[2]
    assert not bad.success
    assert "poisoned" in str(bad.failure)
    for i, ((tid, partial, _), out) in enumerate(zip(txns, outs)):
        if i == 2:
            continue
        assert out.success, f"batchmate {tid} infected by the poison"
        outcome, witnessed, deps = out.value()
        assert witnessed == store.command(tid).execute_at
        host = store.host_calculate_deps(
            tid, store.owned(partial.keys), witnessed)
        assert deps == host, f"batchmate {tid} deps diverge"
    assert resolver.host_fallbacks == 0

    # pipeline still live: a fresh wave through the same resolver completes
    monkeypatch.setattr(commands, "preaccept", real)
    txn = mk_txn([3], value=99)
    tid = node.next_txn_id(txn.kind, txn.domain)
    out = store.submit_preaccept(
        tid, txn.slice(store.ranges, include_query=False),
        node.compute_route(txn))
    cluster.queue.drain(max_events=100_000)
    assert out.success
    outcome, witnessed, deps = out.value()
    assert deps == store.host_calculate_deps(tid, store.owned(Keys([3])),
                                             witnessed)


def test_compaction_between_stage_and_dispatch():
    """compact() landing in the gap between encode-ahead (plan cut against
    generation G, pin taken) and the deferred launch must be absorbed by
    the plan-time pin: the harvest translates its rows on the DEVICE path
    (stale_harvests, not host_fallbacks) and matches the host scan."""
    rng = np.random.default_rng(37)
    cluster, node, store = setup_store()
    resolver = BatchDepsResolver(num_buckets=128, initial_cap=128)
    store.deps_resolver = resolver
    store.batch_window_ms = 0.5
    node.device_latency_ms = 50.0

    chaff_keys = [sorted(set(rng.integers(100, 140, 2).tolist()))
                  for _ in range(50)]
    chaff = _preaccept_population(store, node, chaff_keys)
    live_keys = [sorted(set(rng.integers(0, 12, 2).tolist()))
                 for _ in range(40)]
    live = _preaccept_population(store, node, live_keys)
    arena = resolver._arenas[id(store)]
    for t, ks in zip(chaff, chaff_keys):
        resolver.on_prune(store, t, ks)

    subs = []
    for i in range(20, 26):
        t = live[i]
        keys = Keys(live_keys[i])
        before = store.command(t).execute_at
        subs.append((t, keys, before,
                     resolver.enqueue_deps(store, t, keys, before)))

    # pump to the exact pipeline gap: plans staged (pins taken at plan
    # time), deferred launch not yet fired
    while not resolver._staged.get(id(node)):
        assert cluster.queue.process_one(), "stage never cut a plan"
    assert resolver.dispatches == 0

    gen0 = arena.gen
    assert arena.compact(), "compaction should reclaim the pruned chaff"
    assert arena.gen == gen0 + 1
    # the plan-time pin forced a row->txn snapshot of the retired mapping
    assert gen0 in arena.retired_ids

    while not all(out.done for *_, out in subs):
        assert cluster.queue.process_one(), "harvest never fired"
    assert resolver.stale_harvests >= 1
    assert resolver.host_fallbacks == 0
    cluster.queue.drain(max_events=10_000)
    assert gen0 not in arena.retired_ids  # pin released after harvest

    nonempty = 0
    for t, keys, before, out in subs:
        host = store.host_calculate_deps(t, keys, before)
        assert out.value() == host, f"subject {t} diverges post-compaction"
        nonempty += bool(host.key_deps.all_txn_ids())
    assert nonempty > 0, "differential vacuous"


def test_drain_flushes_both_stages():
    """Node.shutdown() with one call in flight AND one encode-ahead plan
    staged must flush both: every AsyncResult completes (host-identical),
    and the pipeline state for the node is empty."""
    rng = np.random.default_rng(53)
    cluster, node, store = setup_store()
    resolver = BatchDepsResolver(num_buckets=128, initial_cap=128)
    store.deps_resolver = resolver
    store.batch_window_ms = 0.5
    node.device_latency_ms = 50.0  # harvest lands far beyond the ticks

    live_keys = [sorted(set(rng.integers(0, 12, 2).tolist()))
                 for _ in range(30)]
    live = _preaccept_population(store, node, live_keys)

    def enqueue(idxs):
        outs = []
        for i in idxs:
            t = live[i]
            keys = Keys(live_keys[i])
            before = store.command(t).execute_at
            outs.append((t, keys, before,
                         resolver.enqueue_deps(store, t, keys, before)))
        return outs

    wave_a = enqueue(range(10, 15))
    while resolver.dispatches < 1:
        assert cluster.queue.process_one(), "first launch never fired"
    wave_b = enqueue(range(20, 25))
    while not resolver._staged.get(id(node)):
        assert cluster.queue.process_one(), "second stage never cut a plan"

    # the exact mid-pipeline state: call in flight + plan staged
    assert len(resolver._inflight[id(node)]) == 1
    assert all(not out.done for *_, out in wave_a + wave_b)

    node.shutdown()

    assert all(out.done for *_, out in wave_a + wave_b)
    assert not resolver._staged.get(id(node))
    assert not resolver._inflight.get(id(node))
    assert resolver.host_fallbacks == 0
    nonempty = 0
    for t, keys, before, out in wave_a + wave_b:
        host = store.host_calculate_deps(t, keys, before)
        assert out.value() == host, f"subject {t} diverges after drain"
        nonempty += bool(host.key_deps.all_txn_ids())
    assert nonempty > 0, "differential vacuous"
    # idempotent
    node.shutdown()
