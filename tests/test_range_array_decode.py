"""The range lanes' array decode against the per-dependency decode it
replaced, kept here as a plain oracle.

`OldDecode` is the harvest that `ops/resolver.py` ran before the range lanes
were decoded as arrays over the dispatch: stage 1 resolves every CSR segment
to txn ids one row at a time, stage 2 applies the store's current maps one
dependency at a time into `KeyDepsBuilder` / `RangeDepsBuilder`, and a key
subject's two `KeyDeps` are unioned. It reads the same readback buffers and
routing tables (`g.rents`, `g.rk_slots`, `g.fin_slots`) and none of the new
decode's code. Every dispatch of every case below is decoded by both and
compared with `==` on `Deps`, tuple for tuple, and with the host scan.
"""
from __future__ import annotations

import numpy as np
import pytest

from accord_tpu.local.cfk import CfkStatus
from accord_tpu.ops.resolver import _RSUB, BatchDepsResolver
from accord_tpu.primitives.deps import (Deps, KeyDeps, KeyDepsBuilder,
                                        RangeDepsBuilder)
from accord_tpu.primitives.keyspace import Keys, Range, Ranges
from accord_tpu.primitives.timestamp import Domain, Timestamp, TxnId, TxnKind
from tests.test_local_engine import setup_store

KEYSPACE = 400


class OldDecode:
    """The old decode, hooked beside the new one: `fence` after the
    resolver's own fence (the arena not yet mutated), `decode` after the
    resolver's own decode (the same host maps)."""

    def __init__(self, resolver):
        self.resolver = resolver
        self.fenced = {}        # id(group) -> {"r": raw, "rk": raw}
        self.compared = 0
        self.filtered_seen = 0
        fence, core = resolver._fence_finalized, resolver._decode_core

        def fence_too(store, arena):
            fence(store, arena)
            for call in resolver._inflight.get(id(store.node), ()):
                for g in call.groups:
                    if g.arena is not arena:
                        continue
                    mine = self.fenced.setdefault(id(g), {})
                    if g.rmat is not None and "r" not in mine:
                        mine["r"] = self.stab_range(g)
                    if g.rk_mat is not None and "rk" not in mine:
                        mine["rk"] = self.stab_rkey(g)

        def core_too(call):
            got = core(call)
            for g in call.groups:
                want = self.group(call, g)
                if want is None:
                    continue
                for j, deps in want.items():
                    assert got[g.idx[j]] == deps, (
                        f"item {j} {g.items[j].txn_id}: array decode "
                        f"{got[g.idx[j]]!r} != old decode {deps!r}")
                    self.compared += 1
            return got

        resolver._fence_finalized = fence_too
        resolver._decode_core = core_too

    # -- stage 1, a row at a time --------------------------------------------
    @staticmethod
    def stab_range(g):
        indptr, dep_rows = g.rfin_np[0], g.rfin_np[1]
        ids = g.arena.ranges.ids_np
        raw = []
        for e, j, k in g.rents:
            lo, hi = int(indptr[e]), int(indptr[e + 1])
            if lo == hi:
                continue
            tid = g.items[j].txn_id
            raw.append((j, k, [rid for rid in
                               (ids[row] for row in dep_rows[lo:hi])
                               if rid is not None and rid != tid]))
        return raw

    @staticmethod
    def stab_rkey(g):
        if not g.rk_slots:
            return []
        indptr, dep_rows = g.rkfin_np[0], g.rkfin_np[1]
        ids = g.arena.ids_np
        raw = []
        for s, (j, k) in enumerate(g.rk_slots):
            lo, hi = int(indptr[s]), int(indptr[s + 1])
            if lo == hi:
                continue
            tid = g.items[j].txn_id
            raw.append((j, k, [d for d in
                               (ids[row] for row in dep_rows[lo:hi])
                               if d is not None and d != tid]))
        return raw

    # -- stage 2, a dependency at a time -------------------------------------
    @staticmethod
    def finish_range(g, raw):
        builders, rsub = {}, {}
        for j, k, rids in raw:
            item = g.items[j]
            rt = item.store.range_txns
            if k is _RSUB:
                rb = rsub.setdefault(j, RangeDepsBuilder())
                for rid in rids:
                    rngs = rt.get(rid)
                    if rngs is None:
                        continue
                    for r in rngs.intersection(item.owned):
                        rb.add(r, rid)
                continue
            kb = builders.setdefault(j, KeyDepsBuilder())
            for rid in rids:
                rngs = rt.get(rid)
                if rngs is None or not rngs.contains_key(k):
                    continue
                kb.add(k, rid)
        return {j: kb.build() for j, kb in builders.items()}, rsub

    @staticmethod
    def finish_rkey(g, raw, rsub):
        for j, k, dep_ids in raw:
            item = g.items[j]
            c = item.store.cfks.get(k)
            if c is None:
                continue
            cov = c.covered if c.covered else None
            rb = rsub.setdefault(j, RangeDepsBuilder())
            pt = Range.point(k)
            for dep_id in dep_ids:
                info = c.get(dep_id)
                if info is None or info.status == CfkStatus.INVALIDATED:
                    continue
                e = cov.get(dep_id) if cov else None
                if e is not None and e[0] <= item.cover_seq \
                        and e[1] < item.before:
                    continue
                rb.add(pt, dep_id)

    def group(self, call, g):
        """item -> Deps as the old harvest delivered them, or None where it
        would have left the finalized lanes (nothing to compare)."""
        arena = g.arena
        mine = self.fenced.pop(id(g), {})
        if call.degraded or any(it.fallback for it in g.items):
            return None
        # key lane: the cut the key-only path still runs
        kds = None
        if g.fin_slots is not None:
            if g.fin_mat is not None:
                kds = g.fin_mat
            elif g.gen == arena.gen and g.kseq == arena.kseq \
                    and (g.fin_np is not None or not g.fin_slots[0]):
                kds = self.key_lane(g)
            if kds is None:
                return None
        raw_r = None
        guarded_r = g.rgen == arena.ranges.gen \
            and g.rseq == arena.ranges.rseq
        if g.rents is not None:
            raw_r = mine.get("r")
            if raw_r is None and guarded_r and g.rfin_np is not None:
                raw_r = self.stab_range(g)
            if raw_r is None:
                return None
        filtered = raw_r is not None and not guarded_r
        rkb, rsub = self.finish_range(g, raw_r) if raw_r is not None \
            else ({}, {})
        has_rsub = any(not isinstance(it.owned, Keys) for it in g.items)
        if has_rsub and g.kp is not None:
            raw_rk = mine.get("rk")
            guarded_k = g.gen == arena.gen and g.kseq == arena.kseq
            if raw_rk is None and g.rk_slots is not None and guarded_k \
                    and (g.rkfin_np is not None or not g.rk_slots):
                raw_rk = self.stab_rkey(g)
            if raw_rk is None:
                return None
            filtered = filtered or not guarded_k
            self.finish_rkey(g, raw_rk, rsub)
        self.filtered_seen += filtered
        out = {}
        for j, item in enumerate(g.items):
            if not isinstance(item.owned, Keys):
                rb = rsub.get(j)
                out[j] = Deps(KeyDeps.EMPTY, rb.build()) if rb is not None \
                    else Deps(KeyDeps.EMPTY)
                continue
            deps = Deps(kds[j] if kds is not None else KeyDeps.EMPTY)
            extra = rkb.get(j)
            if extra is not None and not extra.is_empty():
                deps = deps.union(Deps(extra))
            out[j] = deps
        return out

    def key_lane(self, g):
        flat_key, key_off = g.fin_slots
        out = [KeyDeps.EMPTY] * len(g.items)
        if not flat_key:
            return out
        indptr, dep_rows = g.fin_np[0], g.fin_np[1]
        ns = len(flat_key)
        h_slot = np.repeat(np.arange(ns), np.diff(indptr[:ns + 1]))
        h_row = dep_rows[:int(indptr[ns])].astype(np.int64)
        slot_item = np.repeat(np.arange(len(g.items)), np.diff(key_off))
        flat_cov = []
        for s in range(ns):
            c = g.items[int(slot_item[s])].store.cfks.get(flat_key[s])
            flat_cov.append(c.covered if c is not None and c.covered
                            else None)
        return self.resolver._assemble_key_deps(
            g.arena, g.items, h_slot, h_row, flat_key, flat_cov,
            any(c is not None for c in flat_cov), slot_item, key_off, out)


# -- populations --------------------------------------------------------------

class World:
    def __init__(self, seed=0, latency_ms=None):
        self.rng = np.random.default_rng(seed)
        self.cluster, self.node, self.store = setup_store()
        self.resolver = BatchDepsResolver(num_buckets=128, initial_cap=256)
        self.store.deps_resolver = self.resolver
        self.old = OldDecode(self.resolver)
        if latency_ms is not None:
            self.store.batch_window_ms = 0.5
            self.node.device_latency_ms = latency_ms
            self.node.device_poll_ms = 1.0
        self.stamps = []
        self.key_ids, self.range_ids = [], []

    def _id(self, kind, domain):
        ts = self.node.unique_now()
        self.stamps.append(ts)
        return TxnId.create(ts.epoch, ts.hlc, ts.node, kind, domain), ts

    def key_txn(self, keys, kind=TxnKind.WRITE,
                status=CfkStatus.WITNESSED, execute_at=None):
        tid, ts = self._id(kind, Domain.KEY)
        self.store.register(tid, Keys(keys), status, ts, execute_at)
        self.key_ids.append(tid)
        return tid

    def range_txn(self, pieces, kind=TxnKind.WRITE):
        tid, ts = self._id(kind, Domain.RANGE)
        self.store.register(tid, Ranges(Range(s, e) for s, e in pieces),
                            CfkStatus.WITNESSED, ts)
        self.range_ids.append(tid)
        return tid

    def populate(self, n_key=80, n_range=50):
        rng = self.rng
        for i in range(n_key + n_range):
            kind = TxnKind.WRITE if rng.integers(0, 3) else TxnKind.READ
            if i % (n_key + n_range) < n_key:
                self.key_txn({int(k) for k in rng.integers(
                    0, KEYSPACE, 1 + int(rng.integers(0, 4)))}, kind)
            else:
                pieces = []
                for _ in range(1 + int(rng.integers(0, 2))):
                    s = int(rng.integers(0, KEYSPACE - 40))
                    pieces.append((s, s + 1 + int(rng.integers(0, 40))))
                self.range_txn(pieces, kind)

    def far(self):
        return Timestamp(self.node.epoch,
                         self.node.time_service.now_micros() + 50_000,
                         0, self.node.id)

    def subject(self, what, kind=TxnKind.WRITE, before=None, tid=None):
        """`what`: a set of keys, or a list of (start, end) pieces."""
        if isinstance(what, (set, frozenset)):
            owned = self.store.owned(Keys(what))
            domain = Domain.KEY
        else:
            owned = self.store.owned(Ranges(Range(s, e) for s, e in what))
            domain = Domain.RANGE
        if tid is None:
            tid = self.node.next_txn_id(kind, domain)
        return tid, owned, before if before is not None else self.far()

    def random_subjects(self, n):
        rng, subs = self.rng, []
        for i in range(n):
            kind = TxnKind.WRITE if i % 2 else TxnKind.READ
            before = None if i % 4 else \
                self.stamps[int(rng.integers(0, len(self.stamps)))]
            if i % 3 == 0:
                pieces = []
                for _ in range(1 + (i % 6 == 0)):
                    s = int(rng.integers(0, KEYSPACE - 60))
                    pieces.append((s, s + 1 + int(rng.integers(0, 60))))
                subs.append(self.subject(pieces, kind, before))
            else:
                subs.append(self.subject(
                    {int(k) for k in rng.integers(
                        0, KEYSPACE, 1 + int(rng.integers(0, 5)))},
                    kind, before))
        return subs

    def resolve(self, subs):
        """One sync dispatch of all the subjects: the array decode runs
        over the whole batch, the oracle beside it; then the host scan."""
        got = self.resolver.resolve_batch(self.store, subs)
        for (tid, owned, before), deps in zip(subs, got):
            host = self.store.host_calculate_deps(tid, owned, before)
            assert deps == host, f"{tid}: {deps!r} != host scan {host!r}"
        return got

    def launch(self, subs):
        outs = [self.resolver.enqueue_deps(self.store, *s) for s in subs]
        d0 = self.resolver.dispatches
        while self.resolver.dispatches == d0:
            assert self.cluster.queue.process_one(), "tick never fired"
        assert not any(o.done for o in outs)
        return outs

    def land(self, subs, outs, host="all"):
        """`host`: which answers the host scan at harvest can speak for --
        "all", "range" (range subjects only: a fenced key lane keeps what
        it cut before the prune, before this change as after), or None."""
        while not all(o.done for o in outs):
            assert self.cluster.queue.process_one(), "harvest never fired"
        for (tid, owned, before), out in zip(subs, outs):
            if host == "all" or (host == "range"
                                 and not isinstance(owned, Keys)):
                want = self.store.host_calculate_deps(tid, owned, before)
                assert out.value() == want, f"{tid} against the host scan"
        return [o.value() for o in outs]

    def truncate_range_txn(self, tid):
        self.store.range_txns.pop(tid, None)
        self.store.range_index.remove(tid)
        self.resolver.on_truncate(self.store, tid)

    def prune_key_txn(self, tid, key):
        c = self.store.cfks[key]
        c.remove(tid)
        self.resolver.on_prune(self.store, tid, (key,))

    def settled(self, filtered=0):
        r = self.resolver
        assert r.host_fallbacks == 0 and r.range_fallbacks == 0
        assert r.legacy_decodes == 0
        assert r.range_array_decodes > 0
        assert r.range_filtered_decodes == filtered
        assert self.old.filtered_seen == filtered
        assert self.old.compared > 0, "the oracle compared nothing"


# -- the cases ----------------------------------------------------------------

@pytest.mark.parametrize("seed", [3, 17, 2**31 + 5])
def test_randomized_mixed_batches(seed):
    w = World(seed)
    w.populate()
    deps = w.resolve(w.random_subjects(48))
    assert any(not d.key_deps.is_empty() for d in deps)
    assert any(not d.range_deps.is_empty() for d in deps)
    # and again with more registered, the arena grown in between
    w.populate(n_key=40, n_range=60)
    w.resolve(w.random_subjects(48))
    w.settled()


def test_point_beside_one_key_wide_intersection():
    """`[k, k+)` (a key txn inside the subject) and `[k, k+1)` (a range txn
    one key wide) are two rows, the point first, as Range orders them."""
    w = World()
    a = w.key_txn({5})
    b = w.range_txn([(5, 6)])
    c = w.range_txn([(4, 7)])
    d = w.key_txn({6})
    (deps,) = w.resolve([w.subject([(0, 10)])])
    rows = list(deps.range_deps.items())
    assert [r for r, _ in rows] == [Range(4, 7), Range.point(5),
                                    Range(5, 6), Range.point(6)]
    assert [ids for _, ids in rows] == [(c,), (a,), (b,), (d,)]
    # a key subject joins both arenas' ids in one order
    (kd,) = w.resolve([w.subject({5, 6})])
    assert list(kd.key_deps.items()) == [(5, (a, b, c)), (6, (c, d))]
    w.settled()


def test_two_range_subject_hit_through_both_ranges():
    w = World()
    wide = w.range_txn([(0, 100)])
    both = w.range_txn([(5, 12), (48, 60)])
    one = w.range_txn([(55, 70)])
    k = w.key_txn({8, 52, 90})
    (deps,) = w.resolve([w.subject([(0, 10), (50, 58)])])
    assert list(deps.range_deps.items()) == [
        (Range(0, 10), (wide,)), (Range(5, 10), (both,)),
        (Range.point(8), (k,)), (Range(50, 58), (wide, both)),
        (Range.point(52), (k,)), (Range(55, 58), (one,))]
    w.settled()


def test_touching_ranges():
    """A subject's touching pieces merge; a registered range that only
    touches the subject is no dependency, one that overlaps by a key is."""
    w = World()
    inside = w.range_txn([(5, 15)])
    w.range_txn([(20, 30)])
    last = w.range_txn([(19, 25)])
    w.range_txn([(0, 3), (3, 4)])
    (deps,) = w.resolve([w.subject([(4, 10), (10, 20)])])
    assert list(deps.range_deps.items()) == [(Range(5, 15), (inside,)),
                                             (Range(19, 20), (last,))]
    w.settled()


def test_read_subjects_take_no_read():
    w = World()
    rr = w.range_txn([(0, 50)], TxnKind.READ)
    rw = w.range_txn([(10, 60)], TxnKind.WRITE)
    kr = w.key_txn({20}, TxnKind.READ)
    kw = w.key_txn({20}, TxnKind.WRITE)
    r, wr, k = w.resolve([w.subject([(5, 40)], TxnKind.READ),
                          w.subject([(5, 40)], TxnKind.WRITE),
                          w.subject({20}, TxnKind.READ)])
    assert set(r.range_deps.all_txn_ids()) == {rw, kw}
    assert set(wr.range_deps.all_txn_ids()) == {rr, rw, kr, kw}
    assert k.key_deps.for_key(20) == (rw, kw)
    w.settled()


def test_subject_that_is_itself_registered():
    w = World()
    w.populate(n_key=20, n_range=20)
    me = w.range_txn([(10, 90)])
    k_me = w.key_txn({15, 30})
    w.populate(n_key=10, n_range=10)
    deps = w.resolve([w.subject([(10, 90)], tid=me),
                      w.subject({15, 30}, tid=k_me),
                      w.subject([(0, 200)])])
    assert me not in deps[0].range_deps.all_txn_ids()
    assert k_me not in deps[1].key_deps.all_txn_ids()
    assert me in deps[2].range_deps.all_txn_ids()
    assert k_me in deps[2].range_deps.all_txn_ids()
    w.settled()


def test_covered_elision_inside_a_range_subject():
    """A key txn covered by a committed write on its key is elided from a
    range subject's point row and from a key subject's row alike, for a
    bound above the cover's executeAt only."""
    w = World()
    d1 = w.key_txn({7})
    d2 = w.key_txn({7, 9})
    cover = w.key_txn({7})
    w.range_txn([(0, 20)])
    for t in (d1, cover):
        w.store.register(t, Keys({7}), CfkStatus.COMMITTED, t.as_timestamp(),
                         w.node.unique_now())
    cover_exec = w.store.cfks[7].get(cover).execute_at
    w.store.register_commit_cover(cover, cover_exec,
                                  Deps(KeyDeps.of({7: [d1, d2]})))
    assert set(w.store.cfks[7].covered) == {d1}
    rs, ks, at_cover = w.resolve([
        w.subject([(0, 20)]), w.subject({7, 9}),
        w.subject([(0, 20)], before=cover_exec)])
    assert rs.range_deps.for_key(7) and d1 not in rs.range_deps.all_txn_ids()
    assert {d2, cover} <= set(rs.range_deps.all_txn_ids())
    assert d1 not in ks.key_deps.all_txn_ids()
    assert d1 in at_cover.range_deps.all_txn_ids()
    w.settled()


def test_invalidated_key_txn_inside_a_range_subject():
    """Invalidated before the launch: the device's valid lane drops it.
    Invalidated between launch and harvest, under guards that still hold:
    the rk lane's harvest-time status rule drops it from the range subject
    (the key lane has no such rule, and the old decode had none either)."""
    w = World(latency_ms=50.0)
    early = w.key_txn({11})
    late = w.key_txn({12})
    w.key_txn({13})
    w.range_txn([(0, 30)])
    w.store.register(early, Keys({11}), CfkStatus.INVALIDATED,
                     early.as_timestamp())
    subs = [w.subject([(5, 25)]), w.subject({11, 12, 13})]
    outs = w.launch(subs)
    w.store.register(late, Keys({12}), CfkStatus.INVALIDATED,
                     late.as_timestamp())
    got = w.land(subs, outs, host=None)
    assert early not in got[0].range_deps.all_txn_ids()
    assert late not in got[0].range_deps.all_txn_ids()
    assert got[0] == w.store.host_calculate_deps(*subs[0])
    w.settled()


@pytest.mark.parametrize("shape", ["no_interval_rows", "no_key_rows",
                                   "no_deps", "only_range_subjects"])
def test_empty_lanes(shape):
    w = World()
    if shape != "no_interval_rows":
        w.range_txn([(100, 120)])
        w.range_txn([(110, 130)], TxnKind.READ)
    if shape != "no_key_rows":
        w.key_txn({105, 300})
        w.key_txn({115})
    if shape == "no_deps":
        subs = [w.subject([(200, 250)]), w.subject({1, 2})]
    elif shape == "only_range_subjects":
        subs = [w.subject([(90, 125)]), w.subject([(0, 50), (104, 106)])]
    else:
        subs = [w.subject([(90, 125)]), w.subject({105, 115, 7}),
                w.subject([(0, 50)])]
    w.resolve(subs)
    r = w.resolver
    assert r.host_fallbacks == 0 and r.range_fallbacks == 0
    assert r.legacy_decodes == 0 and r.range_filtered_decodes == 0
    assert w.old.compared == len(subs)


@pytest.mark.parametrize("mutation", ["truncate_range", "prune_key", "both",
                                      "truncate_and_widen"])
def test_mutation_between_launch_and_harvest(mutation):
    """A truncation or prune between launch and harvest: the fence caches
    stage 1 under the pins, the guards break, and the harvest applies the
    host-map filters to the cache (`range_filtered_decodes`), equal to the
    old decode of the same cache and to the host scan after the mutation."""
    w = World(seed=23, latency_ms=50.0)
    w.populate(n_key=60, n_range=40)
    subs = w.random_subjects(30)
    outs = w.launch(subs)
    assert w.resolver.range_filtered_decodes == 0
    if mutation in ("truncate_range", "both", "truncate_and_widen"):
        for tid in w.range_ids[::3]:
            w.truncate_range_txn(tid)
    if mutation in ("prune_key", "both"):
        for tid in w.key_ids[::4]:
            for k in list(w.store.cfks):
                if w.store.cfks[k].get(tid) is not None:
                    w.prune_key_txn(tid, k)
    if mutation == "truncate_and_widen":
        # a hit txn registers more ranges after the fence: the filtered
        # decode answers with its current intersections, as the old did
        tid = w.range_ids[1]
        w.store.register(tid, Ranges([Range(0, KEYSPACE)]),
                         CfkStatus.WITNESSED, tid.as_timestamp())
    got = w.land(subs, outs, host={"truncate_range": "all",
                                   "truncate_and_widen": None}.get(
                                       mutation, "range"))
    assert any(not d.is_empty() for d in got)
    w.settled(filtered=1)
    # the next dispatch is guarded again
    w.resolve(w.random_subjects(12))
    w.settled(filtered=1)


def test_sync_point_kinds_and_sharded_twin_share_the_decode():
    """Exclusive sync points (range-domain, witness everything) through the
    same decode, on the mesh-sharded resolver's buffers."""
    from accord_tpu.ops.resolver import ShardedBatchDepsResolver
    from accord_tpu.parallel.mesh import make_mesh
    w = World(seed=5)
    w.resolver = ShardedBatchDepsResolver(mesh=make_mesh(), num_buckets=256,
                                          initial_cap=512)
    w.store.deps_resolver = w.resolver
    w.old = OldDecode(w.resolver)
    w.populate(n_key=30, n_range=25)
    w.range_txn([(0, KEYSPACE)], TxnKind.EXCLUSIVE_SYNC_POINT)
    subs = w.random_subjects(18) + [
        w.subject([(0, KEYSPACE)], TxnKind.EXCLUSIVE_SYNC_POINT)]
    w.resolve(subs)
    w.settled()
