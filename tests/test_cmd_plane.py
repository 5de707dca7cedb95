"""Differential tests of the device command plane (ops/cmd_plane.py): with
and without cmd_plane the engine must produce BIT-identical outcomes, status
histories, executeAt choices, promised/accepted ballots and HLC clocks -- the
kernel (ops/kernels.cmd_tick) re-expresses local/commands.py, it does not
approximate it. The randomized script deliberately drives the awkward
interleavings: ballot contention, redundant re-delivery, compaction in
flight, truncation floors (where the plane must FALL BACK, identically)."""
from __future__ import annotations

import random

import pytest

from accord_tpu.local import commands
from accord_tpu.local.commands import AcceptOutcome, CommitOutcome
from accord_tpu.local.status import Status
from accord_tpu.primitives.deps import Deps, KeyDeps
from accord_tpu.primitives.keyspace import Keys
from accord_tpu.primitives.timestamp import Ballot, Timestamp, TxnKind
from accord_tpu.primitives.txn import Txn
from accord_tpu.sim.cluster import Cluster, ClusterConfig
from accord_tpu.sim.list_store import ListQuery, ListRead, ListUpdate

pytestmark = pytest.mark.cmd_plane


def _env(cmd_plane: bool):
    cluster = Cluster(1, ClusterConfig(num_nodes=1, rf=1, num_shards=1,
                                       stores_per_node=1, progress=False,
                                       cmd_plane=cmd_plane))
    node = cluster.nodes[1]
    return cluster, node, node.command_stores.stores[0]


def _mk_txn(keys, value):
    k = Keys(sorted(keys))
    return Txn(TxnKind.WRITE, k, read=ListRead(k),
               update=ListUpdate(k, value), query=ListQuery())


def _snap(store, node, tid):
    cmd = store.command_if_present(tid)
    if cmd is None:
        return ("absent", node._last_hlc)
    return (int(cmd.status), cmd.execute_at, cmd.promised,
            cmd.accepted_ballot, cmd.txn is not None, int(cmd.durability),
            node._last_hlc)


def _script(rng: random.Random, n_ops: int):
    """Abstract op script over txn refs; realized identically per env."""
    ops = []
    n_txns = 0
    live = []
    for _ in range(n_ops):
        r = rng.random()
        if r < 0.35 or not live:
            ref = n_txns
            n_txns += 1
            live.append(ref)
            keys = rng.sample(range(1, 9), rng.randint(1, 3))
            ops.append(("new", ref, tuple(keys), ref + 1))
        else:
            ref = rng.choice(live)
            r2 = rng.random()
            if r2 < 0.2:
                # ballot contention: recovery-style re-preaccept (possibly
                # a LOWER ballot, which must be rejected)
                ops.append(("re_pa", ref, rng.choice((0, 1, 2, 5))))
            elif r2 < 0.45:
                ops.append(("accept", ref, rng.choice((1, 2, 5)),
                            rng.randint(0, 50), rng.random() < 0.5))
            elif r2 < 0.75:
                ops.append(("commit", ref, rng.random() < 0.2))
            else:
                ops.append(("apply", ref))
        if rng.random() < 0.06:
            ops.append(("compact",))
    return ops


def _realize(env, script, batch_plane: bool, compact_live: bool):
    """Run the script against one env; returns the full history. With
    batch_plane the device side routes contiguous runs through
    CmdPlane.eval_batch (exercising the multi-op kernel carry); the host
    side always calls the Python handlers one by one."""
    cluster, node, store = env
    hist = []
    tids, txns, routes = {}, {}, {}

    def _ids(ref):
        return tids[ref], txns[ref], routes[ref]

    def run_one(op):
        kind = op[0]
        if kind == "compact":
            if compact_live and store.cmd_plane is not None:
                store.cmd_plane.compact()
            hist.append(("compacted",))
            return
        ref = op[1]
        if kind == "new":
            txn = _mk_txn(op[2], op[3])
            tid = node.next_txn_id(txn.kind, txn.domain)
            tids[ref], txns[ref] = tid, txn
            routes[ref] = node.compute_route(txn)
            out = store.submit_preaccept(
                tid, txn.slice(store.ranges, include_query=False),
                routes[ref])
            got = {}
            out.on_success(lambda v: got.update(v=v))
            assert "v" in got or out.done
            outcome = got["v"][0]
        elif kind == "re_pa":
            tid, txn, route = _ids(ref)
            ballot = Ballot.ZERO if op[2] == 0 else Ballot(1, op[2], 0, 1)
            if store.cmd_plane is not None and batch_plane:
                from accord_tpu.ops.cmd_plane import CmdOp
                outcome = store.cmd_plane.eval_batch([CmdOp.preaccept(
                    tid, txn.slice(store.ranges, include_query=False),
                    route, ballot)])[0].outcome
            else:
                outcome = commands.preaccept(
                    store, tid,
                    txn.slice(store.ranges, include_query=False), route,
                    ballot)
        elif kind == "accept":
            tid, txn, route = _ids(ref)
            cmd = store.command_if_present(tid)
            base = cmd.execute_at if cmd is not None \
                and cmd.execute_at is not None else tid
            proposal = Timestamp(base.epoch, base.hlc + op[3], 0, 1)
            deps = Deps(KeyDeps.of(
                {sorted(txn.keys)[0]: [tid]})) if op[4] else None
            outcome = store.accept_op(tid, Ballot(1, op[2], 0, 1), route,
                                      store.owned(txn.keys), proposal, deps)
        elif kind == "commit":
            tid, txn, route = _ids(ref)
            cmd = store.command_if_present(tid)
            ea = cmd.execute_at if cmd is not None \
                and cmd.execute_at is not None else tid.as_timestamp()
            if op[2]:   # inconsistent-timestamp probe on redundant delivery
                ea = Timestamp(ea.epoch, ea.hlc + 1, ea.flags, ea.node)
            outcome = store.commit_op(
                tid, route, txn.slice(store.ranges, include_query=False),
                ea, Deps.NONE)
        else:   # apply
            tid, txn, route = _ids(ref)
            cmd = store.command_if_present(tid)
            ea = cmd.execute_at if cmd is not None \
                and cmd.execute_at is not None else tid.as_timestamp()
            outcome = store.apply_op(
                tid, route, txn.slice(store.ranges, include_query=False),
                ea, Deps.NONE, None, None)
        hist.append((kind, ref, outcome, _snap(store, node, tids[ref])))
        cluster.drain()

    for op in script:
        run_one(op)
    return hist


def _differential(seed: int, compact_live: bool = True,
                  truncate: bool = False) -> None:
    rng = random.Random(seed)
    script = _script(rng, 60)
    hists = []
    for flag in (False, True):
        env = _env(flag)
        if truncate:
            # a live truncation floor makes every op inadmissible: the plane
            # must FALL BACK to the handlers and still match bit for bit
            _c, node, store = env
            floor = Timestamp(1, 10, 0, 1)
            store.truncated_before = store.truncated_before.with_range(
                1, 5, floor, Timestamp.merge_max)
        hists.append(_realize(env, script, batch_plane=True,
                              compact_live=compact_live))
        if flag and truncate:
            assert env[2].cmd_plane.fallbacks > 0, \
                "truncation floor never forced a fallback"
    assert len(hists[0]) == len(hists[1])
    for i, (a, b) in enumerate(zip(*hists)):
        assert a == b, (f"seed {seed} diverged at step {i}:\n "
                        f"host {a}\n dev  {b}")


def test_randomized_differential():
    """Ballot contention + redundant deliveries + compaction in flight:
    identical histories across random interleavings."""
    for seed in (3, 17, 40, 71):
        _differential(seed)


def test_differential_under_truncation():
    """With a truncation floor active the plane admits nothing; the host
    fallback path must keep the histories identical."""
    _differential(9, truncate=True)


def test_compaction_in_flight():
    """Ops hold TxnIds, not rows: compacting between op construction and
    eval_batch must not corrupt evaluation (rows re-resolve at dispatch,
    applied txns re-seed from the store's Command objects)."""
    from accord_tpu.ops.cmd_plane import CmdOp
    _cluster, node, store = _env(True)
    plane = store.cmd_plane
    txn = _mk_txn([3], 1)
    tid = node.next_txn_id(txn.kind, txn.domain)
    route = node.compute_route(txn)
    part = txn.slice(store.ranges, include_query=False)
    assert plane.eval_batch([CmdOp.preaccept(tid, part, route)])[0] \
        .outcome == AcceptOutcome.SUCCESS
    ea = store.command(tid).execute_at
    # construct the commit+apply ops FIRST, compact while they're in flight
    ops = [CmdOp.commit(tid, route, part, ea, Deps.NONE),
           CmdOp.apply(tid, route, part, ea, Deps.NONE)]
    plane.compact()
    before = plane.compactions
    res = plane.eval_batch(ops)
    assert [r.outcome for r in res] == [CommitOutcome.SUCCESS,
                                       CommitOutcome.SUCCESS]
    _cluster.drain()
    assert store.command(tid).status == Status.APPLIED
    # applied rows drop at the next compaction; a redundant re-delivery
    # re-seeds the row from the Command and stays REDUNDANT
    plane.compact()
    assert plane.compactions == before + 1
    assert tid not in plane.row_of
    res = plane.eval_batch([CmdOp.commit(tid, route, part, ea, Deps.NONE)])
    assert res[0].outcome == CommitOutcome.REDUNDANT
    assert tid in plane.row_of


def test_burn_differential():
    """Full-cluster end-to-end: identical burn event logs with the plane
    threaded under every replica's PreAccept/Accept/Commit/Apply."""
    from accord_tpu.sim.burn import run_burn
    kw = dict(ops=60, write_ratio=0.85, key_count=6, collect_log=True)
    host = run_burn(7, config=ClusterConfig(), **kw)
    dev = run_burn(7, config=ClusterConfig(cmd_plane=True), **kw)
    assert host.acked == dev.acked == 60
    assert host.log == dev.log, "cmd_plane burn diverged from host burn"


def test_burn_differential_contended():
    """High write ratio on few keys: the slow path (witness bumps, accept
    rounds, recovery ballots) must stay bit-identical too."""
    from accord_tpu.sim.burn import run_burn
    kw = dict(ops=80, write_ratio=0.95, key_count=3, collect_log=True)
    host = run_burn(23, config=ClusterConfig(durability=True), **kw)
    dev = run_burn(23, config=ClusterConfig(durability=True,
                                            cmd_plane=True), **kw)
    assert host.acked == dev.acked == 80
    assert host.log == dev.log


def test_burn_differential_authoritative():
    """The `cmd_plane_authoritative` cluster flag: device promotions decide
    status transitions WITH the store attached (host handlers replay side
    effects only). The promotion predicates are >=-band status compares, so
    arena rows running ahead of the store must never change a decision --
    the burn history stays bit-identical to the host baseline."""
    from accord_tpu.sim.burn import run_burn
    kw = dict(ops=60, write_ratio=0.85, key_count=6, collect_log=True)
    host = run_burn(7, config=ClusterConfig(), **kw)
    auth = run_burn(7, config=ClusterConfig(
        cmd_plane=True, cmd_plane_authoritative=True), **kw)
    assert host.acked == auth.acked == 60
    assert host.log == auth.log, \
        "authoritative cmd_plane burn diverged from host burn"
    assert auth.counters.get("cmd_plane_dispatches", 0) > 0


def test_warmup_zero_recompiles():
    """After warmup_cmd_plane at the exact arena/op tiers, a live workload
    mints no new cmd_tick compiles (the bench's recompile gate)."""
    from accord_tpu.ops.cmd_plane import warmup_cmd_plane
    from accord_tpu.ops.kernels import jit_cache_sizes
    warmup_cmd_plane(caps=(1024,), key_caps=(1024,), kpad=4,
                     op_tiers=(8,), promote_modes=(False,))
    warmed = jit_cache_sizes()["cmd_tick"]
    assert warmed > 0
    _cluster, node, store = _env(True)
    from accord_tpu.ops.cmd_plane import CmdOp
    for v in range(6):
        txn = _mk_txn([v + 1], v)
        tid = node.next_txn_id(txn.kind, txn.domain)
        part = txn.slice(store.ranges, include_query=False)
        out = store.cmd_plane.eval_batch(
            [CmdOp.preaccept(tid, part, node.compute_route(txn))])
        assert out[0].outcome == AcceptOutcome.SUCCESS
    assert store.cmd_plane.dispatches >= 6
    assert jit_cache_sizes()["cmd_tick"] == warmed, \
        "live cmd_plane workload minted compiles past warmup"


def test_plane_metrics_reach_node_snapshot():
    """The four glossary counters surface through Node.metrics_snapshot."""
    _cluster, node, store = _env(True)
    txn = _mk_txn([2], 1)
    tid = node.next_txn_id(txn.kind, txn.domain)
    store.submit_preaccept(tid, txn.slice(store.ranges, include_query=False),
                           node.compute_route(txn))
    snap = node.metrics_snapshot()
    assert snap.get("cmd_plane_dispatches", 0) >= 1
    assert snap.get("cmd_plane_upload_bytes", 0) > 0
    assert snap.get("cmd_fastpath_device_evals", 0) >= 1


def test_plane_failure_replays_span_counted_and_logged(caplog, monkeypatch):
    """A cmd plane that RAISES (a refused program, a launch error) must not
    vanish: the resolver's drain replays the span through the Python
    handlers, counts it in resolver.cmd_span_replays and logs the first
    one. Only the drain runs -- nothing is dispatched, nothing compiles."""
    import logging

    from accord_tpu.ops.resolver import BatchDepsResolver

    resolver = BatchDepsResolver(num_buckets=128, initial_cap=256)
    cluster = Cluster(1, ClusterConfig(
        num_nodes=1, rf=1, num_shards=1, stores_per_node=1, progress=False,
        cmd_plane=True, deps_resolver_factory=lambda: resolver,
        deps_batch_window_ms=2.0))
    node = cluster.nodes[1]
    store = node.command_stores.stores[0]

    def refused(ops):
        raise RuntimeError("XLA refused cmd_tick")

    monkeypatch.setattr(store.cmd_plane, "eval_batch", refused)

    def enqueue(value):
        txn = _mk_txn((value, value + 1), value)
        tid = node.next_txn_id(txn.kind, txn.domain)
        resolver.enqueue_preaccept(
            store, tid, txn.slice(store.ranges, include_query=False),
            node.compute_route(txn), Ballot.ZERO)
        return tid

    with caplog.at_level(logging.ERROR, logger="accord_tpu.ops.resolver"):
        tids = [enqueue(1), enqueue(2)]
        items = resolver._drain_and_preaccept(node)
        assert resolver.cmd_span_replays == 1   # one span of two ops
        tids.append(enqueue(3))
        items += resolver._drain_and_preaccept(node)
    assert resolver.metrics.snapshot()["resolver.cmd_span_replays"] == 2
    # the host handlers answered every replayed op
    assert [it.txn_id for it in items] == tids
    assert all(store.command(t).status == Status.PRE_ACCEPTED for t in tids)
    assert store.cmd_plane.dispatches == 0
    logged = [r for r in caplog.records if "cmd plane failed" in r.message]
    assert len(logged) == 1 and logged[0].exc_info is not None
