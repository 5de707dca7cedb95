"""The node cell (`preaccept-8stores-100k.fanout-4096`, runners/node.py): one
replica node whose ranges are split over several CommandStores, every request
fanned out to the stores its keys fall in and merged into one reply.

Load-bearing properties:
  1. the plain reference -- key -> registered ids -- agrees with the
     program's own host scan (`store.host_calculate_deps`) unioned over the
     stores;
  2. the async pipeline through `CommandStores.map_reduce_async` against
     that reference, over seeds x 1, 2 and 8 stores x 1 and 4 keys a txn:
     every merged reply exact, each (key, txn id) once, the fan-out's and the
     resolver's counters counting what happened, nothing from a host scan,
     the legacy decode or a finalize fallback;
  3. the tie between the split and the whole: on the same seeded data the
     8-store node's merged reply is the 1-store node's reply;
  4. `correct` follows the timed path -- the control (a reply a dispatch
     loses one store's part) and the planted fault (two store slices
     swapped) read false at the rehearsal size, the sound run true;
  5. the cell's rehearsal, as the command runs it, ends `correct` with no
     compile request in its window, and its notes are what `noise.py`
     reads; a program without the entry point ends by itself;
  6. `PreAccept.process` and `Accept.process` reply through the shared
     method what their own loops replied before PR 35: one store, several
     stores, a Nack from one store.
"""
from __future__ import annotations

import json

import pytest

from benchmark import common, node_control
from benchmark.runners import node as node_runner

CELL = "preaccept-8stores-100k.fanout-4096"


def _params(**over):
    cell = common.load_json(common.HERE / "workloads" / f"{CELL}.json")
    config = common.load_json(
        common.HERE / "configs" / f"{cell['config']}.json")
    return {**config, **cell, **cell["rehearsal"], **over}


def _ask_all(node, n):
    """n fresh transactions asked of the node at once and drained: the
    subjects and, for each, (merged reply, failure)."""
    subjects = [node.fresh() for _ in range(n)]
    replies = [None] * n
    for i, (t, keys, bound, _) in enumerate(subjects):
        node.ask(t, keys, bound).add_callback(
            lambda value, failure, i=i: replies.__setitem__(
                i, (value, failure)))
    node.cluster.queue.drain(max_events=1_000_000)
    return subjects, replies


# -- 1. the reference against the program's host scan --------------------------

@pytest.mark.parametrize("stores", [1, 8])
def test_reference_is_the_host_scan_unioned_over_the_stores(stores):
    node = node_runner.Deployment(_params(stores=stores), 5)
    for _ in range(40):
        t, keys, bound, raw = node.fresh()
        scanned = set()
        for store in node.stores.intersecting(keys):
            deps = store.host_calculate_deps(t, store.owned(keys), bound)
            assert deps.range_deps.is_empty()
            scanned |= {(k, x) for k, ids in deps.key_deps.items()
                        for x in ids}
        assert scanned == node.expected(raw, bound)
        assert scanned, "a subject with no dependency at these sizes"


# -- 2. the pipeline against the reference -------------------------------------

@pytest.mark.parametrize("keys_per_txn", [1, 4])
@pytest.mark.parametrize("stores", [1, 2, 8])
@pytest.mark.parametrize("seed", [7, 4000000007])
def test_every_merged_reply_is_exact(seed, stores, keys_per_txn):
    p = _params(stores=stores, keys_per_txn=keys_per_txn)
    node = node_runner.Deployment(p, seed)
    assert node.resolver.pad_store_tiers == (stores if stores > 1 else None)
    assert [s.ranges for s in node.stores.all()] == \
        [s.slice_ranges for s in node.stores.all()]
    width = p["keys"] // stores
    before = node.counters()
    asked = slices = 0
    for _ in range(2):
        subjects, replies = _ask_all(node, p["subjects"])
        for (t, keys, bound, raw), (reply, failure) in zip(subjects, replies):
            assert failure is None
            got, pairs = node_runner.answer_set(reply)
            assert got == node.expected(raw, bound)
            assert pairs == len(got), "a (key, txn id) came twice"
            slices += len({k // width for k in raw})
        asked += len(subjects)
    d = common.delta(node.counters(), before)
    assert d["node.requests"] == asked
    assert d["node.store_slices"] == slices == d["resolver.subjects"]
    assert d["node.fanout_s"] > 0.0 and d["node.reduce_s"] > 0.0
    for name in node_runner.HOST_PATH_COUNTERS:
        assert not d.get(name), name
    assert d["resolver.finalized_decodes"] > 0
    fused = d.get("resolver.fused_dispatches", 0)
    groups = d.get("resolver.store_groups", 0)
    if stores == 1:
        # one group a dispatch: the plain kernels, and neither counter moves
        assert (fused, groups) == (0, 0)
    else:
        assert 0 < fused <= d["resolver.dispatches"]
        assert 2 * fused <= groups <= stores * fused
    if stores == 8 and keys_per_txn == 4:
        # the cell's shape: every dispatch meets every store
        assert fused == d["resolver.dispatches"] and groups == 8 * fused


def test_pad_store_tiers_holds_one_fused_tier_for_every_store_count():
    """`sim/cluster.py` derives pad_store_tiers from the node's store count:
    a dispatch that meets 2, 3 or 5 of the eight stores is topped up to
    eight blocks, so the fused program compiled for eight serves it (CHANGES
    PR 31's `bench_pad_tiers` row), and every reply stays exact."""
    from accord_tpu.ops.kernels import jit_cache_sizes
    from accord_tpu.primitives.keyspace import Keys
    p = _params(keys_per_txn=1)
    node = node_runner.Deployment(p, 23)
    assert node.resolver.pad_store_tiers == 8
    width, met, sizes = p["keys"] // 8, set(), []
    # single-key txns on keys of 8, then of 2, 3 and 5 stores: the subject
    # tier and the CSR tier are the same in every round
    for stores in (8, 2, 3, 5, 8):
        raws = [[(i % stores) * width + i % width] for i in range(8)]
        subjects = [(t, Keys(raw), ts, raw) for (t, _, ts, _), raw in
                    zip((node.fresh() for _ in raws), raws)]
        replies = []
        g0, d0 = node.resolver.store_groups, node.resolver.dispatches
        for t, keys, bound, _ in subjects:
            node.ask(t, keys, bound).add_callback(
                lambda value, failure: replies.append((value, failure)))
        node.cluster.queue.drain(max_events=100_000)
        assert node.resolver.dispatches == d0 + 1
        met.add(node.resolver.store_groups - g0)
        for (t, keys, bound, raw), (reply, failure) in zip(subjects, replies):
            assert failure is None
            assert node_runner.answer_set(reply)[0] == \
                node.expected(raw, bound)
        sizes.append(jit_cache_sizes()["fused_deps_resolve"])
    assert met == {8, 2, 3, 5}
    assert len(set(sizes)) == 1, sizes
    assert not node.resolver.host_fallbacks \
        and not node.resolver.finalize_fallbacks


# -- 3. the split and the whole -------------------------------------------------

@pytest.mark.parametrize("seed", [3, 4000000123])
def test_the_split_nodes_reply_is_the_whole_nodes_reply(seed):
    whole = node_runner.Deployment(_params(stores=1), seed)
    split = node_runner.Deployment(_params(stores=8), seed)
    assert whole.by_key == split.by_key
    (ws, wr), (ss, sr) = (_ask_all(n, 60) for n in (whole, split))
    assert [(t, raw) for t, _, _, raw in ws] == \
        [(t, raw) for t, _, _, raw in ss]
    for (one, f1), (merged, f8) in zip(wr, sr):
        assert f1 is None and f8 is None
        got, pairs = node_runner.answer_set(merged)
        assert (got, pairs) == node_runner.answer_set(one)
        assert pairs == len(got) > 0
        assert merged == one
    assert split.resolver.fused_dispatches > 0 == \
        whole.resolver.fused_dispatches
    rows = [a.count for a in split.arenas()]
    assert sum(rows) > whole.arenas()[0].count == _params()["active"]
    assert min(rows) > 0


# -- 4. the control and the planted fault ---------------------------------------

@pytest.mark.parametrize("kind", node_control.KINDS)
def test_correct_follows_the_timed_path(kind):
    out = node_control.run_broken(kind, _params(), seed=4000000007,
                                  seconds=0.3)
    wrong, limit = out["compared"]["wrong_answers"]
    assert limit == 0
    if kind == "sound":
        assert out["correct"] and wrong == 0 and not out["notes"]["faults"]
        return
    assert not out["correct"] and wrong > 0
    assert any("wrong answers" in f for f in out["notes"]["faults"])
    # the warm-up rounds were sound: the window's comparison saw it
    assert not any("warm-up" in f for f in out["notes"]["faults"])
    if kind == "lost_part":
        # one reply a dispatch
        assert wrong == out["counters"]["resolver.dispatches"]


def test_a_program_without_the_entry_point_ends_by_itself(monkeypatch,
                                                          capsys):
    from accord_tpu.local.stores import CommandStores
    monkeypatch.delattr(CommandStores, "map_reduce_async")
    with pytest.raises(SystemExit) as e:
        node_runner.run(_params(), seed=1, seconds=0.1, trace=False,
                        meter=common.CompileMeter())
    assert e.value.code == 4
    assert "map_reduce_async" in capsys.readouterr().err


# -- 5. the cell's rehearsal, as the command runs it ----------------------------

def test_notes_and_compared_are_what_noise_and_the_check_read():
    out = node_runner.run(_params(), seed=7, seconds=0.3, trace=False,
                          meter=common.CompileMeter())
    notes, counters, compared = out["notes"], out["counters"], out["compared"]
    rounds = notes["rounds"]
    assert out["correct"], notes["faults"]
    assert rounds == counters["rounds"] > 1
    for key in ("round_s", "round_cpu_s", "round_wait_s",
                "round_materialize_s", "round_fanout_s", "round_reduce_s"):
        assert len(notes[key]) == rounds and all(x >= 0 for x in notes[key])
    assert sum(notes["round_s"]) == pytest.approx(counters["window_s"])
    assert sum(notes["round_fanout_s"]) == \
        pytest.approx(counters["node.fanout_s"])
    assert sum(notes["round_reduce_s"]) == \
        pytest.approx(counters["node.reduce_s"])
    assert len(notes["collector"]["collections"]) == 3
    assert notes["warm_settled"] and notes["warm_compiles"][-1] == 0
    assert notes["pad_store_tiers"] == 8
    assert notes["arenas"]["cap"] == [_params()["cap"]] * 8
    assert out["attempted"] == rounds * _params()["subjects"] == \
        counters["node.requests"]
    assert set(compared) == {
        "wrong_answers", "failed_replies", "deps_checked_min",
        "gated_counters", "device_work_min", *node_runner.HOST_PATH_COUNTERS,
        "compile_requests_in_window", "fused_dispatch_share_min",
        "store_slices_per_txn_min", "store_slices_per_txn_max"}
    for name in ("wrong_answers", "failed_replies", "gated_counters",
                 "compile_requests_in_window",
                 *node_runner.HOST_PATH_COUNTERS):
        assert compared[name] == [0, 0], name
    assert compared["fused_dispatch_share_min"] == [1.0, 0.9]
    lo, hi = _params()["slices_per_txn"]
    assert lo <= compared["store_slices_per_txn_min"][0] <= hi
    assert compared["store_slices_per_txn_max"][1] == hi


def test_the_cells_rehearsal_ends_correct(capsys):
    from benchmark import run
    assert run.main(["--workload", CELL, "--rehearsal", "--seed", "4242424243",
                     "--seconds", "0.5"]) == 0
    counters_line, result_line = capsys.readouterr().out.splitlines()[-2:]
    line, counters = json.loads(result_line), json.loads(counters_line)
    assert line["correct"] and line["failed"] == 0 and line["rehearsal"]
    assert set(line["metrics"]) == {"deps_resolved_per_s", "setup_s"}
    assert line["attempted"] == counters["counters"]["node.requests"] > 0
    assert line["compared"]["compile_requests_in_window"] == \
        {"value": 0, "limit": 0}
    assert counters["counters"]["compile_requests_in_window"] == 0
    assert counters["counters"]["resolver.fused_dispatches"] == \
        counters["counters"]["resolver.dispatches"] > 0


def test_the_cells_file_holds_the_deployment():
    """The sizes the issue names, none cut, and the key domain that gives
    each of the eight stores its share."""
    cell = common.load_json(common.HERE / "workloads" / f"{CELL}.json")
    config = common.load_json(
        common.HERE / "configs" / f"{cell['config']}.json")
    assert (config["active"], config["keys"], config["stores"],
            config["keys_per_txn"], cell["subjects"]) == \
        (100_000, 10_000, 8, 4, 4096)
    assert config["reduced"] == {} and cell["chips"] == 1
    assert "CommandStores.java" in config["source"] \
        and "Cluster.java:417" in config["source"]
    assert len(config["source"]) <= 200 and len(cell["why"]) <= 200
    from accord_tpu.local.stores import even_int_splitter
    from accord_tpu.primitives.keyspace import Range
    pieces = even_int_splitter(Range(0, config["keys"]), config["stores"])
    assert [(r.start, r.end) for r in pieces] == \
        [(i * 1250, (i + 1) * 1250) for i in range(8)]


# -- 6. the handlers reply what they replied ------------------------------------

def _cluster(stores, seed=3):
    from accord_tpu.sim.cluster import Cluster, ClusterConfig
    cluster = Cluster(seed, ClusterConfig(
        num_nodes=1, rf=1, stores_per_node=stores, num_shards=1,
        key_domain=80, progress=False))
    return cluster, cluster.nodes[1]


def _write(node, raw):
    from accord_tpu.primitives.keyspace import Keys
    from accord_tpu.primitives.timestamp import Domain, TxnId, TxnKind
    from accord_tpu.primitives.txn import Txn
    from accord_tpu.sim.list_store import ListQuery, ListRead, ListUpdate
    ts = node.unique_now()
    txn_id = TxnId.create(ts.epoch, ts.hlc, ts.node, TxnKind.WRITE,
                          Domain.KEY)
    keys = Keys(raw)
    txn = Txn(TxnKind.WRITE, keys, read=ListRead(keys),
              update=ListUpdate(keys, 1), query=ListQuery())
    return txn_id, txn, node.compute_route(txn)


def _old_preaccept(msg, node):
    """`PreAccept.process` as it was before PR 35 (36492f0), replying to the
    caller: the oracle."""
    from accord_tpu.local.commands import AcceptOutcome
    from accord_tpu.messages.preaccept import PreAcceptNack, PreAcceptOk
    from accord_tpu.primitives.timestamp import Timestamp
    from accord_tpu.utils.async_ import all_of
    stores = node.command_stores.intersecting(msg.txn.keys)
    if not stores:
        return [None]
    parts = [s.submit_preaccept(
        msg.txn_id, msg.txn.slice(s.ranges, include_query=False), msg.route)
        for s in stores]
    replies = []

    def finish(results):
        reply = None
        for outcome, witnessed, deps in results:
            if outcome in (AcceptOutcome.REJECTED_BALLOT,
                           AcceptOutcome.TRUNCATED):
                reply = PreAcceptNack(msg.txn_id)
                break
            part = PreAcceptOk(msg.txn_id, witnessed, deps)
            if reply is None:
                reply = part
            else:
                reply = PreAcceptOk(
                    msg.txn_id,
                    Timestamp.merge_witnessed(reply.witnessed_at,
                                              part.witnessed_at),
                    reply.deps.union(part.deps))
        replies.append(reply)

    all_of(parts).on_success(finish)
    return replies


def _old_accept(msg, node):
    """`Accept.process` as it was before PR 35 (36492f0): the oracle."""
    from accord_tpu.local.commands import AcceptOutcome
    from accord_tpu.messages.accept import (AcceptNack, AcceptOk,
                                            AcceptRedundant)
    from accord_tpu.utils.async_ import all_of, success
    stores = node.command_stores.intersecting(msg.keys)
    if not stores:
        return [None]

    def one_store(store):
        outcome = store.accept_op(msg.txn_id, msg.ballot, msg.route,
                                  store.owned(msg.keys), msg.execute_at,
                                  msg.deps)
        if outcome == AcceptOutcome.REJECTED_BALLOT:
            return success(AcceptNack(msg.txn_id,
                                      store.command(msg.txn_id).promised))
        if outcome == AcceptOutcome.TRUNCATED:
            return success(AcceptNack(msg.txn_id, None))
        if outcome == AcceptOutcome.REDUNDANT:
            return success(AcceptRedundant(
                msg.txn_id, store.command(msg.txn_id).execute_at))
        return store.calculate_deps_async(
            msg.txn_id, store.owned(msg.keys), msg.execute_at) \
            .map(lambda deps: AcceptOk(msg.txn_id, deps))

    replies = []

    def finish(parts):
        reply = None
        for part in parts:
            if isinstance(part, (AcceptNack, AcceptRedundant)):
                reply = part
                break
            reply = part if reply is None \
                else AcceptOk(msg.txn_id, reply.deps.union(part.deps))
        replies.append(reply)

    all_of([one_store(s) for s in stores]).on_success(finish)
    return replies


def _fields(reply):
    return None if reply is None else \
        (type(reply).__name__,
         {name: getattr(reply, name) for name in type(reply).__slots__})


def _processed(msg, cluster, node):
    replies = []
    node.reply = lambda to, ctx, reply: replies.append((to, ctx, reply))
    msg.process(node, 1, "ctx")
    cluster.queue.drain(max_events=100_000)
    return replies


# keys 0-9 lie in the first of eight stores; 5, 25, 45 and 75 in four
HISTORY = ([5, 25], [5, 45, 75], [25, 75], [3, 5])
SUBJECTS = {"one store": [3, 5], "several stores": [5, 25, 45, 75],
            "no store": [200]}


def _nack_second_store(node, monkeypatch, handler):
    """The second store a request meets refuses it."""
    from accord_tpu.local.commands import AcceptOutcome
    from accord_tpu.utils.async_ import success
    store = node.command_stores.all()[2]
    if handler == "preaccept":
        monkeypatch.setattr(
            store, "submit_preaccept", lambda *a, **k: success(
                (AcceptOutcome.REJECTED_BALLOT, None, None)))
    else:
        monkeypatch.setattr(store, "accept_op",
                            lambda *a, **k: AcceptOutcome.TRUNCATED)


@pytest.mark.parametrize("nack", [False, True], ids=["ok", "nack"])
@pytest.mark.parametrize("subject", sorted(SUBJECTS))
@pytest.mark.parametrize("stores", [1, 8])
def test_preaccept_replies_what_its_own_loop_replied(stores, subject, nack,
                                                     monkeypatch):
    from accord_tpu.messages.preaccept import PreAccept
    (ca, a), (cb, b) = _cluster(stores), _cluster(stores)
    for cluster, node in ((ca, a), (cb, b)):
        for raw in HISTORY:
            _processed(PreAccept(*_write(node, raw)), cluster, node)
        if nack and stores == 8:
            _nack_second_store(node, monkeypatch, "preaccept")
    old = _old_preaccept(PreAccept(*_write(a, SUBJECTS[subject])), a)
    new = _processed(PreAccept(*_write(b, SUBJECTS[subject])), cb, b)
    assert len(old) == len(new) == 1
    assert new[0][:2] == (1, "ctx")
    assert _fields(new[0][2]) == _fields(old[0])
    if subject == "no store":
        assert new[0][2] is None
    elif nack and stores == 8 and subject == "several stores":
        assert type(new[0][2]).__name__ == "PreAcceptNack"
    else:
        assert type(new[0][2]).__name__ == "PreAcceptOk"
        assert not new[0][2].deps.is_empty()


@pytest.mark.parametrize("nack", [False, True], ids=["ok", "nack"])
@pytest.mark.parametrize("subject", sorted(SUBJECTS))
@pytest.mark.parametrize("stores", [1, 8])
def test_accept_replies_what_its_own_loop_replied(stores, subject, nack,
                                                  monkeypatch):
    from accord_tpu.messages.accept import Accept
    from accord_tpu.messages.preaccept import PreAccept
    from accord_tpu.primitives.timestamp import Ballot
    (ca, a), (cb, b) = _cluster(stores), _cluster(stores)
    msgs = []
    for cluster, node in ((ca, a), (cb, b)):
        for raw in HISTORY:
            _processed(PreAccept(*_write(node, raw)), cluster, node)
        txn_id, txn, route = _write(node, SUBJECTS[subject])
        _processed(PreAccept(txn_id, txn, route), cluster, node)
        if nack and stores == 8:
            _nack_second_store(node, monkeypatch, "accept")
        msgs.append(Accept(txn_id, Ballot.ZERO, route, txn.keys,
                           node.unique_now()))
    assert msgs[0].txn_id == msgs[1].txn_id
    assert msgs[0].execute_at == msgs[1].execute_at
    old = _old_accept(msgs[0], a)
    new = _processed(msgs[1], cb, b)
    assert len(old) == len(new) == 1
    assert _fields(new[0][2]) == _fields(old[0])
    if subject == "no store":
        assert new[0][2] is None
    elif nack and stores == 8 and subject == "several stores":
        assert _fields(new[0][2]) == \
            ("AcceptNack", {"txn_id": msgs[1].txn_id, "promised": None})
    else:
        assert type(new[0][2]).__name__ == "AcceptOk"
        assert not new[0][2].deps.is_empty()
