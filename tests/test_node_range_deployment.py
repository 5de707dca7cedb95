"""The node-ranges cell (`preaccept-8stores-ranges-100k.range-20`,
runners/noderanges.py): the eight-store node of the node cell with a fifth of
its residents and of every round range-domain, at the cell's rehearsal size
on the CPU.

Load-bearing properties:
  1. the runner's reference -- flat arrays, knowing nothing of stores --
     gives what the range runner's accepted reference gives, in both
     domains, for reads and writes and below any bound;
  2. the runner's merged replies equal that reference in both domains, the
     range call of every dispatch ran the fused cross-store program, and
     the fan-out counted its Ranges requests;
  3. a range transaction across a store boundary, answered by two stores,
     merges to the unsliced reference, and residents cut by the same
     boundary come back as one piece;
  4. `correct` follows the timed path -- the control (a reply a dispatch
     loses one store's part) and the planted fault (two store slices
     swapped) read false, the sound run true; a program without the new
     counters ends by itself with exit code 4;
  5. the eight range arenas present one shape: after `warmup()` at the
     rehearsal's node shapes two rounds request no compile, and they do
     where one store's range arena is left at another capacity;
  6. the cell's rehearsal, as the command runs it, ends `correct`.
"""
from __future__ import annotations

import json
import random

import numpy as np
import pytest

from benchmark import common, node_range_control
from benchmark.runners import noderanges, ranges

CELL = "preaccept-8stores-ranges-100k.range-20"


def _params(**over):
    cell = common.load_json(common.HERE / "workloads" / f"{CELL}.json")
    config = common.load_json(
        common.HERE / "configs" / f"{cell['config']}.json")
    return {**config, **cell, **cell["rehearsal"], **over}


@pytest.fixture(scope="module")
def deployment():
    return noderanges.Deployment(_params(), seed=4000000009)


def _ask(dep, txn_id, seekables, bound):
    reply = []
    dep.ask(txn_id, seekables, bound).add_callback(
        lambda value, failure: reply.append((value, failure)))
    dep.cluster.queue.drain(max_events=1_000_000)
    (value, failure), = reply
    assert failure is None
    return value


# -- 1. the reference against the range runner's ------------------------------

@pytest.mark.parametrize("seed", [5, 4000000005])
def test_the_reference_is_the_range_runners_reference(seed):
    """Both references fed the same registrations (txn ids as ints in
    registration order); every subject compared in the runner's terms."""
    rnd = random.Random(seed)
    keys = 60

    def pieces():
        out = []
        for _ in range(rnd.choice([1, 2])):
            w = 1 + rnd.randrange(10)
            s = rnd.randrange(keys - w + 1)
            out.append((s, s + w))
        return ranges.merged(out)

    old, new = ranges.Reference(), noderanges.Reference()
    for t in range(400):
        kind = rnd.choice("RW")
        if rnd.random() < 0.3:
            what = pieces()
            old.add_range_txn(t, kind, what)
            new.add_range_txn(t, kind, what)
        else:
            what = [rnd.randrange(keys) for _ in range(4)]
            old.add_key_txn(t, kind, what)
            new.add_key_txn(t, kind, what)
    old.freeze()
    new.freeze(keys)
    for _ in range(300):
        kind, bound = rnd.choice("RW"), rnd.randrange(420)
        if rnd.random() < 0.5:
            what = [rnd.randrange(keys) for _ in range(4)]
            want = sorted(k * new.stride + t
                          for k, t in old.expected("key", kind, what, bound))
            assert new.expected_key(kind, what, bound).tolist() == want
        else:
            what = pieces()
            rows = np.array([(t, s, s + 1 if e is None else e) for s, e, t
                             in old.expected("range", kind, what, bound)],
                            np.int64).reshape(-1, 3)
            want = noderanges.segments(*rows.T)
            assert noderanges.same(new.expected_range(kind, what, bound),
                                   want)


def test_two_overlapping_pieces_of_one_txn_are_a_double_count():
    a = np.array
    assert noderanges.segments(a([7, 7]), a([0, 5]), a([5, 9]))[2].tolist() \
        == [9]
    assert noderanges.segments(a([7, 7]), a([0, 4]), a([5, 9])) is None
    assert not noderanges.same(None, (a([7]), a([0]), a([9])))


# -- 2. the merged replies against the reference -------------------------------

def test_every_merged_reply_equals_the_reference(deployment):
    p = _params()
    before = deployment.counters()
    r = deployment.round(p["subjects"], p["range_subjects"])
    counters = common.delta(deployment.counters(), before)
    assert r["failed"] == 0
    assert r["wrong"] == {"key": 0, "range": 0}
    assert r["subjects"] == {"key": p["subjects"] - p["range_subjects"],
                             "range": p["range_subjects"]}
    assert all(r["deps"].values())
    assert counters["node.requests"] == p["subjects"]
    assert counters["node.range_requests"] == p["range_subjects"]
    assert counters["node.range_store_slices"] == \
        counters["resolver.range_subjects"] > p["range_subjects"]
    assert counters["resolver.fused_range_dispatches"] == \
        counters["resolver.range_dispatches"] == \
        counters["resolver.dispatches"] > 0
    assert counters["resolver.fused_range_groups"] == \
        p["stores"] * counters["resolver.fused_range_dispatches"]
    for name in noderanges.HOST_PATH_COUNTERS:
        assert counters.get(name, 0) == 0, name


# -- 3. across a store boundary --------------------------------------------------

@pytest.mark.parametrize("kind", ["R", "W"])
def test_a_range_across_a_store_boundary_merges_to_the_unsliced_reference(
        deployment, kind):
    from accord_tpu.primitives.keyspace import Range, Ranges
    from accord_tpu.primitives.timestamp import Domain
    dep = deployment
    edge = dep.stores.all()[1].ranges[0].start
    pieces = [(edge - 4, edge + 4)]
    seekables = Ranges([Range(s, e) for s, e in pieces])
    assert dep.stores.intersecting(seekables) == list(dep.stores.all()[:2])
    txn_id, bound = dep._txn_id(kind, Domain.RANGE)
    before = dep.counters()
    reply = _ask(dep, txn_id, seekables, bound)
    counters = common.delta(dep.counters(), before)
    assert counters["node.range_store_slices"] == 2
    want = dep.reference.expected_range(kind, pieces, bound)
    assert noderanges.same(noderanges.range_answer(dep.reference, reply), want)
    # a resident range txn over the edge comes back from each store as its
    # own piece, and the two merge into the one the reference holds
    pieces_of = {}
    for r, ids in reply.range_deps.items():
        if isinstance(r.end, int):
            for t in ids:
                pieces_of.setdefault(t, []).append((r.start, r.end))
    cut = [t for t, ps in pieces_of.items()
           if any(e == edge for _, e in ps) and any(s == edge for s, _ in ps)]
    assert cut, "no resident range was cut by the store boundary"
    for t in cut:
        place = dep.reference.place_of[t]
        at = np.nonzero(want[0] == place)[0]
        assert len(at) == 1 and want[1][at[0]] < edge < want[2][at[0]]


# -- 4. correct follows the timed path --------------------------------------------

@pytest.mark.parametrize("kind", node_range_control.KINDS)
def test_correct_follows_the_timed_path(kind):
    out = node_range_control.run_broken(kind, _params(), seed=4000000007,
                                        seconds=0.3)
    wrong, limit = out["compared"]["wrong_answers"]
    assert limit == 0
    if kind == "sound":
        assert out["correct"] and wrong == 0 and not out["notes"]["faults"]
        return
    assert not out["correct"] and wrong > 0
    assert any("wrong answers" in f for f in out["notes"]["faults"])
    assert not any("warm-up" in f for f in out["notes"]["faults"])
    if kind == "lost_part":
        # one reply a dispatch
        assert wrong == out["counters"]["resolver.dispatches"]


@pytest.mark.parametrize("missing", ["resolver", "node"])
def test_a_program_without_the_new_counters_ends_by_itself(
        monkeypatch, capsys, missing):
    if missing == "resolver":
        from accord_tpu.ops.resolver import BatchDepsResolver
        monkeypatch.delattr(BatchDepsResolver, "fused_range_dispatches")
        name = "fused_range_dispatches"
    else:
        from accord_tpu.obs import metrics
        monkeypatch.delitem(metrics.GLOSSARY, "node.range_requests")
        name = "node.range_requests"
    with pytest.raises(SystemExit) as e:
        noderanges.run(_params(), seed=1, seconds=0.1, trace=False,
                       meter=common.CompileMeter())
    assert e.value.code == 4
    assert name in capsys.readouterr().err


# -- 5. one range capacity, no compile ----------------------------------------

def _one_store_left_at_another_range_capacity(monkeypatch):
    """The first store's range arena made at twice the resolver's range
    capacity, the others at it: the mix the deployment's one setting
    rules out."""
    from accord_tpu.ops.resolver import BatchDepsResolver
    arena = BatchDepsResolver._arena

    def first_arena_wider(self, store):
        if self._arenas:
            return arena(self, store)
        cap, self.range_cap = self.range_cap, 2 * self.range_cap
        try:
            return arena(self, store)
        finally:
            self.range_cap = cap

    monkeypatch.setattr(BatchDepsResolver, "_arena", first_arena_wider)


@pytest.mark.parametrize("layout", ["one_capacity", "one_store_apart"])
def test_two_rounds_after_warmup_request_no_compile(monkeypatch, layout):
    from accord_tpu.ops.kernels import jit_cache_sizes
    if layout == "one_store_apart":
        _one_store_left_at_another_range_capacity(monkeypatch)
    p = _params()
    dep = noderanges.Deployment(p, seed=4000000011)
    caps = [a.ranges.cap for a in dep.arenas()]
    assert (len(set(caps)) == 1) == (layout == "one_capacity"), caps
    # the arenas' first upload, then the program's warmup at the node's
    # shapes, as set-up does them
    for a in dep.arenas():
        a.device_arrays()
        a.kid_arrays()
        a.ranges.device_arrays()
    noderanges.warm_kernels(p)
    meter = common.CompileMeter()
    sizes = jit_cache_sizes()
    for _ in range(2):
        r = dep.round(p["subjects"], p["range_subjects"])
        assert r["wrong"] == {"key": 0, "range": 0} and r["failed"] == 0
    grown = {k: v - sizes[k] for k, v in jit_cache_sizes().items()
             if v != sizes[k]}
    if layout == "one_capacity":
        assert meter.requests == 0 and not grown, grown
    else:
        assert grown.get("fused_range_deps_resolve", 0) > 0, grown


# -- 6. the cell's rehearsal, as the command runs it -----------------------------

def test_the_cells_rehearsal_ends_correct(capsys):
    from benchmark import run
    assert run.main(["--workload", CELL, "--rehearsal", "--seed", "4242424243",
                     "--seconds", "0.5"]) == 0
    counters_line, result_line = capsys.readouterr().out.splitlines()[-2:]
    line, out = json.loads(result_line), json.loads(counters_line)
    counters, notes = out["counters"], out["notes"]
    assert line["correct"] and line["failed"] == 0 and line["rehearsal"]
    assert set(line["metrics"]) == {"deps_resolved_per_s", "setup_s"}
    assert line["attempted"] == counters["node.requests"] > 0
    for name in ("compile_requests_in_window", "wrong_answers",
                 "wrong_range_answers", *noderanges.HOST_PATH_COUNTERS):
        assert line["compared"][name] == {"value": 0, "limit": 0}, name
    assert line["compared"]["fused_range_dispatch_share_min"] == \
        {"value": 1.0, "limit": 0.9}
    assert notes["arenas"]["range_cap"] == [_params()["range_cap"]] * 8
    assert notes["warm_settled"] and notes["warm_compiles"][-1] == 0
    rounds = notes["rounds"]
    for key in ("round_s", "round_reduce_s", "round_range_decode_s"):
        assert len(notes[key]) == rounds
    assert sum(notes["round_s"]) == pytest.approx(counters["window_s"])


def test_the_cells_file_holds_the_deployment():
    """The deployment's sizes, none cut, and exact range counts."""
    cell = common.load_json(common.HERE / "workloads" / f"{CELL}.json")
    config = common.load_json(
        common.HERE / "configs" / f"{cell['config']}.json")
    assert (config["active"], config["range_active"], config["keys"],
            config["stores"], config["keys_per_txn"], cell["subjects"],
            cell["range_subjects"]) == \
        (100_000, 20_000, 10_000, 8, 4, 4096, 819)
    assert (config["cap"], config["kid_cap"], config["range_cap"],
            config["max_dispatch"]) == (65536, 2048, 8192, 1024)
    assert config["reduced"] == {} and cell["chips"] == 1
    assert len(config["source"]) <= 200 and len(cell["why"]) <= 200
