"""The range deployment (`benchmark/configs/preaccept-ranges-10k.json`) at its
cell's rehearsal size, on the CPU: the runner's plain reference against the
program's host scan and against hand-made cases, the resolver's async
pipeline against the reference over range shares and subject kinds, the
planted faults reading `correct` false, and the cell's `--rehearsal`.
"""
from __future__ import annotations

import json

import pytest

from benchmark import common
from benchmark.runners import ranges

CELL = "preaccept-ranges-10k.range-20"
SEEDS = (2147483659, 7)


def _params():
    cell = common.load_json(common.HERE / "workloads" / f"{CELL}.json")
    config = common.load_json(
        common.HERE / "configs" / f"{cell['config']}.json")
    return {**config, **cell, **cell["rehearsal"]}


@pytest.fixture(scope="module", params=SEEDS)
def arena(request):
    return ranges.Arena(_params(), request.param)


# -- the reference against the program's host scan ---------------------------

def test_reference_agrees_with_the_host_scan(arena):
    """Two independent implementations agree on every subject."""
    store, checked = arena.store, {"key": 0, "range": 0}
    subjects = arena.draw(60) + [
        (t, store.owned(s), ts, spec) for t, s, ts, spec in
        [arena.fresh_key_txn("R") for _ in range(10)]
        + [arena.fresh_range_txn(kind) for kind in "RW" * 10]]
    for t, owned, bound, (domain, kind, what) in subjects:
        host = store.host_calculate_deps(t, owned, bound)
        want = arena.reference.expected(domain, kind, what, bound)
        assert ranges.answer_set(domain, host) == want, (t, what)
        checked[domain] += len(want)
    assert all(checked.values()), checked


# -- the resolver through the async pipeline against the reference -----------

@pytest.mark.parametrize("kind", ["R", "W"])
@pytest.mark.parametrize("share", [0.0, 0.2, 1.0])
def test_pipeline_answers_equal_the_reference(arena, share, kind):
    """96 subjects of one kind, each range-domain with probability `share`,
    enqueued at once and drained."""
    rng, store = arena._rng, arena.store
    subjects = [(t, store.owned(s), ts, spec) for t, s, ts, spec in (
        arena.fresh_range_txn(kind) if rng.decide(share)
        else arena.fresh_key_txn(kind) for _ in range(96))]
    before = arena.counters()
    answers, failures, _, _ = arena.resolve(subjects)
    got = arena.check(subjects, answers)
    moved = common.delta(arena.counters(), before)
    assert not failures and got["wrong"] == {"key": 0, "range": 0}
    n_range = got["subjects"]["range"]
    assert got["subjects"]["key"] + n_range == 96
    assert (n_range == 0) == (share == 0.0) and (n_range == 96) == (share == 1.0)
    assert all(got["deps"][d] > 0 for d in got["deps"] if got["subjects"][d])
    # the range path's counters move for range subjects, and only for them
    assert moved.get("resolver.range_subjects", 0) == n_range
    assert moved.get("resolver.range_subject_device_decodes", 0) == n_range
    assert moved.get("resolver.range_deps", 0) == got["range_range_deps"]
    assert (moved.get("resolver.range_intervals", 0) > 0) == (n_range > 0)
    for name in ranges.HOST_PATH_COUNTERS:
        assert moved.get(name, 0) == 0, name


# -- hand-made cases for the reference ----------------------------------------

def _ids(n, kind="W", domain="range", start=100):
    from accord_tpu.primitives.timestamp import Domain, TxnId, TxnKind
    return [TxnId.create(1, start + i, 1,
                         {"R": TxnKind.READ, "W": TxnKind.WRITE}[kind],
                         {"key": Domain.KEY, "range": Domain.RANGE}[domain])
            for i in range(n)]


def _bound(hlc=10_000):
    from accord_tpu.primitives.timestamp import Timestamp
    return Timestamp(1, hlc, 0, 1)


def _reference(*txns):
    """txns: (txn id, kind, keys or pieces), ascending."""
    ref = ranges.Reference()
    for txn_id, kind, what in txns:
        if what and isinstance(what[0], tuple):
            ref.add_range_txn(txn_id, kind, what)
        else:
            ref.add_key_txn(txn_id, kind, what)
    ref.freeze()
    return ref


def test_a_read_takes_no_read():
    (r,), (w,) = _ids(1, "R"), _ids(1, "W", start=200)
    ref = _reference((r, "R", [(0, 10)]), (w, "W", [(0, 10)]))
    assert ref.expected("range", "R", [(5, 8)], _bound()) == {(5, 8, w)}
    assert ref.expected("range", "W", [(5, 8)], _bound()) == \
        {(5, 8, r), (5, 8, w)}
    assert ref.expected("key", "R", [5], _bound()) == {(5, w)}


def test_a_range_that_ends_where_another_starts_is_no_dependency():
    (w,) = _ids(1)
    ref = _reference((w, "W", [(0, 10)]))
    assert ref.expected("range", "W", [(10, 20)], _bound()) == set()
    assert ref.expected("range", "W", [(9, 20)], _bound()) == {(9, 10, w)}


def test_a_key_on_a_ranges_start_is_inside_and_on_its_end_outside():
    (w,) = _ids(1)
    ref = _reference((w, "W", [(5, 10)]))
    assert ref.expected("key", "W", [5, 10, 4], _bound()) == {(5, w)}
    assert ref.expected("key", "W", [9], _bound()) == {(9, w)}


def test_a_two_range_txn_hit_through_both_ranges_is_one_dependency():
    (w,) = _ids(1)
    ref = _reference((w, "W", [(0, 10), (20, 30)]))
    got = ref.expected("range", "W", [(5, 25)], _bound())
    assert got == {(5, 10, w), (20, 25, w)}
    assert len({t for _, _, t in got}) == 1
    # and a two-piece subject through one range of the txn
    assert ref.expected("range", "W", [(2, 4), (6, 8)], _bound()) == \
        {(2, 4, w), (6, 8, w)}


def test_key_txns_inside_a_range_subject_are_points():
    k1, k2 = _ids(2, domain="key")
    ref = _reference((k1, "W", [3, 7]), (k2, "W", [7, 12]))
    assert ref.expected("range", "R", [(5, 12)], _bound()) == \
        {(7, None, k1), (7, None, k2)}
    assert ref.expected("key", "W", [7, 8], _bound()) == {(7, k1), (7, k2)}


def test_only_what_is_below_the_bound():
    a, b = _ids(2)
    (k,) = _ids(1, domain="key", start=300)
    ref = _reference((a, "W", [(0, 10)]), (b, "W", [(0, 10)]),
                     (k, "W", [4]))
    assert ref.expected("range", "W", [(0, 5)], _bound(101)) == {(0, 5, a)}
    assert ref.expected("key", "W", [4], _bound(101)) == {(4, a)}
    assert ref.expected("key", "W", [4], _bound()) == \
        {(4, a), (4, b), (4, k)}


def test_pieces_that_touch_or_overlap_are_one_range():
    assert ranges.merged([(5, 9), (0, 5), (20, 30), (25, 40)]) == \
        [(0, 9), (20, 40)]
    (w,) = _ids(1)
    ref = _reference((w, "W", [(0, 5), (5, 9)]))
    assert ref.expected("range", "W", [(3, 7)], _bound()) == {(3, 7, w)}


# -- the planted control -------------------------------------------------------

KINDS = ("dropped", "swapped", "sound")


def alter(kind, results):
    """`dropped`: one answer of the dispatch loses one range-domain
    dependency, as a range arena that lags a registration would answer.
    `swapped`: two answers change places as they leave the decode."""
    from accord_tpu.primitives.timestamp import Domain
    if kind == "dropped":
        for i, deps in enumerate(results):
            victims = [t for t in deps.all_txn_ids()
                       if t.domain == Domain.RANGE]
            if victims:
                results[i] = deps.without(lambda t: t == victims[-1])
                break
    elif kind == "swapped" and len(results) > 1:
        results[0], results[-1] = results[-1], results[0]
    return results


def broken_arena(kind):
    """`ranges.Arena` whose resolver's decode is altered inside the window
    (a round that is given the collector's watch), not in the warm-up."""

    class Broken(ranges.Arena):
        def __init__(self, p, seed):
            super().__init__(p, seed)
            self.armed = False
            decode = self.resolver._decode_dispatch
            self.resolver._decode_dispatch = lambda call: (
                alter(kind, decode(call)) if self.armed else decode(call))

        def round(self, n, timed=None, watch=None):
            self.armed = watch is not None
            return super().round(n, timed=timed, watch=watch)

    return Broken


def run_broken(kind, params, seed, seconds):
    """One run of the range runner with `kind` planted; what it returned."""
    sound = ranges.Arena
    ranges.Arena = broken_arena(kind)
    try:
        return ranges.run(params, seed=seed, seconds=seconds, trace=False,
                          meter=common.CompileMeter())
    finally:
        ranges.Arena = sound


@pytest.mark.parametrize("kind", KINDS)
def test_correct_follows_the_timed_path(kind):
    out = run_broken(kind, _params(), seed=4000000007, seconds=0.3)
    wrong, limit = out["compared"]["wrong_answers"]
    by_domain = [out["compared"][f"wrong_{d}_answers"][0]
                 for d in ("key", "range")]
    assert limit == 0 and sum(by_domain) == wrong
    if kind == "sound":
        assert out["correct"] and wrong == 0 and not out["notes"]["faults"]
        for value, lim in out["compared"].values():
            assert value >= lim
        return
    assert not out["correct"] and wrong > 0
    assert any("wrong answers" in f for f in out["notes"]["faults"])
    # the warm-up rounds were sound: the window's comparison saw it
    assert not any("warm-up" in f for f in out["notes"]["faults"])


def test_notes_are_what_noise_reads():
    out = run_broken("sound", _params(), seed=7, seconds=0.3)
    notes, counters = out["notes"], out["counters"]
    rounds = notes["rounds"]
    assert rounds == counters["rounds"] > 1 and len(notes["warm_compiles"]) >= 2
    for key in ("round_s", "round_cpu_s", "round_wait_s",
                "round_materialize_s"):
        assert len(notes[key]) == rounds and all(x >= 0 for x in notes[key])
    assert sum(notes["round_s"]) == pytest.approx(counters["window_s"])
    assert len(notes["collector"]["collections"]) == 3
    assert notes["range_range_deps_reference"] == counters["resolver.range_deps"]
    assert notes["compile_requests_in_window"] == \
        [counters["compile_requests_in_window"], 0]


# -- the cell's rehearsal, as the command runs it ------------------------------

def test_the_cells_rehearsal_ends_correct(capsys):
    from benchmark import run
    assert run.main(["--workload", CELL, "--rehearsal", "--seed", "4242424243",
                     "--seconds", "0.5"]) == 0
    counters_line, result_line = capsys.readouterr().out.splitlines()[-2:]
    line, counters = json.loads(result_line), json.loads(counters_line)
    assert line["correct"] and line["failed"] == 0 and line["rehearsal"]
    assert set(line["metrics"]) == {"deps_resolved_per_s", "setup_s"}
    compared = line["compared"]
    assert compared["resolver.range_subject_device_decodes"]["value"] > 0
    for name in ranges.HOST_PATH_COUNTERS:
        assert compared[name] == {"value": 0, "limit": 0}
    assert compared["gated_counters"] == {"value": 0, "limit": 0}
    assert counters["counters"]["resolver.range_subjects"] > 0
