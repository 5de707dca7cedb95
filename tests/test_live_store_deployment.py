"""The live cell (`preaccept-batch-100k.resolve-4096`, runners/live.py): a
CommandStore that registers what it answers and truncates what is durable.

Load-bearing properties:
  1. the plain reference -- on hand-made histories it gives the store's
     rule: resident ids below the subject, less what a committed write
     covers, nothing below the floor, one edge to the floor's sync point;
  2. the program against that reference, a round at a time, from an empty
     store at cap 128 -> 256 and rounds of 32: over a growth, a kid-table
     doubling, two and more compactions and a wave in every round every
     answer is exact, the arena's live ids are the reference's residents,
     the lifecycle counters count what happened and nothing comes from a
     host scan, the legacy decode or a finalize fallback;
  3. `correct` follows the timed path -- the control (an answer a dispatch
     keeps a truncated txn) and the planted fault (two answers swapped) read
     false at the rehearsal size, the sound run true;
  4. the cell's rehearsal, as the command runs it, ends `correct`, and its
     notes are what `noise.py` reads;
  5. set-up -- whole rounds until the arena has grown on the device, the
     early rounds filled without a resolve and truncated by one wave, the
     last `resident_rounds` + 2 whole; no finalize fallback on the way, and
     the reference's own seconds are left out of `setup_s`.
"""
from __future__ import annotations

import json
import time

import pytest

from benchmark import common, live_control
from benchmark.runners import live

CELL = "preaccept-batch-100k.resolve-4096"


def _params(**over):
    cell = common.load_json(common.HERE / "workloads" / f"{CELL}.json")
    config = common.load_json(
        common.HERE / "configs" / f"{cell['config']}.json")
    return {**config, **cell, **cell["rehearsal"], **over}


# the differential runs' shape: cap 128 -> 256, rounds of 32, a compaction
# every 4th round (the rehearsal's own is longer, so that set-up has a fill)
SMALL = {"resident_rounds": 4, "steady_cap": 256}


# -- 1. the reference on hand-made histories ----------------------------------

Id = int  # the reference orders ids and asks nothing else of them


def test_reference_answers_resident_ids_below_the_subject():
    ref = live.Reference()
    for t, keys in ((1, [5, 6]), (2, [5]), (3, [6, 7]), (4, [5, 7])):
        ref.submit(Id(t), keys)
    assert ref.expected(Id(4), [5, 7]) == {(5, 1), (5, 2), (7, 3)}
    assert ref.expected(Id(2), [5]) == {(5, 1)}
    assert ref.expected(Id(1), [5, 6]) == set()


def test_reference_elides_what_a_committed_write_covers():
    ref = live.Reference()
    for t in (1, 2, 3):
        ref.submit(Id(t), [5])
    ref.commit(Id(1), [])
    # 2 commits over 1: from now on 2 stands for 1 on key 5
    ref.commit(Id(2), [(5, Id(1))])
    ref.submit(Id(4), [5])
    assert ref.expected(Id(4), [5]) == {(5, 2), (5, 3)}
    # a subject below the cover still sees what it covers
    assert ref.expected(Id(2), [5]) == {(5, 1)}
    # an uncommitted dependency is not covered
    ref.commit(Id(4), [(5, Id(2)), (5, Id(3))])
    ref.submit(Id(5), [5])
    assert ref.expected(Id(5), [5]) == {(5, 3), (5, 4)}


def test_reference_wave_drops_what_is_below_the_floor_and_adds_its_edge():
    ref = live.Reference()
    for t in (1, 2, 4, 5):
        ref.submit(Id(t), [5] if t != 2 else [5, 6])
    ref.wave(Id(3))
    assert ref.dropped == {5: [1, 2], 6: [2]}
    assert ref.resident == {4, 5}
    ref.submit(Id(7), [5, 6, 9])
    assert ref.expected(Id(7), [5, 6, 9]) == \
        {(5, 4), (5, 5), (5, 3), (6, 3), (9, 3)}
    # a floor above the subject is no edge of it
    assert (5, 3) not in ref.expected(Id(2), [5])


# -- 2. the program against the reference -------------------------------------

@pytest.fixture(scope="module", params=[7, 4000000007])
def lived(request):
    """A deployment run 18 rounds from an empty store, with what every round
    measured and the change of every counter over each."""
    p = _params(**SMALL)
    d = live.Deployment(p, request.param)
    rounds, deltas, kid_caps = [], [], []
    for _ in range(18):
        before = d.counters()
        rounds.append(d.round())
        deltas.append(common.delta(d.counters(), before))
        kid_caps.append(d.arena().kid_cap)
        assert d.resident_difference() == 0
    return d, p, rounds, deltas, kid_caps


def test_every_answer_of_every_round_is_exact(lived):
    _, p, rounds, _, _ = lived
    for i, r in enumerate(rounds):
        assert (r["wrong"], r["failed"], r["refused"]) == (0, 0, 0), i
        assert r["deps"] > 0 or i == 0
        assert r["waved"] == (i >= p["resident_rounds"])


def test_the_arena_grew_doubled_its_kid_table_and_compacted_twice(lived):
    d, p, _, deltas, kid_caps = lived
    total = common.summed(deltas)
    arena = d.arena()
    assert total["resolver.arena_growths"] == 1
    assert arena.cap == p["steady_cap"] == 2 * p["cap"]
    assert kid_caps[0] == p["kid_cap"] * 2 == p["steady_kid_cap"] \
        == kid_caps[-1]
    assert total["resolver.arena_compactions"] >= 2
    assert arena.gen == total["resolver.arena_compactions"]
    # a compaction keeps the residents of the round's start, no tombstone
    assert total["resolver.compact_rows_kept"] == \
        total["resolver.arena_compactions"] \
        * p["resident_rounds"] * p["subjects"]
    compacting = [i for i, x in enumerate(deltas)
                  if x.get("resolver.arena_compactions")]
    assert all(b - a == (p["steady_cap"] - p["resident_rounds"]
                         * p["subjects"]) // p["subjects"]
               for a, b in zip(compacting, compacting[1:]))


def test_lifecycle_counters_count_what_happened(lived):
    _, p, rounds, deltas, _ = lived
    n = p["subjects"]
    for r, x in zip(rounds, deltas):
        dispatches = x["resolver.dispatches"]
        assert dispatches == -(-n // p["max_dispatch"])
        assert x["resolver.subjects"] == n
        # every subject is registered: its row ships whole
        assert x["resolver.arena_rows_uploaded"] >= n
        assert x["resolver.arena_upload_calls"] > 0
        assert 0.0 < x["resolver.arena_sync_s"] <= x["resolver.encode_s"]
        assert x["resolver.preaccept_s"] > 0.0
        assert x.get("resolver.compact_s", 0.0) \
            + x.get("resolver.grow_s", 0.0) <= x["resolver.preaccept_s"]
        if r["waved"]:
            assert x["resolver.truncated_txns"] == n
            # the round's calls are all in flight behind the wave's first
            # truncation: one key lane a dispatch
            assert x["resolver.fence_materializes"] == dispatches
            assert 0.0 < x["resolver.fence_s"] <= x["resolver.truncate_s"]
            assert x["resolver.truncate_s"] <= r["wave_s"]
        else:
            for name in ("resolver.truncated_txns", "resolver.truncate_s",
                         "resolver.fence_materializes", "resolver.fence_s"):
                assert not x.get(name), name


def test_no_answer_left_the_device_path(lived):
    d, _, _, deltas, _ = lived
    total = common.summed(deltas)
    for name in live.HOST_PATH_COUNTERS + common.GATED_RESOLVER:
        assert total.get(name, 0) == 0, name
    assert total["resolver.finalized_decodes"] == \
        total["resolver.dispatches"] == d.resolver.dispatches
    assert total["resolver.staged_dispatches"] == total["resolver.dispatches"]


def test_elision_and_the_floor_shape_the_answers(lived):
    """The answers hold far fewer pairs than are resident on the subject's
    keys, and once waves run every answer carries its floor's edge."""
    d, p, rounds, _, _ = lived
    ref = d.reference
    resident_pairs = sum(len(v) for v in ref.by_key.values())
    assert resident_pairs >= p["resident_rounds"] * p["subjects"]
    assert ref.floor is not None and ref.floor.kind.is_sync_point
    assert any(ref.covered.values())
    last = rounds[-1]
    # per subject: at most its keys' uncovered residents and one edge a key
    assert 0 < last["deps"] < resident_pairs


# -- 3. the control and the planted fault -------------------------------------

@pytest.mark.parametrize("kind", live_control.KINDS)
def test_correct_follows_the_timed_path(kind):
    out = live_control.run_broken(kind, _params(), seed=4000000007,
                                  seconds=0.3)
    wrong, limit = out["compared"]["wrong_answers"]
    assert limit == 0
    if kind == "sound":
        assert out["correct"] and wrong == 0 and not out["notes"]["faults"]
        for value, lim in out["compared"].values():
            assert value >= lim
        return
    assert not out["correct"] and wrong > 0
    assert any("wrong answers" in f for f in out["notes"]["faults"])
    # the set-up rounds were sound: the window's comparison saw it
    assert not any("set-up" in f for f in out["notes"]["faults"])


def test_a_program_without_the_counters_ends_by_itself(monkeypatch, capsys):
    from accord_tpu.ops.resolver import BatchDepsResolver
    monkeypatch.delattr(BatchDepsResolver, "arena_compactions")
    with pytest.raises(SystemExit) as e:
        live.run(_params(), seed=1, seconds=0.1, trace=False,
                 meter=common.CompileMeter())
    assert e.value.code == 4
    assert "resolver.arena_compactions" in capsys.readouterr().err


# -- 4. the cell's rehearsal, as the command runs it ---------------------------

def test_notes_are_what_noise_reads():
    out = live.run(_params(), seed=7, seconds=0.3, trace=False,
                   meter=common.CompileMeter())
    notes, counters = out["notes"], out["counters"]
    rounds = notes["rounds"]
    assert out["correct"], notes["faults"]
    assert rounds == counters["rounds"] > 4
    for key in ("round_s", "round_cpu_s", "round_wait_s",
                "round_materialize_s", "round_wave_s", "round_truncate_s",
                "round_fence_s", "round_preaccept_s", "round_arena_sync_s",
                "round_compact_s"):
        assert len(notes[key]) == rounds and all(x >= 0 for x in notes[key])
    assert sum(notes["round_s"]) == pytest.approx(counters["window_s"])
    assert len(notes["collector"]["collections"]) == 3
    assert notes["rounds_with_a_wave"] == notes["rounds_with_a_fence"] \
        == rounds == counters["waves"]
    # the window's phase: the 4th round compacts
    assert notes["rounds_with_a_compaction"][0] == 3
    assert notes["window_shape"].startswith("SSSC")
    assert len(notes["window_shape"]) == rounds
    # the two kinds of round apart: together they are the window's rate
    n = _params()["subjects"]
    steady = notes["window_shape"].count("S")
    assert steady * n / notes["steady_rounds_per_s"] \
        + (rounds - steady) * n / notes["compacting_rounds_per_s"] \
        == pytest.approx(counters["window_s"])
    for where, timed, seconds in notes["full_collections"]:
        assert where.split(":")[0] in ("setup", "window")
        assert timed in (True, False) and seconds > 0.0
    assert notes["compile_requests_in_window"] == \
        [counters["compile_requests_in_window"], 0]
    setup = notes["setup"]
    assert setup["arena_growths"] == 2 and setup["cap"] == 512
    assert (setup["rounds"], setup["filled"]) == (13, 1)
    assert counters["resolver.truncated_txns"] == \
        rounds * _params()["subjects"]


def test_the_cells_rehearsal_ends_correct(capsys):
    from benchmark import run
    assert run.main(["--workload", CELL, "--rehearsal", "--seed", "4242424243",
                     "--seconds", "0.5"]) == 0
    counters_line, result_line = capsys.readouterr().out.splitlines()[-2:]
    line, counters = json.loads(result_line), json.loads(counters_line)
    assert line["correct"] and line["failed"] == 0 and line["rehearsal"]
    assert set(line["metrics"]) == {"deps_resolved_per_s", "setup_s"}
    compared = line["compared"]
    for name in live.HOST_PATH_COUNTERS:
        assert compared[name] == {"value": 0, "limit": 0}
    for name in live.LIFECYCLE_COUNTERS:
        assert compared[name]["value"] >= compared[name]["limit"] == 1
    assert compared["gated_counters"] == {"value": 0, "limit": 0}
    assert compared["resident_set_difference"] == {"value": 0, "limit": 0}
    assert compared["preaccepts_not_success"] == {"value": 0, "limit": 0}
    assert counters["counters"]["compile_requests_in_window"] == 0


# -- 5. set-up: lead, fill, whole ----------------------------------------------

def _cell_params():
    cell = common.load_json(common.HERE / "workloads" / f"{CELL}.json")
    return {**common.load_json(
        common.HERE / "configs" / f"{cell['config']}.json"), **cell}


@pytest.mark.parametrize("params, plan", [
    (_cell_params, (5, 30, 26)), (_params, (5, 1, 7)),
    (lambda: _params(steady_cap=1024), (5, 17, 7)),
    (lambda: _params(**SMALL), (5, 0, 0))])
def test_setup_plan_leads_fills_and_ends_whole(params, plan):
    p = params()
    assert live.setup_plan(p) == plan
    assert sum(plan) == p["steady_cap"] // p["subjects"] \
        - p["rounds_before_fill"]


@pytest.mark.parametrize("seed", [11, 3000000019])
def test_a_filled_past_leaves_tombstones_and_exact_answers(seed):
    """Set-up's three parts by hand at a size with 17 filled rounds: the
    fill registers, commits and applies without one dispatch, one wave
    truncates all of it but the newest `lead` rounds, and every whole round
    after it is exact, with no finalize fallback while the residents build
    up again and a wave a round once they have."""
    p = _params(steady_cap=1024)
    lead, filled, whole = live.setup_plan(p)
    d = live.Deployment(p, seed)
    n = p["subjects"]
    for _ in range(lead):
        r = d.round()
        assert (r["wrong"], r["failed"], r["refused"]) == (0, 0, 0)
    assert d.resolver.arena_growths == 1   # on the device, in a tick
    before = d.counters()
    for _ in range(filled):
        d.fill_round()
    d.catch_up(keep=lead)
    x = common.delta(d.counters(), before)
    assert not x.get("resolver.dispatches") and not x.get("resolver.subjects")
    assert x["resolver.arena_growths"] == 2            # 256 -> 1024
    assert x["resolver.truncated_txns"] == (filled + lead - lead) * n
    assert not x.get("resolver.fence_materializes")    # nothing in flight
    assert len(d.reference.resident) == lead * n
    assert d.resident_difference() == 0
    waves = []
    for _ in range(whole + 4):
        r = d.round()
        assert (r["wrong"], r["failed"], r["refused"]) == (0, 0, 0)
        waves.append(r["waved"])
        assert d.resident_difference() == 0
    # the residents build up from `lead` rounds, then a wave a round
    quiet = p["resident_rounds"] - lead
    assert waves == [False] * quiet + [True] * (len(waves) - quiet)
    assert len(d.reference.resident) == p["resident_rounds"] * n
    total = d.counters()
    for name in live.HOST_PATH_COUNTERS + common.GATED_RESOLVER:
        assert total.get(name, 0) == 0, name
    assert d.arena().kid_cap == p["steady_kid_cap"]


def test_setup_s_leaves_the_references_seconds_out(monkeypatch):
    """What `run.py` reports as `setup_s` ends at `window_opened_at`: the
    moment the window opened, less the seconds set-up spent in the
    reference."""
    expected = live.Reference.expected

    def slow(self, txn_id, keys):
        time.sleep(0.002)
        return expected(self, txn_id, keys)

    plan, began = live.setup_plan, []

    def clocked_plan(p):
        # set-up's first call, right after its clock starts: building the
        # deployment before it takes as long as the machine's load makes it
        began.append(time.perf_counter())
        return plan(p)

    monkeypatch.setattr(live.Reference, "expected", slow)
    monkeypatch.setattr(live, "warm_kernels", lambda p: None)
    monkeypatch.setattr(live, "setup_plan", clocked_plan)
    # set-up ends with the arena full, so the window's first round compacts
    # (`correct` asks for a compaction) however slow this machine's rounds
    p = _params(rounds_before_fill=0)
    out = live.run(p, seed=5, seconds=0.1, trace=False,
                   meter=common.CompileMeter())
    setup = out["notes"]["setup"]
    lead, _, whole = plan(p)
    assert setup["reference_s"] >= 0.002 * (lead + whole) * p["subjects"]
    assert out["window_opened_at"] - began[0] == pytest.approx(
        setup["seconds"] - setup["reference_s"], abs=0.25)
    assert out["correct"], out["notes"]["faults"]
