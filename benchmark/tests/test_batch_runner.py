"""The batch runner in process at the cell's rehearsal size: what ISSUE 28
put into `notes`, the numbers compared beside their limits, and `correct`
coming out false when the timed path is broken underneath (`control.py`)."""
from __future__ import annotations

import pytest

from benchmark import common, control

CELL = "preaccept-batch-10k.resolve-4096"


@pytest.fixture(scope="module")
def params():
    cell = common.load_json(common.HERE / "workloads" / f"{CELL}.json")
    config = common.load_json(common.HERE / "configs" / f"{cell['config']}.json")
    return {**config, **cell, **cell["rehearsal"]}


@pytest.mark.parametrize("kind", control.KINDS)
def test_correct_follows_the_timed_path(params, kind):
    out = control.run_broken(kind, params, seed=4000000007, seconds=0.3)
    wrong, limit = out["compared"]["wrong_answers"]
    assert limit == 0
    if kind == "sound":
        assert out["correct"] and wrong == 0 and not out["notes"]["faults"]
    else:
        assert not out["correct"] and wrong > 0
        assert "wrong answers" in out["notes"]["faults"][0]
        # the warm-up round was sound: the window's comparison saw it
        assert not any("warm-up" in f for f in out["notes"]["faults"])


def test_notes_say_where_the_time_varied(params):
    out = control.run_broken("sound", params, seed=7, seconds=0.3)
    notes = out["notes"]
    rounds = notes["rounds"]
    assert rounds == out["counters"]["rounds"] > 1
    for key in ("round_s", "round_cpu_s", "round_wait_s", "round_materialize_s"):
        assert len(notes[key]) == rounds and all(x >= 0 for x in notes[key])
    assert sum(notes["round_s"]) == pytest.approx(out["counters"]["window_s"])
    assert sum(notes["round_materialize_s"]) == pytest.approx(
        out["counters"]["resolver.materialize_s"])
    collector = notes["collector"]
    assert len(collector["collections"]) == len(collector["seconds"]) == 3
    assert collector["collections"][0] > 0 and collector["seconds"][0] > 0
    assert sum(collector["seconds"]) < out["counters"]["window_s"]
    for value, limit in out["compared"].values():
        assert value >= limit
