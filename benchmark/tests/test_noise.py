"""`benchmark/noise.py` on hand-made sets: the three spreads, the check's
verdict on PR 27's own figures, the variance split on a made-up two-level
set, and the pairing of a run's two lines."""
from __future__ import annotations

import json
import statistics

import pytest

from benchmark import noise


@pytest.mark.parametrize("values, want", [
    # the farthest run (130) is left out: 104 - 98 over the median 101.5
    ([100, 101, 102, 98, 104, 130], 6 / 101.5),
    # equal runs
    ([7.0] * 6, 0.0),
    # leaving out the farthest (10, as far as 14) narrows nothing: its twin
    # stays, so the whole range stands
    ([10, 10, 12, 14, 14], 4 / 12),
    # two runs: nothing to leave out
    ([100, 110], 10 / 105),
    ([5.0], 0.0),
])
def test_spread_range_leaves_out_the_farthest_only_where_that_narrows(values, want):
    assert noise.spread_range(values) == pytest.approx(want)


def test_spread_iqr_is_the_contracts():
    values = [100, 101, 102, 98, 104, 130]
    q1, _, q3 = statistics.quantiles(values, n=4)
    assert noise.spread_iqr(values) == pytest.approx((q3 - q1) / 101.5)
    rest = [100, 101, 102, 98, 104]
    q1, _, q3 = statistics.quantiles(rest, n=4)
    # the median stays that of the whole set: the run is left out of the
    # spread, not of the level
    assert noise.spread_iqr(values, leave_out_farthest=True) == \
        pytest.approx((q3 - q1) / 101.5)
    assert noise.spread_iqr([3, 3, 3, 3]) == 0.0
    # never wider than the range with the same run left out
    assert noise.spread_iqr(values, True) <= noise.spread_range(values)


@pytest.mark.parametrize("spread, bound, want", [
    (32.9019, 17.9012, "too noisy"),   # PR 27, the parent's runs
    (22.352, 17.9012, "too noisy"),    # PR 27, the change's runs
    (118.867, 127.683, "noted"),       # PR 26's note: past half the bound
    (60.0, 127.683, "ok"),
])
def test_verdict_on_the_ledgers_figures(spread, bound, want):
    assert noise.verdict(spread, bound) == want


def test_variance_split_two_levels():
    # two seeds, two processes each, four rounds a run. Every run repeats one
    # pattern (a slow third round), and round i of run j scatters by e[j][i],
    # whose rows and columns add up to nought
    pattern = [-2, -2, 6, -2]
    e = [[1, -1, 1, -1], [-1, 1, -1, 1], [1, -1, 1, -1], [-1, 1, -1, 1]]

    def run(j, mean):
        return [mean + p + x for p, x in zip(pattern, e[j])]
    runs = [(1, run(0, 100)), (1, run(1, 104)), (2, run(2, 110)), (2, run(3, 114))]
    assert noise.scatter_of_rounds([r for _, r in runs]) == \
        pytest.approx([16 / 9] * 4)     # 16 over (4 - 1)(4 - 1) freedoms
    s = noise.variance_split(runs)
    within = (16 / 9) / 4
    assert s["within"] == pytest.approx(within)
    # run means 100, 104 | 110, 114: pooled variance (8 + 8) / 2 = 8
    assert s["process"] == pytest.approx(8 - within)
    # seeds' means 102, 112: variance 50, less the pooled 8 over 2 runs
    assert s["seed"] == pytest.approx(50 - 8 / 2)
    assert s["mean"] == pytest.approx(107)
    assert sum(s["share"].values()) == pytest.approx(1.0)
    assert s["share"]["seed"] > s["share"]["process"] > s["share"]["within"]
    # one run alone has no pattern to take: its rounds' own variance
    assert noise.scatter_of_rounds([runs[0][1]]) == \
        pytest.approx([statistics.variance(runs[0][1])])


def test_variance_split_says_what_the_sets_cannot_show():
    one_seed = noise.variance_split([(5, [1.0, 1.2]), (5, [1.1, 1.3])])
    assert one_seed["seed"] is None and one_seed["process"] is not None
    six_seeds = noise.variance_split([(i, [1.0, 1.1 + i / 100]) for i in range(6)])
    assert six_seeds["process"] is None and six_seeds["seed"] is not None
    flat = noise.variance_split([(1, [2.0, 2.0]), (1, [2.0, 2.0])])
    assert (flat["within"], flat["process"]) == (0.0, 0.0)


def test_read_set_pairs_a_result_with_the_notes_before_it():
    notes = {"workload": "c", "seed": 9, "values": {}, "counters": {"x": 1},
             "notes": {"round_s": [0.5, 0.6], "rounds": 2}}
    result = {"correct": True, "attempted": 8, "failed": 0,
              "metrics": {"deps_resolved_per_s": {"value": 8000.5,
                                                  "unit": "subjects/s"}},
              "device": {}}
    text = "\n".join(["some log line", json.dumps(notes), json.dumps(result),
                      "{not json", json.dumps(result)])
    first, second = noise.read_set(text)
    assert first["seed"] == 9 and first["rounds"] == [0.5, 0.6]
    assert first["metrics"] == {"deps_resolved_per_s": 8000.5}
    assert second["seed"] is None and second["rounds"] is None
    table = noise.set_table([first, second])
    assert table["deps_resolved_per_s"]["n"] == 2
    assert table["deps_resolved_per_s"]["range-1"] == 0.0
