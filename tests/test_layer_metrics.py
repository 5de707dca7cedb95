"""The benchmark's per-layer metrics against the program's counters: the
batch runner in process at the cell's rehearsal sizes, then every
`benchmark/layer_metrics/*.batch.json` that BENCHMARK.json lists for the
cell through `common.evaluate_ratio`. A counter renamed in the program, a
metric file without its manifest entry, or the two disagreeing, fails here
and not on the chip."""
from __future__ import annotations

import math

import pytest

from benchmark import common

CELL = "preaccept-batch-10k.resolve-4096"
RANGE_CELL = "preaccept-ranges-10k.range-20"
MANIFEST = common.load_json(common.ROOT / "BENCHMARK.json")
RANGE_METRICS = ("range_encode_us_per_subject.batch",
                 "range_decode_us_per_subject.batch",
                 "range_intervals_per_subject.batch",
                 "range_deps_per_subject.batch",
                 "range_device_us_per_dispatch.batch")


def listed(cell):
    return [m for m in MANIFEST["per_layer"]
            if "workloads" not in m or cell in m["workloads"]]


LISTED = [pytest.param(cell, m, id=f"{cell.split('.')[1]}-{m['name']}")
          for cell in (CELL, RANGE_CELL) for m in listed(cell)]


def _run(cell_name):
    import importlib
    cell = common.load_json(common.HERE / "workloads" / f"{cell_name}.json")
    config = common.load_json(
        common.HERE / "configs" / f"{cell['config']}.json")
    runner = importlib.import_module(f"benchmark.runners.{config['runner']}")
    out = runner.run({**config, **cell, **cell["rehearsal"]}, seed=7,
                     seconds=0.3, trace=False, meter=common.CompileMeter())
    assert out["correct"], out["notes"]["faults"]
    assert out["failed"] == 0 and out["attempted"] > 0
    return out["counters"]


@pytest.fixture(scope="module")
def runs():
    """The counters of one rehearsal-size run of each cell."""
    return {cell: _run(cell) for cell in (CELL, RANGE_CELL)}


@pytest.fixture(scope="module")
def counters(runs):
    return runs[CELL]


def test_the_cell_lists_its_metrics():
    names = [m["name"] for m in listed(CELL)]
    assert len(names) == len(set(names)) >= 12
    for name in ("device_wait_us_per_subject.batch",
                 "transfer_us_per_subject.batch",
                 "readback_bytes_per_subject.batch",
                 "starved_stage_us_per_subject.batch",
                 "starved_decode_us_per_subject.batch",
                 "starved_outside_us_per_subject.batch"):
        assert name in names
    # the range cell reports the sibling's metrics and its own five
    assert [m["name"] for m in listed(RANGE_CELL)] == \
        [n for n in names if n not in RANGE_METRICS] + list(RANGE_METRICS)
    assert not set(names) & set(RANGE_METRICS)


@pytest.mark.parametrize("cell,entry", LISTED)
def test_manifest_entry_and_metric_file_agree(cell, entry):
    path = common.HERE / "layer_metrics" / f"{entry['name']}.json"
    assert path.is_file(), f"{entry['name']} has no file under layer_metrics/"
    spec = common.load_json(path)
    for key in ("name", "unit", "layer", "better", "source", "moves"):
        assert spec[key] == entry[key], f"{entry['name']}: {key} differs"
    assert spec["runner"] == (
        "ranges" if entry["name"] in RANGE_METRICS else "batch")


@pytest.mark.parametrize("cell,entry", LISTED)
def test_metric_reads_the_programs_counters(cell, entry, runs):
    counters = runs[cell]
    spec = common.load_json(
        common.HERE / "layer_metrics" / f"{entry['name']}.json")
    value = common.evaluate_ratio(spec, counters)
    if entry["source"] == "device_trace":
        assert value is None  # nothing traced: the line leaves it out
        return
    assert value is not None, \
        f"{entry['name']}: a counter of {spec['num'] + spec['den']} is " \
        f"missing from the program's snapshot"
    assert math.isfinite(value) and value >= 0.0
    if "starved_outside" not in entry["name"]:
        assert value > 0.0


def test_range_metrics_read_nothing_where_the_program_has_no_range_path(
        counters):
    """On the key cell (as on a parent without the counters) the four that
    read the program evaluate to nothing and do not raise."""
    for name in RANGE_METRICS:
        spec = common.load_json(common.HERE / "layer_metrics" / f"{name}.json")
        assert common.evaluate_ratio(spec, counters) is None, name


def test_fetch_split_is_the_readback_metric(counters):
    """device_wait + transfer is what readback_us_per_subject.batch reads,
    less the bound's share."""
    def read(name):
        return common.evaluate_ratio(common.load_json(
            common.HERE / "layer_metrics" / f"{name}.json"), counters)
    bound = 1e6 * counters["resolver.bound_readback_s"] \
        / counters["resolver.subjects"]
    assert read("device_wait_us_per_subject.batch") \
        + read("transfer_us_per_subject.batch") == \
        pytest.approx(read("readback_us_per_subject.batch") - bound, rel=1e-6)
