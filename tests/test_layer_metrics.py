"""The benchmark's per-layer metrics against the program's counters: each
cell's runner in process at the cell's rehearsal sizes, then every
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
LIVE_CELL = "preaccept-batch-100k.resolve-4096"
NODE_CELL = "preaccept-8stores-100k.fanout-4096"
NODE_RANGE_CELL = "preaccept-8stores-ranges-100k.range-20"
CELLS = (CELL, RANGE_CELL, LIVE_CELL, NODE_CELL, NODE_RANGE_CELL)
MANIFEST = common.load_json(common.ROOT / "BENCHMARK.json")
RANGE_METRICS = ("range_encode_us_per_subject.batch",
                 "range_decode_us_per_subject.batch",
                 "range_intervals_per_subject.batch",
                 "range_deps_per_subject.batch",
                 "range_device_us_per_dispatch.batch")
LIVE_METRICS = ("preaccept_us_per_subject.batch",
                "arena_sync_us_per_subject.batch",
                "arena_rows_uploaded_per_subject.batch",
                "truncate_us_per_txn.batch",
                "fence_us_per_subject.batch",
                "compact_ms_per_compaction.batch",
                "arena_sync_device_us_per_dispatch.batch",
                "arena_donated_share.batch",
                "cleanup_us_per_txn.batch",
                "cleanup_scanned_per_txn.batch")
CLEANUP_METRICS = LIVE_METRICS[-2:]
# the device's idle time the account found with calls in flight, and the
# collector's pauses (PR 37): every cell, and nothing forces either above 0
# in a rehearsal
IDLE_METRICS = ("drained_stage_us_per_subject.batch",
                "drained_decode_us_per_subject.batch",
                "drained_outside_us_per_subject.batch",
                "gc_pause_us_per_subject.batch")
NODE_METRICS = ("store_slices_per_txn.batch",
                "fanout_us_per_txn.batch",
                "reduce_us_per_txn.batch",
                "store_groups_per_dispatch.batch",
                "fused_resolve_device_us_per_dispatch.batch",
                "finalize_device_us_per_dispatch.batch")
NODE_RANGE_METRICS = ("fused_range_device_us_per_dispatch.batch",
                      "range_finalize_device_us_per_dispatch.batch",
                      "range_slices_per_range_txn.batch",
                      "range_groups_per_dispatch.batch")


def listed(cell):
    return [m for m in MANIFEST["per_layer"]
            if "workloads" not in m or cell in m["workloads"]]


LISTED = [pytest.param(cell, m, id=f"{cell.split('.')[1]}-{m['name']}")
          for cell in CELLS for m in listed(cell)]


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
    return {cell: _run(cell) for cell in CELLS}


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
    assert all(name in names for name in IDLE_METRICS)

    def own(cell, metrics):
        got = [m["name"] for m in listed(cell)]
        assert [n for n in got if n not in metrics] == names
        assert [n for n in got if n in metrics] == list(metrics)
        assert not set(names) & set(metrics)
    # the range cell reports the sibling's metrics and its own five; the
    # live cell its own ten; the node cell its six; the node-ranges cell
    # the range cell's five, the node cell's six and its own four
    own(RANGE_CELL, RANGE_METRICS)
    own(LIVE_CELL, LIVE_METRICS)
    own(NODE_CELL, NODE_METRICS)
    own(NODE_RANGE_CELL, RANGE_METRICS + NODE_METRICS + NODE_RANGE_METRICS)


@pytest.mark.parametrize("cell,entry", LISTED)
def test_manifest_entry_and_metric_file_agree(cell, entry):
    path = common.HERE / "layer_metrics" / f"{entry['name']}.json"
    assert path.is_file(), f"{entry['name']} has no file under layer_metrics/"
    spec = common.load_json(path)
    for key in ("name", "unit", "layer", "better", "source", "moves"):
        assert spec[key] == entry[key], f"{entry['name']}: {key} differs"
    assert spec["runner"] == (
        "ranges" if entry["name"] in RANGE_METRICS
        else "live" if entry["name"] in LIVE_METRICS
        else "node" if entry["name"] in NODE_METRICS
        else "noderanges" if entry["name"] in NODE_RANGE_METRICS else "batch")


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
    if "starved_outside" not in entry["name"] \
            and entry["name"] not in IDLE_METRICS:
        assert value > 0.0


def test_range_metrics_read_nothing_where_the_program_has_no_range_path(
        counters):
    """On the key cell (as on a parent without the counters) the four that
    read the program evaluate to nothing and do not raise."""
    for name in RANGE_METRICS:
        spec = common.load_json(common.HERE / "layer_metrics" / f"{name}.json")
        assert common.evaluate_ratio(spec, counters) is None, name


def test_live_metrics_read_nothing_on_the_static_cells(runs):
    """A store that never registers, truncates or fills (the two accepted
    cells, or a parent without the counters) gives the eight nothing to
    read; the preaccept span opens on every tick and finds an empty queue
    there, so that one reads next to 0."""
    for cell in (CELL, RANGE_CELL):
        for name in LIVE_METRICS:
            spec = common.load_json(
                common.HERE / "layer_metrics" / f"{name}.json")
            value = common.evaluate_ratio(spec, runs[cell])
            if name == "preaccept_us_per_subject.batch":
                assert 0.0 <= value < 1.0, (cell, value)
            else:
                assert not value, (cell, name, value)


def test_cleanup_metrics_read_nothing_on_the_static_cells(runs):
    """The three cells that never run a wave open no `store.cleanup` span:
    its two metrics evaluate to nothing (as on a parent without the span);
    on the live cell the walk visits every resident command, twice a wave,
    for each txn it truncates."""
    def read(name, cell):
        return common.evaluate_ratio(common.load_json(
            common.HERE / "layer_metrics" / f"{name}.json"), runs[cell])
    for cell in (CELL, RANGE_CELL, NODE_CELL):
        for name in CLEANUP_METRICS:
            assert read(name, cell) is None, (cell, name)
            assert "store.cleanup_s" not in runs[cell]
    assert read("cleanup_us_per_txn.batch", LIVE_CELL) > 0.0
    assert read("cleanup_scanned_per_txn.batch", LIVE_CELL) >= 2.0


def test_idle_metrics_read_a_number_in_every_cell(runs):
    """The account's drained timers and the collector's pause are in every
    resolver's registry from its construction: each cell reads a number
    (0 where nothing of the kind happened), never nothing."""
    for cell in CELLS:
        for name in IDLE_METRICS:
            value = common.evaluate_ratio(common.load_json(
                common.HERE / "layer_metrics" / f"{name}.json"), runs[cell])
            assert value is not None and value >= 0.0, (cell, name)


def test_node_metrics_read_nothing_on_a_one_store_cell(runs):
    """The three accepted cells hold one store a dispatch and never come
    through the node's fan-out (as a parent without the counters does not):
    the six find nothing to read and do not raise; on the node cell the
    fan-out's ratios are what the deployment says."""
    for cell in (CELL, RANGE_CELL, LIVE_CELL):
        for name in NODE_METRICS:
            spec = common.load_json(
                common.HERE / "layer_metrics" / f"{name}.json")
            assert not common.evaluate_ratio(spec, runs[cell]), (cell, name)

    def read(name):
        return common.evaluate_ratio(common.load_json(
            common.HERE / "layer_metrics" / f"{name}.json"), runs[NODE_CELL])
    assert 3.0 <= read("store_slices_per_txn.batch") <= 3.6
    assert read("store_groups_per_dispatch.batch") == 8.0
    assert read("subjects_per_dispatch.batch") > 8


def test_node_range_metrics_read_nothing_on_the_other_cells(runs):
    """The four accepted cells hold no range call fused over stores and ask
    no store for Ranges (nor does a parent without the counters): the four
    find nothing to read and do not raise; on the node-ranges cell every
    dispatch's range call rode all eight stores."""
    def read(name, cell):
        return common.evaluate_ratio(common.load_json(
            common.HERE / "layer_metrics" / f"{name}.json"), runs[cell])
    for cell in CELLS[:-1]:
        for name in NODE_RANGE_METRICS:
            assert read(name, cell) is None, (cell, name)
    assert read("range_groups_per_dispatch.batch", NODE_RANGE_CELL) == 8.0
    assert 1.0 < read("range_slices_per_range_txn.batch", NODE_RANGE_CELL) \
        < read("store_slices_per_txn.batch", NODE_RANGE_CELL)


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
