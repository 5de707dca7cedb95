"""`trace_reduce.label_gap` and the labelled `idle_gaps` on hand-made events."""
from __future__ import annotations

import pytest

from benchmark import trace_reduce as tr

MS = 1_000_000


@pytest.mark.parametrize("gap, spans, want", [
    # the end of a round: the harvest holds the gap and so do the materialize
    # spans inside it, the more specific of the two
    ((0, 200), [("resolver.harvest", 0, 95), ("resolver.materialize", 5, 95),
                ("resolver.harvest", 100, 200), ("resolver.transfer", 100, 104),
                ("resolver.materialize", 104, 198)], "resolver.materialize"),
    # the start of a round: the enqueue loop, then a longer tick whose two
    # short children hold too little to stand for the gap
    ((0, 90), [("bench.enqueue", 0, 18), ("resolver.tick", 18, 85),
               ("resolver.preaccept", 20, 30), ("resolver.encode", 40, 50)],
     "resolver.tick"),
    # the enqueue loop alone over most of a short gap
    ((10, 30), [("bench.enqueue", 0, 25)], "bench.enqueue"),
    # spans hold under half of the gap
    ((0, 100), [("resolver.launch", 0, 30), ("resolver.tick", 90, 140)],
     tr.UNATTRIBUTED),
    # no name holds half, all together do: the one that holds most
    ((0, 100), [("bench.enqueue", 0, 20), ("resolver.tick", 20, 60),
                ("resolver.encode", 30, 40), ("resolver.launch", 60, 70)],
     "resolver.tick"),
    # exactly half is enough
    ((0, 100), [("resolver.launch", 0, 50)], "resolver.launch"),
    ((0, 100), [], tr.UNATTRIBUTED),
    # a span that only touches the gap's edge holds none of it
    ((50, 60), [("resolver.tick", 0, 50), ("resolver.launch", 60, 70)],
     tr.UNATTRIBUTED),
])
def test_label_gap(gap, spans, want):
    assert tr.label_gap(*gap, spans) == want


@pytest.mark.parametrize("name, is_span", [
    ("resolver.materialize", True), ("bench.enqueue", True),
    ("serve.admission.wait", True), ("bench.window", True),
    ("copy", False), ("PjitFunction(finalize_csr)", False),
    ("ArrayImpl.copy_to_host_async", False), ("shard_args", False),
])
def test_span_names(name, is_span):
    assert bool(tr.SPAN_NAME.match(name)) == is_span


def test_reduce_planes_labels_its_gaps():
    planes = [
        ("/host:CPU", [("python3", [
            ("bench.window", 0, 100 * MS),
            ("bench.enqueue", 0, 9 * MS),
            ("resolver.tick", 9 * MS, 10 * MS),
            ("PjitFunction(finalize_csr)", 19 * MS, 1 * MS),
            ("resolver.harvest", 40 * MS, 60 * MS),
            ("resolver.materialize", 42 * MS, 55 * MS)])]),
        ("/device:TPU:0", [("XLA Ops", [("fusion", 20 * MS, 20 * MS)]),
                           ("XLA Modules", [("jit_f(1)", 20 * MS, 20 * MS)])]),
    ]
    r = tr.reduce_planes(planes, fallback_window_s=1.0)
    assert r["breakdown"]["idle_gaps"] == [
        ["resolver.materialize", 0.060], ["resolver.tick", 0.020]]
    assert r["busy_s"] == pytest.approx(0.020)
    assert r["window_s"] == pytest.approx(0.100)
