#!/usr/bin/env python3
"""Check the benchmark's own arithmetic, by hand and on the CPU:

    JAX_PLATFORMS=cpu python3 benchmark/selfcheck.py

the interval arithmetic of trace_reduce.py on hand-made intervals, the ratio
evaluator on a hand-made counter dict, percentile_exact on a known list, the
Zipf draw's skew, and the batch runner's reference against the program's own
host scan (`store.host_calculate_deps`) at the cell's rehearsal size. Not
collected by the repo's tests; it compiles nothing but that one tiny arena.
"""
from __future__ import annotations

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from benchmark import common, trace_reduce  # noqa: E402


def check_intervals():
    u = trace_reduce.union([[5, 7], [0, 2], [1, 3], [7, 8], [4, 4]])
    assert u == [[0, 3], [5, 8]], u
    assert trace_reduce.total(u) == 6
    assert trace_reduce.clip(u, [[2, 6]]) == [[2, 3], [5, 6]]
    assert trace_reduce.gaps(u, [[0, 10]]) == [[3, 5], [8, 10]]
    assert trace_reduce.gaps([], [[0, 4]]) == [[0, 4]]
    # two device planes, one window span of 10 us on the host plane; the op
    # at 20 us lies outside the window and counts nowhere
    planes = [
        ("/host:CPU", [("python", [("bench.window", 1000, 10_000)])]),
        ("/device:TPU:0", [("XLA Ops", [("fusion", 2000, 1000),
                                        ("fusion", 2500, 1500),
                                        ("copy", 9000, 3000),
                                        ("copy", 20_000, 500)]),
                           ("XLA Modules", [("jit_f(123)", 0, 50_000)])]),
        ("/device:TPU:1", [("XLA Ops", [("fusion", 1000, 10_000)]),
                           ("XLA Modules", [("jit_f(123)", 0, 50_000)])]),
    ]
    r = trace_reduce.reduce_planes(planes, fallback_window_s=1.0)
    assert abs(r["window_s"] - 10e-6) < 1e-12, r
    assert abs(r["busy_s"] - (4e-6 + 10e-6) / 2) < 1e-12, r
    assert r["breakdown"]["device_ops"][0] == ["jit_f: fusion", 6.25e-6], r
    assert [g[1] for g in r["breakdown"]["idle_gaps"]] == [5e-6, 1e-6], r
    assert trace_reduce.reduce_planes(planes[:1], 1.0) is None
    # no span of ours: the window starts at the first op and lasts what the
    # host's clock said
    r = trace_reduce.reduce_planes(planes[1:2], fallback_window_s=20e-6)
    assert abs(r["busy_s"] - 5.5e-6) < 1e-12, r


def check_ratio():
    counters = {"a_s": 0.5, "b_s": 1.5, "n": 4, "zero": 0}
    spec = {"scale": 1e3, "num": ["a_s", "b_s"], "den": ["n"]}
    assert common.evaluate_ratio(spec, counters) == 500.0
    assert common.evaluate_ratio({"num": ["n"], "den": []}, counters) == 4
    assert common.evaluate_ratio({"num": ["n"], "den": ["zero"]}, counters) is None
    assert common.evaluate_ratio({"num": ["absent"], "den": ["n"]}, counters) is None
    assert common.delta({"x": 5, "new": 2}, {"x": 3}) == {"x": 2, "new": 2}
    assert common.summed([{"x": 1, "s": "tpu"}, {"x": 2, "y": 1.5}]) == \
        {"x": 3, "y": 1.5}
    for path in sorted((common.HERE / "layer_metrics").glob("*.json")):
        spec = common.load_json(path)
        assert path.stem == spec["name"] and spec["num"], path


def check_percentile():
    xs = list(range(1, 101))
    assert common.percentile_exact(xs, 50) == 50
    assert common.percentile_exact(xs, 95) == 95
    assert common.percentile_exact(xs, 100) == 100
    assert common.percentile_exact([7.0], 95) == 7.0
    assert common.percentile_exact([], 95) == 0.0


def check_zipf():
    from benchmark.loadgen import LoadGen
    gen = LoadGen(None, seed=1, key_count=100, write_ratio=0.5,
                  max_keys_per_txn=4, key_stride=65, key_dist="zipf",
                  theta=0.99)
    draws = [gen._pick_key() for _ in range(20_000)]
    assert set(draws) <= {i * 65 for i in range(100)}
    share = draws.count(0) / len(draws)  # 1 / H(100, 0.99) = 0.189
    assert 0.17 < share < 0.21, share


def check_batch_reference():
    from benchmark.runners.batch import Arena
    cell = common.load_json(common.HERE / "workloads"
                            / "preaccept-batch-10k.resolve-4096.json")
    config = common.load_json(common.HERE / "configs" / f"{cell['config']}.json")
    p = {**config, **cell, **cell["rehearsal"]}
    arena = Arena(p, seed=2147483659)
    checked = 0
    for _ in range(p["subjects"]):
        t, keys, ts, raw = arena.fresh()
        owned = arena.store.owned(keys)
        host = arena.store.host_calculate_deps(t, owned, ts)
        want = arena.expected(raw, ts)
        assert set(host.key_deps.all_txn_ids()) == want, (t, raw)
        checked += len(want)
    assert checked > 0
    resolve_s, _, wrong, failed, deps = arena.round(p["subjects"])
    assert (wrong, failed) == (0, 0) and deps > 0, (wrong, failed, deps)
    return checked + deps


def main() -> int:
    check_intervals()
    check_ratio()
    check_percentile()
    check_zipf()
    deps = check_batch_reference()
    print(f"selfcheck: ok ({deps} dependencies agreed with the host scan "
          f"and the device)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
