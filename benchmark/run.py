#!/usr/bin/env python3
"""Run ONE cell of BENCHMARK.json once and print its result as the last line.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>
    python3 benchmark/run.py --workload <cell> --rehearsal     # tiny sizes, any platform

One process, the only one that touches JAX; it starts no child. Everything
that belongs to one cell is data found by name: `workloads/<cell>.json`, its
`configs/<config>.json`, the runner `runners/<runner>.py` the configuration
names, and one `layer_metrics/<metric>.json` for every per-layer metric
BENCHMARK.json lists for the cell. README.md says how a later PR adds each.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()  # set-up is counted from here

import argparse  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))


def applies(metric, cell):
    return "workloads" not in metric or cell in metric["workloads"]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearsal", action="store_true",
                    help="the cell's tiny `rehearsal` sizes on whatever "
                         "platform JAX finds; the last line shows it")
    ap.add_argument("--dump-trace", default=None, metavar="FILE",
                    help="with --trace 1: also write what the trace holds "
                         "(planes, lines, first events) to FILE")
    args = ap.parse_args(argv)

    if importlib.util.find_spec("accord_tpu") is None:
        print("benchmark: the accord_tpu package is not beside benchmark/; "
              "nothing was run", file=sys.stderr)
        return 1
    from benchmark import common
    manifest = common.load_json(ROOT / "BENCHMARK.json")
    cell = common.load_json(common.HERE / "workloads" / f"{args.workload}.json")
    config = common.load_json(common.HERE / "configs" / f"{cell['config']}.json")
    params = {**config, **cell}
    if args.rehearsal:
        params.update(cell["rehearsal"])
    seconds = args.seconds if args.seconds is not None else (
        3.0 if args.rehearsal else float(manifest["run_seconds"]))

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" and not args.rehearsal:
        print("benchmark: JAX found platform %r, not a TPU; nothing was run"
              % devices[0].platform, file=sys.stderr)
        return 2
    if len(devices) < cell["chips"] and not args.rehearsal:
        print("benchmark: the cell asks for %d chips, JAX found %d; nothing "
              "was run" % (cell["chips"], len(devices)), file=sys.stderr)
        return 2
    devices = devices[:cell["chips"]]
    from accord_tpu.utils.compile_cache import place_compile_cache
    cache_dir = place_compile_cache()
    meter = common.CompileMeter()

    runner = importlib.import_module(f"benchmark.runners.{config['runner']}")
    out = runner.run(params, seed=args.seed, seconds=seconds,
                     trace=bool(args.trace), meter=meter,
                     dump_trace=args.dump_trace)

    values = dict(out["values"], setup_s=out["window_opened_at"] - T0)
    counters = out["counters"]
    device = common.device_report(devices)
    line = {"correct": bool(out["correct"]), "attempted": out["attempted"],
            "failed": out["failed"], "metrics": {}, "device": device}
    if args.trace:
        for m in manifest["per_layer"]:
            if not applies(m, args.workload):
                continue
            spec = common.load_json(
                common.HERE / "layer_metrics" / f"{m['name']}.json")
            value = common.evaluate_ratio(spec, counters)
            if value is not None:
                line["metrics"][m["name"]] = {"value": value,
                                              "unit": m["unit"]}
        traced = out.get("traced")
        if traced:
            device["busy_s"] = traced["busy_s"]
            device["window_s"] = traced["window_s"]
            line["breakdown"] = traced["breakdown"]
    else:
        for m in manifest["end_to_end"]:
            if applies(m, args.workload):
                line["metrics"][m["name"]] = {"value": values[m["name"]],
                                              "unit": m["unit"]}
    if args.rehearsal:
        line["rehearsal"] = True
    # each number `correct` compared, beside its limit: last in the line,
    # and the last lines of stderr
    line["compared"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in out.get("compared", {}).items()}
    # what a reader of the run wants beside the contract's line; the last
    # line of stdout is the result
    print(json.dumps({"workload": args.workload, "seed": args.seed,
                      "seconds": seconds, "compile_cache": cache_dir,
                      "compile": meter.read(), "values": values,
                      "counters": counters, "notes": out.get("notes", {})},
                     default=str), flush=True)
    print(json.dumps(line), flush=True)
    for k, c in line["compared"].items():
        print(f"compared {k}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(f"correct: {line['correct']}", file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
