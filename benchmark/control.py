#!/usr/bin/env python3
"""The batch cells' control and planted fault: the batch runner driven with
its timed path broken underneath, to see `correct` come out false.

    python3 benchmark/control.py --workload <cell> --seeds 11 12 13 [--seconds 2]
    python3 benchmark/control.py --workload <cell> --seeds 11 --rehearsal

- `stale` (the control): the configuration's one guarantee is the exact
  dependency set; here one answer of every dispatch loses its newest
  dependency, as an arena that lags a registration would answer.
- `swapped` (an answer altered where it is produced): two answers of every
  dispatch change places as they leave the decode.
- `sound`: nothing broken; `correct` has to stay true.

Only the window is broken, not the warm-up round, so it is the window's own
comparison that has to see it. One process for all seeds and kinds; exits 0
when every broken run read `correct` false (wrong answers counted) and every
sound one true. `benchmark/tests/test_batch_faults.py` runs the same at the
rehearsal size; the benchmark's own runs never come here.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

KINDS = ("stale", "swapped", "sound")


def alter(kind, results):
    if kind == "stale":
        for i, deps in enumerate(results):
            newest = deps.max_txn_id()
            if newest is not None:
                results[i] = deps.without(lambda t: t == newest)
                break
    elif kind == "swapped" and len(results) > 1:
        results[0], results[-1] = results[-1], results[0]
    return results


def broken_arena(kind):
    """`runners.batch.Arena` whose resolver's decode is altered inside the
    window (a round that is given the collector's watch)."""
    from benchmark.runners import batch

    class Broken(batch.Arena):
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
    """One run of the batch runner with `kind` planted; what it returned."""
    from benchmark import common
    from benchmark.runners import batch
    sound = batch.Arena
    batch.Arena = broken_arena(kind)
    try:
        return batch.run(params, seed=seed, seconds=seconds, trace=False,
                         meter=common.CompileMeter())
    finally:
        batch.Arena = sound


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args(argv)
    from benchmark import common
    cell = common.load_json(common.HERE / "workloads" / f"{args.workload}.json")
    config = common.load_json(common.HERE / "configs" / f"{cell['config']}.json")
    params = {**config, **cell, **(cell["rehearsal"] if args.rehearsal else {})}
    import jax
    platform = jax.devices()[0].platform
    if platform != "tpu" and not args.rehearsal:
        print(f"control: JAX found platform {platform!r}, not a TPU; nothing "
              "was run", file=sys.stderr)
        return 2
    from accord_tpu.utils.compile_cache import place_compile_cache
    place_compile_cache()
    ok = True
    for seed in args.seeds:
        for kind in KINDS:
            out = run_broken(kind, params, seed, args.seconds)
            wrong = out["compared"]["wrong_answers"][0]
            as_expected = out["correct"] == (kind == "sound") and \
                (wrong > 0) == (kind != "sound")
            ok &= as_expected
            print(json.dumps({"seed": seed, "kind": kind, "platform": platform,
                              "correct": out["correct"], "wrong_answers": wrong,
                              "attempted": out["attempted"],
                              "as_expected": as_expected}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
