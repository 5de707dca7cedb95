#!/usr/bin/env python3
"""The node-ranges cell's control and planted fault: the node-ranges runner
driven with its timed path broken underneath, to see `correct` come out
false. The kinds are `node_control.py`'s:

    python3 benchmark/node_range_control.py --workload <cell> --seeds 11 12 [--seconds 2]
    python3 benchmark/node_range_control.py --workload <cell> --seeds 11 --rehearsal

- `lost_part` (the control): one store slice of every dispatch answers
  nothing, so one reply a dispatch lacks the part one of its stores
  answered, key or range, as a reduce that dropped a store would reply.
- `swapped` (an answer altered where it is produced): two store slices of
  every dispatch change places as they leave the decode.
- `sound`: nothing broken; `correct` has to stay true.

Only the window is broken, not the warm-up rounds. One process for all
seeds and kinds; exits 0 when every broken run read `correct` false (wrong
answers counted) and every sound one true.
`tests/test_node_range_deployment.py` runs the same at the rehearsal size;
the benchmark's own runs never come here.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from benchmark.node_control import KINDS, alter  # noqa: E402


def broken_deployment(kind):
    """`runners.noderanges.Deployment` whose resolver's decode is altered
    inside the window (a round that is given the collector's watch)."""
    from benchmark.runners import noderanges

    class Broken(noderanges.Deployment):
        def __init__(self, p, seed):
            super().__init__(p, seed)
            self.armed = False
            decode = self.resolver._decode_dispatch
            self.resolver._decode_dispatch = lambda call: (
                alter(kind, decode(call)) if self.armed else decode(call))

        def round(self, n, n_range, timed=None, watch=None):
            self.armed = watch is not None
            return super().round(n, n_range, timed=timed, watch=watch)

    return Broken


def run_broken(kind, params, seed, seconds):
    """One run of the node-ranges runner with `kind` planted; what it
    returned."""
    from benchmark import common
    from benchmark.runners import noderanges
    sound = noderanges.Deployment
    noderanges.Deployment = broken_deployment(kind)
    try:
        return noderanges.run(params, seed=seed, seconds=seconds, trace=False,
                              meter=common.CompileMeter())
    finally:
        noderanges.Deployment = sound


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--rehearsal", action="store_true")
    ap.add_argument("--kinds", nargs="+", choices=KINDS, default=KINDS)
    args = ap.parse_args(argv)
    from benchmark import common
    cell = common.load_json(common.HERE / "workloads" / f"{args.workload}.json")
    config = common.load_json(common.HERE / "configs" / f"{cell['config']}.json")
    params = {**config, **cell, **(cell["rehearsal"] if args.rehearsal else {})}
    import jax
    platform = jax.devices()[0].platform
    if platform != "tpu" and not args.rehearsal:
        print(f"node_range_control: JAX found platform {platform!r}, not a "
              "TPU; nothing was run", file=sys.stderr)
        return 2
    from accord_tpu.utils.compile_cache import place_compile_cache
    place_compile_cache()
    ok = True
    for seed in args.seeds:
        for kind in args.kinds:
            out = run_broken(kind, params, seed, args.seconds)
            wrong = out["compared"]["wrong_answers"][0]
            as_expected = out["correct"] == (kind == "sound") and \
                (wrong > 0) == (kind != "sound")
            ok &= as_expected
            print(json.dumps({"seed": seed, "kind": kind, "platform": platform,
                              "correct": out["correct"], "wrong_answers": wrong,
                              "wrong_key_answers":
                                  out["compared"]["wrong_key_answers"][0],
                              "wrong_range_answers":
                                  out["compared"]["wrong_range_answers"][0],
                              "attempted": out["attempted"],
                              "faults": out["notes"]["faults"],
                              "as_expected": as_expected}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
