#!/usr/bin/env python3
"""How far runs of one cell on one code differ, and where the difference
comes from.

    python3 benchmark/noise.py SET_FILE [SET_FILE ...]

A set file holds the standard output of the runs of one set, one after the
other (whole, or only each run's last two lines): `run.py` prints the line
with `notes` and then the result's line. Imports nothing but the standard
library, so it reads sets wherever they were brought back to.

Per set and metric it prints the median and three spreads, each a share of
the median:

- `range-1`: largest minus smallest, leaving out the run farthest from the
  median where that narrows it. The reckoning ISSUE 28 took from the check's
  refusal of PR 27 ("A spread leaves out the run farthest from its median
  where that narrows it"); the widest a set can read by any of the three.
- `iqr`: third minus first quartile of all runs,
  `statistics.quantiles(values, n=4)`: what a bound is set from (five times
  the widest) and what the check reads for "too loose".
- `iqr-1`: the same with the farthest run left out: the check's "too tight"
  takes the mean of its two sets' and refuses above half the bound.

Over all the sets given it splits the variance of a run's mean round time
(the inverse of `deps_resolved_per_s`) by the runs' `round_s` lists: inside
a run (the scatter between rounds, divided by the rounds of a run: what a
longer window averages away), between processes of one seed (runs that drew
the same work), and between seeds.
"""
from __future__ import annotations

import json
import statistics
import sys


def spread_range(values):
    """(max - min) / median, the run farthest from the median left out where
    that narrows it."""
    m = statistics.median(values)
    if len(values) < 2 or m == 0:
        return 0.0
    far = max(range(len(values)), key=lambda i: abs(values[i] - m))
    rest = values[:far] + values[far + 1:]
    whole = max(values) - min(values)
    part = max(rest) - min(rest) if len(rest) > 1 else whole
    return min(whole, part) / abs(m)


def spread_iqr(values, leave_out_farthest=False):
    """(Q3 - Q1) / median by `statistics.quantiles(values, n=4)`."""
    values = list(values)
    m = statistics.median(values)
    if leave_out_farthest and len(values) > 2:
        values.remove(max(values, key=lambda v: abs(v - m)))
    if len(values) < 2 or m == 0:
        return 0.0
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / abs(m)


def verdict(spread_abs, bound_abs):
    """What the check says of a cell whose runs spread so, under a bound (both
    in the metric's unit): refused past the bound, noted past half of it."""
    if spread_abs > bound_abs:
        return "too noisy"
    return "noted" if spread_abs > bound_abs / 2 else "ok"


def scatter_of_rounds(runs):
    """Per run, the variance of its rounds about its own mean once the
    pattern that all runs repeat is taken out: round i of every run holds the
    same full collections (the collector counts allocations, and every seed
    draws the same sizes), so a round that is slow in every run is no
    scatter. The pattern is the mean, over runs, of round i's distance from
    its run's mean, over the rounds all runs have; with one run there is no
    pattern to take."""
    shared = min(len(rounds) for rounds in runs)
    means = [statistics.fmean(rounds) for rounds in runs]
    j = len(runs)
    if j < 2 or shared < 2:
        return [statistics.variance(r) if len(r) > 1 else 0.0 for r in runs]
    pattern = [statistics.fmean(r[i] - m for r, m in zip(runs, means))
               for i in range(shared)]
    # the pattern was fitted to these j runs: j / (j - 1) gives the freedom
    # back
    return [sum((r[i] - m - pattern[i]) ** 2 for i in range(shared))
            / (shared - 1) * j / (j - 1) for r, m in zip(runs, means)]


def variance_split(runs):
    """`runs`: [(seed, [round seconds])]. The variance of a run's mean round
    time in three parts, by the method of moments for a nested design:

    - `within`: mean over runs of (the scatter of its rounds, the shared
      pattern taken out: `scatter_of_rounds`) / (its rounds): what the
      scatter between rounds leaves in a run's mean;
    - `process`: the pooled variance of run means among runs of one seed,
      less `within` (needs a seed with two runs or more, else None);
    - `seed`: the variance of the seeds' means, less what the two above leave
      in a seed's mean (needs two seeds or more, else None).

    None of the three is negative. Returns them with `mean` (the grand mean
    round time) and each part's share of their sum."""
    means = [statistics.fmean(rounds) for _, rounds in runs]
    within = [v / len(rounds) for v, (_, rounds) in
              zip(scatter_of_rounds([rounds for _, rounds in runs]), runs)]
    by_seed = {}
    for (seed, _), mean in zip(runs, means):
        by_seed.setdefault(seed, []).append(mean)
    w = statistics.fmean(within)
    groups = list(by_seed.values())
    repeated = [g for g in groups if len(g) > 1]
    pooled = process = seed_part = None
    if repeated:
        pooled = sum(sum((x - statistics.fmean(g)) ** 2 for x in g)
                     for g in repeated) / sum(len(g) - 1 for g in repeated)
        process = max(0.0, pooled - w)
    if len(groups) > 1:
        runs_per_seed = statistics.harmonic_mean([len(g) for g in groups])
        inside = (pooled if pooled is not None else w) / runs_per_seed
        seed_part = max(0.0, statistics.variance(
            [statistics.fmean(g) for g in groups]) - inside)
    parts = {"within": w, "process": process, "seed": seed_part}
    whole = sum(v for v in parts.values() if v)
    return {"mean": statistics.fmean(means), "runs": len(means),
            "seeds": len(groups), **parts,
            "share": {k: (v / whole if whole and v is not None else None)
                      for k, v in parts.items()}}


def read_set(text):
    """The runs in a set file's text: [{seed, metrics: {name: value},
    correct, failed, rounds: [..] or None, notes, compiles}]. A result's
    line is paired with the `notes` line before it."""
    runs, notes = [], None
    for raw in text.splitlines():
        raw = raw.strip()
        if not raw.startswith("{"):
            continue
        try:
            d = json.loads(raw)
        except ValueError:
            continue
        if "notes" in d and "workload" in d:
            notes = d
        elif "correct" in d and "metrics" in d:
            n = notes or {}
            runs.append({
                "seed": n.get("seed"), "correct": d["correct"],
                "failed": d["failed"],
                "metrics": {k: v["value"] for k, v in d["metrics"].items()},
                "rounds": n.get("notes", {}).get("round_s"),
                "notes": n.get("notes", {}),
                "compiles": n.get("counters", {}).get(
                    "compile_requests_in_window"),
            })
            notes = None
    return runs


def set_table(runs):
    """{metric: {n, median, range-1, iqr, iqr-1}} over a set's runs."""
    out = {}
    for name in runs[0]["metrics"] if runs else ():
        values = [r["metrics"][name] for r in runs if name in r["metrics"]]
        out[name] = {"n": len(values), "median": statistics.median(values),
                     "range-1": spread_range(values),
                     "iqr": spread_iqr(values),
                     "iqr-1": spread_iqr(values, leave_out_farthest=True)}
    return out


def run_line(r):
    """One run in a few numbers: where its time went and what the collector
    did inside the timed spans."""
    parts = [f"seed {r['seed']}"]
    parts += [f"{k} {v:.6g}" for k, v in r["metrics"].items()]
    n = r["notes"]
    if r["rounds"]:
        ms = sorted(x * 1e3 for x in r["rounds"])
        parts.append(f"rounds {len(ms)} (ms: min {ms[0]:.1f} median "
                     f"{statistics.median(ms):.1f} max {ms[-1]:.1f})")
    per_round = (("round_cpu_s", "cpu", "s"), ("round_wait_s", "device wait", "s"),
                 ("round_materialize_s", "materialize", "s"))
    for key, label, unit in per_round:
        if n.get(key):
            parts.append(f"{label} {sum(n[key]):.6g} {unit}".rstrip())
    if r["rounds"]:
        i = max(range(len(r["rounds"])), key=r["rounds"].__getitem__)
        parts.append(f"slowest round {i}: {r['rounds'][i] * 1e3:.1f} ms (" + ", ".join(
            f"{label} {n[key][i]:.4g}" for key, label, _ in per_round
            if n.get(key)) + ")")
    if "collector" in n:
        c = n["collector"]
        parts.append("collector " + " ".join(
            f"g{g}: {c['collections'][g]} in {c['seconds'][g]:.3f} s"
            for g in range(len(c["collections"]))))
    return "    " + ", ".join(parts)


def main(argv=None) -> int:
    paths = (sys.argv[1:] if argv is None else argv)
    if not paths:
        print(__doc__, file=sys.stderr)
        return 2
    everything = []
    for path in paths:
        with open(path) as f:
            runs = read_set(f.read())
        everything += runs
        bad = [r["seed"] for r in runs if not r["correct"] or r["failed"]]
        compiles = [r["compiles"] for r in runs if r["compiles"] is not None]
        print(f"{path}: {len(runs)} runs"
              + (f", NOT correct or failed: seeds {bad}" if bad else
                 ", all correct, 0 failed")
              + (f", {sum(compiles)} compile requests in the windows"
                 if compiles else ""))
        for name, row in set_table(runs).items():
            print(f"  {name}: n {row['n']}  median {row['median']:.6g}  "
                  f"range-1 {row['range-1']:.4%}  iqr {row['iqr']:.4%}  "
                  f"iqr-1 {row['iqr-1']:.4%}")
        for r in runs:
            print(run_line(r))
    timed = [(r["seed"], r["rounds"]) for r in everything if r["rounds"]]
    if timed:
        s = variance_split(timed)
        sd = {k: (None if s[k] is None else s[k] ** 0.5 / s["mean"])
              for k in ("within", "process", "seed")}
        print(f"variance of a run's mean round time ({s['runs']} runs, "
              f"{s['seeds']} seeds, mean round {s['mean'] * 1e3:.2f} ms), as "
              f"standard deviation over the mean and share of the sum:")
        for k, label in (
                ("within", "inside a run (rounds, less their shared pattern, "
                           "over the rounds of a run)"),
                ("process", "between processes of one seed"),
                ("seed", "between seeds" if sd["process"] is not None else
                         "between seeds, with what lies between processes "
                         "(no seed ran twice)")):
            if sd[k] is None:
                print(f"  {label}: not in these sets")
            else:
                print(f"  {label}: {sd[k]:.4%}  share {s['share'][k]:.1%}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
