"""The range runner: the batch runner's deployment with range transactions in
it. One CommandStore behind one BatchDepsResolver; `active` registered txns
resident, `range_active` of them range-domain (1 or 2 half-open ranges, READ
or WRITE) at positions of the registration order sampled from the seed, the
rest 4-key WRITEs; rounds of `subjects` fresh queries through the async
pipeline, each range-domain with probability `range_share`.

The timed window, the `notes` and the `bench.enqueue` span are the batch
runner's (`runners/batch.py`), so `noise.py` reads these runs too. The plain
reference is this file's own and imports nothing of the program's dependency
code: `by_key` (key -> registered key txns) and flat arrays of (start, end,
txn) for the registered ranges, tested by brute force, one vectorised
overlap test a subject. Answers are compared as sets of (key, txn id) for a
key subject and of (start, end, txn id) for a range subject, where a key txn
inside the subject's ranges is the point (key, None, txn id), as the program
delivers it in `range_deps`.
"""
from __future__ import annotations

import bisect
import contextlib
import gc
import json
import shutil
import time

import numpy as np

from benchmark import common, trace_reduce
from benchmark.runners.batch import ENQUEUE_SPAN, ROUND_TIMERS

# TxnKind.witnesses for the two kinds this deployment has, written out:
# WITNESSES[subject's kind][dependency's kind]. A read takes no read.
WITNESSES = {"R": {"R": False, "W": True},
             "W": {"R": True, "W": True}}
# an answer that came from a host scan or the legacy decode is not this
# deployment: zero over the window
HOST_PATH_COUNTERS = ("resolver.range_fallbacks", "resolver.host_fallbacks",
                      "resolver.legacy_decodes")
DEVICE_RANGE_DECODES = "resolver.range_subject_device_decodes"
# the device programs of the range path, as the "XLA Modules" line names them
RANGE_PROGRAMS = ("jit_range_deps_resolve", "jit_range_finalize_csr",
                  "jit_fused_range_deps_resolve")
# the out-cap policy shrinks a finalize lane's tier, and so asks for a new
# program, after 6 dispatches in a row that fit a smaller one
# (ops/tiers.py): the window opens after this many warm-up dispatches in a
# row that requested no compile
SETTLED_DISPATCHES = 6
MAX_WARM_ROUNDS = 40


def merged(pieces):
    """Half-open (start, end) pieces sorted and merged where they overlap or
    touch: what a set of ranges is once it is a set."""
    out = []
    for s, e in sorted(pieces):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


class Reference:
    """What is registered, and the exact dependency set of a subject by
    brute force. `add_*` in registration order, then `freeze()`."""

    def __init__(self):
        self.order = []     # every registered txn id, ascending
        self.by_key = {}    # key -> [(place in `order`, txn id, kind)]
        self.rows = []      # (start, end, place in `order`, txn id, kind)

    def add_key_txn(self, txn_id, kind, keys):
        place = len(self.order)
        self.order.append(txn_id)
        for k in set(keys):
            self.by_key.setdefault(k, []).append((place, txn_id, kind))

    def add_range_txn(self, txn_id, kind, pieces):
        place = len(self.order)
        self.order.append(txn_id)
        self.rows += [(s, e, place, txn_id, kind) for s, e in merged(pieces)]

    def freeze(self):
        assert all(a < b for a, b in zip(self.order, self.order[1:])), \
            "the registered ids do not ascend in registration order"
        self.r_start = np.array([r[0] for r in self.rows], np.int64)
        self.r_end = np.array([r[1] for r in self.rows], np.int64)
        self.r_place = np.array([r[2] for r in self.rows], np.int64)
        self.r_id = [r[3] for r in self.rows]
        self.r_witnessed_by = {
            kind: np.array([WITNESSES[kind][r[4]] for r in self.rows], bool)
            for kind in WITNESSES}

    def key_txns(self, key, kind, places_below):
        return [t for place, t, dep_kind in self.by_key.get(key, ())
                if place < places_below and WITNESSES[kind][dep_kind]]

    def expected(self, domain, kind, what, bound):
        """`what`: the keys of a key subject, or the merged pieces of a range
        subject. The set the answer has to equal."""
        places_below = bisect.bisect_left(self.order, bound)
        seen = (self.r_place < places_below) & self.r_witnessed_by[kind]
        want = set()
        if domain == "key":
            keys = np.array(sorted(set(what)), np.int64)
            hit = (self.r_start[None, :] <= keys[:, None]) \
                & (keys[:, None] < self.r_end[None, :]) & seen[None, :]
            for i, row in zip(*np.nonzero(hit)):
                want.add((int(keys[i]), self.r_id[row]))
            for k in keys.tolist():
                want.update((k, t)
                            for t in self.key_txns(k, kind, places_below))
            return want
        for s, e in what:
            hit = (self.r_start < e) & (s < self.r_end) & seen
            for row in np.nonzero(hit)[0]:
                want.add((max(s, int(self.r_start[row])),
                          min(e, int(self.r_end[row])), self.r_id[row]))
            for k in range(s, e):
                want.update((k, None, t)
                            for t in self.key_txns(k, kind, places_below))
        return want


def answer_set(domain, deps):
    """The program's answer in the reference's terms; None where it holds
    dependencies in the other domain's half of `Deps`."""
    if deps is None:
        return None
    if domain == "key":
        if not deps.range_deps.is_empty():
            return None
        return {(k, t) for k, ids in deps.key_deps.items() for t in ids}
    if not deps.key_deps.is_empty():
        return None
    return {(r.start, r.end if isinstance(r.end, int) else None, t)
            for r, ids in deps.range_deps.items() for t in ids}


class Arena:
    """The store, its resolver, and the reference of what is registered."""

    def __init__(self, p, seed):
        from accord_tpu.local.cfk import CfkStatus
        from accord_tpu.ops.resolver import BatchDepsResolver
        from accord_tpu.sim.cluster import Cluster, ClusterConfig
        from accord_tpu.utils.rng import RandomSource

        self.resolver = BatchDepsResolver(num_buckets=p["buckets"],
                                          initial_cap=p["cap"],
                                          max_dispatch=p["max_dispatch"])
        self.cluster = Cluster(3, ClusterConfig(
            num_nodes=1, rf=1, stores_per_node=1, num_shards=1, progress=False,
            deps_resolver_factory=lambda: self.resolver,
            deps_batch_window_ms=None))
        self.node = self.cluster.nodes[1]
        self.store = self.node.command_stores.all()[0]
        self.store.batch_window_ms = p["batch_window_ms"]
        self.range_share = p["range_share"]
        self._rng = rng = RandomSource(seed)
        self._p = p
        self.reference = ref = Reference()
        range_at = set(rng.sample(range(p["active"]), p["range_active"]))
        for place in range(p["active"]):
            txn_id, seekables, ts, (domain, kind, what) = \
                self.fresh_range_txn() if place in range_at \
                else self.fresh_key_txn()
            self.store.register(txn_id, seekables, CfkStatus.WITNESSED, ts)
            if domain == "key":
                ref.add_key_txn(txn_id, kind, what)
            else:
                ref.add_range_txn(txn_id, kind, what)
        ref.freeze()

    def _txn_id(self, kind, domain):
        from accord_tpu.primitives.timestamp import TxnId, TxnKind
        ts = self.node.unique_now()
        return TxnId.create(ts.epoch, ts.hlc, ts.node,
                            {"R": TxnKind.READ, "W": TxnKind.WRITE}[kind],
                            domain), ts

    def fresh_key_txn(self, kind="W"):
        """(txn id, Keys, timestamp, what the reference needs). The
        deployment's key txns are all WRITEs."""
        from accord_tpu.primitives.keyspace import Keys
        from accord_tpu.primitives.timestamp import Domain
        txn_id, ts = self._txn_id(kind, Domain.KEY)
        keys = [self._rng.next_int(self._p["keys"])
                for _ in range(self._p["keys_per_txn"])]
        return txn_id, Keys(keys), ts, ("key", kind, keys)

    def fresh_range_txn(self, kind=None):
        """The same for a range txn: READ or WRITE at equal odds unless
        given, `ranges_per_txn` ranges, each of width uniform in
        1..`max_range_width` with its start uniform in [0, keys - width]."""
        from accord_tpu.primitives.keyspace import Range, Ranges
        from accord_tpu.primitives.timestamp import Domain
        rng, p = self._rng, self._p
        if kind is None:
            kind = "R" if rng.next_bool() else "W"
        txn_id, ts = self._txn_id(kind, Domain.RANGE)
        lo, hi = p["ranges_per_txn"]
        pieces = []
        for _ in range(rng.next_int_between(lo, hi + 1)):
            width = 1 + rng.next_int(p["max_range_width"])
            start = rng.next_int(p["keys"] - width + 1)
            pieces.append((start, start + width))
        return txn_id, Ranges(Range(s, e) for s, e in pieces), ts, \
            ("range", kind, merged(pieces))

    def draw(self, n):
        """n fresh subjects, each range-domain with probability
        `range_share`: (txn id, owned seekables, bound, reference's spec)."""
        return [(t, self.store.owned(s), ts, spec) for t, s, ts, spec in (
            self.fresh_range_txn() if self._rng.decide(self.range_share)
            else self.fresh_key_txn() for _ in range(n))]

    def resolve(self, subjects, timed=None, watch=None):
        """Enqueue the subjects at once and drain (the timed part, inside
        `timed()` where given and inside `watch`, a common.CollectorWatch).
        Returns (answers, failures, resolve seconds, cpu seconds)."""
        answers = [None] * len(subjects)
        failures = []

        def done(i):
            def on_done(value, failure):
                if failure is not None:
                    failures.append(failure)
                answers[i] = value
            return on_done

        enqueue = self.resolver.enqueue_deps
        with watch if watch is not None else contextlib.nullcontext():
            c0 = time.process_time()
            t0 = time.perf_counter()
            with timed() if timed is not None else contextlib.nullcontext():
                with common.host_span(ENQUEUE_SPAN):
                    for i, (t, owned, bound, _) in enumerate(subjects):
                        enqueue(self.store, t, owned,
                                bound).add_callback(done(i))
                self.cluster.queue.drain(max_events=1_000_000)
            resolve_s = time.perf_counter() - t0
            cpu_s = time.process_time() - c0
        return answers, failures, resolve_s, cpu_s

    def check(self, subjects, answers):
        """Every answer against the reference. By domain: subjects, wrong
        answers and dependencies checked; and the range-vs-range
        dependencies among them."""
        out = {"range_range_deps": 0,
               "subjects": {"key": 0, "range": 0},
               "wrong": {"key": 0, "range": 0},
               "deps": {"key": 0, "range": 0}}
        for (_, _, bound, (domain, kind, what)), a in zip(subjects, answers):
            want = self.reference.expected(domain, kind, what, bound)
            out["subjects"][domain] += 1
            out["deps"][domain] += len(want)
            out["wrong"][domain] += answer_set(domain, a) != want
            if domain == "range":
                out["range_range_deps"] += sum(
                    1 for x in want if x[1] is not None)
        return out

    def round(self, n, timed=None, watch=None):
        """Draw n fresh subjects, resolve them, check every answer."""
        subjects = self.draw(n)
        answers, failures, resolve_s, cpu_s = self.resolve(subjects, timed,
                                                           watch)
        return dict(self.check(subjects, answers), resolve_s=resolve_s,
                    cpu_s=cpu_s, failed=len(failures))

    def counters(self):
        return common.numeric(self.resolver.metrics.snapshot())


def warm_kernels(p):
    """The deployment's start-up, as `serve/server.py` `warm_kernels` does
    it: the program's `warmup` over the rk lane's finalize shapes the
    configuration lists under `warm`. That lane's slot count (range
    subjects x keys covered, a dispatch) and its out-cap estimate each
    straddle a step of their ladders at this population, so a dispatch
    would else meet one of the sizes for the first time inside the
    window."""
    from accord_tpu.ops.resolver import warmup
    warmup(num_buckets=p["buckets"], cap=p["cap"],
           batch_tiers=(p["max_dispatch"],), scatter_tiers=(),
           nnz_tiers=tuple(p["warm"]["slot_tiers"]), store_tiers=(1,),
           out_tiers=tuple(p["warm"]["out_tiers"]), range_out_tiers=())


def range_device_seconds(planes):
    """Device time of the range programs' operations inside the benchmark's
    window spans, averaged over the device planes; None where the trace has
    no device plane."""
    windows, devices = [], []
    for plane, lines in planes:
        by_line = dict(lines)
        if plane.startswith(trace_reduce.DEVICE_PLANE):
            devices.append(trace_reduce.short_names(
                by_line.get(trace_reduce.OPS_LINE, ()),
                by_line.get(trace_reduce.MODULES_LINE, ())))
            continue
        for _, events in lines:
            windows += [[s, s + d] for n, s, d in events
                        if n == trace_reduce.WINDOW_SPAN]
    devices = [ev for ev in devices if ev]
    if not devices or not windows:
        return None
    windows = trace_reduce.union(windows)
    busy = [trace_reduce.total(trace_reduce.clip(trace_reduce.union(
        [s, s + d] for name, s, d in events
        if name.split(":")[0] in RANGE_PROGRAMS), windows))
        for events in devices]
    return sum(busy) / len(busy) / 1e9


def reduce_slice(fallback_window_s, dump_to=None):
    """`common.reduce_trace`, and the range programs' device time read from
    the same slice before it is removed."""
    try:
        files = sorted(common.TRACE_DIR.glob("plugins/profile/*/*.xplane.pb"))
        if not files:
            return None, None
        planes = trace_reduce.read_planes(str(files[-1]))
        if dump_to:
            with open(dump_to, "w") as f:
                json.dump(trace_reduce.describe(planes), f, indent=1)
        return (trace_reduce.reduce_planes(planes, fallback_window_s),
                range_device_seconds(planes))
    finally:
        shutil.rmtree(common.TRACE_DIR, ignore_errors=True)


def run(p, seed, seconds, trace, meter, dump_trace=None):
    arena = Arena(p, seed)
    n = p["subjects"]
    warm_kernels(p)
    # warm-up, untimed and checked: rounds of one full dispatch (and the
    # round's short last one, where it has one), so every shape of a round
    # is met; the first compiles, and the out-cap tiers leave their cold
    # sizes over the next ones
    warm_n = min(n, p["max_dispatch"] + n % p["max_dispatch"])
    faults, warm_compiles, quiet = [], [], 0
    while quiet < SETTLED_DISPATCHES and len(warm_compiles) < MAX_WARM_ROUNDS:
        compiles, d0 = meter.requests, arena.resolver.dispatches
        r = arena.round(warm_n)
        warm_compiles.append(meter.requests - compiles)
        wrong = sum(r["wrong"].values())
        if wrong or r["failed"] or not any(r["deps"].values()):
            faults.append(f"warm-up round {len(warm_compiles)}: {wrong} "
                          f"wrong, {r['failed']} failed, deps {r['deps']}")
        quiet = 0 if warm_compiles[-1] \
            else quiet + arena.resolver.dispatches - d0
    watch = common.CollectorWatch()
    gc.callbacks.append(watch.on_collection)
    timers = {k: arena.resolver.metrics.timer(v)
              for k, v in ROUND_TIMERS.items()}
    per_round = {"round_s": [], "round_cpu_s": [], **{k: [] for k in timers}}

    compiles_open = meter.requests
    before = arena.counters()
    window_opened_at = time.perf_counter()
    resolve_s = cpu_s = traced_s = 0.0
    traced_dispatches = 0
    checked = []  # what each round's check returned
    # a profiler slice of whole rounds in the middle of the window; only the
    # timed spans carry the benchmark's span, so the checks are outside it
    slice_s = min(p.get("trace_s", 3.0), seconds / 2) if trace else 0.0
    slice_state = "before" if trace else "closed"
    traced = range_device_s = None
    while resolve_s < seconds:
        if slice_state == "before" and resolve_s >= (seconds - slice_s) / 2:
            common.start_trace()
            slice_state, d0 = "open", arena.resolver.dispatches
        in_slice = slice_state == "open"
        at = {k: t.total for k, t in timers.items()}
        r = arena.round(n, timed=common.window_span if in_slice else None,
                        watch=watch)
        per_round["round_s"].append(r["resolve_s"])
        per_round["round_cpu_s"].append(r["cpu_s"])
        for k, t in timers.items():
            per_round[k].append(t.total - at[k])
        resolve_s, cpu_s = resolve_s + r["resolve_s"], cpu_s + r["cpu_s"]
        checked.append(r)
        traced_s += r["resolve_s"] if in_slice else 0.0
        if in_slice and (traced_s >= slice_s or resolve_s >= seconds):
            common.stop_trace()
            traced_dispatches = arena.resolver.dispatches - d0
            slice_state = "closed"
            traced, range_device_s = reduce_slice(traced_s,
                                                  dump_to=dump_trace)
    gc.callbacks.remove(watch.on_collection)
    rounds = len(checked)
    failed = sum(r["failed"] for r in checked)
    range_range_deps = sum(r["range_range_deps"] for r in checked)
    subjects, wrong, deps = (
        {d: sum(r[what][d] for r in checked) for d in ("key", "range")}
        for what in ("subjects", "wrong", "deps"))
    after = arena.counters()
    counters = common.delta(after, before)
    faults += common.counter_faults(after)
    host_path = {name: counters.get(name, 0) for name in HOST_PATH_COUNTERS}
    device_decodes = counters.get(DEVICE_RANGE_DECODES, 0)
    if any(wrong.values()) or failed:
        faults.append(f"wrong answers {wrong}, {failed} failed resolutions "
                      f"of {rounds * n}")
    if not all(deps.values()):
        faults.append(f"the reference found no dependency in a domain: {deps}")
    if any(host_path.values()):
        faults.append(f"answers off the device path in the window: "
                      f"{host_path}")
    if subjects["range"] and not device_decodes:
        faults.append("no range subject was decoded from the device lanes")
    counters.update(window_s=resolve_s, cpu_s=cpu_s, attempted=rounds * n,
                    rounds=rounds, deps_total=sum(deps.values()),
                    compile_requests_in_window=meter.requests - compiles_open,
                    **common.traced_counters(traced, traced_dispatches))
    if traced and range_device_s is not None:
        counters["range_device_s"] = range_device_s
    return {
        "correct": not faults, "attempted": rounds * n, "failed": failed,
        "values": {"deps_resolved_per_s": rounds * n / resolve_s},
        "counters": counters, "traced": traced,
        "window_opened_at": window_opened_at,
        "notes": {"faults": faults, "rounds": rounds,
                  "warm_subjects": warm_n, "warm_compiles": warm_compiles,
                  "warm_settled": quiet >= SETTLED_DISPATCHES,
                  "compile_requests_in_window": [
                      counters["compile_requests_in_window"], 0],
                  "subjects": subjects,
                  "deps_per_subject": {
                      d: deps[d] / max(1, subjects[d]) for d in deps},
                  "range_range_deps_reference": range_range_deps,
                  "device_id": arena.resolver.device.id,
                  **per_round, "collector": watch.read()},
        "compared": {
            "wrong_answers": [sum(wrong.values()), 0],
            "wrong_key_answers": [wrong["key"], 0],
            "wrong_range_answers": [wrong["range"], 0],
            "failed_resolutions": [failed, 0],
            "deps_checked_min": [min(deps.values()), 1],
            **common.counter_comparisons(after),
            **{name: [v, 0] for name, v in host_path.items()},
            DEVICE_RANGE_DECODES: [device_decodes,
                                   1 if subjects["range"] else 0]},
    }
