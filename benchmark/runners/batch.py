"""The batch runner: one CommandStore behind one BatchDepsResolver, `active`
registered txns resident in the device arena, rounds of `subjects` fresh
queries through the async pipeline. `chip_smoke.py` `leg_store` (commit
ee317a3) with a timed window round the middle and the benchmark's own
reference in place of the program's host scan.

Only the enqueue-to-last-callback span of each round is timed; rounds repeat
until those spans add up to `seconds`, so the window is `seconds` of resolve
time and the check between rounds is in none of it.
"""
from __future__ import annotations

import contextlib
import gc
import time

from benchmark import common

ENQUEUE_SPAN = "bench.enqueue"  # the runner's enqueue loop, for gap labels
# the program's timers whose change over each round goes into `notes`
ROUND_TIMERS = {"round_wait_s": "resolver.device_wait_s",
                "round_materialize_s": "resolver.materialize_s"}


class Arena:
    """The store, its resolver, and the benchmark's reference of what is
    registered: key -> txn ids, filled while registering."""

    def __init__(self, p, seed):
        from accord_tpu.local.cfk import CfkStatus
        from accord_tpu.ops.resolver import BatchDepsResolver
        from accord_tpu.primitives.keyspace import Keys
        from accord_tpu.primitives.timestamp import Domain, TxnId, TxnKind
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
        rng = RandomSource(seed)
        self.by_key = {}

        def fresh():
            ts = self.node.unique_now()
            txn_id = TxnId.create(ts.epoch, ts.hlc, ts.node, TxnKind.WRITE,
                                  Domain.KEY)
            keys = [rng.next_int(p["keys"]) for _ in range(p["keys_per_txn"])]
            return txn_id, Keys(keys), ts, keys

        self.fresh = fresh
        for _ in range(p["active"]):
            txn_id, keys, ts, raw = fresh()
            self.store.register(txn_id, keys, CfkStatus.WITNESSED, ts)
            for k in set(raw):
                self.by_key.setdefault(k, []).append(txn_id)

    def expected(self, keys, bound):
        """The plain reference: per key, the registered ids below the bound."""
        return {x for k in keys for x in self.by_key.get(k, ()) if x < bound}

    def round(self, n, timed=None, watch=None):
        """Draw n fresh subjects, resolve them (the timed part, inside
        `timed()` where given, and inside `watch`, a common.CollectorWatch,
        which counts the collector's work in it), check every answer. Returns (resolve seconds, cpu seconds, wrong answers, failed
        resolutions, deps checked)."""
        subjects = [(t, self.store.owned(k), ts, raw)
                    for t, k, ts, raw in (self.fresh() for _ in range(n))]
        answers = [None] * n
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
        wrong = deps = 0
        for (t, owned, bound, raw), a in zip(subjects, answers):
            want = self.expected(raw, bound)
            deps += len(want)
            wrong += a is None or set(a.key_deps.all_txn_ids()) != want
        return resolve_s, cpu_s, wrong, len(failures), deps

    def counters(self):
        return common.numeric(self.resolver.metrics.snapshot())


def run(p, seed, seconds, trace, meter, dump_trace=None):
    arena = Arena(p, seed)
    n = p["subjects"]
    _, _, wrong, failed, deps = arena.round(n)  # warm-up: compiles, untimed
    faults = [f"warm-up round: {wrong} wrong, {failed} failed"] \
        if wrong or failed or not deps else []
    watch = common.CollectorWatch()
    gc.callbacks.append(watch.on_collection)
    timers = {k: arena.resolver.metrics.timer(v)
              for k, v in ROUND_TIMERS.items()}
    per_round = {"round_s": [], "round_cpu_s": [], **{k: [] for k in timers}}

    compiles_open = meter.requests
    before = arena.counters()
    window_opened_at = time.perf_counter()
    resolve_s = cpu_s = traced_s = 0.0
    rounds = wrong = failed = deps = traced_dispatches = 0
    # a profiler slice of whole rounds in the middle of the window; only the
    # timed spans carry the benchmark's span, so the checks are outside it
    slice_s = min(p.get("trace_s", 3.0), seconds / 2) if trace else 0.0
    slice_state = "before" if trace else "closed"
    traced = None
    while resolve_s < seconds:
        if slice_state == "before" and resolve_s >= (seconds - slice_s) / 2:
            common.start_trace()
            slice_state, d0 = "open", arena.resolver.dispatches
        in_slice = slice_state == "open"
        at = {k: t.total for k, t in timers.items()}
        r, c, w, f, d = arena.round(
            n, timed=common.window_span if in_slice else None, watch=watch)
        per_round["round_s"].append(r)
        per_round["round_cpu_s"].append(c)
        for k, t in timers.items():
            per_round[k].append(t.total - at[k])
        resolve_s, cpu_s, rounds = resolve_s + r, cpu_s + c, rounds + 1
        wrong, failed, deps = wrong + w, failed + f, deps + d
        traced_s += r if in_slice else 0.0
        if in_slice and (traced_s >= slice_s or resolve_s >= seconds):
            common.stop_trace()
            traced_dispatches = arena.resolver.dispatches - d0
            slice_state = "closed"
            traced = common.reduce_trace(traced_s, dump_to=dump_trace)
    gc.callbacks.remove(watch.on_collection)
    after = arena.counters()
    counters = common.delta(after, before)
    faults += common.counter_faults(after)
    if wrong or failed:
        faults.append(f"{wrong} wrong answers, {failed} failed resolutions "
                      f"of {rounds * n}")
    if not deps:
        faults.append("the reference found no dependency at all")
    counters.update(window_s=resolve_s, cpu_s=cpu_s, attempted=rounds * n,
                    rounds=rounds, deps_total=deps,
                    compile_requests_in_window=meter.requests - compiles_open,
                    **common.traced_counters(traced, traced_dispatches))
    return {
        "correct": not faults, "attempted": rounds * n, "failed": failed,
        "values": {"deps_resolved_per_s": rounds * n / resolve_s},
        "counters": counters, "traced": traced,
        "window_opened_at": window_opened_at,
        "notes": {"faults": faults, "rounds": rounds,
                  "deps_per_subject": deps / max(1, rounds * n),
                  "device_id": arena.resolver.device.id,
                  **per_round, "collector": watch.read()},
        "compared": {
            "wrong_answers": [wrong, 0], "failed_resolutions": [failed, 0],
            "deps_checked_min": [deps, 1],
            **common.counter_comparisons(after)},
    }
