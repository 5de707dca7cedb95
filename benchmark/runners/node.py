"""The node runner: one replica node whose ranges are split over `stores`
CommandStores (upstream's CommandStores with ShardDistributor.EvenSplit)
behind one BatchDepsResolver. `active` WITNESSED key-domain WRITEs are
registered in every store their keys fall in; a round issues `subjects` fresh
transactions at once through `node.command_stores.map_reduce_async`, with the
map function `Accept.process` uses (`store.calculate_deps_async` on the
store's slice of the keys, which registers nothing, so the stores are the same
in every round) and `Deps.union` as the reduce, and drains. A subject of this
runner is one transaction's merged reply; the program's `resolver.subjects`
counts store slices.

The timed window, the `notes` and the `bench.enqueue` span are the batch
runner's (`runners/batch.py`), so `noise.py` reads these runs too. The plain
reference is this file's own and knows nothing of stores, slices, arenas or
`Deps`: key -> registered ids, filled while registering; an answer is
compared as a set of (key, txn id), each pair once.
"""
from __future__ import annotations

import contextlib
import gc
import json
import shutil
import sys
import time

from benchmark import common, trace_programs, trace_reduce
from benchmark.runners.batch import ENQUEUE_SPAN, ROUND_TIMERS
from benchmark.runners.live import FullCollections

# an answer that came from a host scan, the legacy decode or a finalize lane
# that fell back is not this deployment: zero over the window
HOST_PATH_COUNTERS = ("resolver.host_fallbacks", "resolver.legacy_decodes",
                      "resolver.finalize_fallbacks")
# what the program has to keep for `correct` to be judged here (PR 35)
NODE_COUNTERS = ("fused_dispatches", "store_groups")
# the program's timers whose change over each round goes into `notes`
NODE_ROUND_TIMERS = {**ROUND_TIMERS, "round_fanout_s": "node.fanout_s",
                     "round_reduce_s": "node.reduce_s"}
# device programs summed apart from the slice, as the "XLA Modules" line
# names them: counter name -> programs
TRACED_PROGRAMS = {"fused_resolve_device_s": ("jit_fused_deps_resolve",),
                   "finalize_device_s": ("jit_finalize_csr",)}
# the out-cap policy shrinks a finalize lane's tier, and so asks for a new
# program, after 6 dispatches in a row that fit a smaller one
# (ops/tiers.py): the window opens after this many warm-up dispatches in a
# row that requested no compile
SETTLED_DISPATCHES = 6
MAX_WARM_ROUNDS = 12


def answer_set(deps):
    """The node's reply in the reference's terms, and how many (key, txn id)
    pairs it holds: where the two differ a pair came twice. None where there
    is no reply or it holds range dependencies."""
    if deps is None or not deps.range_deps.is_empty():
        return None, 0
    pairs = [(k, t) for k, ids in deps.key_deps.items() for t in ids]
    return set(pairs), len(pairs)


class Deployment:
    """The node on a one-node cluster, the resolver behind its stores, and
    the benchmark's reference of what is registered: key -> txn ids."""

    def __init__(self, p, seed):
        from accord_tpu.local.cfk import CfkStatus
        from accord_tpu.ops.resolver import BatchDepsResolver
        from accord_tpu.primitives.deps import Deps
        from accord_tpu.primitives.keyspace import Keys
        from accord_tpu.primitives.timestamp import Domain, TxnId, TxnKind
        from accord_tpu.sim.cluster import Cluster, ClusterConfig
        from accord_tpu.utils.rng import RandomSource

        self.p = p
        self.union = Deps.union
        self.resolver = BatchDepsResolver(num_buckets=p["buckets"],
                                          initial_cap=p["cap"],
                                          max_dispatch=p["max_dispatch"],
                                          kid_cap=p["kid_cap"])
        # the key domain is the keys drawn, so that the even split gives
        # every store its share of them; pad_store_tiers is what the cluster
        # derives from the store count
        self.cluster = Cluster(3, ClusterConfig(
            num_nodes=1, rf=1, stores_per_node=p["stores"], num_shards=1,
            key_domain=p["keys"], progress=False,
            deps_resolver_factory=lambda: self.resolver,
            deps_batch_window_ms=None))
        self.node = self.cluster.nodes[1]
        self.stores = self.node.command_stores
        for store in self.stores.all():
            store.batch_window_ms = p["batch_window_ms"]
        rng = RandomSource(seed)
        self.by_key = {}

        def fresh():
            ts = self.node.unique_now()
            txn_id = TxnId.create(ts.epoch, ts.hlc, ts.node, TxnKind.WRITE,
                                  Domain.KEY)
            raw = [rng.next_int(p["keys"]) for _ in range(p["keys_per_txn"])]
            return txn_id, Keys(raw), ts, raw

        self.fresh = fresh
        for _ in range(p["active"]):
            txn_id, keys, ts, raw = fresh()
            for store in self.stores.intersecting(keys):
                store.register(txn_id, keys, CfkStatus.WITNESSED, ts)
            for k in set(raw):
                self.by_key.setdefault(k, []).append(txn_id)

    def expected(self, keys, bound):
        """The plain reference: per key, the registered ids below the bound."""
        return {(k, x) for k in keys for x in self.by_key.get(k, ())
                if x < bound}

    def ask(self, txn_id, keys, bound):
        """One transaction's deps from the node: every store its keys fall
        in, asked as Accept.process asks, merged into one reply."""
        return self.stores.map_reduce_async(
            keys,
            lambda store: store.calculate_deps_async(
                txn_id, store.owned(keys), bound),
            self.union)

    def round(self, n, timed=None, watch=None):
        """Draw n fresh transactions, ask the node for each at once and drain
        (the timed part, inside `timed()` where given, and inside `watch`, a
        common.CollectorWatch), check every reply. Returns (resolve seconds,
        cpu seconds, wrong answers, failed replies, deps checked)."""
        subjects = [self.fresh() for _ in range(n)]
        answers = [None] * n
        failures = []

        def done(i):
            def on_done(value, failure):
                if failure is not None:
                    failures.append(failure)
                answers[i] = value
            return on_done

        with watch if watch is not None else contextlib.nullcontext():
            c0 = time.process_time()
            t0 = time.perf_counter()
            with timed() if timed is not None else contextlib.nullcontext():
                with common.host_span(ENQUEUE_SPAN):
                    for i, (t, keys, bound, _) in enumerate(subjects):
                        self.ask(t, keys, bound).add_callback(done(i))
                self.cluster.queue.drain(max_events=1_000_000)
            resolve_s = time.perf_counter() - t0
            cpu_s = time.process_time() - c0
        wrong = deps = 0
        for (t, keys, bound, raw), a in zip(subjects, answers):
            want = self.expected(raw, bound)
            deps += len(want)
            got, pairs = answer_set(a)
            wrong += got != want or pairs != len(want)
        return resolve_s, cpu_s, wrong, len(failures), deps

    def counters(self):
        return common.numeric(self.node.metrics_snapshot())

    def arenas(self):
        return [self.resolver._arena(s) for s in self.stores.all()]


def warm_kernels(p):
    """The deployment's start-up, as `serve/server.py` `warm_kernels` does
    it: the program's `warmup` at this node's shapes, one call an entry of
    the configuration's `warm` (its `warm_note` says where each tier comes
    from): the store tier of the node's store count, the batch tiers of a
    full and a tail dispatch, the CSR tiers of their key lists and of one
    store's finalize slots, the out-cap tier the stores' lanes pin. The
    checked rounds after it meet what is left (the arenas' first upload)."""
    from accord_tpu.ops.resolver import warmup
    for call in p["warm"]:
        warmup(num_buckets=p["buckets"], cap=p["cap"],
               batch_tiers=tuple(call["batch_tiers"]), scatter_tiers=(),
               nnz_tiers=tuple(call["nnz_tiers"]),
               store_tiers=(p["stores"],),
               out_tiers=tuple(call["out_tiers"]), range_out_tiers=(),
               kid_cap=p["kid_cap"])


def reduce_slice(fallback_window_s, dump_to=None):
    """`common.reduce_trace`, and the device time of each entry of
    TRACED_PROGRAMS read from the same slice before it is removed (as
    `trace_programs.reduce_slice` does for one list of programs)."""
    try:
        files = sorted(common.TRACE_DIR.glob("plugins/profile/*/*.xplane.pb"))
        if not files:
            return None, {}
        planes = trace_reduce.read_planes(str(files[-1]))
        if dump_to:
            with open(dump_to, "w") as f:
                json.dump(trace_reduce.describe(planes), f, indent=1)
        device_s = {name: trace_programs.device_seconds(planes, programs)
                    for name, programs in TRACED_PROGRAMS.items()}
        return (trace_reduce.reduce_planes(planes, fallback_window_s),
                {k: v for k, v in device_s.items() if v is not None})
    finally:
        shutil.rmtree(common.TRACE_DIR, ignore_errors=True)


def lacking():
    """What `correct` in this cell rests on and the program does not keep."""
    from accord_tpu.local.stores import CommandStores
    from accord_tpu.ops.resolver import BatchDepsResolver
    return [n for n in NODE_COUNTERS if not hasattr(BatchDepsResolver, n)] + \
        ([] if hasattr(CommandStores, "map_reduce_async")
         else ["map_reduce_async"])


def run(p, seed, seconds, trace, meter, dump_trace=None):
    missing = lacking()
    if missing:
        # a program from before PR 35: no one entry point to the node's
        # stores, none of the counters that say the fused path did the work
        print(f"benchmark: this program has no {missing}; the node cell "
              "cannot be judged on it; nothing was run", file=sys.stderr)
        raise SystemExit(4)
    # the node's start-up, as `serve/server.py` `run` does it: the program's
    # own setting of the collector, from start-up to shutdown (eight stores
    # keep millions of acyclic objects: a full collection is a stall that
    # frees nothing, `accord_tpu/utils/collector.py`)
    from accord_tpu.utils.collector import settled_collector
    with settled_collector():
        return serve(p, seed, seconds, trace, meter, dump_trace)


def serve(p, seed, seconds, trace, meter, dump_trace):
    watch = common.CollectorWatch()
    full = FullCollections(watch)
    gc.callbacks.extend((watch.on_collection, full.on_collection))
    node = Deployment(p, seed)
    n = p["subjects"]
    warm_kernels(p)
    # warm-up, untimed and checked: whole rounds until the dispatches of the
    # last rounds asked for no compile
    faults, warm_compiles, quiet = [], [], 0
    while quiet < SETTLED_DISPATCHES and len(warm_compiles) < MAX_WARM_ROUNDS:
        compiles, d0 = meter.requests, node.resolver.dispatches
        full.round = f"warm:{len(warm_compiles)}"
        _, _, wrong, failed, deps = node.round(n)
        warm_compiles.append(meter.requests - compiles)
        if wrong or failed or not deps:
            faults.append(f"warm-up round {len(warm_compiles)}: {wrong} "
                          f"wrong, {failed} failed")
        quiet = 0 if warm_compiles[-1] \
            else quiet + node.resolver.dispatches - d0
    registries = {"resolver": node.resolver.metrics, "node": node.node.metrics}
    timers = {k: registries[v.split(".")[0]].timer(v)
              for k, v in NODE_ROUND_TIMERS.items()}
    per_round = {"round_s": [], "round_cpu_s": [], **{k: [] for k in timers}}

    compiles_open = meter.requests
    before = node.counters()
    window_opened_at = time.perf_counter()
    resolve_s = cpu_s = traced_s = 0.0
    rounds = wrong = failed = deps = traced_dispatches = 0
    # a profiler slice of whole rounds in the middle of the window; only the
    # timed spans carry the benchmark's span, so the checks are outside it
    slice_s = min(p.get("trace_s", 3.0), seconds / 2) if trace else 0.0
    slice_state = "before" if trace else "closed"
    traced, traced_device_s = None, {}
    while resolve_s < seconds:
        if slice_state == "before" and resolve_s >= (seconds - slice_s) / 2:
            common.start_trace()
            slice_state, d0 = "open", node.resolver.dispatches
        in_slice = slice_state == "open"
        at = {k: t.total for k, t in timers.items()}
        full.round = f"window:{rounds}"
        r, c, w, f, d = node.round(
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
            traced_dispatches = node.resolver.dispatches - d0
            slice_state = "closed"
            traced, traced_device_s = reduce_slice(traced_s,
                                                   dump_to=dump_trace)
    for callback in (watch.on_collection, full.on_collection):
        gc.callbacks.remove(callback)
    after = node.counters()
    counters = common.delta(after, before)
    faults += common.counter_faults(after)
    host_path = {name: counters.get(name, 0) for name in HOST_PATH_COUNTERS}
    compiled = meter.requests - compiles_open
    dispatches = counters.get("resolver.dispatches", 0)
    fused_share = counters.get("resolver.fused_dispatches", 0) \
        / max(1, dispatches)
    slices = counters.get("node.store_slices", 0) \
        / max(1, counters.get("node.requests", 0))
    lo, hi = p["slices_per_txn"]
    if wrong or failed:
        faults.append(f"{wrong} wrong answers, {failed} failed replies of "
                      f"{rounds * n}")
    if not deps:
        faults.append("the reference found no dependency at all")
    if any(host_path.values()):
        faults.append(f"answers off the device path in the window: "
                      f"{host_path}")
    if compiled:
        faults.append(f"{compiled} compile requests inside the window")
    if fused_share < p["fused_share_min"]:
        faults.append(f"a fused cross-store program ran in {fused_share:.3f} "
                      f"of the dispatches, under {p['fused_share_min']}")
    if not lo <= slices <= hi:
        faults.append(f"{slices:.4f} store slices a transaction, outside "
                      f"{lo} to {hi}")
    counters.update(window_s=resolve_s, cpu_s=cpu_s, attempted=rounds * n,
                    rounds=rounds, deps_total=deps,
                    compile_requests_in_window=compiled,
                    **common.traced_counters(traced, traced_dispatches))
    if traced:
        counters.update(traced_device_s)
    arenas = node.arenas()
    return {
        "correct": not faults, "attempted": rounds * n, "failed": failed,
        "values": {"deps_resolved_per_s": rounds * n / resolve_s},
        "counters": counters, "traced": traced,
        "window_opened_at": window_opened_at,
        "notes": {"faults": faults, "rounds": rounds,
                  "deps_per_subject": deps / max(1, rounds * n),
                  "warm_compiles": warm_compiles,
                  "warm_settled": quiet >= SETTLED_DISPATCHES,
                  "pad_store_tiers": node.resolver.pad_store_tiers,
                  "arenas": {"cap": [a.cap for a in arenas],
                             "count": [a.count for a in arenas],
                             "kid_cap": [a.kid_cap for a in arenas]},
                  "device_id": node.resolver.device.id,
                  **per_round, "collector": watch.read(),
                  "full_collections": full.found,
                  "collections_since_start": full.runs},
        "compared": {
            "wrong_answers": [wrong, 0], "failed_replies": [failed, 0],
            "deps_checked_min": [deps, 1],
            **common.counter_comparisons(after),
            **{name: [v, 0] for name, v in host_path.items()},
            "compile_requests_in_window": [compiled, 0],
            "fused_dispatch_share_min": [fused_share, p["fused_share_min"]],
            "store_slices_per_txn_min": [slices, lo],
            "store_slices_per_txn_max": [slices, hi]},
    }
