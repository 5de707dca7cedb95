"""The node-ranges runner: the node runner's replica node (`runners/node.py`:
one node whose ranges are split over `stores` CommandStores by EvenSplit,
behind one BatchDepsResolver) with the range runner's transactions in it
(`runners/ranges.py`). `active` WITNESSED residents, exactly `range_active`
of them range-domain (1 or 2 half-open ranges, READ or WRITE) at positions
of the registration order drawn from the seed, the rest 4-key WRITEs, each
registered in every store it intersects; a round asks the node for
`subjects` fresh transactions at once, exactly `range_subjects` of them
range-domain at positions drawn from the seed, through
`node.command_stores.map_reduce_async` with the map function
`Accept.process` uses (`store.calculate_deps_async` on the store's slice,
which registers nothing) and `Deps.union` as the reduce, and drains. A
subject is one transaction's merged reply; `resolver.subjects` counts store
slices.

The plain reference (`Reference`, flat arrays as `ranges.Reference` keeps
them) imports nothing of the program's dependency code and knows nothing of
stores or slices. An answer is compared in terms that do not depend on where the stores cut the key
space: a key subject as the set of (key, txn id); a range subject per
dependency txn, its pieces (a key txn's keys as points [k, k+1)) merged,
against the reference's intersections merged. A (key, txn) answered twice,
or two overlapping pieces of one txn, is wrong: the merge double-counted.
"""
from __future__ import annotations

import bisect
import contextlib
import gc
import json
import shutil
import sys
import time

import numpy as np

from benchmark import common, trace_programs, trace_reduce
from benchmark.runners import node, ranges
from benchmark.runners.batch import ENQUEUE_SPAN
from benchmark.runners.live import FullCollections

# an answer from a host scan, the legacy decode or a finalize lane that fell
# back is not this deployment: zero over the window
HOST_PATH_COUNTERS = ("resolver.host_fallbacks", "resolver.range_fallbacks",
                      "resolver.legacy_decodes", "resolver.finalize_fallbacks")
# what the program has to keep for `correct` to be judged here: the range
# call's own fused count in the resolver, the Ranges requests in the node's
# fan-out
RESOLVER_COUNTERS = ("range_dispatches", "fused_range_dispatches",
                     "fused_range_groups")
NODE_COUNTERS = ("node.range_requests", "node.range_store_slices")
# the program's timers whose change over each round goes into `notes`
ROUND_TIMERS = {**node.NODE_ROUND_TIMERS,
                "round_range_encode_s": "resolver.range_encode_s",
                "round_range_decode_s": "resolver.range_decode_s"}
# device programs summed apart from the slice, as the "XLA Modules" line
# names them: counter name -> programs
TRACED_PROGRAMS = {**node.TRACED_PROGRAMS,
                   "fused_range_device_s": ("jit_fused_range_deps_resolve",),
                   "range_finalize_device_s": ("jit_range_finalize_csr",),
                   "range_device_s": ranges.RANGE_PROGRAMS}


class Reference:
    """What is registered, and the exact dependency set of a subject, as
    flat arrays: `by_key` (key -> its registered key txns, a CSR over keys)
    and (start, end, txn, kind) for the registered ranges, which a range
    subject's pieces test by brute force, one vectorised overlap test a
    piece (as `ranges.Reference` does), and which a key subject reads as
    `cover` (key -> the range txns whose ranges contain it, a CSR over keys
    unrolled from the same rows). A txn is its place in the registration
    order. `add_*` in registration order, then `freeze(keys)`."""

    def __init__(self):
        self.order = []     # every registered txn id, ascending
        self.writes = []    # place -> is the txn a WRITE
        self.key_pairs = []  # (key, place) of every key txn
        self.rows = []      # (start, end, place) of every range txn

    def _add(self, txn_id, kind):
        self.order.append(txn_id)
        self.writes.append(kind == "W")
        return len(self.order) - 1

    def add_key_txn(self, txn_id, kind, keys):
        place = self._add(txn_id, kind)
        self.key_pairs += [(k, place) for k in set(keys)]

    def add_range_txn(self, txn_id, kind, pieces):
        place = self._add(txn_id, kind)
        self.rows += [(s, e, place) for s, e in ranges.merged(pieces)]

    def freeze(self, keys):
        assert all(a < b for a, b in zip(self.order, self.order[1:])), \
            "the registered ids do not ascend in registration order"
        self.place_of = {t: i for i, t in enumerate(self.order)}
        self.stride = len(self.order) + 1
        self.is_write = np.array(self.writes, bool)
        pairs = np.array(sorted(self.key_pairs), np.int64).reshape(-1, 2)
        self.kt_place = pairs[:, 1]
        self.kt_indptr = np.searchsorted(pairs[:, 0], np.arange(keys + 1))
        rows = np.array(self.rows, np.int64).reshape(-1, 3)
        self.r_start, self.r_end, self.r_place = rows.T
        width = self.r_end - self.r_start
        first = np.repeat(np.cumsum(width) - width, width)
        key = np.repeat(self.r_start, width) + np.arange(width.sum()) - first
        order = np.argsort(key, kind="stable")
        self.cover_place = np.repeat(self.r_place, width)[order]
        self.cover_indptr = np.searchsorted(key[order], np.arange(keys + 1))

    def _seen(self, kind, places, bound):
        """The places a subject of `kind` below `bound` witnesses: what is
        registered before the bound, and a read takes no read."""
        below = places < bisect.bisect_left(self.order, bound)
        return below & (self.is_write[places] | (kind == "W"))

    def expected_key(self, kind, keys, bound):
        """A key subject: sorted codes key * stride + place."""
        key, place = [], []
        for k in set(keys):
            for indptr, places in ((self.kt_indptr, self.kt_place),
                                   (self.cover_indptr, self.cover_place)):
                place.append(places[indptr[k]:indptr[k + 1]])
                key.append(np.full(len(place[-1]), k, np.int64))
        key, place = np.concatenate(key), np.concatenate(place)
        keep = self._seen(kind, place, bound)
        return np.sort(key[keep] * self.stride + place[keep])

    def expected_range(self, kind, pieces, bound):
        """A range subject: per dependency txn its intersections with the
        subject's merged pieces (a key txn's keys as points [k, k+1)),
        merged; as `segments` gives them."""
        place, start, end = [], [], []
        for s, e in pieces:
            hit = np.nonzero((self.r_start < e) & (s < self.r_end))[0]
            place.append(self.r_place[hit])
            start.append(np.maximum(s, self.r_start[hit]))
            end.append(np.minimum(e, self.r_end[hit]))
            lo, hi = self.kt_indptr[s], self.kt_indptr[e]
            place.append(self.kt_place[lo:hi])
            k = np.repeat(np.arange(s, e),
                          np.diff(self.kt_indptr[s:e + 1]))
            start.append(k)
            end.append(k + 1)
        place, start, end = (np.concatenate(x) for x in (place, start, end))
        keep = self._seen(kind, place, bound)
        return segments(place[keep], start[keep], end[keep])

    def places(self, txn_ids):
        """The places of a reply's txn ids; -1 for one never registered."""
        return np.array([self.place_of.get(t, -1) for t in txn_ids], np.int64)


def segments(place, start, end):
    """(place, start, end) pieces -> the same merged per place where they
    touch, sorted; None where two pieces of one place overlap (touching is
    a store's cut, overlapping a double count)."""
    if not len(place):
        return place, start, end
    order = np.lexsort((start, place))
    place, start, end = place[order], start[order], end[order]
    tie = place[1:] == place[:-1]
    if np.any(tie & (start[1:] < end[:-1])):
        return None
    new = np.concatenate([[True], ~tie | (start[1:] > end[:-1])])
    last = np.concatenate([np.nonzero(new)[0][1:] - 1, [len(place) - 1]])
    return place[new], start[new], end[last]


def key_answer(reference, deps):
    """A key subject's reply as sorted codes key * stride + place; None
    where there is no reply or it holds range deps. A pair answered twice
    stays twice."""
    if deps is None or not deps.range_deps.is_empty():
        return None
    kd = deps.key_deps
    places = reference.places(kd.txn_ids)
    keys = np.repeat(np.array(kd.keys, np.int64), np.diff(kd.offsets))
    return np.sort(keys * reference.stride
                   + places[np.array(kd.value_idx, np.int64)])


def range_answer(reference, deps):
    """A range subject's reply as `segments` gives it (a key txn's point
    [k, k+1)); None where there is no reply, it holds key deps or two
    pieces of one txn overlap."""
    if deps is None or not deps.key_deps.is_empty():
        return None
    rd = deps.range_deps
    places = reference.places(rd.txn_ids)
    n = np.diff(rd.offsets)
    start = np.array([r.start for r in rd.ranges], np.int64)
    end = np.array([r.end if isinstance(r.end, int) else r.start + 1
                    for r in rd.ranges], np.int64)
    return segments(places[np.array(rd.value_idx, np.int64)],
                    np.repeat(start, n), np.repeat(end, n))


def same(got, want):
    """A reply in the reference's terms equals the reference's."""
    if got is None or want is None:
        return got is want
    if isinstance(want, tuple):
        return all(np.array_equal(g, w) for g, w in zip(got, want))
    return np.array_equal(got, want)


class Deployment:
    """The node on a one-node cluster, the resolver behind its stores, and
    the reference of what is registered."""

    # the range runner's draws, which read `node`, `_rng` and `_p`
    _txn_id = ranges.Arena._txn_id
    fresh_key_txn = ranges.Arena.fresh_key_txn
    fresh_range_txn = ranges.Arena.fresh_range_txn

    def __init__(self, p, seed):
        from accord_tpu.local.cfk import CfkStatus
        from accord_tpu.ops.resolver import BatchDepsResolver
        from accord_tpu.primitives.deps import Deps
        from accord_tpu.sim.cluster import Cluster, ClusterConfig
        from accord_tpu.utils.rng import RandomSource

        self._p = p
        self.union = Deps.union
        # one range capacity for every store: the fused range program is
        # compiled for the capacities of the arenas it is given
        self.resolver = BatchDepsResolver(num_buckets=p["buckets"],
                                          initial_cap=p["cap"],
                                          max_dispatch=p["max_dispatch"],
                                          kid_cap=p["kid_cap"],
                                          initial_range_cap=p["range_cap"])
        self.cluster = Cluster(3, ClusterConfig(
            num_nodes=1, rf=1, stores_per_node=p["stores"], num_shards=1,
            key_domain=p["keys"], progress=False,
            deps_resolver_factory=lambda: self.resolver,
            deps_batch_window_ms=None))
        self.node = self.cluster.nodes[1]
        self.stores = self.node.command_stores
        for store in self.stores.all():
            store.batch_window_ms = p["batch_window_ms"]
        self._rng = RandomSource(seed)
        self.reference = ref = Reference()
        range_at = set(self._rng.sample(range(p["active"]),
                                        p["range_active"]))
        for place in range(p["active"]):
            txn_id, seekables, ts, (domain, kind, what) = \
                self.fresh_range_txn() if place in range_at \
                else self.fresh_key_txn()
            for store in self.stores.intersecting(seekables):
                store.register(txn_id, seekables, CfkStatus.WITNESSED, ts)
            if domain == "key":
                ref.add_key_txn(txn_id, kind, what)
            else:
                ref.add_range_txn(txn_id, kind, what)
        ref.freeze(p["keys"])

    def draw(self, n, n_range):
        """n fresh transactions, exactly n_range of them range-domain at
        positions drawn from the seed: (txn id, seekables, bound, spec)."""
        range_at = set(self._rng.sample(range(n), n_range))
        return [self.fresh_range_txn() if i in range_at
                else self.fresh_key_txn() for i in range(n)]

    def ask(self, txn_id, seekables, bound):
        """One transaction's deps from the node, as node.Deployment.ask."""
        return self.stores.map_reduce_async(
            seekables,
            lambda store: store.calculate_deps_async(
                txn_id, store.owned(seekables), bound),
            self.union)

    def check(self, subjects, answers):
        """Every answer against the reference, by domain: subjects, wrong
        answers, dependencies checked."""
        out = {what: {"key": 0, "range": 0}
               for what in ("subjects", "wrong", "deps")}
        ref = self.reference
        for (_, _, bound, (domain, kind, what)), a in zip(subjects, answers):
            if domain == "key":
                want = ref.expected_key(kind, what, bound)
                got = key_answer(ref, a)
                deps = len(want)
            else:
                want = ref.expected_range(kind, what, bound)
                got = range_answer(ref, a)
                deps = len(want[0])
            out["subjects"][domain] += 1
            out["deps"][domain] += deps
            out["wrong"][domain] += not same(got, want)
        return out

    def round(self, n, n_range, timed=None, watch=None):
        """Draw the round, ask the node for each transaction at once and
        drain (the timed part, inside `timed()` where given and inside
        `watch`, a common.CollectorWatch), check every reply."""
        subjects = self.draw(n, n_range)
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
                    for i, (t, seekables, bound, _) in enumerate(subjects):
                        self.ask(t, seekables, bound).add_callback(done(i))
                self.cluster.queue.drain(max_events=1_000_000)
            resolve_s = time.perf_counter() - t0
            cpu_s = time.process_time() - c0
        return dict(self.check(subjects, answers), resolve_s=resolve_s,
                    cpu_s=cpu_s, failed=len(failures))

    def counters(self):
        return common.numeric(self.node.metrics_snapshot())

    def arenas(self):
        return [self.resolver._arena(s) for s in self.stores.all()]


def warm_kernels(p):
    """The deployment's start-up: the program's `warmup` at this node's
    shapes, one call an entry of the configuration's `warm` (its
    `warm_note` says where each tier comes from), every one at the node's
    store tier and the one range capacity of its arenas."""
    from accord_tpu.ops.resolver import warmup
    for call in p["warm"]:
        warmup(num_buckets=p["buckets"], cap=p["cap"],
               batch_tiers=tuple(call["batch_tiers"]), scatter_tiers=(),
               nnz_tiers=tuple(call["nnz_tiers"]),
               store_tiers=(p["stores"],), range_cap=p["range_cap"],
               out_tiers=tuple(call["out_tiers"]),
               range_out_tiers=tuple(call["range_out_tiers"]),
               kid_cap=p["kid_cap"])


def reduce_slice(fallback_window_s, dump_to=None):
    """`common.reduce_trace`, and the device time of each entry of
    TRACED_PROGRAMS read from the same slice before it is removed."""
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
    from accord_tpu.obs.metrics import GLOSSARY
    from accord_tpu.ops.resolver import BatchDepsResolver
    return node.lacking() + \
        [n for n in RESOLVER_COUNTERS if not hasattr(BatchDepsResolver, n)] \
        + [n for n in NODE_COUNTERS if n not in GLOSSARY]


def run(p, seed, seconds, trace, meter, dump_trace=None):
    missing = lacking()
    if missing:
        # a program from before the range call's fused count and the
        # fan-out's Ranges counters: the cell cannot be judged on it
        print(f"benchmark: this program has no {missing}; the node-ranges "
              "cell cannot be judged on it; nothing was run", file=sys.stderr)
        raise SystemExit(4)
    # the node's start-up, as `serve/server.py` `run` does it (node.run)
    from accord_tpu.utils.collector import settled_collector
    with settled_collector():
        return serve(p, seed, seconds, trace, meter, dump_trace)


def serve(p, seed, seconds, trace, meter, dump_trace):
    watch = common.CollectorWatch()
    full = FullCollections(watch)
    gc.callbacks.extend((watch.on_collection, full.on_collection))
    dep = Deployment(p, seed)
    n, n_range = p["subjects"], p["range_subjects"]
    warm_kernels(p)
    # warm-up, untimed and checked: whole rounds until the dispatches of the
    # last rounds asked for no compile
    faults, warm_compiles, quiet = [], [], 0
    while quiet < node.SETTLED_DISPATCHES \
            and len(warm_compiles) < node.MAX_WARM_ROUNDS:
        compiles, d0 = meter.requests, dep.resolver.dispatches
        full.round = f"warm:{len(warm_compiles)}"
        r = dep.round(n, n_range)
        warm_compiles.append(meter.requests - compiles)
        wrong = sum(r["wrong"].values())
        if wrong or r["failed"] or not all(r["deps"].values()):
            faults.append(f"warm-up round {len(warm_compiles)}: {wrong} "
                          f"wrong, {r['failed']} failed, deps {r['deps']}")
        quiet = 0 if warm_compiles[-1] \
            else quiet + dep.resolver.dispatches - d0
    registries = {"resolver": dep.resolver.metrics, "node": dep.node.metrics}
    timers = {k: registries[v.split(".")[0]].timer(v)
              for k, v in ROUND_TIMERS.items()}
    per_round = {"round_s": [], "round_cpu_s": [], **{k: [] for k in timers}}

    compiles_open = meter.requests
    before = dep.counters()
    window_opened_at = time.perf_counter()
    resolve_s = cpu_s = traced_s = 0.0
    traced_dispatches = 0
    checked = []  # what each round's check returned
    # a profiler slice of whole rounds in the middle of the window; only the
    # timed spans carry the benchmark's span, so the checks are outside it
    slice_s = min(p.get("trace_s", 3.0), seconds / 2) if trace else 0.0
    slice_state = "before" if trace else "closed"
    traced, traced_device_s = None, {}
    while resolve_s < seconds:
        if slice_state == "before" and resolve_s >= (seconds - slice_s) / 2:
            common.start_trace()
            slice_state, d0 = "open", dep.resolver.dispatches
        in_slice = slice_state == "open"
        at = {k: t.total for k, t in timers.items()}
        full.round = f"window:{len(checked)}"
        r = dep.round(n, n_range,
                      timed=common.window_span if in_slice else None,
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
            traced_dispatches = dep.resolver.dispatches - d0
            slice_state = "closed"
            traced, traced_device_s = reduce_slice(traced_s,
                                                   dump_to=dump_trace)
    for callback in (watch.on_collection, full.on_collection):
        gc.callbacks.remove(callback)
    rounds = len(checked)
    failed = sum(r["failed"] for r in checked)
    subjects, wrong, deps = (
        {d: sum(r[what][d] for r in checked) for d in ("key", "range")}
        for what in ("subjects", "wrong", "deps"))
    after = dep.counters()
    counters = common.delta(after, before)
    faults += common.counter_faults(after)
    host_path = {name: counters.get(name, 0) for name in HOST_PATH_COUNTERS}
    device_decodes = counters.get(ranges.DEVICE_RANGE_DECODES, 0)
    compiled = meter.requests - compiles_open
    fused_range_share = counters.get("resolver.fused_range_dispatches", 0) \
        / max(1, counters.get("resolver.range_dispatches", 0))
    slices = counters.get("node.store_slices", 0) \
        / max(1, counters.get("node.requests", 0))
    range_slices = counters.get("node.range_store_slices", 0) \
        / max(1, counters.get("node.range_requests", 0))
    lo, hi = p["slices_per_txn"]
    rlo, rhi = p["range_slices_per_range_txn"]
    if any(wrong.values()) or failed:
        faults.append(f"wrong answers {wrong}, {failed} failed replies of "
                      f"{rounds * n}")
    if not all(deps.values()):
        faults.append(f"the reference found no dependency in a domain: {deps}")
    if any(host_path.values()):
        faults.append(f"answers off the device path in the window: "
                      f"{host_path}")
    if not device_decodes:
        faults.append("no range subject was decoded from the device lanes")
    if compiled:
        faults.append(f"{compiled} compile requests inside the window")
    if fused_range_share < p["fused_range_share_min"]:
        faults.append(f"the fused range program ran in "
                      f"{fused_range_share:.3f} of the dispatches with a "
                      f"range call, under {p['fused_range_share_min']}")
    if not lo <= slices <= hi:
        faults.append(f"{slices:.4f} store slices a transaction, outside "
                      f"{lo} to {hi}")
    if not rlo <= range_slices <= rhi:
        faults.append(f"{range_slices:.4f} store slices a range transaction, "
                      f"outside {rlo} to {rhi}")
    counters.update(window_s=resolve_s, cpu_s=cpu_s, attempted=rounds * n,
                    rounds=rounds, deps_total=sum(deps.values()),
                    compile_requests_in_window=compiled,
                    **common.traced_counters(traced, traced_dispatches))
    if traced:
        counters.update(traced_device_s)
    arenas = dep.arenas()
    return {
        "correct": not faults, "attempted": rounds * n, "failed": failed,
        "values": {"deps_resolved_per_s": rounds * n / resolve_s},
        "counters": counters, "traced": traced,
        "window_opened_at": window_opened_at,
        "notes": {"faults": faults, "rounds": rounds,
                  "subjects": subjects,
                  "deps_per_subject": {
                      d: deps[d] / max(1, subjects[d]) for d in deps},
                  "warm_compiles": warm_compiles,
                  "warm_settled": quiet >= node.SETTLED_DISPATCHES,
                  "pad_store_tiers": dep.resolver.pad_store_tiers,
                  "arenas": {"cap": [a.cap for a in arenas],
                             "count": [a.count for a in arenas],
                             "kid_cap": [a.kid_cap for a in arenas],
                             "range_cap": [a.ranges.cap for a in arenas],
                             "range_count": [a.ranges.count for a in arenas]},
                  "device_id": dep.resolver.device.id,
                  **per_round, "collector": watch.read(),
                  "full_collections": full.found,
                  "collections_since_start": full.runs},
        "compared": {
            "wrong_answers": [sum(wrong.values()), 0],
            "wrong_key_answers": [wrong["key"], 0],
            "wrong_range_answers": [wrong["range"], 0],
            "failed_replies": [failed, 0],
            "deps_checked_min": [min(deps.values()), 1],
            **common.counter_comparisons(after),
            **{name: [v, 0] for name, v in host_path.items()},
            ranges.DEVICE_RANGE_DECODES: [device_decodes, 1],
            "compile_requests_in_window": [compiled, 0],
            "fused_range_dispatch_share_min": [fused_range_share,
                                               p["fused_range_share_min"]],
            "store_slices_per_txn_min": [slices, lo],
            "store_slices_per_txn_max": [slices, hi],
            "range_slices_per_range_txn_min": [range_slices, rlo],
            "range_slices_per_range_txn_max": [range_slices, rhi]},
    }
