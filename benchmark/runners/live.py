"""The live runner: one CommandStore in steady state. Every round `subjects`
fresh key-domain WRITEs are PreAccepted through `store.submit_preaccept` (so
each is registered in the tick that answers it), the round just answered is
committed and applied in txn-id order between rounds, and a durability wave
inside every round's timed span truncates the round `resident_rounds` back
while the round's dispatches are in flight. Set-up brings an empty store to
that state, untimed: one such round (so the arena is on the device before it
grows), then the early rounds filled without a resolve, then the last
`resident_rounds` + 2 as the window runs them, until the arena stands
`rounds_before_fill` rounds short of filling at its steady capacity, so a
window holds exactly one compaction. The process's collector is set as a
node server sets it at start-up (`accord_tpu/utils/collector.py`).

The timed span, the `notes` and the `bench.enqueue` span are the batch
runner's (`runners/batch.py`), so `noise.py` reads these runs too. The plain
reference is this file's own and reads nothing of the store, its cfks or the
arena: per key the live ids in id order, the floor with its sync point's id,
and per key which ids a committed write covers, each updated by what this
runner submitted, committed and marked durable. An answer is compared as a
set of (key, txn id), with the timestamp the store witnessed the subject at.
"""
from __future__ import annotations

import contextlib
import gc
import sys
import time

from benchmark import common, trace_programs
from benchmark.runners.batch import ENQUEUE_SPAN, ROUND_TIMERS

# an answer that came from a host scan, the legacy decode or a finalize lane
# that fell back is not this deployment: zero over the window
HOST_PATH_COUNTERS = ("resolver.host_fallbacks", "resolver.legacy_decodes",
                      "resolver.finalize_fallbacks")
# what a store that lives has to have done inside the window: at least 1 each
LIFECYCLE_COUNTERS = ("resolver.arena_compactions",
                      "resolver.fence_materializes",
                      "resolver.truncated_txns")
# the program's timers whose change over each round goes into `notes`
LIVE_ROUND_TIMERS = {**ROUND_TIMERS,
                     "round_preaccept_s": "resolver.preaccept_s",
                     "round_arena_sync_s": "resolver.arena_sync_s",
                     "round_truncate_s": "resolver.truncate_s",
                     "round_fence_s": "resolver.fence_s",
                     "round_compact_s": "resolver.compact_s"}
# the device programs that keep the arena in step with the host, as the
# "XLA Modules" line names them
ARENA_PROGRAMS = ("jit_arena_scatter", "jit_arena_scatter_keys",
                  "jit_scatter_rows", "jit_kid_word_scatter",
                  "jit_arena_grow")
WAVE_SPAN = "bench.wave"  # the runner's durability wave, for gap labels


class Reference:
    """What is resident, and the exact answer of a PreAccept by the store's
    rule (upstream's CommandsForKey scan with transitive dependency elision,
    and RedundantBefore.collectDeps): per key of the subject every resident
    id below the subject's own that no committed write below the subject
    covers, and one edge to the floor's sync point. Ids below the floor are
    not resident."""

    def __init__(self):
        self.by_key = {}      # key -> resident ids, ascending
        self.covered = {}     # key -> {id: the covering write's id}
        self.committed = set()
        self.floor = None     # the sync point everything below is durable
        self.dropped = {}     # key -> ids the newest wave dropped there
        self.resident = set()

    def submit(self, txn_id, keys):
        self.resident.add(txn_id)
        for k in keys:
            self.by_key.setdefault(k, []).append(txn_id)

    def expected(self, txn_id, keys):
        want = set()
        for k in keys:
            cov = self.covered.get(k, {})
            for x in self.by_key.get(k, ()):
                if not x < txn_id:
                    break
                c = cov.get(x)
                if c is None or not c < txn_id:
                    want.add((k, x))
            if self.floor is not None and self.floor < txn_id:
                want.add((k, self.floor))
        return want

    def commit(self, txn_id, deps):
        """`txn_id`, a write, commits at its own id with `deps` (pairs of
        (key, id)): every dependency that is committed below it is covered
        by it from now on, on that key."""
        for k, x in deps:
            if x in self.committed and x < txn_id:
                self.covered.setdefault(k, {}).setdefault(x, txn_id)
        self.committed.add(txn_id)

    def wave(self, sync_id):
        """Everything below `sync_id` is applied and durable: it leaves."""
        self.floor = sync_id
        self.dropped = {}
        for k, ids in self.by_key.items():
            n = 0
            while n < len(ids) and ids[n] < sync_id:
                n += 1
            if n:
                gone = self.dropped[k] = ids[:n]
                del ids[:n]
                cov = self.covered.get(k)
                for x in gone:
                    self.resident.discard(x)
                    self.committed.discard(x)
                    if cov is not None:
                        cov.pop(x, None)


def answer_set(deps):
    """The program's answer in the reference's terms; None where there is
    none or it holds range dependencies."""
    if deps is None or not deps.range_deps.is_empty():
        return None
    return {(k, t) for k, ids in deps.key_deps.items() for t in ids}


class Deployment:
    """The store on a one-node cluster as the batch runner builds it, the
    resolver in front of it, the reference, and the rounds."""

    def __init__(self, p, seed):
        from accord_tpu.ops.resolver import BatchDepsResolver
        from accord_tpu.sim.cluster import Cluster, ClusterConfig
        from accord_tpu.utils.rng import RandomSource

        self.p = p
        self.resolver = BatchDepsResolver(num_buckets=p["buckets"],
                                          initial_cap=p["cap"],
                                          max_dispatch=p["max_dispatch"],
                                          kid_cap=p["kid_cap"])
        self.cluster = Cluster(3, ClusterConfig(
            num_nodes=1, rf=1, stores_per_node=1, num_shards=1, progress=False,
            deps_resolver_factory=lambda: self.resolver,
            deps_batch_window_ms=None))
        self.node = self.cluster.nodes[1]
        self.store = self.node.command_stores.all()[0]
        self.store.batch_window_ms = p["batch_window_ms"]
        self.rng = RandomSource(seed)
        self.reference = Reference()
        self.syncs = []       # the sync point drawn after each round
        self.waved = -1       # which of them the newest wave made the floor
        self.pending = []     # the round answered last: to commit and apply
        self.wave_s = 0.0     # wall time of the newest wave, cleanup() in it
        self.reference_s = 0.0  # wall time spent in the reference so far

    @contextlib.contextmanager
    def referee(self):
        """Round the reference's own work, so set-up can leave it out."""
        t0 = time.perf_counter()
        try:
            yield self.reference
        finally:
            self.reference_s += time.perf_counter() - t0

    # -- what a round is made of ----------------------------------------------
    def fresh(self):
        """A fresh 4-key WRITE: (txn id, its keys as drawn, the PartialTxn
        this store holds of it, its route)."""
        from accord_tpu.primitives.keyspace import Keys
        from accord_tpu.primitives.timestamp import Domain, TxnId, TxnKind
        from accord_tpu.primitives.txn import Txn
        from accord_tpu.sim.list_store import ListQuery, ListRead, ListUpdate
        ts = self.node.unique_now()
        txn_id = TxnId.create(ts.epoch, ts.hlc, ts.node, TxnKind.WRITE,
                              Domain.KEY)
        raw = [self.rng.next_int(self.p["keys"])
               for _ in range(self.p["keys_per_txn"])]
        keys = Keys(raw)
        txn = Txn(TxnKind.WRITE, keys, read=ListRead(keys),
                  update=ListUpdate(keys, 1), query=ListQuery())
        return (txn_id, sorted(set(raw)),
                txn.slice(self.store.ranges, include_query=False),
                self.node.compute_route(txn))

    def draw_sync_point(self):
        """After a round: an ExclusiveSyncPoint id above everything drawn so
        far, marked as its PreAccept marks it. It is an id with the store's
        marks, not a command of this store."""
        from accord_tpu.primitives.timestamp import Domain, TxnId, TxnKind
        ts = self.node.unique_now()
        sync = TxnId.create(ts.epoch, ts.hlc, ts.node,
                            TxnKind.EXCLUSIVE_SYNC_POINT, Domain.RANGE)
        self.store.mark_exclusive_sync_point(sync, self.store.ranges)
        self.syncs.append(sync)

    def settle(self):
        """Between rounds, untimed: the round just answered is committed and
        applied in txn-id order, each with the deps the store answered less
        the edge to the floor's sync point (which is no command here, and
        everything it stands for is applied), then that round's sync point
        is drawn."""
        from accord_tpu.local.commands import CommitOutcome
        store = self.store
        for txn_id, partial, route, witnessed, deps, _ in self.pending:
            deps = deps.without(lambda t: t.kind.is_sync_point)
            for op in (store.commit_op(txn_id, route, partial, witnessed,
                                       deps),
                       store.apply_op(txn_id, route, partial, witnessed, deps,
                                      None, None)):
                if op is not CommitOutcome.SUCCESS:
                    raise RuntimeError(f"{txn_id}: commit/apply gave {op}")
        with self.referee() as ref:
            for txn_id, _, _, _, _, want in self.pending:
                ref.commit(txn_id, [(k, x) for k, x in want
                                    if not x.kind.is_sync_point])
        self.cluster.queue.drain(max_events=1_000_000)
        self.pending = []
        self.draw_sync_point()

    def wave_due(self, back=None):
        """The sync points of this round's wave: (shard-durable, globally
        durable or None), the first drawn `back` rounds ago
        (`resident_rounds` unless given); None while fewer rounds are behind
        or a wave has gone as far already."""
        at = len(self.syncs) - (back or self.p["resident_rounds"])
        if at <= self.waved:
            return None
        self.waved = at
        return self.syncs[at], self.syncs[at - 1] if at else None

    def run_wave(self, shard, universal):
        """The durability wave, on the store's own thread: the round
        `resident_rounds` back is locally applied and shard-durable, the one
        before it durable everywhere."""
        t0 = time.perf_counter()
        store = self.store
        with common.host_span(WAVE_SPAN):
            store.mark_exclusive_sync_point_locally_applied(shard,
                                                            store.ranges)
            store.mark_shard_durable(shard, store.ranges)
            if universal is not None:
                at = universal.as_timestamp()
                store.mark_globally_durable(
                    [(r.start, r.end, at) for r in store.ranges])
        self.wave_s = time.perf_counter() - t0

    def fill_round(self):
        """A round of set-up's fill: `subjects` fresh txns registered by the
        store's own `commands.preaccept` and left for `settle` to commit and
        apply, with no resolve: so with no deps, covering nothing, which is
        what the reference is told. No wave: `catch_up` truncates them."""
        from accord_tpu.local import commands
        from accord_tpu.primitives.deps import Deps
        if self.pending:
            self.settle()
        subjects = [self.fresh() for _ in range(self.p["subjects"])]
        for txn_id, _, partial, route in subjects:
            outcome = commands.preaccept(self.store, txn_id, partial, route)
            witnessed = self.store.command(txn_id).execute_at
            if outcome is not commands.AcceptOutcome.SUCCESS \
                    or witnessed != txn_id.as_timestamp():
                raise RuntimeError(f"{txn_id}: filled as {outcome} at "
                                   f"{witnessed}")
            self.pending.append((txn_id, partial, route, witnessed, Deps.NONE,
                                 ()))
        with self.referee() as ref:
            for txn_id, keys, _, _ in subjects:
                ref.submit(txn_id, keys)

    def catch_up(self, keep):
        """After the fill: one wave, with nothing in flight, that leaves the
        newest `keep` rounds resident and everything before them durable and
        truncated: rows that are tombstones until a compaction."""
        due = self.wave_due(back=keep)
        with self.referee() as ref:
            ref.wave(due[0])
        self.run_wave(*due)

    def round(self, timed=None, watch=None):
        """Settle the round before, draw `subjects` fresh txns, PreAccept
        them at once with the wave (where one is due) on the queue behind the
        launch (the timed part, inside `timed()` where given and inside
        `watch`), check every answer. Returns what the round measured and
        what the check found."""
        if self.pending:
            self.settle()
        n = self.p["subjects"]
        subjects = [self.fresh() for _ in range(n)]
        due = self.wave_due()
        with self.referee() as ref:
            for txn_id, keys, _, _ in subjects:
                ref.submit(txn_id, keys)
            if due is not None:
                # every answer of the round is harvested after the wave
                ref.wave(due[0])
        answers = [None] * n
        failures = []

        def done(i):
            def on_done(value, failure):
                if failure is not None:
                    failures.append(failure)
                answers[i] = value
            return on_done

        submit = self.store.submit_preaccept
        self.wave_s = 0.0
        with watch if watch is not None else contextlib.nullcontext():
            c0 = time.process_time()
            t0 = time.perf_counter()
            with timed() if timed is not None else contextlib.nullcontext():
                with common.host_span(ENQUEUE_SPAN):
                    for i, (txn_id, _, partial, route) in enumerate(subjects):
                        submit(txn_id, partial, route).add_callback(done(i))
                if due is not None:
                    self.node.scheduler.once(
                        self.p["wave_after_ms"],
                        lambda: self.run_wave(*due))
                self.cluster.queue.drain(max_events=1_000_000)
            resolve_s = time.perf_counter() - t0
            cpu_s = time.process_time() - c0
        from accord_tpu.local.commands import AcceptOutcome
        wrong = refused = deps = 0
        with self.referee() as ref:
            for (txn_id, keys, partial, route), a in zip(subjects, answers):
                want = ref.expected(txn_id, keys)
                deps += len(want)
                outcome, witnessed, got = a if a is not None else (None,) * 3
                if outcome is not AcceptOutcome.SUCCESS:
                    refused += 1
                    continue
                ok = witnessed == txn_id.as_timestamp() \
                    and answer_set(got) == want
                wrong += not ok
                if ok:
                    self.pending.append(
                        (txn_id, partial, route, witnessed, got, want))
        return {"resolve_s": resolve_s, "cpu_s": cpu_s, "wrong": wrong,
                "failed": len(failures), "refused": refused, "deps": deps,
                "wave_s": self.wave_s, "waved": due is not None}

    # -- what the runner reads of the program ---------------------------------
    def counters(self):
        return common.numeric(self.resolver.metrics.snapshot())

    def arena(self):
        return self.resolver._arena(self.store)

    def resident_difference(self):
        """Ids the arena holds live and the reference does not, or the other
        way round: the window's last word on truncation."""
        arena = self.arena()
        live = {arena.txn_ids[i] for i in range(arena.count)
                if arena.key_sets[i]}
        return len(live ^ self.reference.resident)


class FullCollections:
    """Every collection of the oldest generation over the whole run: the
    round it fell in, whether inside that round's timed part (`watch`), its
    seconds; and how many collections of each generation ran. A full
    collection of this store's heap is a stall of more than a second, so
    where one falls is part of what a window reads; the program's start-up
    makes them rare (`accord_tpu/utils/collector.py`)."""

    def __init__(self, watch):
        self.watch, self.round, self.found, self._t0 = watch, "start", [], 0.0
        self.runs = [0, 0, 0]  # collections so far, by generation

    def on_collection(self, phase, info):
        self.runs[info["generation"]] += phase == "stop"
        if info["generation"] == 2:
            if phase == "start":
                self._t0 = time.perf_counter()
            else:
                self.found.append([self.round, self.watch.timed,
                                   time.perf_counter() - self._t0])


def warm_kernels(p):
    """The deployment's start-up, as `serve/server.py` `warm_kernels` does
    it: the program's `warmup` at the arena's steady capacity, so the lanes a
    compaction makes anew there (and the kid table rebuilt after it) are
    shapes the process has met. No resolve or finalize program is compiled
    here (`batch_tiers` is empty; `out_tiers` only turns the kid table's
    part on): the set-up rounds meet those."""
    from accord_tpu.ops.resolver import warmup
    warmup(num_buckets=p["buckets"], cap=p["steady_cap"],
           batch_tiers=(), scatter_tiers=(8, 64), nnz_tiers=(),
           store_tiers=(1,), out_tiers=(0,), range_out_tiers=(),
           kid_cap=p["steady_kid_cap"])


def at_phase(arena, p):
    return arena.cap == p["steady_cap"] and arena.count == \
        p["steady_cap"] - p["rounds_before_fill"] * p["subjects"]


def setup_plan(p):
    """Set-up's rounds, from an empty store to the window's phase, as
    (lead, filled, whole): `lead` whole rounds until the arena has grown
    once on the device; `filled` rounds without a resolve, which a wave
    then truncates but for the newest `lead` (the rows stay, as tombstones:
    they stand for a past that is durable); the last `resident_rounds` + 2
    whole, as a store's first rounds are: the residents build up from `lead`
    rounds to `resident_rounds`, as gently as from an empty store (the
    out-cap tiers follow a bound that rises a round at a time; a jump would
    cost a finalize fallback), and then a wave a round truncates a round.
    Every resident of the window's start was answered by the device."""
    rounds = p["steady_cap"] // p["subjects"] - p["rounds_before_fill"]
    lead = min(rounds, p["cap"] // p["subjects"] + 1)
    whole = min(rounds - lead, p["resident_rounds"] + 2)
    return lead, rounds - lead - whole, whole


def run(p, seed, seconds, trace, meter, dump_trace=None):
    from accord_tpu.ops.resolver import BatchDepsResolver
    lacking = [n for n in LIFECYCLE_COUNTERS
               if not hasattr(BatchDepsResolver, n.split(".", 1)[1])]
    if lacking:
        # a program from before PR 33: `correct` in this cell rests on
        # counters it does not keep
        print(f"benchmark: this program keeps no {lacking}; the live cell "
              "cannot be judged on it; nothing was run", file=sys.stderr)
        raise SystemExit(4)
    # the deployment's start-up, as `serve/server.py` `run` does it: the
    # program's own setting of the collector, from start-up to shutdown
    from accord_tpu.utils.collector import settled_collector
    with settled_collector():
        return serve(p, seed, seconds, trace, meter, dump_trace)


def serve(p, seed, seconds, trace, meter, dump_trace):
    live = Deployment(p, seed)
    warm_kernels(p)
    watch = common.CollectorWatch()
    full = FullCollections(watch)
    gc.callbacks.extend((watch.on_collection, full.on_collection))
    faults, setup = [], {"wrong": 0, "failed": 0, "refused": 0}
    arena = live.arena()
    setup_t0 = time.perf_counter()
    lead, filled, whole = setup_plan(p)
    for i in range(lead + filled + whole):
        full.round = f"setup:{i}"
        if lead <= i < lead + filled:
            live.fill_round()
            if i == lead + filled - 1:
                live.catch_up(keep=lead)
            continue
        r = live.round()
        for k in ("wrong", "failed", "refused"):
            setup[k] += r[k]
    setup.update(rounds=lead + filled + whole, filled=filled,
                 seconds=time.perf_counter() - setup_t0,
                 reference_s=live.reference_s)
    if not at_phase(arena, p):
        faults.append(f"set-up never reached the window's phase: cap "
                      f"{arena.cap}, count {arena.count}")
    if setup["wrong"] or setup["failed"] or setup["refused"]:
        faults.append(f"set-up rounds: {setup}")
    grown = live.counters()
    setup.update(arena_growths=grown.get("resolver.arena_growths", 0),
                 arena_compactions=grown.get("resolver.arena_compactions", 0),
                 kid_cap=arena.kid_cap, cap=arena.cap)
    if not setup["arena_growths"] or arena.kid_cap <= p["kid_cap"]:
        faults.append("set-up never grew the arena on the device, or never "
                      "doubled the kid table")
    timers = {k: live.resolver.metrics.timer(v)
              for k, v in LIVE_ROUND_TIMERS.items()}
    per_round = {"round_s": [], "round_cpu_s": [], "round_wave_s": [],
                 **{k: [] for k in timers}}

    compiles_open = meter.requests
    before = live.counters()
    # the reference's own seconds in set-up (every check, and the model of
    # every wave and commit) are the benchmark's, not the deployment's
    window_opened_at = time.perf_counter() - live.reference_s
    resolve_s = cpu_s = traced_s = 0.0
    rounds = traced_dispatches = 0
    checked = []
    # a profiler slice of whole rounds from the round before the one that
    # compacts; only the timed spans carry the benchmark's span, so the
    # checks and the commits between rounds are outside it
    slice_s = min(p.get("trace_s", 3.0), seconds / 2) if trace else 0.0
    slice_state = "before" if trace else "closed"
    traced = arena_device_s = None
    while resolve_s < seconds:
        if slice_state == "before" and rounds >= p["trace_from_round"]:
            common.start_trace()
            slice_state, d0 = "open", live.resolver.dispatches
        in_slice = slice_state == "open"
        at = {k: t.total for k, t in timers.items()}
        full.round = f"window:{rounds}"
        r = live.round(timed=common.window_span if in_slice else None,
                       watch=watch)
        per_round["round_s"].append(r["resolve_s"])
        per_round["round_cpu_s"].append(r["cpu_s"])
        per_round["round_wave_s"].append(r["wave_s"])
        for k, t in timers.items():
            per_round[k].append(t.total - at[k])
        resolve_s, cpu_s = resolve_s + r["resolve_s"], cpu_s + r["cpu_s"]
        rounds += 1
        checked.append(r)
        traced_s += r["resolve_s"] if in_slice else 0.0
        if in_slice and (traced_s >= slice_s or resolve_s >= seconds):
            common.stop_trace()
            traced_dispatches = live.resolver.dispatches - d0
            slice_state = "closed"
            traced, arena_device_s = trace_programs.reduce_slice(
                ARENA_PROGRAMS, traced_s, dump_to=dump_trace)
    for callback in (watch.on_collection, full.on_collection):
        gc.callbacks.remove(callback)
    n = p["subjects"]
    wrong, failed, refused, deps = (sum(r[k] for r in checked) for k in
                                    ("wrong", "failed", "refused", "deps"))
    waved = sum(r["waved"] for r in checked)
    after = live.counters()
    counters = common.delta(after, before)
    faults += common.counter_faults(after)
    host_path = {name: counters.get(name, 0) for name in HOST_PATH_COUNTERS}
    lifecycle = {name: counters.get(name, 0) for name in LIFECYCLE_COUNTERS}
    difference = live.resident_difference()
    if wrong or failed or refused:
        faults.append(f"{wrong} wrong answers, {failed} failed resolutions, "
                      f"{refused} PreAccepts not SUCCESS of {rounds * n}")
    if not deps:
        faults.append("the reference found no dependency at all")
    if any(host_path.values()):
        faults.append(f"answers off the device path in the window: "
                      f"{host_path}")
    if not all(lifecycle.values()):
        faults.append(f"the store did not live in the window: {lifecycle}")
    if difference:
        faults.append(f"{difference} ids resident in the arena or in the "
                      "reference and not in both")
    counters.update(window_s=resolve_s, cpu_s=cpu_s, attempted=rounds * n,
                    rounds=rounds, deps_total=deps, waves=waved,
                    wave_s=sum(per_round["round_wave_s"]),
                    compile_requests_in_window=meter.requests - compiles_open,
                    **common.traced_counters(traced, traced_dispatches))
    if traced and arena_device_s is not None:
        counters["arena_sync_device_s"] = arena_device_s
    # the window's shape: which rounds made it. A gain reads against the
    # same shape only; the two kinds of round are given apart beside it
    compacted = [x > 0.0 for x in per_round["round_compact_s"]]
    kinds = {kind: [s for s, c in zip(per_round["round_s"], compacted)
                    if c == (kind == "compacting")]
             for kind in ("steady", "compacting")}
    return {
        "correct": not faults, "attempted": rounds * n, "failed": failed,
        "values": {"deps_resolved_per_s": rounds * n / resolve_s},
        "counters": counters, "traced": traced,
        "window_opened_at": window_opened_at,
        "notes": {"faults": faults, "rounds": rounds, "setup": setup,
                  "deps_per_subject": deps / max(1, rounds * n),
                  "compile_requests_in_window": [
                      counters["compile_requests_in_window"], 0],
                  "rounds_with_a_wave": waved,
                  "rounds_with_a_fence": sum(
                      1 for x in per_round["round_fence_s"] if x > 0.0),
                  "rounds_with_a_compaction": [
                      i for i, c in enumerate(compacted) if c],
                  "window_shape": "".join("C" if c else "S"
                                          for c in compacted),
                  **{f"{kind}_rounds_per_s": len(s) * n / sum(s) if s else None
                     for kind, s in kinds.items()},
                  "arena": {"cap": arena.cap, "count": arena.count,
                            "kid_cap": arena.kid_cap, "gen": arena.gen,
                            "resident": len(live.reference.resident)},
                  "device_id": live.resolver.device.id,
                  **per_round, "collector": watch.read(),
                  "full_collections": full.found,
                  "collections_since_start": full.runs},
        "compared": {
            "wrong_answers": [wrong, 0], "failed_resolutions": [failed, 0],
            "preaccepts_not_success": [refused, 0],
            "deps_checked_min": [deps, 1],
            "resident_set_difference": [difference, 0],
            **common.counter_comparisons(after),
            **{name: [v, 0] for name, v in host_path.items()},
            **{name: [v, 1] for name, v in lifecycle.items()}},
    }
