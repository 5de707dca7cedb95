"""The phase primitive, the occupancy account, the fetch split and the kernel
scope names (obs/trace.py `phase` + `Occupancy`, ops/resolver.py, ops/kernels.py).

Load-bearing properties:
  1. the account -- on a scripted clock the three starved totals are exact,
     partition starved time, and stay zero while nothing is pending;
  2. the resolver -- at the benchmark cell's rehearsal sizes the starved
     timers fit inside the enqueue-to-last-callback wall time, the fetch
     split sums to readback_s, and readback_bytes repeats for one seed;
  3. the profiler's clock -- a resolve under a jax.profiler session shows
     the resolver.* spans on a host plane, launch and harvest of one
     dispatch sharing their `did`;
  4. scope names -- the lowered text of the key and the range programs
     carries every stage name, and the names change no answer;
  5. the flight recorder's vocabulary is what it was before the primitive;
  6. the range path -- at the range cell's rehearsal sizes its two spans lie
     inside their parents, their timers inside the parents' timers, and its
     counters move for range subjects only;
  7. the store's lifecycle -- at the live cell's rehearsal sizes the five
     spans (arena_sync, compact, grow, fence, truncate) lie inside their
     parents, their timers inside the parents' timers, their counters move
     only where a registration, a wave or a fill happened, and the arena's
     device programs carry their stage names;
  8. the node's fan-out -- at the node cell's rehearsal sizes `node.fanout`
     and `node.reduce` open once a request, both outside every resolver
     phase (the fan-out in the caller's enqueue loop, the reduce where a
     harvest delivers the last store's part), their timers move, `node.store_slices` counts the
     stores asked, and a one-store node counts no fused dispatch.
"""
from __future__ import annotations

import contextlib
import subprocess
import sys
import threading
import time
from collections import deque

import numpy as np
import pytest

from accord_tpu.obs.metrics import MetricsRegistry
from accord_tpu.obs.trace import REC, Occupancy, phase

CELL = "preaccept-batch-10k.resolve-4096"
PHASES = {"p.stage": "stage", "p.launch": "stage", "p.harvest": "decode"}


@pytest.fixture(autouse=True)
def _recorder_reset():
    yield
    REC.enabled = False
    REC.wall = False
    REC.clear()


# -- (a) the occupancy account on a scripted clock ----------------------------

class Script:
    """A clock the test moves by hand."""

    def __init__(self):
        self.now = 100.0

    def __call__(self):
        return self.now

    def advance(self, dt):
        self.now += dt


def _account():
    reg, clock = MetricsRegistry(), Script()
    occ = Occupancy(reg, "p", PHASES, clock=clock)

    def starved():
        return {b: reg.timer(f"p.starved_{b}_s").total
                for b in ("stage", "decode", "outside")}
    return occ, clock, starved


def test_occupancy_scripted_pipeline():
    """enqueue, stage, launch, two overlapping calls, harvest, deliver."""
    occ, clock, starved = _account()
    clock.advance(5.0)              # nothing pending: nobody's time
    occ.accept()
    occ.accept()
    clock.advance(0.25)             # the caller's enqueue loop
    occ.enter("p.stage")
    clock.advance(0.5)              # encode, nothing in flight
    occ.exit()
    clock.advance(0.125)            # the batch-window timer
    occ.enter("p.launch")
    clock.advance(0.0625)           # starved until the call is launched
    occ.exit()
    occ.launched()
    clock.advance(1.0)              # the device works: not starved
    occ.enter("p.launch")
    clock.advance(0.0625)
    occ.exit()
    occ.launched()                  # two calls overlap
    occ.enter("p.harvest")
    clock.advance(2.0)              # waiting for call 1
    occ.landed()
    occ.enter("p.materialize")      # not in the map: its parent's bucket
    clock.advance(0.5)              # decode under call 2: hidden, not starved
    occ.exit()
    occ.exit()
    occ.deliver()
    occ.enter("p.harvest")
    clock.advance(1.0)
    occ.landed()                    # nothing in flight, one answer pending
    occ.enter("p.materialize")
    clock.advance(0.75)             # the starved decode
    occ.exit()
    occ.exit()
    clock.advance(0.03125)          # the callback, outside any phase
    occ.deliver()
    clock.advance(9.0)              # idle again
    assert starved() == {"stage": 0.5625, "decode": 0.75,
                         "outside": 0.40625}
    assert occ.pending == 0 and occ.inflight == 0


def test_occupancy_zero_while_nothing_pending():
    occ, clock, starved = _account()
    for name in ("p.stage", "p.harvest", "p.other"):
        occ.enter(name)
        clock.advance(1.0)
        occ.exit()
        clock.advance(1.0)
    assert starved() == {"stage": 0.0, "decode": 0.0, "outside": 0.0}
    # and a call in flight with work pending is not starved either
    occ.accept()
    occ.launched()
    clock.advance(3.0)
    occ.landed()
    occ.deliver()
    assert starved() == {"stage": 0.0, "decode": 0.0, "outside": 0.0}


def test_occupancy_partitions_starved_time():
    """Whatever the interleaving, the buckets add up to the time with work
    pending and nothing in flight."""
    occ, clock, starved = _account()
    rng = np.random.default_rng(5)
    expect = 0.0
    depth = 0
    for _ in range(2000):
        dt = float(rng.integers(1, 64)) / 64.0  # exact in binary
        if occ.pending and not occ.inflight:
            expect += dt
        clock.advance(dt)
        op = int(rng.integers(0, 6))
        if op == 0 and occ.pending < 3:
            occ.accept()
        elif op == 1 and occ.pending:
            occ.deliver()
        elif op == 2 and occ.inflight < 2:
            occ.launched()
        elif op == 3 and occ.inflight:
            occ.landed()
        elif op == 4 and depth < 3:
            occ.enter(("p.stage", "p.harvest", "p.other")[depth])
            depth += 1
        elif op == 5 and depth:
            occ.exit()
            depth -= 1
    occ.deliver(occ.pending)  # an open interval is credited as it closes
    assert sum(starved().values()) == pytest.approx(expect, rel=1e-12)
    assert min(starved().values()) > 0.0


class Stamp:
    """A call's stamp holder: `done_at` as the completion waiter writes it."""

    def __init__(self):
        self.done_at = None


def _drained(reg):
    return {b: reg.timer(f"p.drained_{b}_s").total
            for b in ("stage", "decode", "outside")}


def test_occupancy_drained_scripted_pipeline():
    """Two calls in flight; the device finishes them while the host is in
    a phase, between phases, and before it asks."""
    reg, clock = MetricsRegistry(), Script()
    occ = Occupancy(reg, "p", PHASES, clock=clock)
    starved = {b: reg.timer(f"p.starved_{b}_s") for b in
               ("stage", "decode", "outside")}
    one, two = Stamp(), Stamp()
    occ.accept()
    occ.enter("p.launch")
    clock.advance(0.25)             # starved: nothing in flight yet
    occ.exit()
    occ.launched(one)
    clock.advance(1.0)              # running
    one.done_at = clock.now
    clock.advance(0.5)              # drained, outside every phase
    occ.enter("p.launch")
    clock.advance(0.125)            # drained, on the tick path
    occ.exit()
    occ.launched(two)               # the newest is running again
    occ.enter("p.harvest")
    clock.advance(2.0)              # running: two is not done
    two.done_at = clock.now - 0.5   # done half a second ago: a late stamp
    occ.enter("p.materialize")      # splits at two's done_at
    clock.advance(0.75)             # drained, in the harvest's bucket
    occ.landed()
    occ.exit()
    occ.exit()
    occ.landed()                    # nothing in flight: starved from here
    clock.advance(0.0625)
    occ.deliver()
    assert _drained(reg) == {"stage": 0.125, "decode": 1.25,
                             "outside": 0.5}
    assert {b: t.total for b, t in starved.items()} == {
        "stage": 0.25, "decode": 0.0, "outside": 0.0625}


def test_occupancy_three_states_partition_pending_time():
    """Whatever the interleaving, with the device finishing calls in launch
    order at moments the host does not see: running, starved and drained
    add up to the time with work pending, each bucket gets what a model of
    the rule gives it, and calls launched with no stamp are never
    drained."""
    reg, clock = MetricsRegistry(), Script()
    occ = Occupancy(reg, "p", PHASES, clock=clock)
    rng = np.random.default_rng(9)
    names = ("p.stage", "p.harvest", "p.other")
    buckets = ("stage", "decode", "outside")
    want_starved = dict.fromkeys(buckets, 0.0)
    want_drained = dict.fromkeys(buckets, 0.0)
    pending_time = running = 0.0
    stack, flight, newest = ["outside"], deque(), None
    for _ in range(4000):
        dt = float(rng.integers(1, 64)) / 64.0  # exact in binary
        if occ.pending:
            pending_time += dt
            if not flight:
                want_starved[stack[-1]] += dt
            elif newest.done_at is not None:
                want_drained[stack[-1]] += dt
            else:
                running += dt
        clock.advance(dt)
        op = int(rng.integers(0, 8))
        if op == 0 and occ.pending < 3:
            occ.accept()
        elif op == 1 and occ.pending:
            occ.deliver()
        elif op == 2 and len(flight) < 3:
            newest = Stamp()
            flight.append(newest)
            occ.launched(newest)
        elif op == 3 and flight:
            call = flight.popleft()  # landing implies done: stamp it now
            if call.done_at is None:
                call.done_at = clock.now
            occ.landed()
        elif op in (4, 5) and flight:
            # the device finishes the oldest call not yet done
            call = next((c for c in flight if c.done_at is None), None)
            if call is not None:
                call.done_at = clock.now
        elif op == 6 and len(stack) < 4:
            name = names[len(stack) - 1]
            stack.append(PHASES.get(name, stack[-1]))
            occ.enter(name)
        elif op == 7 and len(stack) > 1:
            stack.pop()
            occ.exit()
    occ.deliver(occ.pending)  # an open interval is credited as it closes
    starved = {b: reg.timer(f"p.starved_{b}_s").total for b in buckets}
    drained = _drained(reg)
    for b in buckets:
        assert starved[b] == pytest.approx(want_starved[b], rel=1e-12)
        assert drained[b] == pytest.approx(want_drained[b], rel=1e-12)
        assert drained[b] > 0.0
    assert sum(starved.values()) + sum(drained.values()) + running == \
        pytest.approx(pending_time, rel=1e-12)


def test_occupancy_unstamped_calls_are_never_drained():
    occ, clock, starved = _account()
    occ.accept()
    occ.launched()
    clock.advance(2.0)
    occ.enter("p.harvest")
    clock.advance(1.0)
    occ.landed()
    occ.exit()
    occ.deliver()
    assert sum(starved().values()) == 0.0
    assert occ._drained["decode"].total == occ._drained["outside"].total \
        == 0.0


# -- the collector's hook ------------------------------------------------------

def test_a_full_collection_is_timed_into_every_registered_registry(tmp_path):
    import gc

    import jax
    from accord_tpu.obs.trace import watch_collector
    regs = [MetricsRegistry(), MetricsRegistry()]
    for reg in regs:
        watch_collector(reg)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        gc.collect(2)
    finally:
        jax.profiler.stop_trace()
    for reg in regs:
        assert reg.timer("gc.pause_s").total > 0.0
        assert reg.counter("gc.full_collections").value >= 1
        assert reg.counter("gc.collections").value >= \
            reg.counter("gc.full_collections").value
    (path,) = tmp_path.glob("plugins/profile/*/*.xplane.pb")
    data = jax.profiler.ProfileData.from_file(str(path))
    found = [dict(e.stats) for plane in data.planes if
             plane.name.startswith("/host:") for line in plane.lines
             for e in line.events if e.name == "gc.collect"]
    assert found and all(st["generation"] == 2 for st in found)


def test_young_collections_count_and_open_no_span():
    import gc
    from accord_tpu.obs.trace import watch_collector
    reg = MetricsRegistry()
    watch_collector(reg)
    gc.collect(0)
    assert reg.counter("gc.collections").value >= 1
    assert reg.counter("gc.full_collections").value == 0
    # the registry is held weakly: one dropped is let go
    import weakref
    gone = MetricsRegistry()
    watch_collector(gone)
    ref = weakref.ref(gone)
    del gone
    gc.collect(0)
    assert ref() is None


def test_collections_count_only_while_the_account_has_work_pending():
    import gc
    from accord_tpu.obs.trace import watch_collector
    reg = MetricsRegistry()
    occ = Occupancy(reg, "p", PHASES)
    watch_collector(reg, occ)
    gc.collect(0)  # nothing pending: the caller's, not the owner's
    assert reg.counter("gc.collections").value == 0
    assert reg.timer("gc.pause_s").total == 0.0
    occ.accept()
    gc.collect(2)
    occ.deliver()
    assert reg.counter("gc.collections").value == 1
    assert reg.counter("gc.full_collections").value == 1
    assert reg.timer("gc.pause_s").total > 0.0


# -- the primitive itself ------------------------------------------------------

def test_phase_feeds_timer_recorder_and_account():
    reg, clock = MetricsRegistry(), Script()
    occ = Occupancy(reg, "p", PHASES, clock=clock)
    REC.configure(wall=True)
    REC.enabled = True
    occ.accept()
    with phase(reg, "p.stage", "p.stage_s", account=occ, node=None,
               track="stage_host", event="encode", did=3) as ph:
        clock.advance(2.0)
        time.sleep(0.002)
        ph.args = {"did": 3, "n": 1}
    assert ph.dt >= 0.002
    assert reg.timer("p.stage_s").total == ph.dt
    assert reg.timer("p.starved_stage_s").total == 2.0
    (ev,) = REC.events()
    assert (ev["ph"], ev["tid"], ev["name"]) == ("X", "stage_host", "encode")
    assert ev["dur"] == round(ph.dt * 1e6, 3)
    assert ev["args"] == {"did": 3, "n": 1}
    # no track: a timer and a profiler span, nothing in the recorder
    with phase(reg, "p.other", "p.other_s"):
        pass
    assert len(REC.events()) == 1 and reg.timer("p.other_s").total > 0.0


def test_a_host_only_process_imports_no_jax_for_a_phase():
    """A maelstrom node builds no device resolver; its first request's spans
    must not import jax on the protocol thread (seconds, against an rpc
    time-out of three). A fresh interpreter, because this one has jax."""
    code = (
        "import sys\n"
        "from accord_tpu.obs.metrics import MetricsRegistry\n"
        "from accord_tpu.obs.trace import phase\n"
        "reg = MetricsRegistry()\n"
        "with phase(reg, 'node.fanout', 'node.fanout_s', did=1) as ph:\n"
        "    pass\n"
        "assert reg.timer('node.fanout_s').total == ph.dt > 0.0\n"
        "assert 'jax' not in sys.modules, 'a phase imported jax'\n")
    done = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=60)
    assert done.returncode == 0, done.stderr


# -- (b) a real resolver at the cell's rehearsal sizes ------------------------

def _params():
    from benchmark import common
    cell = common.load_json(common.HERE / "workloads" / f"{CELL}.json")
    config = common.load_json(
        common.HERE / "configs" / f"{cell['config']}.json")
    return {**config, **cell, **cell["rehearsal"]}


def _resolved(seed):
    """One warm round through a fresh arena: (arena, the round's wall
    seconds, the change of every resolver counter over it)."""
    from benchmark import common
    from benchmark.runners.batch import Arena
    p = _params()
    arena = Arena(p, seed)
    assert arena.round(p["subjects"])[2:4] == (0, 0)  # compiles
    before = arena.counters()
    wall, _, wrong, failed, deps = arena.round(p["subjects"])
    assert (wrong, failed) == (0, 0) and deps > 0
    return arena, wall, common.delta(arena.counters(), before)


@pytest.fixture(scope="module")
def resolved():
    return _resolved(11)


def test_starved_timers_fit_the_wall_time(resolved):
    arena, wall, d = resolved
    starved = [d[f"resolver.starved_{b}_s"]
               for b in ("stage", "decode", "outside")]
    assert all(s >= 0.0 for s in starved)
    assert 0.0 < sum(starved) <= wall
    # nothing in flight when a round starts: all of encode is starved
    assert d["resolver.starved_stage_s"] >= d["resolver.encode_s"]
    snap = arena.resolver.snapshot()
    assert snap["resolver.pending"] == 0
    assert arena.resolver._occ.inflight == 0


def test_fetch_split_sums_to_readback(resolved):
    _, _, d = resolved
    assert d["resolver.device_wait_s"] > 0.0
    assert d["resolver.transfer_s"] > 0.0
    assert d["resolver.device_wait_s"] + d["resolver.transfer_s"] == \
        pytest.approx(d["resolver.readback_s"], rel=1e-9)
    assert d["resolver.harvest_stall_s"] == \
        pytest.approx(d["resolver.readback_s"], rel=1e-9)


def test_readback_bytes_repeat_for_one_seed(resolved):
    _, _, d = resolved
    _, _, again = _resolved(11)
    assert d["resolver.readback_bytes"] > 0
    assert again["resolver.readback_bytes"] == d["resolver.readback_bytes"]
    assert again["resolver.dispatches"] == d["resolver.dispatches"]


def test_idle_timers_fit_the_wall_time(resolved):
    _, wall, d = resolved
    drained = [d[f"resolver.drained_{b}_s"]
               for b in ("stage", "decode", "outside")]
    starved = [d[f"resolver.starved_{b}_s"]
               for b in ("stage", "decode", "outside")]
    assert all(x >= 0.0 for x in drained)
    assert 0.0 < sum(starved) + sum(drained) <= wall


def test_completion_stamps_come_in_launch_order():
    """Every call of a round is stamped by the time its harvest ends, in
    launch order, and no waiter thread outlives the round."""
    from benchmark.runners.batch import Arena
    p = _params()
    arena = Arena(p, 11)
    r = arena.resolver
    seen = []
    collect = r._collect

    def spy(node, call, hidden):
        out = collect(node, call, hidden)
        seen.append((call.did, call.done.done_at, time.perf_counter()))
        return out
    r._collect = spy
    for _ in range(2):
        assert arena.round(p["subjects"])[2:4] == (0, 0)
    assert [did for did, _, _ in seen] == list(range(r.dispatches))
    stamps = [at for _, at, _ in seen]
    assert all(at is not None for at in stamps)
    assert stamps == sorted(stamps)
    assert all(at <= end for _, at, end in seen)
    assert r._occ.inflight == 0 and r._waiter._thread is None
    assert not [t for t in threading.enumerate()
                if t.name == "resolver-completion"]


def test_completion_waiter_under_a_short_switch_interval():
    """The host adds calls and lands them while the thread pops and stamps
    them, the interpreter switching every microsecond: every call is
    stamped, the earliest stamp kept, in launch order, and the thread
    ends."""
    import jax.numpy as jnp
    from accord_tpu.ops.resolver import _Call, _CompletionWaiter
    bufs = [jnp.full((4,), i) for i in range(64)]
    calls = [_Call(bufs[i % 64], None, None, [], []) for i in range(3000)]
    waiter = _CompletionWaiter()
    was = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        landed = 0
        for i, call in enumerate(calls):
            waiter.add(call)
            if i % 3 == 0:  # the host lands calls in launch order, stamping
                for c in calls[landed:i - 8]:
                    waiter.stamp(c.done, time.perf_counter())
                landed = max(landed, i - 8)
        thread = waiter._thread
        if thread is not None:
            thread.join(timeout=60)
            assert not thread.is_alive()
    finally:
        sys.setswitchinterval(was)
    stamps = [c.done.done_at for c in calls]
    assert all(at is not None for at in stamps)
    assert stamps == sorted(stamps)
    assert waiter._thread is None and not waiter._queue


def test_a_call_whose_buffer_is_gone_counts_as_done():
    import jax.numpy as jnp
    from accord_tpu.ops.resolver import _Call, _CompletionWaiter
    buf = jnp.arange(8) + 1
    call = _Call(buf, None, None, [], [])
    buf.delete()
    waiter = _CompletionWaiter()
    t0 = time.perf_counter()
    waiter.add(call)
    waiter.join()
    assert call.done.done_at is not None and call.done.done_at >= t0
    assert waiter._thread is None


def test_a_call_given_up_on_does_not_hold_up_the_harvest():
    """Every call is stuck past the watchdog's budget on a device that
    stays wedged for a minute: the harvest answers host-side at once, the
    landing stamps each call, and the waiter lets them go without asking
    their buffers again."""
    from accord_tpu.ops import fault_plane
    from accord_tpu.ops.resolver import _Call
    from accord_tpu.utils.rng import RandomSource
    from benchmark.runners.batch import Arena

    class Wedged:
        probes = 0

        def is_deleted(self):
            return False

        def is_ready(self):
            Wedged.probes += 1
            return time.perf_counter() >= until

    p = _params()
    arena = Arena(p, 13)
    r = arena.resolver
    assert arena.round(p["subjects"])[2:4] == (0, 0)  # compiles
    until = time.perf_counter() + 60.0
    wedged, calls, add = Wedged(), [], r._waiter.add

    def add_wedged(call):
        calls.append(call)
        saved = _Call.buffers
        _Call.buffers = lambda self: [(None, None, wedged)]
        try:
            add(call)
        finally:
            _Call.buffers = saved
    r._waiter.add = add_wedged
    r.watchdog_probes = 0  # every stuck call trips the watchdog
    plane = fault_plane.DeviceFaultPlane(RandomSource(5).fork(),
                                         stuck_rate=1.0)
    t0 = time.perf_counter()
    with fault_plane.scoped(plane):
        assert arena.round(p["subjects"])[2:4] == (0, 0)
    assert time.perf_counter() - t0 < 20.0
    assert calls and r.device_watchdog_trips == len(calls)
    assert all(c.degraded and t0 < c.done.done_at < until for c in calls)
    assert r._waiter._thread is None and not r._waiter._queue
    probes = Wedged.probes
    time.sleep(0.01)
    assert Wedged.probes == probes


# -- (c) the spans on the profiler's clock ------------------------------------

def test_profiler_host_plane_shows_the_resolver_spans(resolved, tmp_path):
    import jax
    arena = resolved[0]
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    d0 = arena.resolver.dispatches
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("test.window"):
            assert arena.round(_params()["subjects"])[2:4] == (0, 0)
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.glob("plugins/profile/*/*.xplane.pb")
    data = jax.profiler.ProfileData.from_file(str(path))
    spans, window = {}, None
    for plane in data.planes:
        for line in plane.lines:
            for e in line.events:
                if e.name == "test.window":
                    window = (e.start_ns, e.start_ns + e.duration_ns)
                elif e.name.startswith("resolver."):
                    assert plane.name.startswith("/host:")
                    spans.setdefault(e.name, []).append(
                        (e.start_ns, e.duration_ns, dict(e.stats)))
    for name in ("resolver.tick", "resolver.preaccept", "resolver.encode",
                 "resolver.launch", "resolver.harvest",
                 "resolver.device_wait", "resolver.transfer",
                 "resolver.materialize"):
        assert name in spans, f"no {name} span on a host plane"
        assert all(window[0] <= s and s + d <= window[1]
                   for s, d, _ in spans[name]), f"{name} outside the window"
    launched = sorted(st["did"] for _, _, st in spans["resolver.launch"])
    harvested = sorted(st["did"] for _, _, st in spans["resolver.harvest"])
    assert launched == harvested == \
        list(range(d0, arena.resolver.dispatches))
    # the children lie inside their dispatch's harvest span
    for s, d, st in spans["resolver.materialize"]:
        (hs, hd, _), = [h for h in spans["resolver.harvest"]
                        if h[2]["did"] == st["did"]]
        assert hs <= s and s + d <= hs + hd


# -- (d) scope names on the device side ---------------------------------------

def _kernel_args(seed=3, b=8, cap=64, k=32, nnz=24, s=16, kc=16):
    import jax.numpy as jnp
    from accord_tpu.ops.encoding import WITNESS_TABLE
    rng = np.random.default_rng(seed)
    resolve = (
        rng.integers(0, b, nnz).astype(np.int32),
        rng.integers(0, k, nnz).astype(np.int32),
        np.stack([np.zeros(b), rng.integers(40, 60, b), np.zeros(b)],
                 axis=1).astype(np.int32),
        rng.integers(0, 2, b).astype(np.int32),
        (rng.random((cap, k)) < 0.2).astype(np.float32),
        np.stack([np.zeros(cap), rng.integers(0, 80, cap), np.arange(cap)],
                 axis=1).astype(np.int32),
        rng.integers(0, 2, cap).astype(np.int32),
        rng.random(cap) < 0.9,
        np.asarray(WITNESS_TABLE))
    finalize = (
        jnp.int32(0),
        rng.integers(0, 2 ** 32, (kc, cap // 32), dtype=np.uint32),
        rng.integers(0, b + 1, s).astype(np.int32),
        rng.integers(0, kc + 1, s).astype(np.int32),
        rng.integers(-1, cap, b).astype(np.int32))
    return resolve, finalize


RESOLVE_SCOPES = ("subject_bitmap", "overlap", "witness_before_mask",
                  "pack_bits")
COMPACT_SCOPES = ("popcount_prefix", "word_fold", "word_compact",
                  "row_expand", "mark_scatter", "owner_fill", "bit_select")
FINALIZE_SCOPES = ("slot_mask", "bound", *COMPACT_SCOPES, "checksum")
RANGE_RESOLVE_SCOPES = ("interval_overlap", "range_witness_before_mask",
                        "covered_buckets", "bucket_overlap",
                        "key_witness_before_mask", "pack_bits")
RANGE_FINALIZE_SCOPES = ("interval_stab", "bound", "witness_before_mask",
                         "pack_bits", *COMPACT_SCOPES, "checksum")


def _range_kernel_args(seed=3, nv=24, rcap=32):
    """Inputs of the two range programs at the small shapes of
    `_kernel_args`: (range_deps_resolve's, range_finalize_csr's)."""
    resolve, _ = _kernel_args(seed)
    subj_of, _, before, s_kinds, act_bm, act_ts, a_kinds, valid, table = \
        resolve
    rng = np.random.default_rng(seed + 1)
    iv_s = rng.integers(0, 40, nv).astype(np.int32)
    iv_e = iv_s + rng.integers(1, 8, nv).astype(np.int32)
    r_start = rng.integers(0, 40, rcap).astype(np.int32)
    arena = (r_start, r_start + rng.integers(1, 8, rcap).astype(np.int32),
             act_ts[:rcap], a_kinds[:rcap], valid[:rcap])
    return ((subj_of[:nv], iv_s, iv_e, before, s_kinds,
             rng.random(len(s_kinds)) < 0.5, *arena,
             act_bm, act_ts, a_kinds, valid, table),
            (subj_of[:nv], iv_s, iv_e, rng.random(nv) < 0.8, before, s_kinds,
             *arena, table))


def _programs(key):
    """The (resolve, finalize) pair of the key or of the range path, with
    their inputs and scope names; finalize_csr takes deps_resolve's output
    first."""
    from accord_tpu.ops import kernels
    if key == "key":
        resolve, finalize = _kernel_args(seed=9)
        return ((kernels.deps_resolve, resolve, RESOLVE_SCOPES),
                (kernels.finalize_csr, finalize, FINALIZE_SCOPES))
    resolve, finalize = _range_kernel_args(seed=9)
    return ((kernels.range_deps_resolve, resolve, RANGE_RESOLVE_SCOPES),
            (kernels.range_finalize_csr, finalize, RANGE_FINALIZE_SCOPES))


@pytest.mark.parametrize("program", [
    "deps_resolve", "finalize_csr", "range_deps_resolve",
    "range_finalize_csr"])
def test_lowered_text_carries_the_scope_names(program):
    (resolve, r_args, r_scopes), (finalize, f_args, f_scopes) = _programs(
        "range" if program.startswith("range") else "key")
    if program.endswith("deps_resolve"):
        lowered, scopes = resolve.lower(*r_args), r_scopes
    else:
        if program == "finalize_csr":
            f_args = (resolve(*r_args),) + f_args
        # an out-cap under the 32 (24) words of the slot matrix, so that the
        # compaction folds once and its word stages are in the program
        lowered, scopes = finalize.lower(*f_args, out_cap=16), f_scopes
    text = lowered.as_text(debug_info=True)
    missing = [s for s in scopes if f"/{s}/" not in text]
    assert not missing, f"{program} lowered without scopes {missing}"


def _lowered_resolve(program):
    """One of the three single-device resolve programs, lowered (never
    compiled) at the small shapes of `_kernel_args`."""
    from accord_tpu.ops import kernels
    resolve, _ = _kernel_args()
    subj_of, subj_keys, before, s_kinds, act_bm, act_ts, a_kinds, valid, \
        table = resolve
    b = len(s_kinds)
    if program == "deps_resolve":
        return kernels.deps_resolve.lower(*resolve)
    if program == "fused_deps_resolve":
        arena = (act_bm, act_ts, a_kinds, valid)
        return kernels.fused_deps_resolve.lower(
            subj_of, subj_keys, np.arange(b, dtype=np.int32) % 2, before,
            s_kinds, np.arange(2, dtype=np.int32), (arena, arena), table)
    rcap = 32
    ivs = np.arange(len(subj_of), dtype=np.int32)
    r_start = np.arange(rcap, dtype=np.int32)
    return kernels.range_deps_resolve.lower(
        subj_of, ivs, ivs + 3, before, s_kinds, np.ones(b, bool),
        r_start, r_start + 5, act_ts[:rcap], a_kinds[:rcap], valid[:rcap],
        act_bm, act_ts, a_kinds, valid, table)


@pytest.mark.parametrize("program", [
    "deps_resolve", "fused_deps_resolve", "range_deps_resolve"])
def test_lowered_resolve_holds_no_gather(program):
    # the witness test is a kind bit mask and one AND (kernels._witness_mask):
    # a table lookup at every candidate was 119 ms of 178 a dispatch on the
    # chip (ledger, PR 25)
    lowered = _lowered_resolve(program)
    assert "gather" not in lowered.as_text()
    if program == "deps_resolve":  # and the scope still holds the AND
        assert "/witness_before_mask/and" in lowered.as_text(debug_info=True)


@pytest.mark.parametrize("path", ["key", "range"])
def test_scope_names_change_no_answer(monkeypatch, path):
    import jax
    (resolve, r_args, r_scopes), (finalize, f_args, _) = _programs(path)

    def answers(resolve, finalize):
        out = resolve(*r_args)
        if path == "key":  # finalize_csr takes deps_resolve's output first
            return (out,) + tuple(finalize(out, *f_args, out_cap=256))
        return tuple(out) + tuple(finalize(*f_args, out_cap=256))

    named = answers(resolve, finalize)
    assert int(named[-4][-1]) > 0, "the inputs produced no dependency"
    # the same trace bodies with every scope a no-op
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    # (fresh functions: jit's trace cache would hand back the named trace)
    bare_resolve = jax.jit(lambda *a: resolve.__wrapped__(*a))
    bare_finalize = jax.jit(
        lambda *a, out_cap: finalize.__wrapped__(*a, out_cap=out_cap),
        static_argnames=("out_cap",))
    assert f"/{r_scopes[0]}/" not in \
        bare_resolve.lower(*r_args).as_text(debug_info=True)
    for a, b in zip(named, answers(bare_resolve, bare_finalize)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# -- (e) the flight recorder's vocabulary -------------------------------------

# the resolver's complete events of one rehearsal round (seed 11, recorded at
# commit 2386b74, before the primitive): track, name, args, simulated ms
# after the first event
GOLDEN = [
    ("stage_host", "preaccept", {"batch": 0}, 0),
    ("stage_host", "encode", {"subjects": 64, "stores": 1}, 0),
    ("stage_host", "encode", {"subjects": 32, "stores": 1}, 0),
    ("stage_host", "stage_host", {"hidden": False, "items": 96}, 0),
    ("device", "launch", {"did": 0}, 2),
    ("device", "launch", {"did": 1}, 2),
    ("stage_host", "preaccept", {"batch": 0}, 2),
    ("stage_host", "stage_host", {"hidden": True, "items": 0}, 2),
    ("device", "decode", {"hidden": True, "did": 0}, 6),
    ("device", "decode", {"hidden": False, "did": 1}, 6),
]


@pytest.mark.parametrize("wall", [False, True])
def test_recorder_events_are_what_they_were(wall):
    from benchmark.runners.batch import Arena
    p = _params()
    arena = Arena(p, 11)
    REC.clear()
    REC.configure(capacity=1 << 16, wall=wall)
    REC.enabled = True
    try:
        assert arena.round(p["subjects"])[2:4] == (0, 0)
    finally:
        REC.enabled = False
    events = REC.events()
    assert len(events) == 206 and REC.dropped == 0
    spans = [e for e in events if e["ph"] == "X"
             and e["tid"] in ("stage_host", "device")
             and e["name"] != "dispatch"]
    t0 = spans[0]["ts"]
    assert [(e["tid"], e["name"], e["args"], (e["ts"] - t0) // 1000)
            for e in spans] == GOLDEN
    if not wall:
        assert all(e["dur"] == 0 for e in spans)
        return
    assert all(e["dur"] > 0 for e in spans)
    r = arena.resolver
    by_name = {}
    for e in spans:
        by_name[e["name"]] = by_name.get(e["name"], 0.0) + e["dur"]
    # dur is the phase's own contribution to its timer, in microseconds
    for name, timer in (("preaccept", r.preaccept_s), ("encode", r.encode_s),
                        ("launch", r.dispatch_s), ("decode", r.decode_s)):
        assert by_name[name] == pytest.approx(timer * 1e6, abs=0.01)
    # a hidden decode ran with a call in flight: what of it the device had
    # finished is drained time, and the harvest's drained time lies in
    # those decodes or in the fetch before them
    hidden = sum(e["dur"] for e in spans
                 if e["name"] == "decode" and e["args"]["hidden"])
    drained = r.metrics.timer("resolver.drained_decode_s").total
    assert 0.0 <= drained * 1e6 <= hidden + (r.readback_s + 0.002) * 1e6


# -- (f) the range path's spans and counters -----------------------------------

RANGE_CELL = "preaccept-ranges-10k.range-20"


@pytest.fixture(scope="module")
def range_arena():
    from benchmark import common
    from benchmark.runners import ranges
    cell = common.load_json(common.HERE / "workloads" / f"{RANGE_CELL}.json")
    config = common.load_json(
        common.HERE / "configs" / f"{cell['config']}.json")
    p = {**config, **cell, **cell["rehearsal"]}
    arena = ranges.Arena(p, 11)
    assert sum(arena.round(p["subjects"])["wrong"].values()) == 0  # compiles
    return arena, p


@pytest.mark.parametrize("share", [0.0, 0.2, 1.0])
def test_range_timers_fit_their_parents_and_counters_follow_range_subjects(
        range_arena, share):
    from benchmark import common
    arena, p = range_arena
    arena.range_share = share
    before = arena.counters()
    r = arena.round(p["subjects"])
    d = common.delta(arena.counters(), before)
    assert sum(r["wrong"].values()) == 0 and r["failed"] == 0
    # the store holds range txns, so the range path runs for key subjects too
    assert 0.0 < d["resolver.range_encode_s"] <= d["resolver.encode_s"]
    assert 0.0 < d["resolver.range_decode_s"] <= d["resolver.decode_s"]
    n_range = r["subjects"]["range"]
    assert (n_range > 0) == (share > 0.0)
    assert d.get("resolver.range_subjects", 0) == n_range
    assert d.get("resolver.range_deps", 0) == r["range_range_deps"]
    # every group's range lanes decode as arrays; nothing here mutates the
    # store between a launch and its harvest, so no group filters
    assert d["resolver.range_array_decodes"] == d["resolver.dispatches"]
    assert "resolver.range_filtered_decodes" not in d
    # the whole-dispatch cut, once a domain a group: the key subjects'
    # (their key-lane pairs joined in) and the range subjects', each where
    # the dispatch holds any
    if share in (0.0, 1.0):
        assert d["resolver.array_cuts"] == d["resolver.dispatches"]
    else:
        assert d["resolver.dispatches"] < d["resolver.array_cuts"] \
            <= 2 * d["resolver.dispatches"]
    assert (d.get("resolver.range_intervals", 0) >= n_range) \
        and (d.get("resolver.range_intervals", 0) > 0) == (n_range > 0)


def test_key_only_store_cuts_once_a_dispatch(resolved):
    arena, _, d = resolved
    assert d["resolver.array_cuts"] == d["resolver.dispatches"] > 0
    assert arena.resolver.snapshot()["resolver.array_cuts"] >= \
        d["resolver.array_cuts"]


def test_key_only_store_never_enters_the_range_path(resolved):
    _, _, d = resolved
    for name in ("resolver.range_encode_s", "resolver.range_decode_s",
                 "resolver.range_subjects", "resolver.range_intervals",
                 "resolver.range_deps", "resolver.range_array_decodes",
                 "resolver.range_filtered_decodes"):
        assert name not in d, name


def test_range_spans_nest_inside_their_parents(range_arena, tmp_path):
    import jax
    arena, p = range_arena
    arena.range_share = 0.2
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        assert sum(arena.round(p["subjects"])["wrong"].values()) == 0
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.glob("plugins/profile/*/*.xplane.pb")
    data = jax.profiler.ProfileData.from_file(str(path))
    spans = {}
    for plane in data.planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("resolver."):
                    spans.setdefault(e.name, []).append(
                        (e.start_ns, e.start_ns + e.duration_ns))
    for child, parent in (("resolver.range_encode", "resolver.encode"),
                          ("resolver.range_decode", "resolver.materialize")):
        assert spans.get(child), f"no {child} span on a host plane"
        for s, e in spans[child]:
            assert any(ps <= s and e <= pe for ps, pe in spans[parent]), \
                f"a {child} span lies outside every {parent} span"


# -- (g) the store's lifecycle --------------------------------------------------

LIVE_CELL = "preaccept-batch-100k.resolve-4096"
LIFECYCLE = ("resolver.arena_sync_s", "resolver.arena_rows_uploaded",
             "resolver.arena_upload_calls", "resolver.compact_s",
             "resolver.arena_compactions", "resolver.compact_rows_kept",
             "resolver.grow_s", "resolver.arena_growths", "resolver.fence_s",
             "resolver.fence_materializes", "resolver.truncate_s",
             "resolver.truncated_txns")


def _live_params():
    from benchmark import common
    cell = common.load_json(common.HERE / "workloads" / f"{LIVE_CELL}.json")
    config = common.load_json(
        common.HERE / "configs" / f"{cell['config']}.json")
    # cap 128 -> 256 and a compaction every 4th round: the rehearsal's own
    # shape is longer, so that the runner's set-up has rounds to fill
    return {**config, **cell, **cell["rehearsal"],
            "resident_rounds": 4, "steady_cap": 256}


def _query_round(live):
    """A round of bare queries (`enqueue_deps`): nothing is registered, no
    wave runs. The change of every counter over it."""
    from benchmark import common
    subjects = [live.fresh() for _ in range(live.p["subjects"])]
    answers = []
    before = live.counters()
    for txn_id, _, partial, _ in subjects:
        live.resolver.enqueue_deps(
            live.store, txn_id, live.store.owned(partial.keys),
            txn_id.as_timestamp()).add_callback(
                lambda value, failure: answers.append((value, failure)))
    live.cluster.queue.drain(max_events=1_000_000)
    assert len(answers) == len(subjects)
    assert all(f is None for _, f in answers)
    return common.delta(live.counters(), before)


def test_lifecycle_counters_move_only_where_something_happened():
    from benchmark import common
    from benchmark.runners import live as live_runner
    p = _live_params()
    live = live_runner.Deployment(p, 11)
    seen = set()
    for i in range(p["resident_rounds"] + 8):
        before = live.counters()
        r = live.round()
        d = common.delta(live.counters(), before)
        assert (r["wrong"], r["failed"], r["refused"]) == (0, 0, 0)
        # a registration: every round has them, and they are what syncs
        assert d["resolver.arena_rows_uploaded"] >= p["subjects"]
        assert 0.0 < d["resolver.arena_sync_s"] <= d["resolver.encode_s"] \
            <= d["resolver.starved_stage_s"]
        # a wave: truncation and the fence, and only then
        waved = bool(r["waved"])
        assert bool(d.get("resolver.truncated_txns")) == waved
        assert bool(d.get("resolver.fence_materializes")) == waved
        assert (d.get("resolver.truncate_s", 0.0) > 0.0) == waved
        assert d.get("resolver.fence_s", 0.0) <= \
            d.get("resolver.truncate_s", 0.0)
        # the wave's walk: only where it truncates, and it holds them
        assert (d.get("store.cleanup_s", 0.0) > 0.0) == waved
        assert d.get("resolver.truncate_s", 0.0) <= \
            d.get("store.cleanup_s", 0.0)
        if waved:
            assert d["store.cleanup_scanned"] >= len(live.store.commands)
        # a fill: the arena was full when a row was asked for, so it grew
        # or compacted, and only then
        filled = bool(d.get("resolver.arena_compactions")
                      or d.get("resolver.arena_growths"))
        assert (d.get("resolver.compact_s", 0.0) > 0.0) == filled
        assert d.get("resolver.compact_s", 0.0) + \
            d.get("resolver.grow_s", 0.0) <= d["resolver.preaccept_s"]
        seen |= {k for k in LIFECYCLE if d.get(k)}
    assert seen == set(LIFECYCLE)
    # a round of bare queries ships what the last wave left dirty (the rows
    # it emptied: their key sets and valid flags, and the kid words), and
    # nothing else moves; the next one finds nothing registered, no wave,
    # no fill: none of them moves
    d = _query_round(live)
    assert d["resolver.arena_rows_uploaded"] == 2 * p["subjects"]
    assert {k for k in LIFECYCLE if d.get(k)} == {
        "resolver.arena_sync_s", "resolver.arena_rows_uploaded",
        "resolver.arena_upload_calls"}
    d = _query_round(live)
    assert d["resolver.dispatches"] > 0 and d["resolver.subjects"] > 0
    assert [k for k in LIFECYCLE if d.get(k)] == []
    assert not d.get("store.cleanup_s") and not d.get("store.cleanup_scanned")


def test_lifecycle_spans_nest_inside_their_parents(tmp_path):
    import jax
    from benchmark.runners import live as live_runner
    p = _live_params()
    live = live_runner.Deployment(p, 13)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        # from an empty store: the growth, the first waves, a compaction
        for _ in range(p["resident_rounds"] + 5):
            r = live.round()
            assert (r["wrong"], r["failed"], r["refused"]) == (0, 0, 0)
    finally:
        jax.profiler.stop_trace()
    assert live.resolver.arena_growths and live.resolver.arena_compactions
    (path,) = tmp_path.glob("plugins/profile/*/*.xplane.pb")
    data = jax.profiler.ProfileData.from_file(str(path))
    spans = {}
    for plane in data.planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(("resolver.", "store.")):
                    assert plane.name.startswith("/host:")
                    spans.setdefault(e.name, []).append(
                        (e.start_ns, e.start_ns + e.duration_ns))
    for child, parent in (("resolver.arena_sync", "resolver.encode"),
                          ("resolver.truncate", "store.cleanup"),
                          ("resolver.compact", "resolver.preaccept"),
                          ("resolver.grow", "resolver.preaccept"),
                          ("resolver.fence", "resolver.truncate"),
                          ("resolver.preaccept", "resolver.tick"),
                          ("resolver.encode", "resolver.tick")):
        assert spans.get(child), f"no {child} span on a host plane"
        for s, e in spans[child]:
            assert any(ps <= s and e <= pe for ps, pe in spans[parent]), \
                f"a {child} span lies outside every {parent} span"
    # a truncation is the store's call: inside no phase of the pipeline
    for s, e in spans["resolver.truncate"]:
        assert not any(ps <= s and e <= pe
                       for name in ("resolver.tick", "resolver.harvest")
                       for ps, pe in spans[name])
    assert len(spans["resolver.truncate"]) == live.resolver.truncated_txns
    assert len(spans["resolver.fence"]) == 5  # one a wave


ARENA_SCOPES = {
    "arena_scatter": ("bitmap_rebuild", "lane_scatter"),
    "arena_scatter_keys": ("bitmap_rebuild",),
    "scatter_rows": ("lane_scatter",),
    "kid_word_scatter": ("kid_word_scatter",),
    "arena_grow": ("arena_grow",),
}


@pytest.mark.parametrize("program", sorted(ARENA_SCOPES))
def test_arena_programs_carry_their_scope_names(program):
    from accord_tpu.ops import kernels
    cap, k, m, z = 64, 32, 8, 64
    bm = np.zeros((cap, k), np.float32)
    ts = np.zeros((cap, 3), np.int32)
    kd = np.zeros(cap, np.int32)
    vl = np.zeros(cap, bool)
    rows = np.zeros(m, np.int32)
    csr = (np.full(z, cap, np.int32), np.zeros(z, np.int32))
    args = {
        "arena_scatter": (bm, ts, ts, kd, vl, rows, *csr, ts[:m], ts[:m],
                          kd[:m], vl[:m]),
        "arena_scatter_keys": (bm, rows, *csr),
        "scatter_rows": (ts, rows, ts[:m]),
        "kid_word_scatter": (np.zeros((16, cap // 32), np.uint32),
                             np.full(z, 16, np.int32), np.zeros(z, np.int32),
                             np.zeros(z, np.uint32)),
        "arena_grow": (bm, ts, ts, kd, vl),
    }[program]
    kwargs = {"new_cap": 2 * cap} if program == "arena_grow" else {}
    text = getattr(kernels, program).lower(*args, **kwargs).as_text(
        debug_info=True)
    missing = [s for s in ARENA_SCOPES[program] if f"/{s}/" not in text]
    assert not missing, f"{program} lowered without scopes {missing}"


# -- (h) the node's fan-out -----------------------------------------------------

NODE_CELL = "preaccept-8stores-100k.fanout-4096"


def _node(stores, seed=17):
    from benchmark import common
    from benchmark.runners import node as node_runner
    cell = common.load_json(common.HERE / "workloads" / f"{NODE_CELL}.json")
    config = common.load_json(
        common.HERE / "configs" / f"{cell['config']}.json")
    p = {**config, **cell, **cell["rehearsal"], "stores": stores}
    return node_runner.Deployment(p, seed), p


@pytest.mark.parametrize("stores", [1, 8])
def test_node_counters_count_requests_and_stores_asked(stores):
    from benchmark import common
    node, p = _node(stores)
    asked = []
    intersecting = node.stores.intersecting
    node.stores.intersecting = lambda keys: (
        asked.append(intersecting(keys)) or asked[-1])
    before = node.counters()
    assert node.round(p["subjects"])[2:4] == (0, 0)
    d = common.delta(node.counters(), before)
    assert d["node.requests"] == len(asked) == p["subjects"]
    assert d["node.store_slices"] == sum(len(s) for s in asked) \
        == d["resolver.subjects"]
    assert d["node.fanout_s"] > 0.0 and d["node.reduce_s"] > 0.0
    if stores == 1:
        assert d["node.store_slices"] == d["node.requests"]
        # one group a dispatch runs the plain kernels: neither moves
        assert not d.get("resolver.fused_dispatches")
        assert not d.get("resolver.store_groups")
        assert d["resolver.dispatches"] > 0
    else:
        assert d["resolver.fused_dispatches"] == d["resolver.dispatches"] > 0
        assert d["resolver.store_groups"] == 8 * d["resolver.dispatches"]


def test_node_spans_open_once_a_request_and_nest_as_written(tmp_path):
    import jax
    node, p = _node(8)
    assert node.round(p["subjects"])[2:4] == (0, 0)  # compiles
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        assert node.round(p["subjects"])[2:4] == (0, 0)
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.glob("plugins/profile/*/*.xplane.pb")
    data = jax.profiler.ProfileData.from_file(str(path))
    spans = {}
    for plane in data.planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.split(".")[0] in ("node", "resolver", "bench"):
                    assert plane.name.startswith("/host:")
                    spans.setdefault(e.name, []).append(
                        (e.start_ns, e.start_ns + e.duration_ns))

    def inside(span, parents):
        return any(ps <= span[0] and span[1] <= pe
                   for name in parents for ps, pe in spans[name])

    assert len(spans["node.fanout"]) == len(spans["node.reduce"]) \
        == p["subjects"]
    # the fan-out is the caller's: inside the enqueue loop, in no phase of
    # the pipeline; the reduce runs where a harvest hands out the last
    # store's part, after that harvest's span has closed: in no phase either
    pipeline = ("resolver.tick", "resolver.launch", "resolver.harvest")
    assert all(inside(s, ("bench.enqueue",)) for s in spans["node.fanout"])
    for name in ("node.fanout", "node.reduce"):
        assert not any(inside(s, pipeline) for s in spans[name]), name
    assert not any(inside(s, ("bench.enqueue",)) for s in spans["node.reduce"])
    assert max(e for _, e in spans["node.fanout"]) \
        <= min(e for _, e in spans["resolver.harvest"]) \
        <= min(s for s, _ in spans["node.reduce"])
