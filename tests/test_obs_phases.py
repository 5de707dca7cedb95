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
  4. scope names -- the lowered text of both programs carries every stage
     name, and the names change no answer;
  5. the flight recorder's vocabulary is what it was before the primitive.
"""
from __future__ import annotations

import contextlib
import time

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
        rng.integers(-1, cap, b).astype(np.int32),
        resolve[5])
    return resolve, finalize


RESOLVE_SCOPES = ("subject_bitmap", "overlap", "witness_before_mask",
                  "pack_bits")
FINALIZE_SCOPES = ("slot_mask", "bound", "popcount_prefix", "word_compact",
                   "bit_expand", "row_scatter", "ts_gather", "checksum")


@pytest.mark.parametrize("program,scopes", [
    ("deps_resolve", RESOLVE_SCOPES), ("finalize_csr", FINALIZE_SCOPES)])
def test_lowered_text_carries_the_scope_names(program, scopes):
    from accord_tpu.ops import kernels
    resolve, finalize = _kernel_args()
    if program == "deps_resolve":
        lowered = kernels.deps_resolve.lower(*resolve)
    else:
        packed = kernels.deps_resolve(*resolve)
        lowered = kernels.finalize_csr.lower(packed, *finalize, out_cap=256)
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


def test_scope_names_change_no_answer(monkeypatch):
    import jax
    from accord_tpu.ops import kernels
    resolve, finalize = _kernel_args(seed=9)
    packed = kernels.deps_resolve(*resolve)
    named = (packed,) + tuple(
        kernels.finalize_csr(packed, *finalize, out_cap=256))
    assert int(named[1][-1]) > 0, "the inputs produced no dependency"
    # the same trace bodies with every scope a no-op
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    # (fresh functions: jit's trace cache would hand back the named trace)
    bare_resolve = jax.jit(
        lambda *a: kernels.deps_resolve.__wrapped__(*a))
    bare_finalize = jax.jit(
        lambda *a, out_cap: kernels.finalize_csr.__wrapped__(
            *a, out_cap=out_cap), static_argnames=("out_cap",))
    assert "/overlap/" not in \
        bare_resolve.lower(*resolve).as_text(debug_info=True)
    bare_packed = bare_resolve(*resolve)
    bare = (bare_packed,) + tuple(
        bare_finalize(bare_packed, *finalize, out_cap=256))
    for a, b in zip(named, bare):
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
    hidden = sum(e["dur"] for e in spans if e["args"].get("hidden"))
    assert hidden == pytest.approx(r.host_hidden_s * 1e6, abs=0.01)
