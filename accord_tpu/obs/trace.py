"""FlightRecorder: a bounded ring buffer of trace events.

Disabled (the default) every record call is a single attribute check and
an immediate return -- no allocation, no clock read -- so the recorder can
stay compiled into every hot path. Enabled, events append into a deque
ring (oldest dropped beyond `capacity`, counted in `dropped`).

Timestamps are supplied by callers in MICROSECONDS from the owning node's
time service -- deterministic sim time in the simulator, so two same-seed
runs produce byte-identical event streams. Wall-clock durations are
recorded only when `wall=True` (the bench's trace mode); with it off, the
default, spans carry dur=0 and the stream stays replay-identical.

Event vocabulary (Chrome trace_event phases, exported by obs/export.py):
  X  complete span   (host pipeline stages; dur = wall us when enabled)
  i  instant         (messages, status transitions, delta uploads)
  b/e async span     (device in-flight windows keyed by dispatch id;
                      txn lifecycle keyed by TxnId)
  s/t/f flow         (coordinator -> replica -> device dispatch linking)

No recorder call may originate under jax tracing: the append funnel
asserts jax's `trace_state_clean()` while recording, so a span
accidentally placed inside a jit-traced function fails loudly at trace
time instead of silently baking one stale event into the compiled
artifact (guard unit-tested in tests/test_obs.py).

Host pipeline stages are entered through `phase`, which also puts the span
on the jax profiler's clock and feeds an owner's `Occupancy` account (both
below; unit-tested in tests/test_obs_phases.py). `watch_collector` puts the
garbage collector's pauses into the registries that ask for them.
"""
from __future__ import annotations

import contextlib
import gc
import sys
import time
import weakref
from collections import deque
from typing import Callable, List, Optional

_TXN_CAT = "txn"
_FLOW_CAT = "txnflow"

_jax_clean: Optional[Callable[[], bool]] = None


def _tracing_clean() -> bool:
    """True when NOT under a jax trace (cheap after the first call, which
    imports jax)."""
    global _jax_clean
    if _jax_clean is None:
        # jax 0.9 keeps this predicate in jax._src.core only
        from jax._src.core import trace_state_clean
        _jax_clean = trace_state_clean
    return _jax_clean()


class FlightRecorder:
    __slots__ = ("enabled", "wall", "clock", "dropped", "_buf")

    def __init__(self, capacity: int = 1 << 16):
        self.enabled = False
        # include wall-clock durations/args in events (breaks byte-identical
        # replay of same-seed sim traces; the bench opts in)
        self.wall = False
        # () -> int microseconds, used only by callers with no node in scope
        # (deltas.flush_lane); the sim cluster and maelstrom point it at
        # their deterministic clocks
        self.clock: Optional[Callable[[], int]] = None
        self.dropped = 0
        self._buf: deque = deque(maxlen=capacity)

    # -- lifecycle -----------------------------------------------------------
    def configure(self, capacity: Optional[int] = None,
                  wall: Optional[bool] = None) -> None:
        if capacity is not None and capacity != self._buf.maxlen:
            self._buf = deque(self._buf, maxlen=capacity)
        if wall is not None:
            self.wall = wall

    def clear(self) -> None:
        self._buf.clear()
        self.dropped = 0

    def events(self) -> List[dict]:
        return list(self._buf)

    def __len__(self) -> int:
        return len(self._buf)

    def now_us(self) -> int:
        if self.clock is not None:
            return self.clock()
        return time.monotonic_ns() // 1000

    # -- append funnel -------------------------------------------------------
    def _append(self, ev: dict) -> None:
        if not _tracing_clean():
            raise RuntimeError(
                "FlightRecorder call under jax tracing: recorder calls must "
                f"stay outside jit-traced code (event {ev.get('name')!r})")
        if len(self._buf) == self._buf.maxlen:
            self.dropped += 1
        self._buf.append(ev)

    # -- record API (every method no-ops unless enabled) ---------------------
    def complete(self, pid: int, tid: str, name: str, ts: int,
                 dur: float = 0.0, args: Optional[dict] = None) -> None:
        if not self.enabled:
            return
        ev = {"ph": "X", "pid": pid, "tid": tid, "name": name, "ts": ts,
              "dur": dur if self.wall else 0}
        if args:
            ev["args"] = args
        self._append(ev)

    def instant(self, pid: int, tid: str, name: str, ts: int,
                args: Optional[dict] = None) -> None:
        if not self.enabled:
            return
        ev = {"ph": "i", "pid": pid, "tid": tid, "name": name, "ts": ts,
              "s": "t"}
        if args:
            ev["args"] = args
        self._append(ev)

    def async_begin(self, pid: int, tid: str, name: str, span_id: str,
                    ts: int, cat: str = "device", local: bool = False,
                    args: Optional[dict] = None) -> None:
        """local=True scopes the span id to the process (Chrome id2.local):
        device dispatch ids are per-node counters, so five nodes each
        opening window "d0" must not pair up cross-process. Txn spans stay
        global -- their ids (TxnIds) are cluster-unique and their flows
        deliberately cross processes."""
        if not self.enabled:
            return
        ev = {"ph": "b", "pid": pid, "tid": tid, "name": name, "ts": ts,
              "cat": cat}
        ev.update({"id2": {"local": span_id}} if local else {"id": span_id})
        if args:
            ev["args"] = args
        self._append(ev)

    def async_end(self, pid: int, tid: str, name: str, span_id: str,
                  ts: int, cat: str = "device", local: bool = False,
                  args: Optional[dict] = None) -> None:
        if not self.enabled:
            return
        ev = {"ph": "e", "pid": pid, "tid": tid, "name": name, "ts": ts,
              "cat": cat}
        ev.update({"id2": {"local": span_id}} if local else {"id": span_id})
        if args:
            ev["args"] = args
        self._append(ev)

    def flow(self, pid: int, tid: str, ph: str, flow_id: str,
             ts: int) -> None:
        """One flow step: ph 's' (start), 't' (step), or 'f' (finish),
        binding to the zero-duration slice emitted at the same (track, ts)."""
        if not self.enabled:
            return
        self._append({"ph": ph, "pid": pid, "tid": tid, "name": "txn",
                      "ts": ts, "cat": _FLOW_CAT, "id": flow_id,
                      **({"bp": "e"} if ph == "f" else {})})

    # -- txn lifecycle helpers (coordinator + replica call sites) ------------
    def txn_begin(self, pid: int, txn_id, ts: int,
                  args: Optional[dict] = None) -> None:
        if not self.enabled:
            return
        tid_s = str(txn_id)
        self.async_begin(pid, "txn", "coordinate", tid_s, ts, cat=_TXN_CAT,
                         args=args)
        self.flow(pid, "txn", "s", tid_s, ts)

    def txn_step(self, pid: int, txn_id, name: str, ts: int,
                 args: Optional[dict] = None) -> None:
        """A replica/coordinator status transition: a zero-duration slice
        (so the flow has something to bind to) plus a flow step."""
        if not self.enabled:
            return
        tid_s = str(txn_id)
        ev = {"ph": "X", "pid": pid, "tid": "txn", "name": name, "ts": ts,
              "dur": 0}
        if args:
            ev["args"] = args
        self._append(ev)
        self.flow(pid, "txn", "t", tid_s, ts)

    def txn_end(self, pid: int, txn_id, ts: int,
                args: Optional[dict] = None) -> None:
        if not self.enabled:
            return
        tid_s = str(txn_id)
        self.async_end(pid, "txn", "coordinate", tid_s, ts, cat=_TXN_CAT,
                       args=args)
        self.flow(pid, "txn", "f", tid_s, ts)


# The process-global recorder every instrumentation site checks. Hot paths
# read `REC.enabled` (one attribute load) before doing any work.
REC = FlightRecorder()

# Trace process id for cluster-scoped spans (the ClusterTickEngine's
# per-tick megakernel span): node pids are NodeIds >= 1, so 0 is free.
CLUSTER_PID = 0


def recorder() -> FlightRecorder:
    return REC


def node_pid(node) -> int:
    """Trace process id for a Node: its integer NodeId."""
    return int(getattr(node, "id", 0) or 0)


def node_ts(node) -> int:
    """Deterministic event timestamp for a Node: its time service's
    microsecond clock (sim time under the simulator, so same-seed runs
    emit byte-identical streams)."""
    svc = getattr(node, "time_service", None)
    return svc.now_micros() if svc is not None else REC.now_us()


class Occupancy:
    """Where the device's idle time went, by what the host was doing.

    Two counts on the host clock: work accepted and not yet answered
    (`pending`) and device calls launched and not yet fetched (`inflight`).
    While work is pending the time falls in one of three states:

      running  -- a call is in flight and the device has not finished the
                  newest one;
      STARVED  -- no call is in flight: the host has given the device
                  nothing;
      DRAINED  -- calls are in flight and the device has finished all of
                  them: their results are not on the host yet.

    Starved and drained time is credited to what the host was doing in it:
    `phases` maps a phase name (as `phase` reports it) to a bucket, a
    nested phase that is not in the map stays in its parent's bucket, and
    time outside every phase is bucket "outside". One registry timer
    `<prefix>.starved_<bucket>_s` and one `<prefix>.drained_<bucket>_s` per
    bucket; while work is pending the two families together are the
    device's idle time on the host clock. Time with nothing pending is in
    none of them. An interval is credited when it closes: at every phase
    boundary and every launch, and when the first item is accepted, the
    last call lands or the last answer is delivered.

    The device's side comes from the caller: `launched(stamp)` hands over
    an object whose `done_at` is None until someone (the resolver's
    completion waiter, or the call's landing) writes the clock
    reading at which the call's outputs were ready. Calls finish in launch
    order, so the open interval is split at the newest call's `done_at`:
    what lies after it is drained. The clock is never read per item."""

    __slots__ = ("pending", "inflight", "_clock", "_phases", "_starved",
                 "_drained", "_stack", "_since", "_busy", "_newest")

    def __init__(self, registry, prefix: str, phases: dict,
                 clock: Callable[[], float] = time.perf_counter):
        self.pending = 0
        self.inflight = 0
        self._clock = clock
        buckets = ("outside", *phases.values())
        self._starved = {b: registry.timer(f"{prefix}.starved_{b}_s")
                         for b in buckets}
        self._drained = {b: registry.timer(f"{prefix}.drained_{b}_s")
                         for b in buckets}
        self._phases = dict(phases)
        self._stack = ["outside"]  # the bucket of each open phase
        # start of the open interval with work pending (None: nothing
        # pending), and whether a call was in flight through it
        self._since: Optional[float] = None
        self._busy = False
        # the newest launched call's stamp holder
        self._newest = None

    def _turn(self) -> None:
        """Close the open interval into the current bucket: all of it if
        nothing was in flight, what followed the newest call's completion
        if something was; open the next one if work is still pending."""
        now = self._clock()
        since = self._since
        if since is not None:
            if not self._busy:
                self._starved[self._stack[-1]].add(now - since)
            else:
                done = getattr(self._newest, "done_at", None)
                if done is not None and done < now:
                    self._drained[self._stack[-1]].add(now - max(done, since))
        self._since = now if self.pending else None
        self._busy = bool(self.inflight)

    def accept(self) -> None:
        self.pending += 1
        if self.pending == 1:
            self._turn()

    def deliver(self, n: int = 1) -> None:
        self.pending -= n
        if not self.pending:
            self._turn()

    def launched(self, stamp=None) -> None:
        """A call left for the device; `stamp.done_at` says when it
        finished (None: not known yet, or never where no stamp is given)."""
        self.inflight += 1
        self._turn()
        self._newest = stamp

    def landed(self) -> None:
        self.inflight -= 1
        if not self.inflight:
            self._turn()

    def enter(self, name: str) -> None:
        self._turn()
        self._stack.append(self._phases.get(name, self._stack[-1]))

    def exit(self) -> None:
        self._turn()
        self._stack.pop()


class _CollectorWatch:
    """The process's one `gc.callbacks` hook: every collection's pause is
    added to `gc.pause_s` and counted in `gc.collections` (and, for the
    oldest generation, `gc.full_collections`) of each registry that asked,
    held weakly -- of a registry that came with an Occupancy account, only
    the collections that ran while the account had work pending, so what a
    caller does between its requests is not its owner's. A full collection
    also opens a `gc.collect` span on the profiler's clock (arg
    `generation`); the young generations run hundreds of times a second
    and get none."""

    def __init__(self):
        self._cells = weakref.WeakKeyDictionary()
        self._t0 = 0.0
        self._span = None
        gc.callbacks.append(self._on_collection)

    def add(self, registry, account: Optional[Occupancy]) -> None:
        self._cells[registry] = (registry.timer("gc.pause_s"),
                                 registry.counter("gc.collections"),
                                 registry.counter("gc.full_collections"),
                                 account)

    def _on_collection(self, phase_: str, info: dict) -> None:
        full = info["generation"] == 2
        if phase_ == "start":
            if full and "jax" in sys.modules:
                from jax.profiler import TraceAnnotation
                self._span = TraceAnnotation("gc.collect", generation=2)
                self._span.__enter__()
            self._t0 = time.perf_counter()
            return
        dt = time.perf_counter() - self._t0
        if self._span is not None:
            self._span.__exit__(None, None, None)
            self._span = None
        for pause, runs, fulls, account in list(self._cells.values()):
            if account is not None and not account.pending:
                continue
            pause.total += dt
            runs.value += 1
            fulls.value += full


_collector_watch: Optional[_CollectorWatch] = None


def watch_collector(registry, account: Optional[Occupancy] = None) -> None:
    """Time the garbage collector into `registry` from now on, where
    `account` is given only while it has work pending (the hook is
    installed by the first caller and stays for the process)."""
    global _collector_watch
    if _collector_watch is None:
        _collector_watch = _CollectorWatch()
    _collector_watch.add(registry, account)


class phase:
    """One host phase of an owner's pipeline, entered and left in one place:

        with phase(registry, "resolver.encode", "resolver.encode_s",
                   account=occupancy, node=node, track="stage_host",
                   event="encode") as ph:
            ...
            ph.args = {"subjects": n}

    Leaving it adds the wall time to the registry timer (`timer`, where
    given; `ph.dt` holds it afterwards), emits the flight recorder's
    complete event on (`track`, `event`) with `ph.args` when REC is enabled,
    closes the `jax.profiler.TraceAnnotation` opened on entry -- so the span
    is in the profiler's host plane, on the device trace's clock, whenever a
    profiler session is open, and costs well under a microsecond when none is;
    a process that has not imported jax gets none and imports none --
    and tells `account` (an Occupancy) that the host left the phase. `ids`
    (a dispatch id) become the annotation's arguments and seed `ph.args`.
    Meant per phase and per dispatch, never per item."""

    __slots__ = ("args", "dt", "_timer", "_account", "_name", "_node",
                 "_track", "_event", "_span", "_ts", "_t0")

    def __init__(self, registry, name: str, timer: Optional[str] = None, *,
                 account: Optional[Occupancy] = None, node=None,
                 track: Optional[str] = None, event: Optional[str] = None,
                 **ids):
        self.args = ids or None
        self.dt = 0.0
        self._timer = registry.timer(timer) if timer else None
        self._account = account
        self._name = name
        self._node = node
        self._track = track
        self._event = event
        # a process that never imported jax has no profiler session to
        # annotate, and importing it here would stall a host-only node's
        # protocol thread for seconds inside its first request
        if "jax" in sys.modules:
            from jax.profiler import TraceAnnotation
            self._span = TraceAnnotation(name, **ids)
        else:
            self._span = contextlib.nullcontext()

    def __enter__(self) -> "phase":
        if self._account is not None:
            self._account.enter(self._name)
        self._ts = node_ts(self._node) if self._track and REC.enabled else 0
        self._span.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.dt = dt = time.perf_counter() - self._t0
        self._span.__exit__(*exc)
        if self._timer is not None:
            self._timer.add(dt)
        if self._track and REC.enabled:
            REC.complete(node_pid(self._node), self._track, self._event,
                         self._ts, dur=round(dt * 1e6, 3), args=self.args)
        if self._account is not None:
            self._account.exit()
