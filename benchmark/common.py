"""What both runners share: the compile meter, the gated counters, counter
snapshots and their change over a window, the ratio evaluator behind every
per-layer metric file, exact percentiles, the device check and the profiler
slice. `CompileMeter` and the counter lists are copies of `chip_smoke.py`'s
(commit ee317a3); `percentile_exact` is `serve/loadgen.py`'s."""
from __future__ import annotations

import json
import math
import shutil
import time
from pathlib import Path

from benchmark import trace_reduce

ROOT = Path(__file__).resolve().parent.parent
HERE = Path(__file__).resolve().parent
TRACE_DIR = ROOT / ".chip_scratch" / "benchmark_trace"

# counters that mean "the device answer was not used" or "a device plane lost
# integrity": zero in a fault-free run
GATED_RESOLVER = (
    "resolver.checksum_mismatches", "resolver.degraded_dispatches",
    "resolver.quarantine_entries", "resolver.device_watchdog_trips",
    "resolver.finalize_fallbacks", "resolver.cmd_span_replays",
)
# device-work counters: positive, so that "no fallback" cannot mean "no
# device work"
RESOLVER_WORK = ("resolver.dispatches", "resolver.finalized_decodes")


class CompileMeter:
    """XLA compile activity as jax.monitoring reports it: every backend
    compile request, the time spent in them, and how many were answered from
    the persistent cache instead of compiled from source."""

    def __init__(self):
        import jax.monitoring as mon
        self.requests = 0
        self.seconds = 0.0
        self.cache_hits = 0
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.requests += 1
            self.seconds += duration

    def _on_event(self, event, **_kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def read(self):
        return {"requests": self.requests, "compile_s": self.seconds,
                "cache_hits": self.cache_hits}


class CollectorWatch:
    """The garbage collector's runs inside the timed spans, by generation:
    how many and their seconds. A context manager round each span, and
    `on_collection` in `gc.callbacks`."""

    def __init__(self):
        self.timed = False
        self.collections = [0, 0, 0]
        self.seconds = [0.0, 0.0, 0.0]
        self._t0 = None

    def __enter__(self):
        self.timed = True

    def __exit__(self, *exc):
        self.timed = False

    def on_collection(self, phase, info):
        if phase == "start":
            self._t0 = time.perf_counter() if self.timed else None
        elif self._t0 is not None:
            g = info["generation"]
            self.collections[g] += 1
            self.seconds[g] += time.perf_counter() - self._t0
            self._t0 = None

    def read(self):
        return {"collections": list(self.collections),
                "seconds": list(self.seconds)}


def counter_comparisons(counters):
    """What `counter_faults` compares, each number beside its limit: the
    gated counters summed against 0, the least device-work counter against
    1."""
    return {"gated_counters": [sum(counters.get(n, 0) for n in GATED_RESOLVER),
                               0],
            "device_work_min": [min(counters.get(n, 0) for n in RESOLVER_WORK),
                                1]}


def counter_faults(counters):
    """Why a run's resolver counters make it incorrect: a gated counter that
    moved, or a device-work counter that did not. Empty when sound."""
    bad = {n: counters.get(n, 0) for n in GATED_RESOLVER if counters.get(n, 0)}
    idle = [n for n in RESOLVER_WORK if not counters.get(n, 0) > 0]
    return ([f"gated counters not zero: {bad}"] if bad else []) + \
        ([f"no device work counted in {idle}"] if idle else [])


def numeric(snapshot):
    return {k: v for k, v in snapshot.items()
            if isinstance(v, (int, float)) and not isinstance(v, bool)}


def summed(snapshots):
    out = {}
    for snap in snapshots:
        for k, v in numeric(snap).items():
            out[k] = out.get(k, 0) + v
    return out


def delta(after, before):
    """Each counter's change over the window; one that first appears inside
    the window started from 0."""
    return {k: v - before.get(k, 0) for k, v in after.items()}


def evaluate_ratio(spec, counters):
    """`scale * sum(num) / sum(den)` over names in the flat counter dict; an
    empty `den` divides by 1. None (nothing to read) where a name is missing
    or the denominator is 0."""
    names = list(spec["num"]) + list(spec.get("den", ()))
    if any(n not in counters for n in names):
        return None
    den = sum(counters[n] for n in spec["den"]) if spec.get("den") else 1
    if den == 0:
        return None
    return spec.get("scale", 1.0) * sum(counters[n] for n in spec["num"]) / den


def percentile_exact(samples, p):
    """Exact sample percentile (nearest-rank)."""
    if not samples:
        return 0.0
    s = sorted(samples)
    return s[max(0, math.ceil(len(s) * p / 100.0) - 1)]


def load_json(path):
    with open(path) as f:
        return json.load(f)


def device_report(devices):
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": max(peaks)}


def start_trace():
    """Open a profiler slice under .chip_scratch/ (ignored by git). The
    Python tracer is off: it records 10^4 events a second and none of them
    is read."""
    import jax
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    TRACE_DIR.mkdir(parents=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)


def stop_trace():
    import jax
    jax.profiler.stop_trace()


def host_span(name):
    """A span of the benchmark's own on the profiler's clock (inert, well
    under a microsecond, when no profiler session is open)."""
    import jax
    return jax.profiler.TraceAnnotation(name)


def window_span():
    """trace_reduce counts device time and gaps inside such spans only."""
    return host_span(trace_reduce.WINDOW_SPAN)


def reduce_trace(fallback_window_s, dump_to=None):
    """Reduce the slice just closed and remove it, so the tree a check copies
    stays small."""
    try:
        files = sorted(TRACE_DIR.glob("plugins/profile/*/*.xplane.pb"))
        if not files:
            return None
        return trace_reduce.reduce_file(str(files[-1]), fallback_window_s,
                                        dump_to=dump_to)
    finally:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)


def traced_counters(traced, dispatches):
    """What a traced slice adds to the run's counter dict."""
    if not traced:
        return {}
    return {"device_busy_s": traced["busy_s"], "traced_s": traced["window_s"],
            "traced_dispatches": dispatches}
