"""Device time of named programs inside a traced slice: what a runner adds to
`common.reduce_trace` when one of its per-layer metrics sums the operations
of some device programs (`runners/live.py`: the arena's sync programs).
`runners/ranges.py` holds an older copy of both functions for its range
programs; its move onto this file is a benchmark PR's."""
from __future__ import annotations

import json
import shutil

from benchmark import common, trace_reduce


def device_seconds(planes, programs):
    """Device time of the operations of `programs` (names as the "XLA
    Modules" line gives them) inside the benchmark's window spans, averaged
    over the device planes; None where the trace has no device plane."""
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
        if name.split(":")[0] in programs), windows))
        for events in devices]
    return sum(busy) / len(busy) / 1e9


def reduce_slice(programs, fallback_window_s, dump_to=None):
    """`common.reduce_trace`, and the device time of `programs` read from the
    same slice before it is removed."""
    try:
        files = sorted(common.TRACE_DIR.glob("plugins/profile/*/*.xplane.pb"))
        if not files:
            return None, None
        planes = trace_reduce.read_planes(str(files[-1]))
        if dump_to:
            with open(dump_to, "w") as f:
                json.dump(trace_reduce.describe(planes), f, indent=1)
        return (trace_reduce.reduce_planes(planes, fallback_window_s),
                device_seconds(planes, programs))
    finally:
        shutil.rmtree(common.TRACE_DIR, ignore_errors=True)
