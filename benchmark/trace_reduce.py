"""From a profiler trace (.xplane.pb) to device busy time, the traced window
and the breakdown the last line carries. Reads the file with JAX alone
(`jax.profiler.ProfileData`).

Busy is the union of the intervals in which an operation ran on a device
plane (`/device:TPU:<n>`, its "XLA Ops" line), clipped to the benchmark's own
`bench.window` spans where the trace has them (a runner wraps each measured
stretch in one, so the checks between the batch cell's rounds are in neither
busy nor window). With several chips busy is averaged over the device planes.
Each of the longest gaps between device operations inside the window is
labelled by the host span that holds most of it (`label_gap`): the program's
`obs.trace.phase` spans (`resolver.materialize`, `resolver.tick`, ...) and
the runners' own (`bench.enqueue`). "host, unattributed" stays where spans
cover under half a gap.
"""
from __future__ import annotations

import bisect
import json
import re

WINDOW_SPAN = "bench.window"
# a span of the program or of a runner: a lower-case dotted name, which no
# event of JAX's runtime on the host planes has
SPAN_NAME = re.compile(r"[a-z_]+(\.[a-z_0-9]+)+$")
UNATTRIBUTED = "host, unattributed"
DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"  # one event per program run: "jit_f(<hash>)"
TOP = 10


def union(intervals):
    """Sorted, merged [start, end) intervals."""
    out = []
    for a, b in sorted(i for i in intervals if i[1] > i[0]):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def total(merged):
    return sum(b - a for a, b in merged)


def clip(merged, windows):
    """The part of merged intervals that lies inside merged windows."""
    out = []
    for a, b in merged:
        for wa, wb in windows:
            lo, hi = max(a, wa), min(b, wb)
            if hi > lo:
                out.append([lo, hi])
    return union(out)


def gaps(merged, windows):
    """The idle stretches of each window: what no merged interval covers."""
    out = []
    for wa, wb in windows:
        at = wa
        for a, b in clip(merged, [[wa, wb]]):
            if a > at:
                out.append([at, a])
            at = b
        if wb > at:
            out.append([at, wb])
    return out


def short_names(ops, modules):
    """Operation events renamed "<program>: <op> = <result type>": the op
    line's names are whole HLO instructions, and "%fusion" alone says nothing
    without the program it ran in (the module event that holds its start)."""
    modules = sorted((s, s + d, n.split("(")[0]) for n, s, d in modules)
    starts = [m[0] for m in modules]
    out = []
    for name, s, d in ops:
        i = bisect.bisect_right(starts, s) - 1
        program = modules[i][2] if i >= 0 and s < modules[i][1] else "?"
        out.append((f"{program}: {name.split('{')[0]}", s, d))
    return out


def label_gap(a, b, spans):
    """The name of the span that holds most of the gap [a, b). A name holds
    what the union of its spans covers of the gap. Of the names that hold
    half of it or more, the one that holds least: a child lies inside its
    parent, so that is the most specific span that still accounts for the
    gap (`resolver.materialize` inside `resolver.harvest`). Where none holds
    half, the name that holds most; UNATTRIBUTED where all spans together
    hold under half. `spans`: [(name, start, end)]."""
    by_name = {}
    for name, s, e in spans:
        if e > a and s < b:
            by_name.setdefault(name, []).append([max(s, a), min(e, b)])
    held = {name: total(union(parts)) for name, parts in by_name.items()}
    half = (b - a) / 2
    if total(union(p for parts in by_name.values() for p in parts)) < half:
        return UNATTRIBUTED
    enough = [name for name in held if held[name] >= half]
    if enough:
        return min(enough, key=held.get)
    return max(held, key=held.get)


def reduce_planes(planes, fallback_window_s):
    """`planes`: [(plane name, [(line name, [(event name, start_ns,
    duration_ns)])])]. Returns busy_s, window_s and the breakdown, or None
    where no device plane holds an operation."""
    windows, devices, spans = [], [], []
    for plane, lines in planes:
        by_line = dict(lines)
        if plane.startswith(DEVICE_PLANE):
            devices.append(short_names(by_line.get(OPS_LINE, ()),
                                       by_line.get(MODULES_LINE, ())))
            continue
        for _, events in lines:
            windows += [[s, s + d] for n, s, d in events if n == WINDOW_SPAN]
            spans += [(n, s, s + d) for n, s, d in events
                      if n != WINDOW_SPAN and SPAN_NAME.match(n)]
    devices = [ev for ev in devices if ev]
    if not devices:
        return None
    windows = union(windows)
    if not windows:  # no span of ours in the trace: the host's clock decides
        lo = min(s for ev in devices for _, s, _ in ev)
        windows = [[lo, lo + fallback_window_s * 1e9]]
    busy, by_op, idle = [], {}, []
    for events in devices:
        merged = clip(union([s, s + d] for _, s, d in events), windows)
        busy.append(total(merged))
        idle += gaps(merged, windows)
        for name, s, d in events:
            inside = total(clip([[s, s + d]], windows))
            if inside:
                by_op[name] = by_op.get(name, 0) + inside
    n = len(devices)
    ops = sorted(by_op.items(), key=lambda kv: -kv[1])[:TOP]
    idle = sorted(idle, key=lambda g: g[0] - g[1])[:TOP]
    return {
        "busy_s": sum(busy) / n / 1e9,
        "window_s": total(windows) / 1e9,
        "windows": len(windows),
        "device_planes": n,
        "breakdown": {
            "device_ops": [[name, ns / n / 1e9] for name, ns in ops],
            "idle_gaps": [[label_gap(a, b, spans), (b - a) / 1e9]
                          for a, b in idle],
        },
    }


def read_planes(path):
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    return [(plane.name, [(line.name, [(e.name, e.start_ns, e.duration_ns)
                                       for e in line.events])
                          for line in plane.lines])
            for plane in data.planes]


def describe(planes, first=5):
    """What a trace holds, for reading one by hand."""
    return [{"plane": plane, "lines": [
        {"line": line, "events": len(events),
         "first": [list(e) for e in events[:first]],
         "names": sorted({e[0] for e in events})[:40]}
        for line, events in lines]} for plane, lines in planes]


def reduce_file(path, fallback_window_s, dump_to=None):
    planes = read_planes(path)
    if dump_to:
        with open(dump_to, "w") as f:
            json.dump(describe(planes), f, indent=1)
    return reduce_planes(planes, fallback_window_s)
