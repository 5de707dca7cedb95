"""The served runner: `nodes` NodeServers at ServeConfig defaults on one
asyncio loop and loopback sockets, in the process that owns the chip, driven
by benchmark/loadgen.py in a closed loop (`clients`) or an open loop
(`rate`). `chip_smoke.py` `serve_and_drive` (commit ee317a3) with a timed
window round the middle; `free_ports` and `loop_stall_watch` are copies.

Set-up: `NodeServer.run()` warms its tiers, the client connects, and
`warmup_s` of the cell's own traffic runs untimed. Every counter is its
change over the window (the nodes' `stats` admin call before and after).
The time-out is the deployment's and is never lengthened; a failed txn
counts as failed whatever the loop was doing, and freezes of the shared loop
are reported in the notes.
"""
from __future__ import annotations

import asyncio
import socket
import time

from benchmark import common
from benchmark.loadgen import LoadClient, LoadGen, verify_history

BAD_LOG = ("error handling", "uncaught", "inconsistent timestamp",
           "frame error")


def free_ports(n):
    socks = [socket.socket() for _ in range(n)]
    for s in socks:
        s.bind(("127.0.0.1", 0))
    ports = [s.getsockname()[1] for s in socks]
    for s in socks:
        s.close()
    return ports


async def loop_stall_watch(worst, freezes, freeze_s, device_call_s):
    """worst[0] = the longest this loop went without running a ready task
    (ms). The nodes and the load client share the loop, so whatever blocks it
    eats into every node's rpc time-out at once. A stall of `freeze_s` or
    more is kept in `freezes` as (from, to, seconds of it spent inside the
    resolvers' device calls)."""
    while True:
        t0, d0 = time.monotonic(), device_call_s()
        await asyncio.sleep(0.01)
        t1 = time.monotonic()
        worst[0] = max(worst[0], (t1 - t0 - 0.01) * 1e3)
        if t1 - t0 >= freeze_s:
            freezes.append((t0, t1, device_call_s() - d0))


async def traced_slice(servers, delay_s, trace_s, box):
    """A profiler slice of `trace_s` in the middle of the window. start and
    stop run on a thread: stop collects the trace, and on the loop that
    would eat into every node's rpc time-out."""
    loop = asyncio.get_running_loop()

    def dispatches():
        return sum(s.resolver.dispatches for s in servers.values())

    await asyncio.sleep(delay_s)
    await loop.run_in_executor(None, common.start_trace)
    d0, t0 = dispatches(), time.perf_counter()
    with common.window_span():
        await asyncio.sleep(trace_s)
    box.update(traced_dispatches=dispatches() - d0,
               slice_s=time.perf_counter() - t0)
    await loop.run_in_executor(None, common.stop_trace)


async def serve_and_drive(p, seed, seconds, trace, meter, dump_trace):
    import jax
    from accord_tpu.serve.server import NodeServer, ServeConfig

    platform = jax.devices()[0].platform

    addrs = {i + 1: ("127.0.0.1", port)
             for i, port in enumerate(free_ports(p["nodes"]))}
    nodes = sorted(addrs)
    logs = {nid: [] for nid in addrs}
    servers = {nid: NodeServer(ServeConfig(nid, addrs[nid], addrs),
                               log=logs[nid].append) for nid in addrs}
    runs = {nid: asyncio.ensure_future(s.run()) for nid, s in servers.items()}
    client = LoadClient(addrs)
    box = {}

    async def stats():
        replies = [await client.admin(nid, "stats") for nid in nodes]
        return [r["snapshot"] for r in replies]

    async def drive(duration_s):
        if "rate" in p:
            await gen.open_loop(p["rate"], nodes, duration_s)
        else:
            await gen.closed_loop(p["clients"], nodes, duration_s)

    try:
        # run() warms the resolver tiers before it binds; a node whose run()
        # died shows here instead of as a refused connection
        while not all(any(line.startswith("serving node") for line in log)
                      for log in logs.values()):
            for nid, task in runs.items():
                if task.done():
                    task.result()
                    raise RuntimeError(f"node {nid} stopped before serving")
            await asyncio.sleep(0.05)
        await client.connect()
        gen = LoadGen(client, seed=seed, key_count=p["key_count"],
                      write_ratio=p["write_ratio"],
                      max_keys_per_txn=p["max_keys_per_txn"],
                      key_stride=p["key_stride"], key_dist=p["key_dist"],
                      theta=p.get("theta", 0.99))
        await drive(p["warmup_s"])  # the cell's own traffic, untimed

        stall, freezes = [0.0], []
        rpc_s = servers[nodes[0]].cfg.rpc_timeout_ms / 1e3
        watch = asyncio.ensure_future(loop_stall_watch(
            stall, freezes, 0.8 * rpc_s, lambda: sum(
                s.resolver.dispatch_s + s.resolver.readback_s
                + s.resolver.bound_readback_s for s in servers.values())))
        before = common.summed(await stats())
        compiles_open = meter.requests
        slicer = None
        if trace:
            trace_s = min(p.get("trace_s", 5.0), seconds / 2)
            slicer = asyncio.ensure_future(traced_slice(
                servers, (seconds - trace_s) / 2, trace_s, box))
        window_opened_at = time.perf_counter()
        c0, w0 = time.process_time(), gen.now_us()
        await drive(seconds)
        # the window is `seconds` long; what was in flight at its end has
        # been awaited and counts by where its start and end fall
        cpu_s, w1 = time.process_time() - c0, w0 + int(seconds * 1e6)
        watch.cancel()
        compiles_in_window = meter.requests - compiles_open
        if slicer is not None:
            await slicer
        snaps = await stats()
        after = common.summed(snaps)
        await asyncio.sleep(1.0)  # let trailing applies land on every node
        lists = {nid: (await client.admin(nid, "keylists"))["lists"]
                 for nid in nodes}
        shutdowns = {nid: await client.admin(nid, "shutdown") for nid in nodes}
        # every run() must return now although this client still holds its
        # connections open
        await asyncio.wait_for(asyncio.gather(*runs.values()), timeout=30.0)
    finally:
        await client.close()
        for task in runs.values():
            task.cancel()
        await asyncio.gather(*runs.values(), return_exceptions=True)

    traced = None
    if trace:
        traced = common.reduce_trace(box["slice_s"], dump_to=dump_trace)

    faults = []
    for nid, reply in shutdowns.items():
        if reply is None or reply["t"] != "shutdown_ok" or not reply["drained"]:
            faults.append(f"node {nid} shutdown: {reply}")
    bad = [f"n{nid}: {line[:300]}" for nid, log in logs.items()
           for line in log if line.startswith(BAD_LOG)]
    if bad:
        faults.append(f"{len(bad)} server error line(s), first: {bad[0]}")
    # the replicas' lists are prefixes of one another; `merged` is the longest
    merged = {}
    for nid, per_node in lists.items():
        for k, v in per_node.items():
            cur = merged.setdefault(k, v)
            short, long_ = (cur, v) if len(cur) <= len(v) else (v, cur)
            if tuple(long_[:len(short)]) != tuple(short):
                faults.append(f"final lists diverged on key {k}")
            merged[k] = long_
    # every acknowledged append is in the final list of EVERY replica
    acked = [(k, v) for e in gen.entries if e["outcome"] == "ok"
             for k, v in e["writes"].items()]
    held = {nid: {k: set(v) for k, v in per_node.items()}
            for nid, per_node in lists.items()}
    missing = [(nid, k, v) for nid in nodes for k, v in acked
               if v not in held[nid].get(k, ())]
    if missing:
        faults.append(f"{len(missing)} acknowledged appends missing from a "
                      f"replica, first (node, key, value): {missing[0]}")
    try:
        verify_history(gen.issues, gen.entries, final_lists=merged)
    except Exception as e:  # noqa: BLE001 -- reported: the run is incorrect
        faults.append(f"history: {type(e).__name__}: {e}"[:600])
    platforms = {snap["serve.device_platform"] for snap in snaps}
    if platforms != {platform}:
        faults.append(f"nodes serve from {platforms}, JAX found {platform}")
    faults += common.counter_faults(after)

    in_window = [e for e in gen.entries if w0 <= e["start_us"] < w1]
    outcomes = {o: sum(e["outcome"] == o for e in in_window)
                for o in ("ok", "busy", "error", "lost")}
    committed = sum(e["outcome"] == "ok" and w0 <= e["end_us"] < w1
                    for e in gen.entries)
    lat_ms = [(e["end_us"] - e["start_us"]) / 1e3 for e in in_window
              if e["outcome"] == "ok"]
    if not committed:
        faults.append("no txn committed inside the window")
    counters = common.delta(after, before)
    counters.update(window_s=seconds, cpu_s=cpu_s, committed=committed,
                    attempted=len(in_window),
                    compile_requests_in_window=compiles_in_window,
                    loop_max_stall_ms=stall[0],
                    **common.traced_counters(
                        traced, box.get("traced_dispatches")))
    failed = [e for e in in_window if e["outcome"] in ("error", "lost")]
    return {
        "correct": not faults, "attempted": len(in_window),
        "failed": len(in_window) - outcomes["ok"],
        "values": {"committed_tps": committed / seconds,
                   "commit_p50_ms": common.percentile_exact(lat_ms, 50),
                   "commit_p95_ms": common.percentile_exact(lat_ms, 95)},
        "counters": counters, "traced": traced,
        "window_opened_at": window_opened_at,
        "notes": {
            "faults": faults, "outcomes": outcomes,
            "issued_in_all": len(gen.entries), "latency_samples": len(lat_ms),
            "commit_max_ms": max(lat_ms, default=0.0),
            "keys_written": len(merged),
            "loop_freezes": [
                {"ms": round((b - a) * 1e3),
                 "in_device_calls_ms": round(d * 1e3)} for a, b, d in freezes],
            "first_failed": [(e["outcome"], e.get("error", ""),
                              e["end_us"] - e["start_us"])
                             for e in failed[:8]],
            "generator_late_ms_max": max(gen.late_us, default=0) / 1e3,
            "warmup": [line for log in logs.values() for line in log
                       if line.startswith("warmup done")],
        },
    }


def run(p, seed, seconds, trace, meter, dump_trace=None):
    return asyncio.run(
        serve_and_drive(p, seed, seconds, trace, meter, dump_trace))
