#!/usr/bin/env python3
"""chip_smoke.py: prove the device path starts, runs and answers right on
the accelerator JAX finds.

    python3 chip_smoke.py              # what the driver runs; needs a TPU
    python3 chip_smoke.py --rehearsal  # tiny sizes on whatever JAX finds,
                                       # every line marked "rehearsal": true

ONE process; it is the only one that initialises JAX, and it starts no child.
It never selects a platform: the first line says what `jax.devices()` found
and anything but `tpu` ends the run there, non-zero, before any work.

Five legs drive the system through the classes its entry points use, at the
sizes BASELINE.json fixes, each checked by the repo's own reference:

  store    one CommandStore behind a BatchDepsResolver, 10,000 in-flight
           4-key writes over 1,000 keys, 4,096 deps queries through the async
           pipeline, EVERY answer compared with the host scan
  cluster  the contended rw-register analog (sim.burn.run_burn: 5 nodes,
           rf 3, Zipf 0.99 over 16 hot keys, ~1,024 concurrent, 800 ops,
           durability on) under the strict-serializability verifier
  served   three NodeServers at their defaults on one asyncio loop and
           loopback sockets, driven by serve.loadgen until 500 txns are
           acknowledged; history verified against the final key lists.
           Every issued txn must be acknowledged; the one exception, said
           in the line, is a txn caught in flight by a freeze of the whole
           process as long as the rpc time-out that was not a device call
  fused    sim.mesh_burn.run_mesh_burn with every device plane folded into
           protocol_tick, its committed history compared with the unfused
           node-lane run of the same seed; then the same workload on the
           standalone frontier kernels
  sharded  the fused run as one shard_map program per tick over
           parallel.mesh.make_mesh(), compared with the single-device fused
           history (four or more devices; otherwise reported as skipped)

Each leg prints one JSON line when it ends: wall seconds, programs compiled
(jit-cache growth), XLA compile requests / persistent-cache hits / compile
seconds from jax.monitoring, and three groups of counters -- `gated`
(integrity and hidden-fallback counters, must all be zero), `work`
(device-work counters, must all be positive, so that "no fallback" cannot
mean "no device work") and `capacity` (printed, not gated). A failed leg is
reported and the run goes on, but the exit code is non-zero and the result
says `"ok": false`. The last two lines of stdout are the summary (legs,
program families, compile totals) and the result, `{"ok": ..., "device":
{"platform", "kind", "count"}}` as JAX reports the device.
"""
from __future__ import annotations

import argparse
import asyncio
import importlib.util
import json
import socket
import sys
import time
import traceback

# counters that mean "the device answer was not used" or "a device plane
# lost integrity": zero in a fault-free run. The resolver's apply to every
# leg; the planes' to the legs that switch those planes on
GATED_RESOLVER = (
    "resolver.checksum_mismatches", "resolver.degraded_dispatches",
    "resolver.quarantine_entries", "resolver.device_watchdog_trips",
    "resolver.finalize_fallbacks", "resolver.cmd_span_replays",
)
GATED_PLANES = (
    "cmd_plane_checksum_mismatches", "recovery_scan_fallbacks",
    "exec.compact_fallbacks", "exec_coord.compact_fallbacks",
    "exec.dropped_frontiers", "mailbox_verify_fallbacks",
    "mesh_tick_fallbacks", "sharded_megakernel_fallbacks",
)
# capacity counters: a tier or a ring was too small and the host covered;
# printed where they moved, not gated
CAPACITY = (
    "resolver.host_fallbacks", "resolver.range_fallbacks",
    "resolver.legacy_decodes", "resolver.outcap_tier_switches",
    "cmd_plane_fallbacks", "exec.compact_overflows",
    "exec_coord.compact_overflows", "recovery_scan_overflows",
    "mailbox_overflow_spills",
)
RESOLVER_WORK = ("resolver.dispatches", "resolver.finalized_decodes")
# the families kernels.jit_cache_sizes() tracks that need one device only
SINGLE_DEVICE_FAMILIES = (
    "deps_resolve", "range_deps_resolve", "fused_deps_resolve",
    "fused_range_deps_resolve", "arena_scatter", "arena_scatter_keys",
    "scatter_rows", "range_scatter", "finalize_csr", "range_finalize_csr",
    "kid_word_scatter", "fused_execution_frontier", "frontier_compact",
    "recovery_scan", "cmd_tick", "protocol_tick",
    "node_fused_deps_resolve", "node_fused_range_deps_resolve", "lane_slice",
)

# leg sizes: BASELINE.json's, and a tiny set for --rehearsal
FULL = dict(
    store=dict(active=10_000, keys=1_000, subjects=4_096, buckets=1_024,
               cap=16_384, max_dispatch=1_024),
    cluster=dict(ops=800, concurrency=1_024),
    served=dict(target_ok=500, rate=25.0, leg_s=5.0, deadline_s=240.0),
    fused=dict(ops=24),
)
REHEARSAL = dict(
    store=dict(active=600, keys=100, subjects=96, buckets=128,
               cap=1_024, max_dispatch=64),
    cluster=dict(ops=60, concurrency=32),
    served=dict(target_ok=30, rate=10.0, leg_s=2.0, deadline_s=120.0),
    fused=dict(ops=8),
)


class CompileMeter:
    """XLA compile activity as jax.monitoring reports it: every backend
    compile request, the time spent in them, and how many were answered
    from the persistent cache instead of compiled from source."""

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
        return self.requests, self.seconds, self.cache_hits


def check_counters(counters, gated, work):
    """Split a leg's flat counter dict into the three reported groups and
    fail on a non-zero gated counter or an idle device-work counter (a
    registry cell nothing ever touched reads as 0)."""
    gated = {n: counters.get(n, 0) for n in gated}
    bad = {k: v for k, v in gated.items() if v != 0}
    assert not bad, f"hidden-fallback / integrity counters not zero: {bad}"
    worked = {n: counters.get(n, 0) for n in work}
    idle = [k for k, v in worked.items() if not v > 0]
    assert not idle, f"no device work counted in {idle}: {worked}"
    return {"gated": gated, "work": worked,
            "capacity": {n: counters[n] for n in CAPACITY if n in counters}}


def merged_resolver_counters(resolvers):
    from accord_tpu.obs.metrics import MetricsRegistry
    agg = MetricsRegistry()
    for r in resolvers:
        agg.merge_from(r.metrics)
    return {k: v for k, v in agg.snapshot().items()
            if isinstance(v, (int, float))}


# -- store -------------------------------------------------------------------

def leg_store(size):
    from accord_tpu.local.cfk import CfkStatus
    from accord_tpu.ops.resolver import BatchDepsResolver
    from accord_tpu.primitives.keyspace import Keys
    from accord_tpu.primitives.timestamp import Domain, TxnId, TxnKind
    from accord_tpu.sim.cluster import Cluster, ClusterConfig
    from accord_tpu.utils.rng import RandomSource

    resolver = BatchDepsResolver(num_buckets=size["buckets"],
                                 initial_cap=size["cap"],
                                 max_dispatch=size["max_dispatch"])
    cluster = Cluster(3, ClusterConfig(
        num_nodes=1, rf=1, stores_per_node=1, num_shards=1, progress=False,
        deps_resolver_factory=lambda: resolver, deps_batch_window_ms=None))
    node = cluster.nodes[1]
    store = node.command_stores.all()[0]
    rng = RandomSource(17)

    def fresh():
        ts = node.unique_now()
        txn_id = TxnId.create(ts.epoch, ts.hlc, ts.node, TxnKind.WRITE,
                              Domain.KEY)
        return txn_id, Keys(rng.next_int(size["keys"]) for _ in range(4)), ts

    for _ in range(size["active"]):
        txn_id, keys, ts = fresh()
        store.register(txn_id, keys, CfkStatus.WITNESSED, ts)
    subjects = [(t, store.owned(k), ts)
                for t, k, ts in (fresh() for _ in range(size["subjects"]))]
    expected = [store.host_calculate_deps(t, k, b) for t, k, b in subjects]

    # the async pipeline as the protocol consumes it: enqueue, batched
    # ticks, finalize on device, harvest events on the sim queue
    store.batch_window_ms = 2.0
    answers = [None] * len(subjects)
    failures = []
    for i, (t, k, b) in enumerate(subjects):
        def done(value, failure, i=i):
            if failure is not None:
                failures.append(failure)
            answers[i] = value
        resolver.enqueue_deps(store, t, k, b).add_callback(done)
    cluster.queue.drain(max_events=1_000_000)
    assert not failures, f"{len(failures)} resolutions failed: {failures[0]!r}"
    wrong = [i for i, (a, e) in enumerate(zip(answers, expected)) if a != e]
    assert not wrong, (
        f"{len(wrong)}/{len(subjects)} device answers differ from the host "
        f"scan; first at subject {wrong[0]}: {answers[wrong[0]]!r} != "
        f"{expected[wrong[0]]!r}")
    deps_total = sum(len(e.key_deps.all_txn_ids()) for e in expected)
    assert deps_total > 0, "the host scan found no dependency at all"
    counters = merged_resolver_counters([resolver])
    out = check_counters(counters, GATED_RESOLVER, RESOLVER_WORK)
    out.update(in_flight=size["active"], keys=size["keys"],
               subjects=len(subjects), equal_to_host_scan=len(subjects),
               deps_checked=deps_total, arena_cap=size["cap"],
               buckets=size["buckets"], device_id=resolver.device.id)
    return out


# -- cluster -----------------------------------------------------------------

def leg_cluster(size):
    from accord_tpu.ops.resolver import BatchDepsResolver
    from accord_tpu.sim.burn import run_burn
    from accord_tpu.sim.cluster import ClusterConfig

    resolvers = []

    def factory():
        r = BatchDepsResolver(num_buckets=1024, initial_cap=2048,
                              max_dispatch=256)
        resolvers.append(r)
        return r

    cfg = ClusterConfig(
        num_nodes=5, rf=3, stores_per_node=2,
        deps_resolver_factory=factory,
        deps_batch_window_ms=16.0, device_latency_ms=80.0,
        durability=True, durability_interval_ms=1000.0,
        timeout_ms=8000.0, preaccept_timeout_ms=8000.0,
        progress_stall_ms=5000.0)
    # run_burn witnesses every ack in the strict-serializability verifier,
    # then checks no node failed, replicas converged and the final state
    # extends every observed order -- and raises if any of it does not hold
    report = run_burn(9, ops=size["ops"], key_count=16, zipf_theta=0.99,
                      max_keys_per_txn=4, concurrency=size["concurrency"],
                      write_ratio=0.7, config=cfg)
    assert (report.acked, report.failed, report.lost) == (size["ops"], 0, 0), \
        f"acked/failed/lost = {report.acked}/{report.failed}/{report.lost}"
    counters = merged_resolver_counters(resolvers)
    out = check_counters(counters, GATED_RESOLVER, RESOLVER_WORK)
    out.update(acked=report.acked, failed=report.failed, lost=report.lost,
               sim_events=report.events, nodes=5, rf=3, stores_per_node=2,
               device_ids=sorted({r.device.id for r in resolvers}))
    return out


# -- served ------------------------------------------------------------------

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
    (ms). The three nodes and the load client share the loop, so whatever
    blocks it eats into every node's rpc time-out at once. A stall of
    `freeze_s` or more is kept in `freezes` as (from, to, seconds of it
    spent inside the resolvers' device calls): a compile or a readback that
    blocks is the device path's doing, a process the host did not run is
    not, and `device_call_s()` (the resolvers' own timers) tells them
    apart."""
    while True:
        t0, d0 = time.monotonic(), device_call_s()
        await asyncio.sleep(0.01)
        t1 = time.monotonic()
        worst[0] = max(worst[0], (t1 - t0 - 0.01) * 1e3)
        if t1 - t0 >= freeze_s:
            freezes.append((t0, t1, device_call_s() - d0))


async def serve_and_drive(size, platform, meter):
    from accord_tpu.serve.loadgen import LoadClient, LoadGen, verify_history
    from accord_tpu.serve.server import NodeServer, ServeConfig

    addrs = {i + 1: ("127.0.0.1", p) for i, p in enumerate(free_ports(3))}
    logs = {nid: [] for nid in addrs}
    servers = {nid: NodeServer(ServeConfig(nid, addrs[nid], addrs),
                               log=logs[nid].append) for nid in addrs}
    runs = {nid: asyncio.ensure_future(s.run()) for nid, s in servers.items()}
    client = LoadClient(addrs)
    try:
        # run() warms the resolver tiers before it binds; a node whose
        # run() died shows here instead of as a refused connection
        while not all(any(line.startswith("serving node") for line in log)
                      for log in logs.values()):
            for nid, task in runs.items():
                if task.done():
                    task.result()
                    raise AssertionError(f"node {nid} stopped before serving")
            await asyncio.sleep(0.05)
        await client.connect()
        compiles_at_serving, compile_s_at_serving, _ = meter.read()
        stall, freezes = [0.0], []
        rpc_s = servers[1].cfg.rpc_timeout_ms / 1e3
        watch = asyncio.ensure_future(loop_stall_watch(
            stall, freezes, 0.8 * rpc_s, lambda: sum(
                r.dispatch_s + r.readback_s + r.bound_readback_s
                for r in (s.resolver for s in servers.values()))))
        gen = LoadGen(client, seed=31, key_count=1_000, write_ratio=0.5,
                      max_keys_per_txn=4)
        gen_t0 = time.monotonic()  # the origin of the entries' start_us
        total = dict(issued=0, ok=0, busy=0, errors=0, lost=0)  # txns
        deadline = time.monotonic() + size["deadline_s"]
        latencies = []
        while total["ok"] < size["target_ok"]:
            assert time.monotonic() < deadline, \
                f"{total} after {size['deadline_s']}s"
            leg = await gen.run_leg(size["rate"], size["leg_s"])
            for k in total:
                total[k] += leg[k]
            latencies.append(leg["p50_us"])
        watch.cancel()
        compiles_now, compile_s_now, _ = meter.read()
        under_load = {"compile_requests": compiles_now - compiles_at_serving,
                      "compile_s": round(compile_s_now - compile_s_at_serving,
                                         2),
                      "max_loop_stall_ms": round(stall[0], 1)}
        await asyncio.sleep(1.0)  # let trailing applies land on every node
        lists, stats = {}, {}
        for nid in addrs:
            lists[nid] = (await client.admin(nid, "keylists"))["lists"]
            stats[nid] = await client.admin(nid, "stats")
        for nid in addrs:
            reply = await client.admin(nid, "shutdown")
            assert reply is not None and reply["t"] == "shutdown_ok" \
                and reply["drained"], f"node {nid} shutdown: {reply}"
        # every run() must return now although this client still holds its
        # connections open
        await asyncio.wait_for(asyncio.gather(*runs.values()), timeout=30.0)
    finally:
        await client.close()
        for task in runs.values():
            task.cancel()
        await asyncio.gather(*runs.values(), return_exceptions=True)

    # a txn in flight while the whole process stood still for about the rpc
    # time-out fails by that time-out whatever the system does: all three
    # replicas share the frozen loop, none can answer and every timer is due
    # on wake-up. Where the resolvers' timers show the freeze was NOT spent
    # in a device call, such an error is the host's: reported, not failed --
    # for ONE freeze. A freeze inside a device call, a second freeze, or any
    # error outside one fails the leg. The time-out is never lengthened.
    under_load["loop_freezes"] = [
        {"ms": round((b - a) * 1e3), "in_device_calls_ms": round(d * 1e3)}
        for a, b, d in freezes]
    host_froze = [((a - gen_t0) * 1e6, (b - gen_t0) * 1e6)
                  for a, b, d in freezes if d < 0.5 * (b - a)]
    failed = [e for e in gen.entries if e["outcome"] in ("error", "lost")]
    in_freeze = [e for e in failed if e["outcome"] == "error" and any(
        e["start_us"] <= b and a <= e["end_us"] for a, b in host_froze)]
    total["errors_in_host_freeze"] = len(in_freeze)
    said = [f"n{nid}: {line[:300]}" for nid, log in logs.items()
            for line in log
            if not line.startswith(("metrics", "warmup done", "serving node"))]
    shown = [(e["outcome"], e.get("error", ""), e["end_us"] - e["start_us"])
             for e in failed[:8]]
    assert total["lost"] == 0 and total["errors"] == len(in_freeze) \
        and len(host_froze) == len(freezes) <= 1, (
        f"{total}; under load {under_load}; (outcome, text, us): {shown}; "
        f"server logs: {said[:8]}")
    assert total["issued"] == (total["ok"] + total["busy"]
                               + total["errors"]), total
    bad = [f"n{nid}: {line}" for nid, log in logs.items() for line in log
           if line.startswith(("error handling", "uncaught",
                               "inconsistent timestamp", "frame error"))]
    assert not bad, f"{len(bad)} server error line(s), first: {bad[0]}"
    merged = {}
    for per_node in lists.values():
        for k, v in per_node.items():
            cur = merged.setdefault(k, v)
            short, long_ = (cur, v) if len(cur) <= len(v) else (v, cur)
            assert tuple(long_[:len(short)]) == tuple(short), \
                f"final lists diverged on key {k}: {cur} vs {v}"
            merged[k] = long_
    verify_history(gen.issues, gen.entries, final_lists=merged)
    counters = {}
    for nid, st in stats.items():
        snap = st["snapshot"]
        assert snap["serve.device_platform"] == platform, \
            f"node {nid} serves from {snap['serve.device_platform']}"
        for k, v in snap.items():
            if isinstance(v, (int, float)):
                counters[k] = counters.get(k, 0) + v
    out = check_counters(counters, GATED_RESOLVER, RESOLVER_WORK)
    out.update(txns=total, keys_written=len(merged), under_load=under_load,
               client_p50_us_by_leg=latencies,
               node_devices={nid: "%s (%s)" % (
                   st["snapshot"]["serve.device_platform"],
                   st["snapshot"]["serve.device_kind"])
                   for nid, st in stats.items()},
               warmup=[line for log in logs.values() for line in log
                       if line.startswith("warmup done")],
               runs_returned=len(runs))
    return out


# -- fused / sharded ---------------------------------------------------------

PLANES = dict(nodes=5, rf=3, cmd_plane=True, exec_plane=True,
              exec_compact=True, recovery_scan="device", collect_log=True,
              range_read_ratio=0.2, range_write_ratio=0.1)
MEGA = dict(megakernel=True, exec_in_megakernel=True, device_messages=True)
PLANE_WORK = RESOLVER_WORK + ("cmd_plane_dispatches",
                              "recovery_scan_dispatches")
FUSED_WORK = PLANE_WORK + ("megakernel_dispatches",
                           "exec_coord.staged_blocks",
                           "device_messages_delivered")


def mesh_burn(ops, sharded=False, mesh_tick=True, **flags):
    """One run_mesh_burn at the smoke's seed; returns (report, counters)
    with the adopted resolvers' registries folded in beside the report's
    plane, mailbox and engine counters."""
    from accord_tpu.sim.mesh_burn import ClusterTickEngine, run_mesh_burn

    resolvers = []

    class Engine(ClusterTickEngine):
        def adopt(self, resolver):
            resolvers.append(resolver)
            return super().adopt(resolver)

    engine = Engine(mesh_tick=mesh_tick,
                    **{k: flags.get(k, False) for k in MEGA})
    report, _ = run_mesh_burn(5, ops, engine=engine, sharded=sharded,
                              **{**PLANES, **flags})
    assert (report.acked + report.failed, report.lost) == (ops, 0) \
        and report.acked > 0, (
        f"acked/failed/lost = {report.acked}/{report.failed}/{report.lost}")
    return report, {**merged_resolver_counters(resolvers),
                    **report.counters}


def leg_fused(size, keep):
    """Every plane inside protocol_tick, against the runs that launch the
    same plans through the standalone kernels: the merged node-lane
    dispatch, and the per-node loop with the bitmask frontier (what a
    compact harvest degrades to). The repo's differential contract is that
    all three commit the same history."""
    ops = size["ops"]
    fused, counters = mesh_burn(ops, **MEGA)
    keep["fused_log"] = fused.log
    out = check_counters(counters, GATED_RESOLVER + GATED_PLANES, FUSED_WORK)
    launches = counters["launches_per_tick"]
    assert launches == 1.0, f"launches_per_tick = {launches}"
    for name, flags, work in (
            ("node_lane", {}, PLANE_WORK + ("node_lane_dispatches",
                                            "exec_coord.dispatches")),
            ("node_loop", dict(mesh_tick=False, exec_compact=False),
             PLANE_WORK + ("exec_coord.dispatches",))):
        other, counters = mesh_burn(ops, **flags)
        assert fused.log == other.log, (
            f"fused history ({len(fused.log)} entries) differs from the "
            f"{name} run ({len(other.log)} entries)")
        out[name] = check_counters(
            counters, GATED_RESOLVER + GATED_PLANES, work)
    out.update(acked=fused.acked, failed=fused.failed, lost=fused.lost,
               history_entries=len(fused.log),
               equal_to=["node_lane", "node_loop"],
               launches_per_tick=launches)
    return out


def leg_sharded(size, keep):
    import jax
    if len(jax.devices()) < 4:
        return {"skipped": "%d device" % len(jax.devices())}
    from accord_tpu.ops.kernels import jit_cache_sizes
    from accord_tpu.parallel.mesh import make_mesh
    assert "fused_log" in keep, "the fused leg left no history to compare"
    sharded, counters = mesh_burn(size["ops"], sharded=True, **MEGA)
    assert sharded.log == keep["fused_log"], (
        f"sharded history ({len(sharded.log)} entries) differs from the "
        f"single-device fused run ({len(keep['fused_log'])} entries)")
    out = check_counters(counters, GATED_RESOLVER + GATED_PLANES, FUSED_WORK)
    programs = jit_cache_sizes()["sharded_protocol_tick"]
    assert programs > 0, "no sharded_protocol_tick program was compiled"
    out.update(acked=sharded.acked, failed=sharded.failed, lost=sharded.lost,
               history_entries=len(sharded.log), equal_to=["fused"],
               mesh=dict(make_mesh().shape),
               sharded_protocol_tick_programs=programs)
    return out


# -- driver ------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearsal", action="store_true",
                    help="tiny sizes on whatever platform JAX finds; every "
                         "line says \"rehearsal\": true")
    args = ap.parse_args(argv)
    mark = {"rehearsal": True} if args.rehearsal else {}

    if importlib.util.find_spec("accord_tpu") is None:
        print("chip_smoke: the accord_tpu package is not beside this script; "
              "nothing was run", file=sys.stderr)
        return 1
    import jax
    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    print(json.dumps({
        **device, "jax": jax.__version__,
        "placement": "store, cluster, served and fused run on device 0 "
                     "only; sharded spans all %d" % len(devices),
        **mark}), flush=True)
    if device["platform"] != "tpu" and not args.rehearsal:
        print("chip_smoke: JAX found platform %r, not a TPU; nothing was run"
              % device["platform"], file=sys.stderr)
        return 2

    from accord_tpu.ops.kernels import jit_cache_sizes
    from accord_tpu.utils.compile_cache import place_compile_cache
    cache_dir = place_compile_cache()
    meter = CompileMeter()
    size = REHEARSAL if args.rehearsal else FULL
    keep = {}
    legs = (
        ("store", lambda: leg_store(size["store"])),
        ("cluster", lambda: leg_cluster(size["cluster"])),
        ("served", lambda: asyncio.run(
            serve_and_drive(size["served"], device["platform"], meter))),
        ("fused", lambda: leg_fused(size["fused"], keep)),
        ("sharded", lambda: leg_sharded(size["fused"], keep)),
    )
    t_start = time.perf_counter()
    summary = {}
    for name, run in legs:
        t0 = time.perf_counter()
        p0 = sum(jit_cache_sizes().values())
        r0, s0, h0 = meter.read()
        try:
            line = run()
            if "skipped" not in line:
                line = {"ok": True, **line}
        except Exception as e:  # noqa: BLE001 -- reported, and fails the run
            traceback.print_exc()
            line = {"ok": False, "error": f"{type(e).__name__}: {e}"[:2000]}
        r1, s1, h1 = meter.read()
        line = {"leg": name, **line,
                "wall_s": round(time.perf_counter() - t0, 2),
                "programs": sum(jit_cache_sizes().values()) - p0,
                "compile_requests": r1 - r0, "cache_hits": h1 - h0,
                "compiled_from_source": (r1 - r0) - (h1 - h0),
                "compile_s": round(s1 - s0, 2), **mark}
        print(json.dumps(line), flush=True)
        summary[name] = ("skipped" if "skipped" in line
                         else "ok" if line["ok"] else "FAILED")
    families = jit_cache_sizes()
    uncovered = [f for f in SINGLE_DEVICE_FAMILIES if not families[f]]
    if uncovered:
        summary["coverage"] = "FAILED"
        print("chip_smoke: kernel families never compiled: %s" % uncovered,
              file=sys.stderr)
    r, s, h = meter.read()
    ok = all(v != "FAILED" for v in summary.values())
    print(json.dumps({
        "summary": summary,
        "families_compiled": families, "families_uncovered": uncovered,
        "wall_s": round(time.perf_counter() - t_start, 2),
        "programs": sum(families.values()),
        "compile_requests": r, "cache_hits": h,
        "compiled_from_source": r - h, "compile_s": round(s, 2),
        "compile_cache": cache_dir, **mark}), flush=True)
    print(json.dumps({"ok": ok, "device": device, **mark}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
