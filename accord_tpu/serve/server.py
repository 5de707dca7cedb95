"""NodeServer: one accord node as a real OS process on an asyncio loop.

Role-equivalent to what the reference only sketches via maelstrom's
stdin/stdout executable (accord-maelstrom Main.java:60), grown into an
actual serving surface: the node listens on a TCP port, peers and clients
speak the same length-prefixed `serve/transport.py` codec (payloads ride
`sim/wire.py`, so sim and serve share one serialization), and everything --
protocol ingress, accord timers, the device resolver tick, admission
control, metrics dumps -- runs single-threaded on the event loop, which
keeps `local/node.py` exactly as re-entrancy-free as it is under the sim
scheduler.

Envelope vocabulary (plain dicts through the wire codec):

  inter-node   {"t": "accord", "mid", "from", "payload": <Request>}
               {"t": "accord_reply", "mid", "from", "payload": <Reply>}
  client       {"t": "txn", "msg_id", "ops": [["r",k,None]|["append",k,v]]}
           ->  {"t": "txn_ok"|"busy"|"error", "msg_id", ...}
  admin        ping/pong, stats/stats_ok (registry snapshot + jit cache
               sizes), keylists/keylists_ok (the node's list-store state,
               for convergence + final-state checks), shutdown/shutdown_ok

The txn surface is maelstrom's list-append micro-op format, translated the
same way (`maelstrom/core.py` owns the Txn build); replies echo the ops
with reads filled in, which is exactly the shape `sim/verifier.py`
consumes. Client txns pass the `serve/admission.py` governor first: BUSY
is an explicit reply, and sustained shedding widens the device resolver's
staged window (`note_admission_pressure`) so admitted work rides bigger
batches while the overload lasts.
"""
from __future__ import annotations

import argparse
import asyncio
import itertools
import json
import sys
import time
from typing import Dict, List, Optional, Tuple

from accord_tpu import api
from accord_tpu.local.node import Node
from accord_tpu.maelstrom.core import (KEY_DOMAIN, LoopScheduler,
                                       MultiAppendUpdate, WallClock,
                                       _StaticConfigService, _StderrAgent,
                                       build_topology)
from accord_tpu.messages.base import Timeout
from accord_tpu.obs.metrics import MetricsRegistry
from accord_tpu.primitives.keyspace import Keys
from accord_tpu.primitives.timestamp import TxnKind
from accord_tpu.primitives.txn import Txn
from accord_tpu.serve import transport
from accord_tpu.serve.admission import AdmissionController
from accord_tpu.sim.list_store import ListQuery, ListRead, ListStore
from accord_tpu.utils.collector import settled_collector
from accord_tpu.utils.rng import RandomSource


class ServeConfig:
    """Everything one node process needs to join the cluster."""

    def __init__(self, node_id: int, listen: Tuple[str, int],
                 peers: Dict[int, Tuple[str, int]],
                 num_stores: int = 1,
                 batch_window_ms: float = 1.0,
                 device_latency_ms: float = 1.0,
                 rpc_timeout_ms: float = 3000.0,
                 device_deps: bool = True,
                 admission_rate: float = 500.0,
                 admission_burst: int = 64,
                 max_inflight: int = 256,
                 metrics_interval_s: float = 10.0,
                 drain_timeout_s: float = 10.0,
                 warmup: bool = True,
                 bind_host: Optional[str] = None):
        self.node_id = node_id
        self.listen = listen
        # the socket binds bind_host when set (e.g. "0.0.0.0" so peers on
        # other hosts can reach us); `listen` stays the ADVERTISED address
        # peers dial. None = bind the advertised host (loopback in CI).
        self.bind_host = bind_host
        self.peers = dict(peers)  # includes self or not; self is ignored
        self.num_stores = num_stores
        self.batch_window_ms = batch_window_ms
        self.device_latency_ms = device_latency_ms
        self.rpc_timeout_ms = rpc_timeout_ms
        self.device_deps = device_deps
        self.admission_rate = admission_rate
        self.admission_burst = admission_burst
        self.max_inflight = max_inflight
        self.metrics_interval_s = metrics_interval_s
        self.drain_timeout_s = drain_timeout_s
        self.warmup = warmup


class _SocketSink(api.MessageSink):
    """Accord messages over transport frames: send_with_callback demuxes
    replies by mid with a scheduler-armed timeout (the maelstrom transport's
    shape, on sockets). Self-sends still round-trip the wire codec so a
    node never shares live objects with itself either."""

    def __init__(self, server: "NodeServer"):
        self.server = server
        self._mids = itertools.count(1)
        self._pending: Dict[int, Tuple[object, object]] = {}

    def send(self, to: int, request) -> None:
        self._send(to, request, None)

    def send_with_callback(self, to: int, request, callback) -> None:
        self._send(to, request, callback)

    def _send(self, to: int, request, callback) -> None:
        mid = next(self._mids)
        if callback is not None:
            handle = self.server.scheduler.once(
                self.server.cfg.rpc_timeout_ms,
                lambda: self._on_timeout(mid, to))
            self._pending[mid] = (callback, handle)
        env = {"t": "accord", "mid": mid, "from": self.server.cfg.node_id,
               "payload": request}
        if to == self.server.cfg.node_id:
            env = transport.decode_message(transport.encode_message(env))
            self.server.scheduler.once(
                0.0, lambda: self.server.handle_envelope(env, None))
        else:
            self.server.send_to_peer(to, env)

    def reply(self, to: int, reply_context, reply) -> None:
        if reply is None:
            return
        conn, mid = reply_context
        env = {"t": "accord_reply", "mid": mid,
               "from": self.server.cfg.node_id, "payload": reply}
        if conn is None:  # self-send: loop back through the codec
            env = transport.decode_message(transport.encode_message(env))
            self.server.scheduler.once(
                0.0, lambda: self.server.handle_envelope(env, None))
        else:
            self.server.send_on_conn(conn, env)

    def on_reply(self, env: dict) -> None:
        entry = self._pending.pop(env["mid"], None)
        if entry is None:
            return  # reply after timeout: drop
        callback, handle = entry
        handle.cancel()
        callback.on_success(env.get("from", -1), env["payload"])

    def _on_timeout(self, mid: int, to: int) -> None:
        entry = self._pending.pop(mid, None)
        if entry is None:
            return
        callback, _ = entry
        callback.on_failure(to, Timeout(f"no reply from n{to}"))


class _Conn:
    """One live connection (inbound or outbound): a writer plus transport
    byte accounting into the server registry."""

    __slots__ = ("writer", "server", "decoder")

    def __init__(self, server: "NodeServer", writer: asyncio.StreamWriter):
        self.server = server
        self.writer = writer
        self.decoder = transport.FrameDecoder()

    def send(self, env: dict) -> None:
        frame = transport.encode_envelope(env)
        self.server.bytes_out.inc(len(frame))
        try:
            self.writer.write(frame)
        except Exception:
            pass  # connection died; accord timeouts handle the loss


class NodeServer:
    def __init__(self, cfg: ServeConfig, log=None):
        self.cfg = cfg
        self.log = log if log is not None else (
            lambda s: print(s, file=sys.stderr, flush=True))
        self.clock = WallClock()
        self.scheduler = LoopScheduler(self.clock)
        self.metrics = MetricsRegistry()
        self.bytes_in = self.metrics.counter("serve.transport_bytes_in")
        self.bytes_out = self.metrics.counter("serve.transport_bytes_out")
        self.txn_ok = self.metrics.counter("serve.txn_ok")
        self.txn_error = self.metrics.counter("serve.txn_error")
        self.sink = _SocketSink(self)
        self.resolver = None
        # which device answers this node's deps queries, as JAX placed the
        # resolver's arrays (never chosen here): reported in the warm-up
        # line and in every stats snapshot, so a node that JAX quietly put
        # on the CPU cannot pass for one on the chip
        self.device = {"platform": "host", "kind": "host scan"}
        if cfg.device_deps:
            from accord_tpu.ops.resolver import BatchDepsResolver
            # adaptive_window on: the admission governor's pressure hook
            # sheds into this resolver's staged-window scale
            self.resolver = BatchDepsResolver(adaptive_window=True)
            dev = self.resolver.device
            self.device = {"platform": dev.platform, "kind": dev.device_kind}
        peer_ids = sorted(set(cfg.peers) | {cfg.node_id})
        topology = build_topology(peer_ids)
        from accord_tpu.impl.progress import ProgressEngine
        engine = ProgressEngine(interval_ms=500.0, stall_ms=3000.0)
        self.node = Node(
            cfg.node_id,
            message_sink=self.sink,
            config_service=_StaticConfigService(topology),
            scheduler=self.scheduler,
            agent=_StderrAgent(self.log),
            rng=RandomSource(cfg.node_id * 7919 + 17),
            time_service=self.clock,
            data_store=ListStore(),
            num_stores=cfg.num_stores,
            progress_log_factory=engine.log_for,
            deps_resolver=self.resolver,
            deps_batch_window_ms=cfg.batch_window_ms,
            device_latency_ms=cfg.device_latency_ms,
        )
        engine.bind(self.node)
        self.node.metrics_sink = self.log
        self.admission = AdmissionController(
            cfg.admission_rate, cfg.admission_burst, cfg.max_inflight,
            registry=self.metrics, on_pressure=self._on_pressure)
        # outbound peer links: id -> _Conn (None until connected); frames
        # queued while the dial is in flight, one dial task per peer
        self._peer_conns: Dict[int, Optional[_Conn]] = {}
        self._peer_backlog: Dict[int, List[dict]] = {}
        self._peer_dialing: Dict[int, asyncio.Task] = {}
        # accepted connections (peers' dials and clients), closed on stop
        self._inbound: set = set()
        self._kick: Optional[asyncio.Event] = None
        self._server: Optional[asyncio.base_events.Server] = None
        self._stopping: Optional[asyncio.Event] = None
        self._loop: Optional[asyncio.AbstractEventLoop] = None

    # -- admission pressure -> device pipeline -------------------------------
    def _on_pressure(self, overloaded: bool) -> None:
        if self.resolver is not None:
            self.resolver.note_admission_pressure(self.node, overloaded)

    # -- outbound ------------------------------------------------------------
    def send_to_peer(self, to: int, env: dict) -> None:
        conn = self._peer_conns.get(to)
        if conn is not None:
            conn.send(env)
            return
        self._peer_backlog.setdefault(to, []).append(env)
        if to not in self._peer_dialing and self._loop is not None:
            self._peer_dialing[to] = self._loop.create_task(
                self._dial_peer(to))

    def send_on_conn(self, conn: _Conn, env: dict) -> None:
        conn.send(env)

    async def _dial_peer(self, to: int) -> None:
        host, port = self.cfg.peers[to]
        try:
            while not self._stopping.is_set():
                try:
                    reader, writer = await asyncio.open_connection(host, port)
                    break
                except OSError:
                    # peer not up yet (cluster start) or crashed: retry;
                    # accord's rpc timeouts own the failure semantics
                    await asyncio.sleep(0.2)
            else:
                return
            conn = _Conn(self, writer)
            self._peer_conns[to] = conn
            for env in self._peer_backlog.pop(to, []):
                conn.send(env)
            await self._read_loop(reader, conn)
        finally:
            self._peer_dialing.pop(to, None)
            conn = self._peer_conns.get(to)
            if conn is not None:
                conn.writer.close()
                self._peer_conns[to] = None  # reconnect on next send

    # -- inbound -------------------------------------------------------------
    async def _read_loop(self, reader: asyncio.StreamReader,
                         conn: _Conn) -> None:
        while True:
            chunk = await reader.read(1 << 16)
            if not chunk:
                return
            self.bytes_in.inc(len(chunk))
            for payload in conn.decoder.feed(chunk):
                env = transport.decode_message(payload)
                self.handle_envelope(env, conn)
            self._kick.set()

    async def _on_client(self, reader: asyncio.StreamReader,
                         writer: asyncio.StreamWriter) -> None:
        conn = _Conn(self, writer)
        self._inbound.add(conn)
        try:
            await self._read_loop(reader, conn)
        except transport.FrameError as e:
            self.log(f"frame error: {e}")
        finally:
            self._inbound.discard(conn)
            writer.close()

    def handle_envelope(self, env: dict, conn: Optional[_Conn]) -> None:
        kind = env.get("t")
        try:
            if kind == "accord":
                self.node.receive(env["payload"], env["from"],
                                  (conn, env["mid"]))
            elif kind == "accord_reply":
                self.sink.on_reply(env)
            elif kind == "txn":
                self._on_txn(env, conn)
            elif kind == "ping":
                conn.send({"t": "pong", "msg_id": env.get("msg_id"),
                           "node": self.cfg.node_id})
            elif kind == "stats":
                conn.send({"t": "stats_ok", "msg_id": env.get("msg_id"),
                           "snapshot": self.snapshot(),
                           "jit_cache": self._jit_cache()})
            elif kind == "keylists":
                store: ListStore = self.node.data_store
                lists = {k: list(store.snapshot(k)) for k in store.data}
                conn.send({"t": "keylists_ok", "msg_id": env.get("msg_id"),
                           "lists": lists})
            elif kind == "shutdown":
                self._loop.create_task(self._graceful_stop(conn, env))
            else:
                self.log(f"ignoring envelope type {kind!r}")
        except BaseException as e:  # noqa: BLE001 -- a server must not die
            self.log(f"error handling {kind}: {e!r}")
            if kind == "txn" and conn is not None:
                conn.send({"t": "error", "msg_id": env.get("msg_id"),
                           "code": 13, "text": f"internal error: {e!r}"})

    # -- the client txn surface ----------------------------------------------
    def _on_txn(self, env: dict, conn: _Conn) -> None:
        msg_id = env.get("msg_id")
        if not self.admission.try_admit(time.monotonic()):
            conn.send({"t": "busy", "msg_id": msg_id})
            return
        ops = env.get("ops", [])
        try:
            txn, build_reply = self._build_txn(ops)
        except ValueError as e:
            self.admission.on_complete(time.monotonic())
            conn.send({"t": "error", "msg_id": msg_id, "code": 10,
                       "text": str(e)})
            return
        if txn is None:  # no keys: trivially ok
            self.admission.on_complete(time.monotonic())
            self.txn_ok.inc()
            conn.send({"t": "txn_ok", "msg_id": msg_id, "txn": ops})
            return

        def done(result, failure):
            self.admission.on_complete(time.monotonic())
            if failure is not None:
                self.txn_error.inc()
                conn.send({"t": "error", "msg_id": msg_id, "code": 11,
                           "text": f"{type(failure).__name__}: {failure}"})
                return
            self.txn_ok.inc()
            conn.send({"t": "txn_ok", "msg_id": msg_id,
                       "txn": build_reply(result)})
            self._kick.set()

        self.node.coordinate(txn).add_callback(done)

    @staticmethod
    def _build_txn(ops: List[list]):
        """Maelstrom list-append micro-ops -> one accord Txn (the
        maelstrom/core.py translation, reply including intra-txn
        visibility: a read AFTER an append in op order sees it)."""
        read_keys: List[int] = []
        appends: Dict[int, List[int]] = {}
        for op, key, value in ops:
            k = int(key) % KEY_DOMAIN
            if op == "r":
                read_keys.append(k)
            elif op == "append":
                if int(value) in appends.get(k, ()):
                    raise ValueError(
                        f"duplicate append of {value} to key {key}")
                appends.setdefault(k, []).append(int(value))
            else:
                raise ValueError(f"unsupported op {op!r}")
        all_keys = Keys(set(read_keys) | set(appends))
        if len(all_keys) == 0:
            return None, None
        update = MultiAppendUpdate(
            {k: tuple(v) for k, v in appends.items()}) if appends else None
        txn = Txn(TxnKind.WRITE if appends else TxnKind.READ, all_keys,
                  read=ListRead(all_keys), update=update, query=ListQuery())

        def build_reply(result) -> List[list]:
            out = []
            appended: Dict[int, List[int]] = {}
            for op, key, value in ops:
                k = int(key) % KEY_DOMAIN
                if op == "r":
                    out.append([op, key, list(result.reads.get(k, ()))
                                + appended.get(k, [])])
                else:
                    appended.setdefault(k, []).append(value)
                    out.append([op, key, value])
            return out

        return txn, build_reply

    # -- observability -------------------------------------------------------
    def snapshot(self) -> dict:
        """One flat dict: the serve registry (transport/admission counters)
        over the node's full snapshot (txn lifecycle + resolver planes)."""
        snap = self.node.metrics_snapshot()
        snap.update(self.metrics.snapshot())
        snap["serve.device_platform"] = self.device["platform"]
        snap["serve.device_kind"] = self.device["kind"]
        return snap

    def _jit_cache(self) -> dict:
        if self.resolver is None:
            return {}
        from accord_tpu.ops.kernels import jit_cache_sizes
        return jit_cache_sizes()

    def _dump_metrics(self, reason: str) -> None:
        self.log("metrics %s node=%s %s" % (
            reason, self.cfg.node_id,
            self.metrics.snapshot_json(extra=self.node.metrics_snapshot())))

    # -- lifecycle -----------------------------------------------------------
    async def _graceful_stop(self, conn: Optional[_Conn],
                             env: Optional[dict]) -> None:
        """Stop admitting, wait out in-flight coordinations (bounded), drain
        the staged device pipeline, then exit the serve loop. Safe to hit
        more than once (Ctrl-C during drain): Node.shutdown is idempotent
        and a second call just waits alongside the first."""
        self.admission.closed = True
        deadline = time.monotonic() + self.cfg.drain_timeout_s
        while self.admission.inflight > 0 and time.monotonic() < deadline:
            self.scheduler.run_due()
            await asyncio.sleep(0.01)
        self.node.shutdown()
        self._dump_metrics("shutdown")
        if conn is not None and env is not None:
            conn.send({"t": "shutdown_ok", "msg_id": env.get("msg_id"),
                       "drained": self.admission.inflight == 0})
            try:
                await conn.writer.drain()
            except Exception:
                pass
        self._stopping.set()

    async def _ticker(self) -> None:
        """Drive the timer heap (accord timeouts, the resolver's batch tick
        and harvest events) from the event loop: sleep until the next
        deadline OR the next inbound frame kicks us, whichever is first."""
        last_snap = time.monotonic()
        while not self._stopping.is_set():
            self.scheduler.run_due()
            deadline = self.scheduler.next_deadline_us()
            if deadline is None:
                wait = 0.05
            else:
                wait = max(0.0, (deadline - self.clock.now_micros()) / 1e6)
                wait = min(wait, 0.05)
            try:
                await asyncio.wait_for(self._kick.wait(), timeout=wait)
            except asyncio.TimeoutError:
                pass
            self._kick.clear()
            if (time.monotonic() - last_snap
                    >= self.cfg.metrics_interval_s):
                last_snap = time.monotonic()
                self._dump_metrics("periodic")

    def warm_kernels(self) -> dict:
        """Pre-compile the device resolver's jit tiers for this node's
        arena shape. Serving without this makes the FIRST preaccept pay
        multi-second XLA compiles inside the rpc timeout window (observed:
        ~4s on 8 virtual CPU devices vs a 3s timeout -- every early txn
        dies). Returns jit_cache_sizes() so callers can assert zero
        post-warmup recompiles."""
        if self.resolver is None:
            return {}
        from accord_tpu.ops.kernels import jit_cache_sizes
        from accord_tpu.ops.resolver import warmup
        r = self.resolver
        warmup(num_buckets=r.num_buckets, cap=r.initial_cap,
               batch_tiers=(8, 64, 128), scatter_tiers=(8, 64),
               store_tiers=(min(self.cfg.num_stores, 2),),
               out_tiers=(256, 2048), range_out_tiers=())
        return jit_cache_sizes()

    async def run(self) -> None:
        self._loop = asyncio.get_running_loop()
        self._kick = asyncio.Event()
        self._stopping = asyncio.Event()
        if self.cfg.warmup:
            t0 = time.monotonic()
            self.warm_kernels()
            self.log("warmup done in %.1fs on %s (%s)" % (
                time.monotonic() - t0, self.device["platform"],
                self.device["kind"]))
        host, port = self.cfg.listen
        bind = self.cfg.bind_host or host
        self._server = await asyncio.start_server(self._on_client, bind, port)
        self.log(f"serving node {self.cfg.node_id} on {bind}:{port}"
                 + (f" (advertised {host})" if bind != host else ""))
        ticker = self._loop.create_task(self._ticker())
        # a node's stores hold millions of acyclic objects: full collections
        # are stalls of the loop that free nothing (utils/collector.py)
        try:
            with settled_collector():
                await self._stopping.wait()
        finally:
            ticker.cancel()
            self._server.close()
            # wait_closed() waits for every accepted connection's handler,
            # and peers and clients keep theirs open for as long as they
            # live: close what this server holds (buffered replies such as
            # shutdown_ok still flush) so the handlers see EOF and return
            for conn in list(self._inbound):
                conn.writer.close()
            dials = list(self._peer_dialing.values())
            for task in dials:
                task.cancel()
            await asyncio.gather(ticker, *dials, return_exceptions=True)
            await self._server.wait_closed()


def _parse_addr(spec: str) -> Tuple[str, int]:
    host, _, port = spec.rpartition(":")
    return host or "127.0.0.1", int(port)


def _parse_peers(spec: str) -> Dict[int, Tuple[str, int]]:
    out = {}
    for part in spec.split(","):
        if not part:
            continue
        nid, _, addr = part.partition("=")
        out[int(nid)] = _parse_addr(addr)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="serve one accord node over the socket transport")
    ap.add_argument("--node-id", type=int, required=True)
    ap.add_argument("--listen", required=True,
                    help="host:port peers dial (the advertised address)")
    ap.add_argument("--bind-host", default=None,
                    help="interface to bind instead of the advertised host "
                         "(e.g. 0.0.0.0 for multi-host clusters; default: "
                         "the --listen host)")
    ap.add_argument("--peers", required=True,
                    help="comma list id=host:port (all nodes incl. self)")
    ap.add_argument("--num-stores", type=int, default=1)
    ap.add_argument("--batch-window-ms", type=float, default=1.0)
    ap.add_argument("--host-deps", action="store_true",
                    help="disable the device deps resolver (host scans)")
    ap.add_argument("--admission-rate", type=float, default=500.0)
    ap.add_argument("--admission-burst", type=int, default=64)
    ap.add_argument("--max-inflight", type=int, default=256)
    ap.add_argument("--metrics-interval-s", type=float, default=10.0)
    ap.add_argument("--no-warmup", action="store_true",
                    help="skip kernel pre-compilation at startup (first "
                         "txns then compile in-band; pair with a bigger "
                         "--rpc-timeout-ms)")
    ap.add_argument("--rpc-timeout-ms", type=float, default=3000.0)
    args = ap.parse_args(argv)
    cfg = ServeConfig(
        node_id=args.node_id,
        listen=_parse_addr(args.listen),
        peers=_parse_peers(args.peers),
        num_stores=args.num_stores,
        batch_window_ms=args.batch_window_ms,
        device_deps=not args.host_deps,
        admission_rate=args.admission_rate,
        admission_burst=args.admission_burst,
        max_inflight=args.max_inflight,
        metrics_interval_s=args.metrics_interval_s,
        warmup=not args.no_warmup,
        rpc_timeout_ms=args.rpc_timeout_ms,
        bind_host=args.bind_host)
    from accord_tpu.utils.compile_cache import place_compile_cache
    place_compile_cache()
    server = NodeServer(cfg)

    async def _run():
        import signal
        loop = asyncio.get_running_loop()
        for sig in (signal.SIGINT, signal.SIGTERM):
            try:
                loop.add_signal_handler(
                    sig, lambda: loop.create_task(
                        server._graceful_stop(None, None)))
            except NotImplementedError:
                pass
        await server.run()

    asyncio.run(_run())
    return 0
