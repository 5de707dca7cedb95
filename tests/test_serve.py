"""Serving surface tests: the transport codec (pure, no sockets), the
admission governor (deterministic injected clock), one in-process node on a
loopback socket (host deps, no compile) for the stop path, and -- marked
slow -- real 3-process clusters over TCP: commit + strict-serializability
verify, and a crash-one-node leg where the surviving quorum keeps
committing.

No sockets are bound at collection time; every bind happens inside a test
body."""
from __future__ import annotations

import asyncio
import os
import socket
import subprocess
import sys
import time

import pytest

from accord_tpu.serve import transport
from accord_tpu.serve.admission import AdmissionController, TokenBucket

pytestmark = pytest.mark.serve


# -- framing ------------------------------------------------------------------

def test_frame_roundtrip_single():
    payload = b"hello accord"
    frame = transport.encode_frame(payload)
    assert frame[:4] == len(payload).to_bytes(4, "big")
    dec = transport.FrameDecoder()
    assert dec.feed(frame) == [payload]
    assert dec.pending_bytes() == 0


def test_frame_decoder_handles_arbitrary_segmentation():
    payloads = [b"", b"x", b"y" * 300, b"z" * 70000]
    stream = b"".join(transport.encode_frame(p) for p in payloads)
    # worst case: the stream arrives one byte at a time (headers and
    # payloads both split across feeds)
    dec = transport.FrameDecoder()
    out = []
    for i in range(len(stream)):
        out.extend(dec.feed(stream[i:i + 1]))
    assert out == payloads
    assert dec.pending_bytes() == 0
    assert dec.bytes_in == len(stream)


def test_frame_decoder_many_frames_one_chunk():
    payloads = [bytes([i]) * (i * 37 + 1) for i in range(20)]
    stream = b"".join(transport.encode_frame(p) for p in payloads)
    dec = transport.FrameDecoder()
    assert dec.feed(stream) == payloads


def test_frame_large_payload_over_64kib():
    # bigger than any single socket read chunk (the server reads 64 KiB at
    # a time), so the decoder must hold a partial body across feeds
    payload = os.urandom((1 << 20) + 17)
    stream = transport.encode_frame(payload)
    dec = transport.FrameDecoder()
    out = []
    for off in range(0, len(stream), 1 << 16):
        out.extend(dec.feed(stream[off:off + (1 << 16)]))
    assert out == [payload]


def test_frame_ceiling_enforced_both_directions():
    with pytest.raises(transport.FrameError):
        transport.encode_frame(b"x" * (transport.MAX_FRAME_BYTES + 1))
    # a hostile/corrupt header must fail fast, not buffer gigabytes
    bad = (transport.MAX_FRAME_BYTES + 1).to_bytes(4, "big")
    with pytest.raises(transport.FrameError):
        transport.FrameDecoder().feed(bad)


def test_envelope_roundtrips_wire_codec():
    env = {"t": "accord", "mid": 7, "from": 2,
           "payload": {"nested": [1, 2, (3, 4)], "k": "v"}}
    frame = transport.encode_envelope(env)
    (payload,) = transport.FrameDecoder().feed(frame)
    got = transport.decode_message(payload)
    assert got == env
    assert got is not env  # value copy, never a shared live object


def test_line_decoder_partial_lines():
    dec = transport.LineDecoder()
    assert list(dec.feed(b'{"a": 1}\n{"b"')) == [b'{"a": 1}']
    assert list(dec.feed(b": 2}\n\n")) == [b'{"b": 2}']
    assert transport.decode_json_line(b'{"b": 2}') == {"b": 2}


def test_bind_host_parses_and_roundtrips():
    """Multi-host plumbing, no sockets: non-loopback addresses parse, the
    bind/advertise split lands in ServeConfig (bind_host is the bound
    interface; `listen` stays the ADVERTISED address peers dial, and
    None means bind the advertised host -- the loopback CI default), and
    peer maps carrying non-loopback addresses survive the frame codec."""
    from accord_tpu.serve.server import ServeConfig, _parse_addr, _parse_peers

    assert _parse_addr("0.0.0.0:7001") == ("0.0.0.0", 7001)
    assert _parse_addr("10.1.2.3:7102") == ("10.1.2.3", 7102)
    assert _parse_addr("7103") == ("127.0.0.1", 7103)  # bare-port default

    peers = _parse_peers("1=10.1.2.3:7101,2=10.1.2.4:7101,3=127.0.0.1:7103")
    assert peers == {1: ("10.1.2.3", 7101), 2: ("10.1.2.4", 7101),
                     3: ("127.0.0.1", 7103)}

    cfg = ServeConfig(node_id=1, listen=("10.1.2.3", 7101), peers=peers,
                      bind_host="0.0.0.0")
    assert cfg.bind_host == "0.0.0.0"
    assert cfg.listen == ("10.1.2.3", 7101)  # advertised, not the bind
    assert ServeConfig(node_id=1, listen=("127.0.0.1", 7101),
                       peers=peers).bind_host is None

    # a peer-exchange payload with routable addresses round-trips the
    # length-prefixed wire codec byte-exactly
    env = {"t": "peers", "from": 1,
           "payload": {nid: list(addr) for nid, addr in peers.items()}}
    (raw,) = transport.FrameDecoder().feed(transport.encode_envelope(env))
    assert transport.decode_message(raw) == env


# -- admission ----------------------------------------------------------------

def test_token_bucket_rate_and_burst():
    b = TokenBucket(rate_per_s=10.0, burst=5)
    # burst drains immediately...
    assert [b.try_take(0.0) for _ in range(6)] == [True] * 5 + [False]
    # ...then refills at exactly rate_per_s
    assert not b.try_take(0.05)   # half a token earned: still dry
    assert b.try_take(0.1)        # one token earned
    assert not b.try_take(0.1)


def test_admission_overload_sheds_with_explicit_busy():
    """Offered load far beyond capacity: every arrival is either admitted
    or answered BUSY (nothing silently dropped), queue depth stays at the
    bound, and pressure engages once per episode."""
    pressure_calls = []
    adm = AdmissionController(rate_per_s=100.0, burst=10, max_inflight=8,
                              on_pressure=pressure_calls.append)
    admitted = busy = 0
    inflight = []
    # 1000 arrivals in one simulated second = 10x the sustained rate
    for i in range(1000):
        now = i / 1000.0
        if adm.try_admit(now):
            admitted += 1
            inflight.append(now)
            assert adm.inflight <= adm.max_inflight
        else:
            busy += 1
        # complete admitted work slowly: 1 completion per 4 arrivals keeps
        # the queue pinned at its depth bound
        if i % 4 == 0 and inflight:
            inflight.pop()
            adm.on_complete(now)
    assert admitted + busy == 1000  # zero dropped-without-reply
    assert busy > 0 and adm.busy_count == busy
    assert adm.metrics.gauge("serve.queue_depth").value <= adm.max_inflight
    # overload is one episode: pressure engaged once, not per BUSY
    assert pressure_calls == [True]
    assert adm.shed_count == 1
    # drain whatever is still in flight, then a full quiet window later
    # the next admit disengages the governor
    while inflight:
        inflight.pop()
        adm.on_complete(0.999)
    t = 1.0 + AdmissionController.QUIET_WINDOW_S
    assert adm.try_admit(t)
    adm.on_complete(t)
    assert pressure_calls == [True, False]
    # the next overload is a NEW episode
    for i in range(200):
        adm.try_admit(t + 0.001 * i)
    assert adm.shed_count == 2


def test_admission_closed_rejects_everything():
    adm = AdmissionController(rate_per_s=1000.0, burst=100, max_inflight=10)
    assert adm.try_admit(0.0)
    adm.closed = True
    assert not adm.try_admit(0.1)
    adm.on_complete(0.2)
    assert adm.inflight == 0


# -- shutdown semantics -------------------------------------------------------

def test_node_shutdown_idempotent_and_schedulerless():
    """Node.shutdown drains the device pipeline exactly once (a second
    call -- serve-mode Ctrl-C racing a client shutdown -- is a no-op) and
    works on a node whose scheduler is gone (an external event loop owns
    the drain; harvest timers are skipped, the blocking drain still runs
    to completion)."""
    from accord_tpu.maelstrom.runner import Runner

    r = Runner(seed=3, num_nodes=2)
    r.run_random_workload(ops=8, keys=4)
    first, second = (mn.node for mn in r.nodes.values())
    snapshots = []
    first.metrics_sink = snapshots.append
    first.shutdown()
    first.shutdown()
    assert len(snapshots) == 1, "second shutdown re-drained the pipeline"
    second.scheduler = None
    second.shutdown()  # must not touch the missing scheduler


def test_run_returns_after_shutdown_with_client_still_connected():
    """`run()` must come back once a client asked for shutdown, although
    that client (like every peer) keeps its connection open: since Python
    3.12 `Server.wait_closed()` waits for every accepted connection, so
    the server closes the ones it holds. Host deps, no warm-up: nothing
    here compiles."""
    from accord_tpu.serve.loadgen import LoadClient
    from accord_tpu.serve.server import NodeServer, ServeConfig

    (port,) = _free_ports(1)
    addrs = {1: ("127.0.0.1", port)}
    lines = []
    server = NodeServer(ServeConfig(1, addrs[1], addrs, device_deps=False,
                                    warmup=False), log=lines.append)
    assert server.snapshot()["serve.device_platform"] == "host"

    async def scenario():
        run = asyncio.ensure_future(server.run())
        while not any(line.startswith("serving node") for line in lines):
            assert not run.done(), run
            await asyncio.sleep(0.01)
        client = LoadClient(addrs)
        await client.connect()
        try:
            reply = await client.conns[1].request(
                {"t": "txn", "msg_id": client.next_msg_id(),
                 "ops": [["append", 7, 1], ["r", 7, None]]}, 10.0)
            assert reply["t"] == "txn_ok", reply
            assert reply["txn"][1] == ["r", 7, [1]]
            reply = await client.admin(1, "shutdown", timeout_s=10.0)
            assert reply == {"t": "shutdown_ok", "msg_id": reply["msg_id"],
                             "drained": True}
            assert client.conns[1].writer is not None  # still held open
            await asyncio.wait_for(run, timeout=10.0)
        finally:
            run.cancel()
            await client.close()

    asyncio.run(scenario())
    assert not [line for line in lines if line.startswith("error")], lines


# -- multi-process cluster (slow) ---------------------------------------------

def _free_ports(n):
    socks = [socket.socket() for _ in range(n)]
    for s in socks:
        s.bind(("127.0.0.1", 0))
    ports = [s.getsockname()[1] for s in socks]
    for s in socks:
        s.close()
    return ports


class _Cluster:
    """N serve processes on loopback. Started with --no-warmup (tests warm
    in-band instead of paying the full tier pre-compile per process) and a
    long rpc timeout so in-band compilation cannot fail early txns."""

    def __init__(self, n=3, tmpdir="/tmp"):
        self.ports = _free_ports(n)
        peers = ",".join(f"{i + 1}=127.0.0.1:{p}"
                         for i, p in enumerate(self.ports))
        # the node entry point places a persistent compile cache; keep it
        # out of the checkout and private to this cluster
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   JAX_COMPILATION_CACHE_DIR=os.path.join(tmpdir, "jax-cache"))
        self.logs = []
        self.procs = []
        for i, port in enumerate(self.ports):
            log = open(os.path.join(tmpdir, f"serve-n{i + 1}.log"), "w")
            self.logs.append(log)
            self.procs.append(subprocess.Popen(
                [sys.executable, "-m", "accord_tpu.serve",
                 "--node-id", str(i + 1),
                 "--listen", f"127.0.0.1:{port}", "--peers", peers,
                 "--no-warmup", "--rpc-timeout-ms", "20000",
                 "--metrics-interval-s", "60"],
                env=env, stdout=log, stderr=log))

    @property
    def addrs(self):
        return {i + 1: ("127.0.0.1", p) for i, p in enumerate(self.ports)}

    async def wait_listening(self, timeout_s=60.0):
        for host, port in self.addrs.values():
            deadline = time.monotonic() + timeout_s
            while True:
                try:
                    _, w = await asyncio.open_connection(host, port)
                    w.close()
                    break
                except OSError:
                    if time.monotonic() > deadline:
                        raise AssertionError(f"node on :{port} never bound")
                    await asyncio.sleep(0.2)

    def kill(self, nid):
        self.procs[nid - 1].kill()

    def teardown(self):
        for p in self.procs:
            if p.poll() is None:
                p.kill()
            p.wait(timeout=10)
        for log in self.logs:
            log.close()


async def _shutdown_all(client, cluster, nids):
    for nid in nids:
        reply = await client.admin(nid, "shutdown", timeout_s=30)
        assert reply is not None and reply["t"] == "shutdown_ok", reply
    for nid in nids:
        assert cluster.procs[nid - 1].wait(timeout=15) == 0


def _merged_keylists(lists_by_node):
    """Per-key longest list across nodes, asserting every node's copy is a
    prefix of the longest (append-only convergence)."""
    merged = {}
    for lists in lists_by_node.values():
        for k, v in lists.items():
            cur = merged.setdefault(k, v)
            short, long_ = (cur, v) if len(cur) <= len(v) else (v, cur)
            assert tuple(long_[:len(short)]) == tuple(short), \
                f"key {k} diverged: {cur} vs {v}"
            merged[k] = long_
    return merged


@pytest.mark.slow
def test_three_process_commit_and_verify(tmp_path):
    from accord_tpu.serve.loadgen import LoadClient, LoadGen, verify_history

    cluster = _Cluster(3, str(tmp_path))

    async def scenario():
        await cluster.wait_listening()
        client = LoadClient(cluster.addrs)
        await client.connect()
        try:
            gen = LoadGen(client, seed=31, txn_timeout_s=60.0)
            # warm leg: drives every node's in-band kernel compiles; its
            # entries stay part of the one verified history
            await gen.run_leg(rate_per_s=3, duration_s=4)
            leg = await gen.run_leg(rate_per_s=25, duration_s=4)
            assert leg["ok"] > 0, leg
            assert leg["lost"] == 0 and leg["errors"] == 0, leg
            assert leg["p99_us"] > 0
            await asyncio.sleep(1.0)
            lists_by_node = {}
            for nid in cluster.addrs:
                reply = await client.admin(nid, "keylists")
                lists_by_node[nid] = reply["lists"]
            verify_history(gen.issues, gen.entries,
                           final_lists=_merged_keylists(lists_by_node))
            await _shutdown_all(client, cluster, list(cluster.addrs))
        finally:
            await client.close()

    try:
        asyncio.run(scenario())
    finally:
        cluster.teardown()


@pytest.mark.slow
def test_crash_one_node_survivors_commit(tmp_path):
    from accord_tpu.serve.loadgen import LoadClient, LoadGen, verify_history

    cluster = _Cluster(3, str(tmp_path))

    async def scenario():
        await cluster.wait_listening()
        client = LoadClient(cluster.addrs)
        await client.connect()
        try:
            gen = LoadGen(client, seed=47, txn_timeout_s=60.0)
            await gen.run_leg(rate_per_s=3, duration_s=4)  # in-band warm
            cluster.kill(3)
            # rf=3 electorate: {1, 2} is still a quorum, so the survivors
            # keep committing (txns sent to the dead node count as lost)
            leg = await gen.run_leg(rate_per_s=15, duration_s=4,
                                    nodes=[1, 2])
            assert leg["ok"] > 0, leg
            assert leg["lost"] == 0, leg
            # the acked history must still linearize; final-state check is
            # skipped (the dead node may hold acked-but-unreplicated reads'
            # context, and survivors converge only after recovery settles)
            verify_history(gen.issues, gen.entries)
            await _shutdown_all(client, cluster, [1, 2])
        finally:
            await client.close()

    try:
        asyncio.run(scenario())
    finally:
        cluster.teardown()
