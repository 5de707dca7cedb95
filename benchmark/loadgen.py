"""The list-append load generator and history recorder of the served cells.

A copy of `accord_tpu/serve/loadgen.py` (commit c139469; unchanged at
ee317a3) cut to what the benchmark drives -- `_NodeConn`, `LoadClient`,
`LoadGen._gen_ops`/`_issue_one`, `verify_history` -- with its registry
histograms dropped (the runner keeps raw latencies and uses
`common.percentile_exact`), Python's `random.Random` in place of the
program's `RandomSource`, the benchmark's copy of the verifier, and three
additions: keys spread over the key domain (`key_stride`), a Zipf draw
(`key_dist` "zipf", `theta`), and the two drivers `closed_loop` and
`open_loop`. From the program it takes the wire codec alone
(`serve.transport`): that is the protocol a client speaks.
"""
from __future__ import annotations

import asyncio
import bisect
import itertools
import math
import random
import time
from typing import Dict, List, Optional, Tuple

from accord_tpu.serve import transport
from benchmark.verifier import StrictSerializabilityVerifier


class _NodeConn:
    """One client connection to one node: request/reply matched by msg_id,
    lost connections resolve every outstanding future with None."""

    def __init__(self, addr: Tuple[str, int]):
        self.addr = addr
        self.reader: Optional[asyncio.StreamReader] = None
        self.writer: Optional[asyncio.StreamWriter] = None
        self._pending: Dict[int, asyncio.Future] = {}
        self._task: Optional[asyncio.Task] = None
        self.alive = False

    async def connect(self) -> None:
        self.reader, self.writer = await asyncio.open_connection(*self.addr)
        self.alive = True
        self._task = asyncio.get_running_loop().create_task(self._pump())

    async def _pump(self) -> None:
        decoder = transport.FrameDecoder()
        try:
            while True:
                chunk = await self.reader.read(1 << 16)
                if not chunk:
                    break
                for payload in decoder.feed(chunk):
                    env = transport.decode_message(payload)
                    fut = self._pending.pop(env.get("msg_id"), None)
                    if fut is not None and not fut.done():
                        fut.set_result(env)
        except Exception:
            pass
        finally:
            self.alive = False
            for fut in self._pending.values():
                if not fut.done():
                    fut.set_result(None)
            self._pending.clear()

    async def request(self, env: dict, timeout_s: float) -> Optional[dict]:
        """Send one envelope, await its reply; None on timeout or a dead
        connection (the caller decides what 'unknown outcome' means)."""
        if not self.alive:
            return None
        fut = asyncio.get_running_loop().create_future()
        self._pending[env["msg_id"]] = fut
        try:
            self.writer.write(transport.encode_envelope(env))
        except Exception:
            self._pending.pop(env["msg_id"], None)
            return None
        try:
            return await asyncio.wait_for(fut, timeout=timeout_s)
        except asyncio.TimeoutError:
            self._pending.pop(env["msg_id"], None)
            return None

    async def close(self) -> None:
        if self.writer is not None:
            try:
                self.writer.close()
                await self.writer.wait_closed()
            except Exception:
                pass
        if self._task is not None:
            self._task.cancel()


class LoadClient:
    """Connections to every node + the shared msg-id space."""

    def __init__(self, addrs: Dict[int, Tuple[str, int]]):
        self.conns = {nid: _NodeConn(addr) for nid, addr in addrs.items()}
        self._msg_ids = itertools.count(1)

    async def connect(self) -> None:
        for conn in self.conns.values():
            await conn.connect()

    async def close(self) -> None:
        for conn in self.conns.values():
            await conn.close()

    def next_msg_id(self) -> int:
        return next(self._msg_ids)

    async def admin(self, nid: int, kind: str,
                    timeout_s: float = 30.0) -> Optional[dict]:
        return await self.conns[nid].request(
            {"t": kind, "msg_id": self.next_msg_id()}, timeout_s)


class LoadGen:
    """The generator + history recorder. One instance spans warm-up and
    window, so values stay globally unique and the recorded history is one
    coherent list-append run."""

    def __init__(self, client: LoadClient, seed: int, key_count: int,
                 write_ratio: float, max_keys_per_txn: int,
                 key_stride: int = 1, key_dist: str = "uniform",
                 theta: float = 0.99, txn_timeout_s: float = 15.0):
        self.client = client
        self.rng = random.Random(seed)
        self.keys = [i * key_stride for i in range(key_count)]
        self.write_ratio = write_ratio
        self.max_keys_per_txn = max_keys_per_txn
        self.txn_timeout_s = txn_timeout_s
        if key_dist == "zipf":
            weights = [1.0 / (i + 1) ** theta for i in range(key_count)]
            self._cdf = list(itertools.accumulate(weights))
        elif key_dist == "uniform":
            self._cdf = None
        else:
            raise ValueError(f"key_dist {key_dist!r}: uniform or zipf")
        self._next_value = itertools.count(1)
        self._t0 = time.monotonic()
        # the recorded history: issue marks + one entry per issued txn
        self.issues: List[Tuple[int, int]] = []   # (value, start_us)
        self.entries: List[dict] = []
        self.late_us: List[int] = []  # open loop: how late each send ran

    def now_us(self) -> int:
        return int((time.monotonic() - self._t0) * 1e6)

    def _pick_key(self) -> int:
        if self._cdf is None:
            return self.keys[self.rng.randrange(len(self.keys))]
        u = self.rng.random() * self._cdf[-1]
        return self.keys[bisect.bisect_left(self._cdf, u)]

    def _gen_ops(self):
        """Reads first, then appends of ONE fresh value to the write keys:
        the reply's read echoes are then exactly the txn's observed
        pre-state (no intra-txn visibility), which is the verifier's
        witness format."""
        nkeys = 1 + self.rng.randrange(self.max_keys_per_txn)
        chosen = sorted({self._pick_key() for _ in range(nkeys)})
        ops = [["r", k, None] for k in chosen]
        value = None
        writes: Dict[int, int] = {}
        if self.rng.random() < self.write_ratio:
            value = next(self._next_value)
            for k in chosen:
                ops.append(["append", k, value])
                writes[k] = value
        return ops, value, writes, chosen

    async def _issue_one(self, nid: int, due_us: Optional[int] = None) -> None:
        """One txn against node `nid`. `due_us` (open loop) is when the
        schedule wanted it sent: the entry is timed from then."""
        ops, value, writes, read_keys = self._gen_ops()
        sent_us = self.now_us()
        start_us = sent_us if due_us is None else due_us
        if due_us is not None:
            self.late_us.append(sent_us - due_us)
        if value is not None:
            self.issues.append((value, start_us))
        env = {"t": "txn", "msg_id": self.client.next_msg_id(), "ops": ops}
        reply = await self.client.conns[nid].request(env, self.txn_timeout_s)
        end_us = self.now_us()
        entry = {"node": nid, "start_us": start_us, "end_us": end_us,
                 "writes": writes, "reads": {}}
        if reply is None:
            entry["outcome"] = "lost"  # timeout/disconnect: outcome unknown
        elif reply["t"] == "busy":
            entry["outcome"] = "busy"
        elif reply["t"] == "error":
            entry["outcome"] = "error"
            entry["error"] = reply.get("text", "")
        else:
            assert reply["t"] == "txn_ok", reply
            entry["outcome"] = "ok"
            for op, key, val in reply["txn"]:
                if op == "r":
                    entry["reads"][key] = tuple(val)
            assert set(entry["reads"]) == set(read_keys)
        self.entries.append(entry)

    async def closed_loop(self, clients: int, nodes: List[int],
                          duration_s: float) -> None:
        """`clients` callers, each sending its next txn when the last is
        answered, no think time; client j talks to nodes[j % len(nodes)].
        They stop issuing after `duration_s`; what is in flight is awaited."""
        t_end = time.monotonic() + duration_s

        async def client(j):
            nid = nodes[j % len(nodes)]
            while time.monotonic() < t_end:
                await self._issue_one(nid)

        await asyncio.gather(*[client(j) for j in range(clients)])

    async def open_loop(self, rate_per_s: float, nodes: List[int],
                        duration_s: float) -> None:
        """Poisson arrivals at `rate_per_s` for `duration_s`, issued whether
        or not earlier txns completed, coordinators in rotation; each txn is
        timed from when it was due, and `late_us` keeps how late the
        generator ran. Waits for every issued txn to resolve or time out."""
        loop = asyncio.get_running_loop()
        tasks = []
        due = time.monotonic()
        t_end = due + duration_s
        while due < t_end:
            wait = due - time.monotonic()
            if wait > 0:
                await asyncio.sleep(wait)
            due_us = int((due - self._t0) * 1e6)
            tasks.append(loop.create_task(
                self._issue_one(nodes[len(tasks) % len(nodes)], due_us)))
            due += -math.log(max(self.rng.random(), 1e-9)) / rate_per_s
        if tasks:
            await asyncio.gather(*tasks)


def verify_history(issues: List[Tuple[int, int]], entries: List[dict],
                   final_lists: Optional[Dict[int, tuple]] = None
                   ) -> StrictSerializabilityVerifier:
    """Replay a recorded history through the strict-serializability checker;
    raises verifier.HistoryViolation on the first anomaly. Only "ok" entries
    are witnessed; busy/error/lost txns leave their values as maybe-writes
    (allowed, never required) -- except that `final_lists` (the converged
    authoritative state) must still extend every observed order and contain
    every *acked* write."""
    verifier = StrictSerializabilityVerifier()
    for value, start_us in issues:
        verifier.on_issue_write(value, start_us)
    for entry in sorted((e for e in entries if e["outcome"] == "ok"),
                        key=lambda e: e["end_us"]):
        verifier.witness(entry["start_us"], entry["end_us"],
                         dict(entry["reads"]), dict(entry["writes"]))
    if final_lists is not None:
        verifier.check_final_state(
            {k: tuple(v) for k, v in final_lists.items()})
    return verifier
