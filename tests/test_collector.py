"""The collector's setting for a process that serves stores
(`accord_tpu/utils/collector.py`).

Load-bearing properties:
  1. `settled_collector` moves the oldest generation's threshold only, for
     as long as it is held, and holders nest: the last to leave puts back
     what the first found, whatever the order they leave in;
  2. what it rests on -- a store that lives leaves no cyclic garbage: with
     the collector off, rounds of PreAccepts, commits, applies and a wave
     each free what they drop by reference count alone;
  3. its holders -- a node server from start-up to shutdown, and the live
     runner's start-up, and neither leaves the setting behind.
"""
from __future__ import annotations

import contextlib
import gc

import pytest

from accord_tpu.utils import collector
from benchmark import common
from benchmark.runners import live

CELL = "preaccept-batch-100k.resolve-4096"


def _params(**over):
    cell = common.load_json(common.HERE / "workloads" / f"{CELL}.json")
    config = common.load_json(
        common.HERE / "configs" / f"{cell['config']}.json")
    return {**config, **cell, **cell["rehearsal"], **over}


def test_only_the_oldest_generation_moves_and_comes_back():
    was = gc.get_threshold()
    with collector.settled_collector():
        assert gc.get_threshold() == (was[0], was[1],
                                      collector.OLD_GENERATION_EVERY)
    assert gc.get_threshold() == was


@pytest.mark.parametrize("first_out", ["inner", "outer"])
def test_holders_nest_in_either_order(first_out):
    was = gc.get_threshold()
    with contextlib.ExitStack() as outer, contextlib.ExitStack() as inner:
        outer.enter_context(collector.settled_collector())
        inner.enter_context(collector.settled_collector())
        held = gc.get_threshold()
        assert held[2] == collector.OLD_GENERATION_EVERY
        (inner if first_out == "inner" else outer).close()
        assert gc.get_threshold() == held  # one holder is left
    assert gc.get_threshold() == was


def test_a_failure_inside_puts_the_setting_back():
    was = gc.get_threshold()
    with pytest.raises(RuntimeError):
        with collector.settled_collector():
            raise RuntimeError("the server fell over")
    assert gc.get_threshold() == was


def test_a_store_that_lives_leaves_no_cyclic_garbage():
    """Rounds of the live deployment with the collector off: what a full
    collection then finds is under one object a txn (the reading this rests
    on: 0 collected by every full collection of a run of 73 rounds)."""
    p = _params()
    d = live.Deployment(p, seed=11)
    for _ in range(p["resident_rounds"] + 3):  # past the first waves
        r = d.round()
        assert not (r["wrong"] or r["failed"] or r["refused"]), r
    gc.collect()
    gc.disable()
    try:
        rounds = 6
        for _ in range(rounds):
            r = d.round()
            assert r["waved"] and not (r["wrong"] or r["failed"]), r
        found = gc.collect()
    finally:
        gc.enable()
    assert found < rounds * p["subjects"], found


def test_the_live_runner_holds_it_from_start_up_and_leaves_nothing(monkeypatch):
    seen = []
    warm = live.warm_kernels

    def spy(p):
        seen.append(gc.get_threshold())
        return warm(p)

    monkeypatch.setattr(live, "warm_kernels", spy)
    was = gc.get_threshold()
    # set-up ends with the arena full, so the window's first round compacts
    # (`correct` asks for a compaction) however slow this machine's rounds
    out = live.run(_params(rounds_before_fill=0), seed=3, seconds=0.1,
                   trace=False, meter=common.CompileMeter())
    assert out["correct"], out["notes"]["faults"]
    assert seen == [(was[0], was[1], collector.OLD_GENERATION_EVERY)]
    assert gc.get_threshold() == was
    young, middle, old = out["notes"]["collections_since_start"]
    assert young >= middle >= old >= 0 and young > 0


def test_a_node_server_holds_it_from_start_up_to_shutdown():
    """Host deps, no warm-up: nothing here compiles."""
    import asyncio
    import socket

    from accord_tpu.serve.server import NodeServer, ServeConfig

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        addr = ("127.0.0.1", s.getsockname()[1])
    lines = []
    server = NodeServer(ServeConfig(1, addr, {1: addr}, device_deps=False,
                                    warmup=False), log=lines.append)
    was = gc.get_threshold()

    async def scenario():
        run = asyncio.ensure_future(server.run())
        while not any(line.startswith("serving node") for line in lines):
            assert not run.done(), run
            await asyncio.sleep(0.01)
        await asyncio.sleep(0.01)
        serving = gc.get_threshold()
        await server._graceful_stop(None, None)
        await asyncio.wait_for(run, timeout=10.0)
        return serving

    serving = asyncio.run(scenario())
    assert serving == (was[0], was[1], collector.OLD_GENERATION_EVERY)
    assert gc.get_threshold() == was
