"""Sharded protocol megakernel tests: parallel/mesh.sharded_protocol_tick
(one shard_map program per cluster tick) against the single-device
megakernel and the per-node host loop.

conftest.py forces a virtual 8-device CPU mesh, so every test here runs
the genuinely sharded lowering (data=4, model=2) in-process. The contract
is the megakernel's, extended across shards: bit-identical committed
histories, exactly one launch per dispatching tick, and the cross-shard
mailbox hop (lax.all_to_all over 'data') landing every payload on its
destination shard's ring.

Tier-1 budget note: the full tier-1 suite runs within ~2% of its hard
timeout on the reference box, so only the compile-free unit tests ride
tier 1 here; every differential that compiles a sharded program is
marked slow. Run the whole module (no -m filter) for the multichip
smoke: nothing else gates this contract.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from accord_tpu.ops.encoding import WITNESS_TABLE
from accord_tpu.ops.kernels import protocol_tick
from accord_tpu.ops.mailbox import MailboxPlane
from accord_tpu.parallel.mesh import (make_mesh, mesh_supports_message_plane,
                                      sharded_protocol_tick)
from accord_tpu.sim.mesh_burn import run_mesh_burn
from accord_tpu.sim.network import _MailMsg

pytestmark = pytest.mark.sharded_megakernel


@pytest.fixture(scope="module")
def mesh():
    m = make_mesh()
    assert len(jax.devices()) >= 8, "conftest should force 8 virtual devices"
    assert m.shape["data"] > 1, "mesh must actually shard the node axis"
    return m


def _gate_fused(counters):
    assert counters["megakernel_dispatches"] > 0
    assert counters["launches_per_tick"] == 1.0
    assert counters["sharded_megakernel_fallbacks"] == 0


# -- compile-free units (tier 1) ----------------------------------------------

def test_mesh_reports_message_plane_support(mesh):
    assert mesh_supports_message_plane(mesh)


def test_mailbox_sharded_staging_layout():
    """The sharded emit-lane layout, host side only: lanes grouped by
    (src shard, dst shard) at segment (s*S+t)*bcap, each entry's return
    position receiver-major at (t*S+s)*bcap + j, node v owning rows on
    shard v // npsh -- and the shards=1 layout degenerating to the flat
    staging order."""
    rng = np.random.default_rng(4)
    n, S = 6, 4

    def mk_entries():
        ents = []
        for i in range(12):
            e = _MailMsg(kind=1, src=int(rng.integers(1, n + 1)),
                         dst=int(rng.integers(1, n + 1)),
                         payload=bytes([i]) * 8)
            e.ticket = i
            ents.append(e)
        return ents

    state = rng.bit_generator.state
    ents = mk_entries()
    p = MailboxPlane(n, depth=8, words=16, shards=S)
    assert p.npsh == 2 and p.rows_nodes == 8
    out = p.stage_batch(ents)
    assert out is not None
    e_src, e_dst, e_keep = (np.asarray(out[2]), np.asarray(out[3]),
                            np.asarray(out[5]))
    bcap = len(e_src) // (S * S)
    for e in ents:
        _batch, pos, dst, idx = e.slot
        s, t = e.src // p.npsh, e.dst // p.npsh
        # send position lives in segment (s, t); the return position is
        # the same lane index in the receiver-major segment (t, s)
        j = pos - (t * S + s) * bcap
        assert 0 <= j < bcap
        send = (s * S + t) * bcap + j
        assert e_keep[send]
        assert e_src[send] == e.src and e_dst[send] == e.dst
        assert dst == e.dst
    # every kept lane sits inside its group's segment
    for pos in np.flatnonzero(e_keep):
        s, t = e_src[pos] // p.npsh, e_dst[pos] // p.npsh
        assert (s * S + t) * bcap <= pos < (s * S + t) * bcap + bcap

    # shards=1: one group, positions are exactly the staging order
    rng.bit_generator.state = state
    ents1 = mk_entries()
    p1 = MailboxPlane(n, depth=8, words=16, shards=1)
    assert p1.npsh == n + 1 and p1.rows_nodes == n + 1
    p1.stage_batch(ents1)
    for j, e in enumerate(ents1):
        assert e.slot[1] == j


# -- tick-level differentials (sharded program vs single-device program) ------

@pytest.mark.slow
def test_sharded_tick_key_finalize_matches_single_device(mesh):
    """Key resolve + two finalize-CSR compactions on different store spans:
    the sharded program's packed bitmap and CSR outputs must equal the
    single-device protocol_tick's bit for bit."""
    data = mesh.shape["data"]
    table = jnp.asarray(WITNESS_TABLE)
    rng = np.random.default_rng(1)
    cap = 32 * data * 2
    K = 8 * mesh.shape["model"]
    b, z, ns, kc, oc = 16, 32, 2, 8, 64
    w = cap // 32
    arenas = tuple(
        (jnp.asarray((rng.random((cap, K)) < 0.1).astype(np.float32)),
         jnp.asarray(rng.integers(0, 100, (cap, 3)).astype(np.int32)),
         jnp.asarray(rng.integers(0, 6, cap).astype(np.int32)),
         jnp.asarray(rng.random(cap) < 0.9)) for _ in range(ns))
    sof = rng.integers(0, b, z).astype(np.int32)
    sk = rng.integers(0, K, z).astype(np.int32)
    sst = rng.integers(0, ns, b).astype(np.int32)
    sb = rng.integers(50, 150, (b, 3)).astype(np.int32)
    sknd = rng.integers(0, 6, b).astype(np.int32)
    slots = np.arange(ns, dtype=np.int32)
    key_in = tuple(map(jnp.asarray, (sof, sk, sst, sb, sknd, slots))) \
        + (arenas,)
    kid_rows = jnp.asarray(
        rng.integers(0, 2**32, (kc, w), dtype=np.uint64).astype(np.uint32))
    j_subj = jnp.asarray(rng.integers(0, b, 12).astype(np.int32))
    j_kid = jnp.asarray(rng.integers(0, kc, 12).astype(np.int32))
    j_srow = jnp.asarray(rng.integers(-1, cap, b).astype(np.int32))
    fins = (("key", 0, 0, b, w, 0, kid_rows, j_subj, j_kid, j_srow, oc),
            ("key", 0, w, b, w, 0, kid_rows, j_subj, j_kid, j_srow, oc))
    ref = protocol_tick(table, key_in=key_in, fins=fins)
    got = sharded_protocol_tick(mesh, table, key_in=key_in, fins=fins)
    np.testing.assert_array_equal(np.asarray(ref[0]), np.asarray(got[0]))
    for fr, fg in zip(ref[2], got[2]):
        for a, c in zip(fr, fg):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(c))


@pytest.mark.slow
def test_sharded_tick_range_resolve_matches_single_device(mesh):
    data = mesh.shape["data"]
    table = jnp.asarray(WITNESS_TABLE)
    rng = np.random.default_rng(2)
    cap = 32 * data * 2
    K = 8 * mesh.shape["model"]
    b, z, ns = 16, 32, 2
    arenas = tuple(
        (jnp.asarray((rng.random((cap, K)) < 0.1).astype(np.float32)),
         jnp.asarray(rng.integers(0, 100, (cap, 3)).astype(np.int32)),
         jnp.asarray(rng.integers(0, 6, cap).astype(np.int32)),
         jnp.asarray(rng.random(cap) < 0.9)) for _ in range(ns))
    rcap = max(64, 32 * data)
    nrs = 2
    rars = tuple(
        (jnp.asarray(rng.integers(0, 50, rcap).astype(np.int32)),
         jnp.asarray(rng.integers(50, 100, rcap).astype(np.int32)),
         jnp.asarray(rng.integers(0, 100, (rcap, 3)).astype(np.int32)),
         jnp.asarray(rng.integers(0, 6, rcap).astype(np.int32)),
         jnp.asarray(rng.random(rcap) < 0.9)) for _ in range(nrs))
    sst = rng.integers(0, ns, b).astype(np.int32)
    sb = rng.integers(50, 150, (b, 3)).astype(np.int32)
    sknd = rng.integers(0, 6, b).astype(np.int32)
    slots = np.arange(ns, dtype=np.int32)
    iv_of = rng.integers(0, b, z).astype(np.int32)
    iv_s = rng.integers(0, 80, z).astype(np.int32)
    iv_e = iv_s + rng.integers(1, 20, z).astype(np.int32)
    srng = rng.random(b) < 0.5
    rng_in = (tuple(map(jnp.asarray,
                        (iv_of, iv_s, iv_e, sst, sb, sknd, srng)))
              + (jnp.asarray(slots[:nrs]), rars,
                 jnp.asarray(slots), arenas))
    ref = protocol_tick(table, rng_in=rng_in)
    got = sharded_protocol_tick(mesh, table, rng_in=rng_in)
    for a, c in zip(ref[1], got[1]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(c))


@pytest.mark.slow
def test_mailbox_cross_shard_parity(mesh):
    """The same staged entries routed through the shards=1 single-device
    layout and the shards=data sharded layout must land identically --
    including a partition whose endpoints live on DIFFERENT shards."""
    data = mesh.shape["data"]
    table = jnp.asarray(WITNESS_TABLE)
    rng = np.random.default_rng(3)
    n = 6

    def mk_entries():
        ents = []
        for i in range(24):
            src = int(rng.integers(1, n + 1))
            dst = int(rng.integers(1, n + 1))
            e = _MailMsg(kind=1 + i % 3, src=src, dst=dst,
                         payload=bytes(
                             rng.integers(0, 256, 20).astype(np.uint8)))
            e.ticket = i
            ents.append(e)
        return ents

    state = rng.bit_generator.state
    ents1 = mk_entries()
    rng.bit_generator.state = state
    ents_s = mk_entries()
    # nodes 1 and 4 land on different shards (npsh = ceil(7/4) = 2)
    parts = {frozenset((1, 4))}

    p1 = MailboxPlane(n, depth=8, words=16, shards=1)
    p1.set_partitions(parts, version=1)
    p1.adopt(protocol_tick(table, mailbox=p1.stage_batch(ents1))[5])

    ps = MailboxPlane(n, depth=8, words=16, shards=data)
    ps.set_partitions(parts, version=1)
    ps.adopt(sharded_protocol_tick(
        mesh, table, mailbox=ps.stage_batch(ents_s))[5])

    for e1, es in zip(ents1, ents_s):
        r1, rs = p1.read_landed(e1), ps.read_landed(es)
        assert r1 == rs, (e1.src, e1.dst)
        if frozenset((e1.src, e1.dst)) == frozenset((1, 4)):
            assert r1 is None
        else:
            assert r1 == e1.payload


# -- burn differentials (sharded engine vs single-device vs host loop) --------

@pytest.mark.slow
def test_sharded_burn_matches_single_device_and_host():
    kw = dict(ops=30, nodes=3, collect_log=True)
    host, _ = run_mesh_burn(5, megakernel=False, mesh_tick=False, **kw)
    single, _ = run_mesh_burn(5, megakernel=True, **kw)
    sh, _ = run_mesh_burn(5, megakernel=True, sharded=True, **kw)
    assert host.log == single.log
    assert host.log == sh.log
    _gate_fused(sh.counters)


@pytest.mark.slow
def test_sharded_burn_range_traffic():
    kw = dict(ops=25, nodes=3, range_read_ratio=0.3,
              range_write_ratio=0.2, collect_log=True)
    loop, _ = run_mesh_burn(9, megakernel=False, mesh_tick=False, **kw)
    sh, _ = run_mesh_burn(9, megakernel=True, sharded=True, **kw)
    assert loop.log == sh.log
    _gate_fused(sh.counters)


@pytest.mark.slow
def test_sharded_device_messages_match_host():
    kw = dict(ops=30, nodes=3, megakernel=True, collect_log=True)
    host, _ = run_mesh_burn(5, **kw)
    dev, _ = run_mesh_burn(5, device_messages=True, sharded=True, **kw)
    assert host.log == dev.log
    c = dev.counters
    _gate_fused(c)
    assert c["device_messages_delivered"] > 0
    assert c["mailbox_verify_fallbacks"] == 0
    assert c["mailbox_overflow_spills"] == 0


@pytest.mark.slow
def test_sharded_chaos_crash_restart_parity():
    """Seeded drops + partitions (masks spanning shard boundaries) +
    crash/restart must stay bit-identical through the sharded plane."""
    kw = dict(ops=30, nodes=4, megakernel=True, collect_log=True,
              chaos_drop=0.05, chaos_partitions=True, crash_restart=True)
    host, _ = run_mesh_burn(23, **kw)
    dev, _ = run_mesh_burn(23, device_messages=True, sharded=True, **kw)
    assert host.log == dev.log
    assert dev.counters["mailbox_verify_fallbacks"] == 0


@pytest.mark.slow
def test_tiny_ring_spills_degrade_not_diverge():
    """A 2-slot ring cannot hold the traffic: entries spill to the host
    path (counted) and the committed history must not move."""
    kw = dict(ops=25, nodes=3, megakernel=True, collect_log=True,
              mailbox_depth=2, mailbox_words=16)
    host, _ = run_mesh_burn(5, **kw)
    dev, _ = run_mesh_burn(5, device_messages=True, sharded=True, **kw)
    assert host.log == dev.log
    c = dev.counters
    assert c["mailbox_overflow_spills"] > 0
    assert c["mailbox_verify_fallbacks"] == 0


# -- slow legs ----------------------------------------------------------------

@pytest.mark.slow
def test_sharded_chaos_seed_sweep():
    kw = dict(ops=40, nodes=4, megakernel=True, collect_log=True,
              chaos_drop=0.05, chaos_partitions=True)
    for seed in (7, 8, 9, 10):
        host, _ = run_mesh_burn(seed, **kw)
        dev, _ = run_mesh_burn(seed, device_messages=True, sharded=True,
                               **kw)
        assert host.log == dev.log, f"seed {seed} diverged"
        assert dev.counters["mailbox_verify_fallbacks"] == 0


@pytest.mark.slow
def test_sharded_reconcile_64_nodes():
    """The --reconcile contract at cluster scale: two same-seed sharded
    megakernel burns are bit-identical, and match the per-node loop."""
    kw = dict(ops=40, nodes=64, rf=5, collect_log=True)
    a, _ = run_mesh_burn(11, megakernel=True, sharded=True, **kw)
    b, _ = run_mesh_burn(11, megakernel=True, sharded=True, **kw)
    assert a.log == b.log, "sharded megakernel burn is non-deterministic"
    loop, _ = run_mesh_burn(11, megakernel=False, mesh_tick=False, **kw)
    assert a.log == loop.log
    _gate_fused(a.counters)
