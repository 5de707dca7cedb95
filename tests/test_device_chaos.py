"""Device-plane chaos: seeded fault injection against the resolver's
dispatch/harvest pipeline (ops/fault_plane.py).

The hardening claims under test, end to end:

  * every corrupted readback is caught by the finalize checksum lane
    BEFORE decode (checksum_mismatches == corrupt injections, and the
    strict-serializability verifier sees no wrong deps);
  * stuck calls either complete late inside the watchdog's probe budget
    or trip it and answer host-side -- never wedge the pipeline;
  * the per-node health ladder quarantines a faulting node's device path,
    serves the countdown through the host differential path, and walks
    back to HEALTHY through probation canaries;
  * all handling is sim-timing-neutral: two chaos runs reconcile
    bit-identically, and the fault-free run of the same seed commits the
    SAME history (the injected-fault rng is forked unconditionally, so the
    streams align).

Fast subset runs in tier 1; the per-kind x protocol-flag matrix is
slow-marked (the `chaos` marker selects the whole family).
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np
import pytest

from accord_tpu.ops.fault_plane import (DEGRADED, FAULT_KINDS, HEALTHY,
                                        PROBATION, QUARANTINED,
                                        DeviceFaultPlane, DeviceHealth)
from accord_tpu.ops.kernels import csr_checksum, csr_checksum_host
from accord_tpu.ops.resolver import BatchDepsResolver
from accord_tpu.sim.burn import run_burn
from accord_tpu.sim.cluster import ClusterConfig
from accord_tpu.utils import faults
from accord_tpu.utils.rng import RandomSource

pytestmark = pytest.mark.chaos


# -- units: health ladder ----------------------------------------------------

def test_health_ladder_full_round_trip():
    """HEALTHY -> DEGRADED -> QUARANTINED -> (host countdown) -> PROBATION
    -> (canaries) -> HEALTHY, with every transition observed."""
    seen = []
    h = DeviceHealth(quarantine_after=2, recover_after=4,
                     quarantine_dispatches=3, probation_canaries=2,
                     on_transition=lambda old, new: seen.append((old, new)))
    h.on_fault("stuck")
    assert h.state == DEGRADED and not h.route_host
    h.on_fault("corrupt")
    assert h.state == QUARANTINED and h.route_host
    for _ in range(3):
        assert h.route_host
        h.on_host_dispatch()
    assert h.state == PROBATION and h.wants_canary
    h.canary_ok()
    assert h.state == PROBATION  # needs probation_canaries consecutive
    h.canary_ok()
    assert h.state == HEALTHY
    assert seen == [(HEALTHY, DEGRADED), (DEGRADED, QUARANTINED),
                    (QUARANTINED, PROBATION), (PROBATION, HEALTHY)]
    assert h.transitions == 4


def test_health_ladder_degraded_recovers_without_quarantine():
    """A single fault followed by enough clean harvests walks DEGRADED back
    to HEALTHY; consecutive-fault counting resets on a clean dispatch."""
    h = DeviceHealth(quarantine_after=2, recover_after=3)
    h.on_fault("dispatch_exc")
    assert h.state == DEGRADED
    h.on_clean_dispatch()          # resets the consecutive-fault count
    h.on_fault("dispatch_exc")     # so this is 1 again, not 2
    assert h.state == DEGRADED
    for _ in range(3):
        h.on_clean_dispatch()
    assert h.state == HEALTHY


def test_health_ladder_probation_fault_requarantines():
    h = DeviceHealth(quarantine_after=1, quarantine_dispatches=1,
                     probation_canaries=2)
    h.on_fault("stuck")
    assert h.state == QUARANTINED
    h.on_host_dispatch()
    assert h.state == PROBATION
    h.canary_ok()
    h.on_fault("corrupt")          # mid-probation fault: straight back
    assert h.state == QUARANTINED
    h.on_host_dispatch()
    assert h.state == PROBATION    # a full fresh countdown was served


# -- units: checksum lane ----------------------------------------------------

@pytest.mark.parametrize("lane", [0, 1], ids=["indptr", "dep_rows"])
def test_checksum_device_host_agree_and_catch_bit_flips(lane):
    """The jitted fold and its host twin agree exactly on the finalize
    kernels' result shapes (indptr i32[S+1], dep_rows i32[N]), the device's
    fold over a lane with a zero tail equals the host's over the words up
    to the total, and ANY single-bit flip in either covered array changes
    the sum."""
    rng = np.random.default_rng(5)
    indptr = np.cumsum(rng.integers(0, 5, 33)).astype(np.int32)
    rows = rng.integers(0, 1 << 20, int(indptr[-1])).astype(np.int32)
    dev = int(csr_checksum(jnp.asarray(indptr), jnp.asarray(rows)))
    host = csr_checksum_host(indptr, rows)
    assert dev == host
    tail = np.concatenate([rows, np.zeros(37, np.int32)])
    assert int(csr_checksum(jnp.asarray(indptr), jnp.asarray(tail))) == host
    for _ in range(8):
        clone = [np.array(indptr), np.array(rows)]
        flat = clone[lane].view(np.uint32)
        pos = int(rng.integers(flat.shape[0]))
        bit = int(rng.integers(32))
        flat[pos] ^= np.uint32(1) << np.uint32(bit)
        assert csr_checksum_host(*clone) != host, \
            f"flip at word {pos} bit {bit} not detected"


def _finalized_lane(program, dense, out_cap):
    """A real finalize_csr or range_finalize_csr result at small shapes, as
    the harvest fetches it: (indptr, dep_rows, bound, csum) host copies.
    `dense` inputs hold more dependencies than the 256 out-cap tier."""
    from accord_tpu.ops.encoding import WITNESS_TABLE
    from accord_tpu.ops.kernels import finalize_csr, range_finalize_csr
    rng = np.random.default_rng(11 if dense else 7)
    b, cap = 8, 256
    if program == "finalize_csr":
        w, kc, s = cap // 32, 16, 32
        bits = rng.random((b, w, 32)) < (0.6 if dense else 0.08)
        packed = np.packbits(bits, axis=-1, bitorder="little") \
            .view(np.uint32).reshape(b, w)
        kid = np.packbits(rng.random((kc, w, 32)) < 0.3, axis=-1,
                          bitorder="little").view(np.uint32).reshape(kc, w)
        out = finalize_csr(
            packed, np.int32(0), kid,
            rng.integers(0, b + 1, s).astype(np.int32),
            rng.integers(0, kc + 1, s).astype(np.int32),
            rng.integers(-1, cap, b).astype(np.int32), out_cap=out_cap)
    else:
        nv, rcap = 24, 64
        r_start = rng.integers(0, 60, rcap).astype(np.int32)
        iv_s = rng.integers(0, 60, nv).astype(np.int32)
        width = 40 if dense else 6
        out = range_finalize_csr(
            rng.integers(0, b, nv).astype(np.int32), iv_s,
            iv_s + rng.integers(1, width, nv).astype(np.int32),
            np.ones(nv, bool),
            np.stack([np.zeros(b), np.full(b, 90), np.zeros(b)],
                     axis=1).astype(np.int32),
            np.ones(b, np.int32), r_start,
            r_start + rng.integers(1, width, rcap).astype(np.int32),
            np.stack([np.zeros(rcap), rng.integers(0, 80, rcap),
                      np.arange(rcap)], axis=1).astype(np.int32),
            np.ones(rcap, np.int32), rng.random(rcap) < 0.9,
            np.asarray(WITNESS_TABLE), out_cap=out_cap)
    return tuple(np.asarray(a) for a in out)


@pytest.mark.parametrize("program", ["finalize_csr", "range_finalize_csr"])
@pytest.mark.parametrize("dense,out_cap", [(False, 256), (False, 2048),
                                           (True, 256)],
                         ids=["tier-256", "tier-2048", "overflow"])
def test_finalized_lane_checks_exactly_the_words_the_decode_reads(
        program, dense, out_cap):
    """On the kernels' real outputs: dep_rows is 0 past the total, the
    harvest's fold of indptr and dep_rows[:total] equals the device's
    checksum word, and a single-bit flip in any word it covers is caught
    (on overflow every dep_rows word is covered)."""
    from accord_tpu.ops.resolver import _checked_words
    indptr, dep_rows, bound, csum = _finalized_lane(program, dense, out_cap)
    total = int(indptr[-1])
    assert 0 < total <= int(bound)
    assert (total > out_cap) == dense, (total, out_cap)
    assert not dep_rows[total:].any()
    words = _checked_words((indptr, dep_rows, bound, csum))
    assert words[1].shape[0] == min(total, out_cap)
    assert csr_checksum_host(*words) == int(csum)
    rng = np.random.default_rng(out_cap + total)
    for lane, n in ((0, indptr.shape[0]), (1, words[1].shape[0])):
        for pos in range(n):
            clone = [np.array(indptr), np.array(dep_rows)]
            bit = int(rng.integers(32))
            clone[lane].view(np.uint32)[pos] ^= np.uint32(1) << np.uint32(bit)
            assert csr_checksum_host(*_checked_words(clone)) != int(csum), \
                f"lane {lane} word {pos} bit {bit} not detected"


def test_injected_corruption_lands_in_the_checked_words():
    """The fault plane's flip, drawn over `_checked_words` as the harvest
    draws it, always lands where the check sees it and never in the zero
    tail the decode does not read."""
    from accord_tpu.ops.resolver import _checked_words
    indptr, dep_rows, _, csum = _finalized_lane("finalize_csr", False, 2048)
    total = int(indptr[-1])
    plane = DeviceFaultPlane(RandomSource(3).fork(), corrupt_rate=1.0)
    for _ in range(64):
        arrs = (np.array(indptr), np.array(dep_rows))
        assert plane.corrupt_arrays(_checked_words(arrs))
        assert not arrs[1][total:].any()
        assert csr_checksum_host(*_checked_words(arrs)) != int(csum)
    assert plane.injected["corrupt"] == 64


def test_fault_plane_deterministic_and_exact_ledger():
    """Two planes over identically-seeded rngs draw the same schedule and
    flip the same bits; the injected ledger counts only APPLIED faults."""
    rates = dict(dispatch_exc_rate=0.2, stuck_rate=0.2, corrupt_rate=0.2,
                 overflow_rate=0.1)
    a = DeviceFaultPlane(RandomSource(99).fork(), **rates)
    b = DeviceFaultPlane(RandomSource(99).fork(), **rates)
    assert [a.draw() for _ in range(300)] == [b.draw() for _ in range(300)]
    bufs_a = [np.arange(16, dtype=np.int32), np.arange(8, dtype=np.int32)]
    bufs_b = [np.arange(16, dtype=np.int32), np.arange(8, dtype=np.int32)]
    assert a.corrupt_arrays(bufs_a) and b.corrupt_arrays(bufs_b)
    assert all(np.array_equal(x, y) for x, y in zip(bufs_a, bufs_b))
    assert a.injected["corrupt"] == 1
    assert not a.corrupt_arrays([np.empty(0, np.int32)])  # nothing to hit
    assert a.injected["corrupt"] == 1  # dropped draws are not counted


# -- burns: the fast tier-1 chaos leg ----------------------------------------

CHAOS_RATES = {"dispatch_exc_rate": 0.08, "stuck_rate": 0.08,
               "corrupt_rate": 0.08, "overflow_rate": 0.03}


def _chaos_leg(seed, ops, chaos, rates=None, **burn_kwargs):
    resolvers = []

    def factory():
        r = BatchDepsResolver(num_buckets=128)
        resolvers.append(r)
        return r

    cfg = ClusterConfig(deps_resolver_factory=factory,
                        deps_batch_window_ms=2.0, device_latency_ms=8.0)
    rep = run_burn(seed, ops=ops, key_count=8, concurrency=8,
                   write_ratio=0.7, device_chaos=chaos,
                   device_fault_rates=rates, collect_log=True, config=cfg,
                   **burn_kwargs)
    return rep, resolvers


def _agg(resolvers, name):
    return sum(getattr(r, name) for r in resolvers)


def test_chaos_burn_all_kinds_reconciles_and_matches_fault_free():
    """The tier-1 chaos gate: one contended burn with every fault kind
    armed. All four kinds fire and are handled (exact per-kind ledgers),
    the health ladder round-trips quarantine, two chaos runs are
    bit-identical, and the fault-free run of the same seed commits the
    same history -- injected faults are invisible to simulated state."""
    rep_a, res_a = _chaos_leg(31, 120, True, CHAOS_RATES)
    rep_b, _ = _chaos_leg(31, 120, True, CHAOS_RATES)
    rep_c, _ = _chaos_leg(31, 120, False)

    assert rep_a.lost == 0 and rep_a.failed == 0
    assert rep_a.log == rep_b.log, "chaos burn is not reconcile-identical"
    assert rep_a.log == rep_c.log, \
        "chaos history diverged from the fault-free run of the same seed"
    inj = rep_a.device_faults
    assert all(inj[k] > 0 for k in FAULT_KINDS), inj
    assert rep_c.device_faults is None
    # exact ledgers: every injection was consumed and counted once
    assert _agg(res_a, "device_faults_injected") == sum(inj.values())
    assert _agg(res_a, "checksum_mismatches") == inj["corrupt"]
    assert _agg(res_a, "device_watchdog_trips") > 0
    assert _agg(res_a, "device_retries") > 0
    # the ladder round-tripped: nodes were quarantined AND recovered
    assert _agg(res_a, "quarantine_entries") > 0
    assert _agg(res_a, "quarantine_exits") > 0
    assert _agg(res_a, "device_canaries") > 0
    assert _agg(res_a, "degraded_dispatches") > 0
    # finalize fallbacks under chaos are EXACTLY the handled injections
    # that abandon the compacted CSR -- caught corruptions plus consumed
    # overflow storms (each falls back to the legacy decode of the
    # uncorrupted raw candidate buffers); nothing else trips the guards
    assert _agg(res_a, "finalize_fallbacks") == inj["corrupt"] + inj["overflow"]


# -- slow matrix: isolated fault kinds x protocol fault flags -----------------

_KIND_RATE = {"dispatch_exc": "dispatch_exc_rate", "stuck": "stuck_rate",
              "corrupt": "corrupt_rate", "overflow": "overflow_rate"}


@pytest.mark.slow
@pytest.mark.parametrize("fast_path_disabled", [False, True])
@pytest.mark.parametrize("kind", FAULT_KINDS)
def test_fault_matrix_each_kind_isolated(kind, fast_path_disabled):
    """One fault kind at a time, with and without the protocol-level
    FAST_PATH_DISABLED flag: the kind fires, its specific handling ledger
    moves, no other kind's does, and the history still matches the
    fault-free leg under the same flag."""
    rates = {_KIND_RATE[kind]: 0.12}
    with faults.scoped(FAST_PATH_DISABLED=fast_path_disabled):
        rep, res = _chaos_leg(47, 100, True, rates)
        rep_clean, _ = _chaos_leg(47, 100, False)
    assert rep.lost == 0
    assert rep.log == rep_clean.log
    inj = rep.device_faults
    assert inj[kind] > 0, inj
    assert all(v == 0 for k, v in inj.items() if k != kind), inj
    assert _agg(res, "device_faults_injected") == inj[kind]
    assert _agg(res, "checksum_mismatches") == \
        (inj["corrupt"] if kind == "corrupt" else 0)
    if kind == "stuck":
        assert _agg(res, "device_retries") > 0
    if kind == "dispatch_exc":
        assert _agg(res, "device_retries") > 0
