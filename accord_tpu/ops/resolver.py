"""The DepsResolver SPI and its implementations.

The reference computes deps per-request inside each CommandStore via
hand-tuned scans (SafeCommandStore.mapReduceActive ->
CommandsForKey.mapReduceActive, local/cfk/CommandsForKey.java:910). Here that
query is an SPI:

  HostDepsResolver  -- delegates to the store's Python scan (reference
                       behaviour, used for differential testing)
  BatchDepsResolver -- maintains an incremental DEVICE ARENA per STORE
                       (mirroring the reference's shard-per-CommandStore
                       layout) and answers the whole node tick's deps queries
                       -- across ALL of the node's stores -- with ONE fused
                       MXU kernel call, fully asynchronously.

Why the shape of this design:
  - a kernel launch returns before the device finishes, while a synchronous
    device->host readback stalls the protocol thread for the whole call, so
    every result rides an ASYNC copy harvested one tick later (what a
    launch, a readback and an upload cost on a local chip: not measured);
  - uploads and readbacks are kept small: the arena is maintained by
    scattering a variable-width CSR of KEY INDICES (flat i32[nnz]) and
    rebuilding bitmap rows on device, and results come back BIT-PACKED
    (u32[B, cap/32], 8x smaller than a boolean matrix and independent of how
    many deps each subject has).

Range txns live in a SECOND device mirror (_RangeArena): active ranges as
sorted-endpoint int32 pairs, one row per (txn, interval). Every dispatch that
touches range state also runs the fused range kernel -- key subjects stab the
interval rows with point intervals, range subjects overlap both the interval
rows and the key arena's bucket bitmaps (covered-bucket contraction on the
MXU) -- so range-domain subjects ride the same dispatch/harvest pipeline and
the old per-harvest host scans are retired. Decode stays exact: candidate rows translate to txn ids
and are re-filtered host-side per real key/range before entering the Deps.

Async protocol (deterministic, overlapped): a node tick drains every store's
queued PreAccepts/deps queries, runs the host-side preaccept transitions
(witness timestamps come from the O(1) host MaxConflicts map), and dispatches
ONE FUSED CROSS-STORE kernel call per max_dispatch slice (enqueue +
copy_to_host_async -- no blocking): every participating store's arena lanes
enter the same call as a tuple block, a store-id lane routes each subject to
its own store's rows, and the per-store word spans of the concatenated packed
result (the row-offset table, recorded per _Group at encode time) route the
readback to each store's decode. Generation pinning stays PER STORE, so one
store compacting mid-flight never invalidates a batchmate's rows. Each call
appends to the node's IN-ORDER in-flight queue. Three
stages then overlap in real time: host-encode of call N+1 (the next tick),
device-execute of call N, and host-decode of call N-1 (its harvest event).
Between dispatch and harvest a cheap deterministic POLL (sim/scheduler.py
poll()) prefetches transfers the device has already finished via the
non-blocking `is_ready()` probe, so the harvest's blocking read is the
exception (pipeline shallower than the link latency), not the rule. Harvest
events still fire at the deterministic `device_latency_ms` offset and polls
mutate only host-side caches invisible to simulated state, so runs remain
bit-for-bit deterministic. Compaction while calls are in flight pins the
retiring row->txn snapshot; the harvest translates its packed rows to the
new mapping instead of falling back to the host scan.
"""
from __future__ import annotations

import functools
import logging
import threading
import time
from collections import deque
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from accord_tpu.local.cfk import CfkStatus
from accord_tpu.obs.metrics import MetricsRegistry, RegCounter, RegTimer
from accord_tpu.obs.trace import (REC, Occupancy, node_pid, node_ts, phase,
                                  watch_collector)
from accord_tpu.ops.encoding import (TimestampEncoder, WITNESS_TABLE,
                                     encode_interval,
                                     encode_key_point_intervals,
                                     encode_seekable_intervals)
from accord_tpu.primitives.deps import (Deps, KeyDeps, KeyDepsBuilder,
                                        RangeDeps, RangeDepsBuilder)
from accord_tpu.primitives.keyspace import (Keys, Range, Ranges, Seekables,
                                            _Successor)
from accord_tpu.primitives.timestamp import Timestamp, TxnId
from accord_tpu.utils.async_ import AsyncResult, success
from accord_tpu.utils.invariants import Invariants


logger = logging.getLogger(__name__)

# rows a range arena's row_memo holds before it starts over
_ROW_MEMO_CAP = 1 << 18

_EMPTY_I32 = np.empty(0, dtype=np.int32)
_EMPTY_I64 = np.empty(0, dtype=np.int64)

# rents-key sentinel marking a range subject's OWN interval pieces in the
# range-finalize entry table: the hit segment decodes as range-vs-range deps
# (intersection with the subject's owned ranges) instead of key-point deps
_RSUB = object()


def _unpack_row(prow: np.ndarray) -> np.ndarray:
    """One subject's packed u32 result row -> int64 arena row indices."""
    wnz = np.nonzero(prow)[0]
    if wnz.size == 0:
        return _EMPTY_I64
    sub = np.unpackbits(prow[wnz].astype("<u4").view(np.uint8),
                        bitorder="little").reshape(wnz.size, 32)
    rr, cc = np.nonzero(sub)
    return (wnz[rr].astype(np.int64) << 5) | cc


def _endpoint_code(p) -> int:
    """A range endpoint of the integer key domain as one order-preserving
    int: key k -> 2k, _Successor(k) -> 2k + 1, so `[k, k+)` (a key txn
    inside a range subject) and `[k, k+1)` (an intersection one key wide)
    stay two rows and sort as Range orders them."""
    if isinstance(p, _Successor):
        return 2 * int(p.key) + 1
    return 2 * int(p)


def _code_endpoint(c: int):
    return _Successor(c >> 1) if c & 1 else c >> 1


class DepsResolver:
    def resolve_one(self, store, txn_id: TxnId, seekables: Seekables,
                    before: Timestamp) -> Deps:
        raise NotImplementedError

    def on_register(self, store, txn_id: TxnId, keys, status: CfkStatus,
                    witnessed_at: Timestamp) -> None:
        """Observer hook: the store reports every conflict-registry update."""

    def max_conflict(self, store, txn_id: TxnId,
                     seekables: Seekables) -> Tuple[bool, Optional[Timestamp]]:
        """Optional device path for the max-conflict query; (False, _) means
        unsupported here -- ask the host scan."""
        return False, None

    def on_truncate(self, store, txn_id: TxnId) -> None:
        """Observer hook: the store truncated this txn's local record."""

    def on_prune(self, store, txn_id: TxnId, keys) -> None:
        """Observer hook: the store pruned this txn from `keys`' conflict
        registries (its ordering is subsumed by the injected floor dep)."""


class HostDepsResolver(DepsResolver):
    def resolve_one(self, store, txn_id, seekables, before) -> Deps:
        return store.host_calculate_deps(txn_id, seekables, before)


def warmup(num_buckets: int = 1024, cap: int = 8192,
           batch_tiers=(8, 64, 128), scatter_tiers=(8, 64),
           nnz_tiers=None, scatter_nnz_tiers=None,
           range_cap: int = 64, store_tiers=(1, 2),
           exec_caps=(), out_tiers=(), range_out_tiers=None,
           kid_cap: int = 4096, cmd_caps=(), cmd_key_caps=(1024,),
           cmd_kpad: int = 4, cmd_op_tiers=None,
           cmd_promote_modes=(False,),
           node_tiers=(), node_batch_tiers=None,
           mega_quorum_sizes=(), mega_lane_tiers=None,
           exec_tiers=(), recovery_tiers=()) -> None:
    """Pre-compile the jit shape tiers the async pipeline uses (a first
    compilation costs seconds, so a serving process does this at start).
    The jit cache is process-global, so one call
    covers every resolver with the same (num_buckets, cap, range_cap).

    The CSR encoding makes each kernel's shape a (batch tier, nnz tier)
    PAIR, and the fused cross-store kernels add a third axis: the
    participating-store count (`store_tiers` -- jit specializes on the
    arena-tuple structure; the staged pipeline dispatches the same tiers one
    tick later, so encode-ahead adds no new shapes). Warmup compiles the
    cross product -- a handful of variants, bounded by the deliberately
    short tier ladders in ops/kernels.py. The benchmark holds compile
    requests inside its timed windows at zero against exactly this coverage
    (compile_requests_in_window), including the field-granular delta scatters
    (arena_scatter_keys and the single-lane scatter_rows used by ts-only /
    valid-only updates) and the arena_copy a sync starts with where its
    lanes were handed out. `exec_caps` additionally warms the exec_plane's
    per-field lane deltas (exec-ts / applied / pending rows) for each
    execution-arena capacity in use. `out_tiers` (opt-in: it multiplies the
    cross product) warms the finalized-CSR harvest kernels -- finalize_csr
    across (batch, slot-nnz, store, out_cap) tiers at (`kid_cap`, cap/32)
    kid-table shape, range_finalize_csr across (nnz, batch, out_cap), and
    the kid-table word scatter per scatter-nnz tier. `range_out_tiers`
    overrides the range kernel's out ladder (pass () for key-only
    workloads, where compiling the range compaction would be waste).
    `cmd_caps` (opt-in) additionally warms the device coordination plane:
    cmd_tick and its lane scatters across every (arena cap, key cap,
    op tier, promote mode) in use -- the same coverage
    ops.cmd_plane.warmup_cmd_plane provides standalone, folded in here so
    one warmup call covers deps + exec + cmd kernels. `node_tiers` (opt-in)
    warms the cluster-tick node-lane kernels (ops/node_lane.py) across
    every (block-count tier x merged-row tier x nnz tier): with resolvers
    built at `pad_node_tiers` matching, node-count churn (crashes,
    membership change) then pads to pre-compiled shapes and causes zero
    steady-state recompiles. `node_batch_tiers` overrides the merged-row
    ladder (default: the first NODE_SUBJECT_TIERS rungs); the span demux
    (`lane_slice`) pads its word width to the node-block tiers
    (node_lane.build_key_merge), so it sits under the same strict
    zero-recompile gates as every other tick kernel. `mega_quorum_sizes`
    (opt-in) warms the protocol megakernel's quorum-only variants
    (kernels.protocol_tick) across `mega_lane_tiers` (default: the first
    MEGA_LANE_TIERS rungs) for each electorate majority in use; the full
    fused programs key on per-tick finalize signatures and warm on a
    caller's own warm pass instead. `exec_tiers` (opt-in) warms the
    compacted execution-frontier harvest (kernels.frontier_compact) across
    (exec cap x plane count x out_cap) -- plane counts follow `store_tiers`
    plus the solo plane -- and the engine's exec-only fused flush
    (protocol_tick with only exec blocks), so OutCapTiers cap churn mints
    zero recompiles. `recovery_tiers` likewise warms kernels.recovery_scan
    across every (cmd arena cap x out_cap) the progress sweeps query."""
    import jax.numpy as jnp
    from accord_tpu.ops.kernels import (NNZ_TIERS, SCATTER_NNZ_TIERS,
                                        arena_copy, arena_scatter,
                                        arena_scatter_keys,
                                        deps_resolve, fused_deps_resolve,
                                        fused_range_deps_resolve,
                                        range_deps_resolve, range_scatter,
                                        scatter_rows)
    if nnz_tiers is None:
        nnz_tiers = NNZ_TIERS
    if scatter_nnz_tiers is None:
        scatter_nnz_tiers = SCATTER_NNZ_TIERS
    neg = np.iinfo(np.int32).min
    bm = jnp.zeros((cap, num_buckets), jnp.float32)
    ts = jnp.zeros((cap, 3), jnp.int32)
    ex = jnp.full((cap, 3), neg, jnp.int32)
    kd = jnp.zeros(cap, jnp.int32)
    vl = jnp.zeros(cap, bool)
    rs = jnp.zeros(range_cap, jnp.int32)
    re_ = jnp.zeros(range_cap, jnp.int32)
    rts = jnp.zeros((range_cap, 3), jnp.int32)
    rkd = jnp.zeros(range_cap, jnp.int32)
    rvl = jnp.zeros(range_cap, bool)
    table = jnp.asarray(WITNESS_TABLE)
    out = None
    if scatter_tiers:
        # a sync's first step where its lanes were handed out: a copy of
        # all five (whole rows) or of the bitmap alone (key sets)
        bm, ts, ex, kd, vl = arena_copy(bm, ts, ex, kd, vl)
        bm, = arena_copy(bm)
    for m in scatter_tiers:
        for z in scatter_nnz_tiers:
            # the arena's scatters donate the lanes they rewrite: thread
            # the outputs through, the later programs here read them
            bm, ts, ex, kd, vl = arena_scatter(
                bm, ts, ex, kd, vl, jnp.zeros(m, jnp.int32),
                jnp.full(z, cap, jnp.int32), jnp.zeros(z, jnp.int32),
                jnp.zeros((m, 3), jnp.int32), jnp.zeros((m, 3), jnp.int32),
                jnp.zeros(m, jnp.int32), jnp.zeros(m, bool))
            out = bm = arena_scatter_keys(
                bm, jnp.zeros(m, jnp.int32),
                jnp.full(z, cap, jnp.int32), jnp.zeros(z, jnp.int32))
        out = range_scatter(
            rs, re_, rts, rkd, rvl, jnp.zeros(m, jnp.int32),
            jnp.zeros(m, jnp.int32), jnp.zeros(m, jnp.int32),
            jnp.zeros((m, 3), jnp.int32), jnp.zeros(m, jnp.int32),
            jnp.zeros(m, bool))
        # the field-granular single-lane deltas: exec-ts bumps, key-arena
        # valid flips, range-arena valid flips
        out = scatter_rows(ex, jnp.zeros(m, jnp.int32),
                           jnp.zeros((m, 3), jnp.int32))
        out = scatter_rows(vl, jnp.zeros(m, jnp.int32), jnp.zeros(m, bool))
        out = scatter_rows(rvl, jnp.zeros(m, jnp.int32), jnp.zeros(m, bool))
        # the exec_plane's per-field lane deltas share scatter_rows; its
        # arena capacity differs from the resolver's, so warm each in use
        for ecap in exec_caps:
            ets = jnp.full((ecap, 3), neg, jnp.int32)
            eflag = jnp.zeros(ecap, bool)
            out = scatter_rows(ets, jnp.zeros(m, jnp.int32),
                               jnp.zeros((m, 3), jnp.int32))
            out = scatter_rows(eflag, jnp.zeros(m, jnp.int32),
                               jnp.zeros(m, bool))
    for b in batch_tiers:
        sb = jnp.zeros((b, 3), jnp.int32)
        sknd = jnp.zeros(b, jnp.int32)
        srng = jnp.zeros(b, bool)
        sst = jnp.zeros(b, jnp.int32)
        for z in nnz_tiers:
            of = jnp.full(z, b, jnp.int32)
            zz = jnp.zeros(z, jnp.int32)
            out = deps_resolve(of, zz, sb, sknd, bm, ts, kd, vl, table)
            out = range_deps_resolve(of, zz, zz, sb, sknd, srng,
                                     rs, re_, rts, rkd, rvl,
                                     bm, ts, kd, vl, table)
            for s in store_tiers:
                if s < 2:
                    continue  # single-group dispatches use the plain kernels
                slots = jnp.arange(s, dtype=jnp.int32)
                arenas = tuple((bm, ts, kd, vl) for _ in range(s))
                out = fused_deps_resolve(of, zz, sst, sb, sknd, slots,
                                         arenas, table)
                rarenas = tuple((rs, re_, rts, rkd, rvl) for _ in range(s))
                karenas = tuple((bm, ts, kd, vl) for _ in range(s))
                out = fused_range_deps_resolve(of, zz, zz, sst, sb, sknd,
                                               srng, slots, rarenas, slots,
                                               karenas, table)
    if out_tiers:
        from accord_tpu.ops.kernels import (finalize_csr, kid_word_scatter,
                                            range_finalize_csr)
        w = cap // 32
        kid_rows, = arena_copy(jnp.zeros((kid_cap, w), jnp.uint32))
        for z in scatter_nnz_tiers:
            out = kid_rows = kid_word_scatter(
                kid_rows, jnp.full(z, kid_cap, jnp.int32),
                jnp.zeros(z, jnp.int32), jnp.zeros(z, jnp.uint32))
        zero_off = jnp.asarray(0, jnp.int32)
        for b in batch_tiers:
            sb = jnp.zeros((b, 3), jnp.int32)
            sknd = jnp.zeros(b, jnp.int32)
            srow = jnp.full(b, -1, jnp.int32)
            for s in store_tiers:
                packed = jnp.zeros((b, max(s, 1) * w), jnp.uint32)
                for z in nnz_tiers:
                    subj = jnp.full(z, b, jnp.int32)
                    kidx = jnp.full(z, kid_cap, jnp.int32)
                    for oc in out_tiers:
                        out = finalize_csr(packed, zero_off, kid_rows,
                                           subj, kidx, srow, out_cap=oc)
            for z in nnz_tiers:
                of = jnp.full(z, b, jnp.int32)
                zz = jnp.zeros(z, jnp.int32)
                ok = jnp.zeros(z, bool)
                for oc in (out_tiers if range_out_tiers is None
                           else range_out_tiers):
                    out = range_finalize_csr(of, zz, zz, ok, sb, sknd,
                                             rs, re_, rts, rkd, rvl,
                                             table, out_cap=oc)
    if cmd_caps:
        from accord_tpu.ops.cmd_plane import (CMD_OP_TIERS,
                                              warmup_cmd_plane)
        warmup_cmd_plane(
            caps=tuple(cmd_caps), key_caps=tuple(cmd_key_caps),
            kpad=cmd_kpad,
            op_tiers=(CMD_OP_TIERS if cmd_op_tiers is None
                      else tuple(cmd_op_tiers)),
            promote_modes=tuple(cmd_promote_modes))
    if node_tiers:
        from accord_tpu.ops.node_lane import (NODE_SUBJECT_TIERS,
                                              node_fused_deps_resolve,
                                              node_fused_range_deps_resolve)
        nb_tiers = (tuple(node_batch_tiers) if node_batch_tiers is not None
                    else NODE_SUBJECT_TIERS[:2])
        for nblk in node_tiers:
            slots = jnp.arange(nblk, dtype=jnp.int32)
            arenas = tuple((bm, ts, kd, vl) for _ in range(nblk))
            rarenas = tuple((rs, re_, rts, rkd, rvl) for _ in range(nblk))
            for b in nb_tiers:
                sb = jnp.zeros((b, 3), jnp.int32)
                sknd = jnp.zeros(b, jnp.int32)
                srng = jnp.zeros(b, bool)
                snode = jnp.zeros(b, jnp.int32)
                for z in nnz_tiers:
                    of = jnp.full(z, b, jnp.int32)
                    zz = jnp.zeros(z, jnp.int32)
                    out = node_fused_deps_resolve(of, zz, snode, sb, sknd,
                                                  slots, arenas, table)
                    out = node_fused_range_deps_resolve(
                        of, zz, zz, snode, sb, sknd, srng, slots, rarenas,
                        slots, arenas, table)
    if mega_quorum_sizes:
        from accord_tpu.ops.kernels import protocol_tick
        from accord_tpu.ops.tiers import MEGA_LANE_TIERS
        lt = (tuple(mega_lane_tiers) if mega_lane_tiers is not None
              else MEGA_LANE_TIERS[:2])
        for qs in mega_quorum_sizes:
            for t in lt:
                out = protocol_tick(
                    table,
                    quorum=(jnp.zeros((t, 3), jnp.int32),
                            jnp.zeros((t, 3), jnp.int32),
                            jnp.zeros(t, jnp.int32),
                            jnp.zeros(t, bool)),
                    quorum_size=qs)[4][2]
    if exec_tiers:
        from accord_tpu.ops.kernels import frontier_compact, protocol_tick
        for ecap in (tuple(exec_caps) or (1024,)):
            plane = (jnp.zeros((ecap, ecap), bool),
                     jnp.full((ecap, 3), neg, jnp.int32),
                     jnp.zeros(ecap, bool), jnp.zeros(ecap, bool),
                     jnp.zeros(ecap, bool))
            counts = (1,) + tuple(s for s in store_tiers if s > 1)
            for n in counts:
                planes = tuple(plane for _ in range(n))
                for oc in exec_tiers:
                    out = frontier_compact(planes, out_cap=oc)[0]
                    out = protocol_tick(table,
                                        execs=((planes, oc),))[7][0][0]
    if recovery_tiers:
        from accord_tpu.ops.kernels import recovery_scan
        for ccap in (tuple(cmd_caps) or (1024,)):
            st = jnp.zeros(ccap, jnp.int32)
            tm = jnp.zeros(ccap, jnp.int32)
            for oc in recovery_tiers:
                out = recovery_scan(st, tm, np.int32(0), np.int32(0),
                                    out_cap=oc)[0]
    if out is not None:
        import jax
        jax.block_until_ready(out)


class _NodeEncoder:
    """The per-NODE timestamp-encoder cell shared by every store arena on
    the node: the fused cross-store kernels compare all subject/row
    timestamps in ONE encoding window, so the window anchors once per node
    (by whichever store sees a timestamp first), not once per store."""

    __slots__ = ("encoder",)

    def __init__(self):
        self.encoder: Optional[TimestampEncoder] = None


class _StoreArena:
    """Incremental device mirror of one STORE's key-domain active set (rows
    keyed by txn id). Arenas are per store -- mirroring the reference's
    shard-per-CommandStore layout -- so compaction, growth, and generation
    pins stay store-local while the node tick fuses every store's pending
    subjects into ONE kernel call over the concatenation of their arena
    blocks (exact per-key recovery at harvest filters bucket false
    positives).

    Device arrays (authoritative once scattered): bitmaps f32[cap, K],
    ts i32[cap, 3], exec_ts i32[cap, 3], kinds i32[cap], valid bool[cap]
    (range subjects test the same bitmaps by covered-bucket contraction --
    the old [kmin, kmax] hull lanes are retired). Host shadows exist only
    to source dirty-row scatters and
    exact key sets. Key lists upload as a variable-width CSR, so arbitrarily
    wide rows stay on the device path (no MAXK demotion, no host residual).
    Uploads are FIELD-GRANULAR: a row whose only change is an exec-ts bump
    (the common status path) ships one int32 triple, not the whole row.
    """

    GROW = 2

    def __init__(self, num_buckets: int, initial_cap: int = 4096,
                 range_cap: int = 64,
                 shared_encoder: Optional[_NodeEncoder] = None,
                 kid_cap: int = 4096, *, metrics: MetricsRegistry,
                 account: Occupancy):
        # the owning resolver's registry and occupancy account: the arena's
        # lifecycle (device sync, compaction, growth) reports there through
        # the same primitive as every pipeline phase
        self._metrics = metrics
        self._phase = functools.partial(phase, metrics, account=account)
        self._rows_uploaded = self._metrics.counter(
            "resolver.arena_rows_uploaded")
        self._upload_calls = self._metrics.counter(
            "resolver.arena_upload_calls")
        self._scatters_donated = self._metrics.counter(
            "resolver.arena_scatters_donated")
        self.num_buckets = num_buckets
        self.cap = initial_cap
        self.count = 0
        self.txn_ids: List[TxnId] = []
        # object-dtype mirror of txn_ids: decode materializes dep id tuples
        # with one fancy index instead of a per-id Python loop
        self.ids_np = np.empty(self.cap, dtype=object)
        self.key_sets: List[frozenset] = []
        self.row_of: Dict[TxnId, int] = {}
        self._enc = shared_encoder if shared_encoder is not None \
            else _NodeEncoder()
        self.exec_max: List[Optional[Timestamp]] = []
        # host shadows for scatter sourcing
        self.ts = np.zeros((self.cap, 3), dtype=np.int32)
        self.exec_ts = np.full((self.cap, 3), np.iinfo(np.int32).min,
                               dtype=np.int32)
        self.kinds = np.zeros(self.cap, dtype=np.int32)
        self.valid = np.zeros(self.cap, dtype=bool)
        # variable-width CSR source: sorted unique key-bucket indices per row
        self.row_mods: List[np.ndarray] = []
        # per-KEY packed row bitmask (u32[cap/32]): which arena rows touch
        # the key. AND-ing it with a subject's packed dependency row yields
        # that key's dependency rows with pure numpy -- the vectorized CSR
        # decode that makes the device path cheaper than the host scan
        self.key_rows: Dict[object, np.ndarray] = {}
        # DEVICE mirror of key_rows for finalize_csr (the on-device exact
        # filter): each key gets a dense id at first sighting and a
        # u32[kid_cap, cap/32] row in _kid_dev. Maintained by WORD-granular
        # deltas -- any bit set/clear marks its (kid, word) coordinate dirty,
        # and kid_sync ships the deduped words' full current values (no RMW
        # hazard). Ids are never reused; the mirror rebuilds wholesale on
        # compaction / growth (shape change).
        self.kid_cap = kid_cap
        self.kid_of: Dict[object, int] = {}
        self._key_of_kid: Dict[int, object] = {}
        self._kid_dev = None
        self._dirty_kid_words: set = set()
        # exact per-key live-row popcount: sizing finalize_csr's out_cap from
        # the sum over a dispatch's (subject, key) slots gives a bound the
        # compaction output can never overflow (belt-and-braces checked)
        self.key_pop: Dict[object, int] = {}
        # sorted int view of kid_of for the range-subject stab lane: binary
        # searching a range piece's [start, end) against it enumerates the
        # exact arena keys the piece covers (so range subjects reuse
        # finalize_csr's kid masks instead of the host key-set walk).
        # Invalidated only when a NEW kid is allocated -- kid ids persist
        # across compaction. None-cached as unsupported when any key is not
        # a plain int (ordering would not match interval containment).
        self._key_index = None
        # bumped whenever a key's row-mask bits change on rows the device
        # may already have answered for: key-set widening of an EXISTING row
        # and prune/truncate clears. An in-flight finalized result whose
        # kseq no longer matches falls back to the legacy decode (new-row
        # bit sets don't bump -- rows born after the encode have no bits in
        # either path's snapshot)
        self.kseq = 0
        # rows of INVALIDATED txns: the device excludes them via the valid
        # lane (the `valid` lane is overloaded -- also false for emptied rows)
        self.invalidated: set = set()
        # once any truncation shrank a row, the device bitmap may understate
        # historical key coverage -- the (monotone) max-conflict kernel must
        # defer to the host map from then on
        self.had_truncation = False
        # field-granular dirty masks: `full` rows re-upload every lane (new
        # rows, device re-init); `keys`/`ts`/`valid` rows ship only that
        # lane group. A row in `full` never also sits in a granular set
        # (see _mark_dirty), so no lane uploads twice.
        self._dirty_full: set = set()
        self._dirty_keys: set = set()
        self._dirty_ts: set = set()
        self._dirty_valid: set = set()
        self._device = None
        # WHO HOLDS A DEVICE LANE. An array that device_arrays() or
        # kid_arrays() has returned is lent: a staged plan's closure may
        # keep it until it launches, or re-runs at a larger out-cap, ticks
        # later, so it is never donated. Every other array here (fresh
        # zeros, arena_grow's pad, a scatter's output) the arena alone has
        # seen, and the next arena_scatter / arena_scatter_keys /
        # kid_word_scatter rewrites it in place. A sync that starts from
        # lent lanes copies, once, each one it is about to write (_own).
        # _lent: indexes into _device.
        self._lent: set = set()
        self._kid_lent = False
        # bumped by compact(): in-flight async calls hold packed rows in the
        # OLD row mapping. Dispatch pins the generation it encoded against;
        # compact() then snapshots the retiring row->txn table so the harvest
        # can TRANSLATE its rows onto the new mapping (no host fallback)
        self.gen = 0
        self.retired_ids: Dict[int, np.ndarray] = {}
        self._gen_pins: Dict[int, int] = {}
        # (gen, count) -> (rank, order, ids by rank) cache for the global ts
        # lexorder -- ts[row] and ids_np[row] are written once at row
        # creation, so it only invalidates on compaction (gen) or growth of
        # the live prefix (count)
        self._rank = None
        # bytes shipped host->device by dirty-row scatters (bench counters):
        # total, broken out per field group, and the bytes the retired
        # all-lanes scheme would have shipped for the same dirty sets (the
        # baseline the field-granular deltas are measured against)
        self.upload_bytes = 0
        self.upload_bytes_by_field = {"full": 0, "keys": 0, "ts": 0,
                                      "valid": 0, "kids": 0}
        self.upload_bytes_full_equiv = 0
        # the store's ACTIVE RANGE TXNS, mirrored as interval rows; shares
        # the node's timestamp encoder so the kernels' before-compares are
        # in one window
        self.ranges = _RangeArena(self, range_cap)

    @property
    def encoder(self) -> Optional[TimestampEncoder]:
        return self._enc.encoder

    # -- host-side mutation ---------------------------------------------------
    def _ensure_encoder(self, ts: Timestamp) -> None:
        if self._enc.encoder is None:
            # base epoch 0: epochs are small ints, and the epoch delta must
            # stay non-negative even when an OLDER-epoch txn registers after
            # a newer one; the hlc window is symmetric around the first hlc
            # (the cell is node-shared: sibling store arenas join the window)
            self._enc.encoder = TimestampEncoder(0, ts.hlc)

    def _mark_dirty(self, row: int, field_set: set) -> None:
        # a row queued for a full upload already ships every lane
        if row not in self._dirty_full:
            field_set.add(row)

    def _grow_host(self) -> None:
        new_cap = self.cap * self.GROW
        ids = np.empty(new_cap, dtype=object)
        ids[:self.cap] = self.ids_np
        self.ids_np = ids
        self.ts = np.pad(self.ts, ((0, new_cap - self.cap), (0, 0)))
        self.exec_ts = np.pad(self.exec_ts, ((0, new_cap - self.cap), (0, 0)),
                              constant_values=np.iinfo(np.int32).min)
        self.kinds = np.pad(self.kinds, (0, new_cap - self.cap))
        self.valid = np.pad(self.valid, (0, new_cap - self.cap))
        for k in self.key_rows:
            self.key_rows[k] = np.pad(self.key_rows[k],
                                      (0, (new_cap - self.cap) // 32))
        self.cap = new_cap
        # word width changed: the kid mirror rebuilds at the new shape
        self._kid_dev = None
        self._dirty_kid_words.clear()

    def compact(self) -> bool:
        """Rebuild the arena keeping only rows that still carry keys: pruned
        /truncated rows (empty key_sets) are settled history no scan can
        match. Returns False when that would reclaim less than half the
        capacity (caller grows instead). Bumps `gen`: in-flight async calls
        hold packed rows in the OLD mapping; their harvests translate those
        rows through the snapshot pinned below (no host fallback)."""
        with self._phase("resolver.compact", "resolver.compact_s"):
            live = [i for i in range(self.count) if self.key_sets[i]]
            kept = len(live) <= self.cap // 2
            if kept:
                self._compact_onto(live)
                self._metrics.counter("resolver.arena_compactions").inc()
                self._metrics.counter("resolver.compact_rows_kept").inc(
                    len(live))
        return kept

    def _compact_onto(self, live: List[int]) -> None:
        """compact()'s rebuild: the rows in `live`, in order, become rows
        0..len(live)-1 of a fresh mapping."""
        if self._gen_pins.get(self.gen):
            # calls encoded against this mapping are still in flight: keep
            # the row->txn table alive so their harvests can translate
            self.retired_ids[self.gen] = self.ids_np[:self.count].copy()
        old_ids = self.txn_ids
        old_keys = self.key_sets
        old_exec = self.exec_max
        old_ts = self.ts.copy()
        old_exec_ts = self.exec_ts.copy()
        old_kinds = self.kinds.copy()
        old_invalidated = self.invalidated
        self.count = 0
        self.txn_ids = []
        self.ids_np[:] = None
        self.key_sets = []
        self.exec_max = []
        self.row_of = {}
        self.key_rows = {}
        self.key_pop = {}
        self._kid_dev = None
        self._dirty_kid_words = set()
        self.row_mods = []
        self.invalidated = set()
        self.ts[:] = 0
        self.exec_ts[:] = np.iinfo(np.int32).min
        self.kinds[:] = 0
        self.valid[:] = False
        for old_row in live:
            row = self.count
            self.count += 1
            self.txn_ids.append(old_ids[old_row])
            self.ids_np[row] = old_ids[old_row]
            self.key_sets.append(old_keys[old_row])
            self.exec_max.append(old_exec[old_row])
            self.row_of[old_ids[old_row]] = row
            self.ts[row] = old_ts[old_row]
            self.exec_ts[row] = old_exec_ts[old_row]
            self.kinds[row] = old_kinds[old_row]
            # validity is RECOMPUTED, not copied: the old lane is overloaded
            # (false for invalidated AND emptied rows) -- copying would
            # strand a still-live row invisible to the kernel
            self.valid[row] = old_row not in old_invalidated
            if old_row in old_invalidated:
                self.invalidated.add(row)
            self.row_mods.append(None)
            self._set_row_keys(row)
            for k in old_keys[old_row]:
                self._set_key_row_bit(k, row)
        self._device = None
        self._dirty_full = set()
        self._dirty_keys = set()
        self._dirty_ts = set()
        self._dirty_valid = set()
        self.gen += 1

    # -- in-flight generation pinning -----------------------------------------
    def pin_gen(self) -> int:
        """An async call just encoded against the current row mapping: keep
        its row->txn snapshot reachable across compaction until it drains."""
        self._gen_pins[self.gen] = self._gen_pins.get(self.gen, 0) + 1
        return self.gen

    def unpin_gen(self, gen: int) -> None:
        left = self._gen_pins.get(gen, 0) - 1
        if left > 0:
            self._gen_pins[gen] = left
        else:
            self._gen_pins.pop(gen, None)
            if gen != self.gen:
                self.retired_ids.pop(gen, None)

    def translate_rows(self, gen: int, rows: np.ndarray) -> Optional[np.ndarray]:
        """Map dep rows addressed in a RETIRED generation's packed result
        onto the current mapping via txn ids. Exact: compaction only drops
        rows whose key sets emptied (pruned/truncated history), and those
        could no longer pass the exact key-membership filter anyway. None
        when no snapshot was pinned (the caller falls back to the host)."""
        ids = self.retired_ids.get(gen)
        if ids is None:
            return None
        rows = rows[rows < ids.size]
        out = np.fromiter((self.row_of.get(t, -1) for t in ids[rows]),
                          np.int64, rows.size)
        return out[out >= 0]

    def row_rank(self) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Global ts-lane lexorder over rows [0, count): rank[row] = position
        of the row in TxnId order, order = the inverse permutation, by_rank
        = the rows' txn ids in that order. The lane encoding is
        order-preserving, so rank order == TxnId order -- the batched
        decode sorts dep rows once with it instead of lexsorting per
        item."""
        key = (self.gen, self.count)
        cached = self._rank
        if cached is not None and cached[0] == key:
            return cached[1:]
        ts = self.ts[:self.count]
        order = np.lexsort((ts[:, 2], ts[:, 1], ts[:, 0]))
        rank = np.empty(self.count, np.int64)
        rank[order] = np.arange(self.count)
        self._rank = (key, rank, order, self.ids_np[order])
        return self._rank[1:]

    def update(self, txn_id: TxnId, key_set, status: CfkStatus,
               conflict_ts: Timestamp) -> None:
        key_set = frozenset(key_set)
        row = self.row_of.get(txn_id)
        if row is None:
            self._ensure_encoder(txn_id)
            Invariants.check_state(self.encoder.in_window(txn_id),
                                   "active txn %s outside encoder window",
                                   txn_id)
            if self.count == self.cap and not self.compact():
                with self._phase("resolver.grow", "resolver.grow_s",
                                 cap=self.cap * self.GROW):
                    self._grow_host()
                    if self._device is not None:
                        from accord_tpu.ops.kernels import arena_grow
                        # (a pad cannot alias its input: nothing donated;
                        # its outputs are the arena's own)
                        self._device = arena_grow(*self._device,
                                                  new_cap=self.cap)
                        self._lent.clear()
                    self._metrics.counter("resolver.arena_growths").inc()
            row = self.count
            self.count += 1
            self.txn_ids.append(txn_id)
            self.ids_np[row] = txn_id
            self.key_sets.append(frozenset(key_set))
            self.exec_max.append(None)
            self.row_of[txn_id] = row
            self.ts[row] = self.encoder.encode_one(txn_id)
            self.kinds[row] = int(txn_id.kind)
            self.valid[row] = True
            self.row_mods.append(None)
            self._set_row_keys(row)
            for k in key_set:
                self._set_key_row_bit(k, row)
            self._dirty_full.add(row)
        elif key_set and not (key_set <= self.key_sets[row]):
            # a later registration may widen the key set (partial txn unions)
            # -- including invalidations, whose keys must stay visible to the
            # monotone max-conflict kernel
            for k in key_set - self.key_sets[row]:
                self._set_key_row_bit(k, row)
            self.key_sets[row] = self.key_sets[row] | frozenset(key_set)
            self._set_row_keys(row)
            self._mark_dirty(row, self._dirty_keys)
            # an EXISTING row gained key bits: in-flight finalized results
            # snapshotted the old mask, so their exact filter may miss this
            # row where the legacy re-decode would see it
            self.kseq += 1
        # MaxConflicts is monotone in the reference: even an invalidated
        # txn's registration bumps the conflict floor
        prev = self.exec_max[row]
        if prev is None or conflict_ts > prev:
            self.exec_max[row] = conflict_ts
            self.exec_ts[row] = self.encoder.encode_one(conflict_ts)
            self._mark_dirty(row, self._dirty_ts)
        if status == CfkStatus.INVALIDATED:
            # drops the row from deps scans (a dep that never applies);
            # never reset -- invalidation is terminal
            self.valid[row] = False
            self.invalidated.add(row)
            self._mark_dirty(row, self._dirty_valid)

    def _set_row_keys(self, row: int) -> None:
        ks = self.key_sets[row]
        if not ks:
            self.row_mods[row] = _EMPTY_I32
            return
        mods = sorted({int(k) % self.num_buckets for k in ks})
        self.row_mods[row] = np.asarray(mods, dtype=np.int32)

    def _set_key_row_bit(self, key, row: int) -> None:
        kr = self.key_rows.get(key)
        if kr is None:
            kr = self.key_rows[key] = np.zeros(self.cap // 32, np.uint32)
            if key not in self.kid_of:
                kid = len(self.kid_of)
                self.kid_of[key] = kid
                self._key_of_kid[kid] = key
                self._key_index = None
                if kid >= self.kid_cap:
                    # dense id space overflowed the mirror: double and rebuild
                    self.kid_cap *= 2
                    self._kid_dev = None
                    self._dirty_kid_words.clear()
        bit = np.uint32(1 << (row & 31))
        if not kr[row >> 5] & bit:
            kr[row >> 5] |= bit
            self.key_pop[key] = self.key_pop.get(key, 0) + 1
            self._dirty_kid_words.add((self.kid_of[key], row >> 5))

    def _clear_key_row_bit(self, key, row: int) -> None:
        kr = self.key_rows.get(key)
        if kr is not None:
            bit = np.uint32(1 << (row & 31))
            if kr[row >> 5] & bit:
                kr[row >> 5] &= ~bit
                self.key_pop[key] = self.key_pop.get(key, 1) - 1
                self._dirty_kid_words.add((self.kid_of[key], row >> 5))

    def decode_packed(self, txn_id: TxnId, owned_keys, prow: np.ndarray,
                      store=None, before=None, cover_seq=0):
        """Vectorized CSR recovery, O(deps) not O(cap): unpack only the
        NONZERO words of the subject's packed dependency row once, then test
        each key's membership with packed-bit gathers over that small row
        list (a per-key unpackbits+nonzero over the full arena made the
        decode cost scale with capacity and dominate the block time at 10k
        inflight). Exactness: key_rows bits track REAL key sets, so bucket
        collisions and cross-store rows drop out here; invalid rows were
        already excluded by the kernel's valid lane."""
        wnz = np.nonzero(prow)[0]
        if wnz.size == 0:
            from accord_tpu.primitives.deps import KeyDeps
            return KeyDeps.EMPTY
        sub = np.unpackbits(prow[wnz].astype("<u4").view(np.uint8),
                            bitorder="little").reshape(wnz.size, 32)
        rr, cc = np.nonzero(sub)
        rows_all = (wnz[rr].astype(np.int64) << 5) | cc
        return self.decode_rows(txn_id, owned_keys, rows_all, store, before,
                                cover_seq)

    def decode_rows(self, txn_id: TxnId, owned_keys, rows_all: np.ndarray,
                    store=None, before=None, cover_seq=0):
        """CSR recovery from already-extracted dep row indices (the batched
        harvest unpacks the WHOLE dispatch's bit matrix in one numpy call
        and hands each subject its row list -- per-subject numpy-call
        overhead was the decode bottleneck at large dispatch sizes).
        `store`/`before` enable the transitive-dependency elision filter so
        the device path matches the host scan's covered-id rule exactly."""
        from accord_tpu.primitives.deps import KeyDeps
        srow = self.row_of.get(txn_id)
        if srow is not None and rows_all.size:
            rows_all = rows_all[rows_all != srow]
        if rows_all.size == 0:
            return KeyDeps.EMPTY
        hi = rows_all >> 5
        lo = rows_all & 31
        keys = []
        per_key_rows = []
        cfks = store.cfks if store is not None else {}
        for k in owned_keys:
            kr = self.key_rows.get(k)
            if kr is None:
                continue
            sel = rows_all[((kr[hi] >> lo) & 1).astype(bool)]
            if sel.size and before is not None:
                c = cfks.get(k)
                if c is not None and c.covered:
                    cov = c.covered
                    ids = self.ids_np

                    def live(r):
                        e = cov.get(ids[r])
                        # elide only covers the kernel snapshot already saw
                        # (seq <= cover_seq) whose cover executes below the
                        # subject's bound -- the host scan's exact rule plus
                        # the snapshot guard
                        return e is None or e[0] > cover_seq \
                            or not e[1] < before

                    mask = np.fromiter((live(r) for r in sel), bool, sel.size)
                    sel = sel[mask]
            if sel.size:
                keys.append(k)
                per_key_rows.append(sel)
        if not keys:
            return KeyDeps.EMPTY
        uniq = np.unique(np.concatenate(per_key_rows)) \
            if len(per_key_rows) > 1 else per_key_rows[0]
        ts = self.ts
        order = np.lexsort((ts[uniq, 2], ts[uniq, 1], ts[uniq, 0]))
        sorted_rows = uniq[order]
        txn_ids = tuple(self.ids_np[sorted_rows].tolist())
        if len(per_key_rows) == 1:
            # single key: its value list is exactly the sorted unique set
            n = len(sorted_rows)
            return KeyDeps(tuple(keys), txn_ids, (0, n), tuple(range(n)))
        inv = np.empty(int(uniq[-1]) + 1, np.int32)
        inv[sorted_rows] = np.arange(len(sorted_rows), dtype=np.int32)
        offsets = [0]
        value_idx: List[int] = []
        for rows in per_key_rows:
            value_idx.extend(np.sort(inv[rows]).tolist())
            offsets.append(len(value_idx))
        return KeyDeps(tuple(keys), txn_ids, tuple(offsets), tuple(value_idx))

    def remove_keys(self, txn_id: TxnId, keys) -> bool:
        """A store truncated its record of txn_id: its slice of the keys no
        longer yields deps (other stores' keys in the row live on). True
        where that emptied the row: a tombstone until the next compaction."""
        row = self.row_of.get(txn_id)
        if row is None:
            return False
        remaining = self.key_sets[row] - frozenset(keys)
        if remaining == self.key_sets[row]:
            return False
        for k in self.key_sets[row] - remaining:
            self._clear_key_row_bit(k, row)
        self.key_sets[row] = remaining
        self.had_truncation = True
        # bits cleared on rows in-flight finalized results may have kept:
        # their kseq no longer matches, routing them to the legacy decode
        self.kseq += 1
        self._set_row_keys(row)
        self._mark_dirty(row, self._dirty_keys)
        if not remaining:
            self.valid[row] = False
            self._mark_dirty(row, self._dirty_valid)
        return not remaining

    # -- device sync ----------------------------------------------------------
    def device_arrays(self):
        """The device lanes, brought up to the host shadows first where a
        row is dirty (under resolver.arena_sync; a clean arena opens no
        span)."""
        if self._device is None or self._dirty_full or self._dirty_keys \
                or self._dirty_ts or self._dirty_valid:
            with self._phase("resolver.arena_sync",
                             "resolver.arena_sync_s"):
                self._sync_device()
        self._lent.update(range(len(self._device)))
        return self._device

    def _own(self, lanes) -> bool:
        """The scatter about to run donates `lanes` of _device: those that
        were lent are replaced by a device copy first (one arena_copy call),
        so the lent arrays stay whole for whoever holds them. True where
        none was lent: the scatter runs in place at no copy."""
        lent = [i for i in lanes if i in self._lent]
        if not lent:
            return True
        from accord_tpu.ops.kernels import arena_copy
        d = list(self._device)
        for i, own in zip(lent, arena_copy(*(d[i] for i in lent))):
            d[i] = own
        self._device = tuple(d)
        self._lent.difference_update(lent)
        return False

    def _sync_device(self) -> None:
        import jax.numpy as jnp
        from accord_tpu.ops.kernels import scatter_nnz_tier
        if self._device is None:
            neg = np.iinfo(np.int32).min
            self._device = (
                jnp.zeros((self.cap, self.num_buckets), jnp.float32),
                jnp.zeros((self.cap, 3), jnp.int32),
                jnp.full((self.cap, 3), neg, jnp.int32),
                jnp.zeros(self.cap, jnp.int32),
                jnp.zeros(self.cap, bool),
            )
            self._lent.clear()
            self._dirty_full = set(range(self.count))
            self._dirty_keys.clear()
            self._dirty_ts.clear()
            self._dirty_valid.clear()
        if self._dirty_full:
            for chunk in self._csr_chunks(sorted(self._dirty_full)):
                self._scatter_chunk(chunk)
            # the full upload carried every lane: granular marks on the same
            # rows are satisfied
            self._dirty_keys -= self._dirty_full
            self._dirty_ts -= self._dirty_full
            self._dirty_valid -= self._dirty_full
            self._dirty_full.clear()
        if self._dirty_keys or self._dirty_ts or self._dirty_valid:
            # baseline accounting FIRST, over the UNION of granular rows
            # chunked exactly like the all-lanes scheme would have: a row
            # dirty in several fields was still one full-row upload there
            union = sorted(self._dirty_keys | self._dirty_ts
                           | self._dirty_valid)
            for chunk in self._csr_chunks(union):
                m = 8 if len(chunk) <= 8 else 64
                z = scatter_nnz_tier(
                    sum(len(self.row_mods[r]) for r in chunk))
                # idx + ts + exec_ts + kinds + valid lanes (m * 33 bytes)
                # plus the padded CSR pair (z * 8 bytes)
                self.upload_bytes_full_equiv += m * 33 + z * 8
            for chunk in self._csr_chunks(sorted(self._dirty_keys)):
                self._scatter_keys_chunk(chunk)
            self._dirty_keys.clear()
            self._scatter_lane(sorted(self._dirty_ts), 2, "ts", self.exec_ts)
            self._dirty_ts.clear()
            self._scatter_lane(sorted(self._dirty_valid), 4, "valid",
                               self.valid)
            self._dirty_valid.clear()

    def _csr_chunks(self, rows: List[int]):
        """Greedy chunks bounded in BOTH rows (<= 64) and flat CSR key
        entries (<= SCATTER_NNZ_TIERS[-1]) so the jit shape tiers stay few
        and warmable; a single ultra-wide row gets its own power-of-two nnz
        bucket."""
        lo = 0
        while lo < len(rows):
            hi = lo + 1
            nnz = len(self.row_mods[rows[lo]])
            while hi < len(rows) and hi - lo < 64:
                w = len(self.row_mods[rows[hi]])
                if nnz + w > 512:
                    break
                nnz += w
                hi += 1
            yield rows[lo:hi]
            lo = hi

    def _scatter_chunk(self, chunk: List[int]) -> None:
        import jax.numpy as jnp
        from accord_tpu.ops.kernels import arena_scatter, scatter_nnz_tier
        m = 8 if len(chunk) <= 8 else 64
        # pad by repeating the first dirty row: duplicate scatter indexes
        # write identical (correct) data -- harmless (the bitmap scatter is
        # clear-then-max, so double writes commute)
        idx = np.full(m, chunk[0], dtype=np.int32)
        idx[:len(chunk)] = chunk
        mods_list = [self.row_mods[r] for r in chunk]
        counts = np.fromiter((len(a) for a in mods_list), np.int64,
                             len(chunk))
        total = int(counts.sum())
        z = scatter_nnz_tier(total)
        # CSR padding entries use row index == cap: out of bounds, dropped
        key_rows = np.full(z, self.cap, dtype=np.int32)
        key_mods = np.zeros(z, dtype=np.int32)
        if total:
            key_rows[:total] = np.repeat(np.asarray(chunk, np.int32), counts)
            key_mods[:total] = np.concatenate(mods_list)
        uploads = (idx, key_rows, key_mods, self.ts[idx], self.exec_ts[idx],
                   self.kinds[idx], self.valid[idx])
        nb = sum(a.nbytes for a in uploads)
        self.upload_bytes += nb
        self.upload_bytes_by_field["full"] += nb
        self.upload_bytes_full_equiv += nb
        self._rows_uploaded.inc(len(chunk))
        self._upload_calls.inc()
        if self._own(range(5)):
            self._scatters_donated.inc()
        self._device = arena_scatter(
            *self._device, *(jnp.asarray(a) for a in uploads))

    def _scatter_keys_chunk(self, chunk: List[int]) -> None:
        """Key-set-only delta: rebuild the rows' bitmaps from the CSR;
        ts/exec/kind/valid lanes stay."""
        import jax.numpy as jnp
        from accord_tpu.ops.kernels import (arena_scatter_keys,
                                            scatter_nnz_tier)
        m = 8 if len(chunk) <= 8 else 64
        idx = np.full(m, chunk[0], dtype=np.int32)
        idx[:len(chunk)] = chunk
        mods_list = [self.row_mods[r] for r in chunk]
        counts = np.fromiter((len(a) for a in mods_list), np.int64,
                             len(chunk))
        total = int(counts.sum())
        z = scatter_nnz_tier(total)
        key_rows = np.full(z, self.cap, dtype=np.int32)
        key_mods = np.zeros(z, dtype=np.int32)
        if total:
            key_rows[:total] = np.repeat(np.asarray(chunk, np.int32), counts)
            key_mods[:total] = np.concatenate(mods_list)
        uploads = (idx, key_rows, key_mods)
        nb = sum(a.nbytes for a in uploads)
        self.upload_bytes += nb
        self.upload_bytes_by_field["keys"] += nb
        self._rows_uploaded.inc(len(chunk))
        self._upload_calls.inc()
        if self._own((0,)):
            self._scatters_donated.inc()
        d = list(self._device)
        d[0] = arena_scatter_keys(d[0], *(jnp.asarray(a) for a in uploads))
        self._device = tuple(d)

    def _scatter_lane(self, rows: List[int], lane: int, field: str,
                      src: np.ndarray) -> None:
        """Single-lane delta (exec-ts bumps, valid flips): ship one lane's
        dirty rows via the shared flush_lane helper (ops/deltas.py), which
        the exec plane's field deltas ride too. Its scatter_rows does not
        donate (the exec plane's holders are its own affair, and the lanes
        here are small: `valid` is cap bytes): a copy a call, as before."""
        if not rows:
            return
        from accord_tpu.ops.deltas import flush_lane

        def account(nbytes: int, _m: int) -> None:
            self.upload_bytes += nbytes
            self.upload_bytes_by_field[field] += nbytes
            self._upload_calls.inc()

        self._rows_uploaded.inc(len(rows))
        d = list(self._device)
        d[lane] = flush_lane(d[lane], rows, src, account)
        self._device = tuple(d)
        self._lent.discard(lane)

    def kid_arrays(self):
        """Device mirror of key_rows for finalize_csr: u32[kid_cap, cap/32],
        row kid = the packed row-mask of the key with that dense id. Synced
        by word-granular deltas -- each dirty (kid, word) coordinate ships
        the word's FULL current value (host-deduped set, so no read-modify-
        write hazard), chunked through the shared scatter_nnz tiers; under
        resolver.arena_sync where there is anything to ship."""
        w = self.cap // 32
        if self._kid_dev is None or self._dirty_kid_words \
                or self._kid_dev.shape != (self.kid_cap, w):
            with self._phase("resolver.arena_sync",
                             "resolver.arena_sync_s"):
                self._sync_kids()
        self._kid_lent = True
        return self._kid_dev

    def _sync_kids(self) -> None:
        import jax.numpy as jnp
        from accord_tpu.ops.kernels import (arena_copy, kid_word_scatter,
                                            scatter_nnz_tier)
        w = self.cap // 32
        if self._kid_dev is None or self._kid_dev.shape != (self.kid_cap, w):
            self._kid_dev = jnp.zeros((self.kid_cap, w), jnp.uint32)
            self._kid_lent = False
            # wholesale rebuild: every nonzero word of every key's mask
            self._dirty_kid_words = {
                (self.kid_of[k], int(wi))
                for k, kr in self.key_rows.items()
                for wi in np.nonzero(kr)[0]
            }
        if self._dirty_kid_words:
            coords = sorted(self._dirty_kid_words)
            self._dirty_kid_words = set()
            for lo in range(0, len(coords), 512):
                chunk = coords[lo:lo + 512]
                z = scatter_nnz_tier(len(chunk))
                # padding coordinates use kid == kid_cap: out of bounds in
                # the scatter's drop mode
                kid_idx = np.full(z, self.kid_cap, dtype=np.int32)
                word_idx = np.zeros(z, dtype=np.int32)
                words = np.zeros(z, dtype=np.uint32)
                for j, (kid, wi) in enumerate(chunk):
                    kid_idx[j] = kid
                    word_idx[j] = wi
                    words[j] = self.key_rows[self._key_of_kid[kid]][wi]
                nb = kid_idx.nbytes + word_idx.nbytes + words.nbytes
                self.upload_bytes += nb
                self.upload_bytes_by_field["kids"] += nb
                # the kid table is a finalize-path structure both upload
                # strategies would ship identically, so it lands in the
                # full-equivalent baseline too (granular-vs-full deltas
                # stay a statement about the row lanes)
                self.upload_bytes_full_equiv += nb
                self._upload_calls.inc()
                if self._kid_lent:
                    # the table kid_arrays() returned: a plan may hold it
                    self._kid_dev, = arena_copy(self._kid_dev)
                    self._kid_lent = False
                else:
                    self._scatters_donated.inc()
                self._kid_dev = kid_word_scatter(
                    self._kid_dev, jnp.asarray(kid_idx),
                    jnp.asarray(word_idx), jnp.asarray(words))

    def key_index(self):
        """(keys_sorted int64[n], kids int32[n]) over every key the arena
        has ever allotted a dense id, sorted by key -- the binary-search
        index the range-subject stab lane enumerates covered keys from.
        None when any key is not a plain int (a non-integer ordering could
        disagree with interval containment, so those arenas answer range
        subjects via the candidate re-filter instead). Cached until a new
        kid is allocated; ids persist across compaction, so all-zero masks
        (emptied keys) stay in the index and simply stab to nothing."""
        idx = self._key_index
        if idx is None:
            for k in self.kid_of:
                if type(k) is not int:
                    self._key_index = idx = (None, None)
                    break
            else:
                try:
                    keys = np.fromiter(self.kid_of.keys(), dtype=np.int64,
                                       count=len(self.kid_of))
                except OverflowError:
                    self._key_index = idx = (None, None)
                else:
                    kids = np.fromiter(self.kid_of.values(), dtype=np.int32,
                                       count=len(self.kid_of))
                    order = np.argsort(keys, kind="stable")
                    self._key_index = idx = (keys[order], kids[order])
        return None if idx[0] is None else idx


class _RangeArena:
    """Incremental device mirror of one STORE's active RANGE-TXN set: one
    row per (txn, interval), interval endpoints normalized to half-open
    int32 pairs (a _Successor endpoint encodes as key+1 -- exact for integer
    key domains). Owned by a _StoreArena and sharing the node's timestamp
    encoder, so the range kernel's before-compares live in the same window
    as every sibling arena in a fused call.

    Sorted-endpoint pairs instead of an interval tree: the kernel tests every
    (subject interval, row) pair with a branch-free broadcast compare -- pure
    VPU work -- where a tree descent would be serial and branchy on device.

    Device lanes: starts/ends i32[rcap], ts i32[rcap, 3], kinds i32[rcap],
    valid bool[rcap]. The device result is a CANDIDATE set: the harvest
    decode re-filters per real range against store.range_txns, which also
    makes freed-row reuse between dispatch and harvest safe (a wrong-id
    candidate fails the host re-check exactly like a bucket collision).

    A non-integer / out-of-window endpoint flips `encode_ok` False
    permanently: the store reverts to the host range scans (counted by the
    resolver as range_fallbacks; never hit by the integer key domains the
    burns and benches use)."""

    GROW = 2

    def __init__(self, owner: "_StoreArena", initial_cap: int = 64):
        self.owner = owner
        self.cap = initial_cap          # multiple of 32 (and, sharded, of
                                        # 32*data -- see ShardedBatchDepsResolver)
        self.count = 0                  # high-water row mark
        self.ids_np = np.empty(self.cap, dtype=object)
        self.rows_of: Dict[TxnId, List[int]] = {}
        # node-level union of each txn's registered ranges (stores register
        # their slices separately; deps recovery re-slices per store)
        self.ranges_of: Dict[TxnId, Ranges] = {}
        self._encoded_of: Dict[TxnId, List[Tuple[int, int]]] = {}
        self.starts = np.zeros(self.cap, dtype=np.int32)
        self.ends = np.zeros(self.cap, dtype=np.int32)
        # host only: each row's endpoints as order codes (_endpoint_code),
        # which keep a _Successor end apart from the next key that the
        # int32 lanes fold it onto -- the harvest decode cuts exact
        # intersections from these without touching a Range
        self.codes = np.zeros((self.cap, 2), dtype=np.int64)
        # (start code, end code) -> the Range: a RangeDeps row the harvest
        # decode has made before is not made again (Ranges are immutable)
        self.row_memo: Dict[Tuple[int, int], Range] = {}
        self.ts = np.zeros((self.cap, 3), dtype=np.int32)
        self.kinds = np.zeros(self.cap, dtype=np.int32)
        self.valid = np.zeros(self.cap, dtype=bool)
        self.invalidated_ids: set = set()
        self.encode_ok = True
        self._free: List[int] = []
        # field-granular dirty masks, mirroring _StoreArena: dropped rows
        # only flip the valid lane, so they ship 5 bytes/row, not the full
        # 29-byte interval row
        self._dirty_full: set = set()
        self._dirty_valid: set = set()
        self._device = None
        self.upload_bytes = 0
        self.upload_bytes_by_field = {"range_full": 0, "range_valid": 0}
        self.upload_bytes_full_equiv = 0
        # generation pinning across compact(), mirroring _StoreArena: stale
        # harvests translate candidate rows BY TXN ID via the pinned
        # snapshot (no row translation needed -- decode re-filters against
        # current store state anyway)
        self.gen = 0
        self.retired_ids: Dict[int, np.ndarray] = {}
        self._gen_pins: Dict[int, int] = {}
        # bumped whenever rows are FREED (drop / re-registration): a freed
        # row can be REUSED for another txn before an in-flight finalized
        # range result harvests, and the exact hits it computed at dispatch
        # would then translate to the wrong txn id. On mismatch the harvest
        # falls back to the legacy candidate decode, which re-filters
        # against current host state (bit-identical by construction)
        self.rseq = 0

    # -- host-side mutation ---------------------------------------------------
    def update(self, txn_id: TxnId, rngs: Ranges, status: CfkStatus) -> None:
        if not self.encode_ok:
            return
        if status == CfkStatus.INVALIDATED:
            self.invalidate(txn_id)
            return
        if txn_id in self.invalidated_ids:
            return  # invalidation is terminal
        prev = self.ranges_of.get(txn_id)
        merged = rngs if prev is None else prev.union(rngs)
        encoded = []
        for r in merged:
            iv = encode_interval(r)
            if iv is None:
                self.encode_ok = False
                return
            encoded.append(iv)
        if encoded == self._encoded_of.get(txn_id):
            self.ranges_of[txn_id] = merged
            return  # ts/kind are txn-id-fixed; nothing device-visible changed
        self.owner._ensure_encoder(txn_id)
        Invariants.check_state(self.owner.encoder.in_window(txn_id),
                               "active range txn %s outside encoder window",
                               txn_id)
        self._set_rows(txn_id, merged, encoded)

    def invalidate(self, txn_id: TxnId) -> None:
        """Terminal: drop the txn's rows (a dep that never applies). The
        host's range map keeps max-conflict monotonicity, not the arena."""
        self.invalidated_ids.add(txn_id)
        self._drop_rows(txn_id)

    def truncate(self, txn_id: TxnId) -> None:
        """The owning store truncated its record of txn_id: the arena is per
        store, so the txn's whole row set retires (the old cross-store slice
        subtraction died with the shared node arena)."""
        if txn_id in self.ranges_of:
            self._drop_rows(txn_id)

    def _drop_rows(self, txn_id: TxnId) -> None:
        rows = self.rows_of.pop(txn_id, [])
        if rows:
            self.rseq += 1
        for r in rows:
            self.valid[r] = False
            self.ids_np[r] = None
            self._free.append(r)
            # a row the device never saw (still queued full) keeps its full
            # mark -- that upload carries valid=False
            if r not in self._dirty_full:
                self._dirty_valid.add(r)
        self.ranges_of.pop(txn_id, None)
        self._encoded_of.pop(txn_id, None)

    def _set_rows(self, txn_id: TxnId, merged: Ranges,
                  encoded: List[Tuple[int, int]]) -> None:
        old = self.rows_of.get(txn_id, [])
        # ensure capacity BEFORE mutating: compaction rebuilds from
        # ranges_of, so it must not run while this txn's rows are half-moved
        if len(self._free) + len(old) + (self.cap - self.count) \
                < len(encoded):
            self.compact()
            old = self.rows_of.get(txn_id, [])
        while len(self._free) + len(old) + (self.cap - self.count) \
                < len(encoded):
            self._grow()
        if old:
            self.rseq += 1
        for r in old:
            self.valid[r] = False
            self.ids_np[r] = None
            self._free.append(r)
            if r not in self._dirty_full:
                self._dirty_valid.add(r)
        enc3 = self.owner.encoder.encode_one(txn_id)
        rows = []
        for (s, e), r in zip(encoded, merged):
            row = self._free.pop() if self._free else self._alloc_tail()
            self.starts[row] = s
            self.ends[row] = e
            self.codes[row] = (_endpoint_code(r.start), _endpoint_code(r.end))
            self.ts[row] = enc3
            self.kinds[row] = int(txn_id.kind)
            self.valid[row] = True
            self.ids_np[row] = txn_id
            rows.append(row)
            self._dirty_full.add(row)
            self._dirty_valid.discard(row)
        self.rows_of[txn_id] = rows
        self.ranges_of[txn_id] = merged
        self._encoded_of[txn_id] = encoded

    def _alloc_tail(self) -> int:
        row = self.count
        self.count += 1
        return row

    def _grow(self) -> None:
        new_cap = self.cap * self.GROW
        ids = np.empty(new_cap, dtype=object)
        ids[:self.cap] = self.ids_np
        self.ids_np = ids
        self.starts = np.pad(self.starts, (0, new_cap - self.cap))
        self.ends = np.pad(self.ends, (0, new_cap - self.cap))
        self.codes = np.pad(self.codes, ((0, new_cap - self.cap), (0, 0)))
        self.ts = np.pad(self.ts, ((0, new_cap - self.cap), (0, 0)))
        self.kinds = np.pad(self.kinds, (0, new_cap - self.cap))
        self.valid = np.pad(self.valid, (0, new_cap - self.cap))
        self.cap = new_cap
        # tiny lanes: re-upload wholesale rather than arena_grow on device
        self._device = None

    def compact(self) -> bool:
        """Repack live rows densely, rebuilding from ranges_of (the
        authoritative host map). Returns False when that would reclaim less
        than half the capacity. Bumps `gen`; pinned in-flight calls keep the
        retiring row->txn snapshot for id-based candidate translation."""
        live = [(t, self._encoded_of[t]) for t in self.ranges_of]
        need = sum(len(e) for _, e in live)
        if need > self.cap // 2:
            return False
        if self._gen_pins.get(self.gen):
            self.retired_ids[self.gen] = self.ids_np[:self.count].copy()
        self.count = 0
        self.ids_np[:] = None
        self.rows_of = {}
        self._free = []
        self.starts[:] = 0
        self.ends[:] = 0
        self.codes[:] = 0
        self.ts[:] = 0
        self.kinds[:] = 0
        self.valid[:] = False
        for t, encoded in live:
            enc3 = self.owner.encoder.encode_one(t)
            rows = []
            for (s, e), r in zip(encoded, self.ranges_of[t]):
                row = self._alloc_tail()
                self.starts[row] = s
                self.ends[row] = e
                self.codes[row] = (_endpoint_code(r.start),
                                   _endpoint_code(r.end))
                self.ts[row] = enc3
                self.kinds[row] = int(t.kind)
                self.valid[row] = True
                self.ids_np[row] = t
                rows.append(row)
            self.rows_of[t] = rows
        self._device = None
        self._dirty_full = set()
        self._dirty_valid = set()
        self.gen += 1
        return True

    # -- in-flight generation pinning -----------------------------------------
    def pin_gen(self) -> int:
        self._gen_pins[self.gen] = self._gen_pins.get(self.gen, 0) + 1
        return self.gen

    def unpin_gen(self, gen: int) -> None:
        left = self._gen_pins.get(gen, 0) - 1
        if left > 0:
            self._gen_pins[gen] = left
        else:
            self._gen_pins.pop(gen, None)
            if gen != self.gen:
                self.retired_ids.pop(gen, None)

    def candidate_ids(self, gen: int, rows: np.ndarray) -> Optional[list]:
        """Packed-result rows (possibly addressed in a retired generation)
        -> deduped candidate txn ids, in row order. None when the snapshot
        is gone (the caller falls back to the host scan; counted)."""
        if gen == self.gen:
            ids = self.ids_np
        else:
            ids = self.retired_ids.get(gen)
            if ids is None:
                return None
            rows = rows[rows < ids.size]
        out = []
        seen = set()
        for r in rows:
            t = ids[r]
            if t is not None and t not in seen:
                seen.add(t)
                out.append(t)
        return out

    # -- device sync ----------------------------------------------------------
    def device_arrays(self):
        import jax.numpy as jnp
        from accord_tpu.ops.kernels import range_scatter
        if self._device is None:
            self._device = (
                jnp.zeros(self.cap, jnp.int32),
                jnp.zeros(self.cap, jnp.int32),
                jnp.zeros((self.cap, 3), jnp.int32),
                jnp.zeros(self.cap, jnp.int32),
                jnp.zeros(self.cap, bool),
            )
            self._dirty_full = set(range(self.count))
            self._dirty_valid.clear()
        if self._dirty_full:
            rows = sorted(self._dirty_full)
            for lo in range(0, len(rows), 64):
                chunk = rows[lo:lo + 64]
                m = 8 if len(chunk) <= 8 else 64
                idx = np.full(m, chunk[0], dtype=np.int32)
                idx[:len(chunk)] = chunk
                uploads = (idx, self.starts[idx], self.ends[idx],
                           self.ts[idx], self.kinds[idx], self.valid[idx])
                nb = sum(a.nbytes for a in uploads)
                self.upload_bytes += nb
                self.upload_bytes_by_field["range_full"] += nb
                self.upload_bytes_full_equiv += nb
                self._device = range_scatter(
                    *self._device, *(jnp.asarray(a) for a in uploads))
            self._dirty_valid -= self._dirty_full
            self._dirty_full.clear()
        if self._dirty_valid:
            from accord_tpu.ops.deltas import flush_lane

            def account(nbytes: int, m: int) -> None:
                self.upload_bytes += nbytes
                self.upload_bytes_by_field["range_valid"] += nbytes
                # all-lanes baseline: the same chunk as a full range_scatter
                self.upload_bytes_full_equiv += m * 29

            d = list(self._device)
            d[4] = flush_lane(d[4], sorted(self._dirty_valid), self.valid,
                              account)
            self._device = tuple(d)
            self._dirty_valid.clear()
        return self._device


class _Item:
    """One queued resolution (a PreAccept's deps or a standalone deps query)."""

    __slots__ = ("store", "txn_id", "owned", "before", "out", "outcome",
                 "cover_seq", "fallback")

    def __init__(self, store, txn_id, owned, before, out, outcome=None):
        self.store = store
        self.txn_id = txn_id
        self.owned = owned          # Keys or Ranges (the store's slice)
        self.before = before
        self.out = out              # AsyncResult
        self.outcome = outcome      # preaccept outcome (None for deps query)
        # set at encode time: covers younger than this were invisible to the
        # kernel snapshot, so the decode must not elide by them (the covering
        # write would be missing from the reply)
        self.cover_seq = 0
        # encode-time demotion (unencodable endpoints only): "full" answers
        # the whole item host-side, "range" answers just the range-dep
        # portion of a key subject host-side
        self.fallback: Optional[str] = None


class _Group:
    """One store's slice of a fused cross-store dispatch: its arena, the
    dispatch positions of its items, the generations the call encoded
    against, and the word-column spans of its blocks inside the concatenated
    packed results -- the per-store row-offset table that routes the fused
    readback back to each store's decode."""

    __slots__ = ("store", "arena", "idx", "items", "gen", "rgen",
                 "pinned", "rpinned", "pk", "rp", "kp",
                 "kseq", "rseq", "fin_dev", "fin_np", "fin_slots",
                 "rfin_dev", "rfin_np", "rents",
                 "rk_slots", "rkfin_dev", "rkfin_np",
                 "fin_mat", "rmat", "rk_mat", "fin_pos", "rtab")

    def __init__(self, store, arena):
        self.store = store
        self.arena = arena
        self.idx: List[int] = []      # positions in the dispatch's item list
        self.items: List[_Item] = []
        self.gen = arena.gen
        self.rgen = arena.ranges.gen
        self.pinned = False           # key-arena generation pin held
        self.rpinned = False          # range-arena generation pin held
        # (lo, hi) word-column spans into packed/rpacked/kpacked; None when
        # this store contributed no block to that buffer
        self.pk: Optional[Tuple[int, int]] = None
        self.rp: Optional[Tuple[int, int]] = None
        self.kp: Optional[Tuple[int, int]] = None
        # finalize_on_device state: the mutation-sequence snapshots the
        # harvest guards against, the deferred finalize kernels' device
        # (indptr, dep_rows, bound, csum) results + their host copies, and the
        # host-side routing tables the materialization walks
        self.kseq = arena.kseq
        self.rseq = arena.ranges.rseq
        self.fin_dev = None
        self.fin_np = None
        # (flat_key list, key_off) in legacy-decode slot order, or None
        # when this group planned no finalized key call; fin_pos: each
        # slot's key's place in its item's owned keys (the slot numbering
        # the key lane shares with the interval stab's point entries)
        self.fin_slots = None
        self.fin_pos = None
        self.rfin_dev = None
        self.rfin_np = None
        # [(global interval-CSR entry, local item index, key)] -- `key` is
        # _RSUB for a range subject's own interval pieces -- or None
        self.rents = None
        self.rtab = None              # _RentTable of rents, cut on first use
        # range-subject KEY-arena stab lane: [(local item index, key)] per
        # finalize_csr slot (empty list: planned with no covered arena
        # keys; None: not planned -- candidate fallback), plus its device
        # result and host copy
        self.rk_slots = None
        self.rkfin_dev = None
        self.rkfin_np = None
        # mutation-fence caches (_fence_finalized): lane results
        # pre-materialized under still-valid pins just before an arena
        # mutation would bump the sequence guards -- plain host objects,
        # immune to the mutation, consumed by _decode_core at harvest.
        # The range-side lanes cache stage 1 only (a _Stab: the hit
        # (entry, row) arrays and the row tables as pinned then); their
        # host-map filters run at harvest
        self.fin_mat = None           # key lane: [KeyDeps] per item
        self.rmat = None              # range lane: _Stab over rents
        self.rk_mat = None            # rk lane: _Stab over rk_slots


class _RentTable:
    """g.rents as arrays, one element an entry: its interval-CSR position
    `e`, its item `j`, its place `pos` among its item's entries (a key
    subject's are 1:1 with its owned keys, a range subject's with its owned
    ranges, both in owned order), `sub` (a range subject's own piece, the
    _RSUB entries) and there the piece's endpoint codes `cs`, `ce`."""

    __slots__ = ("e", "j", "pos", "sub", "cs", "ce")


class _Stab:
    """Stage 1 of a finalized range-side lane as arrays over the whole
    dispatch: one (slot, row) pair a dependency the device delivered --
    `slot` indexes the lane's routing table (g.rents / g.rk_slots), `row`
    the arena's rows -- with every subject's own rows and freed rows
    masked out, beside the arena's row tables (`ids`, the three `ts` lanes,
    `live`; None: every row) as they stood while the lane's pins held:
    copies, so a fence's cache outlives the mutation it ran ahead of. An
    interval-stab lane also carries each pair's intersection as endpoint
    codes (`xs`, `xe`; read on a range subject's own pieces only)."""

    __slots__ = ("slot", "row", "ids", "ts", "live", "xs", "xe")

    def __init__(self):
        self.live = self.xs = self.xe = None

    def take(self, keep: np.ndarray) -> None:
        self.slot = self.slot[keep]
        self.row = self.row[keep]
        if self.xs is not None:
            self.xs = self.xs[keep]
            self.xe = self.xe[keep]


def _joint_rank(tables):
    """One TxnId order over the rows of several arenas' tables [(ids, ts
    lanes, live mask or None)]: the node encoder's three lanes are common
    to a node's arenas and order as TxnIds do. -> ([rank by row, one array
    a table; -1 on a dead row], ids by rank). One txn in two tables shares
    a rank, so an answer that joins two arenas dedupes by it."""
    ids = np.concatenate([t[0] for t in tables])
    ts = np.concatenate([t[1] for t in tables])
    live = np.concatenate([np.ones(len(t[0]), bool) if t[2] is None
                           else t[2] for t in tables])
    rows = np.flatnonzero(live)
    lts = ts[rows]
    rows = rows[np.lexsort((lts[:, 2], lts[:, 1], lts[:, 0]))]
    sts = ts[rows]
    new = np.ones(rows.size, bool)
    new[1:] = (sts[1:] != sts[:-1]).any(axis=1)
    rank = np.full(ids.size, -1, np.int64)
    rank[rows] = np.cumsum(new) - 1
    cuts = np.cumsum([len(t[0]) for t in tables])[:-1]
    return np.split(rank, cuts), ids[rows[new]]


def _sort_entries(slot: np.ndarray, rank: np.ndarray, r: int):
    """(slot, rank) pairs in (slot, rank) order, each once: one sort of
    one int64 key a pair (`r` bounds the ranks from above). Step 6 of the
    batch decode for every lane: the key lane's pairs through
    _assemble_key_deps, the range lanes' and a range-state store's key
    subjects through their builders."""
    key = slot * r + rank
    key.sort()
    if key.size > 1:
        dup = key[1:] == key[:-1]
        if dup.any():
            key = key[np.r_[True, ~dup]]
    e_slot = key // r
    return e_slot, key - e_slot * r


def _cut_csr(e_slot: np.ndarray, e_rank: np.ndarray, slot_off: np.ndarray,
             by_rank: np.ndarray, row_objects, make, out) -> None:
    """Per-item CSR assembly from sorted (slot, rank) pairs, step 8 of the
    batch decode for either domain, as one cut over the whole dispatch:
    item i owns the slots [slot_off[i], slot_off[i+1]); its rows are the
    slots present (their keys or Ranges, made once a present slot by
    `row_objects(slots)` -> list), its dictionary the ranks present, in
    TxnId order (`by_rank`: txn ids by rank). out[i] = make(rows, txn_ids,
    offsets, value_idx) where the item has any pair.

    Every pair's item, every item's dictionary (one sort of item * R + rank
    and a flag-diff), every pair's place in its item's dictionary and every
    row's offset from its item's first pair are computed as arrays over
    the dispatch. The ids and the offsets leave numpy through one tolist
    each; value_idx, the one lane as long as the pairs, through one
    memoryview, so that an item's ints are made as its tuple is (a list of
    every pair's int, made whole and sliced after, costs a second and a
    third pass over objects long out of the cache). The per-item body only
    slices those; the count of numpy calls does not depend on the items."""
    if not e_slot.size:
        return
    n = len(slot_off) - 1
    r = len(by_rank)
    assert n * r < 1 << 62, "item * R + rank must fit int64"
    new = np.ones(e_slot.size, bool)
    new[1:] = e_slot[1:] != e_slot[:-1]
    first = np.flatnonzero(new)
    rows = row_objects(e_slot[first])
    bounds = np.searchsorted(e_slot, slot_off)
    row_at = np.searchsorted(first, bounds)
    cnt = np.diff(bounds)
    item = np.repeat(np.arange(n), cnt)
    # the dictionaries: the distinct (item, rank) in order (`new` now flags
    # the first of each), and each pair's place among its item's (`place`
    # counts from 1 over the dispatch, d_at + 1 brings it to the item's 0)
    key = item * r + e_rank
    o = np.argsort(key)
    key = key[o]
    new[1:] = key[1:] != key[:-1]
    key = key[new]
    place = np.empty(o.size, np.int64)
    place[o] = np.cumsum(new)
    d_item = key // r
    d_at = np.searchsorted(d_item, np.arange(n + 1))
    inv = memoryview(place - (d_at + 1)[item])
    ids = by_rank[key - d_item * r].tolist()
    # row offsets from the item's first pair, the item's pair count closing
    # each item's run: item i's offsets are offs[row_at[i] + i :
    # row_at[i + 1] + i + 1]
    offs = np.insert(first - bounds[item[first]], row_at[1:], cnt).tolist()
    live = np.flatnonzero(cnt).tolist()
    bounds = bounds.tolist()
    row_at = row_at.tolist()
    d_at = d_at.tolist()
    for i in live:
        ra, rb = row_at[i], row_at[i + 1]
        out[i] = make(tuple(rows[ra:rb]), tuple(ids[d_at[i]:d_at[i + 1]]),
                      tuple(offs[ra + i:rb + i + 1]),
                      tuple(inv[bounds[i]:bounds[i + 1]]))


def _dev_ready(dev) -> bool:
    """is_ready over a device value that may be a tuple (the finalize
    kernels return (indptr, dep_rows, bound, csum))."""
    if isinstance(dev, tuple):
        return all(b.is_ready() for b in dev)
    return dev.is_ready()


def _checked_words(buf):
    """The words of a fetched finalize lane that its checksum covers and
    its decode reads: indptr whole (a corrupted total still fails the
    check) and dep_rows up to the total, or whole on overflow. The device
    folds the whole dep_rows lane, which is 0 past the total, and a 0 word
    folds to 0, so the host's prefix fold matches it bit for bit."""
    indptr, dep_rows = buf[0], buf[1]
    n = min(max(int(indptr[-1]), 0), dep_rows.shape[0])
    return indptr, dep_rows[:n]


def _dev_read(dev):
    if isinstance(dev, tuple):
        return tuple(np.asarray(b) for b in dev)
    return np.asarray(dev)


def _dev_copy_async(dev) -> None:
    if isinstance(dev, tuple):
        for b in dev:
            b.copy_to_host_async()
    else:
        dev.copy_to_host_async()


class _Done:
    """When a call's outputs were ready on the device, on the host clock
    (None until learned): all the occupancy account and the completion
    waiter keep of a call, so neither holds its results or its answers."""

    __slots__ = ("done_at",)

    def __init__(self):
        self.done_at: Optional[float] = None


class _CompletionWaiter:
    """When each launched call finished on the device, on the host clock:
    one thread, alive only while calls wait, probes each call's outputs in
    launch order (`is_ready()`, between sleeps of PROBE_S with the GIL
    released) and stamps its `done_at`, late by at most PROBE_S plus the
    interpreter's switch interval. It does not block in
    `jax.block_until_ready`: on the chip that wait burns host CPU for as
    long as the device runs (PERF.md, PR 37). It touches nothing else: the
    stamps feed only the occupancy account. A call whose buffer is gone
    counts as done when the thread learns it; a call the host has landed
    (fetched, or given up on) is stamped at its landing, and the thread
    asks nothing more of its buffers, so a wedged call never holds up the
    harvest's `join`."""

    PROBE_S = 0.001

    def __init__(self):
        self._queue: deque = deque()
        self._lock = threading.Lock()
        self._thread: Optional[threading.Thread] = None

    def add(self, call: "_Call") -> None:
        devs = [b for _, _, dev in call.buffers()
                for b in (dev if isinstance(dev, tuple) else (dev,))]
        with self._lock:
            self._queue.append((call.done, devs))
            if self._thread is None:
                self._thread = threading.Thread(
                    target=self._run, name="resolver-completion", daemon=True)
                self._thread.start()

    def _run(self) -> None:
        while True:
            with self._lock:
                if not self._queue:
                    self._thread = None
                    return
                done, devs = self._queue[0]
            # is_ready() on a deleted buffer is not safe to ask
            if done.done_at is None and not all(
                    b.is_deleted() or b.is_ready() for b in devs):
                time.sleep(self.PROBE_S)
                continue
            self.stamp(done, time.perf_counter())
            with self._lock:
                self._queue.popleft()
            done = devs = None  # hold nothing the harvest has let go

    def stamp(self, done: _Done, at: float) -> None:
        """Keep the earliest clock reading known to follow the call's
        completion (the thread and the landing both write it)."""
        with self._lock:
            if done.done_at is None or at < done.done_at:
                done.done_at = at

    def join(self) -> None:
        """Wait for the thread to stamp what it holds and end."""
        with self._lock:
            thread = self._thread
        if thread is not None:
            thread.join()


class _Call:
    """One in-flight kernel dispatch: up to three device result buffers
    (key-domain deps, range-arena candidates, key-arena candidates for range
    subjects) plus each group's finalized-CSR results, the per-store groups
    whose spans slice them, and the generation pins needed to decode after a
    compaction (held per group, so one store compacting never disturbs a
    batchmate). `want` flags which RAW candidate buffers the harvest reads
    back: the finalized path leaves packed/rpacked device-resident (harvest
    reads only the compacted CSR) unless a guard trips, in which case the
    fallback fetches them lazily -- blocking, and counted as readback."""

    __slots__ = ("packed", "rpacked", "kpacked", "items", "groups",
                 "np_packed", "np_rpacked", "np_kpacked", "want", "did",
                 "stuck_left", "corrupt_pending", "overflow_pending",
                 "degraded", "faulted", "canary", "landed", "done")

    def __init__(self, packed, rpacked, kpacked, items, groups,
                 want=(True, True, True), did=-1):
        self.packed = packed        # fused key-domain result (or None)
        self.rpacked = rpacked      # fused range-arena result
        self.kpacked = kpacked      # fused key-arena hull result
        self.items = items
        self.groups: List[_Group] = groups
        self.want = want
        # host copies, filled by the poll prefetch once the device finishes
        # (or by a blocking read at harvest when it hasn't)
        self.np_packed: Optional[np.ndarray] = None
        self.np_rpacked: Optional[np.ndarray] = None
        self.np_kpacked: Optional[np.ndarray] = None
        # monotone dispatch id (per resolver): keys this call's device-
        # window span in the flight recorder (-1: sync path, untraced)
        self.did = did
        # device-plane fault state (ops/fault_plane.py): pending injected
        # faults to consume at harvest, whether the call was given up on
        # (decode answers host-side), whether any fault landed on it (the
        # health ladder's clean-dispatch gate), and whether this dispatch
        # is a probation canary
        self.stuck_left = 0
        self.corrupt_pending = False
        self.overflow_pending = False
        self.degraded = False
        self.faulted = False
        self.canary = False
        # occupancy account: counted in flight from launch until its
        # results are on the host (BatchDepsResolver._land); `done` says
        # when its outputs were ready on the device, once _CompletionWaiter
        # learns it, or at the latest when the call lands
        self.landed = False
        self.done = _Done()

    def buffers(self):
        """(holder, host attr, device value) triples the async-copy / poll /
        fetch machinery drains: the wanted raw candidate buffers plus every
        group's finalized-CSR results."""
        out = []
        for (attr, buf), w in zip(
                (("np_packed", self.packed), ("np_rpacked", self.rpacked),
                 ("np_kpacked", self.kpacked)), self.want):
            if w and buf is not None:
                out.append((self, attr, buf))
        for g in self.groups:
            if g.fin_dev is not None:
                out.append((g, "fin_np", g.fin_dev))
            if g.rfin_dev is not None:
                out.append((g, "rfin_np", g.rfin_dev))
            if g.rkfin_dev is not None:
                out.append((g, "rkfin_np", g.rkfin_dev))
        return out

    @property
    def has_device(self) -> bool:
        return self.packed is not None or self.rpacked is not None

    def fetch(self, read) -> bool:
        """Blocking read, through `read` (BatchDepsResolver._read), of any
        result the poll didn't drain; True if it actually had to read (the
        harvest stall case)."""
        stalled = False
        for holder, attr, dev in self.buffers():
            if getattr(holder, attr) is None:
                setattr(holder, attr, read(dev))
                stalled = True
        return stalled


class _Plan:
    """One ENCODED-BUT-NOT-LAUNCHED dispatch (the staged tick pipeline's
    hand-off between stage_host and stage_dispatch): the deferred kernel
    launches -- closures over the plan-time arena snapshots and the
    already-uploaded subject arrays -- plus the items/groups the harvest
    will decode. jax arrays are immutable, so the snapshots captured at
    encode time are frozen: scatters, growth, and compaction after the plan
    is cut all build NEW device arrays, and the deferred launch still runs
    against exactly the state this tick's preaccept registrations produced.
    `empty` plans (nothing on device to conflict with) carry no launches
    but still flow through the pipeline so floors and fallbacks inject at
    harvest."""

    __slots__ = ("items", "groups", "key_call", "range_call", "empty",
                 "fused", "range_groups", "fin_calls", "rfin_calls",
                 "kfin_calls", "want", "key_args", "range_args",
                 "fin_args", "rfin_args", "kfin_args")

    def __init__(self, items: List[_Item], groups: List[_Group],
                 empty: bool = False):
        self.items = items
        self.groups = groups
        self.key_call = None        # () -> packed, or None
        self.range_call = None      # () -> (rpacked, kpacked), or None
        self.empty = empty
        # a deferred call above runs a fused cross-store program (several
        # store groups, routed by the store-id lane)
        self.fused = False
        # the store groups with a block in range_call's fused program
        # (fused_range_deps_resolve); 0 where it runs the plain kernel
        self.range_groups = 0
        # node-lane merge inputs (ops/node_lane.py): the EXACT arrays the
        # deferred calls above would feed their kernels, recorded only when
        # a cluster tick_driver is attached -- the mesh-burn engine stacks
        # them across nodes and swaps key_call/range_call for demux slices
        # of the merged result
        self.key_args = None
        self.range_args = None
        # finalize_on_device: deferred finalize kernel launches per group --
        # the key call consumes the packed result, the range call closes
        # over its group's interval-arena snapshot
        self.fin_calls: List[tuple] = []    # [(group, packed -> result)]
        self.rfin_calls: List[tuple] = []   # [(group, () -> result)]
        # range-subject key-arena stab lane: consumes the kpacked result
        self.kfin_calls: List[tuple] = []   # [(group, kpacked -> result)]
        # raw finalize lanes per deferred call above (index-aligned with
        # fin_calls/rfin_calls/kfin_calls), recorded only under a cluster
        # tick_driver: the megakernel folds them into the fused
        # protocol_tick program and swaps the closures for its outputs
        self.fin_args: List[tuple] = []
        self.rfin_args: List[tuple] = []
        self.kfin_args: List[tuple] = []
        # which raw candidate buffers the harvest should read back
        self.want = (True, True, True)


class BatchDepsResolver(DepsResolver):
    MAX_DISPATCH = 128  # subjects per kernel call (a named, warmable jit tier)

    # bench counters -- descriptors proxying onto self.metrics, so every
    # legacy `resolver.dispatches` read/write is a registry cell and
    # `snapshot()` is the single source for bench JSON (obs/metrics.py)
    dispatches = RegCounter("resolver.dispatches")
    subjects = RegCounter("resolver.subjects")
    ticks = RegCounter("resolver.ticks")             # node ticks with items
    preaccept_s = RegTimer("resolver.preaccept_s")   # host preaccepts
    encode_s = RegTimer("resolver.encode_s")         # upload-array build
    dispatch_s = RegTimer("resolver.dispatch_s")     # launch + readback enq
    harvest_stall_s = RegTimer("resolver.harvest_stall_s")  # blocking xfers
    decode_s = RegTimer("resolver.decode_s")         # result materialization
    readback_s = RegTimer("resolver.readback_s")     # device->host transfer
    # readback_s split: waiting for the kernels, then for the copy; and the
    # bytes that reached the host
    device_wait_s = RegTimer("resolver.device_wait_s")
    transfer_s = RegTimer("resolver.transfer_s")
    readback_bytes = RegCounter("resolver.readback_bytes")
    materialize_s = RegTimer("resolver.materialize_s")  # decode minus readback
    staged_dispatches = RegCounter("resolver.staged_dispatches")
    prefetched = RegCounter("resolver.prefetched")   # poll-drained transfers
    polls_armed = RegCounter("resolver.polls_armed")
    stale_harvests = RegCounter("resolver.stale_harvests")  # cross-compaction
    host_fallbacks = RegCounter("resolver.host_fallbacks")  # unpinned + stale
    # subjects demoted host-side for unencodable range endpoints (never
    # hit by integer key domains)
    range_fallbacks = RegCounter("resolver.range_fallbacks")
    # finalized-CSR harvest accounting: groups materialized straight from
    # the compacted device CSR vs groups through the legacy unpackbits
    # decode (finalize off, or a guard tripped -- the latter also counted
    # as finalize_fallbacks)
    finalized_decodes = RegCounter("resolver.finalized_decodes")
    legacy_decodes = RegCounter("resolver.legacy_decodes")
    finalize_fallbacks = RegCounter("resolver.finalize_fallbacks")
    # out-cap tier policy (ops/tiers.OutCapTiers): pinned-tier changes
    # across every finalize lane, and the host cost of folding the
    # device-computed bound back into the policy at harvest
    outcap_tier_switches = RegCounter("resolver.outcap_tier_switches")
    bound_readback_s = RegTimer("resolver.bound_readback_s")
    # range subjects whose deps materialized straight from the device stab
    # lanes (no host candidate re-filter)
    range_subject_device_decodes = RegCounter(
        "resolver.range_subject_device_decodes")
    # the range path's own share of encode_s and decode_s (the interval CSR
    # and the range / rk finalize plans; both stages of the range harvest,
    # the rk lane's, and the key subjects' range-txn deps), and what it
    # handled: range-domain subjects and their interval pieces encoded, and
    # range-vs-range dependencies delivered, one per (intersection, txn)
    range_encode_s = RegTimer("resolver.range_encode_s")
    range_decode_s = RegTimer("resolver.range_decode_s")
    range_subjects = RegCounter("resolver.range_subjects")
    range_intervals = RegCounter("resolver.range_intervals")
    range_deps = RegCounter("resolver.range_deps")
    # groups whose range lanes decoded as arrays over the dispatch, and
    # those among them that applied stage 2's host-map filters a
    # dependency at a time (a fenced cache whose guards broke since)
    range_array_decodes = RegCounter("resolver.range_array_decodes")
    range_filtered_decodes = RegCounter("resolver.range_filtered_decodes")
    # calls of _cut_csr, the whole-dispatch cut: one a domain a group
    array_cuts = RegCounter("resolver.array_cuts")
    # the node's fan-out as the device sees it: dispatches that ran a fused
    # cross-store program, and the store groups that rode them
    fused_dispatches = RegCounter("resolver.fused_dispatches")
    store_groups = RegCounter("resolver.store_groups")
    # the same for the range call alone: dispatches that carried one, those
    # whose range call ran fused_range_deps_resolve, and the store groups
    # with a block in it
    range_dispatches = RegCounter("resolver.range_dispatches")
    fused_range_dispatches = RegCounter("resolver.fused_range_dispatches")
    fused_range_groups = RegCounter("resolver.fused_range_groups")
    # the store's lifecycle, each under its own span. The arenas count the
    # first three groups into this registry themselves: device sync
    # (resolver.arena_sync: dirty rows shipped by device_arrays(), dirty
    # words by kid_arrays(); rows of any lane, device scatter calls, and
    # those among them that rewrote lanes the sync owned, in place),
    # compaction (resolver.compact: attempts timed, rebuilds counted with
    # the rows they kept) and growth (resolver.grow: host lanes and the
    # on-device pad). Then the mutation fence (resolver.fence: finalized
    # lanes materialized ahead of a truncation or prune) and the
    # truncations themselves (resolver.truncate, which holds the fence:
    # on_truncate and on_prune, and the rows they emptied)
    arena_sync_s = RegTimer("resolver.arena_sync_s")
    arena_rows_uploaded = RegCounter("resolver.arena_rows_uploaded")
    arena_upload_calls = RegCounter("resolver.arena_upload_calls")
    arena_scatters_donated = RegCounter("resolver.arena_scatters_donated")
    compact_s = RegTimer("resolver.compact_s")
    arena_compactions = RegCounter("resolver.arena_compactions")
    compact_rows_kept = RegCounter("resolver.compact_rows_kept")
    grow_s = RegTimer("resolver.grow_s")
    arena_growths = RegCounter("resolver.arena_growths")
    fence_s = RegTimer("resolver.fence_s")
    fence_materializes = RegCounter("resolver.fence_materializes")
    truncate_s = RegTimer("resolver.truncate_s")
    truncated_txns = RegCounter("resolver.truncated_txns")
    # host launch time of the sharded finalize compaction (per-shard
    # popcount/prefix + gather-merge) on multi-device meshes
    shard_merge_s = RegTimer("resolver.shard_merge_s")
    # device-plane fault tolerance (ops/fault_plane.py): applied fault
    # injections, bounded launch retries + harvest re-probes, watchdog
    # trips on wedged calls, checksum-lane catches before decode, and the
    # health ladder's traffic (host-routed dispatches, quarantine
    # entries/exits, probation canaries)
    device_faults_injected = RegCounter("resolver.device_faults_injected")
    device_retries = RegCounter("resolver.device_retries")
    device_watchdog_trips = RegCounter("resolver.device_watchdog_trips")
    checksum_mismatches = RegCounter("resolver.checksum_mismatches")
    degraded_dispatches = RegCounter("resolver.degraded_dispatches")
    quarantine_entries = RegCounter("resolver.quarantine_entries")
    quarantine_exits = RegCounter("resolver.quarantine_exits")
    device_canaries = RegCounter("resolver.device_canaries")
    # PreAccept spans a store's cmd plane raised on, replayed through the
    # Python handlers (fault-free runs hold this at zero)
    cmd_span_replays = RegCounter("resolver.cmd_span_replays")

    def __init__(self, num_buckets: int = 256, initial_cap: int = 4096,
                 max_dispatch: Optional[int] = None,
                 pad_store_tiers: Optional[int] = None,
                 finalize_on_device: bool = True,
                 adaptive_window: bool = False,
                 kid_cap: int = 4096,
                 initial_range_cap: int = 64,
                 verify_checksums: bool = True,
                 retry_limit: int = 2,
                 watchdog_probes: int = 3,
                 watchdog_wall_s: Optional[float] = None,
                 health_config: Optional[dict] = None,
                 pad_node_tiers=None):
        # the registry backing every bench counter below (the class-level
        # RegCounter/RegTimer descriptors write through to it), BEFORE any
        # counter touch
        self.metrics = MetricsRegistry()
        # the occupancy account (obs/trace.py): time with work pending and
        # no call in flight is the device starved by the host, time with
        # every call in flight finished on the device (the waiter's stamps)
        # and not yet fetched is the device drained; each is credited to
        # resolver.{starved,drained}_{stage,decode,outside}_s by the phase
        # the host was in -- the tick path (preaccept, encode, launch), the
        # harvest (decode), or neither (the caller's enqueue loop, the
        # batch-window timer, the event queue, a store's wave)
        self._waiter = _CompletionWaiter()
        self._occ = Occupancy(self.metrics, "resolver", {
            "resolver.tick": "stage", "resolver.preaccept": "stage",
            "resolver.encode": "stage", "resolver.launch": "stage",
            "resolver.harvest": "decode"})
        # every host phase of the pipeline goes through this one primitive:
        # registry timer, flight-recorder span, profiler annotation, account
        self._phase = functools.partial(phase, self.metrics,
                                        account=self._occ)
        # the collector's pauses while work is pending, into this registry
        # (obs/trace.py)
        watch_collector(self.metrics, self._occ)
        # the range kernel's covered-bucket contraction reduces intervals
        # modulo the bucket count with int32 arithmetic; that wrap is exact
        # only when num_buckets divides 2^32
        Invariants.check_argument(
            num_buckets > 0 and num_buckets & (num_buckets - 1) == 0,
            "num_buckets %s must be a power of two (covered-bucket "
            "contraction relies on int32 modular wrap)", num_buckets)
        # each dispatch pays one launch and one readback at harvest, so
        # larger dispatches amortize them; the default stays small to
        # bound jit tiers in tests
        self.max_dispatch = max_dispatch or self.MAX_DISPATCH
        # opt-in: pad fused cross-store dispatches to a fixed store tier
        # with cached empty arena blocks so many-store nodes compile ONE
        # jit tier instead of one per participating-store count
        self.pad_store_tiers = pad_store_tiers
        # True (default): the deps kernels' bucket-level results run through
        # finalize_csr / range_finalize_csr on device -- exact key filtering
        # + segment compaction -- so harvest reads back one contiguous
        # (indptr, dep_rows) CSR per store instead of the full bit
        # matrices. False: every group takes the legacy unpackbits decode,
        # which is the automatic per-group fallback when a sequence guard
        # trips mid-flight and the other side of the probation canary's
        # double decode -- the switch tests use to drive that fallback over
        # a whole workload.
        self.finalize_on_device = finalize_on_device
        # one tier policy per (arena, finalize lane): per-slot mean bounds
        # are arena-contention properties, not resolver globals
        self._octiers: Dict[tuple, "OutCapTiers"] = {}
        # opt-in: scale each node's staged dispatch window by drain
        # pressure (empty drains shrink it, full drains widen it)
        self.adaptive_window = adaptive_window
        self._win_scale: Dict[int, float] = {}
        # initial key-id capacity of each arena's device key-mask mirror
        self.kid_cap = kid_cap
        import jax.numpy as jnp
        self.num_buckets = num_buckets
        self.initial_cap = initial_cap
        self._table = jnp.asarray(WITNESS_TABLE)
        self._arenas: Dict[int, _StoreArena] = {}
        self._encoders: Dict[int, _NodeEncoder] = {}
        self._pa_queues: Dict[int, list] = {}
        self._deps_queues: Dict[int, list] = {}
        self._ticking: set = set()
        # per-node IN-ORDER queue of in-flight calls; each dispatch schedules
        # exactly one harvest event, which pops the head
        self._inflight: Dict[int, "deque[_Call]"] = {}
        self._polling: set = set()
        # per-node encode-ahead stage: plans cut by the last tick's
        # stage_host, launched by the NEXT tick's stage_dispatch
        self._staged: Dict[int, List[_Plan]] = {}
        # last batch window seen per node, for the self-armed launch tick
        self._windows: Dict[int, float] = {}
        # cached empty arena blocks for pad_store_tiers, keyed by capacity
        # (the pool grows alongside arenas that outgrow initial_cap)
        self._pad_key: Dict[int, tuple] = {}
        self._pad_range: Dict[int, tuple] = {}
        # initial _RangeArena capacity, every store's alike (the fused range
        # program compiles for the capacities of its arenas; the sharded
        # resolver widens it to keep rcap % (32*data) == 0)
        self.range_cap = initial_range_cap
        # device-plane fault tolerance: re-derive the finalize kernels'
        # fused checksum word from the host copies at harvest (a corrupted
        # readback can never decode into wrong deps -- it falls back to the
        # legacy decode of the raw candidate buffers); bounded launch
        # retries; a harvest watchdog with a deterministic probe budget
        # (plus an optional wall budget for real devices -- None keeps sim
        # runs free of wall-clock-dependent state); and one DeviceHealth
        # ladder per node (HEALTHY -> DEGRADED -> QUARANTINED -> PROBATION)
        self.verify_checksums = verify_checksums
        self.retry_limit = retry_limit
        self.watchdog_probes = watchdog_probes
        self.watchdog_wall_s = watchdog_wall_s
        self.health_config = health_config
        self._health: Dict[int, "DeviceHealth"] = {}
        # cluster-on-mesh burn (sim/mesh_burn.py): when a ClusterTickEngine
        # attaches itself here, tick scheduling routes through it (one
        # cluster-wide tick event instead of per-node once() arms) and
        # _encode_plan records each plan's kernel inputs for the node-lane
        # merge; pad_node_tiers is the block-count ladder the merge pads to
        # (None -> node_lane.NODE_BLOCK_TIERS) so node churn never mints a
        # new jit tier
        self.tick_driver = None
        self.pad_node_tiers = pad_node_tiers

    @property
    def device(self):
        """The jax device JAX placed this resolver's arrays on."""
        return next(iter(self._table.devices()))

    @property
    def upload_bytes(self) -> int:
        """Total bytes shipped host->device by arena dirty-row scatters."""
        return sum(a.upload_bytes + a.ranges.upload_bytes
                   for a in self._arenas.values())

    @property
    def upload_bytes_by_field(self) -> Dict[str, int]:
        """upload_bytes broken out per field group: `full` rows carry every
        lane; `keys`/`ts`/`valid` (and `range_full`/`range_valid`) are the
        field-granular deltas."""
        agg = {"full": 0, "keys": 0, "ts": 0, "valid": 0, "kids": 0,
               "range_full": 0, "range_valid": 0}
        for a in self._arenas.values():
            for k, v in a.upload_bytes_by_field.items():
                agg[k] += v
            for k, v in a.ranges.upload_bytes_by_field.items():
                agg[k] += v
        return agg

    @property
    def upload_bytes_full_equiv(self) -> int:
        """Bytes the retired all-lanes scatter would have shipped for the
        same dirty sets -- the baseline proving the granular deltas' win."""
        return sum(a.upload_bytes_full_equiv
                   + a.ranges.upload_bytes_full_equiv
                   for a in self._arenas.values())

    def snapshot(self) -> dict:
        """Flat registry snapshot plus the arena-computed gauges -- the
        single source for bench JSON and metrics dumps."""
        snap = self.metrics.snapshot()
        snap["resolver.pending"] = self._occ.pending
        snap["resolver.upload_bytes"] = self.upload_bytes
        snap["resolver.upload_bytes_full_equiv"] = self.upload_bytes_full_equiv
        for k, v in self.upload_bytes_by_field.items():
            snap[f"resolver.upload_bytes.{k}"] = v
        return snap

    # -- finalize out-cap policy ----------------------------------------------
    def _note_tier_switch(self) -> None:
        self.outcap_tier_switches += 1

    def _outcap(self, arena, lane: str):
        """The OutCapTiers policy pinning `lane`'s finalize out_cap for
        `arena` (lanes: "key" subject deps, "range" interval stabs, "rkey"
        range-subject key-arena stabs)."""
        pol = self._octiers.get((id(arena), lane))
        if pol is None:
            from accord_tpu.ops.kernels import OUT_TIER_FLOOR, OUT_TIERS
            from accord_tpu.ops.tiers import OutCapTiers
            pol = self._octiers[(id(arena), lane)] = OutCapTiers(
                OUT_TIERS, OUT_TIER_FLOOR, on_switch=self._note_tier_switch)
        return pol

    def _run_finalize_kernel(self, packed, j_off, kid_rows, j_subj, j_kid,
                             j_srow, out_cap: int):
        """The finalize_csr launch point; the sharded resolver overrides it
        with the mesh-compacted twin (per-shard counts + gather-merge)."""
        from accord_tpu.ops.kernels import finalize_csr
        return finalize_csr(packed, j_off, kid_rows, j_subj, j_kid, j_srow,
                            out_cap=out_cap)

    # -- device health + fault handling ---------------------------------------
    def _node_health(self, node) -> "DeviceHealth":
        """The node's DeviceHealth ladder, created on first fault (healthy
        runs never allocate one -- _health.get() elsewhere stays None)."""
        h = self._health.get(id(node))
        if h is None:
            from accord_tpu.ops.fault_plane import DeviceHealth
            cfg = self.health_config or {}
            h = self._health[id(node)] = DeviceHealth(
                on_transition=lambda old, new:
                    self._health_transition(node, old, new), **cfg)
        return h

    def _health_transition(self, node, old: str, new: str) -> None:
        from accord_tpu.ops import fault_plane as fp
        if new == fp.QUARANTINED:
            self.quarantine_entries += 1
        if old == fp.PROBATION and new == fp.HEALTHY:
            self.quarantine_exits += 1
        if REC.enabled:
            REC.instant(node_pid(node), "device", f"health:{old}->{new}",
                        node_ts(node), args={"from": old, "to": new})

    def _csum_ok(self, call: "_Call", g: "_Group", buf) -> bool:
        """Harvest-side integrity check of one finalized lane: re-derive
        the fused checksum word from the fetched host copies. A mismatch
        (corrupted readback) is counted, drives the node's health ladder,
        and returns False so the caller routes the group to the legacy
        fallback -- wrong deps are never delivered. The trailing bound
        word is NOT covered: it only feeds the out-cap sizing policy,
        which self-corrects through the overflow bump."""
        if not self.verify_checksums:
            return True
        from accord_tpu.ops.kernels import csr_checksum_host
        if csr_checksum_host(*_checked_words(buf)) == int(buf[-1]):
            return True
        self.checksum_mismatches += 1
        call.faulted = True
        node = g.store.node
        self._node_health(node).on_fault("corrupt")
        if REC.enabled:
            REC.instant(node_pid(node), "device", "checksum_mismatch",
                        node_ts(node), args={"did": call.did})
        return False

    def _apply_corruption(self, call: "_Call", plane) -> None:
        """Consume a pending corrupt injection: flip one bit of the words
        the checksum covers in the first fetched finalize lane's host copy
        (writable clones -- the fetched arrays may be read-only views of
        device buffers). Dropped when the call carried no finalized lane
        (nothing checksummed to corrupt)."""
        for g in call.groups:
            for attr in ("fin_np", "rfin_np", "rkfin_np"):
                buf = getattr(g, attr)
                if buf is None:
                    continue
                arrs = tuple(np.array(a) for a in buf[:2])
                if plane.corrupt_arrays(_checked_words(arrs)):
                    setattr(g, attr, arrs + tuple(buf[2:]))
                    self.device_faults_injected += 1
                    return
        # no finalized buffer on this call: injection dropped, uncounted

    def _canary_check(self, call: "_Call", g: "_Group", kds) -> None:
        """Probation canary: re-decode this group's key lane through the
        legacy unpackbits path against the SAME plan-time snapshot (lazy
        raw-buffer fetch; warmed tiers, zero recompiles) and compare. A
        match walks the health ladder toward HEALTHY; a divergence means
        the device compaction itself is untrustworthy -- straight back to
        quarantine. The finalized result is still delivered either way:
        the sequence guards + checksum already certify it bit-identical
        to the guarded decode, so histories stay fault-free-identical."""
        if call.packed is None and call.np_packed is None:
            return
        if g.pk is None:
            return
        self.device_canaries += 1
        buf = self._fetch_np(call, "np_packed", call.packed)
        if buf is None:
            return
        idx = np.asarray(g.idx, np.int64)
        gp = buf[idx][:, g.pk[0]:g.pk[1]]
        legacy = self._decode_batch(g.arena, g.items, gp)
        h = self._node_health(g.store.node)
        if list(legacy) == list(kds):
            h.canary_ok()
        else:
            call.faulted = True
            h.canary_failed()

    # -- arena plumbing -------------------------------------------------------
    def _arena(self, store) -> _StoreArena:
        arena = self._arenas.get(id(store))
        if arena is None:
            enc = self._encoders.get(id(store.node))
            if enc is None:
                enc = self._encoders[id(store.node)] = _NodeEncoder()
            arena = _StoreArena(self.num_buckets, self.initial_cap,
                                self.range_cap, shared_encoder=enc,
                                kid_cap=self.kid_cap, metrics=self.metrics,
                                account=self._occ)
            self._arenas[id(store)] = arena
            # adopt anything registered before the resolver was attached
            for key, cfk in store.cfks.items():
                for t, info in cfk._infos.items():
                    arena.update(t, (key,), info.status,
                                 info.execute_at or t.as_timestamp())
            for t, rngs in store.range_txns.items():
                # invalidated range txns were already popped from the map
                arena.ranges.update(t, rngs, CfkStatus.WITNESSED)
        return arena

    # -- observer hooks (store.register funnel) -------------------------------
    def on_register(self, store, txn_id: TxnId, keys, status: CfkStatus,
                    witnessed_at: Timestamp) -> None:
        arena = self._arena(store)
        if isinstance(keys, Keys):
            arena.update(txn_id, set(keys), status, witnessed_at)
        else:
            # range-domain txns land in the interval arena (MaxConflicts for
            # ranges stays on the host map, which the store merges itself)
            arena.ranges.update(txn_id, keys, status)

    def _fence_finalized(self, store, arena) -> None:
        """Mutation fence: pre-materialize in-flight finalized harvests
        that pinned this arena BEFORE a truncation/prune bumps its
        sequence guards. The finalize kernels already ran (launch
        happened), the pins still certify their results, and the
        materialized deps are plain host objects the mutation cannot
        touch -- so the later harvest decodes from the cache instead of
        paying the legacy-fallback readback. On a real device this
        blocks the store's thread on every in-flight call: 1.08-1.13 s a
        wave with a round's four dispatches of 1,024 in flight at 262,144
        rows, 268 us a subject, nearly all of it the wait for the device
        (my chip run 1, PR 33; `fence_us_per_subject.batch`): for the
        dispatches themselves, 0.234 s each -- it read the same once the
        arena's sync stopped copying lanes ahead of them (PR 34)."""
        q = self._inflight.get(id(store.node))
        if not q:
            return
        ranges = arena.ranges

        def lanes(g):
            """Which of g's finalized lanes (key, range, rk) still want
            materializing: planned, not cached yet, guards intact."""
            key_ok = g.gen == arena.gen and g.kseq == arena.kseq
            return (g.fin_slots is not None and g.fin_mat is None and key_ok,
                    g.rents is not None and g.rmat is None
                    and g.rgen == ranges.gen and g.rseq == ranges.rseq,
                    g.rk_slots is not None and g.rk_mat is None and key_ok)

        todo = [(call, g, want) for call in q for g in call.groups
                if g.arena is arena and any(want := lanes(g))]
        if not todo:
            return  # every later call of a wave: the first one cached all
        with self._phase("resolver.fence", "resolver.fence_s"):
            for call, g, (key, rng, rkey) in todo:
                if key:
                    g.fin_mat = self._materialize_finalized(call, g)
                    self.fence_materializes += g.fin_mat is not None
                if rng:
                    # stage 1 only: the host-map filters (stage 2) run at
                    # harvest against post-mutation state, keeping fenced
                    # and guarded harvests bit-identical
                    g.rmat = self._stab_range_finalized(call, g)
                    self.fence_materializes += g.rmat is not None
                if rkey:
                    g.rk_mat = self._stab_rkey_finalized(call, g)
                    self.fence_materializes += g.rk_mat is not None

    def on_truncate(self, store, txn_id: TxnId) -> None:
        arena = self._arenas.get(id(store))
        if arena is None:
            return
        with self._phase("resolver.truncate", "resolver.truncate_s"):
            self._fence_finalized(store, arena)
            row = arena.row_of.get(txn_id)
            if row is not None:
                # the arena is per store, so every key in the row is this
                # store's record -- no slice filtering needed anymore
                self.truncated_txns += arena.remove_keys(
                    txn_id, arena.key_sets[row])
            arena.ranges.truncate(txn_id)

    def on_prune(self, store, txn_id: TxnId, keys) -> None:
        arena = self._arenas.get(id(store))
        if arena is not None:
            with self._phase("resolver.truncate", "resolver.truncate_s"):
                self._fence_finalized(store, arena)
                self.truncated_txns += arena.remove_keys(txn_id, keys)

    # -- async batched path (the hot path) ------------------------------------
    def enqueue_preaccept(self, store, txn_id, partial_txn, route,
                          ballot) -> AsyncResult:
        out: AsyncResult = AsyncResult()
        node = store.node
        self._pa_queues.setdefault(id(node), []).append(
            (store, txn_id, partial_txn, route, ballot, out))
        self._occ.accept()
        self._schedule_tick(store)
        return out

    def enqueue_deps(self, store, txn_id, seekables, before) -> AsyncResult:
        out: AsyncResult = AsyncResult()
        node = store.node
        self._deps_queues.setdefault(id(node), []).append(
            (store, txn_id, seekables, before, out))
        self._occ.accept()
        self._schedule_tick(store)
        return out

    def _schedule_tick(self, store) -> None:
        node = store.node
        self._windows[id(node)] = store.batch_window_ms
        if self.tick_driver is not None:
            # cluster-on-mesh burn: the engine owns tick scheduling (one
            # cluster-wide event fires every pending node's tick in node-id
            # order -- see sim/mesh_burn.ClusterTickEngine)
            self.tick_driver.note_work(
                self, node, self._window(node, store.batch_window_ms))
            return
        if id(node) in self._ticking:
            return
        self._ticking.add(id(node))
        node.scheduler.once(self._window(node, store.batch_window_ms),
                            lambda: self._tick(node))

    def _arm_tick(self, node) -> None:
        """Self-arm the next tick so staged plans launch even when no new
        enqueue arrives to schedule one."""
        if self.tick_driver is not None:
            self.tick_driver.note_work(
                self, node,
                self._window(node, self._windows.get(id(node)) or 0.0))
            return
        if id(node) in self._ticking:
            return
        self._ticking.add(id(node))
        window = self._window(node, self._windows.get(id(node)) or 0.0)
        node.scheduler.once(window, lambda: self._tick(node))

    def _window(self, node, base):
        """The node's effective dispatch window: the store-configured base,
        scaled by the adaptive controller when enabled."""
        if not self.adaptive_window or not base:
            return base
        return base * self._win_scale.get(id(node), 1.0)

    def _adapt(self, node, drained: int) -> None:
        """Adaptive staged window: an empty drain means the window overshot
        the arrival rate (halve the scale, floor 0.25x -- ticks fire sooner,
        trimming queue latency); a drain filling at least one max dispatch
        means it undershot (double, cap 4x -- bigger batches amortize the
        launch/readback round trip under sustained load)."""
        if not self.adaptive_window:
            return
        s = self._win_scale.get(id(node), 1.0)
        if drained == 0:
            if s > 0.25:
                self._win_scale[id(node)] = max(0.25, s * 0.5)
        elif drained >= self.max_dispatch and s < 4.0:
            self._win_scale[id(node)] = min(4.0, s * 2.0)

    def note_admission_pressure(self, node, overloaded: bool) -> None:
        """Admission-governor hook (serve/admission.py): entering overload
        widens the node's staged window one notch -- the txns that ARE
        admitted ride bigger, better-amortized dispatches while clients
        shed as BUSY -- and leaving it snaps the scale back so the queue
        latency the wide window buys doesn't outlive the episode. A no-op
        unless adaptive_window is on (the serve server enables it)."""
        if not self.adaptive_window:
            return
        if overloaded:
            s = self._win_scale.get(id(node), 1.0)
            if s < 4.0:
                self._win_scale[id(node)] = min(4.0, s * 2.0)
        else:
            if self._win_scale.get(id(node), 1.0) > 1.0:
                self._win_scale[id(node)] = 1.0

    def _tick(self, node) -> None:
        """One node tick: stage_dispatch first (launch the PREVIOUS tick's
        encoded plans, putting the device to work immediately) then
        stage_host (preaccept + encode the batch drained now, staged for the
        NEXT tick's launch) -- so the host phases below run in the
        wall-clock shadow of the in-flight call. stage_decode stays on the
        harvest event, which fires per dispatch after device_latency_ms and
        drains in dispatch order."""
        self._ticking.discard(id(node))
        # STAGE_DISPATCH: launch before any host work this event does
        for plan in self._staged.pop(id(node), []):
            self._launch(node, plan, staged=True)
        # STAGE_HOST: preaccept transitions + arena registration + upload-
        # array build for the NEXT tick's launch. Registrations land in the
        # arena before _encode_plan cuts each plan's field-granular delta
        # upload, so batchmates still witness each other.
        with self._phase("resolver.tick", node=node, track="stage_host",
                         event="stage_host") as ph:
            items = self._drain_and_preaccept(node)
            self._adapt(node, len(items))
            plans = [self._stage(node, sub) for sub in self._slices(items)]
            ph.args = {"hidden": bool(self._inflight.get(id(node))),
                       "items": len(items)}
        if plans:
            self._staged[id(node)] = plans
            self._arm_tick(node)

    def _drain_and_preaccept(self, node) -> List[_Item]:
        """Pop the node's enqueued work and run the host preaccept phase:
        registrations land in the arena immediately, so batchmates witness
        each other (deps may be any conservative superset; execution still
        orders by executeAt). A preaccept that raises fails ONLY its own
        AsyncResult -- the rest of the batch, and the pipeline, proceed."""
        from accord_tpu.local import commands
        from accord_tpu.local.commands import AcceptOutcome
        pa = self._pa_queues.pop(id(node), [])
        dq = self._deps_queues.pop(id(node), [])
        items: List[_Item] = []

        def _finish(store, t, p, out, outcome):
            if outcome in (AcceptOutcome.REJECTED_BALLOT,
                           AcceptOutcome.TRUNCATED):
                out.try_set_success((outcome, None, None))
                self._occ.deliver()
                return
            items.append(_Item(store, t, store.owned(p.keys),
                               store.command(t).execute_at, out, outcome))

        def _host_one(store, t, p, route, ballot, out):
            try:
                outcome = commands.preaccept(store, t, p, route, ballot)
            except BaseException as e:  # noqa: BLE001
                out.try_set_failure(e)
                self._occ.deliver()
                return
            _finish(store, t, p, out, outcome)

        with self._phase("resolver.preaccept", "resolver.preaccept_s",
                         node=node, track="stage_host", event="preaccept",
                         batch=len(pa)):
            # contiguous same-store spans route through the device command
            # arena as ONE cmd_tick dispatch (synchronous within the drain, so
            # timing -- and thus histories -- stay bit-identical to the host
            # loop); stores without a plane keep the inline path
            i = 0
            while i < len(pa):
                store = pa[i][0]
                plane = getattr(store, "cmd_plane", None)
                if plane is None:
                    _host_one(*pa[i])
                    i += 1
                    continue
                j = i
                while j < len(pa) and pa[j][0] is store:
                    j += 1
                batch = pa[i:j]
                td = self.tick_driver
                try:
                    from accord_tpu.ops.cmd_plane import CmdOp
                    cmd_ops = [CmdOp.preaccept(t, p, route, ballot)
                               for (_s, t, p, route, ballot, _o) in batch]
                    if td is not None and getattr(td, "cmd_defer", False):
                        # megakernel mode: decide the span with the host twin
                        # now and ride the device transition lanes into the
                        # tick's single fused dispatch (the quorum stage); on
                        # the device-messages path the span's shadow writes
                        # also fold back in-kernel as a repair scatter instead
                        # of a later standalone flush
                        fuse = (getattr(td, "note_cmd_defer", None)
                                if getattr(td, "device_messages", False)
                                else None)
                        res = plane.defer_batch(cmd_ops,
                                                sink=td.note_cmd_lanes,
                                                fuse=fuse)
                    else:
                        d0 = int(plane.dispatches)
                        res = plane.eval_batch(cmd_ops)
                        if td is not None:
                            td.note_cmd_dispatches(int(plane.dispatches) - d0)
                except Exception as e:  # noqa: BLE001 -- degrade, and say so
                    # the plane answers ops it cannot decide itself (counted in
                    # cmd_plane_fallbacks); reaching here means it FAILED -- a
                    # program the compiler refused, a launch error, a handler
                    # bug. The span replays through the Python handlers so the
                    # node keeps its guarantees, but never silently
                    self.cmd_span_replays += 1
                    if self.cmd_span_replays == 1:
                        logger.error(
                            "cmd plane failed on a %d-op PreAccept span; "
                            "replaying it (and counting later ones in "
                            "resolver.cmd_span_replays) through the host "
                            "handlers", len(batch), exc_info=e)
                    for entry in batch:
                        _host_one(*entry)
                else:
                    for (st_, t, p, _r, _b, out), r in zip(batch, res):
                        _finish(st_, t, p, out, r.outcome)
                i = j
        for (store, t, ks, before, out) in dq:
            items.append(_Item(store, t, store.owned(ks), before, out))
        if items:
            self.ticks += 1
        return items

    def _slices(self, items: List[_Item]) -> List[List[_Item]]:
        """Split a tick's items into dispatch slices: ONE device call per
        tick slice, every store's items riding together; oversized batches
        split so subject jit tiers stay bounded (8..max_dispatch)."""
        return [items[lo:lo + self.max_dispatch]
                for lo in range(0, len(items), self.max_dispatch)]

    def _run_plan(self, plan: _Plan):
        """stage_dispatch: fire a plan's deferred kernel launches against
        its plan-time snapshots. Returns (packed, rpacked, kpacked) device
        arrays, each None when that kernel had nothing to do."""
        packed = plan.key_call() if plan.key_call is not None else None
        rpacked = kpacked = None
        if plan.range_call is not None:
            rpacked, kpacked = plan.range_call()
        if packed is not None:
            for g, fn in plan.fin_calls:
                g.fin_dev = fn(packed)
        for g, fn in plan.rfin_calls:
            g.rfin_dev = fn()
        if kpacked is not None:
            for g, fn in plan.kfin_calls:
                g.rkfin_dev = fn(kpacked)
        return packed, rpacked, kpacked

    def _encode_plan(self, groups: List[_Group], items: List[_Item],
                     pin: bool = True) -> _Plan:
        """Build the flat CSR upload arrays for one dispatch spanning one
        or more STORE groups and return a _Plan whose deferred calls run
        the fused kernels against snapshots captured NOW. Shared by the
        async dispatch and the sync path -- the two must never drift. Each
        group's word-column spans (the row-offset table) are recorded from
        the snapshot shapes for decode routing, and (pin=True) the
        generation pins the harvest will need are taken at plan time, so a
        compaction landing between encode-ahead and launch is translated
        like any other stale harvest.

        Key-domain subjects upload one (subject row, key bucket) CSR entry
        per owned key -- variable width, so arbitrarily wide subjects stay
        on the device path. When range state is in play, a second CSR of
        half-open intervals drives the range kernel: key subjects as point
        intervals (stabbing their store's range arena), range subjects as
        their owned ranges (vs both of their store's arenas). With several
        groups, the fused kernels take every participating store's arena
        lanes as one tuple and route subjects by the store-id lane; a single
        group runs the plain kernels."""
        import jax.numpy as jnp
        from accord_tpu.ops.kernels import nnz_tier, subject_tier
        n = len(items)
        b = subject_tier(n)
        # the node-shared encoder cell: any arena with rows has set it
        encoder = groups[0].arena.encoder
        sb = np.zeros((b, 3), dtype=np.int32)
        sb[:n] = encoder.encode_many([item.before for item in items])
        sknd = np.zeros(b, dtype=np.int32)
        sknd[:n] = np.fromiter((int(item.txn_id.kind) for item in items),
                               np.int64, n)
        srng = np.zeros(b, dtype=bool)
        # store-id lane: routes each subject to its own store's arena block
        # inside the fused kernels; padding rows use len(groups), which no
        # block's slot matches
        subj_store = np.full(b, len(groups), dtype=np.int32)
        gkeys: List[List[Tuple[int, _Item]]] = [[] for _ in groups]
        granges: List[List[Tuple[int, _Item]]] = [[] for _ in groups]
        for gi, g in enumerate(groups):
            for i, item in zip(g.idx, g.items):
                subj_store[i] = gi
                item.cover_seq = item.store.cover_seq
                if isinstance(item.owned, Keys):
                    gkeys[gi].append((i, item))
                else:
                    srng[i] = True
                    granges[gi].append((i, item))
        # -- key-domain kernel plan --------------------------------------
        plan = _Plan(items, groups)
        k_parts = [(gi, g) for gi, g in enumerate(groups)
                   if g.arena.count > 0 and gkeys[gi]]
        if k_parts:
            key_items = [pair for gi, _ in k_parts for pair in gkeys[gi]]
            counts = np.fromiter((len(item.owned) for _, item in key_items),
                                 np.int64, len(key_items))
            total = int(counts.sum())
            z = nnz_tier(total)
            # CSR padding entries use subject row == b: out of bounds,
            # dropped by the device scatter
            subj_of = np.full(z, b, dtype=np.int32)
            subj_keys = np.zeros(z, dtype=np.int32)
            if total:
                subj_of[:total] = np.repeat(
                    np.fromiter((i for i, _ in key_items), np.int64,
                                len(key_items)), counts)
                subj_keys[:total] = (np.fromiter(
                    (int(k) for _, item in key_items for k in item.owned),
                    np.int64, total) % self.num_buckets).astype(np.int32)
            if len(groups) == 1:
                g = groups[0]
                ksnap = g.arena.device_arrays()
                g.pk = (0, ksnap[0].shape[0] // 32)
                j_of, j_keys = jnp.asarray(subj_of), jnp.asarray(subj_keys)
                j_sb, j_sknd = jnp.asarray(sb), jnp.asarray(sknd)
                plan.key_call = (
                    lambda ksnap=ksnap, j_of=j_of, j_keys=j_keys,
                    j_sb=j_sb, j_sknd=j_sknd:
                    self._run_kernel(ksnap, j_of, j_keys, j_sb, j_sknd))
                if self.tick_driver is not None:
                    plan.key_args = dict(
                        sb=sb, sknd=sknd, subj_store=subj_store,
                        subj_of=subj_of, subj_keys=subj_keys,
                        ngroups=len(groups), slots=[0], ksnaps=[ksnap],
                        fused=False)
            else:
                slots = np.fromiter((gi for gi, _ in k_parts), np.int64,
                                    len(k_parts)).astype(np.int32)
                ksnaps, off = [], 0
                for _, g in k_parts:
                    snap = g.arena.device_arrays()
                    ksnaps.append(snap)
                    w = snap[0].shape[0] // 32
                    g.pk = (off, off + w)
                    off += w
                j_slots = jnp.asarray(slots)
                j_of, j_keys = jnp.asarray(subj_of), jnp.asarray(subj_keys)
                j_store = jnp.asarray(subj_store)
                j_sb, j_sknd = jnp.asarray(sb), jnp.asarray(sknd)
                plan.fused = True
                plan.key_call = (
                    lambda ksnaps=ksnaps, j_slots=j_slots, j_of=j_of,
                    j_keys=j_keys, j_store=j_store, j_sb=j_sb, j_sknd=j_sknd:
                    self._run_fused_kernel(ksnaps, j_slots, j_of, j_keys,
                                           j_store, j_sb, j_sknd))
                if self.tick_driver is not None:
                    plan.key_args = dict(
                        sb=sb, sknd=sknd, subj_store=subj_store,
                        subj_of=subj_of, subj_keys=subj_keys,
                        ngroups=len(groups),
                        slots=[gi for gi, _ in k_parts], ksnaps=list(ksnaps),
                        fused=True, pad_tier=self.pad_store_tiers)
        if self.finalize_on_device and k_parts:
            # per-store finalize_csr plan: consumes the packed result at
            # launch time, so it rides the same deferred-call pipeline
            for gi, g in k_parts:
                self._plan_key_finalize(plan, g, gkeys[gi], b)
        # -- range path: interval CSR, range kernel plan, range / rk finalize
        if any(granges) or any(g.arena.ranges.count > 0 for g in groups):
            with self._phase("resolver.range_encode",
                             "resolver.range_encode_s"):
                self._plan_range_kernel(plan, groups, gkeys, granges, b, sb,
                                        sknd, srng, subj_store)
        if self.finalize_on_device:
            # the finalized harvest reads only the compacted CSR results;
            # the raw candidate buffers stay device-resident (range
            # subjects included -- the interval-stab + key-index lanes
            # replace the candidate re-filter) unless some range subject's
            # group could not plan a stab lane it needs; guard-tripped
            # fallbacks still fetch lazily
            want_rp = want_kp = False
            for g in groups:
                if not any(not isinstance(it.owned, Keys)
                           and it.fallback is None for it in g.items):
                    continue
                if g.rp is not None and g.rents is None:
                    want_rp = True
                if g.kp is not None and g.rk_slots is None:
                    want_kp = True
            plan.want = (False, want_rp, want_kp)
        if pin:
            for g in groups:
                if g.pk is not None or g.kp is not None:
                    g.arena.pin_gen()
                    g.pinned = True
                if g.rp is not None:
                    g.arena.ranges.pin_gen()
                    g.rpinned = True
        return plan

    def _plan_range_kernel(self, plan: _Plan, groups: List[_Group], gkeys,
                           granges, b: int, sb, sknd, srng,
                           subj_store) -> None:
        """The range half of _encode_plan, under its own span
        (resolver.range_encode, inside resolver.encode): the interval CSR of
        the dispatch -- range subjects' owned pieces, then key subjects as
        point intervals where their store holds range txns -- the range
        kernel's deferred call, and the range and rk finalize lanes."""
        import jax.numpy as jnp
        from accord_tpu.ops.kernels import nnz_tier
        givs: List[List[Tuple[int, int, int]]] = [[] for _ in groups]
        ghull = [False] * len(groups)
        # finalize_on_device: each group's (local interval-CSR entry, global
        # item position, key) records -- key-subject point entries are 1:1
        # with keys, so the finalized range output routes by entry
        grents: List[List[Tuple[int, int, object]]] = [[] for _ in groups]
        # finalize_on_device: each group's encodable RANGE subjects as
        # (global item position, item, interval pieces) -- fed to the
        # device stab lanes (_RSUB rents entries + the key-arena rk lane)
        grsubs: List[List[tuple]] = [[] for _ in groups]
        n_subjects = n_intervals = 0
        for gi, g in enumerate(groups):
            ranges = g.arena.ranges
            for i, item in granges[gi]:
                if not ranges.encode_ok:
                    item.fallback = "full"
                    self.range_fallbacks += 1
                    continue
                ivs = encode_seekable_intervals(item.owned)
                if ivs is None:
                    item.fallback = "full"
                    self.range_fallbacks += 1
                    continue
                ghull[gi] = True
                n_subjects += 1
                n_intervals += len(ivs)
                if self.finalize_on_device:
                    # the subject's own pieces become _RSUB rents entries:
                    # the device interval stab answers its range-vs-range
                    # deps (per-piece hit segments union idempotently)
                    base = len(givs[gi])
                    grents[gi].extend((base + t, i, _RSUB)
                                      for t in range(len(ivs)))
                    grsubs[gi].append((i, item, ivs))
                givs[gi].extend((i, s, e) for (s, e) in ivs)
            if ranges.encode_ok and ranges.count > 0:
                # key subjects stab their store's interval rows with point
                # intervals (the retired host_range_deps union, on device);
                # the key-parallel encoding feeds the candidate kernel the
                # exact same pairs encode_seekable_intervals would
                for i, item in gkeys[gi]:
                    kivs = encode_key_point_intervals(item.owned)
                    if kivs is None:
                        # unencodable keys: this subject's range deps come
                        # from the host union instead (counted)
                        item.fallback = "range"
                        self.range_fallbacks += 1
                        continue
                    if self.finalize_on_device:
                        base = len(givs[gi])
                        grents[gi].extend(
                            (base + t, i, k)
                            for t, (k, _, _) in enumerate(kivs))
                    givs[gi].extend((i, s, e) for (_, s, e) in kivs)
        if n_subjects:
            self.range_subjects += n_subjects
            self.range_intervals += n_intervals
        intervals = [t for gv in givs for t in gv]
        r_parts = [(gi, g) for gi, g in enumerate(groups)
                   if g.arena.ranges.count > 0 and g.arena.ranges.encode_ok
                   and givs[gi]]
        h_parts = [(gi, g) for gi, g in enumerate(groups)
                   if ghull[gi] and g.arena.count > 0]
        if intervals and (len(groups) == 1 or r_parts or h_parts):
            nv = nnz_tier(len(intervals))
            iv_of = np.full(nv, b, dtype=np.int32)
            iv_s = np.zeros(nv, dtype=np.int32)
            iv_e = np.zeros(nv, dtype=np.int32)
            arr = np.asarray(intervals, dtype=np.int64)
            iv_of[:len(intervals)] = arr[:, 0]
            iv_s[:len(intervals)] = arr[:, 1]
            iv_e[:len(intervals)] = arr[:, 2]
            j_iv = (jnp.asarray(iv_of), jnp.asarray(iv_s),
                    jnp.asarray(iv_e))
            j_sb, j_sknd = jnp.asarray(sb), jnp.asarray(sknd)
            j_srng = jnp.asarray(srng)
            if len(groups) == 1:
                g = groups[0]
                rsnap = g.arena.ranges.device_arrays()
                ksnap = g.arena.device_arrays()
                g.rp = (0, rsnap[0].shape[0] // 32)
                g.kp = (0, ksnap[0].shape[0] // 32)
                plan.range_call = (
                    lambda rsnap=rsnap, ksnap=ksnap, j_iv=j_iv, j_sb=j_sb,
                    j_sknd=j_sknd, j_srng=j_srng:
                    self._run_range_kernel(rsnap, ksnap, j_iv[0], j_iv[1],
                                           j_iv[2], j_sb, j_sknd, j_srng))
                if self.tick_driver is not None:
                    plan.range_args = dict(
                        iv_of=iv_of, iv_s=iv_s, iv_e=iv_e, sb=sb, sknd=sknd,
                        srng=srng, subj_store=subj_store,
                        ngroups=len(groups), r_slots=[0], rsnaps=[rsnap],
                        k_slots=[0], ksnaps=[ksnap], has_r=True, has_k=True,
                        fused=False)
            else:
                r_slots = np.fromiter((gi for gi, _ in r_parts), np.int64,
                                      len(r_parts)).astype(np.int32)
                k_slots = np.fromiter((gi for gi, _ in h_parts), np.int64,
                                      len(h_parts)).astype(np.int32)
                rsnaps, off = [], 0
                for _, g in r_parts:
                    snap = g.arena.ranges.device_arrays()
                    rsnaps.append(snap)
                    w = snap[0].shape[0] // 32
                    g.rp = (off, off + w)
                    off += w
                ksnaps, off = [], 0
                for _, g in h_parts:
                    snap = g.arena.device_arrays()
                    ksnaps.append(snap)
                    w = snap[0].shape[0] // 32
                    g.kp = (off, off + w)
                    off += w
                j_rsl, j_ksl = jnp.asarray(r_slots), jnp.asarray(k_slots)
                j_store = jnp.asarray(subj_store)
                has_r, has_k = bool(r_parts), bool(h_parts)

                def range_call(rsnaps=rsnaps, ksnaps=ksnaps, j_rsl=j_rsl,
                               j_ksl=j_ksl, j_iv=j_iv, j_store=j_store,
                               j_sb=j_sb, j_sknd=j_sknd, j_srng=j_srng,
                               has_r=has_r, has_k=has_k):
                    rp, kp = self._run_fused_range_kernel(
                        rsnaps, j_rsl, ksnaps, j_ksl, j_iv[0], j_iv[1],
                        j_iv[2], j_store, j_sb, j_sknd, j_srng)
                    return (rp if has_r else None, kp if has_k else None)

                plan.fused = True
                plan.range_groups = len({gi for gi, _ in r_parts + h_parts})
                plan.range_call = range_call
                if self.tick_driver is not None:
                    plan.range_args = dict(
                        iv_of=iv_of, iv_s=iv_s, iv_e=iv_e, sb=sb, sknd=sknd,
                        srng=srng, subj_store=subj_store,
                        ngroups=len(groups),
                        r_slots=[gi for gi, _ in r_parts],
                        rsnaps=list(rsnaps),
                        k_slots=[gi for gi, _ in h_parts],
                        ksnaps=list(ksnaps), has_r=has_r, has_k=has_k,
                        fused=True, pad_tier=self.pad_store_tiers)
            if self.finalize_on_device:
                self._plan_range_finalize(plan, groups, grents, givs, nv,
                                          j_iv, j_sb, j_sknd)
                # range subjects' KEY-arena deps: stab the sorted key index
                # with each piece and reuse finalize_csr on the kpacked
                # hull result -- exact row masks replace the host key-set
                # walk of the candidate decode
                for gi, g in enumerate(groups):
                    if grsubs[gi] and g.kp is not None:
                        self._plan_rkey_finalize(plan, g, grsubs[gi], b)

    def _plan_key_finalize(self, plan: _Plan, g: _Group, pairs, b: int) -> None:
        """Cut one store's finalize_csr call: the (subject, key) slot list
        in the EXACT order the legacy decode walks it (item order, keys
        sorted unique, keys without a row mask skipped -- bit-identity
        depends on this), the device kid/row-mask inputs, and an out_cap
        tier from the OutCapTiers policy (fed by the DEVICE-computed bound
        riding back with each result, so no host O(keys) popcount pass per
        dispatch; cold: the host-exact popcount bound the compaction
        output can never overflow while kseq holds)."""
        import jax.numpy as jnp
        from accord_tpu.ops.kernels import nnz_tier
        arena = g.arena
        pol = self._outcap(arena, "key")
        want_host_bound = pol.cold
        pos_of = {i: j for j, i in enumerate(g.idx)}
        flat_key: List[object] = []
        slot_pos: List[int] = []
        slot_subj: List[int] = []
        slot_kid: List[int] = []
        key_cnt = np.zeros(len(g.items), np.int64)
        bound = 0
        for i, item in pairs:
            cnt = 0
            for p, k in enumerate(item.owned):  # Keys iterates sorted unique
                if arena.key_rows.get(k) is None:
                    continue
                flat_key.append(k)
                slot_pos.append(p)
                slot_subj.append(i)
                slot_kid.append(arena.kid_of[k])
                if want_host_bound:
                    bound += arena.key_pop.get(k, 0)
                cnt += 1
            key_cnt[pos_of[i]] = cnt
        key_off = np.concatenate(([0], np.cumsum(key_cnt)))
        g.fin_slots = (flat_key, key_off)
        g.fin_pos = np.asarray(slot_pos, np.int64)
        if not flat_key:
            return      # no key has arena rows: the group decodes to EMPTY
        s = nnz_tier(len(flat_key))
        if want_host_bound:
            out_cap = pol.pick(max(bound, 1))
        else:
            out_cap = pol.pick(pol.estimate(len(flat_key)))
        # padding slots use subject == b / kid == kid_cap: out of bounds,
        # masked off inside the kernel
        a_subj = np.full(s, b, dtype=np.int32)
        a_subj[:len(slot_subj)] = slot_subj
        a_kid = np.full(s, arena.kid_cap, dtype=np.int32)
        a_kid[:len(slot_kid)] = slot_kid
        subj_row = np.full(b, -1, dtype=np.int32)
        for i, item in pairs:
            subj_row[i] = arena.row_of.get(item.txn_id, -1)
        kid_rows = arena.kid_arrays()
        j_subj = jnp.asarray(a_subj)
        j_kid = jnp.asarray(a_kid)
        j_srow = jnp.asarray(subj_row)
        j_off = jnp.asarray(g.pk[0], jnp.int32)
        plan.fin_calls.append((g, lambda packed, kid_rows=kid_rows,
                               j_subj=j_subj, j_kid=j_kid, j_srow=j_srow,
                               j_off=j_off, oc=out_cap:
                               self._run_finalize_kernel(
                                   packed, j_off, kid_rows, j_subj, j_kid,
                                   j_srow, out_cap=oc)))
        if self.tick_driver is not None:
            # megakernel lane (index-aligned with the closure above):
            # slot_subj is plan-local and g.pk the plan-local word offset,
            # so the recorded lanes run unchanged against protocol_tick's
            # in-kernel demux of this plan's merge span
            plan.fin_args.append((g, ("key", kid_rows, j_subj, j_kid,
                                      j_srow, int(g.pk[0]), out_cap)))

    def _plan_range_finalize(self, plan: _Plan, groups: List[_Group],
                             grents, givs, nv: int, j_iv, j_sb,
                             j_sknd) -> None:
        """Cut each participating store's range_finalize_csr call: map the
        group's local key-subject point entries onto global interval-CSR
        positions, gate them with ent_ok, and close over the group's OWN
        interval-arena snapshot -- the exact stab reruns against the real
        endpoint lanes, so the fused candidate buffer is not an input."""
        import jax.numpy as jnp
        from accord_tpu.ops.kernels import range_finalize_csr
        offs, off = [], 0
        for gv in givs:
            offs.append(off)
            off += len(gv)
        for gi, g in enumerate(groups):
            ents = grents[gi]
            ranges = g.arena.ranges
            if not ents or not ranges.encode_ok:
                continue
            pos_of = {i: j for j, i in enumerate(g.idx)}
            base = offs[gi]
            g.rents = [(base + lp, pos_of[i], k) for lp, i, k in ents]
            ent_ok = np.zeros(nv, dtype=bool)
            for e, _, _ in g.rents:
                ent_ok[e] = True
            pol = self._outcap(g.arena, "range")
            if pol.cold:
                # cold: seed with the host product bound (entries x live
                # rows) the stab count can never exceed; after the first
                # dispatch the DEVICE stab count riding back with each
                # result feeds the policy instead, so steady state pays no
                # host count_nonzero pass
                nvalid = int(np.count_nonzero(ranges.valid[:ranges.count]))
                bound = max(len(g.rents) * nvalid, 1)
                out_cap = pol.pick(bound)
            else:
                out_cap = pol.pick(pol.estimate(len(g.rents)))
            rsnap = ranges.device_arrays()
            j_ok = jnp.asarray(ent_ok)
            plan.rfin_calls.append((g, lambda rsnap=rsnap, j_ok=j_ok,
                                    oc=out_cap:
                                    range_finalize_csr(
                                        j_iv[0], j_iv[1], j_iv[2], j_ok,
                                        j_sb, j_sknd, *rsnap, self._table,
                                        out_cap=oc)))
            if self.tick_driver is not None:
                plan.rfin_args.append((g, (j_iv[0], j_iv[1], j_iv[2], j_ok,
                                           j_sb, j_sknd, rsnap, out_cap)))

    def _plan_rkey_finalize(self, plan: _Plan, g: _Group, rsubs,
                            b: int) -> None:
        """Cut one store's range-vs-KEY finalize call: each range subject's
        owned pieces binary-search the arena's sorted key index to
        enumerate exactly the keys they cover, and finalize_csr reuses the
        group's kpacked hull span with one (subject, covered key) slot per
        hit -- the device's exact kid row masks (plus its witness/before
        lanes) replace the host candidate decode's per-row key-set walk.
        Skipped entirely (rk_slots stays None -> candidate fallback +
        kpacked readback) when the arena holds keys the int index cannot
        order."""
        import jax.numpy as jnp
        from accord_tpu.ops.kernels import nnz_tier
        arena = g.arena
        idx = arena.key_index()
        if idx is None:
            return
        keys_sorted, kids_sorted = idx
        pol = self._outcap(arena, "rkey")
        want_host_bound = pol.cold
        flat: List[tuple] = []
        slot_subj: List[int] = []
        slot_kid: List[int] = []
        bound = 0
        pos_of = {i: j for j, i in enumerate(g.idx)}
        for i, item, ivs in rsubs:
            j = pos_of[i]
            for (s, e) in ivs:
                lo = int(np.searchsorted(keys_sorted, s, side="left"))
                hi = int(np.searchsorted(keys_sorted, e, side="left"))
                for p in range(lo, hi):
                    k = int(keys_sorted[p])
                    flat.append((j, k))
                    slot_subj.append(i)
                    slot_kid.append(int(kids_sorted[p]))
                    if want_host_bound:
                        bound += arena.key_pop.get(k, 0)
        g.rk_slots = flat
        if not flat:
            return      # no covered key has an arena id: decodes to EMPTY
        s = nnz_tier(len(flat))
        if want_host_bound:
            out_cap = pol.pick(max(bound, 1))
        else:
            out_cap = pol.pick(pol.estimate(len(flat)))
        a_subj = np.full(s, b, dtype=np.int32)
        a_subj[:len(slot_subj)] = slot_subj
        a_kid = np.full(s, arena.kid_cap, dtype=np.int32)
        a_kid[:len(slot_kid)] = slot_kid
        # range subjects hold no key-arena row; the materialize's txn-id
        # check handles self-dependency like the legacy decode
        subj_row = np.full(b, -1, dtype=np.int32)
        kid_rows = arena.kid_arrays()
        j_subj = jnp.asarray(a_subj)
        j_kid = jnp.asarray(a_kid)
        j_srow = jnp.asarray(subj_row)
        j_off = jnp.asarray(g.kp[0], jnp.int32)
        plan.kfin_calls.append((g, lambda kpacked, kid_rows=kid_rows,
                                j_subj=j_subj, j_kid=j_kid, j_srow=j_srow,
                                j_off=j_off, oc=out_cap:
                                self._run_finalize_kernel(
                                    kpacked, j_off, kid_rows, j_subj, j_kid,
                                    j_srow, out_cap=oc)))
        if self.tick_driver is not None:
            plan.kfin_args.append((g, ("rkey", kid_rows, j_subj, j_kid,
                                       j_srow, int(g.kp[0]), out_cap)))

    def _run_kernel(self, ksnap, subj_of, subj_keys, sb, sknd):
        """The single-store kernel call against a plan-time arena snapshot
        (bm, ts, exec_ts, kinds, valid); ShardedBatchDepsResolver overrides
        this to run the same computation sharded over a device mesh."""
        from accord_tpu.ops.kernels import deps_resolve
        act_bm, act_ts, _, act_kinds, act_valid = ksnap
        return deps_resolve(subj_of, subj_keys, sb, sknd,
                            act_bm, act_ts, act_kinds, act_valid, self._table)

    def _run_range_kernel(self, rsnap, ksnap, iv_of, iv_s, iv_e,
                          sb, sknd, srng):
        from accord_tpu.ops.kernels import range_deps_resolve
        r_start, r_end, r_ts, r_kinds, r_valid = rsnap
        k_bm, k_ts, _, k_kinds, k_valid = ksnap
        return range_deps_resolve(iv_of, iv_s, iv_e, sb, sknd, srng,
                                  r_start, r_end, r_ts, r_kinds, r_valid,
                                  k_bm, k_ts, k_kinds, k_valid,
                                  self._table)

    # -- pad_store_tiers helpers ----------------------------------------------
    def _pad_key_block(self, cap: Optional[int] = None):
        """Cached all-invalid key-arena block for pad_store_tiers, shaped
        like an arena at `cap` rows so padded dispatches share the compiled
        shape of their widest real block. Invalid rows contribute nothing,
        and the dummy word columns sit beyond every real group's span, so
        decode never sees them. Cached per capacity: when a real arena
        outgrows initial_cap the pool grows a matching block alongside the
        old ones instead of forcing a shape mismatch."""
        cap = cap or self.initial_cap
        blk = self._pad_key.get(cap)
        if blk is None:
            import jax.numpy as jnp
            blk = self._pad_key[cap] = (
                jnp.zeros((cap, self.num_buckets), jnp.float32),
                jnp.zeros((cap, 3), jnp.int32),
                jnp.zeros(cap, jnp.int32),
                jnp.zeros(cap, bool))
        return blk

    def _pad_range_block(self, cap: Optional[int] = None):
        cap = cap or self.range_cap
        blk = self._pad_range.get(cap)
        if blk is None:
            import jax.numpy as jnp
            blk = self._pad_range[cap] = (
                jnp.zeros(cap, jnp.int32), jnp.zeros(cap, jnp.int32),
                jnp.zeros((cap, 3), jnp.int32), jnp.zeros(cap, jnp.int32),
                jnp.zeros(cap, bool))
        return blk

    def _pad_fused(self, blocks: list, slots, pad_block):
        """pad_store_tiers: top a fused call's block list up to the fixed
        store tier with cached empty blocks under slot -1 (no subject's
        store-id lane is negative, so dummies match nothing). Trades a
        little extra readback width per dummy for ONE compiled jit tier
        across all participating-store counts up to the tier. Dummies take
        the widest real block's capacity so the compiled shape tracks arena
        growth."""
        tier = self.pad_store_tiers
        if not tier or len(blocks) >= tier:
            return slots
        import jax.numpy as jnp
        cap = max(b[0].shape[0] for b in blocks) if blocks else None
        pad = pad_block(cap)
        npad = tier - len(blocks)
        blocks.extend([pad] * npad)
        return jnp.concatenate([slots, jnp.full(npad, -1, jnp.int32)])

    def _run_fused_kernel(self, ksnaps, slots, subj_of, subj_keys,
                          subj_store, sb, sknd):
        """The fused cross-store key kernel: every participating store's
        snapshot lanes enter one call as a tuple block; the
        ShardedBatchDepsResolver override runs it over the mesh."""
        from accord_tpu.ops.kernels import fused_deps_resolve
        arenas = [(bm, ts, kinds, valid)
                  for (bm, ts, _, kinds, valid) in ksnaps]
        slots = self._pad_fused(arenas, slots, self._pad_key_block)
        return fused_deps_resolve(subj_of, subj_keys, subj_store, sb, sknd,
                                  slots, tuple(arenas), self._table)

    def _run_fused_range_kernel(self, rsnaps, r_slots, ksnaps, k_slots,
                                iv_of, iv_s, iv_e, subj_store, sb, sknd,
                                srng):
        from accord_tpu.ops.kernels import fused_range_deps_resolve
        rarenas = list(rsnaps)
        r_slots = self._pad_fused(rarenas, r_slots, self._pad_range_block)
        karenas = [(bm, ts, kinds, valid)
                   for (bm, ts, _, kinds, valid) in ksnaps]
        k_slots = self._pad_fused(karenas, k_slots, self._pad_key_block)
        return fused_range_deps_resolve(iv_of, iv_s, iv_e, subj_store, sb,
                                        sknd, srng, r_slots, tuple(rarenas),
                                        k_slots, tuple(karenas), self._table)

    def _decode_batch(self, arena: _StoreArena, items: List[_Item],
                      packed: np.ndarray) -> list:
        """Recover every item's exact key-domain deps from the dispatch-wide
        bit-packed kernel result in one vectorized pass -> [KeyDeps].

        Replaces the per-item decode loop (whose per-subject numpy-call
        overhead dominated harvest at large dispatch sizes): one unpackbits
        yields all candidate (item, dep row) pairs, a stacked key-bitmask
        gather tests exact key membership for every (candidate, key slot)
        pair at once, and a single global sort by (key slot, timestamp rank)
        puts every item's CSR in final order. Per-item work is reduced to
        slicing its segment. Range-domain items pass through with EMPTY here
        (their deps decode from the range kernel's buffers instead)."""
        from accord_tpu.primitives.deps import KeyDeps
        n = len(items)
        out = [KeyDeps.EMPTY] * n
        # 1. subject rows are 1:1 with items under the CSR encoding (copy:
        #    the self-bit clear below must not mutate the harvested buffer)
        item_packed = packed[:n].astype("<u4", copy=True)
        # 2. clear each subject's own row bit (self is never a dep)
        srows = np.fromiter((arena.row_of.get(item.txn_id, -1)
                             for item in items), np.int64, n)
        # rows past the snapshot width exist only when the arena grew after
        # the plan was cut (staged encode-ahead): the kernel never saw them,
        # so there is no self bit to clear
        has_self = np.nonzero((srows >= 0)
                              & (srows < item_packed.shape[1] * 32))[0]
        if has_self.size:
            r = srows[has_self]
            item_packed[has_self, r >> 5] &= \
                ~(np.uint32(1) << (r & 31).astype(np.uint32))
        if not item_packed.any():
            return out
        # 3. all candidate (item, dep row) pairs in one unpack
        ibits = np.unpackbits(item_packed.view(np.uint8),
                              bitorder="little", axis=1)
        cand_item, cand_row = np.nonzero(ibits)
        # 4. flatten each item's key slots; dedupe identical key-bitmask
        #    arrays so the stacked gather matrix stays small
        masks: List[np.ndarray] = []
        mask_idx: Dict[int, int] = {}
        flat_maskrow: List[int] = []
        flat_key: List[object] = []
        flat_cov: List[Optional[dict]] = []
        key_cnt = np.zeros(n, np.int64)
        covered_any = False
        for i, item in enumerate(items):
            if not isinstance(item.owned, Keys):
                continue            # range subject: no key slots here
            cfks = item.store.cfks
            cnt = 0
            for k in item.owned:    # Keys iterates sorted unique
                kr = arena.key_rows.get(k)
                if kr is None:
                    continue
                mi = mask_idx.get(id(kr))
                if mi is None:
                    mi = mask_idx[id(kr)] = len(masks)
                    masks.append(kr)
                flat_maskrow.append(mi)
                flat_key.append(k)
                c = cfks.get(k)
                cov = c.covered if c is not None and c.covered else None
                flat_cov.append(cov)
                covered_any = covered_any or cov is not None
                cnt += 1
            key_cnt[i] = cnt
        if not masks or cand_item.size == 0:
            return out
        key_off = np.concatenate(([0], np.cumsum(key_cnt)))
        slot_item = np.repeat(np.arange(n), key_cnt)
        KM = np.stack(masks)
        maskrow = np.asarray(flat_maskrow, np.int64)
        # 5. expand candidates over their item's key slots, test membership
        #    with packed-bit gathers (exactness: key_rows tracks REAL key
        #    sets, so bucket collisions and cross-store rows drop out here)
        rep = key_cnt[cand_item]
        e_cand = np.repeat(np.arange(cand_item.size), rep)
        if e_cand.size == 0:
            return out
        cum = np.cumsum(rep)
        pos = np.arange(e_cand.size) - np.repeat(cum - rep, rep)
        slot = key_off[cand_item[e_cand]] + pos
        e_row = cand_row[e_cand].astype(np.int64)
        hit = ((KM[maskrow[slot], e_row >> 5]
                >> (e_row & 31).astype(np.uint32)) & 1).astype(bool)
        h_slot = slot[hit]
        h_row = e_row[hit]
        return self._assemble_key_deps(arena, items, h_slot, h_row, flat_key,
                                       flat_cov, covered_any, slot_item,
                                       key_off, out)

    def _assemble_key_deps(self, arena: _StoreArena, items: List[_Item],
                           h_slot: np.ndarray, h_row: np.ndarray,
                           flat_key: list, flat_cov: list,
                           covered_any: bool, slot_item: np.ndarray,
                           key_off: np.ndarray, out: list) -> list:
        """Steps 6-8 of the batch decode, shared verbatim by the legacy
        unpackbits path and the finalized-CSR materialize (same flat-slot
        layout, so the two paths stay bit-identical by construction): the
        (slot, row) pairs as (slot, rank) through _sort_entries, the
        covered elision, and _cut_csr's cut of every item's KeyDeps. No
        sort and no per-item numpy call of its own."""
        if h_slot.size == 0:
            return out
        # 6. one global sort: flat slots increase per (item, key), so
        #    (slot, rank) order groups by item, then key, then TxnId order.
        #    rank <-> row is a bijection over the arena's rows, so the
        #    sorted pairs' rows come back as order[rank]
        rank, order, by_rank = arena.row_rank()
        h_slot, h_rank = _sort_entries(h_slot, rank[h_row], len(order))
        # 7. transitive-dependency elision, only over slots with covers
        if covered_any:
            h_row = order[h_rank]
            seg = np.flatnonzero(np.r_[True, h_slot[1:] != h_slot[:-1]])
            seg_end = np.r_[seg[1:], h_slot.size]
            keep = np.ones(h_slot.size, bool)
            ids = arena.ids_np
            for a, b in zip(seg, seg_end):
                cov = flat_cov[h_slot[a]]
                if cov is None:
                    continue
                item = items[slot_item[h_slot[a]]]
                cs, bf = item.cover_seq, item.before
                for t in range(a, b):
                    e = cov.get(ids[h_row[t]])
                    # elide only covers the kernel snapshot already saw
                    # (seq <= cover_seq) whose cover executes below the
                    # subject's bound -- the host scan's exact rule plus
                    # the snapshot guard
                    if e is not None and e[0] <= cs and e[1] < bf:
                        keep[t] = False
            if not keep.all():
                h_slot = h_slot[keep]
                h_rank = h_rank[keep]
        if h_slot.size == 0:
            return out
        # 8. every item's CSR from its slice of the sorted pairs
        self.array_cuts += 1
        _cut_csr(h_slot, h_rank, key_off, by_rank,
                 lambda slots: [flat_key[u] for u in slots.tolist()],
                 KeyDeps, out)
        return out

    def _fetch_np(self, holder, attr: str, dev):
        """Lazy blocking host read of a device buffer, cached on its holder
        (_Call for the raw candidate buffers, _Group for the finalized CSR
        results) and timed into readback_s -- the finalized path skips the
        eager raw-buffer readback; fallbacks pay only for what they touch."""
        cached = getattr(holder, attr)
        if cached is not None:
            return cached
        if dev is None:
            return None
        val = self._read(dev)
        setattr(holder, attr, val)
        return val

    def _read(self, dev):
        """Blocking device->host read of one result (an array or a finalize
        tuple), split where the host's two waits differ: for the kernels to
        finish (device_wait_s), then for the copy that follows
        (transfer_s). readback_s stays their sum; readback_bytes counts
        what reached the host."""
        import jax
        with self._phase("resolver.device_wait",
                         "resolver.device_wait_s") as wait:
            jax.block_until_ready(dev)
        with self._phase("resolver.transfer", "resolver.transfer_s") as copy:
            val = _dev_read(dev)
        self.readback_s += wait.dt + copy.dt
        self.readback_bytes += sum(
            a.nbytes for a in (val if isinstance(val, tuple) else (val,)))
        return val

    def _observe_bound(self, arena, lane: str, dbound, n: int):
        """Fold the device-computed bound that rode back with a finalize
        result into the lane's out-cap policy, so the NEXT dispatch's tier
        needs no host O(keys) popcount pass. Returns the policy."""
        with self._phase("resolver.bound_readback",
                         "resolver.bound_readback_s"):
            pol = self._outcap(arena, lane)
            pol.observe(int(dbound), n)
        return pol

    def _stab_finalized(self, call: _Call, g: _Group):
        """Stage 1 of the key lane's finalized harvest: the device's
        (indptr, dep_rows) CSR as flat (slot, row) pairs -- no unpackbits,
        no membership gather, no row translation (kseq/gen guards upstream
        certify rows and slots still mean what the kernel saw). Checksum
        verified and the bound folded into the out-cap policy first. None
        when the compaction overflowed its out_cap tier or the readback is
        unusable (caller falls back to the legacy decode)."""
        arena = g.arena
        flat_key, _ = g.fin_slots
        if not flat_key:
            return _EMPTY_I64, _EMPTY_I64   # no key had arena rows at plan
        buf = self._fetch_np(g, "fin_np", g.fin_dev)
        if buf is None:
            return None     # kernel never launched (defensive)
        if not self._csum_ok(call, g, buf):
            return None     # corrupted readback: caught before decode
        indptr, dep_rows, dbound, _ = buf
        ns = len(flat_key)
        pol = self._observe_bound(arena, "key", dbound, ns)
        if call is not None and call.overflow_pending:
            # injected out-cap overflow storm: report the overflow signal
            # without shrinking/garbling anything -- the policy bumps its
            # pinned tier and this one group pays the legacy fallback
            call.overflow_pending = False
            call.faulted = True
            from accord_tpu.ops import fault_plane
            if fault_plane.ACTIVE is not None:
                fault_plane.ACTIVE.note("overflow")
                self.device_faults_injected += 1
            pol.overflowed()
            return None
        total = int(indptr[ns])
        if total > dep_rows.shape[0]:
            # out_cap overflow (estimate undershot or kseq changed
            # mid-flight): bump the pinned tier so at most this one
            # dispatch pays the legacy fallback
            pol.overflowed()
            return None
        h_slot = np.repeat(np.arange(ns), np.diff(indptr[:ns + 1]))
        h_row = dep_rows[:total].astype(np.int64)
        return h_slot, h_row

    @staticmethod
    def _slot_covers(g: _Group):
        """The key lane's per-slot covered maps, read at HARVEST time in
        both decode paths (the legacy decode builds flat_cov then too), so
        elision stays in lockstep -> (flat_cov, covered_any, slot_item:
        which item owns each flat slot)."""
        flat_key, key_off = g.fin_slots
        items = g.items
        slot_item = np.repeat(np.arange(len(items)), np.diff(key_off))
        flat_cov: List[Optional[dict]] = []
        covered_any = False
        for s in range(len(flat_key)):
            cfks = items[int(slot_item[s])].store.cfks
            c = cfks.get(flat_key[s])
            cov = c.covered if c is not None and c.covered else None
            flat_cov.append(cov)
            covered_any = covered_any or cov is not None
        return flat_cov, covered_any, slot_item

    def _finish_finalized(self, g: _Group, stab):
        """Slice-and-wrap: one store's key-domain deps from the key lane's
        stage 1 -> [KeyDeps] per group item."""
        flat_key, key_off = g.fin_slots
        h_slot, h_row = stab
        out = [KeyDeps.EMPTY] * len(g.items)
        if not flat_key:
            return out
        flat_cov, covered_any, slot_item = self._slot_covers(g)
        return self._assemble_key_deps(g.arena, g.items, h_slot, h_row,
                                       flat_key, flat_cov, covered_any,
                                       slot_item, key_off, out)

    def _materialize_finalized(self, call: _Call, g: _Group):
        """Both stages of the key lane's finalized harvest. None when the
        lane has no usable result (caller falls back to the legacy
        decode)."""
        stab = self._stab_finalized(call, g)
        return None if stab is None else self._finish_finalized(g, stab)

    # -- the range lanes, decoded as arrays over the whole dispatch -----------
    @staticmethod
    def _rent_table(g: _Group) -> "_RentTable":
        """g.rents as arrays, cut once a group (plan-time facts only, so
        the fence and the harvest read the same table)."""
        tab = g.rtab
        if tab is not None:
            return tab
        rents = g.rents
        m = len(rents)
        tab = g.rtab = _RentTable()
        tab.e = np.fromiter((e for e, _, _ in rents), np.int64, m)
        tab.j = np.fromiter((j for _, j, _ in rents), np.int64, m)
        tab.sub = np.fromiter((k is _RSUB for _, _, k in rents), bool, m)
        # an item's entries are consecutive: its keys, or its pieces, in
        # the order it owns them
        first = np.flatnonzero(np.r_[True, tab.j[1:] != tab.j[:-1]])
        tab.pos = np.arange(m) - np.repeat(first, np.diff(np.r_[first, m]))
        tab.cs = np.zeros(m, np.int64)
        tab.ce = np.zeros(m, np.int64)
        for t in np.flatnonzero(tab.sub).tolist():
            r = g.items[tab.j[t]].owned[tab.pos[t]]
            tab.cs[t] = _endpoint_code(r.start)
            tab.ce[t] = _endpoint_code(r.end)
        return tab

    @staticmethod
    def _mask_own_rows(st: "_Stab", slot_item: np.ndarray, items, rows_of):
        """Self is never a dep: drop each subject's pairs on its own rows
        (`rows_of`: txn id -> its row or rows in the lane's arena)."""
        keep = None
        for j in np.unique(slot_item).tolist():
            own = rows_of.get(items[j].txn_id)
            if own is None or own == []:
                continue
            hit = (slot_item[st.slot] == j) & np.isin(st.row, own)
            if hit.any():
                keep = ~hit if keep is None else keep & ~hit
        if keep is not None:
            st.take(keep)

    def _stab_range_finalized(self, call: _Call, g: _Group):
        """Stage 1 of the interval-stab harvest (pin-dependent): every
        entry's CSR segment as flat (entry, arena row) pairs, with each
        pair's intersection cut from the endpoint codes -- rgen/rseq
        holding certifies the rows are the ones the kernel stabbed. Each
        pair already passed the interval, witness, and before tests ON
        DEVICE. Returns a _Stab over g.rents, or None on overflow / no
        buffer. The mutation fence runs this stage under still-valid pins;
        stage 2 (_filter_range_stab) is host-map-dependent and runs at
        harvest, where the guards no longer hold."""
        if g.rfin_dev is None and g.rfin_np is None:
            return None
        buf = self._fetch_np(g, "rfin_np", g.rfin_dev)
        if not self._csum_ok(call, g, buf):
            return None     # corrupted readback: caught before decode
        indptr, dep_rows, dbound, _ = buf
        pol = self._observe_bound(g.arena, "range", dbound,
                                  max(len(g.rents), 1))
        if int(indptr[-1]) > dep_rows.shape[0]:
            # defensively bump the pinned tier (the stab-count bound is a
            # true superset of the compaction, so only a mid-flight rseq
            # change or an undersized warm estimate can land here)
            pol.overflowed()
            return None
        ranges = g.arena.ranges
        tab = self._rent_table(g)
        lo = indptr[tab.e].astype(np.int64)
        cnt = indptr[tab.e + 1] - lo
        total = int(cnt.sum())
        st = _Stab()
        st.slot = np.repeat(np.arange(cnt.size), cnt)
        # each entry's segment, gathered in entry order
        at = np.arange(total) + np.repeat(lo - (np.cumsum(cnt) - cnt), cnt)
        st.row = dep_rows[at].astype(np.int64)
        n = ranges.count
        st.ids = ranges.ids_np[:n].copy()
        st.ts = ranges.ts[:n].copy()
        st.live = ranges.valid[:n].copy()
        # a freed row answers for no txn (never met while rseq holds)
        ok = st.live[st.row]
        if not ok.all():
            st.take(ok)
        self._mask_own_rows(st, tab.j, g.items, ranges.rows_of)
        codes = ranges.codes
        st.xs = np.maximum(tab.cs[st.slot], codes[st.row, 0])
        st.xe = np.minimum(tab.ce[st.slot], codes[st.row, 1])
        return st

    def _filter_range_stab(self, g: _Group, st: "_Stab") -> "_Stab":
        """Stage 2 of the interval stab where its guards broke after a
        fence cached stage 1: the store's CURRENT range_txns membership
        and containment, a dependency at a time -- the filters the legacy
        candidate decode applies at harvest, so the fenced cache decodes
        as the guarded path would have after the same truncation. A range
        subject's hit txn answers with its CURRENT ranges' intersections.
        (While rgen/rseq hold, these filters are no-ops -- rseq certifies
        every stabbed row's txn registered with the same ranges, and the
        device's stab was exact -- and the harvest skips this stage.)"""
        tab = g.rtab
        rt = g.store.range_txns
        keep = np.zeros(st.slot.size, bool)
        seen = set()
        extra: List[tuple] = []
        for t, (ent, row) in enumerate(zip(st.slot.tolist(),
                                           st.row.tolist())):
            rid = st.ids[row]
            rngs = rt.get(rid)
            if rngs is None:
                continue
            if not tab.sub[ent]:
                keep[t] = rngs.contains_key(g.rents[ent][2])
                continue
            j = int(tab.j[ent])
            if (j, rid) in seen:
                continue
            seen.add((j, rid))
            extra.extend((ent, row, _endpoint_code(r.start),
                          _endpoint_code(r.end))
                         for r in rngs.intersection(g.items[j].owned))
        st.take(keep)
        if extra:
            more = np.asarray(extra, np.int64)
            st.slot = np.concatenate((st.slot, more[:, 0]))
            st.row = np.concatenate((st.row, more[:, 1]))
            st.xs = np.concatenate((st.xs, more[:, 2]))
            st.xe = np.concatenate((st.xe, more[:, 3]))
        return st

    def _stab_rkey_finalized(self, call: _Call, g: _Group):
        """Stage 1 of the rk-lane harvest (pin-dependent): the lane's CSR
        as flat (slot, key-arena row) pairs (gen/kseq holding certifies
        them). Returns a _Stab over g.rk_slots -- empty when the lane was
        planned with no covered arena keys -- or None on overflow / missing
        buffer. The mutation fence runs this under still-valid pins; stage
        2 always runs at harvest."""
        arena = g.arena
        n = arena.count
        st = _Stab()
        st.slot = st.row = _EMPTY_I64
        st.ids = arena.ids_np[:n].copy()
        st.ts = arena.ts[:n].copy()
        if not g.rk_slots:
            return st       # planned, but no covered key had an arena id
        if g.rkfin_dev is None and g.rkfin_np is None:
            return None
        buf = self._fetch_np(g, "rkfin_np", g.rkfin_dev)
        if not self._csum_ok(call, g, buf):
            return None     # corrupted readback: caught before decode
        indptr, dep_rows, dbound, _ = buf
        ns = len(g.rk_slots)
        pol = self._observe_bound(arena, "rkey", dbound, ns)
        total = int(indptr[ns])
        if total > dep_rows.shape[0]:
            pol.overflowed()
            return None
        st.slot = np.repeat(np.arange(ns), np.diff(indptr[:ns + 1]))
        st.row = dep_rows[:total].astype(np.int64)
        slot_item = np.fromiter((j for j, _ in g.rk_slots), np.int64, ns)
        self._mask_own_rows(st, slot_item, g.items, arena.row_of)
        return st

    def _filter_rkey_stab(self, g: _Group, st: "_Stab", guarded: bool):
        """Stage 2 of the rk lane (host-map-dependent, always at harvest):
        the candidate decode's harvest-time cfk rules against the store's
        CURRENT maps. A slot whose key has no cfk delivers nothing. While
        gen/kseq hold (`guarded`), a dependency's cfk membership is what
        the row masks the device read say, as on the key lane, so only the
        pairs that can still fail a rule are walked: those on a row the
        arena marks invalidated (a status can change under the guards) and
        those in a slot whose key has covers (transitive-dependency
        elision). A fenced cache whose guards broke walks every pair."""
        slots = g.rk_slots
        if st.slot.size == 0:
            return st
        cfks = g.store.cfks
        arena = g.arena
        gone = np.zeros(len(slots), bool)
        walk = np.zeros(len(slots), bool)
        for s, (_, k) in enumerate(slots):
            c = cfks.get(k)
            if c is None:
                gone[s] = True
            elif c.covered:
                walk[s] = True
        keep = ~gone[st.slot]
        if not guarded:
            check = keep.copy()
        else:
            check = walk[st.slot]
            if arena.invalidated:
                bad = np.zeros(arena.count, bool)
                bad[list(arena.invalidated)] = True
                check |= bad[st.row] & keep
        for t in np.flatnonzero(check).tolist():
            j, k = slots[st.slot[t]]
            item = g.items[j]
            c = cfks[k]
            dep_id = st.ids[st.row[t]]
            info = c.get(dep_id)
            if info is None or info.status == CfkStatus.INVALIDATED:
                keep[t] = False
                continue
            e = c.covered.get(dep_id) if c.covered else None
            if e is not None and e[0] <= item.cover_seq \
                    and e[1] < item.before:
                keep[t] = False   # transitive-dependency elision (cfk rule)
        if not keep.all():
            st.take(keep)
        return st

    def _build_key_subjects(self, g: _Group, rst: "_Stab", kstab):
        """The group's KEY subjects from the interval stab's point entries
        and, where the key lane's stage 1 was held back for it (`kstab`),
        that lane's pairs in the same sort: one KeyDeps an item, cut as
        _assemble_key_deps cuts the key lane's. Slots number (item, place
        of the key among the item's owned keys), the order both lanes walk;
        txn ids order by one rank over both arenas' rows. Returns [KeyDeps]
        per item: whole with `kstab`, else the range-txn deps alone (the
        caller unions them into whatever decoded the key lane)."""
        items = g.items
        n = len(items)
        tab = g.rtab
        cnt = np.fromiter((len(it.owned) if isinstance(it.owned, Keys) else 0
                           for it in items), np.int64, n)
        off = np.concatenate(([0], np.cumsum(cnt)))
        tables = [(rst.ids, rst.ts, rst.live)]
        if kstab is not None:
            arena = g.arena
            tables.append((arena.ids_np[:arena.count],
                           arena.ts[:arena.count], None))
        ranks, by_rank = _joint_rank(tables)
        pick = ~tab.sub[rst.slot]
        ent = rst.slot[pick]
        slot = off[tab.j[ent]] + tab.pos[ent]
        rank = ranks[0][rst.row[pick]]
        if kstab is not None:
            h_slot, h_row = kstab
            flat_cov, covered_any, slot_item = self._slot_covers(g)
            if covered_any and h_slot.size:
                # transitive-dependency elision, only over slots with
                # covers: the key lane's rule (_assemble_key_deps, step 7)
                has = np.fromiter((c is not None for c in flat_cov), bool,
                                  len(flat_cov))
                keep = np.ones(h_slot.size, bool)
                ids = g.arena.ids_np
                for t in np.flatnonzero(has[h_slot]).tolist():
                    s = h_slot[t]
                    item = items[slot_item[s]]
                    e = flat_cov[s].get(ids[h_row[t]])
                    if e is not None and e[0] <= item.cover_seq \
                            and e[1] < item.before:
                        keep[t] = False
                h_slot, h_row = h_slot[keep], h_row[keep]
            slot = np.concatenate(
                (slot, off[slot_item[h_slot]] + g.fin_pos[h_slot]))
            rank = np.concatenate((rank, ranks[1][h_row]))
        out = [KeyDeps.EMPTY] * n
        if slot.size:
            keys = [k for it in items if isinstance(it.owned, Keys)
                    for k in it.owned]
            e_slot, e_rank = _sort_entries(slot, rank, len(by_rank))
            self.array_cuts += 1
            _cut_csr(e_slot, e_rank, off, by_rank,
                     lambda slots: [keys[u] for u in slots.tolist()],
                     KeyDeps, out)
        return out

    def _build_range_subjects(self, g: _Group, rst, kst):
        """The group's RANGE subjects from both stab lanes: the interval
        stab's intersections (`rst`; None: no interval row at plan time)
        and the rk lane's key txns as point ranges (`kst`; likewise), one
        sort -> {item: RangeDeps}. Rows are the distinct (item, start, end)
        of the two lanes in Range order, by endpoint codes; a Range is made
        once a row."""
        n = len(g.items)
        tables, c_j, c_s, c_e, slot, row_of = [], [], [], [], [], []
        nk = 0
        if kst is not None and kst.slot.size:
            rk = np.asarray(g.rk_slots, np.int64).reshape(-1, 2)
            nk = len(rk)
            c_j.append(rk[:, 0])
            c_s.append(2 * rk[:, 1])
            c_e.append(2 * rk[:, 1] + 1)
            slot.append(kst.slot)
            row_of.append((len(tables), kst.row))
            tables.append((kst.ids, kst.ts, None))
        if rst is not None:
            pick = g.rtab.sub[rst.slot]
            if pick.any():
                c_j.append(g.rtab.j[rst.slot[pick]])
                c_s.append(rst.xs[pick])
                c_e.append(rst.xe[pick])
                slot.append(nk + np.arange(c_j[-1].size))
                row_of.append((len(tables), rst.row[pick]))
                tables.append((rst.ids, rst.ts, rst.live))
                self.range_deps += int(c_j[-1].size)
        out: Dict[int, RangeDeps] = {}
        if not tables:
            return out
        c_j, c_s, c_e = (np.concatenate(c) for c in (c_j, c_s, c_e))
        # the candidate rows in Range order an item, densely numbered
        order = np.lexsort((c_e, c_s, c_j))
        s_j, s_s, s_e = c_j[order], c_s[order], c_e[order]
        new = np.ones(order.size, bool)
        new[1:] = (s_j[1:] != s_j[:-1]) | (s_s[1:] != s_s[:-1]) \
            | (s_e[1:] != s_e[:-1])
        dense = np.empty(order.size, np.int64)
        dense[order] = np.cumsum(new) - 1
        u_j, u_s, u_e = s_j[new], s_s[new], s_e[new]
        ranks, by_rank = _joint_rank(tables)
        e_slot, e_rank = _sort_entries(
            dense[np.concatenate(slot)],
            np.concatenate([ranks[t][rows] for t, rows in row_of]),
            len(by_rank))
        memo = g.arena.ranges.row_memo
        if len(memo) > _ROW_MEMO_CAP:
            memo.clear()

        def ranges_of(slots):
            rows = []
            for code in zip(u_s[slots].tolist(), u_e[slots].tolist()):
                r = memo.get(code)
                if r is None:
                    r = memo[code] = Range(_code_endpoint(code[0]),
                                           _code_endpoint(code[1]))
                rows.append(r)
            return rows

        off = np.searchsorted(u_j, np.arange(n + 1))
        self.array_cuts += 1
        _cut_csr(e_slot, e_rank, off, by_rank, ranges_of, RangeDeps, out)
        return out

    def _decode_key_range_deps(self, arena: _StoreArena, rgen: int,
                               rprow: np.ndarray, item: _Item):
        """Range-txn deps of a KEY subject, recovered from the range
        kernel's candidate rows -- the device replacement for the retired
        host_range_deps union. Exact: per-key containment against the
        store's CURRENT range_txns filters interval false positives
        (cross-store rows, freed-row reuse, retired generations), and the
        before/witness masks are re-verified host-side. None when a stale
        call has no pinned snapshot (caller falls back; counted)."""
        rows = _unpack_row(rprow)
        cand = arena.ranges.candidate_ids(rgen, rows)
        if cand is None:
            return None
        kb = KeyDepsBuilder()
        store = item.store
        kind = item.txn_id.kind
        rt = store.range_txns
        for rid in cand:
            if rid == item.txn_id or rid not in rt:
                continue
            if not (rid < item.before and kind.witnesses(rid.kind)):
                continue
            rngs = rt[rid]
            for k in item.owned:
                if rngs.contains_key(k):
                    kb.add(k, rid)
        return kb.build()

    def _decode_range_subject(self, arena: _StoreArena, g: _Group,
                              rprow: Optional[np.ndarray],
                              kprow: Optional[np.ndarray],
                              item: _Item) -> Optional[Deps]:
        """A RANGE subject's full Deps from its group's slices of the two
        candidate buffers: range-vs-range from the interval arena (re-sliced
        against the store's range_txns), range-vs-key from the key arena's
        span hull (re-filtered per real key, with the host scan's
        covered-elision and invalidation rules). None -> no usable snapshot
        (caller falls back; counted)."""
        from accord_tpu.primitives.deps import KeyDeps
        store = item.store
        kind = item.txn_id.kind
        rb = RangeDepsBuilder()
        if rprow is not None:
            rows = _unpack_row(rprow)
            cand = arena.ranges.candidate_ids(g.rgen, rows)
            if cand is None:
                return None
            rt = store.range_txns
            for rid in cand:
                if rid == item.txn_id or rid not in rt:
                    continue
                if not (rid < item.before and kind.witnesses(rid.kind)):
                    continue
                for r in rt[rid].intersection(item.owned):
                    rb.add(r, rid)
        if kprow is not None:
            krows = _unpack_row(kprow)
            if g.gen != arena.gen:
                krows = arena.translate_rows(g.gen, krows)
                if krows is None:
                    return None
            cfks = store.cfks
            for j in krows:
                dep_id = arena.ids_np[j]
                if dep_id is None or dep_id == item.txn_id:
                    continue
                if not (dep_id < item.before
                        and kind.witnesses(dep_id.kind)):
                    continue
                for k in arena.key_sets[j]:
                    if not item.owned.contains_key(k):
                        continue  # span-hull false positive / other store
                    c = cfks.get(k)
                    if c is None:
                        continue
                    info = c.get(dep_id)
                    if info is None or info.status == CfkStatus.INVALIDATED:
                        continue
                    e = c.covered.get(dep_id) if c.covered else None
                    if e is not None and e[0] <= item.cover_seq \
                            and e[1] < item.before:
                        continue  # transitive-dependency elision (cfk rule)
                    rb.add(Range.point(k), dep_id)
        return Deps(KeyDeps.EMPTY, rb.build())

    def _decode_core(self, call: _Call) -> List[Deps]:
        """Decode a harvested call -> raw Deps per item (no floor injection
        -- sync callers' floors are injected by store.calculate_deps; the
        async harvest wraps this with _decode_dispatch). Each _Group slices
        its word-column span out of the fused buffers (the row-offset table
        in action) and decodes against its own store's arena. Handles
        same-gen and stale (compacted mid-flight) groups uniformly:
        key-domain rows translate through the pinned row snapshot, range
        candidates translate by txn id. Falls back to the host scan only
        when no snapshot survived (counted; not expected)."""
        results: List[Optional[Deps]] = [None] * len(call.items)
        if call.degraded:
            # the dispatch was given up on (launch-retry exhaustion or a
            # wedged in-flight call): never touch its device buffers --
            # every item answers through the host differential path,
            # bit-identical to the device decode
            return [item.store.host_calculate_deps(
                        item.txn_id, item.owned, item.before)
                    for item in call.items]
        for g in call.groups:
            arena = g.arena
            idx = np.asarray(g.idx, np.int64)
            has_pk = (call.packed is not None or call.np_packed is not None) \
                and g.pk is not None
            has_rp = (call.rpacked is not None
                      or call.np_rpacked is not None) and g.rp is not None
            has_kp = (call.kpacked is not None
                      or call.np_kpacked is not None) and g.kp is not None
            key_stale = has_pk and g.gen != arena.gen
            gp = grp = gkp = None
            kds = None
            # the key lane's stage 1, held back where the group's interval
            # stab can join it: a key subject's two KeyDeps then come out
            # of one sort as one (_build_key_subjects)
            kstab = None
            if g.fin_slots is not None:
                if g.fin_mat is not None:
                    # the mutation fence materialized this lane while its
                    # pins still held; the cache survives the mutation
                    kds = g.fin_mat
                elif not key_stale and g.kseq == arena.kseq:
                    # device-finalized CSR harvest: exact rows, no raw
                    # readback (empty slot list short-circuits to
                    # all-EMPTY inside)
                    kstab = self._stab_finalized(call, g)
                    if kstab is not None \
                            and (g.rents is None or call.canary):
                        kds = self._finish_finalized(g, kstab)
                        kstab = None
                if kds is not None or kstab is not None:
                    self.finalized_decodes += 1
                    if call.canary and g.fin_mat is None:
                        # probation: check the finalized decode against
                        # the legacy decode of the same plan-time snapshot
                        self._canary_check(call, g, kds)
            if kds is None and kstab is None and has_pk:
                if g.fin_slots is not None:
                    self.finalize_fallbacks += 1
                buf = self._fetch_np(call, "np_packed", call.packed)
                gp = buf[idx][:, g.pk[0]:g.pk[1]]
                if not key_stale:
                    kds = self._decode_batch(arena, g.items, gp)
                    self.legacy_decodes += 1
            # the range lanes, under their own span inside
            # resolver.materialize: the group's KEY subjects' range-txn
            # deps (rkb; or, with the key lane's stage 1 held, their whole
            # KeyDeps) and its range subjects' deps built from both stab
            # lanes (rsub_deps; None -> candidate decode of grp / gkp)
            rkb = rsub_deps = joined = None
            has_rsub = any(not isinstance(it.owned, Keys)
                           and it.fallback is None for it in g.items)
            if g.rents is not None or has_rp or has_kp or has_rsub:
                with self._phase("resolver.range_decode",
                                 "resolver.range_decode_s"):
                    rkb, rsub_deps, grp, gkp, joined = \
                        self._decode_range_lanes(call, g, idx, key_stale,
                                                 has_rp, has_kp, has_rsub,
                                                 kstab)
                if joined is not None:
                    kds, kstab = joined, None
            if kstab is not None:
                # the interval stab did not materialize: the key lane
                # decodes alone, as a key-only store's does
                kds = self._finish_finalized(g, kstab)
            # key subjects' range-txn deps, merged after the item walk
            # (under the same span): (item position, KeyDeps)
            range_unions: List[tuple] = []
            for j, item in enumerate(g.items):
                store = item.store
                if item.fallback == "full":
                    results[g.idx[j]] = store.host_calculate_deps(
                        item.txn_id, item.owned, item.before)
                    continue
                if not isinstance(item.owned, Keys):
                    if not arena.ranges.encode_ok:
                        # reached only via the no-buffer path (encode sets
                        # fallback="full" otherwise): unencodable state
                        self.range_fallbacks += 1
                        results[g.idx[j]] = store.host_calculate_deps(
                            item.txn_id, item.owned, item.before)
                        continue
                    if rsub_deps is not None:
                        # fully device-resident: both stab lanes' builders
                        # merged per item; absent builder -> no deps
                        rd = rsub_deps.get(j)
                        results[g.idx[j]] = Deps(KeyDeps.EMPTY, rd) \
                            if rd is not None else Deps(KeyDeps.EMPTY)
                        self.range_subject_device_decodes += 1
                        continue
                    d = self._decode_range_subject(
                        arena, g, grp[j] if grp is not None else None,
                        gkp[j] if gkp is not None else None, item)
                    if d is None:
                        self.host_fallbacks += 1
                        d = store.host_calculate_deps(
                            item.txn_id, item.owned, item.before)
                    results[g.idx[j]] = d
                    continue
                if kds is not None:
                    kd = kds[j]
                elif key_stale and gp is not None:
                    rows = arena.translate_rows(g.gen, _unpack_row(gp[j]))
                    if rows is None:
                        self.host_fallbacks += 1
                        results[g.idx[j]] = store.host_calculate_deps(
                            item.txn_id, item.owned, item.before)
                        continue
                    kd = arena.decode_rows(item.txn_id, item.owned, rows,
                                           store, item.before,
                                           item.cover_seq)
                else:
                    kd = KeyDeps.EMPTY
                deps = Deps(kd)
                if item.fallback == "range" or not arena.ranges.encode_ok:
                    if store.range_txns:
                        deps = deps.union(store.host_range_deps(
                            item.txn_id, item.owned, item.before))
                elif rkb is not None:
                    extra = rkb.get(j)
                    if extra is not None and not extra.is_empty():
                        range_unions.append((g.idx[j], extra))
                elif grp is not None:
                    extra = self._decode_key_range_deps(arena, g.rgen,
                                                        grp[j], item)
                    if extra is None:
                        self.host_fallbacks += 1
                        deps = deps.union(store.host_range_deps(
                            item.txn_id, item.owned, item.before))
                    elif not extra.is_empty():
                        deps = deps.union(Deps(extra))
                results[g.idx[j]] = deps
            if range_unions:
                with self._phase("resolver.range_decode",
                                 "resolver.range_decode_s"):
                    for pos, extra in range_unions:
                        results[pos] = results[pos].union(Deps(extra))
        return results

    def _decode_range_lanes(self, call: _Call, g: _Group, idx,
                            key_stale: bool, has_rp: bool, has_kp: bool,
                            has_rsub: bool, kstab):
        """One group's range lanes at harvest, decoded as arrays over the
        whole dispatch: stage 1 of the interval stab and of the rk lane
        (or the fence's caches of them), stage 2's host-map filters where
        a lane's guards no longer hold, then one sort a domain -- the key
        subjects' range-txn deps (with the key lane's held stage 1,
        `kstab`, their whole KeyDeps) and the range subjects' deps from
        both lanes. Returns (rkb: item -> KeyDeps or None, rsub_deps: item
        -> RangeDeps, or None when some stab lane a range subject needs
        did not materialize, grp, gkp: the group's slices of the raw
        candidate buffers the fallbacks need, joined: [KeyDeps] per item
        where `kstab` was joined in, else None)."""
        arena = g.arena
        ranges = arena.ranges
        grp = gkp = None
        filtered = False
        # interval stab: point entries for the group's KEY subjects and
        # its range subjects' own pieces (the _RSUB entries)
        rst = None
        if g.rents is not None:
            rst = g.rmat
            guarded = g.rgen == ranges.gen and g.rseq == ranges.rseq
            if rst is None and guarded:
                rst = self._stab_range_finalized(call, g)
            if rst is not None and not guarded:
                # a fenced cache past its guards: current host maps, so
                # it decodes as a guarded harvest would have
                rst = self._filter_range_stab(g, rst)
                filtered = True
            if rst is None:
                self.finalize_fallbacks += 1
        # range subjects decode on device only when EVERY stab lane
        # they need materialized: the interval stab above and the
        # key-arena rk lane below (each absent lane corresponds to an
        # arena with no rows at plan time -- correctly empty)
        rsub_ok = has_rsub and self.finalize_on_device
        if rsub_ok and g.rp is not None and rst is None:
            rsub_ok = False
        kst = None
        if rsub_ok and g.kp is not None:
            kst = g.rk_mat
            guarded = not key_stale and g.gen == arena.gen \
                and g.kseq == arena.kseq
            if kst is None and g.rk_slots is not None and guarded:
                kst = self._stab_rkey_finalized(call, g)
            if kst is None:
                rsub_ok = False
                if g.rk_slots is not None:
                    self.finalize_fallbacks += 1
            else:
                kst = self._filter_rkey_stab(g, kst, guarded)
                filtered = filtered or not guarded
        need_rp = has_rp and (rst is None
                              or (has_rsub and not rsub_ok))
        if need_rp:
            buf = self._fetch_np(call, "np_rpacked", call.rpacked)
            if buf is not None:
                grp = buf[idx][:, g.rp[0]:g.rp[1]]
        if has_kp and any(not isinstance(it.owned, Keys)
                          for it in g.items) and not rsub_ok:
            buf = self._fetch_np(call, "np_kpacked", call.kpacked)
            if buf is not None:
                gkp = buf[idx][:, g.kp[0]:g.kp[1]]
        rkb = rsub_deps = joined = None
        if rst is not None:
            built = self._build_key_subjects(g, rst, kstab)
            if kstab is not None:
                joined, rkb = built, {}
            else:
                rkb = {j: kd for j, kd in enumerate(built)
                       if not kd.is_empty()}
        if rsub_ok:
            rsub_deps = self._build_range_subjects(g, rst, kst)
        if rst is not None or rsub_ok:
            self.range_array_decodes += 1
            if filtered:
                self.range_filtered_decodes += 1
        return rkb, rsub_deps, grp, gkp, joined

    def _decode_dispatch(self, call: _Call) -> List[Deps]:
        """The async harvest decode: core recovery + the store's dep floor
        (the sync path's floors come from store.calculate_deps instead)."""
        return [item.store.inject_dep_floor(item.txn_id, item.owned, d,
                                            item.before)
                for item, d in zip(call.items, self._decode_core(call))]

    def _stage(self, node, items: List[_Item]) -> _Plan:
        """stage_host's encode half: group one dispatch slice by store and
        cut its plan (upload arrays + snapshots + plan-time generation
        pins). The plan launches on the next tick's stage_dispatch (or at
        once, from drain)."""
        # ensure adoption of late-attached stores BEFORE snapshotting group
        # generations -- adoption may mutate (and compact) an arena
        for item in items:
            self._arena(item.store)
        groups_by: Dict[int, _Group] = {}
        groups: List[_Group] = []
        for i, item in enumerate(items):
            g = groups_by.get(id(item.store))
            if g is None:
                g = groups_by[id(item.store)] = \
                    _Group(item.store, self._arenas[id(item.store)])
                groups.append(g)
            g.idx.append(i)
            g.items.append(item)
        health = self._health.get(id(node))
        if health is not None and health.route_host:
            # quarantine reroute: every item answers through the host
            # differential path (bit-identical to the device decode) at
            # the normal harvest event -- no encode, no pins, no device
            # call. The countdown below eventually re-enters the device
            # path on probation.
            for item in items:
                item.fallback = "full"
            self.degraded_dispatches += 1
            health.on_host_dispatch()
            return _Plan(items, groups, empty=True)
        if all(g.arena.count == 0 and g.arena.ranges.count == 0
               for g in groups):
            # nothing on device to conflict with (and possibly no encoder
            # yet): an empty call still flows through the pipeline so floors
            # and fallbacks are injected at harvest
            return _Plan(items, groups, empty=True)
        with self._phase("resolver.encode", "resolver.encode_s", node=node,
                         track="stage_host", event="encode",
                         subjects=len(items), stores=len(groups)):
            return self._encode_plan(groups, items)

    def _launch(self, node, plan: _Plan, staged: bool = False) -> None:
        """stage_dispatch: fire a plan's kernels (generation pins were
        already taken at plan time, matched by unpin_gen in _harvest),
        enqueue the async readback, and schedule the harvest."""
        did = self.dispatches  # monotone per resolver: the trace span key
        if plan.empty:
            call = _Call(None, None, None, plan.items, plan.groups, did=did)
        else:
            from accord_tpu.ops import fault_plane
            plane = fault_plane.ACTIVE
            fault = plane.draw() if plane is not None else None
            degraded = False
            if fault == "dispatch_exc":
                # simulated kernel-launch failure burst: bounded retries
                # (host wall time only -- the harvest event keeps its sim
                # offset, so handling is timing-neutral); a burst past the
                # retry limit gives the dispatch up to the host path
                plane.note("dispatch_exc")
                self.device_faults_injected += 1
                fails = plane.draw_burst()
                self.device_retries += min(fails, self.retry_limit)
                if fails > self.retry_limit:
                    degraded = True
                    self._node_health(node).on_fault("dispatch_exc")
                    if REC.enabled:
                        REC.instant(node_pid(node), "device",
                                    "dispatch_gave_up", node_ts(node),
                                    args={"did": did, "fails": fails})
            if degraded:
                for item in plan.items:
                    item.fallback = "full"
                call = _Call(None, None, None, plan.items, plan.groups,
                             did=did)
                call.degraded = True
                call.faulted = True
                self.degraded_dispatches += 1
            else:
                with self._phase("resolver.launch", "resolver.dispatch_s",
                                 node=node, track="device", event="launch",
                                 did=did):
                    packed, rpacked, kpacked = self._run_plan(plan)
                    call = _Call(packed, rpacked, kpacked, plan.items,
                                 plan.groups, plan.want, did=did)
                    for _, _, dev in call.buffers():
                        _dev_copy_async(dev)
                if call.has_device:
                    self._waiter.add(call)
                    self._occ.launched(call.done)
                if plan.fused:
                    self.fused_dispatches += 1
                    self.store_groups += len(plan.groups)
                if plan.range_call is not None:
                    self.range_dispatches += 1
                    if plan.range_groups:
                        self.fused_range_dispatches += 1
                        self.fused_range_groups += plan.range_groups
                if fault == "stuck":
                    plane.note("stuck")
                    self.device_faults_injected += 1
                    call.stuck_left = plane.draw_stuck()
                elif fault == "corrupt":
                    # applied (and counted) at harvest, once the host
                    # copies exist -- dropped if no finalized lane rode
                    # this call
                    call.corrupt_pending = True
                elif fault == "overflow":
                    # consumed at materialize: the finalize result reports
                    # an out-cap overflow, driving the OutCapTiers bump
                    call.overflow_pending = True
                health = self._health.get(id(node))
                if health is not None and health.wants_canary:
                    call.canary = True
        self.dispatches += 1
        if staged:
            self.staged_dispatches += 1
        self.subjects += len(plan.items)
        if REC.enabled:
            ts = node_ts(node)
            pid = node_pid(node)
            REC.async_begin(pid, "device", "window", f"d{did}", ts,
                            local=True,
                            args={"subjects": len(plan.items),
                                  "staged": staged, "empty": plan.empty})
            # flow steps land each subject txn on the device track, linking
            # coordinator -> replica -> dispatch in the Perfetto view
            for item in plan.items:
                REC.txn_step(pid, item.txn_id, "dispatch", ts,
                             args={"did": did})
        self._inflight.setdefault(id(node), deque()).append(call)
        delay = getattr(node, "device_latency_ms", 4.0)
        # shutdown from an external event loop may arrive with no live
        # scheduler; drain() blocking-harvests, so the timer is optional
        scheduler = getattr(node, "scheduler", None)
        if scheduler is not None:
            scheduler.once(delay, lambda: self._harvest(node))
        self._ensure_poll(node)

    def _dispatch(self, node, items: List[_Item]) -> None:
        """Serial encode+launch of one dispatch slice in a single step:
        drain runs queued-but-unticked items through it at shutdown."""
        self._launch(node, self._stage(node, items))

    def drain(self, node) -> None:
        """Flush the node's pipeline end to end (graceful shutdown): launch
        any encode-ahead plans, run queued-but-unticked items straight
        through serially, then blocking-harvest every in-flight call so no
        AsyncResult strands once the scheduler stops delivering events."""
        for plan in self._staged.pop(id(node), []):
            self._launch(node, plan, staged=True)
        items = self._drain_and_preaccept(node)
        for sub in self._slices(items):
            self._dispatch(node, sub)
        q = self._inflight.get(id(node))
        while q:
            self._harvest(node)

    def _ensure_poll(self, node) -> None:
        """Arm the per-node readiness poll (if the scheduler supports it):
        between dispatch and harvest it drains finished async transfers via
        the non-blocking is_ready() probe, so by the time the deterministic
        harvest event fires the host copy is usually already here. The poll
        only fills _Call.np_packed -- a host-side cache invisible to
        simulated state -- so burns stay bit-for-bit deterministic."""
        poll = getattr(node.scheduler, "poll", None)
        # opt-in via node.device_poll_ms (the bench and real-device deploys
        # set it): poll events are invisible to protocol state but do consume
        # event-queue sequence numbers, so burns that pin exact histories
        # keep their seed-for-seed schedules by defaulting it off
        interval = getattr(node, "device_poll_ms", None)
        if poll is None or interval is None or id(node) in self._polling:
            return
        self._polling.add(id(node))
        self.polls_armed += 1
        q = self._inflight[id(node)]

        def prefetch() -> bool:
            for call in q:
                done = True
                for holder, attr, dev in call.buffers():
                    if getattr(holder, attr) is not None:
                        continue
                    if not _dev_ready(dev):
                        done = False
                        break
                    setattr(holder, attr, self._read(dev))
                if not done:
                    break  # single device stream: later calls finish later
                self._land(call)
            if q:
                return True
            self._polling.discard(id(node))
            return False

        poll(interval, prefetch)

    def _harvest(self, node) -> None:
        q = self._inflight.get(id(node))
        if not q:
            return  # defensive: every dispatch schedules exactly one harvest
        call = q.popleft()
        with self._phase("resolver.harvest", node=node, did=call.did):
            results = self._collect(node, call, hidden=bool(q))
        for item, deps in zip(call.items, results):
            if item.outcome is not None:
                item.out.try_set_success((item.outcome, item.before, deps))
            else:
                item.out.try_set_success(deps)
        self._occ.deliver(len(call.items))

    def _land(self, call: _Call) -> None:
        """The device holds nothing of this call any more: its results are
        on the host (harvest fetch, or the poll drained them early) or it
        was given up on."""
        if call.has_device and not call.landed:
            call.landed = True
            self._waiter.stamp(call.done, time.perf_counter())
            self._occ.landed()
            if not self._occ.inflight:
                self._waiter.join()

    def _collect(self, node, call: _Call, hidden: bool) -> List[Deps]:
        """The harvest's work on one call: wait for the device and fetch
        what the poll left (device_wait + transfer), then decode
        (materialize). `hidden`: calls are still in flight behind this
        one, so the decode runs inside their device window."""
        stalled = False
        if call.has_device and call.stuck_left:
            # harvest watchdog, deterministic half: an injected stuck call
            # eats not-ready probes; within the probe budget it completes
            # late (counted as retries), past it the call is declared
            # wedged and the whole dispatch answers host-side. Probes are
            # host-wall work inside this one harvest event, so sim timing
            # (and therefore the committed history) is unchanged.
            probes = min(call.stuck_left, self.watchdog_probes)
            self.device_retries += probes
            call.stuck_left -= probes
            if call.stuck_left > 0:
                self.device_watchdog_trips += 1
                self._node_health(node).on_fault("stuck")
                call.degraded = True
                for item in call.items:
                    item.fallback = "full"
                if REC.enabled:
                    REC.instant(node_pid(node), "device", "watchdog_trip",
                                node_ts(node), args={"did": call.did})
        if call.has_device and not call.degraded:
            rb0 = self.readback_s
            stalled = call.fetch(self._read)
            ft = self.readback_s - rb0
            if stalled:
                self.harvest_stall_s += ft
            else:
                self.prefetched += 1
            if self.watchdog_wall_s is not None \
                    and ft > self.watchdog_wall_s:
                # wall half (real devices): a transfer past the budget is
                # a late completion -- results are still used (checksum
                # still guards them) but the ladder records the fault
                self.device_watchdog_trips += 1
                call.faulted = True
                self._node_health(node).on_fault("late")
            if call.corrupt_pending:
                from accord_tpu.ops import fault_plane
                if fault_plane.ACTIVE is not None:
                    self._apply_corruption(call, fault_plane.ACTIVE)
                call.corrupt_pending = False
        self._land(call)
        if REC.enabled:
            REC.async_end(node_pid(node), "device", "window",
                          f"d{call.did}", node_ts(node), local=True,
                          args={"stalled": stalled})
        with self._phase("resolver.materialize", "resolver.decode_s",
                         node=node, track="device", event="decode",
                         did=call.did) as ph:
            ph.args = {"hidden": hidden, "did": call.did}
            if any((g.pk is not None and g.gen != g.arena.gen)
                   or (g.rp is not None and g.rgen != g.arena.ranges.gen)
                   for g in call.groups):
                self.stale_harvests += 1
            rb0 = self.readback_s
            results = self._decode_dispatch(call)
            for g in call.groups:
                if g.pinned:
                    g.arena.unpin_gen(g.gen)
                if g.rpinned:
                    g.arena.ranges.unpin_gen(g.rgen)
        # lazy fallback fetches inside the decode were timed into readback_s;
        # what's left is pure host materialization
        self.materialize_s += ph.dt - (self.readback_s - rb0)
        health = self._health.get(id(node))
        if health is not None and call.has_device and not call.degraded \
                and not call.faulted:
            # a fully clean device harvest walks DEGRADED back toward
            # HEALTHY (and counts probation canaries via _canary_check)
            health.on_clean_dispatch()
        return results

    # -- synchronous SPI (tests, rare recovery-path callers) ------------------
    def resolve_one(self, store, txn_id, seekables, before) -> Deps:
        enc = self._encoders.get(id(store.node))
        if enc is not None and enc.encoder is not None \
                and not enc.encoder.in_window(before):
            # e.g. Timestamp.MAX (ephemeral reads bound by "everything"):
            # unencodable on device -- the host scan answers
            return store.host_calculate_deps(txn_id, seekables, before)
        owned = store.owned(seekables)
        return self.resolve_batch(store, [(txn_id, owned, before)])[0]

    def resolve_batch(self, store,
                      subjects: Sequence[Tuple[TxnId, Seekables, Timestamp]]) -> List[Deps]:
        """Synchronous resolve (dispatch + immediate harvest): exact host
        parity for BOTH key- and range-domain subjects, used by differential
        tests and the rare non-batched callers. No floor injection here --
        store.calculate_deps owns the floor on this path."""
        arena = self._arena(store)
        items = [_Item(store, t, owned, before, None)
                 for (t, owned, before) in subjects]
        g = _Group(store, arena)
        g.idx = list(range(len(items)))
        g.items = items
        if arena.count == 0 and arena.ranges.count == 0:
            call = _Call(None, None, None, items, [g])
        else:
            plan = self._encode_plan([g], items, pin=False)
            packed, rpacked, kpacked = self._run_plan(plan)
            call = _Call(packed, rpacked, kpacked, items, [g], plan.want)
            call.fetch(self._read)
        return self._decode_core(call)

    # -- max-conflict (device path; inline mode + bench only) ----------------
    def max_conflict(self, store, txn_id: TxnId,
                     seekables: Seekables) -> Tuple[bool, Optional[Timestamp]]:
        if not isinstance(seekables, Keys):
            return False, None
        if store.batch_window_ms is not None:
            # batched mode: witness timestamps come from the O(1) host
            # MaxConflicts map inside the tick -- a synchronous device call
            # here would serialize the pipeline on a blocking readback
            return False, None
        arena = self._arenas.get(id(store))
        if arena is not None and arena.had_truncation:
            # truncation shrinks bitmap rows, so the (monotone) device
            # max-conflict could understate -- the host decides. (The old
            # host_only guard is gone: the CSR encoding keeps wide rows on
            # device.)
            return False, None
        res = self.max_conflict_batch(store, [(txn_id, seekables)])
        return res[0]

    def max_conflict_batch(self, store, subjects) -> List[Tuple[bool, Optional[Timestamp]]]:
        """subjects: [(txn_id, keys)] -> (handled, max conflicting registered
        timestamp) per subject. The device returns the winning row; a bucket-
        collision false positive (row's real keys don't intersect) falls back
        to the host scan for that subject (rare)."""
        import jax.numpy as jnp
        from accord_tpu.ops.encoding import encode_key_bitmaps
        from accord_tpu.ops.kernels import bucket_size, max_conflict, pad_to
        arena = self._arena(store)
        if arena.count == 0:
            return [(True, None) for _ in subjects]
        b = len(subjects)
        padded_b = bucket_size(b)
        bitmaps = encode_key_bitmaps([tuple(kk) for _, kk in subjects],
                                     self.num_buckets)
        act_bm, _, act_exec, _, act_valid = arena.device_arrays()
        # registered rows count even when invalidated (MaxConflicts is
        # monotone in the reference); valid lane is NOT applied here
        all_rows = jnp.ones_like(act_valid)
        _, rows = max_conflict(
            jnp.asarray(pad_to(bitmaps, padded_b)),
            act_bm, act_exec, all_rows)
        rows = np.asarray(rows)[:b]
        out: List[Tuple[bool, Optional[Timestamp]]] = []
        for i, (subj_id, subj_keys) in enumerate(subjects):
            j = int(rows[i])
            if j < 0 or j >= arena.count:
                out.append((True, None))
                continue
            subj_set = set(subj_keys)
            if any(k in subj_set for k in arena.key_sets[j]):
                out.append((True, arena.exec_max[j]))
            else:
                out.append((False, None))  # bucket collision: host decides
        return out


class ShardedBatchDepsResolver(BatchDepsResolver):
    """BatchDepsResolver whose fused deps kernel runs SHARDED over a device
    mesh: arena rows over the 'data' axis, key buckets over 'model' (the
    overlap contraction psums across it) -- the reference's intra-node scale
    dimension (CommandStores range-splitting, local/CommandStores.java:79)
    mapped onto chips. Everything else -- arena maintenance, async pipeline,
    exact per-key decode -- is inherited unchanged, so host/single-device/
    sharded answers are differentially comparable.

    The mesh jit's in_shardings reshard the arena arrays on entry each call
    (the arena keeps holding the single-device arrays its scatters produce).
    On a virtual CPU mesh that cost is noise; a real multi-chip deployment
    would additionally give the scatter/grow ops matching out_shardings so
    the arrays LIVE sharded and the per-call movement is dirty rows only.

    With a ClusterTickEngine attached in megakernel mode, the recorded
    plan args (the shared staging code records them whenever tick_driver
    is set) launch through parallel/mesh.sharded_protocol_tick instead of
    the unfused sharded pair: one fused mesh program per cluster tick,
    warmed by parallel.mesh.warmup_sharded's mega_quorum_sizes tiers."""

    def __init__(self, mesh=None, num_buckets: int = 256,
                 initial_cap: int = 4096,
                 pad_store_tiers: Optional[int] = None,
                 finalize_on_device: bool = True,
                 adaptive_window: bool = False, kid_cap: int = 4096,
                 pad_node_tiers=None):
        super().__init__(num_buckets, initial_cap,
                         pad_store_tiers=pad_store_tiers,
                         finalize_on_device=finalize_on_device,
                         adaptive_window=adaptive_window, kid_cap=kid_cap,
                         pad_node_tiers=pad_node_tiers)
        from accord_tpu.parallel.mesh import make_mesh
        self.mesh = mesh if mesh is not None else make_mesh()
        data = self.mesh.shape["data"]
        model = self.mesh.shape["model"]
        # both contracts survive arena doubling (the power-of-two bucket
        # count the contraction needs is asserted by the base class)
        Invariants.check_argument(
            initial_cap % (32 * data) == 0,
            "arena cap %s not divisible by 32*data(%s)", initial_cap, data)
        Invariants.check_argument(
            num_buckets % model == 0,
            "num_buckets %s not divisible by model(%s)", num_buckets, model)
        # the range arena shards its rows over 'data' too, so its capacity
        # must honor the same 32*data packing contract (GROW=2 preserves it)
        self.range_cap = max(64, 32 * data)

    def _run_kernel(self, ksnap, subj_of, subj_keys, sb, sknd):
        # sharded_deps_resolve is lru_cached by mesh: every resolver (one
        # per node in a burn) shares one compiled kernel
        from accord_tpu.parallel.mesh import sharded_deps_resolve
        kern = sharded_deps_resolve(self.mesh)
        act_bm, act_ts, _, act_kinds, act_valid = ksnap
        return kern(subj_of, subj_keys, sb, sknd,
                    act_bm, act_ts, act_kinds, act_valid, self._table)

    def _run_finalize_kernel(self, packed, j_off, kid_rows, j_subj, j_kid,
                             j_srow, out_cap: int):
        # the finalize compaction shards its word columns over 'data': each
        # shard popcounts and compacts ITS slice of every slot's row mask,
        # an all-gather of the per-shard counts yields the global indptr
        # plus each shard's write base, and a psum gather-merges the
        # disjoint dep_rows fragments -- no chip ever materializes the full
        # conflict matrix (lru_cached by mesh; launch time in shard_merge_s)
        from accord_tpu.parallel.mesh import sharded_finalize_csr
        kern = sharded_finalize_csr(self.mesh)
        with self._phase("resolver.shard_merge", "resolver.shard_merge_s"):
            return kern(packed, j_off, kid_rows, j_subj, j_kid, j_srow,
                        out_cap=out_cap)

    def _run_range_kernel(self, rsnap, ksnap, iv_of, iv_s, iv_e,
                          sb, sknd, srng):
        # the key-side coverage test runs bucket-contracted over 'model':
        # the subject intervals scatter into local bucket coverage and the
        # key bitmap contracts against it (host decode re-filters per real
        # key, so the conservative coverage superset stays exact end to end)
        from accord_tpu.parallel.mesh import sharded_range_deps_resolve
        kern = sharded_range_deps_resolve(self.mesh)
        r_start, r_end, r_ts, r_kinds, r_valid = rsnap
        act_bm, k_ts, _, k_kinds, k_valid = ksnap
        return kern(iv_of, iv_s, iv_e, sb, sknd, srng,
                    r_start, r_end, r_ts, r_kinds, r_valid,
                    act_bm, k_ts, k_kinds, k_valid, self._table)

    def _run_fused_kernel(self, ksnaps, slots, subj_of, subj_keys,
                          subj_store, sb, sknd):
        # lru_cached by (mesh, store count): all same-width fused dispatches
        # share one compiled kernel
        from accord_tpu.parallel.mesh import sharded_fused_deps_resolve
        arenas = [(bm, ts, kinds, valid)
                  for (bm, ts, _, kinds, valid) in ksnaps]
        slots = self._pad_fused(arenas, slots, self._pad_key_block)
        kern = sharded_fused_deps_resolve(self.mesh, len(arenas))
        return kern(subj_of, subj_keys, subj_store, sb, sknd,
                    slots, tuple(arenas), self._table)

    def _run_fused_range_kernel(self, rsnaps, r_slots, ksnaps, k_slots,
                                iv_of, iv_s, iv_e, subj_store, sb, sknd,
                                srng):
        from accord_tpu.parallel.mesh import sharded_fused_range_deps_resolve
        rarenas = list(rsnaps)
        r_slots = self._pad_fused(rarenas, r_slots, self._pad_range_block)
        karenas = [(bm, ts, kinds, valid)
                   for (bm, ts, _, kinds, valid) in ksnaps]
        k_slots = self._pad_fused(karenas, k_slots, self._pad_key_block)
        kern = sharded_fused_range_deps_resolve(self.mesh, len(rarenas),
                                                len(karenas))
        return kern(iv_of, iv_s, iv_e, subj_store, sb, sknd, srng,
                    r_slots, tuple(rarenas), k_slots, tuple(karenas),
                    self._table)
