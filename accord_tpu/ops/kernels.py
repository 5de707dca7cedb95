"""JAX kernels for the deps data plane.

Design notes (TPU-first):
  - The conflict test is a boolean matmul: bitmap[B,K] @ bitmap[A,K]^T on the
    MXU in bfloat16 with float32 accumulation. K (key buckets) is a multiple
    of 128 (lane width); B and A are padded to multiples of 8 (sublanes).
  - Kind filtering is a per-subject bit mask of the 6x6 witness table ANDed
    with a per-row kind bit (_witness_mask; never a gather over the
    candidate matrix); timestamp comparison is lexicographic over three
    int32 lanes -- both VPU element-wise ops XLA fuses into the matmul
    epilogue.
  - Transitive closure is iterated boolean matmul (repeated squaring), the
    standard reachability-by-matmul formulation; log2(N) MXU rounds.
All functions are jit-compiled with static shapes; callers pad to bucket
sizes (see resolver.py) so compilation caches are hit.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from accord_tpu.ops.tiers import snap


def _lex_before(a, b):
    """a < b lexicographically over 3 int32 lanes; a: [..., 3], b: [..., 3]
    (broadcasting)."""
    return ((a[..., 0] < b[..., 0])
            | ((a[..., 0] == b[..., 0])
               & ((a[..., 1] < b[..., 1])
                  | ((a[..., 1] == b[..., 1]) & (a[..., 2] < b[..., 2])))))


def _witness_mask(witness_table, subj_kinds, act_kinds):
    """The witness relation table[kind_subject, kind_row] == 1 as
    bool[B, A], without the lookup: each subject's table row becomes a bit
    mask (bit j set iff table[kind, j] == 1), each active row one bit
    (1 << kind), and a candidate costs one AND and one compare that fuse
    with the masks around them. The gather this replaces was materialised
    by XLA:TPU as an s32[B * A] array at ~7 ns an element: 119 ms of
    deps_resolve's 178 ms a dispatch at 1,024 x 16,384 (ledger, PR 25).
    Total like the indexing it replaces: a negative kind wraps once, then
    clamps, so no shift count is out of range."""
    n, m = witness_table.shape
    assert m <= 32, "one uint32 of column bits per table row"

    def norm(kinds, size):
        return jnp.clip(jnp.where(kinds < 0, kinds + size, kinds),
                        0, size - 1).astype(jnp.uint32)

    one = jnp.uint32(1)
    rowbits = jnp.sum(
        jnp.where(witness_table == 1,
                  one << jnp.arange(m, dtype=jnp.uint32)[None, :], 0),
        axis=1, dtype=jnp.uint32)
    wbits = jnp.sum(
        jnp.where(norm(subj_kinds, n)[:, None]
                  == jnp.arange(n, dtype=jnp.uint32)[None, :],
                  rowbits[None, :], 0),
        axis=1, dtype=jnp.uint32)
    abit = one << norm(act_kinds, m)
    return (wbits[:, None] & abit[None, :]) != 0


@functools.partial(jax.jit, static_argnames=())
def deps_matrix(subj_bitmaps, subj_before, subj_kinds,
                act_bitmaps, act_ts, act_kinds, act_valid,
                witness_table):
    """Pairwise dependency matrix.

    subj_bitmaps: f32[B, K]   keys touched by each subject txn
    subj_before:  i32[B, 3]   'started before' bound per subject (usually the
                              witnessed executeAt; reference semantics of
                              mapReduceActive STARTED_BEFORE)
    subj_kinds:   i32[B]
    act_bitmaps:  f32[A, K]   active-set key bitmaps
    act_ts:       i32[A, 3]   active txn ids (3-lane window-relative encoding)
    act_kinds:    i32[A]
    act_valid:    bool[A]     false for padding / invalidated entries
    witness_table: i32[6, 6]

    -> bool[B, A] : dep[b, a] == True iff active txn a is a dependency of
                    subject b (keys overlap AND subject witnesses a's kind AND
                    a started before b's bound AND a != b).

    The witness test is _witness_mask's bit mask and one AND, not a lookup
    in the table at each candidate: that gather was 119 ms of deps_resolve's
    178 ms a dispatch on the TPU v5e (ledger, PR 25).
    """
    overlap = jax.lax.dot_general(
        subj_bitmaps.astype(jnp.bfloat16), act_bitmaps.astype(jnp.bfloat16),
        (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32) > 0.5
    witness = _witness_mask(witness_table, subj_kinds, act_kinds)
    before = _lex_before(act_ts[None, :, :], subj_before[:, None, :])
    return overlap & witness & before & act_valid[None, :]


@functools.partial(jax.jit, static_argnames=())
def max_conflict(subj_bitmaps, act_bitmaps, act_exec_ts, act_valid):
    """Max witnessed-conflict timestamp per subject (feeds the fast-path test
    txnId >= maxConflicts; reference: MaxConflicts + CommandStore.preaccept).
    Kind-agnostic, like the reference's MaxConflicts: ANY registered txn on a
    shared key raises the timestamp floor.

    act_exec_ts: i32[A, 3] -- max(executeAt, txnId) per active txn.
    -> (i32[B, 3] lexicographic max (INT32_MIN lanes where no conflict),
        i32[B] winning row (-1 where none)).
    """
    overlap = jax.lax.dot_general(
        subj_bitmaps.astype(jnp.bfloat16), act_bitmaps.astype(jnp.bfloat16),
        (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32) > 0.5
    mask = overlap & act_valid[None, :]
    neg = jnp.int32(np.iinfo(np.int32).min)
    # lexicographic max without int64: successive tie-narrowing per lane
    l0 = jnp.where(mask, act_exec_ts[None, :, 0], neg)
    m0 = jnp.max(l0, axis=1)
    tie0 = mask & (act_exec_ts[None, :, 0] == m0[:, None])
    l1 = jnp.where(tie0, act_exec_ts[None, :, 1], neg)
    m1 = jnp.max(l1, axis=1)
    tie1 = tie0 & (act_exec_ts[None, :, 1] == m1[:, None])
    l2 = jnp.where(tie1, act_exec_ts[None, :, 2], neg)
    m2 = jnp.max(l2, axis=1)
    tie2 = tie1 & (act_exec_ts[None, :, 2] == m2[:, None])
    # winning row per subject (first among ties); -1 when no conflict
    row = jnp.where(jnp.any(tie2, axis=1),
                    jnp.argmax(tie2, axis=1).astype(jnp.int32), -1)
    return jnp.stack([m0, m1, m2], axis=1), row


@functools.partial(jax.jit, static_argnames=("iterations",))
def transitive_closure(adj, iterations: int):
    """Reachability closure of a boolean adjacency matrix by repeated
    squaring: R_{i+1} = R_i | (R_i @ R_i). `iterations` >= ceil(log2(N)).
    (the execute-order closure kernel; BASELINE config 'Synthetic Execute
    DAG')."""

    def body(_, r):
        rf = r.astype(jnp.bfloat16)
        sq = jax.lax.dot_general(rf, rf, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32) > 0.5
        return r | sq

    return jax.lax.fori_loop(0, iterations, body, adj)


@functools.partial(jax.jit, static_argnames=("max_levels",))
def execution_wavefronts(adj, max_levels: int):
    """Topological execution levels of a dependency DAG: level[i] = longest
    dependency chain ending at i (the order the execution engine may release
    txns in parallel waves). adj[i, j] == True iff i depends on j.
    -> i32[N] levels (max_levels if a cycle prevents settling)."""
    n = adj.shape[0]

    def body(_, level):
        # level'_i = 1 + max_j adj[i,j] * level_j   (0 if no deps)
        dep_levels = jnp.where(adj, level[None, :] + 1, 0)
        return jnp.maximum(level, jnp.max(dep_levels, axis=1))

    return jax.lax.fori_loop(0, max_levels, body, jnp.zeros(n, jnp.int32))


def _lex_le(a, b):
    """a <= b lexicographically over 3 int32 lanes (broadcasting)."""
    return ~_lex_before(b, a)


def _frontier_ready(adj, exec_ts, applied, pending, awaits_all):
    """The release test shared by the per-store and fused frontier kernels:
    pending rows whose gates are all clear (dep applied, or dep decided to
    execute after us and we are not an awaits-all kind)."""
    dep_le = _lex_le(exec_ts[None, :, :], exec_ts[:, None, :])  # dep <= waiter
    gates = adj & (~applied)[None, :] & (dep_le | awaits_all[:, None])
    return pending & ~jnp.any(gates, axis=1)


@jax.jit
def execution_frontier(adj, exec_ts, applied, pending, awaits_all):
    """The device execution scheduler's release test (reference: the host
    WaitingOn bitsets + Commands.maybeExecute walk, local/Command.java:1224,
    local/Commands.java:960 -- recomputed in batch on device instead of
    per-edge on the host).

    adj:        bool[cap, cap] dep adjacency; adj[w, d] iff w holds a wait
                edge on arena row d. Kept UNPACKED on device: exec_scatter
                unpacks uploaded rows once, so the per-tick frontier never
                re-expands the whole matrix.
    exec_ts:    i32[cap, 3]  executeAt lanes (INT32_MIN while undecided --
                an undecided dep always gates, the commit-wait)
    applied:    bool[cap]    dep applied (or terminal: no longer gates)
    pending:    bool[cap]    row is STABLE/PRE_APPLIED awaiting release
    awaits_all: bool[cap]    row's kind waits for EVERY dep to apply
                (ExclusiveSyncPoint / EphemeralRead), regardless of
                executeAt order

    -> u32[cap/32] packed release frontier: pending rows whose gates are all
    clear (dep applied, or dep decided to execute after us and we are not an
    awaits-all kind).
    """
    cap = adj.shape[0]
    bits = jnp.arange(32, dtype=jnp.uint32)
    ready = _frontier_ready(adj, exec_ts, applied, pending, awaits_all)
    weights = jnp.uint32(1) << bits
    return jnp.sum(ready.reshape(cap // 32, 32).astype(jnp.uint32)
                   * weights[None, :], axis=-1, dtype=jnp.uint32)


@jax.jit
def fused_execution_frontier(planes):
    """Cross-store fused twin of execution_frontier: one device call answers
    every store's release frontier for a node tick. `planes` is a TUPLE of
    per-store lane tuples (adj, exec_ts, applied, pending, awaits_all) -- jit
    specializes on the tuple structure, so the participating-store count and
    each store's cap are warmable tiers exactly like the resolver's fused
    dispatch. Per-store packed frontiers concatenate along the word axis; the
    host slices them back out with per-store word spans.

    -> u32[sum(cap_s)/32] packed release frontier, store blocks in tuple order
    """
    outs = []
    bits = jnp.arange(32, dtype=jnp.uint32)
    weights = jnp.uint32(1) << bits
    for (adj, exec_ts, applied, pending, awaits_all) in planes:
        cap = adj.shape[0]
        ready = _frontier_ready(adj, exec_ts, applied, pending, awaits_all)
        outs.append(jnp.sum(ready.reshape(cap // 32, 32).astype(jnp.uint32)
                            * weights[None, :], axis=-1, dtype=jnp.uint32))
    return jnp.concatenate(outs)


@functools.partial(jax.jit, static_argnames=("max_levels",))
def dag_wavefronts_packed(adj_packed, max_levels: int):
    """Topological release levels of a dependency DAG at scale (the BASELINE
    'Synthetic Execute DAG' config: 100k nodes). Works entirely on packed
    words -- never materializes the N x N boolean matrix -- so memory is
    N^2/8 bytes and each round is N x N/32 u32 lanes on the VPU.

    adj_packed: u32[N, N/32]; bit d of row w set iff w depends on d.
    -> i32[N] level per node (-1 if not settled within max_levels).
    """
    n, words = adj_packed.shape
    bits = jnp.uint32(1) << jnp.arange(32, dtype=jnp.uint32)

    def body(i, state):
        applied_packed, level = state
        blocked = jnp.any(adj_packed & (~applied_packed)[None, :] != 0, axis=1)
        ready = ~blocked & (level < 0)
        level = jnp.where(ready, i, level)
        rp = jnp.sum(ready.reshape(words, 32).astype(jnp.uint32)
                     * bits[None, :], axis=-1, dtype=jnp.uint32)
        return applied_packed | rp, level

    state = (jnp.zeros(words, jnp.uint32), jnp.full(n, -1, jnp.int32))
    _, level = jax.lax.fori_loop(0, max_levels, body, state)
    return level


@jax.jit
def exec_scatter(adj, exec_ts, applied, pending, awaits_all,
                 rows, adj_rows_packed, ts_rows, applied_rows, pending_rows,
                 awaits_rows):
    """Scatter dirty rows into the execution arena. Adjacency rows arrive
    PACKED from the host (cap/8 bytes per row over the slow link) and are
    unpacked on device into the resident bool matrix."""
    cap = adj.shape[0]
    bits = jnp.arange(32, dtype=jnp.uint32)
    unpacked = (((adj_rows_packed[:, :, None] >> bits[None, None, :]) & 1) > 0) \
        .reshape(adj_rows_packed.shape[0], cap)
    return (adj.at[rows].set(unpacked),
            exec_ts.at[rows].set(ts_rows),
            applied.at[rows].set(applied_rows),
            pending.at[rows].set(pending_rows),
            awaits_all.at[rows].set(awaits_rows))


@jax.jit
def scatter_rows(dst, idx, rows):
    """dst[cap, ...] with dst[idx[i]] = rows[i] -- the incremental device
    active-set update (dirty rows only; jit caches per (cap, len(idx)) shape
    bucket)."""
    with jax.named_scope("lane_scatter"):
        return dst.at[idx].set(rows)


@jax.jit
def arena_copy(*lanes):
    """A device copy of each of `lanes`: the first step of an arena sync
    that starts from lanes a staged plan may hold (resolver._StoreArena).
    The copies are the sync's own, so every scatter after it donates."""
    with jax.named_scope("arena_copy"):
        return tuple(jnp.copy(a) for a in lanes)


@functools.partial(jax.jit, donate_argnums=(0,))
def kid_word_scatter(kid_rows, kid_idx, word_idx, words):
    """Incremental update of the per-key packed row-mask mirror
    (finalize_csr's kid_rows lane): write whole u32 WORDS at (kid, word)
    coordinates. The host dedupes coordinates and sources each word's full
    current value, so duplicate-index write hazards never arise; padding
    entries use kid_idx == KC (out of bounds, dropped). `kid_rows` is
    DONATED and rewritten in place: the caller owns it (see arena_scatter)."""
    with jax.named_scope("kid_word_scatter"):
        return kid_rows.at[kid_idx, word_idx].set(words, mode="drop")


def _pack_bits(m):
    """bool[B, A] -> u32[B, A/32] little-bit-first per lane (A % 32 == 0)."""
    b, a = m.shape
    with jax.named_scope("pack_bits"):
        weights = (jnp.uint32(1) << jnp.arange(32, dtype=jnp.uint32))
        return jnp.sum(m.reshape(b, a // 32, 32).astype(jnp.uint32)
                       * weights[None, None, :], axis=-1, dtype=jnp.uint32)


@jax.jit
def deps_resolve(subj_of, subj_keys, subj_before, subj_kinds,
                 act_bitmaps, act_ts, act_kinds, act_valid,
                 witness_table):
    """The fused hot-path kernel: subject bitmaps built ON DEVICE from a
    variable-width CSR key list (uploading 2 x nnz int32 instead of B x K
    float bitmaps -- the host->device link is the bottleneck, see
    resolver.py). The CSR replaces the old fixed i32[B, MAXK] scatter:
    arbitrarily wide subjects stay on the device path instead of demoting to
    a host residual scan. The pairwise conflict matrix is BIT-PACKED on
    device for the readback: 32 arena rows per uint32 lane, so the transfer
    is B x cap/8 bytes regardless of how many dependencies each subject has
    (a top-k index list was tried first: its coverage/latency trade collapses
    under contention where counts reach hundreds).

    subj_of:     i32[nnz]      subject row per CSR entry (padding entries use
                               B -- out of bounds, dropped by the scatter)
    subj_keys:   i32[nnz]      key bucket indices (already % K)
    subj_before: i32[B, 3]     'started before' bound per subject (3-lane
                               encoding)
    subj_kinds:  i32[B]
    act_*:       the device arena (see resolver._StoreArena); cap % 32 == 0
    -> u32[B, cap/32] packed dependency bitmask, little-bit-first per lane

    The witness test is _witness_mask's per-subject bit mask ANDed with a
    per-row kind bit: the table lookup at each of B x cap candidates it
    replaces was 119 ms of this kernel's 178 ms a dispatch on the TPU v5e
    (ledger, PR 25), and the AND fuses into the contraction's epilogue.

    The stages carry jax.named_scope names (metadata only: the operations'
    `tf_op` path in a profiler trace), as do finalize_csr's.
    """
    b = subj_before.shape[0]
    k = act_bitmaps.shape[1]
    with jax.named_scope("subject_bitmap"):
        subj_bm = jnp.zeros((b, k), jnp.float32) \
            .at[subj_of, subj_keys].max(1.0, mode="drop").astype(jnp.bfloat16)
    with jax.named_scope("overlap"):
        overlap = jax.lax.dot_general(
            subj_bm, act_bitmaps.astype(jnp.bfloat16),
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) > 0.5
    with jax.named_scope("witness_before_mask"):
        witness = _witness_mask(witness_table, subj_kinds, act_kinds)
        before = _lex_before(act_ts[None, :, :], subj_before[:, None, :])
        m = overlap & witness & before & act_valid[None, :]
    return _pack_bits(m)


@jax.jit
def fused_deps_resolve(subj_of, subj_keys, subj_store, subj_before,
                       subj_kinds, slots, arenas, witness_table):
    """Cross-store fused twin of deps_resolve: one device call answers every
    store's slice of a node tick. `arenas` is a TUPLE of per-store lane
    tuples (bitmaps, ts, kinds, valid) -- jit specializes on the tuple
    structure, so the participating-store count is a warmable tier exactly
    like the batch size. The subject bitmap is built ONCE from the CSR; each
    store's block masks by the store-id lane (subj_store == slots[s]) so a
    subject only sees its own store's rows, and the per-store packed blocks
    concatenate into one u32[B, sum(cap_s)/32] readback whose word offsets
    are the host-side row-offset table.

    subj_store: i32[B]   group slot per subject (padding rows use a slot no
                         entry of `slots` matches)
    slots:      i32[S]   the group slot each arena block answers (traced, so
                         slot assignment never recompiles)
    arenas:     tuple of S (bitmaps f32[cap_s, K], ts i32[cap_s, 3],
                kinds i32[cap_s], valid bool[cap_s])
    -> u32[B, sum(cap_s)/32] packed dependency bitmask, store blocks in
       `arenas` order
    """
    b = subj_before.shape[0]
    k = arenas[0][0].shape[1]
    subj_bm = jnp.zeros((b, k), jnp.float32) \
        .at[subj_of, subj_keys].max(1.0, mode="drop").astype(jnp.bfloat16)
    outs = []
    for s, (act_bm, act_ts, act_kinds, act_valid) in enumerate(arenas):
        overlap = jax.lax.dot_general(
            subj_bm, act_bm.astype(jnp.bfloat16),
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) > 0.5
        witness = _witness_mask(witness_table, subj_kinds, act_kinds)
        before = _lex_before(act_ts[None, :, :], subj_before[:, None, :])
        mine = (subj_store == slots[s])[:, None]
        outs.append(_pack_bits(
            overlap & witness & before & act_valid[None, :] & mine))
    return jnp.concatenate(outs, axis=1)


def covered_buckets(iv_of, iv_start, iv_end, b, k_local, base, k_total):
    """Per-subject covered-bucket mask from a CSR interval list under the
    modular bucket hash `key % k_total`: bucket `base + j` is covered by the
    half-open interval [s, e) iff some integer in [s, e) lands in it, i.e.
    `(base + j - s) mod k_total < e - s`. Intervals spanning >= k_total keys
    (and degenerate/padding widths <= 0) cover every bucket. Exact under
    int32 wraparound ONLY when k_total divides 2^32 -- callers assert
    power-of-two bucket counts. `base` may be traced (shard_map axis offset);
    single-device callers pass 0 with k_local == k_total.

    -> bf16[b, k_local] covered-bucket matrix (padding iv_of == b dropped)
    """
    j = base + jnp.arange(k_local, dtype=jnp.int32)
    width = iv_end - iv_start
    wide = (width <= 0) | (width >= k_total)
    covered = wide[:, None] | (
        jnp.mod(j[None, :] - iv_start[:, None], k_total) < width[:, None])
    return jnp.zeros((b, k_local), jnp.float32) \
        .at[iv_of].max(covered.astype(jnp.float32), mode="drop") \
        .astype(jnp.bfloat16)


@jax.jit
def fused_range_deps_resolve(iv_of, iv_start, iv_end, subj_store,
                             subj_before, subj_kinds, subj_is_range,
                             r_slots, rarenas, k_slots, karenas,
                             witness_table):
    """Cross-store fused twin of range_deps_resolve. `rarenas` holds the
    participating stores' RANGE-arena lanes (starts, ends, ts, kinds, valid),
    `karenas` the stores' key-arena lanes (bitmaps, ts, kinds, valid) tested
    by covered-bucket contraction (see range_deps_resolve); either tuple may
    be empty (that side returns a zero-width buffer). Store routing works
    like fused_deps_resolve: each block masks by its slot in the subj_store
    lane, and blocks concatenate along the packed word axis in tuple order.

    -> (u32[B, sum(rcap_s)/32], u32[B, sum(cap_s)/32])
    """
    b = subj_before.shape[0]
    routs = []
    for s, (r_start, r_end, r_ts, r_kinds, r_valid) in enumerate(rarenas):
        rcap = r_start.shape[0]
        hit_r = (iv_start[:, None] < r_end[None, :]) \
            & (r_start[None, :] < iv_end[:, None])
        any_r = jnp.zeros((b, rcap), jnp.int32) \
            .at[iv_of].max(hit_r.astype(jnp.int32), mode="drop") > 0
        witness_r = _witness_mask(witness_table, subj_kinds, r_kinds)
        before_r = _lex_before(r_ts[None, :, :], subj_before[:, None, :])
        mine = (subj_store == r_slots[s])[:, None]
        routs.append(_pack_bits(
            any_r & witness_r & before_r & r_valid[None, :] & mine))
    kouts = []
    if karenas:
        k = karenas[0][0].shape[1]
        cov = covered_buckets(iv_of, iv_start, iv_end, b, k, 0, k)
    for s, (k_bm, k_ts, k_kinds, k_valid) in enumerate(karenas):
        any_k = jax.lax.dot_general(
            cov, k_bm.astype(jnp.bfloat16),
            (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32) > 0.5
        witness_k = _witness_mask(witness_table, subj_kinds, k_kinds)
        before_k = _lex_before(k_ts[None, :, :], subj_before[:, None, :])
        mine = (subj_store == k_slots[s])[:, None] & subj_is_range[:, None]
        kouts.append(_pack_bits(
            any_k & witness_k & before_k & k_valid[None, :] & mine))
    rpacked = jnp.concatenate(routs, axis=1) if routs \
        else jnp.zeros((b, 0), jnp.uint32)
    kpacked = jnp.concatenate(kouts, axis=1) if kouts \
        else jnp.zeros((b, 0), jnp.uint32)
    return rpacked, kpacked


@jax.jit
def range_deps_resolve(iv_of, iv_start, iv_end, subj_before, subj_kinds,
                       subj_is_range,
                       r_start, r_end, r_ts, r_kinds, r_valid,
                       k_bm, k_ts, k_kinds, k_valid,
                       witness_table):
    """The fused RANGE-overlap kernel: every subject carries a CSR list of
    half-open int32 intervals (a key subject's keys become point intervals
    [k, k+1); a range subject's owned ranges upload as-is), tested against

      - the RANGE arena by branch-free interval overlap
        (iv_start < r_end & r_start < iv_end), which for a point interval
        degenerates to the stabbing test r_start <= key < r_end; and
      - the KEY arena by covered-bucket contraction: the subject's intervals
        expand to a covered-bucket mask (covered_buckets) contracted against
        the per-row key bitmaps on the MXU -- range subjects only (key
        subjects get exact key deps from deps_resolve); the host decode
        filters bucket-collision false positives per real key. This replaces
        the old per-row [kmin, kmax] hull span compare: a sparse row with a
        wide key spread no longer candidates every interval inside its hull,
        only intervals actually sharing a bucket.

    Sorted-endpoint broadcast compares beat an interval tree here: the tree's
    pointer-chasing descent is serial and branchy, while [nv, rcap] compares
    are pure VPU work XLA fuses with the witness/before masks.

    iv_of:         i32[nv]   subject row per interval (padding -> B, dropped)
    iv_start/end:  i32[nv]   half-open interval endpoints
    subj_before:   i32[B, 3] 'started before' bound per subject
    subj_kinds:    i32[B]
    subj_is_range: bool[B]   True for range-domain subjects (gates the
                             key-arena output)
    r_*:           the range arena (resolver._RangeArena); rcap % 32 == 0
    k_*:           the key arena lanes (k_bm f32[cap, K]); cap % 32 == 0,
                   K a power of two (covered_buckets wraparound)
    -> (u32[B, rcap/32], u32[B, cap/32]) packed candidate bitmasks, masked by
       witness/before/valid exactly like deps_resolve

    The stages carry jax.named_scope names, as deps_resolve's do.
    """
    b = subj_before.shape[0]
    rcap = r_start.shape[0]
    k = k_bm.shape[1]
    with jax.named_scope("interval_overlap"):
        hit_r = (iv_start[:, None] < r_end[None, :]) \
            & (r_start[None, :] < iv_end[:, None])
        any_r = jnp.zeros((b, rcap), jnp.int32) \
            .at[iv_of].max(hit_r.astype(jnp.int32), mode="drop") > 0
    with jax.named_scope("range_witness_before_mask"):
        witness_r = _witness_mask(witness_table, subj_kinds, r_kinds)
        before_r = _lex_before(r_ts[None, :, :], subj_before[:, None, :])
        m_r = any_r & witness_r & before_r & r_valid[None, :]
    with jax.named_scope("covered_buckets"):
        cov = covered_buckets(iv_of, iv_start, iv_end, b, k, 0, k)
    with jax.named_scope("bucket_overlap"):
        any_k = jax.lax.dot_general(
            cov, k_bm.astype(jnp.bfloat16),
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) > 0.5
    with jax.named_scope("key_witness_before_mask"):
        witness_k = _witness_mask(witness_table, subj_kinds, k_kinds)
        before_k = _lex_before(k_ts[None, :, :], subj_before[:, None, :])
        m_k = any_k & witness_k & before_k & k_valid[None, :] \
            & subj_is_range[:, None]
    return _pack_bits(m_r), _pack_bits(m_k)


def _popcount_u32(x):
    """Branch-free SWAR popcount per u32 lane (jnp.bitwise_count is not
    available across the supported jax versions)."""
    x = x - ((x >> 1) & jnp.uint32(0x55555555))
    x = (x & jnp.uint32(0x33333333)) + ((x >> 2) & jnp.uint32(0x33333333))
    x = (x + (x >> 4)) & jnp.uint32(0x0F0F0F0F)
    return ((x * jnp.uint32(0x01010101)) >> 24).astype(jnp.int32)


def _kth_set_bit(v, k):
    """Position of the k-th set bit (k from 0) of each u32 lane, by popcount
    halving: is it in the low half, else skip the low half's count. Lanes
    with fewer than k + 1 bits give a meaningless position."""
    pos = jnp.zeros_like(k)
    for half in (16, 8, 4, 2, 1):
        low = v & jnp.uint32((1 << half) - 1)
        below = _popcount_u32(low)
        up = k >= below
        pos = pos + jnp.where(up, half, 0)
        k = k - jnp.where(up, below, 0)
        v = jnp.where(up, v >> half, low)
    return pos


def _fold_nonzero(v):
    """u32[N] -> u32[ceil(N / 32)]: bit b of mask g says element 32 g + b is
    non-zero (zero padding to a multiple of 32)."""
    pad = -v.shape[0] % 32
    if pad:
        v = jnp.concatenate([v, jnp.zeros(pad, v.dtype)])
    # the barrier keeps what computes `v` out of _pack_bits' [N/32, 32] view,
    # whose 32 columns the chip pads to 128: fused in, the whole slot-mask
    # stage ran over u32 temporaries of four times the matrix (1.2 GB of
    # temporaries at the live cell's 4,096 x 8,192 words against 0.4 GB,
    # compiled for a described v5e, PR 36); a bool view is a quarter of one
    nonzero = jax.lax.optimization_barrier(v != 0)
    return _pack_bits(nonzero.reshape(1, -1))[0]


def _expand_masks(val, idx, n_out: int):
    """The set bits of the masks `val` u32[C], laid out in mask order: output
    j is the j-th set bit, reported as (idx of the mask that holds it, its
    bit position in that mask, whether there is a j-th bit at all: the
    first two are meaningless where the third is False). Each output FINDS
    its bit: a non-zero mask is written once, at the output position of its
    first bit (its exclusive popcount prefix: distinct targets, C candidates
    and not 32 C), the up to 31 outputs behind a written one copy it in five
    doubling steps, and the distance to it says which of the mask's bits
    the output is."""
    j = jnp.arange(n_out, dtype=jnp.int32)
    with jax.named_scope("mark_scatter"):
        pop = _popcount_u32(val)
        end = jnp.cumsum(pop, dtype=jnp.int32)
        at = jnp.where(val != 0, end - pop, n_out)
        own = jnp.full(n_out, -1, jnp.int32).at[at].set(idx, mode="drop")
        mask = jnp.zeros(n_out, jnp.uint32).at[at].set(val, mode="drop")
    with jax.named_scope("owner_fill"):
        first = jnp.where(own >= 0, j, 0)
        for d in (1, 2, 4, 8, 16):
            has = own >= 0
            own, mask, first = (
                jnp.where(has, x, jnp.pad(x, (d, 0))[:n_out])
                for x in (own, mask, first))
    with jax.named_scope("bit_select"):
        return own, _kth_set_bit(mask, j - first), j < end[-1]


def _packed_segment_compact(m, out_cap: int):
    """Segment compaction over BIT-PACKED segments: `m` is u32[S, W] (each
    segment a packed row set, cap == W*32). Returns (indptr i32[S+1],
    dep_rows i32[out_cap]) where dep_rows packs the set ROW indices of all
    segments contiguously in (segment-major, row-ascending) order and is 0
    beyond indptr[-1]. indptr is exact from the popcounts whatever the
    output holds: indptr[-1] > out_cap signals overflow, and dep_rows then
    carries the first out_cap rows.

    It gathers by output position (_expand_masks) where it used to scatter
    every candidate: a TPU runs a scatter an update at a time, dropped or
    not (4.6 ns each; `out_cap x 32` bit candidates and `S x W` word
    candidates were 55 of the key cell's 59 ms a dispatch, ledger PR 35).
    The flat word matrix is folded, 32 elements to a mask of which are
    non-zero, until a level has at most out_cap masks; the top level is
    expanded into the indices of the level below's non-zero elements, their
    values are gathered (at most out_cap of them matter: the j-th set bit
    lies in one of the first j + 1 non-zero words), and so on down to the
    words, whose expansion is the rows. Groups may cross segments: the flat
    order is the output order. The count of folds follows from S x W and
    out_cap, both static."""
    w = m.shape[1]
    with jax.named_scope("popcount_prefix"):
        counts = jnp.sum(_popcount_u32(m), axis=1, dtype=jnp.int32)
        indptr = jnp.concatenate(
            [jnp.zeros(1, jnp.int32), jnp.cumsum(counts, dtype=jnp.int32)])
    with jax.named_scope("word_fold"):
        levels = [m.reshape(-1)]
        while levels[-1].shape[0] > out_cap:
            levels.append(_fold_nonzero(levels[-1]))
    val = levels[-1]
    idx = jnp.arange(val.shape[0], dtype=jnp.int32)
    with jax.named_scope("word_compact"):
        for lower in reversed(levels[:-1]):
            own, bit, live = _expand_masks(val, idx, out_cap)
            idx = jnp.where(live, own * 32 + bit, 0)
            val = jnp.where(live, lower[idx], jnp.uint32(0))
    with jax.named_scope("row_expand"):
        own, bit, live = _expand_masks(val, idx, out_cap)
        dep_rows = jnp.where(live, (own % w) * 32 + bit, 0)
    return indptr, dep_rows


def _csum_fold(x, seed: int):
    """Position-weighted fold of one CSR lane into a u32 word: bitcast to
    u32, mix the high half down, then a wrapping sum weighted by odd
    per-position multipliers (odd => invertible mod 2^32, so transposing
    or flipping any element changes the sum). Runs in-jit on device; the
    host twin is csr_checksum_host. Sums mod 2^32 are order-independent,
    so device reduction order cannot diverge from numpy's."""
    v = jax.lax.bitcast_convert_type(x.reshape(-1), jnp.uint32)
    v = v ^ (v >> jnp.uint32(16))
    idx = jnp.arange(v.shape[0], dtype=jnp.uint32)
    return jnp.sum(v * (jnp.uint32(2) * idx + jnp.uint32(seed)),
                   dtype=jnp.uint32)


def _csum_fold_host(x, seed: int):
    """numpy twin of _csum_fold: the same words, bit for bit."""
    v = np.ascontiguousarray(x).view(np.uint32).reshape(-1)
    v = v ^ (v >> np.uint32(16))
    idx = np.arange(v.shape[0], dtype=np.uint32)
    return (v * (np.uint32(2) * idx + np.uint32(seed))).sum(dtype=np.uint32)


def csr_checksum(indptr, dep_rows):
    """Device-side integrity word over a finalized CSR pair, fused into
    the finalize kernels' returns and re-derived from the host copies at
    harvest (resolver._csum_ok): a readback that arrives bit-flipped can
    never decode into wrong deps -- the mismatch routes the group to the
    legacy fallback, which re-reads the raw candidate buffers. dep_rows is
    0 past indptr[-1] and a 0 word folds to 0, so the host may fold only
    dep_rows[:indptr[-1]] and still match this whole-lane word."""
    return _csum_fold(indptr, 1) ^ _csum_fold(dep_rows, 5)


def csr_checksum_host(indptr, dep_rows) -> int:
    """numpy twin of csr_checksum, computed over the fetched host copies
    (dep_rows whole, or any prefix that holds its first indptr[-1] words).
    Must track the device fold bit for bit."""
    return int(_csum_fold_host(indptr, 1) ^ _csum_fold_host(dep_rows, 5))


# --------------------------------------------------------------------------
# Execution-frontier compaction + recovery scans: the finalized-CSR twins
# for the exec/recovery planes. The frontier kernel emits the EXACT
# released-row index list (segment per store), a count bound (indptr), and
# a u32 integrity word, so harvest readback is O(released) instead of
# O(arena rows) and the host decode is a direct slice. The recovery scan
# answers "which cmd-arena rows are live and stalled" the same way, so
# progress-engine candidate selection at 10k in-flight is one device query.

FRONTIER_OUT_TIERS = (32, 256, 2048)
RECOVERY_OUT_TIERS = (32, 256, 2048)


def frontier_checksum(indptr, rows):
    """Device integrity word over a compacted frontier (indptr + row list):
    the exec plane's twin of csr_checksum. Fresh fold seeds so a frontier
    word can never alias a finalize word; a readback that arrives
    bit-flipped routes the harvest to the legacy bitmask decode (counted)
    instead of releasing wrong rows."""
    return _csum_fold(indptr, 13) ^ _csum_fold(rows, 17)


def frontier_checksum_host(indptr, rows) -> int:
    """numpy twin of frontier_checksum, computed over the fetched host
    copies. Must track the device fold bit for bit."""
    return int(_csum_fold_host(indptr, 13) ^ _csum_fold_host(rows, 17))


def _frontier_compact_body(planes, out_cap: int):
    """Unjitted body shared by frontier_compact and the protocol_tick exec
    block (one source of truth -> fused and standalone paths bit-identical).
    `planes` is a tuple of per-store lane tuples exactly as
    fused_execution_frontier takes them; each store is one compaction
    SEGMENT, so indptr demuxes per-store released runs and each row value
    is a GLOBAL bit index (32 * store word offset + arena row) that the
    host converts back with its word span."""
    packs = []
    for (adj, exec_ts, applied, pending, awaits_all) in planes:
        cap = adj.shape[0]
        ready = _frontier_ready(adj, exec_ts, applied, pending, awaits_all)
        packs.append(_pack_bits(ready.reshape(1, cap))[0])
    w_tot = sum(int(p.shape[0]) for p in packs)
    rows_m, off = [], 0
    for p in packs:
        w = int(p.shape[0])
        segs = []
        if off:
            segs.append(jnp.zeros(off, jnp.uint32))
        segs.append(p)
        if w_tot - off - w:
            segs.append(jnp.zeros(w_tot - off - w, jnp.uint32))
        rows_m.append(jnp.concatenate(segs) if len(segs) > 1 else segs[0])
        off += w
    m = jnp.stack(rows_m)
    indptr, rows = _packed_segment_compact(m, out_cap)
    return (indptr, rows, frontier_checksum(indptr, rows),
            jnp.concatenate(packs))


@functools.partial(jax.jit, static_argnames=("out_cap",))
def frontier_compact(planes, out_cap: int):
    """Compacted execution frontier for a tuple of store planes: ONE device
    call answering every store's release list for a node tick.

    -> (indptr i32[S+1], rows i32[out_cap], csum u32, packed u32[sum(W_s)])

    rows holds released GLOBAL bit indices in (store-major, row-ascending)
    order; store s's run is rows[indptr[s]:indptr[s+1]] - 32 * w_lo_s.
    indptr is exact regardless of out_cap: indptr[-1] > out_cap signals
    overflow AND gives the true needed size for the tier bump. `packed` is
    the legacy full bitmask, RETAINED ON DEVICE -- the harvest fetches only
    the compacted lanes (O(released) bytes) and touches packed solely on
    the counted checksum-mismatch / overflow fallback paths."""
    return _frontier_compact_body(planes, out_cap)


def _recovery_scan_body(status, touched_ms, now_ms, stall_ms, out_cap: int):
    """Unjitted recovery-candidate scan over cmd-arena SoA columns: a row
    is a candidate iff its status sits in the live band (PRE_ACCEPTED ..
    < APPLIED, which also excludes the INVALIDATED/TRUNCATED terminals
    above it) and its last arena touch is at least stall_ms old. The host
    twin is CmdPlane.recovery_scan_host -- bit for bit the same predicate
    over the numpy shadows."""
    live = (status >= CMD_ST_PRE_ACCEPTED) & (status < CMD_ST_APPLIED)
    stalled = live & ((now_ms - touched_ms) >= stall_ms)
    m = _pack_bits(stalled.reshape(1, -1))
    indptr, rows = _packed_segment_compact(m, out_cap)
    return indptr, rows, frontier_checksum(indptr, rows)


@functools.partial(jax.jit, static_argnames=("out_cap",))
def recovery_scan(status, touched_ms, now_ms, stall_ms, out_cap: int):
    """One-query recovery candidate selection: which cmd-arena rows need a
    MaybeRecover/BeginRecovery probe (reference: the ProgressLog shards'
    pendingTimers walk, impl/progress/*.java -- batch work for the cmd
    plane instead of a host walk over every live waiter).

    status/touched_ms: i32[cap] arena columns; now_ms/stall_ms: i32
    scalars (traced -- value churn mints no recompiles).
    -> (indptr i32[2], rows i32[out_cap], csum u32); same overflow and
    checksum contract as frontier_compact."""
    return _recovery_scan_body(status, touched_ms, now_ms, stall_ms,
                               out_cap)


@functools.partial(jax.jit, static_argnames=("out_cap",))
def finalize_csr(packed, word_off, kid_rows, slot_subj, slot_kid,
                 subj_row, out_cap: int):
    """Device-side dep FINALIZATION for the key domain: consume the packed
    conflict bitmask straight out of deps_resolve (or one store's word span
    of the fused/sharded output -- `word_off` is the traced span offset) and
    emit final, exact, already-translated dep lists in CSR form, so harvest
    becomes a contiguous readback instead of unpackbits + re-filtering.

    Exactness comes from the device mirror of the host's per-key row masks:
    `kid_rows[kid]` is the packed set of arena rows whose key set contains
    the real key with dense id `kid` (resolver._StoreArena.key_rows shipped
    as a lane). ANDing it against the subject's packed bucket-level result
    removes bucket-collision false positives ON DEVICE -- the per-(subject,
    key) slot list replaces the host KM gather stack.

    packed:    u32[B, W_total] deps_resolve / fused output
    word_off:  i32 scalar      word offset of this store's span (0 unfused)
    kid_rows:  u32[KC, W]      per-key packed row masks (W*32 == cap)
    slot_subj: i32[S]          subject row per (subject, key) slot; padding B
    slot_kid:  i32[S]          dense key id per slot; padding KC
    subj_row:  i32[B]          subject's own arena row (-1 if unregistered),
                               cleared from its slots (a txn never deps on
                               itself)
    -> (indptr i32[S+1], dep_rows i32[out_cap], bound i32 scalar,
        csum u32 scalar);
       dep order within a slot is ascending arena row, and dep_rows is 0
       past indptr[-1]; indptr[-1] > out_cap signals overflow. The host
       reads txn ids off its own arena lanes by row. `bound` is the
       segmented reduction over the slots' kid-table row masks -- exactly
       the host popcount bound (sum of key_pop over the dispatch's slot
       keys) -- read back with the result so the NEXT dispatch's out_cap
       tier needs no host O(keys) pass (resolver's OutCapTiers policy).
       `csum` is the csr_checksum integrity word over (indptr, dep_rows),
       verified at harvest.
    """
    return _finalize_csr_body(packed, word_off, kid_rows, slot_subj,
                              slot_kid, subj_row, out_cap)


def _finalize_csr_body(packed, word_off, kid_rows, slot_subj, slot_kid,
                       subj_row, out_cap: int):
    """finalize_csr's trace body, unjitted so protocol_tick can inline the
    same compaction inside the fused cluster-tick program (the standalone
    jit wrapper above delegates here -- one source of truth, bit-identical
    either way)."""
    b = packed.shape[0]
    kc, w = kid_rows.shape
    with jax.named_scope("slot_mask"):
        blk = jax.lax.dynamic_slice_in_dim(packed, word_off, w, axis=1)
        ok = (slot_subj >= 0) & (slot_subj < b) \
            & (slot_kid >= 0) & (slot_kid < kc)
        kid_m = kid_rows[jnp.clip(slot_kid, 0, kc - 1)]
    with jax.named_scope("bound"):
        bound = jnp.sum(jnp.where(
            ok, jnp.sum(_popcount_u32(kid_m), axis=1, dtype=jnp.int32), 0),
            dtype=jnp.int32)
    with jax.named_scope("slot_mask"):
        so = jnp.clip(slot_subj, 0, b - 1)
        m = jnp.where(ok[:, None], blk[so] & kid_m, jnp.uint32(0))
        r = subj_row[so]
        widx = jnp.arange(w, dtype=jnp.int32)
        selfbit = jnp.where(
            (r >= 0)[:, None] & (widx[None, :] == (r >> 5)[:, None]),
            (jnp.uint32(1) << (r & 31).astype(jnp.uint32))[:, None],
            jnp.uint32(0))
        m = m & ~selfbit
    indptr, dep_rows = _packed_segment_compact(m, out_cap)
    with jax.named_scope("checksum"):
        csum = csr_checksum(indptr, dep_rows)
    return indptr, dep_rows, bound, csum


@functools.partial(jax.jit, static_argnames=("out_cap",))
def range_finalize_csr(iv_of, iv_start, iv_end, ent_ok,
                       subj_before, subj_kinds,
                       r_start, r_end, r_ts, r_kinds, r_valid,
                       witness_table, out_cap: int):
    """Device-side finalization of range-arena deps: stab the REAL interval
    endpoint lanes per CSR entry (no covered-bucket hull, no iv_of
    contraction), so each entry gets its own exact hit segment. Entries are
    either a key subject's point interval [k, k+1) (the key-subject
    range-deps lane) or ONE PIECE of a range subject's owned interval set
    (multi-piece subjects contribute one segment lane per piece; the host
    attribution walk unions the per-piece hits, which is idempotent) -- so
    the host re-filter against store.range_txns retires for BOTH subject
    kinds. The witness/before/valid masks gather through iv_of, matching
    range_deps_resolve; `ent_ok` gates which entries finalize (entries of
    the targeted store).

    -> (indptr i32[NV+1], dep_rows i32[out_cap], bound i32 scalar,
        csum u32 scalar); dep_rows is 0 past indptr[-1]. `bound` is the
       segmented STAB COUNT -- the number of (entry, valid-range) interval
       overlaps before the witness/before narrowing -- an exact upper
       bound on indptr[-1] read back with the result so the NEXT
       dispatch's out_cap tier needs no host entries*nvalid product
       (resolver's OutCapTiers policy, mirroring finalize_csr's key-lane
       bound from PR 8). `csum` is the csr_checksum integrity word,
       verified at harvest.
    """
    return _range_finalize_csr_body(iv_of, iv_start, iv_end, ent_ok,
                                    subj_before, subj_kinds,
                                    r_start, r_end, r_ts, r_kinds, r_valid,
                                    witness_table, out_cap)


def _range_finalize_csr_body(iv_of, iv_start, iv_end, ent_ok,
                             subj_before, subj_kinds,
                             r_start, r_end, r_ts, r_kinds, r_valid,
                             witness_table, out_cap: int):
    """range_finalize_csr's trace body, unjitted for protocol_tick (see
    _finalize_csr_body)."""
    b = subj_before.shape[0]
    with jax.named_scope("interval_stab"):
        o = jnp.clip(iv_of, 0, b - 1)
        inb = (iv_of >= 0) & (iv_of < b) & ent_ok
        hit = (iv_start[:, None] < r_end[None, :]) \
            & (r_start[None, :] < iv_end[:, None])
        stab = hit & r_valid[None, :] & inb[:, None]
    with jax.named_scope("bound"):
        bound = jnp.sum(stab.astype(jnp.int32), dtype=jnp.int32)
    with jax.named_scope("witness_before_mask"):
        witness = _witness_mask(witness_table, subj_kinds[o], r_kinds)
        before = _lex_before(r_ts[None, :, :], subj_before[o][:, None, :])
        m = stab & witness & before
    indptr, dep_rows = _packed_segment_compact(_pack_bits(m), out_cap)
    with jax.named_scope("checksum"):
        csum = csr_checksum(indptr, dep_rows)
    return indptr, dep_rows, bound, csum


@functools.partial(jax.jit, donate_argnums=(0, 1, 2, 3, 4))
def arena_scatter(bitmaps, ts, exec_ts, kinds, valid,
                  rows, key_rows, key_mods, ts_rows, exec_rows, kind_rows,
                  valid_rows):
    """Scatter dirty rows into the device arena. Bitmap rows are rebuilt on
    device from a CSR key list (key_rows i32[nnz] holds ABSOLUTE arena row
    indices; padding entries use cap -- out of bounds, dropped): each dirty
    row's bitmap is zeroed, then its current buckets scatter-set, so rows
    whose key sets shrank lose their stale bits. Row-padding duplicates
    row[0] with identical lane data -- harmless double write. The stages
    carry jax.named_scope names (metadata only), as deps_resolve's do.

    The five lanes are DONATED and rewritten in place (undonated, each call
    copied the whole [cap, K] bitmap to change 64 rows of it): the caller
    owns them, and must never pass an array a staged plan may still hold.
    _StoreArena keeps that rule; arena_scatter_keys and kid_word_scatter
    follow it too."""
    with jax.named_scope("bitmap_rebuild"):
        cleared = bitmaps.at[rows].set(0.0)
        rebuilt = cleared.at[key_rows, key_mods].max(1.0, mode="drop")
    with jax.named_scope("lane_scatter"):
        # the two [cap, 3] lanes by ELEMENT: the chip gives a row scatter's
        # operand the layout {1,0:T(8,128)}, three columns padded to 128,
        # so each lane was copied into a 134 MB temporary and back, 0.41 ms
        # a lane a call at 262,144 rows, which donation does not remove
        # (my chip run 1, PR 34; by element: no copy, under 0.12 ms)
        at = (rows[:, None], jnp.arange(3, dtype=jnp.int32)[None, :])
        return (rebuilt,
                ts.at[at].set(ts_rows),
                exec_ts.at[at].set(exec_rows),
                kinds.at[rows].set(kind_rows),
                valid.at[rows].set(valid_rows))


@functools.partial(jax.jit, donate_argnums=(0,))
def arena_scatter_keys(bitmaps, rows, key_rows, key_mods):
    """Field-granular scatter for KEY-SET-ONLY row changes (key widening,
    prune/truncate shrinks): rebuild the dirty rows' bitmaps from the CSR
    without shipping the ts/exec/kind/valid lanes the change didn't touch.
    Same clear-then-max CSR contract as arena_scatter. (The [kmin, kmax]
    hull lanes this used to refresh are retired -- the range kernel now
    contracts over the same bitmaps.) `bitmaps` is DONATED."""
    with jax.named_scope("bitmap_rebuild"):
        cleared = bitmaps.at[rows].set(0.0)
        return cleared.at[key_rows, key_mods].max(1.0, mode="drop")


@jax.jit
def range_scatter(starts, ends, ts, kinds, valid,
                  rows, start_rows, end_rows, ts_rows, kind_rows, valid_rows):
    """Scatter dirty rows into the range arena (tiny flat lanes -- one
    interval per row). Padding duplicates row[0]; harmless double write."""
    return (starts.at[rows].set(start_rows),
            ends.at[rows].set(end_rows),
            ts.at[rows].set(ts_rows),
            kinds.at[rows].set(kind_rows),
            valid.at[rows].set(valid_rows))


@functools.partial(jax.jit, static_argnames=("new_cap",))
def arena_grow(bitmaps, ts, exec_ts, kinds, valid, new_cap: int):
    """Double the arena capacity ON DEVICE (zero/neg padding) -- re-uploading
    a full [cap, K] bitmap over the host link would cost seconds."""
    neg = jnp.int32(np.iinfo(np.int32).min)
    grow = new_cap - bitmaps.shape[0]

    def pad(a, value=0):
        widths = [(0, grow)] + [(0, 0)] * (a.ndim - 1)
        return jnp.pad(a, widths, constant_values=value)

    with jax.named_scope("arena_grow"):
        return (pad(bitmaps), pad(ts), pad(exec_ts, neg), pad(kinds),
                pad(valid, False))


def pad_to(x: np.ndarray, size: int, axis: int = 0) -> np.ndarray:
    """Pad axis up to `size` with zeros (bucketed static shapes for jit)."""
    if x.shape[axis] == size:
        return x
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, size - x.shape[axis])
    return np.pad(x, pad)


def bucket_size(n: int, minimum: int = 8) -> int:
    """Next power-of-two bucket >= n (>= minimum), so jit caches stay warm."""
    return snap(n, (), minimum)


# The deps-resolver subject-batch padding ladder. Deliberately few named
# tiers so the jit cache stays tiny and warmup() can cover every shape the
# async pipeline dispatches: {8, 64, 128} handle the common batch-window
# coalescing sizes (128 is the default MAX_DISPATCH), and anything larger
# falls onto power-of-two buckets from 256 up (the bench's deep-dispatch
# configurations warm their own tier explicitly).
SUBJECT_TIERS = (8, 64, 128)


def subject_tier(n: int) -> int:
    """Padded subject-batch size for a dispatch of n subjects."""
    return snap(n, SUBJECT_TIERS, 256)


# CSR flat-entry padding ladders. The subject CSR (one entry per owned key /
# owned interval) pads to NNZ_TIERS; the dirty-row scatter CSR packs rows
# greedily under SCATTER_NNZ_TIERS[-1] entries per chunk so both the row tier
# ({8, 64}) and the nnz tier stay warmable. Oversized singles fall onto
# power-of-two buckets.
NNZ_TIERS = (32, 256, 2048)
SCATTER_NNZ_TIERS = (64, 512)


def nnz_tier(n: int) -> int:
    """Padded CSR entry count for a dispatch carrying n subject entries."""
    return snap(n, NNZ_TIERS, 4096)


def scatter_nnz_tier(n: int) -> int:
    """Padded CSR entry count for an arena-scatter chunk of n key entries."""
    return snap(n, SCATTER_NNZ_TIERS, 1024)


# Finalized-CSR output padding ladder. The compaction kernels' out_cap tier
# is PINNED by the resolver's OutCapTiers hysteresis policy (ops.tiers),
# fed by the device-computed bound each finalize call reads back -- grow
# immediately, shrink only after several consecutive quiet dispatches -- so
# the picked tier is not data-dependent dispatch to dispatch and a
# zero-recompile check by jit_cache_sizes() covers the finalize kernels
# without exemption.
OUT_TIERS = (256, 2048, 16384)
OUT_TIER_FLOOR = 32768


# ---------------------------------------------------------------------------
# Device command plane (ops/cmd_plane.py): batched txn state machines
# ---------------------------------------------------------------------------

# Status ladder constants mirrored from local.status.Status. ops/cmd_plane.py
# asserts these against the enum at import, so the mirrors cannot drift.
CMD_ST_PRE_ACCEPTED = 1
CMD_ST_ACCEPTED = 3
CMD_ST_COMMITTED = 5
CMD_ST_STABLE = 6
CMD_ST_READY = 7
CMD_ST_PRE_APPLIED = 8
CMD_ST_APPLIED = 9
CMD_ST_INVALIDATED = 10
CMD_ST_TRUNCATED = 11

# outcome codes in the low 3 bits of out_code (cmd_plane maps them back to
# AcceptOutcome / CommitOutcome); high bits carry side-channel facts the
# host residuals need
CMD_OUT_SUCCESS = 0
CMD_OUT_REDUNDANT = 1
CMD_OUT_REJECTED_BALLOT = 2
CMD_OUT_TRUNCATED = 3
CMD_OUT_INSUFFICIENT = 4
CMD_OUT_INCONSISTENT_BIT = 8    # redundant commit/apply with executeAt drift
CMD_OUT_WAS_STABLE_BIT = 16     # apply arrived on an already-stable command

# op kinds in op_kind
CMD_OP_PREACCEPT = 0
CMD_OP_ACCEPT = 1
CMD_OP_COMMIT = 2
CMD_OP_APPLY = 3

# op_flags bits (host admission encodes these per op)
CMD_F_PERMIT_FAST = 1    # ballot == Ballot.ZERO
CMD_F_EPOCH_OK = 2       # txn_id.epoch >= node.epoch at encode time
CMD_F_EXPIRED = 4        # preaccept expiry fired (precomputed at encode: a
                         # pure function of txn hlc + now + agent timeout, so
                         # the host float compare stays exactly authoritative)
CMD_F_MSG_HAS_TXN = 8    # the commit/apply message carries a txn body
CMD_F_VALID = 16         # real op (padding rows leave this clear)
CMD_F_DEPS_EMPTY = 32    # commit/apply deps empty (promote-eligible)

# batched-op padding ladder for cmd_tick dispatches
CMD_OP_TIERS = (8, 64, 512)


def cmd_op_tier(n: int) -> int:
    """Padded op count for a cmd_tick dispatch carrying n ops."""
    return snap(n, CMD_OP_TIERS, 4096)


def _lex_max_masked(rows, valid):
    """Lexicographic max over rows[i] where valid[i]. rows: i32[K, 3],
    valid: bool[K] -> (i32[3] max lanes, bool any_valid); lanes are INT32_MIN
    when nothing is valid."""
    neg = jnp.int32(np.iinfo(np.int32).min)
    l0 = jnp.where(valid, rows[:, 0], neg)
    m0 = jnp.max(l0)
    t0 = valid & (rows[:, 0] == m0)
    l1 = jnp.where(t0, rows[:, 1], neg)
    m1 = jnp.max(l1)
    t1 = t0 & (rows[:, 1] == m1)
    m2 = jnp.max(jnp.where(t1, rows[:, 2], neg))
    return jnp.stack([m0, m1, m2]), jnp.any(valid)


def cmd_checksum(out_code, out_status, out_ts, clock):
    """Device integrity word over a cmd_tick result block (the PR 11 harvest
    checksum discipline extended to the command plane): recomputed from the
    host copies at harvest; a bit-flipped readback falls back to the host
    handlers instead of applying corrupt transitions."""
    return (_csum_fold(out_code, 3) ^ _csum_fold(out_status, 7)
            ^ _csum_fold(out_ts, 11)
            ^ _csum_fold(clock.reshape(1), 13))


def cmd_checksum_host(out_code, out_status, out_ts, clock) -> int:
    """numpy twin of cmd_checksum; must track the device fold bit for bit."""
    def fold(x, seed):
        v = np.ascontiguousarray(x, dtype=np.int32).view(np.uint32).reshape(-1)
        v = v ^ (v >> np.uint32(16))
        idx = np.arange(v.shape[0], dtype=np.uint32)
        return (v * (np.uint32(2) * idx + np.uint32(seed))).sum(
            dtype=np.uint32)
    return int(fold(out_code, 3) ^ fold(out_status, 7) ^ fold(out_ts, 11)
               ^ fold(np.asarray([clock], dtype=np.int32), 13))


@functools.partial(jax.jit, static_argnames=("promote",))
def cmd_tick(status, flags, promised, accepted, execute_at, durability,
             kmax, kmax_valid, clock,
             op_kind, op_row, op_txn, op_ballot, op_exec, op_keys, op_flags,
             op_now, op_prev, op_rlast, op_kprev, op_klast,
             node_epoch, lane2_clean, lane2_rej,
             dur_local, promote: bool = False):
    """One device dispatch evaluating a batch of protocol transitions IN
    ORDER over the SoA command arena: PreAccept witness (fast-path test +
    unique_now twin + expiry), Accept ballot checks, Commit/Apply status
    promotions -- the per-txn Python state machines of local/commands.py
    re-expressed as one fori_loop over op slots.

    Arena columns (authoritative device state between dispatches):
      status:     i32[cap]      Status ladder value
      flags:      i32[cap]      bit0 = definition recorded (cmd.txn != None)
      promised:   i32[cap, 3]   promised ballot lanes
      accepted:   i32[cap, 3]   accepted ballot lanes
      execute_at: i32[cap, 3]   executeAt lanes (INT32_MIN lanes == None)
      durability: i32[cap]      Durability ladder value
      kmax:       i32[kcap, 3]  per-key max-conflict lanes (MaxConflicts)
      kmax_valid: bool[kcap]    false == no conflict witnessed for the key
      clock:      i32 scalar    node HLC register (node._last_hlc)

    Ops (padded to CMD_OP_TIERS; lanes are ABSOLUTE base-(0,0) encodings:
    lane0 epoch, lane1 hlc, lane2 (flags << 16 | node) - 2^31):
      op_kind:  i32[n]     CMD_OP_*
      op_row:   i32[n]     arena row of the op's txn
      op_txn:   i32[n, 3]  TxnId lanes (flags carry kind/domain, so this IS
                           txn_id.as_timestamp() too)
      op_ballot: i32[n, 3] ballot lanes
      op_exec:  i32[n, 3]  proposed/decided executeAt lanes (accept/commit/
                           apply)
      op_keys:  i32[n, KPAD] dense kid-table slots of the op's owned keys
                           (-1 padding)
      op_flags: i32[n]     CMD_F_* bits
      op_now:   i32[n]     now_micros at the op's scheduler instant
      op_prev:  i32[n]     index of the previous op in this batch on the
                           same row (-1 = none): intra-batch row chains
      op_rlast: bool[n]    this op is its row's LAST writer in the batch
      op_kprev: i32[n,KPAD] previous writer of this kid slot, encoded
                           p * KPAD + s (-1 = none)
      op_klast: bool[n,KPAD] this (op, slot) is the kid's last writer
    Scalars: node_epoch; lane2_clean/lane2_rej = the node's lane2 value with
    flags 0 / REJECTED (precomputed host-side: (flags << 16 | node) - 2^31);
    dur_local = Durability.LOCAL.

    The loop carries only op-tier-sized state: each op's view of the arena
    is gathered up front, intra-batch dependencies resolve through the
    prev-writer links, and the final chain values scatter back ONCE after
    the loop (last-writer wins). Carrying the cap-sized columns through the
    fori_loop instead makes XLA's copy insertion duplicate them every
    iteration -- ~17ms per 512-op dispatch at cap 16384 vs ~1ms this way.

    `promote` (static): additionally run the empty-deps maybe_execute
    promotion on device (STABLE -> READY_TO_EXECUTE, PRE_APPLIED ->
    APPLIED + durability merge) -- the arena-only bench mode. With host
    residuals (apply_to_store) the promotion runs host-side instead.

    -> (updated columns..., out_code i32[n], out_ts i32[n, 3] (witnessed /
        echoed executeAt), out_status i32[n], csum u32)
    """
    return _cmd_tick_body(status, flags, promised, accepted, execute_at,
                          durability, kmax, kmax_valid, clock,
                          op_kind, op_row, op_txn, op_ballot, op_exec,
                          op_keys, op_flags, op_now, op_prev, op_rlast,
                          op_kprev, op_klast, node_epoch, lane2_clean,
                          lane2_rej, dur_local, promote)


def _cmd_tick_body(status, flags, promised, accepted, execute_at, durability,
                   kmax, kmax_valid, clock,
                   op_kind, op_row, op_txn, op_ballot, op_exec, op_keys,
                   op_flags, op_now, op_prev, op_rlast, op_kprev, op_klast,
                   node_epoch, lane2_clean, lane2_rej,
                   dur_local, promote: bool = False):
    """cmd_tick's trace body, unjitted so protocol_tick can run the same
    batched transitions inside the fused cluster-tick program. Keeps the
    op-tier-sized fori_loop carry (see the docstring above: a cap-sized
    carry makes XLA duplicate the columns every iteration)."""
    cap = status.shape[0]
    kcap = kmax.shape[0]
    n, kpad = op_keys.shape
    neg = jnp.int32(np.iinfo(np.int32).min)

    # per-op arena views before the batch (padding slots clip to row/kid 0
    # and are masked out of every write by op_rlast/op_klast)
    rowc = jnp.clip(op_row, 0, cap - 1)
    st_0 = status[rowc]
    fl_0 = flags[rowc]
    pr_0 = promised[rowc]
    ab_0 = accepted[rowc]
    ea_0 = execute_at[rowc]
    du_0 = durability[rowc]
    kid_0 = jnp.clip(op_keys, 0, kcap - 1)
    km_0 = kmax[kid_0]          # (n, kpad, 3)
    kv_0 = kmax_valid[kid_0]    # (n, kpad)

    def body(i, c):
        (r_st, r_fl, r_pr, r_ab, r_ea, r_du, k_km, k_kv,
         clock, out_code, out_ts, out_status) = c
        kind = op_kind[i]
        valid = (op_flags[i] & CMD_F_VALID) != 0
        prev = op_prev[i]
        use_prev = prev >= 0
        pc = jnp.where(use_prev, prev, 0)
        st = jnp.where(use_prev, r_st[pc], st_0[i])
        fl = jnp.where(use_prev, r_fl[pc], fl_0[i])
        pr = jnp.where(use_prev, r_pr[pc], pr_0[i])
        ab = jnp.where(use_prev, r_ab[pc], ab_0[i])
        ea = jnp.where(use_prev, r_ea[pc], ea_0[i])
        du = jnp.where(use_prev, r_du[pc], du_0[i])
        txn = op_txn[i]
        bal = op_ballot[i]
        oex = op_exec[i]
        kids = op_keys[i]
        permit_fast = (op_flags[i] & CMD_F_PERMIT_FAST) != 0
        epoch_ok = (op_flags[i] & CMD_F_EPOCH_OK) != 0
        expired = (op_flags[i] & CMD_F_EXPIRED) != 0
        msg_has_txn = (op_flags[i] & CMD_F_MSG_HAS_TXN) != 0
        deps_empty = (op_flags[i] & CMD_F_DEPS_EMPTY) != 0
        now = op_now[i]

        has_txn = (fl & 1) != 0
        ea_set = ea[0] != neg
        terminal = (st == CMD_ST_INVALIDATED) | (st == CMD_ST_TRUNCATED)
        pr_gt_bal = _lex_before(bal, pr)
        pr_max_bal = jnp.where(_lex_before(pr, bal), bal, pr)
        term_code = jnp.where(st == CMD_ST_INVALIDATED,
                              CMD_OUT_REJECTED_BALLOT, CMD_OUT_TRUNCATED)

        # kid-table chain: each slot reads its previous in-batch writer's
        # post-value, else the pre-batch gather
        links = op_kprev[i]
        lv = links >= 0
        lc = jnp.where(lv, links, 0)
        lp, ls = lc // kpad, lc % kpad
        kv_raw = jnp.where(lv, k_kv[lp, ls], kv_0[i])
        kv = kv_raw & (kids >= 0)
        km = jnp.where(lv[:, None], k_km[lp, ls], km_0[i])
        mc, mc_any = _lex_max_masked(km, kv)

        # unique_now twin (local/Node.unique_now): hlc = max(now, clock + 1),
        # bumped past at_least.hlc; epoch = max(node epoch, at_least.epoch)
        def unow(al_ep, al_hlc, lane2):
            h = jnp.maximum(now, clock + 1)
            h = jnp.where(al_hlc >= h, al_hlc + 1, h)
            return jnp.stack([jnp.maximum(node_epoch, al_ep), h, lane2]), h

        # -- PreAccept (commands.preaccept) -----------------------------------
        rej_w, rej_h = unow(txn[0], txn[1], lane2_rej)
        al = jnp.where(mc_any, mc, txn)
        slow_w, slow_h = unow(al[0], al[1], lane2_clean)
        fast = permit_fast & (~mc_any | ~_lex_before(txn, mc)) & epoch_ok
        witness = jnp.where(expired, rej_w, jnp.where(fast, txn, slow_w))
        wit_clock = jnp.where(expired, rej_h,
                              jnp.where(fast, clock, slow_h))
        pa_blocked = terminal | pr_gt_bal
        pa_code = jnp.where(
            terminal, term_code,
            jnp.where(pr_gt_bal, CMD_OUT_REJECTED_BALLOT,
                      jnp.where(has_txn & permit_fast, CMD_OUT_REDUNDANT,
                                CMD_OUT_SUCCESS)))
        pa_wit = ~pa_blocked & ~has_txn & ~ea_set
        pa_st = jnp.where(
            pa_blocked | has_txn, st,
            jnp.where(ea_set, jnp.maximum(st, CMD_ST_PRE_ACCEPTED),
                      CMD_ST_PRE_ACCEPTED))
        pa_fl = jnp.where(pa_blocked, fl, fl | 1)
        pa_pr = jnp.where(pa_blocked, pr, pr_max_bal)
        pa_ea = jnp.where(pa_wit, witness, ea)
        pa_out_ts = jnp.where(pa_wit, witness, ea)

        # -- Accept (commands.accept; the reject_before gate is an admission
        # precondition, so is_rejected_if_not_preaccepted is always false) ---
        committed = st >= CMD_ST_COMMITTED
        ac_code = jnp.where(
            terminal, term_code,
            jnp.where(pr_gt_bal | committed,
                      jnp.where(committed, CMD_OUT_REDUNDANT,
                                CMD_OUT_REJECTED_BALLOT),
                      CMD_OUT_SUCCESS))
        ac_ok = ~terminal & ~pr_gt_bal & ~committed
        ac_st = jnp.where(ac_ok, CMD_ST_ACCEPTED, st)
        ac_pr = jnp.where(ac_ok, bal, pr)
        ac_ab = jnp.where(ac_ok, bal, ab)
        ac_ea = jnp.where(ac_ok, oex, ea)

        # -- Commit -> STABLE (commands.commit) -------------------------------
        ea_eq = jnp.all(ea == oex)
        stable = st >= CMD_ST_STABLE
        cm_incons = stable & ~terminal & ~ea_eq
        cm_insuf = ~stable & ~has_txn & ~msg_has_txn
        cm_ok = ~stable & ~cm_insuf
        cm_code = jnp.where(
            stable,
            CMD_OUT_REDUNDANT + jnp.where(cm_incons,
                                          CMD_OUT_INCONSISTENT_BIT, 0),
            jnp.where(cm_insuf, CMD_OUT_INSUFFICIENT, CMD_OUT_SUCCESS))
        cm_new_st = jnp.int32(CMD_ST_STABLE)
        if promote:
            cm_new_st = jnp.where(deps_empty, CMD_ST_READY, CMD_ST_STABLE)
        cm_st = jnp.where(cm_ok, cm_new_st, st)
        cm_fl = jnp.where(cm_ok & msg_has_txn, fl | 1, fl)
        cm_ea = jnp.where(cm_ok, oex, ea)
        # register at max(executeAt, txnId.as_timestamp()) -- TxnId lanes
        # carry the flags, so the lane compare IS the host compare
        cm_regval = jnp.where(_lex_before(oex, txn), txn, oex)

        # -- Apply -> PRE_APPLIED (commands.apply) ----------------------------
        preapplied = st >= CMD_ST_PRE_APPLIED
        was_stable = st >= CMD_ST_STABLE
        ap_incons = preapplied & ~terminal & ~ea_eq
        ap_insuf = ~preapplied & ~has_txn & ~msg_has_txn
        ap_ok = ~preapplied & ~ap_insuf
        ap_code = jnp.where(
            preapplied,
            CMD_OUT_REDUNDANT + jnp.where(ap_incons,
                                          CMD_OUT_INCONSISTENT_BIT, 0),
            jnp.where(ap_insuf, CMD_OUT_INSUFFICIENT,
                      CMD_OUT_SUCCESS + jnp.where(
                          was_stable, CMD_OUT_WAS_STABLE_BIT, 0)))
        ap_new_st = jnp.int32(CMD_ST_PRE_APPLIED)
        ap_du = du
        if promote:
            ap_new_st = jnp.where(deps_empty, CMD_ST_APPLIED,
                                  CMD_ST_PRE_APPLIED)
            ap_du = jnp.where(ap_ok & deps_empty,
                              jnp.maximum(du, dur_local), du)
        ap_st = jnp.where(ap_ok, ap_new_st, st)
        ap_fl = jnp.where(ap_ok & msg_has_txn, fl | 1, fl)
        ap_ea = jnp.where(ap_ok, oex, ea)

        # -- select per kind, gate on valid, scatter back ---------------------
        is_pa = kind == CMD_OP_PREACCEPT
        is_ac = kind == CMD_OP_ACCEPT
        is_cm = kind == CMD_OP_COMMIT

        def pick(a, b, c_, d):
            return jnp.where(is_pa, a,
                             jnp.where(is_ac, b, jnp.where(is_cm, c_, d)))

        new_st = jnp.where(valid, pick(pa_st, ac_st, cm_st, ap_st), st)
        new_fl = jnp.where(valid, pick(pa_fl, fl, cm_fl, ap_fl), fl)
        new_pr = jnp.where(valid, pick(pa_pr, ac_pr, pr, pr), pr)
        new_ab = jnp.where(valid, pick(ab, ac_ab, ab, ab), ab)
        new_ea = jnp.where(valid, pick(pa_ea, ac_ea, cm_ea, ap_ea), ea)
        new_du = jnp.where(valid, pick(du, du, du, ap_du), du)
        code = pick(pa_code, ac_code, cm_code, ap_code)
        ts_out = pick(pa_out_ts, ac_ea, cm_ea, ap_ea)
        do_reg = valid & pick(pa_wit, ac_ok, cm_ok, ap_ok)
        regval = pick(witness, oex, cm_regval, cm_regval)

        r_st = r_st.at[i].set(new_st)
        r_fl = r_fl.at[i].set(new_fl)
        r_pr = r_pr.at[i].set(new_pr)
        r_ab = r_ab.at[i].set(new_ab)
        r_ea = r_ea.at[i].set(new_ea)
        r_du = r_du.at[i].set(new_du)

        better = ~kv | _lex_before(km, regval[None, :])
        take = do_reg & better & (kids >= 0)
        nkm = jnp.where(take[:, None], regval[None, :], km)
        k_km = k_km.at[i].set(nkm)
        k_kv = k_kv.at[i].set(kv_raw | do_reg)

        clock = jnp.where(valid & is_pa & pa_wit, wit_clock, clock)
        out_code = out_code.at[i].set(jnp.where(valid, code, -1))
        out_ts = out_ts.at[i].set(ts_out)
        out_status = out_status.at[i].set(new_st)
        return (r_st, r_fl, r_pr, r_ab, r_ea, r_du, k_km, k_kv,
                clock, out_code, out_ts, out_status)

    init = (st_0, fl_0, pr_0, ab_0, ea_0, du_0, km_0, kv_0,
            jnp.asarray(clock, jnp.int32),
            jnp.full(n, -1, jnp.int32), jnp.full((n, 3), neg, jnp.int32),
            jnp.full(n, -1, jnp.int32))
    (r_st, r_fl, r_pr, r_ab, r_ea, r_du, k_km, k_kv,
     clock, out_code, out_ts, out_status) = \
        jax.lax.fori_loop(0, n, body, init)

    # single writeback: each row's / kid's last in-batch writer carries the
    # chain's final value (padding and non-last writes drop)
    wrow = jnp.where(op_rlast, op_row, cap)
    status = status.at[wrow].set(r_st, mode="drop")
    flags = flags.at[wrow].set(r_fl, mode="drop")
    promised = promised.at[wrow].set(r_pr, mode="drop")
    accepted = accepted.at[wrow].set(r_ab, mode="drop")
    execute_at = execute_at.at[wrow].set(r_ea, mode="drop")
    durability = durability.at[wrow].set(r_du, mode="drop")
    wkid = jnp.where(op_klast, op_keys, kcap).reshape(-1)
    kmax = kmax.at[wkid].set(k_km.reshape(-1, 3), mode="drop")
    kmax_valid = kmax_valid.at[wkid].set(k_kv.reshape(-1), mode="drop")
    return (status, flags, promised, accepted, execute_at, durability,
            kmax, kmax_valid, clock, out_code, out_ts, out_status,
            cmd_checksum(out_code, out_status, out_ts, clock))


# -- the protocol megakernel --------------------------------------------------
#
# One jitted program per cluster tick: the node-lane resolve (key + range),
# every plan's finalize-CSR compaction demuxed IN-KERNEL at its merge span,
# optional cmd_tick blocks, and the fast-path electorate-quorum count over
# the tick's PreAccept lanes. Each stage is the SAME trace body the
# standalone kernels run (_finalize_csr_body / _range_finalize_csr_body /
# _cmd_tick_body and the node_lane resolve bodies), so fused outputs are
# bit-identical to the unfused ≤2-dispatch path by construction.
#
# Programs are cached per static signature (which stages are present, each
# finalize's slice shape + out_cap, each cmd block's promote flag, the
# quorum size); every shape in the signature rides an existing tier ladder,
# so warm burns re-land on compiled entries.

_PROTOCOL_TICK_FNS: dict = {}


def _cmd_repair_body(status, flags, promised, accepted, execute_at,
                     durability, kmax, kvalid, rows_idx, st_v, fl_v, pr_v,
                     ab_v, ea_v, du_v, kid_idx, km_v, kv_v):
    """One CmdPlane's deferred-twin repair: scatter the host shadows'
    current values over the dirty rows/kids INSIDE the fused program, so
    the device-messages path retires the twin's flush debt without a
    standalone flush_lane dispatch. Idempotent by construction (a repair
    writes exactly what a flush would), so staleness is impossible;
    padding indices point one past the cap and drop."""
    status = status.at[rows_idx].set(st_v, mode="drop")
    flags = flags.at[rows_idx].set(fl_v, mode="drop")
    promised = promised.at[rows_idx].set(pr_v, mode="drop")
    accepted = accepted.at[rows_idx].set(ab_v, mode="drop")
    execute_at = execute_at.at[rows_idx].set(ea_v, mode="drop")
    durability = durability.at[rows_idx].set(du_v, mode="drop")
    kmax = kmax.at[kid_idx].set(km_v, mode="drop")
    kvalid = kvalid.at[kid_idx].set(kv_v, mode="drop")
    return (status, flags, promised, accepted, execute_at, durability,
            kmax, kvalid)


def _protocol_tick_fn(statics):
    fn = _PROTOCOL_TICK_FNS.get(statics)
    if fn is not None:
        return fn
    has_key, has_rng, fin_statics, cmd_promotes, qsize, has_mail, \
        n_repairs, exec_statics = statics
    # node_lane imports from this module -- resolve lazily (first call
    # always happens after the engine imported it)
    from accord_tpu.ops import node_lane as _nl
    from accord_tpu.ops.mailbox import _mailbox_route_body

    def run(witness_table, key_in, rng_in, fin_in, cmd_in, q_in,
            mail_in, rep_in, exec_in):
        packed = ()
        rng_out = ()
        if has_key:
            packed = _nl._key_resolve_body(*key_in, witness_table)
        if has_rng:
            rng_out = _nl._range_resolve_body(*rng_in, witness_table)
        fin_outs = []
        for spec, args in zip(fin_statics, fin_in):
            kind = spec[0]
            if kind == "range":
                (iv_of, iv_s, iv_e, ent_ok, f_sb, f_sknd,
                 (r_start, r_end, r_ts, r_kinds, r_valid)) = args
                fin_outs.append(_range_finalize_csr_body(
                    iv_of, iv_s, iv_e, ent_ok, f_sb, f_sknd,
                    r_start, r_end, r_ts, r_kinds, r_valid,
                    witness_table, spec[1]))
            else:
                _k, rows, words, out_cap = spec
                (r0, w_lo, word_off, kid_rows, slot_subj, slot_kid,
                 subj_row) = args
                src = packed if kind == "key" else rng_out[1]
                blk = jax.lax.dynamic_slice(src, (r0, w_lo), (rows, words))
                fin_outs.append(_finalize_csr_body(
                    blk, word_off, kid_rows, slot_subj, slot_kid,
                    subj_row, out_cap))
        cmd_outs = []
        for promote, args in zip(cmd_promotes, cmd_in):
            cmd_outs.append(_cmd_tick_body(*args, promote=promote))
        q_out = ()
        if qsize is not None:
            q_txn, q_ts, q_code, q_valid = q_in
            # a lane is a fast-path PreAccept witness iff it SUCCEEDED and
            # echoed the txn id unchanged (the host fastpath test)
            fast = q_valid & ((q_code & 7) == CMD_OUT_SUCCESS) \
                & jnp.all(q_ts == q_txn, axis=1)
            same = jnp.all(q_txn[:, None, :] == q_txn[None, :, :], axis=2)
            votes = jnp.sum(same & fast[None, :], axis=1, dtype=jnp.int32)
            q_out = (fast, votes, fast & (votes >= qsize))
        mail_out = ()
        if has_mail:
            mail_out = _mailbox_route_body(*mail_in)
        rep_outs = tuple(_cmd_repair_body(*rep_in[i])
                         for i in range(n_repairs))
        exec_outs = tuple(_frontier_compact_body(exec_in[i], oc)
                          for i, oc in enumerate(exec_statics))
        return (packed, rng_out, tuple(fin_outs), tuple(cmd_outs), q_out,
                mail_out, rep_outs, exec_outs)

    fn = jax.jit(run)
    _PROTOCOL_TICK_FNS[statics] = fn
    return fn


def protocol_tick(witness_table, key_in=None, rng_in=None, fins=(),
                  cmds=(), quorum=None, quorum_size=1, mailbox=None,
                  cmd_repairs=(), execs=()):
    """Launch the fused cluster-tick program: ONE device dispatch covering
    deps resolve, finalize compaction, cmd transitions, the fast-path
    quorum count, the device-message mailbox routing stage, and any
    CmdPlane repair scatters.

    key_in:  node_fused_deps_resolve's args minus witness_table, or None
    rng_in:  node_fused_range_deps_resolve's args minus witness_table
    fins:    finalize specs, one per (plan, group), in harvest order:
               ("key",  row_off, w_lo, rows, words, word_off, kid_rows,
                slot_subj, slot_kid, subj_row, out_cap)
               ("rkey", ... same lanes, sliced from the k-side range output)
               ("range", iv_of, iv_s, iv_e, ent_ok, sb, sknd,
                rsnap 5-tuple, out_cap)
             key/rkey specs dynamic-slice their plan's [rows x words] span
             out of the merged packed result in-kernel, then run the exact
             finalize_csr body with the group's word offset -- slot_subj is
             plan-local, so recorded finalize lanes work unchanged.
    cmds:    cmd_tick arg tuples (every positional arg, promote last); the
             promote flag is static, everything else traced.
    quorum:  (txn i32[t,3], ts i32[t,3], code i32[t], valid bool[t]) lanes
             from the tick's PreAccept spans, padded to a MEGA_LANE_TIERS
             tier; quorum_size the electorate majority (static).
    mailbox: ops/mailbox.MailboxPlane.stage_batch's input tuple (arena,
             meta, emit lanes, partition mask) for the fused routing
             stage, or None.
    cmd_repairs: CmdPlane.collect_repair blocks (18 arrays each, see
             _cmd_repair_body) retiring deferred-twin flush debt in-kernel.
    execs:   execution-frontier compaction blocks, one per ExecCoordinator
             staging this tick: (planes, out_cap) where planes is the
             fused_execution_frontier lane-tuple tuple and out_cap the
             compaction tier (static). Outputs follow frontier_compact's
             contract (indptr, rows, csum, packed).
    -> (packed, (rpacked, kpacked), fin_outs, cmd_outs,
        (fast, votes, met), mail_out, rep_outs, exec_outs); absent stages
        return ().
    """
    fin_statics, fin_traced, order = _fin_split(fins)
    cmd_statics = tuple(bool(c[-1]) for c in cmds)
    cmd_traced = tuple(tuple(c[:-1]) for c in cmds)
    exec_statics = tuple(int(oc) for (_pl, oc) in execs)
    exec_traced = tuple(tuple(tuple(p) for p in pl) for (pl, _oc) in execs)
    statics = (key_in is not None, rng_in is not None, tuple(fin_statics),
               cmd_statics, int(quorum_size) if quorum is not None else None,
               mailbox is not None, len(cmd_repairs), exec_statics)
    fn = _protocol_tick_fn(statics)
    (packed, rng_out, fin_outs, cmd_outs, q_out, mail_out, rep_outs,
     exec_outs) = fn(
        witness_table,
        tuple(key_in) if key_in is not None else (),
        tuple(rng_in) if rng_in is not None else (),
        tuple(fin_traced), cmd_traced,
        tuple(quorum) if quorum is not None else (),
        tuple(mailbox) if mailbox is not None else (),
        tuple(tuple(r) for r in cmd_repairs),
        exec_traced)
    return (packed, rng_out, _fin_unsort(fin_outs, order), cmd_outs,
            q_out, mail_out, rep_outs, exec_outs)


def _fin_split(fins):
    """Split finalize specs into (static signature, traced args) and
    canonically stable-sort them by static signature, so the compiled
    program key depends on the tick's signature MULTISET, not the arrival
    order of plans -- order jitter across ticks would otherwise mint a
    fresh multi-second compile per permutation. Shared by protocol_tick
    and parallel.mesh.sharded_protocol_tick (same cache-key discipline on
    both paths). Returns (fin_statics, fin_traced, order); undo the sort
    on the outputs with _fin_unsort(fin_outs, order)."""
    fin_statics, fin_traced = [], []
    for f in fins:
        if f[0] == "range":
            fin_statics.append(("range", f[8]))
            fin_traced.append(tuple(f[1:8]))
        else:
            fin_statics.append((f[0], f[3], f[4], f[10]))
            fin_traced.append((f[1], f[2]) + tuple(f[5:10]))
    order = sorted(range(len(fin_statics)), key=lambda i: fin_statics[i])
    return ([fin_statics[i] for i in order],
            [fin_traced[i] for i in order], order)


def _fin_unsort(fin_outs, order):
    """Undo _fin_split's canonical sort: callers demux fin_outs
    positionally against the fins they passed in."""
    if order == list(range(len(order))):
        return tuple(fin_outs)
    back = [0] * len(order)
    for pos, i in enumerate(order):
        back[i] = pos
    return tuple(fin_outs[back[i]] for i in range(len(order)))


def protocol_tick_cache_sizes() -> int:
    """Total compiled protocol_tick variants across every static signature
    (the megakernel's entry in jit_cache_sizes)."""
    return sum(f._cache_size() for f in _PROTOCOL_TICK_FNS.values())


def jit_cache_sizes() -> dict:
    """Compiled-variant counts of the warmable hot-path kernels: the bench
    snapshots this around its timed windows to assert warmup() covered every
    jit tier the pipeline dispatches (0 recompiles while timing)."""
    return {
        "deps_resolve": deps_resolve._cache_size(),
        "range_deps_resolve": range_deps_resolve._cache_size(),
        "fused_deps_resolve": fused_deps_resolve._cache_size(),
        "fused_range_deps_resolve": fused_range_deps_resolve._cache_size(),
        "arena_scatter": arena_scatter._cache_size(),
        "arena_scatter_keys": arena_scatter_keys._cache_size(),
        "arena_copy": arena_copy._cache_size(),
        "scatter_rows": scatter_rows._cache_size(),
        "range_scatter": range_scatter._cache_size(),
        "finalize_csr": finalize_csr._cache_size(),
        "range_finalize_csr": range_finalize_csr._cache_size(),
        "kid_word_scatter": kid_word_scatter._cache_size(),
        "fused_execution_frontier": fused_execution_frontier._cache_size(),
        "frontier_compact": frontier_compact._cache_size(),
        "recovery_scan": recovery_scan._cache_size(),
        "cmd_tick": cmd_tick._cache_size(),
        "protocol_tick": protocol_tick_cache_sizes(),
        # node-lane (cluster-on-mesh burn) kernels live in ops/node_lane,
        # which imports from this module -- resolve lazily to avoid a cycle
        **_node_lane_cache_sizes(),
        # likewise the sharded megakernel lives in parallel/mesh
        **_mesh_cache_sizes(),
    }


def _node_lane_cache_sizes() -> dict:
    import sys
    mod = sys.modules.get("accord_tpu.ops.node_lane")
    if mod is None:
        # not imported -> nothing compiled -> all zero (reported anyway so
        # bench deltas stay keyed consistently)
        return {"node_fused_deps_resolve": 0,
                "node_fused_range_deps_resolve": 0,
                "lane_slice": 0}
    return mod.node_lane_cache_sizes()


def _mesh_cache_sizes() -> dict:
    import sys
    mod = sys.modules.get("accord_tpu.parallel.mesh")
    if mod is None:
        return {"sharded_protocol_tick": 0}
    return {"sharded_protocol_tick": mod.sharded_protocol_tick_cache_sizes()}
