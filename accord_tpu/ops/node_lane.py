"""Node-id batch axis for the cluster-on-mesh burn (sim/mesh_burn.py): the
PR 4 store-id-lane fusion lifted one level up. PR 4 folded every STORE's
pending items on one node into a single device call (fused_deps_resolve's
`subj_store` lane + per-store word spans); this module folds every NODE's
encoded dispatch plans in one cluster tick into a single device call with a
traced `subj_node` lane, so the burn's per-tick device cost stops scaling
with cluster size.

The merge is a pure re-batching, engineered for BIT-IDENTITY with the
per-node launch loop:

  - Each plan's already-encoded subject lanes (the CSR entries, 3-lane
    `before` bounds, kinds, the store-id routing lane) stack row-major into
    one node-major block; CSR entries remap by the plan's row offset.
  - Each plan's arena snapshots enter the kernel as lane blocks exactly as
    `fused_deps_resolve` takes them; every (plan, store-group) pair gets a
    globally unique slot id (`plan_base + local group index`), so a subject
    only ever sees its own plan's arena rows. Plan bases advance by
    `len(groups) + 1`, keeping each plan's padding sentinel
    (`plan_base + len(groups)`) unmatched by construction.
  - The masked bf16 products the MXU contracts are exact 0/1 integers and
    every mask/pack op is exact, so per-plan output slices equal the
    per-plan kernel calls bit for bit regardless of how blocks batch
    together (the same argument that made PR 4's fused path differential
    with the per-store loop). Block caps are 32-row multiples (the arena
    capacity contract), so packed word boundaries never straddle blocks.
  - Demux is the `_Group` row-offset-table pattern: each plan slices
    `[row_off : row_off + b, w_lo : w_hi]` out of the merged packed result,
    and the untouched group spans (g.pk / g.rp / g.kp) keep routing the
    harvest decode inside that slice.

Shape discipline mirrors the rest of ops/: the merged subject axis pads to
NODE_SUBJECT_TIERS, the merged CSR to the shared nnz ladder, and the block
COUNT pads to the resolver's `pad_node_tiers` ladder with cached empty
arena blocks under slot -1 -- node-count churn (crashes, membership change)
re-lands on the same compiled tiers, so steady-state burns mint zero new
jit entries (asserted over the node-lane cache sizes below by
tests/test_mesh_burn.py::test_crash_restart_lane_pads_out_without_recompile).

The merge structures built here (build_key_merge / build_range_merge) are
consumed by THREE launch paths, all bit-identical by the argument above:
the single-device fused kernels, ops/kernels.protocol_tick (the
single-device megakernel inlines _key_resolve_body/_range_resolve_body),
and parallel/mesh.sharded_protocol_tick (the sharded megakernel feeds the
same merge inputs to its shard_map'd resolve stage).
"""
from __future__ import annotations

import functools
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from accord_tpu.ops.kernels import (_lex_before, _pack_bits, _witness_mask,
                                    covered_buckets, nnz_tier)
from accord_tpu.ops.tiers import snap

# Merged subject-row ladder: a cluster tick at N nodes stacks up to
# N * max_dispatch subject rows, so the named tiers run past SUBJECT_TIERS;
# oversized totals fall onto power-of-two buckets like every other ladder.
NODE_SUBJECT_TIERS = (64, 256, 1024, 4096)

# Default block-count ladder for pad_node_tiers when the resolver doesn't
# pin one: snaps the per-tick (plan, store) block count so node churn of a
# few replicas (crash / restart / membership change) stays on one tier.
NODE_BLOCK_TIERS = (2, 4, 8, 16, 32, 64, 128, 256)


def node_subject_tier(n: int) -> int:
    """Padded merged-subject row count for a cluster tick of n rows."""
    return snap(n, NODE_SUBJECT_TIERS, 8192)


def node_block_tier(n: int, tiers: Optional[Sequence[int]] = None) -> int:
    """Padded lane-block count for a cluster tick of n (plan, group)
    blocks. `tiers` comes from resolver.pad_node_tiers when set (an int is
    treated as a single named tier, mirroring pad_store_tiers)."""
    if tiers is None:
        tiers = NODE_BLOCK_TIERS
    elif isinstance(tiers, int):
        tiers = (tiers,)
    tiers = tuple(tiers)
    return snap(n, tiers, tiers[-1] if tiers else 2)


@jax.jit
def node_fused_deps_resolve(subj_of, subj_keys, subj_node, subj_before,
                            subj_kinds, slots, arenas, witness_table):
    """Cluster-tick twin of kernels.fused_deps_resolve: ONE device call
    answers every node's key-domain deps slice. `arenas` is a tuple of
    (plan, store)-lane blocks in plan-major order (padding blocks last
    under slot -1); `subj_node` routes each stacked subject row to its own
    plan's block via the globally unique slot ids.

    subj_of:     i32[nnz]   merged CSR subject rows (padding entries use B)
    subj_keys:   i32[nnz]   key bucket indices (already % K)
    subj_node:   i32[B]     global (plan, group) slot per subject row
    slots:       i32[S]     the slot each block answers (traced)
    arenas:      tuple of S (bitmaps f32[cap_s, K], ts i32[cap_s, 3],
                 kinds i32[cap_s], valid bool[cap_s])
    -> u32[B, sum(cap_s)/32] packed dependency bitmask, blocks in tuple
       order (each plan's word span is contiguous)
    """
    return _key_resolve_body(subj_of, subj_keys, subj_node, subj_before,
                             subj_kinds, slots, arenas, witness_table)


def _key_resolve_body(subj_of, subj_keys, subj_node, subj_before,
                      subj_kinds, slots, arenas, witness_table):
    """node_fused_deps_resolve's trace body, unjitted so the protocol
    megakernel (kernels.protocol_tick) inlines the same resolve."""
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
        mine = (subj_node == slots[s])[:, None]
        outs.append(_pack_bits(
            overlap & witness & before & act_valid[None, :] & mine))
    return jnp.concatenate(outs, axis=1)


@jax.jit
def node_fused_range_deps_resolve(iv_of, iv_start, iv_end, subj_node,
                                  subj_before, subj_kinds, subj_is_range,
                                  r_slots, rarenas, k_slots, karenas,
                                  witness_table):
    """Cluster-tick twin of kernels.fused_range_deps_resolve: every node's
    range-arena stab and key-arena hull contraction in one call. Slot
    routing and block order work exactly like node_fused_deps_resolve;
    either block tuple may be empty (that side returns a zero-width
    buffer).

    -> (u32[B, sum(rcap_s)/32], u32[B, sum(cap_s)/32])
    """
    return _range_resolve_body(iv_of, iv_start, iv_end, subj_node,
                               subj_before, subj_kinds, subj_is_range,
                               r_slots, rarenas, k_slots, karenas,
                               witness_table)


def _range_resolve_body(iv_of, iv_start, iv_end, subj_node,
                        subj_before, subj_kinds, subj_is_range,
                        r_slots, rarenas, k_slots, karenas, witness_table):
    """node_fused_range_deps_resolve's trace body, unjitted for
    kernels.protocol_tick (see _key_resolve_body)."""
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
        mine = (subj_node == r_slots[s])[:, None]
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
        mine = (subj_node == k_slots[s])[:, None] & subj_is_range[:, None]
        kouts.append(_pack_bits(
            any_k & witness_k & before_k & k_valid[None, :] & mine))
    rpacked = jnp.concatenate(routs, axis=1) if routs \
        else jnp.zeros((b, 0), jnp.uint32)
    kpacked = jnp.concatenate(kouts, axis=1) if kouts \
        else jnp.zeros((b, 0), jnp.uint32)
    return rpacked, kpacked


@functools.partial(jax.jit, static_argnames=("rows", "words"))
def lane_slice(packed, row_off, word_off, rows: int, words: int):
    """Demux one plan's span out of the merged packed result. Offsets are
    traced (plan position in the merge never recompiles); the slice shape
    is static per (plan row tier, plan word width). Both axes ride bounded
    ladders: rows are per-plan subject tiers and multi-block span WIDTHS
    pad to the node-block tier times the block word width (see
    build_key_merge), so lane_slice sits under the same strict
    zero-recompile gates as every other tick kernel."""
    return jax.lax.dynamic_slice(packed, (row_off, word_off), (rows, words))


def node_lane_cache_sizes() -> dict:
    """Compiled-variant counts of the node-lane kernels (what
    tests/test_mesh_burn.py holds still across node-count churn)."""
    return {
        "node_fused_deps_resolve": node_fused_deps_resolve._cache_size(),
        "node_fused_range_deps_resolve":
            node_fused_range_deps_resolve._cache_size(),
        "lane_slice": lane_slice._cache_size(),
    }


class MergedBuffer:
    """One merged device result shared by every plan's MergedView: a single
    async copy, a single host materialization, views slice it host-side.
    This is the megakernel's harvest half -- the readback is ONE contiguous
    transfer and the per-plan demux costs zero device dispatches."""

    __slots__ = ("dev", "_copied", "_host")

    def __init__(self, dev):
        self.dev = dev
        self._copied = False
        self._host = None

    def copy_async(self) -> None:
        if not self._copied:
            self._copied = True
            self.dev.copy_to_host_async()

    def is_ready(self) -> bool:
        return self.dev.is_ready()

    def host(self):
        if self._host is None:
            self._host = np.asarray(self.dev)
        return self._host


class MergedView:
    """A plan's [row_off:+rows, word_off:+words] window of a MergedBuffer,
    duck-typed to the resolver's device-value protocol (_dev_ready /
    _dev_copy_async / _dev_read / block_until_ready). np.asarray returns a
    COPY of the window: the fault plane may bit-flip one plan's fetched
    arrays (ops/fault_plane.py corrupt draws) and sibling plans sharing the
    merged buffer must never see it."""

    __slots__ = ("buf", "r0", "rows", "w0", "words")

    def __init__(self, buf: MergedBuffer, r0: int, rows: int,
                 w0: int, words: int):
        self.buf = buf
        self.r0 = r0
        self.rows = rows
        self.w0 = w0
        self.words = words

    @property
    def shape(self):
        return (self.rows, self.words)

    def is_ready(self) -> bool:
        return self.buf.is_ready()

    def copy_to_host_async(self) -> None:
        self.buf.copy_async()

    def block_until_ready(self):
        self.buf.host()
        return self

    def __array__(self, dtype=None):
        h = self.buf.host()[self.r0:self.r0 + self.rows,
                            self.w0:self.w0 + self.words]
        return np.array(h, dtype=dtype, copy=True)


class KeyMerge:
    """The stacked inputs + demux spans for one cluster tick's key-domain
    merge. Built host-side from each plan's recorded `key_args` (the exact
    arrays its own kernel call would have consumed); `spans[i]` is plan i's
    (row_off, rows, word_off, words) slice of the merged packed output."""

    __slots__ = ("subj_of", "subj_keys", "subj_node", "sb", "sknd",
                 "slots", "blocks", "spans", "rows_used", "rows_padded")

    def __init__(self, subj_of, subj_keys, subj_node, sb, sknd, slots,
                 blocks, spans, rows_used, rows_padded):
        self.subj_of = subj_of
        self.subj_keys = subj_keys
        self.subj_node = subj_node
        self.sb = sb
        self.sknd = sknd
        self.slots = slots
        self.blocks = blocks
        self.spans = spans
        self.rows_used = rows_used
        self.rows_padded = rows_padded


class RangeMerge:
    """The stacked inputs + demux spans for one cluster tick's range-domain
    merge; `spans[i]` is (row_off, rows, r_word_off, r_words, k_word_off,
    k_words) -- zero-width sides mean the plan had no blocks there."""

    __slots__ = ("iv_of", "iv_s", "iv_e", "subj_node", "sb", "sknd", "srng",
                 "r_slots", "r_blocks", "k_slots", "k_blocks", "spans",
                 "rows_used", "rows_padded")

    def __init__(self, iv_of, iv_s, iv_e, subj_node, sb, sknd, srng,
                 r_slots, r_blocks, k_slots, k_blocks, spans,
                 rows_used, rows_padded):
        self.iv_of = iv_of
        self.iv_s = iv_s
        self.iv_e = iv_e
        self.subj_node = subj_node
        self.sb = sb
        self.sknd = sknd
        self.srng = srng
        self.r_slots = r_slots
        self.r_blocks = r_blocks
        self.k_slots = k_slots
        self.k_blocks = k_blocks
        self.spans = spans
        self.rows_used = rows_used
        self.rows_padded = rows_padded


def _layout(arg_list) -> Tuple[List[int], List[int], int, int]:
    """Common row layout over the plans in merge order: per-plan row
    offsets, per-plan padded widths, the padded total, and the used total.
    Key and range merges share one layout per plan set so subj rows line
    up with both CSRs."""
    offs, widths, off = [], [], 0
    for args in arg_list:
        b = args["sb"].shape[0]
        offs.append(off)
        widths.append(b)
        off += b
    total = node_subject_tier(off) if off else 0
    return offs, widths, off, total


def build_key_merge(entries, pad_block, node_tiers=None) -> KeyMerge:
    """Stack each plan's recorded key_args into one node-major dispatch.
    `entries` is [(plan, key_args)] in launch order; `pad_block(cap)`
    returns a cached empty key-arena 4-tuple (the resolver's
    pad_store_tiers cache, reused as the node-tier pad pool).

    Each fused plan's recorded `pad_tier` mirrors its resolver's
    pad_store_tiers: the baseline `_pad_fused` tops each FUSED call's block
    list up to it at launch time, so each fused plan's packed buffer
    carries those pad word columns. The merge replicates that padding
    INSIDE the plan's span -- the demuxed slice's live word columns equal
    the baseline buffer bit for bit (a multi-block span may then widen to
    its node-block-tier word width with further all-zero columns, which the
    group-span decode never reads -- that's what pins lane_slice's compiled
    shapes to a bounded ladder)."""
    arg_list = [args for _, args in entries]
    offs, widths, used, b_total = _layout(arg_list)
    sb = np.zeros((b_total, 3), np.int32)
    sknd = np.zeros(b_total, np.int32)
    subj_node = np.full(b_total, -9, np.int32)
    # recorded CSRs are already tier-padded per plan; restack only the live
    # entries so the merged nnz tier tracks the real total
    live_of, live_keys = [], []
    slots_all: List[int] = []
    blocks: List[tuple] = []
    spans: List[tuple] = []
    base = 0
    w_off = 0
    for p, (plan, args) in enumerate(entries):
        b = widths[p]
        r0 = offs[p]
        sb[r0:r0 + b] = args["sb"]
        sknd[r0:r0 + b] = args["sknd"]
        ngroups = args["ngroups"]
        # global slot ids: plan_base + local group index; the plan's
        # padding sentinel (plan_base + ngroups) matches no block
        subj_node[r0:r0 + b] = base + args["subj_store"]
        local = args["subj_of"]
        mask = local < b
        live_of.append(np.where(mask, local + r0, 0)[mask])
        live_keys.append(args["subj_keys"][mask])
        w_lo = w_off
        nreal = 0
        nspan = 0
        cap_plan = 0
        caps = set()
        for gslot, snap_ in zip(args["slots"], args["ksnaps"]):
            bm, ts, _ex, kinds, valid = snap_
            blocks.append((bm, ts, kinds, valid))
            slots_all.append(base + int(gslot))
            w_off += bm.shape[0] // 32
            nreal += 1
            caps.add(bm.shape[0])
            cap_plan = max(cap_plan, bm.shape[0])
        nspan = nreal
        tier_p = args["pad_tier"] if args["fused"] else None
        if tier_p and nreal < tier_p:
            pad = pad_block(cap_plan)
            for _ in range(tier_p - nreal):
                blocks.append(pad)
                slots_all.append(-1)
                w_off += cap_plan // 32
            nspan = tier_p
        # demux-span WIDTH tier (the lane_slice zero-recompile fix): pad a
        # multi-block uniform-cap span out to the node-block tier's word
        # width with empty blocks, so harvest slice shapes land on the
        # (subject tier x block tier) ladder instead of minting one shape
        # per participating store count. Single-block spans are already
        # tiered by the arena cap ladder; mixed-cap spans (arenas caught
        # mid-growth) keep their exact width.
        if nspan > 1 and len(caps) == 1 and cap_plan:
            bw = cap_plan // 32
            want = node_block_tier(nspan, node_tiers) * bw
            pad = pad_block(cap_plan)
            while w_off - w_lo < want:
                blocks.append(pad)
                slots_all.append(-1)
                w_off += bw
        spans.append((r0, b, w_lo, w_off - w_lo))
        base += ngroups + 1
    # block-count tier: cached empty blocks under slot -1 (no subject's
    # lane is negative), capacity matching the widest real block so the
    # compiled shape tracks arena growth
    tier = node_block_tier(len(blocks), node_tiers)
    if blocks and len(blocks) < tier:
        cap = max(b[0].shape[0] for b in blocks)
        pad = pad_block(cap)
        while len(blocks) < tier:
            blocks.append(pad)
            slots_all.append(-1)
    total_live = sum(a.shape[0] for a in live_of)
    z = nnz_tier(total_live) if total_live else nnz_tier(1)
    subj_of = np.full(z, b_total, np.int32)
    subj_keys = np.zeros(z, np.int32)
    if total_live:
        subj_of[:total_live] = np.concatenate(live_of)
        subj_keys[:total_live] = np.concatenate(live_keys)
    return KeyMerge(subj_of, subj_keys, subj_node, sb, sknd,
                    np.asarray(slots_all, np.int32), tuple(blocks), spans,
                    used, b_total)


def build_range_merge(entries, pad_key_block, pad_range_block,
                      node_tiers=None) -> RangeMerge:
    """Stack each plan's recorded range_args into one node-major dispatch:
    the merged interval CSR plus plan-major range-arena and key-arena
    block lists (independently tier-padded). Each fused plan's recorded
    `pad_tier` replicates the baseline's per-plan `_pad_fused` padding
    inside that plan's span on BOTH sides (see build_key_merge); a side
    whose baseline result is discarded (has_r/has_k False) contributes no
    blocks at all."""
    arg_list = [args for _, args in entries]
    offs, widths, used, b_total = _layout(arg_list)
    sb = np.zeros((b_total, 3), np.int32)
    sknd = np.zeros(b_total, np.int32)
    srng = np.zeros(b_total, bool)
    subj_node = np.full(b_total, -9, np.int32)
    live_of, live_s, live_e = [], [], []
    r_slots: List[int] = []
    k_slots: List[int] = []
    r_blocks: List[tuple] = []
    k_blocks: List[tuple] = []
    spans: List[tuple] = []
    base = 0
    rw_off = kw_off = 0
    for p, (plan, args) in enumerate(entries):
        b = widths[p]
        r0 = offs[p]
        sb[r0:r0 + b] = args["sb"]
        sknd[r0:r0 + b] = args["sknd"]
        srng[r0:r0 + b] = args["srng"]
        ngroups = args["ngroups"]
        subj_node[r0:r0 + b] = base + args["subj_store"]
        local = args["iv_of"]
        mask = local < b
        live_of.append(np.where(mask, local + r0, 0)[mask])
        live_s.append(args["iv_s"][mask])
        live_e.append(args["iv_e"][mask])
        rw_lo, kw_lo = rw_off, kw_off
        tier_p = args["pad_tier"] if args["fused"] else None
        nreal_r = 0
        rcap_plan = 0
        rcaps = set()
        if args["has_r"]:
            for gslot, snap_ in zip(args["r_slots"], args["rsnaps"]):
                r_blocks.append(snap_)
                r_slots.append(base + int(gslot))
                rw_off += snap_[0].shape[0] // 32
                nreal_r += 1
                rcaps.add(snap_[0].shape[0])
                rcap_plan = max(rcap_plan, snap_[0].shape[0])
            nspan_r = nreal_r
            if tier_p and nreal_r < tier_p:
                pad = pad_range_block(rcap_plan)
                for _ in range(tier_p - nreal_r):
                    r_blocks.append(pad)
                    r_slots.append(-1)
                    rw_off += rcap_plan // 32
                nspan_r = tier_p
            # span-width tier, exactly as build_key_merge
            if nspan_r > 1 and len(rcaps) == 1 and rcap_plan:
                bw = rcap_plan // 32
                want = node_block_tier(nspan_r, node_tiers) * bw
                pad = pad_range_block(rcap_plan)
                while rw_off - rw_lo < want:
                    r_blocks.append(pad)
                    r_slots.append(-1)
                    rw_off += bw
        nreal_k = 0
        kcap_plan = 0
        kcaps = set()
        if args["has_k"]:
            for gslot, snap_ in zip(args["k_slots"], args["ksnaps"]):
                bm, ts, _ex, kinds, valid = snap_
                k_blocks.append((bm, ts, kinds, valid))
                k_slots.append(base + int(gslot))
                kw_off += bm.shape[0] // 32
                nreal_k += 1
                kcaps.add(bm.shape[0])
                kcap_plan = max(kcap_plan, bm.shape[0])
            nspan_k = nreal_k
            if tier_p and nreal_k < tier_p:
                pad = pad_key_block(kcap_plan)
                for _ in range(tier_p - nreal_k):
                    k_blocks.append(pad)
                    k_slots.append(-1)
                    kw_off += kcap_plan // 32
                nspan_k = tier_p
            if nspan_k > 1 and len(kcaps) == 1 and kcap_plan:
                bw = kcap_plan // 32
                want = node_block_tier(nspan_k, node_tiers) * bw
                pad = pad_key_block(kcap_plan)
                while kw_off - kw_lo < want:
                    k_blocks.append(pad)
                    k_slots.append(-1)
                    kw_off += bw
        spans.append((r0, b, rw_lo, rw_off - rw_lo, kw_lo, kw_off - kw_lo))
        base += ngroups + 1
    rtier = node_block_tier(len(r_blocks), node_tiers) if r_blocks else 0
    if r_blocks and len(r_blocks) < rtier:
        cap = max(blk[0].shape[0] for blk in r_blocks)
        pad = pad_range_block(cap)
        while len(r_blocks) < rtier:
            r_blocks.append(pad)
            r_slots.append(-1)
    ktier = node_block_tier(len(k_blocks), node_tiers) if k_blocks else 0
    if k_blocks and len(k_blocks) < ktier:
        cap = max(blk[0].shape[0] for blk in k_blocks)
        pad = pad_key_block(cap)
        while len(k_blocks) < ktier:
            k_blocks.append(pad)
            k_slots.append(-1)
    total_live = sum(a.shape[0] for a in live_of)
    z = nnz_tier(total_live) if total_live else nnz_tier(1)
    iv_of = np.full(z, b_total, np.int32)
    iv_s = np.zeros(z, np.int32)
    iv_e = np.zeros(z, np.int32)
    if total_live:
        iv_of[:total_live] = np.concatenate(live_of)
        iv_s[:total_live] = np.concatenate(live_s)
        iv_e[:total_live] = np.concatenate(live_e)
    return RangeMerge(iv_of, iv_s, iv_e, subj_node, sb, sknd, srng,
                      np.asarray(r_slots, np.int32), tuple(r_blocks),
                      np.asarray(k_slots, np.int32), tuple(k_blocks),
                      spans, used, b_total)


def run_key_merge(merge: KeyMerge, witness_table):
    """Launch the merged key-domain dispatch (single device)."""
    return node_fused_deps_resolve(
        jnp.asarray(merge.subj_of), jnp.asarray(merge.subj_keys),
        jnp.asarray(merge.subj_node), jnp.asarray(merge.sb),
        jnp.asarray(merge.sknd), jnp.asarray(merge.slots),
        merge.blocks, witness_table)


def run_range_merge(merge: RangeMerge, witness_table):
    """Launch the merged range-domain dispatch (single device)."""
    return node_fused_range_deps_resolve(
        jnp.asarray(merge.iv_of), jnp.asarray(merge.iv_s),
        jnp.asarray(merge.iv_e), jnp.asarray(merge.subj_node),
        jnp.asarray(merge.sb), jnp.asarray(merge.sknd),
        jnp.asarray(merge.srng), jnp.asarray(merge.r_slots),
        merge.r_blocks, jnp.asarray(merge.k_slots), merge.k_blocks,
        witness_table)
