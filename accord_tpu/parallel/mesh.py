"""Device-mesh sharding of the deps data plane.

The reference scales inside a node by splitting ranges over single-threaded
CommandStores (local/CommandStores.java:79) -- an embarrassingly parallel
partition of the conflict state. On TPU the analogous dimensions are:

  'data'  axis: the micro-batch of subject transactions (rows of the
          conflict matrix) -- each device computes deps for its slice;
  'model' axis: the key-bucket dimension of the bitmaps -- the conflict
          contraction bitmap[B,K] @ bitmap[A,K]^T is summed over K with a
          psum across the axis (tensor-parallel contraction).

The execute-order closure all-gathers row blocks each squaring round
(ring-friendly collective over ICI). `sharded_deps_step` builds the whole
step -- deps matrix + adjacency closure + execution wavefronts -- as one
shard_map program jitted over the mesh; this is the multi-chip path the
driver dry-runs and the scale-out story for >1 chip.

Finalized-CSR harvest on the sharded path: the COMPACTION ITSELF is
sharded (sharded_finalize_csr) -- each device ANDs and popcounts only ITS
'data' slice of the word columns (kid table and packed candidate words
both sharded P(None, 'data')), an all-gather of the per-(slot, shard)
counts yields the global indptr plus every shard's exclusive write base
inside each segment, and the disjoint per-shard dep_rows fragments
gather-merge with one psum -- so no chip ever materializes a full
(slots x cap) conflict matrix, closing what used to be this module's open
scale-out item. Word order equals row order (cap % (32 * data) == 0) and
shards partition words contiguously, so the merged CSR is bit-identical
to the single-device kernel's. The interval-stab finalize
(range_finalize_csr) stays a plain jit: the range arena is tiny (tens of
rows) and carries no word-packed matrix worth sharding.

The sharded protocol megakernel (sharded_protocol_tick) is the multi-chip
twin of ops/kernels.protocol_tick: ONE jitted mesh program per cluster
tick composing the shard_map'd node-lane key+range resolve, every plan's
finalize-CSR compaction (the _sharded_finalize_body popcount/prefix +
all_gather merge above, sliced at each plan's merge span in-program),
cmd_tick blocks, the fast-path electorate-quorum count, and the
cross-shard mailbox routing stage -- emit lanes whose dst node lives on
another shard ride a tiled lax.all_to_all over 'data' into the
destination shard's rings (ops/mailbox._sharded_mailbox_route_part), with
partition masks and the mailbox arena sharded node-major. Finalize specs
canonically sort by static signature (kernels._fin_split), so the compile
cache keys on the tick-signature multiset exactly as the single-device
path does. sharded_node_tick (the unfused <=2-dispatch pair) stays live
as the megakernel=False baseline.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def make_mesh(n_devices: Optional[int] = None,
              devices=None) -> Mesh:
    """2D mesh ('data', 'model'); 'model' gets 2 when divisible, else 1."""
    if devices is None:
        devices = jax.devices()[:n_devices] if n_devices else jax.devices()
    n = len(devices)
    model = 2 if n % 2 == 0 and n >= 4 else 1
    data = n // model
    dev_array = np.array(devices[:data * model]).reshape(data, model)
    return Mesh(dev_array, ("data", "model"))


def mesh_supports_message_plane(mesh: Mesh) -> bool:
    """Whether the device mailbox plane may fuse into sharded programs.

    True since the mailbox routing stage grew its cross-shard collective:
    sharded_protocol_tick shards the arena and the partition mask node-major
    over 'data' and exchanges src-grouped emit lanes with a tiled
    lax.all_to_all (ops/mailbox._sharded_mailbox_route_part), so every
    payload reaches its destination shard's ring inside the one fused
    launch. Kept as a predicate so a future mesh topology that cannot carry
    the collective can opt back out to host messages (the engine counts
    that in sharded_megakernel_fallbacks)."""
    return True


def sharded_deps_step(mesh: Mesh, closure_iters: int = 8):
    """Build the jitted multi-chip deps step.

    Inputs (global shapes):
      bitmaps  f32[N, K]  key bitmaps of the in-flight batch
      ts       i32[N, 3]  packed txn timestamps (ops.encoding layout)
      kinds    i32[N]
      table    i32[6, 6]  witness table
    Outputs:
      deps     bool[N, N]  pairwise dependency matrix
      levels   i32[N]      execution wavefront level per txn
    Sharding: rows over 'data'; the K contraction over 'model' via psum;
    closure all-gathers row blocks per squaring round.
    """
    from accord_tpu.ops.kernels import _witness_mask

    def step(bitmaps, ts, kinds, table):
        # ---- deps matrix: rows sharded, K sharded, psum over 'model' ----
        def deps_part(bm_rows, ts_rows, kinds_rows, bm_all, ts_all, kinds_all, tbl):
            # bm_rows: [n_local, K_local]; bm_all: [N, K_local]
            partial = jax.lax.dot_general(
                bm_rows.astype(jnp.bfloat16), bm_all.astype(jnp.bfloat16),
                (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
            overlap = jax.lax.psum(partial, "model") > 0.5
            witness = _witness_mask(tbl, kinds_rows, kinds_all)
            a, b = ts_all[None, :, :], ts_rows[:, None, :]
            before = ((a[..., 0] < b[..., 0])
                      | ((a[..., 0] == b[..., 0])
                         & ((a[..., 1] < b[..., 1])
                            | ((a[..., 1] == b[..., 1]) & (a[..., 2] < b[..., 2])))))
            return overlap & witness & before

        deps = shard_map(
            deps_part, mesh=mesh,
            in_specs=(P("data", "model"), P("data", None), P("data"),
                      P(None, "model"), P(None, None), P(None), P(None, None)),
            out_specs=P("data", None),
        )(bitmaps, ts, kinds, bitmaps, ts, kinds, table)

        # ---- transitive closure: row blocks, all-gather per round ----
        def closure_block(rows):
            def body(_, r):
                full = jax.lax.all_gather(r, "data", tiled=True)  # [N, N]
                sq = jax.lax.dot_general(
                    r.astype(jnp.bfloat16), full.astype(jnp.bfloat16),
                    (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32) > 0.5
                return r | sq
            return jax.lax.fori_loop(0, closure_iters, body, rows)

        closed = shard_map(
            closure_block, mesh=mesh,
            in_specs=P("data", None), out_specs=P("data", None),
        )(deps)

        # ---- execution wavefronts over the closed graph ----
        def levels_block(adj_rows):
            def body(_, lv):
                full = jax.lax.all_gather(lv, "data", tiled=True)  # [N]
                dep_lv = jnp.where(adj_rows, full[None, :] + 1, 0)
                return jnp.maximum(lv, jnp.max(dep_lv, axis=1))

            # derive the initial carry from the (axis-varying) input so the
            # loop carry's manual-axes annotation matches the body output
            init = jnp.zeros_like(adj_rows[:, 0], dtype=jnp.int32)
            return jax.lax.fori_loop(0, closure_iters, body, init)

        levels = shard_map(
            levels_block, mesh=mesh,
            in_specs=P("data", None), out_specs=P("data"),
        )(closed)
        return deps, levels

    row_sharding = NamedSharding(mesh, P("data", "model"))
    ts_sharding = NamedSharding(mesh, P("data", None))
    vec_sharding = NamedSharding(mesh, P("data"))
    rep = NamedSharding(mesh, P(None, None))
    return jax.jit(step, in_shardings=(row_sharding, ts_sharding, vec_sharding, rep),
                   out_shardings=(NamedSharding(mesh, P("data", None)), vec_sharding))


@functools.lru_cache(maxsize=8)
def sharded_deps_resolve(mesh: Mesh):
    """Mesh-sharded twin of ops.kernels.deps_resolve -- THE production hot
    kernel, not a demo: arena rows sharded over 'data' (each device holds a
    block of the node's active set), key buckets over 'model' (the overlap
    contraction psums across it). The packed u32[B, cap/32] result comes
    back with its lane dimension sharded over 'data'; lane order equals row
    order because every data block's capacity is a multiple of 32.

    Contracts (enforced by ShardedBatchDepsResolver): cap % (32 * data) == 0
    and num_buckets % model == 0 -- both preserved by arena doubling."""
    from accord_tpu.ops.kernels import (_lex_before, _pack_bits,
                                        _witness_mask)

    def run(subj_of, subj_keys, subj_before, subj_kinds,
            act_bm, act_ts, act_kinds, act_valid, table):
        def part(sof, sk, sb, sknd, bm, ts, kinds, valid, tbl):
            # bm: [cap_local, K_local]; the subject CSR scatter restricted
            # to the LOCAL bucket slice so the contraction psums over
            # 'model'. Out-of-slice entries remap to col == k_local (OOB,
            # dropped); the guard also catches negative cols, which jax
            # would otherwise WRAP into the slice.
            b = sb.shape[0]
            k_local = bm.shape[1]
            base = jax.lax.axis_index("model") * k_local
            col = sk - base
            col = jnp.where((col >= 0) & (col < k_local), col, k_local)
            subj_bm = jnp.zeros((b, k_local), jnp.float32) \
                .at[sof, col].max(1.0, mode="drop").astype(jnp.bfloat16)
            partial = jax.lax.dot_general(
                subj_bm, bm.astype(jnp.bfloat16),
                (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
            overlap = jax.lax.psum(partial, "model") > 0.5
            witness = _witness_mask(tbl, sknd, kinds)
            before = _lex_before(ts[None, :, :], sb[:, None, :])
            return _pack_bits(overlap & witness & before & valid[None, :])

        return shard_map(
            part, mesh=mesh,
            in_specs=(P(None), P(None), P(None, None), P(None),
                      P("data", "model"), P("data", None), P("data"),
                      P("data"), P(None, None)),
            out_specs=P(None, "data"),
        )(subj_of, subj_keys, subj_before, subj_kinds,
          act_bm, act_ts, act_kinds, act_valid, table)

    rep2 = NamedSharding(mesh, P(None, None))
    rep1 = NamedSharding(mesh, P(None))
    return jax.jit(run, in_shardings=(
        rep1, rep1, rep2, rep1,
        NamedSharding(mesh, P("data", "model")),
        NamedSharding(mesh, P("data", None)),
        NamedSharding(mesh, P("data")), NamedSharding(mesh, P("data")),
        rep2), out_shardings=NamedSharding(mesh, P(None, "data")))


def _concat_lane_blocks(mesh: Mesh, blocks):
    """Concatenate per-store packed blocks along the lane axis. The blocks
    come out of the fused kernels sharded P(None, 'data'); on this jax
    version, concatenating along a 'data'-sharded axis on a 2D mesh with a
    >1 'model' axis miscompiles -- the model-replicated lanes are summed as
    if they were partial results, doubling every packed word. Resharding to
    fully replicated first makes the concat collective-free and correct
    (the blocks are a few KB, so the replication copy is noise)."""
    if len(blocks) == 1:
        return blocks[0]
    rep = NamedSharding(mesh, P(None, None))
    return jnp.concatenate([jax.device_put(blk, rep) for blk in blocks],
                           axis=1)


def _covered_buckets(iv_of, iv_start, iv_end, b, k_local, model):
    """The subject intervals' bucket-coverage bitmap, restricted to THIS
    'model' shard's bucket slice -> bf16[b, k_local]. Thin wrapper over the
    shared kernels.covered_buckets modular test (the single-device range
    kernel contracts over the same helper with base == 0): this shard covers
    global buckets [axis_index * k_local, (axis_index + 1) * k_local).
    Widths that overflow int32 go negative (true width < 2^32 always), so
    the helper's `wide` branch catches both them and genuinely-full
    intervals; coverage is a conservative superset either way (the host
    decode re-filters per real key)."""
    from accord_tpu.ops.kernels import covered_buckets
    base = jax.lax.axis_index("model") * k_local
    return covered_buckets(iv_of, iv_start, iv_end, b, k_local, base,
                           k_local * model)


@functools.lru_cache(maxsize=8)
def sharded_range_deps_resolve(mesh: Mesh):
    """Mesh-sharded twin of ops.kernels.range_deps_resolve. Range-arena rows
    shard over 'data' (the interval compares have no bucket dimension, so
    'model' lanes replicate the tiny subject CSR and each compute their data
    block). The key-side test CONTRACTS over 'model' buckets like
    sharded_deps_resolve: the subject intervals scatter into per-shard
    bucket coverage (_covered_buckets) and contract against the key bitmap
    [cap, K] sharded ('data', 'model') -- the same contraction the
    single-device kernel now runs, so no key-arena row lane is replicated
    across 'model'. Both packed outputs come back lane-sharded over 'data';
    lane order equals row order because rcap % (32 * data) == 0 and
    cap % (32 * data) == 0 (the resolver's capacity contracts, preserved by
    doubling). Bucket coverage is a conservative superset of the true key
    overlap; the host decode re-filters per real key, so single-device and
    sharded answers stay differentially identical."""
    from accord_tpu.ops.kernels import (_lex_before, _pack_bits,
                                        _witness_mask)
    model = mesh.shape["model"]

    def run(iv_of, iv_start, iv_end, subj_before, subj_kinds, subj_is_range,
            r_start, r_end, r_ts, r_kinds, r_valid,
            act_bm, k_ts, k_kinds, k_valid, table):
        def part(ivo, ivs, ive, sb, sknd, srng,
                 rs, re_, rts, rkd, rvl, bm, kts, kknd, kvl, tbl):
            b = sb.shape[0]
            rcap_l = rs.shape[0]
            hit_r = (ivs[:, None] < re_[None, :]) & (rs[None, :] < ive[:, None])
            any_r = jnp.zeros((b, rcap_l), jnp.int32) \
                .at[ivo].max(hit_r.astype(jnp.int32), mode="drop") > 0
            witness_r = _witness_mask(tbl, sknd, rkd)
            before_r = _lex_before(rts[None, :, :], sb[:, None, :])
            m_r = any_r & witness_r & before_r & rvl[None, :]
            cov = _covered_buckets(ivo, ivs, ive, b, bm.shape[1], model)
            partial = jax.lax.dot_general(
                cov, bm.astype(jnp.bfloat16),
                (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
            any_k = jax.lax.psum(partial, "model") > 0.5
            witness_k = _witness_mask(tbl, sknd, kknd)
            before_k = _lex_before(kts[None, :, :], sb[:, None, :])
            m_k = any_k & witness_k & before_k & kvl[None, :] & srng[:, None]
            return _pack_bits(m_r), _pack_bits(m_k)

        return shard_map(
            part, mesh=mesh,
            in_specs=(P(None), P(None), P(None), P(None, None), P(None),
                      P(None),
                      P("data"), P("data"), P("data", None), P("data"),
                      P("data"),
                      P("data", "model"), P("data", None), P("data"),
                      P("data"), P(None, None)),
            out_specs=(P(None, "data"), P(None, "data")),
        )(iv_of, iv_start, iv_end, subj_before, subj_kinds, subj_is_range,
          r_start, r_end, r_ts, r_kinds, r_valid,
          act_bm, k_ts, k_kinds, k_valid, table)

    rep2 = NamedSharding(mesh, P(None, None))
    rep1 = NamedSharding(mesh, P(None))
    d1 = NamedSharding(mesh, P("data"))
    d2 = NamedSharding(mesh, P("data", None))
    out = NamedSharding(mesh, P(None, "data"))
    return jax.jit(run, in_shardings=(
        rep1, rep1, rep1, rep2, rep1, rep1,
        d1, d1, d2, d1, d1,
        NamedSharding(mesh, P("data", "model")), d2, d1, d1,
        rep2), out_shardings=(out, out))


# per-store arena in_specs for shard_map'd resolve stages (key arenas:
# rows over 'data', buckets over 'model'; range arenas: rows over 'data')
_KEY_ARENA_SPEC = (P("data", "model"), P("data", None), P("data"), P("data"))
_RNG_ARENA_SPEC = (P("data"), P("data"), P("data", None), P("data"),
                   P("data"))


def _fused_key_resolve_blocks(nstores, sof, sk, sst, sb, sknd, sl, ars, tbl):
    """Per-shard LOCAL key-resolve packed blocks, one per store: the body
    shared by sharded_fused_deps_resolve and the sharded protocol
    megakernel (must run inside a shard_map over ('data', 'model')). The
    subject bitmap is built once per shard restricted to the local bucket
    slice; each arena block applies its store's slot mask and packs its own
    lane block."""
    from accord_tpu.ops.kernels import (_lex_before, _pack_bits,
                                        _witness_mask)
    b = sb.shape[0]
    k_local = ars[0][0].shape[1]
    base = jax.lax.axis_index("model") * k_local
    col = sk - base
    col = jnp.where((col >= 0) & (col < k_local), col, k_local)
    subj_bm = jnp.zeros((b, k_local), jnp.float32) \
        .at[sof, col].max(1.0, mode="drop").astype(jnp.bfloat16)
    outs = []
    for s in range(nstores):
        bm, ts, kinds, valid = ars[s]
        partial = jax.lax.dot_general(
            subj_bm, bm.astype(jnp.bfloat16),
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        overlap = jax.lax.psum(partial, "model") > 0.5
        witness = _witness_mask(tbl, sknd, kinds)
        before = _lex_before(ts[None, :, :], sb[:, None, :])
        mine = (sst == sl[s])[:, None]
        outs.append(_pack_bits(
            overlap & witness & before & valid[None, :] & mine))
    return outs


def _fused_range_resolve_blocks(nr, nk, model, ivo, ivs, ive, sst, sb, sknd,
                                srng, rsl, rars, ksl, kars, tbl):
    """Per-shard LOCAL range-resolve packed blocks -- (r-side list, k-side
    list), shared like _fused_key_resolve_blocks. NR range arenas answer
    the interval stab over their 'data' row blocks; NK key arenas contract
    the subject intervals' bucket coverage over 'model'."""
    from accord_tpu.ops.kernels import (_lex_before, _pack_bits,
                                        _witness_mask)
    b = sb.shape[0]
    routs = []
    for s in range(nr):
        rs, re_, rts, rkd, rvl = rars[s]
        rcap_l = rs.shape[0]
        hit_r = (ivs[:, None] < re_[None, :]) \
            & (rs[None, :] < ive[:, None])
        any_r = jnp.zeros((b, rcap_l), jnp.int32) \
            .at[ivo].max(hit_r.astype(jnp.int32), mode="drop") > 0
        witness_r = _witness_mask(tbl, sknd, rkd)
        before_r = _lex_before(rts[None, :, :], sb[:, None, :])
        mine = (sst == rsl[s])[:, None]
        routs.append(_pack_bits(
            any_r & witness_r & before_r & rvl[None, :] & mine))
    kouts = []
    if nk:
        cov = _covered_buckets(ivo, ivs, ive, b, kars[0][0].shape[1], model)
        for s in range(nk):
            bm, kts, kknd, kvl = kars[s]
            partial = jax.lax.dot_general(
                cov, bm.astype(jnp.bfloat16),
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            any_k = jax.lax.psum(partial, "model") > 0.5
            witness_k = _witness_mask(tbl, sknd, kknd)
            before_k = _lex_before(kts[None, :, :], sb[:, None, :])
            mine = (sst == ksl[s])[:, None] & srng[:, None]
            kouts.append(_pack_bits(
                any_k & witness_k & before_k & kvl[None, :] & mine))
    return routs, kouts


@functools.lru_cache(maxsize=32)
def sharded_fused_deps_resolve(mesh: Mesh, nstores: int):
    """Mesh-sharded twin of ops.kernels.fused_deps_resolve: one call
    resolves subjects against NSTORES arenas, each sharded like
    sharded_deps_resolve (rows over 'data', buckets over 'model'). The
    subject bitmap is built once per shard; each arena block applies its
    store's slot mask and packs its own lane block
    (_fused_key_resolve_blocks, shared with the sharded protocol
    megakernel), and the per-store blocks concatenate OUTSIDE the shard_map
    (inside, the 'data'-sharded lane axes would interleave across stores)
    -- and outside the jit, via _concat_lane_blocks (see its docstring for
    the sharded-axis concat miscompile it routes around). lru_cached by
    (mesh, store count) so same-width dispatches share one compiled
    kernel."""

    def run(subj_of, subj_keys, subj_store, subj_before, subj_kinds,
            slots, arenas, table):
        def part(sof, sk, sst, sb, sknd, sl, ars, tbl):
            return tuple(_fused_key_resolve_blocks(
                nstores, sof, sk, sst, sb, sknd, sl, ars, tbl))

        arena_specs = tuple(_KEY_ARENA_SPEC for _ in range(nstores))
        return shard_map(
            part, mesh=mesh,
            in_specs=(P(None), P(None), P(None), P(None, None), P(None),
                      P(None), arena_specs, P(None, None)),
            out_specs=tuple(P(None, "data") for _ in range(nstores)),
        )(subj_of, subj_keys, subj_store, subj_before, subj_kinds,
          slots, arenas, table)

    jitted = jax.jit(run)

    def call(subj_of, subj_keys, subj_store, subj_before, subj_kinds,
             slots, arenas, table):
        blocks = jitted(subj_of, subj_keys, subj_store, subj_before,
                        subj_kinds, slots, arenas, table)
        return _concat_lane_blocks(mesh, blocks)

    return call


@functools.lru_cache(maxsize=32)
def sharded_fused_range_deps_resolve(mesh: Mesh, nr: int, nk: int):
    """Mesh-sharded twin of ops.kernels.fused_range_deps_resolve: NR range
    arenas (interval stab, rows over 'data') and NK key arenas
    (bucket-contracted coverage test over 'model', like
    sharded_range_deps_resolve) answer one fused call; per-store blocks
    concatenate outside the shard_map and outside the jit via
    _concat_lane_blocks (see its docstring); the per-shard body is
    _fused_range_resolve_blocks, shared with the sharded protocol
    megakernel. Empty sides return a (b, 0) packed array the caller
    discards."""
    model = mesh.shape["model"]

    def run(iv_of, iv_start, iv_end, subj_store, subj_before, subj_kinds,
            subj_is_range, r_slots, rarenas, k_slots, karenas, table):
        def part(ivo, ivs, ive, sst, sb, sknd, srng,
                 rsl, rars, ksl, kars, tbl):
            routs, kouts = _fused_range_resolve_blocks(
                nr, nk, model, ivo, ivs, ive, sst, sb, sknd, srng,
                rsl, rars, ksl, kars, tbl)
            return tuple(routs) + tuple(kouts)

        rarena_specs = tuple(_RNG_ARENA_SPEC for _ in range(nr))
        karena_specs = tuple(_KEY_ARENA_SPEC for _ in range(nk))
        return shard_map(
            part, mesh=mesh,
            in_specs=(P(None), P(None), P(None), P(None), P(None, None),
                      P(None), P(None), P(None), rarena_specs, P(None),
                      karena_specs, P(None, None)),
            out_specs=tuple(P(None, "data") for _ in range(nr + nk)),
        )(iv_of, iv_start, iv_end, subj_store, subj_before, subj_kinds,
          subj_is_range, r_slots, rarenas, k_slots, karenas, table)

    jitted = jax.jit(run)

    def call(iv_of, iv_start, iv_end, subj_store, subj_before, subj_kinds,
             subj_is_range, r_slots, rarenas, k_slots, karenas, table):
        blocks = jitted(iv_of, iv_start, iv_end, subj_store, subj_before,
                        subj_kinds, subj_is_range, r_slots, rarenas,
                        k_slots, karenas, table)
        b = subj_before.shape[0]
        rpacked = _concat_lane_blocks(mesh, blocks[:nr]) if nr \
            else jnp.zeros((b, 0), jnp.uint32)
        kpacked = _concat_lane_blocks(mesh, blocks[nr:]) if nk \
            else jnp.zeros((b, 0), jnp.uint32)
        return rpacked, kpacked

    return call


def sharded_node_tick(mesh: Mesh, key_merge, range_merge, table):
    """Multi-chip twin of the node-lane cluster tick (ops/node_lane.py):
    evaluate a whole cluster's merged key/range deps-resolve dispatches on
    the mesh, sharding the node-major BLOCK axis over 'data' rows and
    reusing the existing 'model'-axis kid-table sharding. The merged
    node-lane inputs are exactly a fused cross-store call with more blocks
    and a node-qualified slot space, so this delegates to the lru-cached
    sharded fused kernels at the merge's block-count tier -- same math,
    same `_concat_lane_blocks` readback layout, so the engine's per-plan
    span demux is unchanged. Returns (packed, rpacked, kpacked), any of
    them None when that merge is absent."""
    packed = rpacked = kpacked = None
    if key_merge is not None and key_merge.blocks:
        kern = sharded_fused_deps_resolve(mesh, len(key_merge.blocks))
        packed = kern(
            jnp.asarray(key_merge.subj_of), jnp.asarray(key_merge.subj_keys),
            jnp.asarray(key_merge.subj_node), jnp.asarray(key_merge.sb),
            jnp.asarray(key_merge.sknd), jnp.asarray(key_merge.slots),
            key_merge.blocks, table)
    if range_merge is not None \
            and (range_merge.r_blocks or range_merge.k_blocks):
        kern = sharded_fused_range_deps_resolve(
            mesh, len(range_merge.r_blocks), len(range_merge.k_blocks))
        rpacked, kpacked = kern(
            jnp.asarray(range_merge.iv_of), jnp.asarray(range_merge.iv_s),
            jnp.asarray(range_merge.iv_e),
            jnp.asarray(range_merge.subj_node),
            jnp.asarray(range_merge.sb), jnp.asarray(range_merge.sknd),
            jnp.asarray(range_merge.srng), jnp.asarray(range_merge.r_slots),
            range_merge.r_blocks, jnp.asarray(range_merge.k_slots),
            range_merge.k_blocks, table)
    return packed, rpacked, kpacked


def _sharded_finalize_body(mesh: Mesh, packed, word_off, kid_rows,
                           slot_subj, slot_kid, subj_row, out_cap: int):
    """Mesh-sharded twin of ops.kernels._finalize_csr_body: the
    finalized-CSR COMPACTION distributed over 'data' word columns, shared
    by the standalone sharded_finalize_csr jit and the sharded protocol
    megakernel (which inlines it per canonically-sorted finalize spec).
    Each shard holds a
    contiguous block of every kid-table row mask and of the packed
    candidate words (P(None, 'data') -- the layout the sharded candidate
    kernels already emit), so the AND + self-bit clear + SWAR popcount all
    run on local slices and no device materializes the full
    (slots x cap) bit matrix:

      1. per-shard popcount -> counts_local i32[S];
      2. all_gather over 'data' -> the global per-slot counts (summed:
         indptr) AND each shard's exclusive prefix within its slot's
         segment (write base);
      3. each shard compacts ITS nonzero words at (segment base + lower
         shards' counts + local bit prefix) -- disjoint global positions
         by construction -- and emits its fragment as a 'data'-stacked
         lane block; the fragments sum-merge (disjoint positions, zeros
         elsewhere) outside the shard_map body, in the same jit.

    The device out-cap BOUND (the kid-table row-mask popcount riding back
    with each result) is additionally sharded over the 'model' axis: each
    model replica popcounts a contiguous slot block of the kid table and a
    psum over 'model' restores the replicated scalar, so the model lanes
    stop duplicating the full [slots x words] SWAR pass.

    Word order equals row order and shards partition words contiguously,
    so (indptr, dep_rows, bound, csum) is bit-identical to the
    single-device finalize_csr -- the csr_checksum integrity word is
    computed over the MERGED pair, after the fragment sum, so it folds
    exactly the arrays the harvest will read back (no fragment writes past
    the total, so dep_rows is 0 there, as the host's prefix fold needs).
    Overflow keeps the same contract (indptr[-1] > out_cap; the exact total
    comes from the gathered counts, never from the possibly-dropped
    scatters)."""
    from accord_tpu.ops.kernels import _popcount_u32
    data = mesh.shape["data"]
    model = mesh.shape["model"]

    b = packed.shape[0]
    kc, w = kid_rows.shape
    blk = jax.lax.dynamic_slice_in_dim(packed, word_off, w, axis=1)

    def part(blk_l, kid_l, ssub, skid, srow):
        wl = blk_l.shape[1]
        d = jax.lax.axis_index("data")
        base_w = d * wl
        s = ssub.shape[0]
        ok = (ssub >= 0) & (ssub < b) & (skid >= 0) & (skid < kc)
        kid_m = kid_l[jnp.clip(skid, 0, kc - 1)]
        if s % model == 0:
            # kid-table popcount sharded over 'model': each model
            # replica bounds a contiguous slot block (the nnz tiers
            # are 32-multiples, so the split is exact), psum restores
            # the model-replicated scalar the out_specs promise --
            # integer partial sums, so bit-identical to the full
            # reduction the single-device kernel computes
            mi = jax.lax.axis_index("model")
            sl = s // model
            skid_b = jax.lax.dynamic_slice_in_dim(skid, mi * sl, sl)
            ok_b = jax.lax.dynamic_slice_in_dim(ok, mi * sl, sl)
            kid_b = kid_l[jnp.clip(skid_b, 0, kc - 1)]
            bound_l = jax.lax.psum(jnp.sum(jnp.where(
                ok_b,
                jnp.sum(_popcount_u32(kid_b), axis=1, dtype=jnp.int32),
                0), dtype=jnp.int32), "model")
        else:
            bound_l = jnp.sum(jnp.where(
                ok,
                jnp.sum(_popcount_u32(kid_m), axis=1, dtype=jnp.int32),
                0), dtype=jnp.int32)
        so = jnp.clip(ssub, 0, b - 1)
        m = jnp.where(ok[:, None], blk_l[so] & kid_m, jnp.uint32(0))
        r = srow[so]
        widx = base_w + jnp.arange(wl, dtype=jnp.int32)
        selfbit = jnp.where(
            (r >= 0)[:, None] & (widx[None, :] == (r >> 5)[:, None]),
            (jnp.uint32(1) << (r & 31).astype(jnp.uint32))[:, None],
            jnp.uint32(0))
        m = m & ~selfbit
        pop = _popcount_u32(m)                            # i32[S, wl]
        counts_l = jnp.sum(pop, axis=1, dtype=jnp.int32)  # i32[S]
        counts_all = jax.lax.all_gather(counts_l, "data")  # i32[D, S]
        counts = jnp.sum(counts_all, axis=0)
        seg0 = jnp.cumsum(counts, dtype=jnp.int32) - counts
        # this shard's exclusive write base within each slot's segment
        prefix = jnp.sum(jnp.where(
            jnp.arange(data, dtype=jnp.int32)[:, None] < d,
            counts_all, 0), axis=0, dtype=jnp.int32)
        seg_base = seg0 + prefix
        # local word compaction: the scatter form kernels had until it
        # compacted by output position (tests/test_gather_compact.py keeps
        # it as the oracle), with shard-global bit offsets and row bases
        flat_pop = pop.reshape(-1)
        flat_val = m.reshape(-1)
        within_seg = jnp.cumsum(pop, axis=1, dtype=jnp.int32) - pop
        bit_off = (seg_base[:, None] + within_seg).reshape(-1)
        nz = flat_pop > 0
        slot = jnp.where(
            nz, jnp.cumsum(nz.astype(jnp.int32), dtype=jnp.int32) - 1,
            out_cap)
        src = jnp.zeros(out_cap, jnp.int32) \
            .at[slot].set(jnp.arange(s * wl, dtype=jnp.int32),
                          mode="drop")
        live = jnp.arange(out_cap, dtype=jnp.int32) \
            < jnp.sum(nz.astype(jnp.int32))
        cw_val = jnp.where(live, flat_val[src], jnp.uint32(0))
        cw_off = bit_off[src]
        cw_row = (base_w + src % wl) * 32
        bits = ((cw_val[:, None] >> jnp.arange(32, dtype=jnp.uint32))
                & 1).astype(jnp.int32)
        within = jnp.cumsum(bits, axis=1, dtype=jnp.int32) - bits
        pos = jnp.where((bits > 0) & live[:, None],
                        cw_off[:, None] + within, out_cap)
        rows = cw_row[:, None] + jnp.arange(32, dtype=jnp.int32)[None, :]
        frag = jnp.zeros(out_cap, jnp.int32) \
            .at[pos.reshape(-1)].set(rows.reshape(-1), mode="drop")
        return counts_l[None], frag[None], bound_l[None]

    counts_all, frags, bounds = shard_map(
        part, mesh=mesh,
        in_specs=(P(None, "data"), P(None, "data"), P(None), P(None),
                  P(None)),
        out_specs=(P("data", None), P("data", None), P("data")),
    )(blk, kid_rows, slot_subj, slot_kid, subj_row)
    counts = jnp.sum(counts_all, axis=0)
    indptr = jnp.concatenate(
        [jnp.zeros(1, jnp.int32), jnp.cumsum(counts, dtype=jnp.int32)])
    dep_rows = jnp.sum(frags, axis=0)
    bound = jnp.sum(bounds, dtype=jnp.int32)
    from accord_tpu.ops.kernels import csr_checksum
    return indptr, dep_rows, bound, csr_checksum(indptr, dep_rows)


@functools.lru_cache(maxsize=8)
def sharded_finalize_csr(mesh: Mesh):
    """Standalone jit over _sharded_finalize_body (the unfused dispatch
    the sharded resolver uses when the megakernel is off). lru_cached by
    mesh: every resolver on the mesh shares one compiled kernel per
    (shape, out_cap)."""

    def run(packed, word_off, kid_rows, slot_subj, slot_kid,
            subj_row, out_cap: int):
        return _sharded_finalize_body(mesh, packed, word_off, kid_rows,
                                      slot_subj, slot_kid, subj_row,
                                      out_cap)

    return jax.jit(run, static_argnames=("out_cap",))


# -- the sharded protocol megakernel ------------------------------------------

_SHARDED_TICK_FNS: dict = {}


def _sharded_tick_fn(mesh: Mesh, statics):
    """Build (or fetch) the one fused mesh program for a tick-signature
    multiset: a single jax.jit composing shard_map regions for the
    node-lane resolve, every finalize compaction, the cross-shard mailbox
    exchange, and the replicated cmd/quorum/repair stages -- one XLA
    executable, so the engine's launch ledger counts exactly one dispatch
    per cluster tick, like the single-device _protocol_tick_fn."""
    key = (mesh, statics)
    fn = _SHARDED_TICK_FNS.get(key)
    if fn is not None:
        return fn
    has_key, has_rng, fin_statics, cmd_promotes, qsize, has_mail, \
        n_repairs, exec_statics = statics
    from accord_tpu.ops import kernels as _k
    from accord_tpu.ops.mailbox import _sharded_mailbox_route_part
    data = mesh.shape["data"]
    rep = NamedSharding(mesh, P(None, None))

    def assemble(blocks):
        # replicate each store's P(None, 'data') lane block before the
        # lane-axis concat -- the in-jit twin of _concat_lane_blocks'
        # workaround for the sharded-axis concat miscompile
        blocks = [jax.lax.with_sharding_constraint(blk, rep)
                  for blk in blocks]
        return blocks[0] if len(blocks) == 1 \
            else jnp.concatenate(blocks, axis=1)

    def run(witness_table, key_in, rng_in, fin_in, cmd_in, q_in,
            mail_in, rep_in, exec_in):
        packed = ()
        rng_out = ()
        if has_key:
            sof, sk, sst, sb, sknd, sl, blocks = key_in
            nstores = len(blocks)

            def kpart(sof, sk, sst, sb, sknd, sl, ars, tbl):
                return tuple(_fused_key_resolve_blocks(
                    nstores, sof, sk, sst, sb, sknd, sl, ars, tbl))

            blks = shard_map(
                kpart, mesh=mesh,
                in_specs=(P(None), P(None), P(None), P(None, None),
                          P(None), P(None),
                          tuple(_KEY_ARENA_SPEC for _ in range(nstores)),
                          P(None, None)),
                out_specs=tuple(P(None, "data") for _ in range(nstores)),
            )(sof, sk, sst, sb, sknd, sl, blocks, witness_table)
            packed = assemble(list(blks))
        if has_rng:
            (iv_of, iv_s, iv_e, snode, sb, sknd, srng, r_slots, r_blocks,
             k_slots, k_blocks) = rng_in
            nr, nk = len(r_blocks), len(k_blocks)
            model = mesh.shape["model"]

            def rpart(ivo, ivs, ive, sst, sbx, skndx, srngx, rsl, rars,
                      ksl, kars, tbl):
                routs, kouts = _fused_range_resolve_blocks(
                    nr, nk, model, ivo, ivs, ive, sst, sbx, skndx, srngx,
                    rsl, rars, ksl, kars, tbl)
                return tuple(routs) + tuple(kouts)

            blks = shard_map(
                rpart, mesh=mesh,
                in_specs=(P(None), P(None), P(None), P(None),
                          P(None, None), P(None), P(None), P(None),
                          tuple(_RNG_ARENA_SPEC for _ in range(nr)),
                          P(None),
                          tuple(_KEY_ARENA_SPEC for _ in range(nk)),
                          P(None, None)),
                out_specs=tuple(P(None, "data") for _ in range(nr + nk)),
            )(iv_of, iv_s, iv_e, snode, sb, sknd, srng, r_slots, r_blocks,
              k_slots, k_blocks, witness_table)
            b = sb.shape[0]
            rp = assemble(list(blks[:nr])) if nr \
                else jnp.zeros((b, 0), jnp.uint32)
            kp = assemble(list(blks[nr:])) if nk \
                else jnp.zeros((b, 0), jnp.uint32)
            rng_out = (rp, kp)
        fin_outs = []
        for spec, args in zip(fin_statics, fin_in):
            kind = spec[0]
            if kind == "range":
                # the range arena is tiny (tens of rows): the interval
                # stab runs replicated, like the unfused sharded path
                (iv_of, iv_s, iv_e, ent_ok, f_sb, f_sknd,
                 (r_start, r_end, r_ts, r_kinds, r_valid)) = args
                fin_outs.append(_k._range_finalize_csr_body(
                    iv_of, iv_s, iv_e, ent_ok, f_sb, f_sknd,
                    r_start, r_end, r_ts, r_kinds, r_valid,
                    witness_table, spec[1]))
            else:
                _kk, rows, words, out_cap = spec
                (r0, w_lo, word_off, kid_rows, slot_subj, slot_kid,
                 subj_row) = args
                src = packed if kind == "key" else rng_out[1]
                blk = jax.lax.dynamic_slice(src, (r0, w_lo), (rows, words))
                fin_outs.append(_sharded_finalize_body(
                    mesh, blk, word_off, kid_rows, slot_subj, slot_kid,
                    subj_row, out_cap))
        cmd_outs = []
        for promote, args in zip(cmd_promotes, cmd_in):
            cmd_outs.append(_k._cmd_tick_body(*args, promote=promote))
        q_out = ()
        if qsize is not None:
            q_txn, q_ts, q_code, q_valid = q_in
            fast = q_valid & ((q_code & 7) == _k.CMD_OUT_SUCCESS) \
                & jnp.all(q_ts == q_txn, axis=1)
            same = jnp.all(q_txn[:, None, :] == q_txn[None, :, :], axis=2)
            votes = jnp.sum(same & fast[None, :], axis=1, dtype=jnp.int32)
            q_out = (fast, votes, fast & (votes >= qsize))
        mail_out = ()
        if has_mail:
            def mpart(*args):
                return _sharded_mailbox_route_part(data, "data", *args)

            mail_out = shard_map(
                mpart, mesh=mesh,
                in_specs=(P("data", None), P("data", None), P("data"),
                          P("data"), P("data"), P("data"), P("data"),
                          P("data"), P("data", None), P("data", None)),
                out_specs=(P("data", None), P("data", None),
                           P("data", None), P("data", None), P("data")),
            )(*mail_in)
        rep_outs = tuple(_k._cmd_repair_body(*rep_in[i])
                         for i in range(n_repairs))
        # exec arenas are host-owned replicated lanes (like cmd/quorum):
        # the frontier compaction runs as a plain body beside the sharded
        # stages -- same source of truth as the single-device exec block
        exec_outs = tuple(_k._frontier_compact_body(exec_in[i], oc)
                          for i, oc in enumerate(exec_statics))
        return (packed, rng_out, tuple(fin_outs), tuple(cmd_outs), q_out,
                mail_out, rep_outs, exec_outs)

    fn = jax.jit(run)
    _SHARDED_TICK_FNS[key] = fn
    return fn


def sharded_protocol_tick(mesh: Mesh, witness_table, key_in=None,
                          rng_in=None, fins=(), cmds=(), quorum=None,
                          quorum_size=1, mailbox=None, cmd_repairs=(),
                          execs=()):
    """Multi-chip twin of ops.kernels.protocol_tick: ONE fused mesh
    program per cluster tick. Same argument contract (see protocol_tick's
    docstring) with `mesh` prepended; key_in/rng_in are the node-lane
    merge inputs sharded_node_tick would dispatch, fins the same finalize
    specs (key/rkey spans compact through _sharded_finalize_body's
    word-column sharding), and `mailbox` a MailboxPlane staged with
    shards == mesh.shape['data'] so the routing stage's all_to_all lands
    cross-shard payloads. Finalize specs canonically sort by static
    signature via kernels._fin_split -- the compile cache keys on the
    tick-signature multiset exactly as the single-device path does."""
    from accord_tpu.ops.kernels import _fin_split, _fin_unsort
    fin_statics, fin_traced, order = _fin_split(fins)
    cmd_statics = tuple(bool(c[-1]) for c in cmds)
    cmd_traced = tuple(tuple(c[:-1]) for c in cmds)
    exec_statics = tuple(int(oc) for (_pl, oc) in execs)
    exec_traced = tuple(tuple(tuple(p) for p in pl) for (pl, _oc) in execs)
    statics = (key_in is not None, rng_in is not None, tuple(fin_statics),
               cmd_statics, int(quorum_size) if quorum is not None else None,
               mailbox is not None, len(cmd_repairs), exec_statics)
    fn = _sharded_tick_fn(mesh, statics)
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


def sharded_protocol_tick_cache_sizes() -> int:
    """Total compiled sharded_protocol_tick variants across every
    (mesh, static signature) -- folded into kernels.jit_cache_sizes."""
    return sum(f._cache_size() for f in _SHARDED_TICK_FNS.values())


def warmup_sharded(mesh: Mesh, num_buckets: int = 256, cap: int = 4096,
                   batch_tiers: Tuple[int, ...] = (8, 64, 128),
                   nnz_tiers: Optional[Tuple[int, ...]] = None,
                   range_cap: Optional[int] = None,
                   store_tiers: Tuple[int, ...] = (1, 2),
                   out_tiers: Tuple[int, ...] = (),
                   kid_cap: int = 4096,
                   cmd_caps: Tuple[int, ...] = (),
                   cmd_key_caps: Tuple[int, ...] = (1024,),
                   cmd_kpad: int = 4,
                   cmd_op_tiers: Optional[Tuple[int, ...]] = None,
                   cmd_promote_modes: Tuple[bool, ...] = (False,),
                   node_tiers: Tuple[int, ...] = (),
                   node_batch_tiers: Optional[Tuple[int, ...]] = None,
                   mega_quorum_sizes: Tuple[int, ...] = (),
                   mega_lane_tiers: Optional[Tuple[int, ...]] = None,
                   exec_caps: Tuple[int, ...] = (),
                   exec_tiers: Tuple[int, ...] = (),
                   recovery_tiers: Tuple[int, ...] = ()) -> None:
    """Pre-compile the sharded hot kernels' (batch tier, nnz tier, store
    tier) jit cross product (the sharded twin of ops.resolver.warmup; same
    padding ladders the overlapped pipeline dispatches). Store tiers >= 2
    warm the fused cross-store kernels; single-group dispatches reuse the
    plain kernels. `out_tiers` additionally warms the sharded finalize
    compaction over (batch x nnz x out_cap) at `kid_cap` -- with the
    resolver's OutCapTiers hysteresis pinning tiers, this covers every
    finalize shape a steady-state burn dispatches. One call covers every
    ShardedBatchDepsResolver on the same mesh + (num_buckets, cap,
    range_cap) -- the kernel builders are lru_cached by (mesh, width) and
    jit caches by shape. `cmd_caps` (opt-in) folds in the device
    coordination plane's warmup (cmd_tick + its lane scatters) -- the cmd
    arena is store-local and replicated, so the single-device variants are
    the ones a sharded deployment dispatches too. `node_tiers` (opt-in)
    warms the cluster-tick node-lane path (`sharded_node_tick` delegates to
    the fused kernels at the merge's block-count tier) across every
    (block tier x merged-row tier x nnz tier) -- the sharded twin of
    ops.resolver.warmup's node_tiers. `mega_quorum_sizes` (opt-in) warms
    the sharded protocol megakernel's quorum-count stage across the lane
    tiers a megakernel burn pads PreAccept spans to -- the sharded twin of
    resolver.warmup's mega block. `exec_tiers` / `recovery_tiers` (opt-in)
    warm the compacted exec-frontier and recovery-scan blocks through the
    sharded megakernel's exec-only variant (the exec arenas are host-owned
    replicated lanes, so the bodies match the single-device kernels bit for
    bit) across (`exec_caps` x plane count x out_cap) and (`cmd_caps` x
    out_cap) respectively."""
    from accord_tpu.ops.encoding import WITNESS_TABLE
    from accord_tpu.ops.kernels import NNZ_TIERS
    if nnz_tiers is None:
        nnz_tiers = NNZ_TIERS
    if range_cap is None:
        range_cap = max(64, 32 * mesh.shape["data"])
    kern = sharded_deps_resolve(mesh)
    rkern = sharded_range_deps_resolve(mesh)
    bm = jnp.zeros((cap, num_buckets), jnp.float32)
    ts = jnp.zeros((cap, 3), jnp.int32)
    kinds = jnp.zeros(cap, jnp.int32)
    valid = jnp.zeros(cap, bool)
    rs = jnp.zeros(range_cap, jnp.int32)
    re_ = jnp.zeros(range_cap, jnp.int32)
    rts = jnp.zeros((range_cap, 3), jnp.int32)
    rkd = jnp.zeros(range_cap, jnp.int32)
    rvl = jnp.zeros(range_cap, bool)
    table = jnp.asarray(WITNESS_TABLE)
    out = None
    for b in batch_tiers:
        sb = jnp.zeros((b, 3), jnp.int32)
        sknd = jnp.zeros(b, jnp.int32)
        srng = jnp.zeros(b, bool)
        sst = jnp.zeros(b, jnp.int32)
        for z in nnz_tiers:
            of = jnp.full(z, b, jnp.int32)
            zz = jnp.zeros(z, jnp.int32)
            out = kern(of, zz, sb, sknd, bm, ts, kinds, valid, table)
            out = rkern(of, zz, zz, sb, sknd, srng,
                        rs, re_, rts, rkd, rvl,
                        bm, ts, kinds, valid, table)
            for s in store_tiers:
                if s < 2:
                    continue  # single group runs the plain kernels
                fkern = sharded_fused_deps_resolve(mesh, s)
                frkern = sharded_fused_range_deps_resolve(mesh, s, s)
                slots = jnp.arange(s, dtype=jnp.int32)
                arenas = tuple((bm, ts, kinds, valid) for _ in range(s))
                out = fkern(of, zz, sst, sb, sknd, slots, arenas, table)
                rarenas = tuple((rs, re_, rts, rkd, rvl) for _ in range(s))
                out = frkern(of, zz, zz, sst, sb, sknd, srng,
                             slots, rarenas, slots, arenas, table)
    if out_tiers:
        fin = sharded_finalize_csr(mesh)
        w = cap // 32
        kid_rows = jnp.zeros((kid_cap, w), jnp.uint32)
        zero_off = jnp.asarray(0, jnp.int32)
        for b in batch_tiers:
            srow = jnp.full(b, -1, jnp.int32)
            # the live packed words arrive lane-sharded out of the sharded
            # candidate kernels; warm with the same committed sharding or
            # the jit lowers a second, single-device entry
            packed = jax.device_put(
                jnp.zeros((b, w), jnp.uint32),
                NamedSharding(mesh, P(None, "data")))
            for z in nnz_tiers:
                subj = jnp.full(z, b, jnp.int32)
                kidx = jnp.full(z, kid_cap, jnp.int32)
                for oc in out_tiers:
                    out = fin(packed, zero_off, kid_rows, subj, kidx,
                              srow, out_cap=oc)
    if cmd_caps:
        from accord_tpu.ops.cmd_plane import (CMD_OP_TIERS,
                                              warmup_cmd_plane)
        warmup_cmd_plane(
            caps=cmd_caps, key_caps=cmd_key_caps, kpad=cmd_kpad,
            op_tiers=(CMD_OP_TIERS if cmd_op_tiers is None
                      else cmd_op_tiers),
            promote_modes=cmd_promote_modes)
    if node_tiers:
        from accord_tpu.ops.node_lane import NODE_SUBJECT_TIERS
        nb_tiers = (tuple(node_batch_tiers) if node_batch_tiers is not None
                    else NODE_SUBJECT_TIERS[:2])
        for nblk in node_tiers:
            fkern = sharded_fused_deps_resolve(mesh, nblk)
            frkern = sharded_fused_range_deps_resolve(mesh, nblk, nblk)
            slots = jnp.arange(nblk, dtype=jnp.int32)
            arenas = tuple((bm, ts, kinds, valid) for _ in range(nblk))
            rarenas = tuple((rs, re_, rts, rkd, rvl) for _ in range(nblk))
            for b in nb_tiers:
                sb = jnp.zeros((b, 3), jnp.int32)
                sknd = jnp.zeros(b, jnp.int32)
                srng = jnp.zeros(b, bool)
                snode = jnp.zeros(b, jnp.int32)
                for z in nnz_tiers:
                    of = jnp.full(z, b, jnp.int32)
                    zz = jnp.zeros(z, jnp.int32)
                    out = fkern(of, zz, snode, sb, sknd, slots, arenas,
                                table)
                    out = frkern(of, zz, zz, snode, sb, sknd, srng, slots,
                                 rarenas, slots, arenas, table)
    if mega_quorum_sizes:
        from accord_tpu.ops.tiers import MEGA_LANE_TIERS
        lt = (tuple(mega_lane_tiers) if mega_lane_tiers is not None
              else MEGA_LANE_TIERS[:2])
        for qs in mega_quorum_sizes:
            for t in lt:
                out = sharded_protocol_tick(
                    mesh, table,
                    quorum=(jnp.zeros((t, 3), jnp.int32),
                            jnp.zeros((t, 3), jnp.int32),
                            jnp.zeros(t, jnp.int32),
                            jnp.zeros(t, bool)),
                    quorum_size=qs)[4][2]
    if exec_tiers:
        from accord_tpu.ops.kernels import frontier_compact
        neg = np.iinfo(np.int32).min
        for ecap in (tuple(exec_caps) or (1024,)):
            plane = (jnp.zeros((ecap, ecap), bool),
                     jnp.full((ecap, 3), neg, jnp.int32),
                     jnp.zeros(ecap, bool), jnp.zeros(ecap, bool),
                     jnp.zeros(ecap, bool))
            counts = (1,) + tuple(s for s in store_tiers if s > 1)
            for n in counts:
                planes = tuple(plane for _ in range(n))
                for oc in exec_tiers:
                    # both homes: the standalone coordinator dispatch and
                    # the engine's exec-only fused flush on this mesh
                    out = frontier_compact(planes, out_cap=oc)[0]
                    out = sharded_protocol_tick(
                        mesh, table, execs=((planes, oc),))[7][0][0]
    if recovery_tiers:
        # the cmd arena is store-local and replicated: sharded deployments
        # dispatch the same single-device recovery_scan
        from accord_tpu.ops.kernels import recovery_scan
        for ccap in (tuple(cmd_caps) or (1024,)):
            st = jnp.zeros(ccap, jnp.int32)
            tm = jnp.zeros(ccap, jnp.int32)
            for oc in recovery_tiers:
                out = recovery_scan(st, tm, np.int32(0), np.int32(0),
                                    out_cap=oc)[0]
    if out is not None:
        jax.block_until_ready(out)


def example_batch(n: int = 64, k: int = 256, seed: int = 0):
    """Deterministic example inputs for compile checks and dry runs."""
    rng = np.random.default_rng(seed)
    bitmaps = (rng.random((n, k)) < 0.05).astype(np.float32)
    hlcs = np.sort(rng.integers(0, 100_000, n))
    ts = np.stack([np.zeros(n, np.int32), hlcs.astype(np.int32),
                   rng.integers(0, 1 << 16, n).astype(np.int32)], axis=1)
    kinds = rng.integers(0, 2, n).astype(np.int32)  # READ/WRITE mix
    from accord_tpu.ops.encoding import WITNESS_TABLE
    return bitmaps, ts, kinds, WITNESS_TABLE.copy()


def example_resolve_batch(cap: int = 512, k: int = 256, b: int = 16,
                          nnz: int = 64, seed: int = 0):
    """Deterministic random inputs in deps_resolve's exact signature shape
    (CSR subject entries padded with out-of-bounds row B, 3-lane int32
    timestamps, arena lanes) -- shared by the dry-run and the
    sharded-vs-single differential tests so the invariants live in one
    place."""
    from accord_tpu.ops.encoding import WITNESS_TABLE
    rng = np.random.default_rng(seed)
    live = rng.random(nnz) < 0.6
    subj_of = np.where(live, rng.integers(0, b, nnz), b).astype(np.int32)
    subj_keys = rng.integers(0, k, nnz).astype(np.int32)
    sb = np.stack([np.zeros(b, np.int32),
                   rng.integers(1000, 100_000, b).astype(np.int32),
                   rng.integers(0, 100, b).astype(np.int32)], 1)
    sknd = rng.integers(0, 5, b).astype(np.int32)
    act_bm = (rng.random((cap, k)) < 0.05).astype(np.float32)
    act_ts = np.stack([np.zeros(cap, np.int32),
                       rng.integers(0, 90_000, cap).astype(np.int32),
                       rng.integers(0, 100, cap).astype(np.int32)], 1)
    act_kinds = rng.integers(0, 5, cap).astype(np.int32)
    act_valid = rng.random(cap) < 0.9
    return (subj_of, subj_keys, sb, sknd, act_bm, act_ts, act_kinds,
            act_valid, WITNESS_TABLE.copy())
