"""Device mailbox arena: replica traffic as a routing stage in protocol_tick.

Each node lane owns a bounded SoA ring of `depth` slots x `words` int32
payload words in one flat arena of shape [(n+1)*depth, words] (row 0..depth-1
is the unused node-0 lane, matching the 1-based node-id convention of every
other lane family). A parallel meta arena [(n+1)*depth, 3] carries
(src, kind, seq) per slot -- kind is the interned message-class id, seq the
message's queue ticket, so delivery can verify provenance and ordering.

Message flow per cluster tick:

  emit   -- DeviceMessageNetwork.mailbox_flush() packs every in-flight
            payload (sim/wire bytes, word 0 = byte length header) into emit
            lanes padded to a MEGA_LANE_TIERS tier, allocating one slot in
            the destination's ring (deterministic lowest-free-first order);
  scatter -- _mailbox_route_body, fused into ops/kernels.protocol_tick,
            lands each kept emit at row dst*depth+slot unless the uploaded
            partition mask cuts the (src, dst) link, and gathers the landed
            words + meta straight back so the host can verify without
            copying the whole arena;
  drain  -- next deliveries read the device copy via read_landed(), compare
            it against the staged host bytes, and fall back to the host
            copy on any mismatch (partition epoch races, injected faults,
            overflow spills) -- the device path degrades, never diverges.

Overflow is graceful by design: an emit whose payload exceeds the slot width
or whose destination ring is full simply keeps its host bytes and bumps
`mailbox_overflow_spills`; tests/test_message_plane.py asserts that counter
stays zero at the default depth.

Sharded meshes (`shards > 1`): the node lanes pad up so shard boundaries
fall on node boundaries (node v lives on shard v // npsh), the arena and
the partition mask both shard node-major over the mesh's 'data' axis, and
emit lanes stage GROUPED by (src shard, dst shard) -- segment (s, t) of
the flat lane arrays holds the lanes shard s emits toward shard t, so the
fused routing stage's `lax.all_to_all` over 'data' delivers every
cross-shard payload into its destination shard's rings in one collective
(`_sharded_mailbox_route_part`, composed into the sharded protocol
megakernel by parallel/mesh.sharded_protocol_tick). shards == 1 degrades
to the exact single-device layout bit for bit.
"""
from __future__ import annotations

from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from accord_tpu.ops.tiers import mega_lane_tier


def pack_words(payload: bytes, width: int) -> Optional[np.ndarray]:
    """Encode payload bytes as [width] int32: word 0 the byte length, the
    rest the zero-padded little-words of the payload. None when the payload
    cannot fit (caller spills to the host path)."""
    if len(payload) > 4 * (width - 1):
        return None
    w = np.zeros(width, np.int32)
    w[0] = len(payload)
    if payload:
        buf = payload + b"\0" * (-len(payload) % 4)
        arr = np.frombuffer(buf, np.int32)
        w[1:1 + arr.size] = arr
    return w


def unpack_words(w: np.ndarray) -> bytes:
    """Inverse of pack_words: header-length bytes out of the word lanes."""
    n = int(w[0])
    return np.ascontiguousarray(w[1:1 + (n + 3) // 4],
                                np.int32).tobytes()[:n]


def _mailbox_route_body(arena, meta, e_src, e_dst, e_slot, e_keep,
                        e_kind, e_seq, e_words, part):
    """The fused routing stage: masked scatter of the tick's emits into
    destination rings plus a gather-back of what actually landed.

    arena  i32[(n+1)*depth, words]  payload rings (row = dst*depth + slot)
    meta   i32[(n+1)*depth, 3]      (src, kind, seq) per slot
    e_*    emit lanes, padded to a MEGA_LANE_TIERS tier (keep=False pads)
    part   bool[n+1, n+1]           True cuts the directed (src, dst) link

    Returns (arena, meta, landed_words, landed_meta, land): non-landing
    emits scatter to an out-of-range row (mode="drop"), and the gather-back
    lets the host verify each landed message without reading the arena.
    """
    rows = arena.shape[0]
    depth = rows // part.shape[0]
    land = e_keep & ~part[e_src, e_dst]
    flat = jnp.where(land, e_dst * depth + e_slot, rows)
    arena = arena.at[flat].set(e_words, mode="drop")
    meta = meta.at[flat].set(
        jnp.stack([e_src, e_kind, e_seq], axis=1), mode="drop")
    back = jnp.minimum(flat, rows - 1)
    return arena, meta, arena[back], meta[back], land


def _sharded_mailbox_route_part(shards, axis, arena_l, meta_l, e_src, e_dst,
                                e_slot, e_keep, e_kind, e_seq, e_words,
                                part_l):
    """Per-shard body of the cross-shard mailbox routing stage, run inside
    a shard_map over the mesh's `axis` ('data') by the sharded protocol
    megakernel.

    arena_l  i32[npsh*depth, words]  THIS shard's node rings (node-major)
    meta_l   i32[npsh*depth, 3]      (src, kind, seq) per local slot
    part_l   bool[npsh, rows_nodes]  partition rows for this shard's nodes
    e_*      this shard's SRC-grouped emit lanes, flat [shards*bcap]:
             segment t holds the lanes destined to shard t (keep=False pads)

    The land decision runs on the SOURCE shard (it owns the partition-mask
    rows for its src nodes), then every lane field -- land flag and payload
    words included -- rides one tiled `lax.all_to_all` over `axis`: segment
    t of each source shard's lanes lands as segment s on destination shard
    t, so after the exchange this shard holds exactly the lanes addressed
    to ITS rings, in (src shard, stage order) order. The local scatter and
    the verify gather-back then mirror _mailbox_route_body on the local row
    frame; the returned landed block stacks receiver-major, giving the
    host's per-entry return position (dst_shard*shards + src_shard)*bcap+j.
    shards == 1 is the degenerate identity exchange: same scatter, same
    landed order as the single-device body."""
    rows_l = arena_l.shape[0]
    npsh = part_l.shape[0]
    depth = rows_l // npsh
    d = jax.lax.axis_index(axis)
    src_loc = jnp.clip(e_src - d * npsh, 0, npsh - 1)
    land = e_keep & ~part_l[src_loc, e_dst]

    def xch(x):
        return jax.lax.all_to_all(x, axis, 0, 0, tiled=True)

    r_src, r_dst, r_slot = xch(e_src), xch(e_dst), xch(e_slot)
    r_kind, r_seq = xch(e_kind), xch(e_seq)
    r_words, r_land = xch(e_words), xch(land)
    dst_loc = r_dst - d * npsh
    flat = jnp.where(r_land & (dst_loc >= 0) & (dst_loc < npsh),
                     dst_loc * depth + r_slot, rows_l)
    arena_l = arena_l.at[flat].set(r_words, mode="drop")
    meta_l = meta_l.at[flat].set(
        jnp.stack([r_src, r_kind, r_seq], axis=1), mode="drop")
    back = jnp.minimum(flat, rows_l - 1)
    return arena_l, meta_l, arena_l[back], meta_l[back], r_land


class _Batch:
    """One flush's worth of landed device outputs, materialized host-side
    lazily (one transfer per launch, not per message). Entries reference
    their batch through slot tuples; the batch is garbage once the last of
    them delivers -- no explicit retirement needed."""

    __slots__ = ("outs", "host")

    def __init__(self):
        self.outs = None   # (landed, landed_meta, land) device arrays
        self.host = None   # same, as numpy, on first read


class MailboxPlane:
    """Host-side manager of the device mailbox arena: slot allocation per
    destination ring, emit-lane staging, partition-mask epochs, and the
    verify-on-read landing buffers."""

    def __init__(self, num_nodes: int, depth: int = 64, words: int = 384,
                 shards: int = 1):
        self.n = int(num_nodes)
        self.depth = int(depth)
        self.words = int(words)
        # shards > 1: pad the node-lane count so shard boundaries fall on
        # node boundaries (node v -> shard v // npsh); shards == 1 keeps
        # rows_nodes == n + 1, the exact single-device layout
        self.shards = max(int(shards), 1)
        self.npsh = -(-(self.n + 1) // self.shards)
        self.rows_nodes = self.npsh * self.shards
        self.arena = None       # device arrays, created on first stage
        self.meta = None
        self.part = None        # device partition mask for current epoch
        self.link_version: Optional[int] = None
        self._free: Dict[int, List[int]] = {}
        self._launched: Optional[_Batch] = None  # staged, awaiting adopt
        self.c: Dict[str, int] = {
            "mailbox_depth_high_water": 0,
            "mailbox_overflow_spills": 0,
            "mailbox_bytes_staged": 0,
            "mailbox_partition_epochs": 0,
        }

    # -- epoch config --------------------------------------------------------
    def set_partitions(self, partitioned, version: int) -> None:
        mask = np.zeros((self.rows_nodes, self.rows_nodes), bool)
        for pair in partitioned:
            a, b = tuple(pair)
            mask[a, b] = mask[b, a] = True
        self.part = jnp.asarray(mask)
        self.link_version = version
        self.c["mailbox_partition_epochs"] += 1

    # -- staging -------------------------------------------------------------
    def stage_batch(self, entries):
        """Allocate a destination slot per entry (lowest-free-first, so the
        order is deterministic), pack payloads into emit lanes, and return
        the kernel-ready mailbox input tuple -- or None when every entry
        spilled. Entries that cannot be slotted keep slot=None and deliver
        from their host bytes (counted as overflow spills)."""
        staged = []
        for e in entries:
            w = pack_words(e.payload, self.words)
            free = self._free.get(e.dst)
            if free is None:
                free = self._free[e.dst] = list(range(self.depth - 1, -1, -1))
            if w is None or not free:
                self.c["mailbox_overflow_spills"] += 1
                continue
            idx = free.pop()
            occupancy = self.depth - len(free)
            if occupancy > self.c["mailbox_depth_high_water"]:
                self.c["mailbox_depth_high_water"] = occupancy
            staged.append((e, idx, w))
        if not staged:
            return None
        if self.arena is None:
            rows = self.rows_nodes * self.depth
            self.arena = jnp.zeros((rows, self.words), jnp.int32)
            self.meta = jnp.zeros((rows, 3), jnp.int32)
        if self.part is None:
            self.set_partitions((), self.link_version or 0)
        # lanes stage grouped by (src shard, dst shard): segment (s, t) of
        # the flat arrays holds shard s's emits toward shard t, so the
        # sharded route's all_to_all delivers each segment whole. With
        # shards == 1 there is one group and this is exactly the old flat
        # staging-order layout.
        S, npsh = self.shards, self.npsh
        groups: Dict[tuple, list] = {}
        for ent in staged:
            e = ent[0]
            groups.setdefault((e.src // npsh, e.dst // npsh), []).append(ent)
        bcap = mega_lane_tier(max(len(g) for g in groups.values()))
        cap = S * S * bcap
        e_src = np.zeros(cap, np.int32)
        e_dst = np.zeros(cap, np.int32)
        e_slot = np.zeros(cap, np.int32)
        e_keep = np.zeros(cap, bool)
        e_kind = np.zeros(cap, np.int32)
        e_seq = np.zeros(cap, np.int32)
        e_words = np.zeros((cap, self.words), np.int32)
        batch = _Batch()
        for (s, t), ents in groups.items():
            for j, (e, idx, w) in enumerate(ents):
                pos = (s * S + t) * bcap + j
                # the landed block comes back receiver-major (identity for
                # shards == 1): the entry's return position swaps s and t
                e.slot = (batch, (t * S + s) * bcap + j, e.dst, idx)
                e_src[pos] = e.src
                e_dst[pos] = e.dst
                e_slot[pos] = idx
                e_keep[pos] = True
                e_kind[pos] = e.kind
                e_seq[pos] = e.ticket & 0x7FFFFFFF
                e_words[pos] = w
                self.c["mailbox_bytes_staged"] += len(e.payload)
        self._launched = batch
        return (self.arena, self.meta, e_src, e_dst, e_slot, e_keep,
                e_kind, e_seq, e_words, self.part)

    def adopt(self, outs) -> None:
        """Take the routing stage's outputs for the batch staged by the
        matching stage_batch call: new arena/meta device state plus the
        landed gather the deliveries will verify against."""
        arena, meta, landed, landed_meta, land = outs
        self.arena = arena
        self.meta = meta
        if self._launched is not None:
            self._launched.outs = (landed, landed_meta, land)
            self._launched = None

    # -- delivery ------------------------------------------------------------
    def read_landed(self, entry) -> Optional[bytes]:
        """The device-routed copy of an entry's payload, or None when it
        never landed (partition mask, not yet launched) -- the caller then
        delivers the retained host bytes."""
        batch, pos, _dst, _idx = entry.slot
        if batch.host is None:
            if batch.outs is None:
                return None  # staged but its launch never adopted
            landed, landed_meta, land = batch.outs
            batch.host = (np.asarray(landed), np.asarray(landed_meta),
                          np.asarray(land))
            batch.outs = None
        words, meta, land = batch.host
        if not bool(land[pos]):
            return None
        if int(meta[pos, 0]) != entry.src or int(meta[pos, 1]) != entry.kind \
                or int(meta[pos, 2]) != (entry.ticket & 0x7FFFFFFF):
            return None
        w = words[pos]
        from accord_tpu.ops import fault_plane as _fp
        if _fp.ACTIVE is not None:
            w = np.array(w)  # corrupt a local copy, never the batch buffer
            if not _fp.ACTIVE.corrupt_mailbox(w):
                w = words[pos]
        return unpack_words(w)

    def release(self, slot) -> None:
        """Free a delivered entry's ring slot (LIFO reuse keeps allocation
        deterministic)."""
        _batch, _pos, dst, idx = slot
        self._free[dst].append(idx)

    def counters(self) -> Dict[str, int]:
        return dict(self.c)
