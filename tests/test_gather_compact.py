"""The compaction that gathers by output position (`_packed_segment_compact`)
against the scatter forms it replaced, kept here verbatim as plain oracles.

`scatter_packed_segment_compact` is `_packed_segment_compact` and
`scatter_segment_compact` is `_segment_compact` as `ops/kernels.py` had them
while every compacted word was expanded to 32 bit candidates and every word
of the slot matrix was a candidate of its own (`out_cap x 32` and `S x W`
scattered updates a call, nearly all dropped). The new body has to return the
same `indptr` and the same `dep_rows`, every element of it: the rows in
(segment-major, row-ascending) order, zeros beyond `indptr[-1]`, the first
`out_cap` rows and the exact `indptr` where the total overflows.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from accord_tpu.ops.kernels import (_pack_bits, _packed_segment_compact,
                                    _popcount_u32)


def scatter_segment_compact(hits, out_cap: int):
    s, n = hits.shape
    counts = jnp.sum(hits, axis=1, dtype=jnp.int32)
    indptr = jnp.concatenate(
        [jnp.zeros(1, jnp.int32), jnp.cumsum(counts, dtype=jnp.int32)])
    within = jnp.cumsum(hits, axis=1, dtype=jnp.int32) - hits
    pos = jnp.where(hits > 0, indptr[:-1][:, None] + within, out_cap)
    col = jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32)[None, :], (s, n))
    dep_rows = jnp.zeros(out_cap, jnp.int32) \
        .at[pos.reshape(-1)].set(col.reshape(-1), mode="drop")
    return indptr, dep_rows


def scatter_packed_segment_compact(m, out_cap: int):
    s, w = m.shape
    pop = _popcount_u32(m)                                # i32[S, W]
    counts = jnp.sum(pop, axis=1, dtype=jnp.int32)
    indptr = jnp.concatenate(
        [jnp.zeros(1, jnp.int32), jnp.cumsum(counts, dtype=jnp.int32)])
    flat_pop = pop.reshape(-1)
    flat_val = m.reshape(-1)
    bit_off = jnp.cumsum(flat_pop, dtype=jnp.int32) - flat_pop
    nz = flat_pop > 0
    slot = jnp.where(
        nz, jnp.cumsum(nz.astype(jnp.int32), dtype=jnp.int32) - 1,
        out_cap)
    src = jnp.zeros(out_cap, jnp.int32) \
        .at[slot].set(jnp.arange(s * w, dtype=jnp.int32), mode="drop")
    live = jnp.arange(out_cap, dtype=jnp.int32) \
        < jnp.sum(nz.astype(jnp.int32))
    cw_val = jnp.where(live, flat_val[src], jnp.uint32(0))
    cw_off = bit_off[src]
    cw_row = (src % w) * 32
    bits = ((cw_val[:, None] >> jnp.arange(32, dtype=jnp.uint32)) & 1) \
        .astype(jnp.int32)                                # [out_cap, 32]
    within = jnp.cumsum(bits, axis=1, dtype=jnp.int32) - bits
    pos = jnp.where((bits > 0) & live[:, None],
                    cw_off[:, None] + within, out_cap)
    rows = cw_row[:, None] + jnp.arange(32, dtype=jnp.int32)[None, :]
    dep_rows = jnp.zeros(out_cap, jnp.int32) \
        .at[pos.reshape(-1)].set(rows.reshape(-1), mode="drop")
    return indptr, dep_rows


# -- matrices ------------------------------------------------------------------

def _with_bits(s, w, bits):
    """u32[s, w] with exactly the given flat bit positions set."""
    m = np.zeros(s * w, np.uint32)
    bits = np.asarray(bits, np.int64)
    np.bitwise_or.at(m, bits >> 5, np.uint32(1) << (bits & 31).astype(np.uint32))
    return m.reshape(s, w)


def _random_total(s, w, total, seed):
    rng = np.random.default_rng(seed)
    return _with_bits(s, w, rng.choice(s * w * 32, total, replace=False))


def _random_density(s, w, density, seed):
    rng = np.random.default_rng(seed)
    return _with_bits(s, w, np.flatnonzero(rng.random(s * w * 32) < density))


def _adversarial(s, w, out_cap):
    """name -> matrix, at one shape (one compile a shape a form)."""
    n = s * w * 32
    yield "empty", np.zeros((s, w), np.uint32)
    yield "one_bit_first", _with_bits(s, w, [0])
    yield "one_bit_last", _with_bits(s, w, [n - 1])
    yield "only_bit_31", _with_bits(s, w, [31])
    yield "bit_31_of_every_word", _with_bits(s, w, np.arange(31, n, 32))
    yield "every_bit", np.full((s, w), 0xFFFFFFFF, np.uint32)
    yield "first_word_of_every_segment", _with_bits(
        s, w, np.arange(s) * w * 32)
    yield "last_word_of_every_segment", _with_bits(
        s, w, (np.arange(s) + 1) * w * 32 - 1)
    for total in (out_cap - 1, out_cap, out_cap + 1):
        if 0 < total <= n:
            yield f"total_{total}", _random_total(s, w, total, total)
    yield "one_full_word_mid", _with_bits(
        s, w, (n // 64) * 32 + np.arange(32))
    for density in (0.002, 0.05, 0.6):
        yield f"random_{density}", _random_density(s, w, density, 7)


# (S, W, out_cap): W of 1, 2 and 33; eight-word segments, so that a fold's
# group of 32 words crosses three segment boundaries; one fold; two folds;
# then each cell's stand-in with the cell's count of folds (key 4,096 x 512 at
# 262,144: one; live 4,096 x 8,192 at 262,144: two; a node lane 256 x 2,048 at
# 16,384: one; the range cell's `rk` lane at 2**20: one; its range lane, a
# packed 4,096 x 4,096 hit matrix at 2**20: none)
SHAPES = {
    "w1": (1, 1, 16), "w1_segments": (40, 1, 32), "w2": (3, 2, 32),
    "w33": (4, 33, 64), "crossing": (5, 8, 32), "one_fold": (16, 64, 256),
    "two_folds": (64, 512, 64), "three_folds": (64, 1024, 32),
    "key_cell": (256, 32, 1024), "live_cell": (128, 512, 1024),
    "node_lane": (32, 128, 512), "rk_lane": (512, 32, 4096),
    "range_lane": (128, 4, 4096),
}


def _forms(out_cap):
    return (jax.jit(functools.partial(_packed_segment_compact,
                                      out_cap=out_cap)),
            jax.jit(functools.partial(scatter_packed_segment_compact,
                                      out_cap=out_cap)))


def _same(name, got, want):
    for lane, g, w in zip(("indptr", "dep_rows"), got, want):
        g, w = np.asarray(g), np.asarray(w)
        assert g.dtype == w.dtype and g.shape == w.shape, (name, lane)
        assert np.array_equal(g, w), (name, lane, np.flatnonzero(g != w)[:8])


@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_gather_compaction_equals_the_scatter_form(shape):
    s, w, out_cap = SHAPES[shape]
    new, old = _forms(out_cap)
    seen = set()
    for name, m in _adversarial(s, w, out_cap):
        got, want = new(jnp.asarray(m)), old(jnp.asarray(m))
        _same(f"{shape}.{name}", got, want)
        total = int(np.asarray(want[0])[-1])
        seen.add(("under", "at", "over")[np.sign(total - out_cap) + 1])
        # the oracle itself against a plain bit walk, where nothing drops
        bits = np.unpackbits(m.view(np.uint8).reshape(s, -1), axis=1,
                             bitorder="little")
        assert total == bits.sum()
        if total <= out_cap:
            assert np.asarray(got[1])[:total].tolist() == \
                np.nonzero(bits)[1].tolist()
    assert "under" in seen and "over" in seen


@pytest.mark.parametrize("shape", ["range_lane", "one_fold", "w33"])
def test_packed_hit_matrix_equals_the_dense_segment_compaction(shape):
    """The range lane: `_segment_compact` over an i32[S, N] hit matrix is
    the one compaction over the matrix packed with `_pack_bits`."""
    s, w, out_cap = SHAPES[shape]
    new = jax.jit(lambda hits: _packed_segment_compact(
        _pack_bits(hits), out_cap))
    old = jax.jit(lambda hits: scatter_segment_compact(
        hits.astype(jnp.int32), out_cap))
    for name, m in _adversarial(s, w, out_cap):
        hits = np.unpackbits(m.view(np.uint8).reshape(s, -1), axis=1,
                             bitorder="little").astype(bool)
        _same(f"{shape}.{name}", new(jnp.asarray(hits)),
              old(jnp.asarray(hits)))
