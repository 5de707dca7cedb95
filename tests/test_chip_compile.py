"""The arena's sync programs compiled for the chip, without one: the TPU's
compiler is installed here and compiles for a v5e that is described and not
attached. Nothing runs; what is held is what the compiler does with the
live cell's steady shapes (f32[262144, 1024] bitmaps, u32[16384, 8192] kid
table):

  * each program that donates aliases every lane it returns onto its
    input and needs no temporary: no whole-lane copy survives, neither the
    one donation removes nor a layout change (a row scatter of a [cap, 3]
    lane cost a 134 MB temporary and two copies a call, PR 34);
  * `arena_copy` donates nothing and returns as many bytes as it was given.

The topology is described inside a fixture, never at import: only one
process may load the TPU's library at a time, and every xdist worker
imports this file.
"""
from __future__ import annotations

import numpy as np
import pytest

CAP, BUCKETS, KID_CAP = 262144, 1024, 16384


@pytest.fixture(scope="module")
def shaped():
    """(shape, dtype) -> a ShapeDtypeStruct on one described v5e chip; the
    persistent compile cache is off meanwhile (a program compiled for a
    described chip cannot be read back from it)."""
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - whatever the plugin raises
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    one_chip = SingleDeviceSharding(topo.devices[0])
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                    sharding=one_chip)
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _args(shaped, program, m, z):
    lanes = (shaped((CAP, BUCKETS), np.float32), shaped((CAP, 3), np.int32),
             shaped((CAP, 3), np.int32), shaped((CAP,), np.int32),
             shaped((CAP,), np.bool_))
    rows = shaped((m,), np.int32)
    csr = (shaped((z,), np.int32), shaped((z,), np.int32))
    kids = shaped((KID_CAP, CAP // 32), np.uint32)
    return {
        "arena_scatter": (lanes + (rows, *csr, shaped((m, 3), np.int32),
                                   shaped((m, 3), np.int32),
                                   shaped((m,), np.int32),
                                   shaped((m,), np.bool_)), lanes),
        "arena_scatter_keys": ((lanes[0], rows, *csr), lanes[:1]),
        "kid_word_scatter": ((kids, *csr, shaped((z,), np.uint32)), (kids,)),
        "arena_copy": (lanes, lanes),
    }[program]


def _bytes(structs):
    return sum(int(np.prod(s.shape)) * np.dtype(s.dtype).itemsize
               for s in structs)


@pytest.mark.parametrize("program, m, z", [
    ("arena_scatter", 64, 512), ("arena_scatter", 8, 64),
    ("arena_scatter_keys", 64, 512), ("kid_word_scatter", 0, 512)])
def test_a_donating_scatter_rewrites_its_lanes_in_place_on_the_chip(
        shaped, program, m, z):
    from accord_tpu.ops import kernels
    args, lanes = _args(shaped, program, m, z)
    memory = getattr(kernels, program).lower(*args).compile() \
        .memory_analysis()
    # the chip pads a [cap, 3] lane to four columns: at least the lanes'
    # own bytes are aliased, and everything the program returns
    assert _bytes(lanes) <= memory.alias_size_in_bytes \
        <= memory.output_size_in_bytes < memory.alias_size_in_bytes + 4096
    assert memory.temp_size_in_bytes == 0


def test_arena_copy_aliases_nothing_on_the_chip(shaped):
    from accord_tpu.ops import kernels
    args, lanes = _args(shaped, "arena_copy", 0, 0)
    memory = kernels.arena_copy.lower(*args).compile().memory_analysis()
    assert memory.alias_size_in_bytes == 0
    assert memory.output_size_in_bytes >= _bytes(lanes)


def test_the_fused_cross_store_program_fits_the_chip_at_the_node_cells_shapes(
        shaped):
    """`fused_deps_resolve` over eight arenas of 65,536 rows, a full
    dispatch of 1,024 store slices (`preaccept-8stores-100k.fanout-4096`):
    the chip's compiler takes it, the eight bitmaps are its arguments (2.15
    GB), the packed result is one u32[1024, 8 x 2048], and what it needs
    beside them stays a fraction of a bitmap (73 MB when PR 35 measured it):
    no store's f32[1024, 65536] product is ever whole in memory."""
    from accord_tpu.ops import kernels
    from accord_tpu.ops.encoding import WITNESS_TABLE
    cap, stores, b, z = 65536, 8, 1024, 2048
    arena = (shaped((cap, BUCKETS), np.float32), shaped((cap, 3), np.int32),
             shaped((cap,), np.int32), shaped((cap,), np.bool_))
    table = np.asarray(WITNESS_TABLE)
    memory = kernels.fused_deps_resolve.lower(
        shaped((z,), np.int32), shaped((z,), np.int32),
        shaped((b,), np.int32), shaped((b, 3), np.int32),
        shaped((b,), np.int32), shaped((stores,), np.int32),
        (arena,) * stores, shaped(table.shape, table.dtype)) \
        .compile().memory_analysis()
    bitmaps = stores * cap * BUCKETS * 4
    assert bitmaps <= memory.argument_size_in_bytes < bitmaps + (64 << 20)
    assert b * stores * cap // 8 <= memory.output_size_in_bytes \
        < b * stores * cap // 8 + 4096
    assert memory.temp_size_in_bytes < cap * BUCKETS * 4


def test_the_fused_range_program_fits_the_chip_at_the_node_range_cells_shapes(
        shaped):
    """`fused_range_deps_resolve` over eight stores, each a range arena of
    8,192 rows and a key arena of 65,536, a full dispatch of 1,024 store
    slices with 2,048 intervals (`preaccept-8stores-ranges-100k.range-20`):
    the chip's compiler takes it, the eight bitmaps are its arguments, the
    two packed results are u32[1024, 8 x 256] and u32[1024, 8 x 2048], and
    what it needs beside them stays under two stores' f32[1024, 65536]
    contraction (445 MB when compiled for this cell): the eight stores'
    products are never all alive at once."""
    from accord_tpu.ops import kernels
    from accord_tpu.ops.encoding import WITNESS_TABLE
    cap, rcap, stores, b, z = 65536, 8192, 8, 1024, 2048
    karena = (shaped((cap, BUCKETS), np.float32), shaped((cap, 3), np.int32),
              shaped((cap,), np.int32), shaped((cap,), np.bool_))
    rarena = (shaped((rcap,), np.int32), shaped((rcap,), np.int32),
              shaped((rcap, 3), np.int32), shaped((rcap,), np.int32),
              shaped((rcap,), np.bool_))
    table = np.asarray(WITNESS_TABLE)
    lanes = [shaped((z,), np.int32) for _ in range(3)]
    memory = kernels.fused_range_deps_resolve.lower(
        *lanes, shaped((b,), np.int32), shaped((b, 3), np.int32),
        shaped((b,), np.int32), shaped((b,), np.bool_),
        shaped((stores,), np.int32), (rarena,) * stores,
        shaped((stores,), np.int32), (karena,) * stores,
        shaped(table.shape, table.dtype)).compile().memory_analysis()
    bitmaps = stores * cap * BUCKETS * 4
    assert bitmaps <= memory.argument_size_in_bytes < bitmaps + (64 << 20)
    packed = b * stores * (rcap + cap) // 8
    assert packed <= memory.output_size_in_bytes < packed + 4096
    assert memory.temp_size_in_bytes < 2 * b * cap * 4


def test_finalize_csr_keeps_no_out_cap_by_32_temporary_at_the_key_cells_shapes(
        shaped):
    """`finalize_csr` at `preaccept-batch-10k.resolve-4096`'s steady shapes
    (1,024 subjects, 4,096 slots against a 16,384-row arena, out-cap
    262,144): the scatter form expanded every compacted word to 32 bit
    candidates, five `[out_cap, 32]` arrays of 33.5 MB in the compiled
    program; the form that gathers by output position works in lanes of
    out_cap elements, and the largest array it holds is the slot matrix
    itself. It returns indptr, dep_rows and two words, and holds under 16
    bytes of temporaries an out-cap row: an `[out_cap, 3]` lane, which the
    chip pads to 128 columns (134 MB here), would break both bounds."""
    import re

    from accord_tpu.ops import kernels
    b, slots, cap, kid_cap, out_cap = 1024, 4096, 16384, 4096, 262144
    compiled = kernels.finalize_csr.lower(
        shaped((b, cap // 32), np.uint32), shaped((), np.int32),
        shaped((kid_cap, cap // 32), np.uint32), shaped((slots,), np.int32),
        shaped((slots,), np.int32), shaped((b,), np.int32),
        out_cap=out_cap).compile()
    sizes = {dims: int(np.prod([int(d) for d in dims.split(",")]))
             for dims in re.findall(r"\b(?:pred|[suf]\d+)\[([\d,]+)\]",
                                    compiled.as_text())}
    assert max(sizes.values()) == slots * (cap // 32), max(
        sizes, key=sizes.get)
    assert not [d for d, n in sizes.items() if n >= out_cap * 32]
    memory = compiled.memory_analysis()
    assert memory.output_size_in_bytes < (slots + 1 + out_cap) * 4 + 8192
    assert memory.temp_size_in_bytes < out_cap * 16
