"""The multi-chip deps data plane IN the suite: the sharded resolver must be
differentially identical to the single-device kernel and the host scan, and
must carry a full burn. Runs on the conftest 8-device virtual CPU mesh
(reference scale analog: CommandStores range-splitting,
local/CommandStores.java:79 -- here the split is arena rows over 'data' and
key buckets over 'model')."""
from __future__ import annotations

import numpy as np
import pytest

from accord_tpu.parallel.mesh import make_mesh, sharded_deps_resolve
from accord_tpu.sim.burn import run_burn
from accord_tpu.sim.cluster import Cluster, ClusterConfig


def test_sharded_kernel_matches_single_device():
    """Pure kernel differential: sharded == unsharded on random arenas."""
    import jax
    import jax.numpy as jnp
    from accord_tpu.ops.kernels import deps_resolve

    mesh = make_mesh()
    assert mesh.shape["data"] * mesh.shape["model"] == len(jax.devices())
    kern = sharded_deps_resolve(mesh)
    from accord_tpu.parallel.mesh import example_resolve_batch
    for trial in range(3):
        args = tuple(jnp.asarray(a) for a in example_resolve_batch(
            cap=512, k=256, b=16, seed=trial))
        single = np.asarray(deps_resolve(*args))
        sharded = np.asarray(kern(*args))
        assert np.array_equal(single, sharded), f"trial {trial} diverged"


def _drive_writes(cluster, n):
    from accord_tpu.primitives.keyspace import Keys
    from accord_tpu.primitives.timestamp import TxnKind
    from accord_tpu.primitives.txn import Txn
    from accord_tpu.sim.list_store import ListQuery, ListRead, ListUpdate
    for v in range(1, n + 1):
        ks = Keys(sorted({100 + v % 7, 9000 + v % 3}))
        r = cluster.nodes[1 + v % 3].coordinate(
            Txn(TxnKind.WRITE, ks, read=ListRead(ks),
                update=ListUpdate(ks, v), query=ListQuery()))
        cluster.drain()
        assert r.done and r.failure is None, r.failure


def test_sharded_resolver_matches_host_and_single_device():
    """Same live store state, three resolvers, identical deps answers."""
    from accord_tpu.ops.resolver import (BatchDepsResolver,
                                         ShardedBatchDepsResolver)
    from accord_tpu.primitives.timestamp import Timestamp, TxnKind, Domain

    c = Cluster(31, ClusterConfig())
    _drive_writes(c, 24)
    node = c.nodes[1]
    single = BatchDepsResolver(num_buckets=256, initial_cap=512)
    sharded = ShardedBatchDepsResolver(mesh=make_mesh(),
                                       num_buckets=256, initial_cap=512)
    before = Timestamp(node.epoch, node.time_service.now_micros() + 10_000,
                       0, node.id)
    checked = 0
    for store in node.command_stores.all():
        for key, cfk in store.cfks.items():
            from accord_tpu.primitives.keyspace import Keys
            subj = node.next_txn_id(TxnKind.WRITE, Domain.KEY)
            owned = store.owned(Keys([key]))
            host = store.host_calculate_deps(subj, owned, before)
            d_single = single.resolve_one(store, subj, owned, before)
            d_sharded = sharded.resolve_one(store, subj, owned, before)
            def as_map(d):
                kd = d.key_deps
                return {k: kd.for_key(k) for k in kd.keys}
            assert as_map(d_single) == as_map(host), \
                f"single-device != host at key {key}"
            assert as_map(d_sharded) == as_map(host), \
                f"sharded != host at key {key}"
            checked += 1
    assert checked >= 5, f"only {checked} keys exercised"


def test_burn_with_sharded_resolver():
    """A full burn (with durability) on the mesh-sharded data plane."""
    from accord_tpu.ops.resolver import ShardedBatchDepsResolver

    factory = lambda: ShardedBatchDepsResolver(  # noqa: E731
        mesh=make_mesh(), num_buckets=256, initial_cap=512)
    r = run_burn(5, ops=120, write_ratio=0.8, key_count=16,
                 config=ClusterConfig(deps_resolver_factory=factory,
                                      deps_batch_window_ms=1.0,
                                      durability=True,
                                      durability_interval_ms=500.0))
    assert r.acked == 120
    assert r.failed == 0


def test_burn_sharded_matches_host_resolver_log():
    """Determinism ACROSS resolvers: the sharded device path must produce
    the exact event log of the host scan path (deps supersets could reorder
    execution; exact per-key decode means they must not)."""
    from accord_tpu.ops.resolver import ShardedBatchDepsResolver

    kw = dict(ops=80, write_ratio=0.8, key_count=12, collect_log=True)
    host = run_burn(9, config=ClusterConfig(), **kw)
    factory = lambda: ShardedBatchDepsResolver(  # noqa: E731
        mesh=make_mesh(), num_buckets=256, initial_cap=512)
    dev = run_burn(9, config=ClusterConfig(deps_resolver_factory=factory,
                                           deps_batch_window_ms=None),
                   **kw)
    assert host.acked == dev.acked == 80


def test_sharded_finalize_kernel_matches_single_device():
    """The sharded compaction twin: per-shard popcount/prefix fragments
    gather-merged into the global CSR must be BIT-identical to
    kernels.finalize_csr -- indptr, dep_rows, the fused bound scalar and
    the checksum word -- including fused word spans (word_off != 0) and overflow
    (where both sides must still report the exact total)."""
    import jax.numpy as jnp
    from accord_tpu.ops.kernels import finalize_csr
    from accord_tpu.parallel.mesh import sharded_finalize_csr

    mesh = make_mesh()
    data = mesh.shape["data"]
    cap = 32 * data * 4
    w = cap // 32
    kern = sharded_finalize_csr(mesh)
    rng = np.random.default_rng(23)
    overflowed = fit = 0
    for trial, (density, out_cap, spans, off) in enumerate(
            ((0.004, 256, 1, 0), (0.02, 256, 2, w), (0.5, 64, 1, 0))):
        b, s, kc = 8, 32, 64
        packed = (rng.random((b, spans * w, 32)) < density)
        packed = np.packbits(packed, axis=-1, bitorder="little") \
            .view(np.uint32).reshape(b, spans * w)
        kid = (rng.random((kc, w, 32)) < 0.1)
        kid = np.packbits(kid, axis=-1, bitorder="little") \
            .view(np.uint32).reshape(kc, w)
        args = (jnp.asarray(packed), jnp.asarray(off, jnp.int32),
                jnp.asarray(kid),
                jnp.asarray(rng.integers(-1, b + 2, s), jnp.int32),
                jnp.asarray(rng.integers(0, kc + 1, s), jnp.int32),
                jnp.asarray(rng.integers(-1, cap, b), jnp.int32))
        single = finalize_csr(*args, out_cap=out_cap)
        sharded = kern(*args, out_cap=out_cap)
        for name, a, c in zip(("indptr", "dep_rows", "bound", "csum"),
                              single, sharded):
            assert np.array_equal(np.asarray(a), np.asarray(c)), \
                f"trial {trial}: sharded {name} != single-device"
        total = int(np.asarray(single[0])[-1])
        overflowed += total > out_cap
        fit += 0 < total <= out_cap
    assert overflowed and fit, "differential vacuous"


def test_model_sharded_kid_bound_matches_single_device():
    """The kid-table out-cap bound is popcounted over 'model'-axis slot
    blocks (each model replica sums a contiguous slice, psum merges):
    across nnz tiers and slot paddings the merged bound must stay
    BIT-identical to the single-device kernel's full reduction -- integer
    partial sums, so this is equality, not tolerance."""
    import jax.numpy as jnp
    from accord_tpu.ops.kernels import finalize_csr
    from accord_tpu.parallel.mesh import sharded_finalize_csr

    mesh = make_mesh()
    assert mesh.shape["model"] > 1, \
        "conftest mesh must exercise a real model axis"
    data = mesh.shape["data"]
    cap = 32 * data * 4
    w = cap // 32
    kern = sharded_finalize_csr(mesh)
    rng = np.random.default_rng(31)
    for s in (32, 64, 256):        # every nnz tier divides by the model axis
        b, kc = 16, 128
        packed = (rng.random((b, w, 32)) < 0.05)
        packed = np.packbits(packed, axis=-1, bitorder="little") \
            .view(np.uint32).reshape(b, w)
        kid = (rng.random((kc, w, 32)) < 0.2)
        kid = np.packbits(kid, axis=-1, bitorder="little") \
            .view(np.uint32).reshape(kc, w)
        args = (jnp.asarray(packed), jnp.asarray(0, jnp.int32),
                jnp.asarray(kid),
                jnp.asarray(rng.integers(-1, b + 2, s), jnp.int32),
                jnp.asarray(rng.integers(0, kc + 1, s), jnp.int32),
                jnp.asarray(rng.integers(-1, cap, b), jnp.int32))
        single = finalize_csr(*args, out_cap=2048)
        sharded = kern(*args, out_cap=2048)
        assert int(np.asarray(single[2])) == int(np.asarray(sharded[2])), \
            f"nnz {s}: model-sharded bound != single-device bound"
        assert int(np.asarray(single[2])) > 0, f"nnz {s}: bound vacuous"
        for name, a, c in zip(("indptr", "dep_rows", "bound", "csum"),
                              single, sharded):
            assert np.array_equal(np.asarray(a), np.asarray(c)), \
                f"nnz {s}: sharded {name} != single-device"


def test_sharded_finalize_e2e_and_zero_recompiles():
    """The sharded resolver rides the finalized-CSR harvest end to end
    (answers == single-device == host, zero legacy decodes), and after
    warmup_sharded(out_tiers=...) the live workload mints NO new sharded
    finalize compiles -- the OutCapTiers rungs are the whole shape space."""
    from accord_tpu.ops.resolver import (BatchDepsResolver,
                                         ShardedBatchDepsResolver)
    from accord_tpu.parallel.mesh import sharded_finalize_csr, warmup_sharded
    from accord_tpu.primitives.keyspace import Keys
    from accord_tpu.primitives.timestamp import Domain, Timestamp, TxnKind

    c = Cluster(37, ClusterConfig())
    _drive_writes(c, 24)
    node = c.nodes[1]
    mesh = make_mesh()
    # resolve_one dispatches pad to batch tier 8 / nnz tier 32; the cold
    # first pick seeds from the exact bound (small workload -> first rung)
    warmup_sharded(mesh, num_buckets=256, cap=512, batch_tiers=(8,),
                   nnz_tiers=(32,), store_tiers=(1,), out_tiers=(256,))
    fin = sharded_finalize_csr(mesh)
    warmed = fin._cache_size()
    assert warmed > 0

    sharded = ShardedBatchDepsResolver(mesh=mesh, num_buckets=256,
                                       initial_cap=512)
    single = BatchDepsResolver(num_buckets=256, initial_cap=512)
    before = Timestamp(node.epoch, node.time_service.now_micros() + 10_000,
                       0, node.id)
    checked = 0
    for store in node.command_stores.all():
        for key in store.cfks:
            subj = node.next_txn_id(TxnKind.WRITE, Domain.KEY)
            owned = store.owned(Keys([key]))
            host = store.host_calculate_deps(subj, owned, before)
            assert single.resolve_one(store, subj, owned, before) == host
            assert sharded.resolve_one(store, subj, owned, before) == host
            checked += 1
    assert checked >= 5, f"only {checked} keys exercised"
    assert sharded.finalized_decodes > 0, "sharded finalize never engaged"
    assert sharded.legacy_decodes == 0
    assert sharded.finalize_fallbacks == 0
    assert sharded.host_fallbacks == 0
    assert sharded.shard_merge_s > 0.0, "sharded merge timer never ran"
    assert fin._cache_size() == warmed, \
        "live workload minted sharded finalize compiles past warmup"
