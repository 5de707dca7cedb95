"""TPU data-plane kernels: unit tests vs naive models + differential tests
against the host deps scan (runs on the CPU backend; the same jitted code
runs on TPU)."""
import numpy as np
import pytest

from accord_tpu.ops.encoding import TimestampEncoder, WITNESS_TABLE, encode_key_bitmaps
from accord_tpu.primitives.timestamp import Timestamp, TxnId, TxnKind


def test_witness_table_matches_kinds():
    for a in TxnKind:
        for b in TxnKind:
            assert WITNESS_TABLE[int(a), int(b)] == (1 if a.witnesses(b) else 0)


def test_timestamp_encoder_roundtrip_order():
    tss = [Timestamp(1 + i % 2, 1000 + i * 7, i % 3, i % 5) for i in range(50)]
    enc = TimestampEncoder.for_timestamps(tss)
    arr = enc.encode(tss)
    # lexicographic order over the 3 lanes must match timestamp order
    idx = sorted(range(len(tss)), key=lambda i: tuple(arr[i]))
    assert [tss[i] for i in idx] == sorted(tss)


def test_timestamp_encoder_epoch_lane():
    # later epoch with SMALLER hlc must still sort after earlier epoch
    tss = [Timestamp(1, 500, 0, 1), Timestamp(2, 100, 0, 1), Timestamp(2, 600, 0, 2)]
    enc = TimestampEncoder.for_timestamps(tss)
    arr = enc.encode(tss)
    assert tuple(arr[0]) < tuple(arr[1]) < tuple(arr[2])
    far = Timestamp(1, 500 + (1 << 32), 0, 1)
    assert not enc.in_window(far)
    with pytest.raises(ValueError):
        enc.encode([far])


def test_deps_matrix_vs_naive():
    import jax.numpy as jnp
    from accord_tpu.ops.kernels import deps_matrix
    rng = np.random.default_rng(0)
    B, A, K = 5, 16, 128
    sb = (rng.random((B, K)) < 0.05).astype(np.float32)
    ab = (rng.random((A, K)) < 0.05).astype(np.float32)
    s_before = rng.integers(0, 10, (B, 3)).astype(np.int32)
    a_ts = rng.integers(0, 10, (A, 3)).astype(np.int32)
    s_kinds = rng.integers(0, 5, B).astype(np.int32)
    a_kinds = rng.integers(0, 5, A).astype(np.int32)
    valid = rng.random(A) < 0.9
    got = np.asarray(deps_matrix(jnp.asarray(sb), jnp.asarray(s_before),
                                 jnp.asarray(s_kinds), jnp.asarray(ab),
                                 jnp.asarray(a_ts), jnp.asarray(a_kinds),
                                 jnp.asarray(valid), jnp.asarray(WITNESS_TABLE)))
    for b in range(B):
        for a in range(A):
            expect = (bool((sb[b] * ab[a]).sum() > 0)
                      and WITNESS_TABLE[s_kinds[b], a_kinds[a]] == 1
                      and (tuple(a_ts[a]) < tuple(s_before[b]))
                      and bool(valid[a]))
            assert got[b, a] == expect, (b, a)


def _table_lookup(table, subj_kinds, act_kinds):
    """The indexing `_witness_mask` replaces, in NumPy: a negative kind wraps
    once (int32), then both clamp into the table."""
    table = np.asarray(table)

    def norm(kinds, size):
        kinds = np.asarray(kinds, np.int32)
        return np.clip(np.where(kinds < 0, kinds + np.int32(size), kinds),
                       0, size - 1)

    return table[norm(subj_kinds, table.shape[0])[:, None],
                 norm(act_kinds, table.shape[1])[None, :]] == 1


def _witness_case(name):
    rng = np.random.default_rng(sum(name.encode()))
    table = np.asarray(WITNESS_TABLE)
    subj = rng.integers(0, 6, 24).astype(np.int32)
    act = rng.integers(0, 6, 64).astype(np.int32)
    if name == "all_36_pairs":
        subj = act = np.arange(6, dtype=np.int32)
    elif name.startswith("random_table"):
        table = rng.integers(0, 2, (6, 6)).astype(np.int32)
    elif name == "values_other_than_0_and_1":
        table = rng.integers(-1, 3, (6, 6)).astype(np.int32)
    elif name == "wide_table_bit_31":
        table = rng.integers(0, 2, (4, 32)).astype(np.int32)
        table[:, 31] = [1, 0, 1, 1]
        subj = rng.integers(0, 4, 24).astype(np.int32)
        act = np.concatenate([rng.integers(0, 32, 60), [31, 31, 30, 0]]) \
            .astype(np.int32)
    elif name == "arena_padding_rows_kind_0":
        act[40:] = 0  # what resolver._StoreArena pads `kinds` with
    elif name == "out_of_range_kinds":
        wild = np.array([-2 ** 31, -2 ** 31 + 5, -13, -12, -7, -6, -5, -1, 0,
                         5, 6, 7, 11, 12, 31, 32, 33, 100, 2 ** 31 - 1],
                        np.int64).astype(np.int32)
        subj = act = wild
    else:
        assert name == "witness_table_random_kinds"
    return table, subj, act


@pytest.mark.parametrize("name", [
    "all_36_pairs", "witness_table_random_kinds", "random_table_0",
    "random_table_1", "random_table_2", "random_table_3",
    "values_other_than_0_and_1", "wide_table_bit_31",
    "arena_padding_rows_kind_0", "out_of_range_kinds"])
def test_witness_mask_is_the_table_lookup(name):
    import jax
    import jax.numpy as jnp
    from accord_tpu.ops.kernels import _witness_mask
    table, subj, act = _witness_case(name)
    got = np.asarray(jax.jit(_witness_mask)(table, subj, act))
    assert got.dtype == np.bool_ and got.shape == (len(subj), len(act))
    np.testing.assert_array_equal(got, _table_lookup(table, subj, act))
    # and the indexing itself, as the kernels wrote it until PR 26
    indexed = jnp.asarray(table)[jnp.asarray(subj)[:, None],
                                 jnp.asarray(act)[None, :]] == 1
    np.testing.assert_array_equal(got, np.asarray(indexed))


def test_witness_mask_refuses_a_table_wider_than_its_word():
    from accord_tpu.ops.kernels import _witness_mask
    with pytest.raises(AssertionError):
        _witness_mask(np.zeros((2, 33), np.int32), np.zeros(1, np.int32),
                      np.zeros(1, np.int32))


@pytest.mark.parametrize("kinds", ["as_the_scope_test_has_them", "all_six"])
def test_deps_resolve_vs_numpy_model_of_the_whole_mask(kinds):
    from accord_tpu.ops.kernels import deps_resolve
    from tests.test_obs_phases import _kernel_args
    resolve, _ = _kernel_args(seed=5)
    (subj_of, subj_keys, before, s_kinds, act_bm, act_ts, a_kinds, valid,
     table) = resolve
    if kinds == "all_six":
        rng = np.random.default_rng(6)
        s_kinds = rng.integers(0, 6, len(s_kinds)).astype(np.int32)
        a_kinds = rng.integers(0, 6, len(a_kinds)).astype(np.int32)
        a_kinds[-8:] = 0
    b, cap = len(s_kinds), len(a_kinds)
    bm = np.zeros((b, act_bm.shape[1]), bool)
    bm[subj_of, subj_keys] = True  # _kernel_args draws no padding entry
    overlap = (bm.astype(np.int64) @ act_bm.astype(np.int64).T) > 0
    lex_before = np.array([[tuple(act_ts[a]) < tuple(before[i])
                            for a in range(cap)] for i in range(b)])
    m = overlap & _table_lookup(table, s_kinds, a_kinds) & lex_before \
        & valid[None, :]
    assert m.any() and not m.all()
    want = (m.reshape(b, cap // 32, 32).astype(np.uint64)
            << np.arange(32, dtype=np.uint64)).sum(axis=-1).astype(np.uint32)
    got = deps_resolve(subj_of, subj_keys, before, s_kinds, act_bm, act_ts,
                       a_kinds, valid, table)
    np.testing.assert_array_equal(np.asarray(got), want)


def test_transitive_closure():
    import jax.numpy as jnp
    from accord_tpu.ops.kernels import transitive_closure
    # chain 0 <- 1 <- 2 <- 3 (i depends on i-1)
    n = 8
    adj = np.zeros((n, n), dtype=bool)
    for i in range(1, 4):
        adj[i, i - 1] = True
    closed = np.asarray(transitive_closure(jnp.asarray(adj), 3))
    assert closed[3, 0] and closed[3, 1] and closed[3, 2]
    assert closed[2, 0] and not closed[0, 3]
    assert not closed[4].any()


def test_execution_wavefronts():
    import jax.numpy as jnp
    from accord_tpu.ops.kernels import execution_wavefronts
    # diamond: 1,2 depend on 0; 3 depends on 1 and 2
    adj = np.zeros((8, 8), dtype=bool)
    adj[1, 0] = adj[2, 0] = adj[3, 1] = adj[3, 2] = True
    levels = np.asarray(execution_wavefronts(jnp.asarray(adj), 8))
    assert levels[0] == 0 and levels[1] == 1 and levels[2] == 1 and levels[3] == 2


def _preaccept_population(store, node, keys_list):
    from accord_tpu.local import commands
    from accord_tpu.primitives.keyspace import Keys
    from tests.test_local_engine import mk_txn
    ids = []
    for i, keys in enumerate(keys_list):
        txn = mk_txn(keys, i + 1)
        txn_id = node.next_txn_id(txn.kind, txn.domain)
        commands.preaccept(store, txn_id, txn.slice(store.ranges, False),
                           node.compute_route(txn))
        ids.append(txn_id)
    return ids


def test_batch_resolver_differential_vs_host():
    """The device resolver must return EXACTLY the host scan's deps."""
    from accord_tpu.ops.resolver import BatchDepsResolver
    from accord_tpu.primitives.keyspace import Keys
    from tests.test_local_engine import setup_store
    rng = np.random.default_rng(7)
    _, node, store = setup_store()
    keys_list = [sorted(set(rng.integers(0, 40, rng.integers(1, 4)).tolist()))
                 for _ in range(60)]
    ids = _preaccept_population(store, node, keys_list)
    resolver = BatchDepsResolver(num_buckets=128)  # buckets < domain: collisions exercised
    for i in rng.choice(len(ids), 20, replace=False):
        subject = ids[i]
        keys = Keys(keys_list[i])
        bound = store.command(subject).execute_at
        host = store.host_calculate_deps(subject, keys, bound)
        dev = resolver.resolve_one(store, subject, keys, bound)
        assert dev == host, f"subject {subject}: {dev} != {host}"


def test_burn_with_device_resolver_matches_host():
    """End-to-end differential in INLINE mode (batch window None): the device
    path answers every query synchronously with exactly the host scan's
    results, so the two event logs must be bit-identical."""
    from accord_tpu.ops.resolver import BatchDepsResolver
    from accord_tpu.sim.burn import run_burn
    from accord_tpu.sim.cluster import ClusterConfig

    host = run_burn(seed=11, ops=40, collect_log=True)
    dev = run_burn(seed=11, ops=40, collect_log=True,
                   config=ClusterConfig(
                       deps_resolver_factory=lambda: BatchDepsResolver(num_buckets=128),
                       deps_batch_window_ms=None))
    assert host.acked == dev.acked == 40
    assert host.log == dev.log


def test_burn_with_batched_device_resolver():
    """End-to-end with the micro-batch tick ON: replies defer to the per-store
    tick, so timing (and thus logs) may differ from host -- but every op still
    acks and strict serializability + convergence hold (checked inside
    run_burn), and the run is deterministic."""
    from accord_tpu.ops.resolver import BatchDepsResolver
    from accord_tpu.sim.burn import run_burn
    from accord_tpu.sim.cluster import ClusterConfig

    def cfg():
        return ClusterConfig(
            deps_resolver_factory=lambda: BatchDepsResolver(num_buckets=128),
            deps_batch_window_ms=0.0)

    a = run_burn(seed=11, ops=40, collect_log=True, config=cfg())
    assert a.acked == 40 and a.lost == 0
    b = run_burn(seed=11, ops=40, collect_log=True, config=cfg())
    assert a.log == b.log  # deterministic under batching


def test_batch_resolver_dense_conflicts_vs_host():
    """Subjects with dependency counts in the hundreds (everything conflicts)
    must still decode exactly from the bit-packed kernel result."""
    from accord_tpu.ops.resolver import BatchDepsResolver
    from accord_tpu.primitives.keyspace import Keys
    from tests.test_local_engine import setup_store
    _, node, store = setup_store()
    # 150 txns all on one key: every subject conflicts with every earlier one
    keys_list = [[0, 1] for _ in range(150)]
    ids = _preaccept_population(store, node, keys_list)
    resolver = BatchDepsResolver(num_buckets=128)
    for i in (120, 130, 149):
        subject = ids[i]
        keys = Keys(keys_list[i])
        bound = store.command(subject).execute_at
        host = store.host_calculate_deps(subject, keys, bound)
        dev = resolver.resolve_one(store, subject, keys, bound)
        assert dev == host, f"subject {subject}"
        assert len(host.key_deps.all_txn_ids()) > 64  # genuinely dense


def test_max_conflict_batch_vs_host():
    """Device max-conflict must agree with the host MaxConflicts scan."""
    from accord_tpu.ops.resolver import BatchDepsResolver
    from accord_tpu.primitives.keyspace import Keys
    from tests.test_local_engine import setup_store
    rng = np.random.default_rng(13)
    _, node, store = setup_store()
    keys_list = [sorted(set(rng.integers(0, 40, rng.integers(1, 4)).tolist()))
                 for _ in range(50)]
    ids = _preaccept_population(store, node, keys_list)
    resolver = BatchDepsResolver(num_buckets=128)
    subjects = []
    for i in rng.choice(len(ids), 15, replace=False):
        subjects.append((ids[i], Keys(keys_list[i])))
    got = resolver.max_conflict_batch(store, subjects)
    for (subj, keys), (handled, ts) in zip(subjects, got):
        host = store.max_conflict_ts(keys)
        if handled:
            assert ts == host, f"{subj}: device {ts} != host {host}"
        else:
            # bucket-collision fallback: the host path is consulted instead
            assert host is not None
