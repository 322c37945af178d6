"""Per-kernel validation: Pallas (interpret mode) vs pure-jnp oracles.

Each kernel is swept over shapes and key distributions and checked exactly
(integer data => bitwise equality, not allclose-with-tolerance)."""

import numpy as np
import jax.numpy as jnp
import pytest

from repro.kernels import ref, ops, merge_path, bitonic_sort, lsm_lookup
from repro.core import semantics as sem

RNG = np.random.default_rng(42)


def _sorted_run(n, key_hi, tombstone_frac=0.2):
    keys = np.sort(RNG.integers(0, key_hi, n)).astype(np.int32)
    status = (RNG.random(n) > tombstone_frac).astype(np.int32)
    kv = np.sort(((keys << 1) | status).astype(np.int32))
    val = RNG.integers(0, 1 << 20, n).astype(np.int32)
    return jnp.array(kv), jnp.array(val)


# ---------------------------------------------------------------------------
# merge_path
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("na,nb", [(256, 256), (256, 512), (512, 256), (1024, 1024), (2048, 256)])
@pytest.mark.parametrize("key_hi", [8, 1000, 1 << 20])
def test_merge_path_matches_ref(na, nb, key_hi):
    a_kv, a_val = _sorted_run(na, key_hi)
    b_kv, b_val = _sorted_run(nb, key_hi)
    rkv, rval = ref.merge_ref(a_kv, a_val, b_kv, b_val)
    pkv, pval = merge_path.merge_path(a_kv, a_val, b_kv, b_val, interpret=True)
    np.testing.assert_array_equal(np.asarray(rkv), np.asarray(pkv))
    np.testing.assert_array_equal(np.asarray(rval), np.asarray(pval))


def test_merge_path_ties_newer_first():
    # all-equal original keys: every output element of `a` must precede `b`'s
    n = merge_path.BLOCK
    a_kv = jnp.full((n,), (5 << 1) | 1, jnp.int32)
    b_kv = jnp.full((n,), (5 << 1) | 1, jnp.int32)
    a_val = jnp.arange(n, dtype=jnp.int32)
    b_val = jnp.arange(n, dtype=jnp.int32) + 10_000
    pkv, pval = merge_path.merge_path(a_kv, a_val, b_kv, b_val, interpret=True)
    np.testing.assert_array_equal(np.asarray(pval[:n]), np.arange(n))
    np.testing.assert_array_equal(np.asarray(pval[n:]), np.arange(n) + 10_000)


def test_merge_path_compare_full_sorts_by_key_variable():
    n = merge_path.BLOCK
    a_kv = jnp.sort(jnp.array(RNG.integers(0, 100, n).astype(np.int32)))
    b_kv = jnp.sort(jnp.array(RNG.integers(0, 100, n).astype(np.int32)))
    a_val = jnp.zeros(n, jnp.int32)
    b_val = jnp.ones(n, jnp.int32)
    pkv, _ = merge_path.merge_path(a_kv, a_val, b_kv, b_val, compare_full=True, interpret=True)
    assert (np.diff(np.asarray(pkv)) >= 0).all()


def test_merge_partition_boundaries():
    a = jnp.array([1, 3, 5, 7], jnp.int32)
    b = jnp.array([2, 4, 6, 8], jnp.int32)
    d = jnp.arange(9, dtype=jnp.int32)
    bounds = np.asarray(merge_path.cascade_partition([a, b], d))
    # merged: 1 2 3 4 5 6 7 8 -> a-counts 0 1 1 2 2 3 3 4 4
    np.testing.assert_array_equal(bounds[0], [0, 1, 1, 2, 2, 3, 3, 4, 4])
    np.testing.assert_array_equal(bounds[1], np.arange(9) - bounds[0])


# ---------------------------------------------------------------------------
# bitonic_sort
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n", [8, 64, 1024, 2048, 4096])
@pytest.mark.parametrize("key_hi", [4, 1 << 16, 1 << 30])
def test_bitonic_sort_matches_ref(n, key_hi):
    kv = jnp.array(RNG.integers(0, key_hi, n).astype(np.int32))
    val = jnp.arange(n, dtype=jnp.int32)
    rkv, rval = ref.sort_ref(kv, val)
    pkv, pval = bitonic_sort.bitonic_sort_pairs(kv, val, interpret=True)
    np.testing.assert_array_equal(np.asarray(rkv), np.asarray(pkv))
    # bitonic is not stable: values must agree as (key, value) pair multisets
    pr = sorted(zip(np.asarray(rkv).tolist(), np.asarray(rval).tolist()))
    pp = sorted(zip(np.asarray(pkv).tolist(), np.asarray(pval).tolist()))
    assert pr == pp


# ---------------------------------------------------------------------------
# lsm_lookup (streamed lower bound)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n", [2048, 4096, 8192])
@pytest.mark.parametrize("q", [256, 512])
def test_lower_bound_streamed_matches_ref(n, q):
    keys = jnp.sort(jnp.array(RNG.integers(0, 1 << 20, n).astype(np.int32)))
    queries = jnp.array(RNG.integers(0, 1 << 20, q).astype(np.int32))
    r = ref.lower_bound_ref(keys, queries)
    p = lsm_lookup.lower_bound_streamed(keys, queries, interpret=True)
    np.testing.assert_array_equal(np.asarray(r), np.asarray(p))


def test_lower_bound_streamed_hits_every_boundary():
    keys = jnp.array(np.repeat(np.arange(8) * 4, 256).astype(np.int32))
    queries = jnp.array(np.arange(256).astype(np.int32) % 36)
    r = ref.lower_bound_ref(keys, queries)
    p = lsm_lookup.lower_bound_streamed(keys, queries, interpret=True)
    np.testing.assert_array_equal(np.asarray(r), np.asarray(p))


@pytest.mark.parametrize("n,q", [(2048, 256), (4096, 512)])
def test_upper_bound_dispatch_uses_lower_bound_kernel(n, q):
    """ops.upper_bound(k) == lower_bound(k+1) through the Pallas kernel must
    match the reference, including duplicate runs, the INT32_MAX guard lane,
    and placebo-tail keys."""
    keys = np.sort(RNG.integers(0, 1 << 16, n - 256)).astype(np.int32)
    keys = np.concatenate([keys, np.full(256, sem.PLACEBO_KEY, np.int32)])  # placebo tail
    queries = RNG.integers(0, 1 << 16, q).astype(np.int32)
    queries[:4] = [0, sem.MAX_USER_KEY, sem.PLACEBO_KEY, np.iinfo(np.int32).max]
    r = ref.upper_bound_ref(jnp.array(keys), jnp.array(queries))
    ops.set_backend("pallas")
    try:
        p = ops.upper_bound(jnp.array(keys), jnp.array(queries))
    finally:
        ops.set_backend("xla")
    np.testing.assert_array_equal(np.asarray(r), np.asarray(p))


def test_sort_pairs_recency_newest_first_within_equal_keys():
    """The write-buffer batch-formation rule: ascending original key, later
    lane first within equal keys (even across the status-bit boundary),
    placebos last."""
    kv = jnp.array([
        (5 << 1) | 1,   # lane 0: insert 5
        (3 << 1) | 1,   # lane 1: insert 3
        (5 << 1) | 0,   # lane 2: tombstone 5 (newer than lane 0)
        sem.PLACEBO_KV, # lane 3: padding
        (5 << 1) | 1,   # lane 4: insert 5 (newest)
    ], jnp.int32)
    val = jnp.array([50, 30, 0, 0, 55], jnp.int32)
    skv, sval = ops.sort_pairs_recency(kv, val)
    np.testing.assert_array_equal(
        np.asarray(sem.original_key(skv)), [3, 5, 5, 5, sem.PLACEBO_KEY]
    )
    # within the key-5 segment: lane 4 (insert 55), lane 2 (tombstone), lane 0
    np.testing.assert_array_equal(np.asarray(sval[1:4]), [55, 0, 50])
    assert bool(sem.is_tombstone(skv[2:3])[0])


# ---------------------------------------------------------------------------
# ops dispatch: pallas backend end-to-end through the LSM
# ---------------------------------------------------------------------------


def test_lsm_update_with_pallas_backend_matches_xla():
    from repro.core import LSMConfig, lsm_init, lsm_insert, lsm_lookup as lsm_lookup_fn

    cfg = LSMConfig(batch_size=merge_path.BLOCK, num_levels=3)
    rng = np.random.default_rng(7)
    batches = [rng.choice(1 << 16, merge_path.BLOCK, replace=False) for _ in range(3)]

    states = {}
    for backend in ("xla", "pallas"):
        ops.set_backend(backend)
        try:
            st = lsm_init(cfg)
            for i, ks in enumerate(batches):
                st = lsm_insert(cfg, st, jnp.array(ks), jnp.array(ks % 997))
            states[backend] = st
        finally:
            ops.set_backend("xla")
    q = jnp.array(batches[0][:128])
    f1, v1 = lsm_lookup_fn(cfg, states["xla"], q)
    f2, v2 = lsm_lookup_fn(cfg, states["pallas"], q)
    np.testing.assert_array_equal(np.asarray(f1), np.asarray(f2))
    np.testing.assert_array_equal(np.asarray(v1), np.asarray(v2))


@pytest.mark.parametrize("backend,n,path", [
    ("pallas", 2048, "pallas_interpret"),  # tiles: the kernel, interpreted off-TPU
    ("pallas", 1000, "xla"),               # does not tile: the reference
    ("xla", 2048, "xla"),
    ("xla", 1 << 14, "xla_fenced"),        # whole rows, at the threshold: fenced
])
def test_record_paths_reports_each_dispatch(backend, n, path):
    keys = jnp.asarray(np.sort(RNG.integers(0, 1 << 16, n)).astype(np.int32))
    queries = jnp.asarray(RNG.integers(0, 1 << 16, 256).astype(np.int32))
    ops.set_backend(backend)
    try:
        with ops.record_paths() as paths:
            ops.lower_bound(keys, queries)
            ops.upper_bound(keys, queries)
    finally:
        ops.set_backend("xla")
    assert paths == [("lower_bound", path), ("upper_bound", path)]
