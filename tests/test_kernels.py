"""The ops of kernels/ops.py against independent NumPy oracles, and the path
each op's dispatch takes by shape.

Merges are checked against a NumPy stable merge, sorts against `np.lexsort`,
searches against `np.searchsorted` and `queries.lookup_runs` against a
newest-first NumPy resolution. Integer data, so every check is bitwise."""

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import semantics as sem
from repro.core.queries import lookup_runs
from repro.kernels import ops, search

RNG = np.random.default_rng(42)
INT32_MAX = np.iinfo(np.int32).max


def _sorted_run(n, key_hi, tombstone_frac=0.2, placebo_frac=0.0):
    """One run of key variables sorted by key variable (a tombstone before an
    insert of its key); optionally placebo-diluted."""
    keys = RNG.integers(0, key_hi, n).astype(np.int32)
    status = (RNG.random(n) > tombstone_frac).astype(np.int32)
    kv = ((keys << 1) | status).astype(np.int32)
    if placebo_frac:
        kv = np.where(RNG.random(n) < placebo_frac, sem.PLACEBO_KV, kv)
    kv = np.sort(kv)
    val = RNG.integers(1, 1 << 20, n).astype(np.int32)
    return jnp.array(kv), jnp.array(val)


def _placebo_run(n):
    return (
        jnp.full((n,), sem.PLACEBO_KV, jnp.int32),
        jnp.full((n,), sem.EMPTY_VALUE, jnp.int32),
    )


def _np_merge(runs):
    """Stable merge by original key of runs given newest first: equal keys
    keep run order (newest first), then their order within the run."""
    kv = np.concatenate([np.asarray(kv) for kv, _ in runs])
    val = np.concatenate([np.asarray(v) for _, v in runs])
    order = np.argsort(kv >> 1, kind="stable")
    return kv[order], val[order]


def _np_lookup(runs, queries):
    """Per query, the newest run holding its original key decides: found iff
    that run's first slot of the key is an insert, with that slot's value."""
    queries = np.asarray(queries)
    found = np.zeros(len(queries), bool)
    vals = np.zeros(len(queries), np.int32)
    resolved = np.zeros(len(queries), bool)
    for kv, val in runs:
        kv, val = np.asarray(kv), np.asarray(val)
        orig = kv >> 1
        idx = np.minimum(np.searchsorted(orig, queries, "left"), len(kv) - 1)
        newly = (orig[idx] == queries) & ~resolved
        live = newly & ((kv[idx] & 1) == 1)
        found |= live
        vals = np.where(live, val[idx], vals)
        resolved |= newly
    return found, vals


def _assert_merge(runs):
    got_kv, got_val = ops.merge_cascade(runs)
    exp_kv, exp_val = _np_merge(runs)
    np.testing.assert_array_equal(np.asarray(got_kv), exp_kv)
    np.testing.assert_array_equal(np.asarray(got_val), exp_val)


def _assert_lookup(runs, queries):
    found, vals = lookup_runs(runs, jnp.asarray(queries, jnp.int32))
    exp_found, exp_vals = _np_lookup(runs, np.asarray(queries, np.int32))
    np.testing.assert_array_equal(np.asarray(found), exp_found)
    np.testing.assert_array_equal(np.where(exp_found, np.asarray(vals), 0), exp_vals)
    assert (np.asarray(vals)[~exp_found] == sem.EMPTY_VALUE).all()


# ---------------------------------------------------------------------------
# merge_cascade
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("na,nb", [(256, 256), (256, 512), (512, 256), (1024, 1024), (2048, 256)])
@pytest.mark.parametrize("key_hi", [8, 1000, 1 << 20])
def test_merge_cascade_two_runs(na, nb, key_hi):
    _assert_merge([_sorted_run(na, key_hi), _sorted_run(nb, key_hi)])


@pytest.mark.parametrize("sizes", [
    (256,), (256, 256), (256, 512), (256, 256, 512),
    (256, 512, 1024, 2048), (512, 256, 256, 512, 1024),
])
@pytest.mark.parametrize("key_hi", [8, 1000, 1 << 20])
def test_merge_cascade_k_way(sizes, key_hi):
    _assert_merge([_sorted_run(n, key_hi) for n in sizes])


def test_merge_cascade_placebo_runs_sort_last():
    _assert_merge([_sorted_run(256, 50), _placebo_run(512), _sorted_run(256, 50)])


def test_merge_cascade_dup_tombstone_heavy():
    _assert_merge([_sorted_run(256, 5, tombstone_frac=0.6, placebo_frac=0.2)
                   for _ in range(4)])


@pytest.mark.parametrize("num_runs", [2, 3])
def test_merge_cascade_ties_keep_newer_run_first(num_runs):
    # All-equal key variables: every element of an earlier (newer) run comes
    # before those of later runs, in input order within each run.
    n = 256
    runs = [(jnp.full((n,), (5 << 1) | 1, jnp.int32),
             jnp.arange(n, dtype=jnp.int32) + 10_000 * i) for i in range(num_runs)]
    _, got_val = ops.merge_cascade(runs)
    np.testing.assert_array_equal(
        np.asarray(got_val), np.concatenate([np.arange(n) + 10_000 * i for i in range(num_runs)])
    )


def test_merge_cascade_ragged_sizes():
    _assert_merge([_sorted_run(100, 50), _sorted_run(33, 50), _sorted_run(256, 50)])


def test_merge_cascade_single_run_passthrough():
    run = _sorted_run(256, 50)
    got = ops.merge_cascade([run])
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(run[0]))


# ---------------------------------------------------------------------------
# sort_pairs, sort_pairs_recency
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n", [8, 64, 1024, 2048, 4096])
@pytest.mark.parametrize("key_hi", [4, 1 << 16, 1 << 30])
def test_sort_pairs_matches_lexsort(n, key_hi):
    kv = RNG.integers(0, key_hi, n).astype(np.int32)
    val = RNG.integers(0, 1 << 20, n).astype(np.int32)
    got_kv, got_val = ops.sort_pairs(jnp.asarray(kv), jnp.asarray(val))
    order = np.lexsort((np.arange(n), kv))  # by key variable, then input order
    np.testing.assert_array_equal(np.asarray(got_kv), kv[order])
    np.testing.assert_array_equal(np.asarray(got_val), val[order])


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
# lower_bound, upper_bound
# ---------------------------------------------------------------------------


def _assert_bounds(keys, queries):
    lo = ops.lower_bound(jnp.asarray(keys), jnp.asarray(queries))
    hi = ops.upper_bound(jnp.asarray(keys), jnp.asarray(queries))
    np.testing.assert_array_equal(np.asarray(lo), np.searchsorted(keys, queries, "left"))
    np.testing.assert_array_equal(np.asarray(hi), np.searchsorted(keys, queries, "right"))


@pytest.mark.parametrize("n", [2048, 4096, 8192])
@pytest.mark.parametrize("q", [256, 512])
def test_bounds_match_searchsorted(n, q):
    keys = np.sort(RNG.integers(0, 1 << 20, n)).astype(np.int32)
    _assert_bounds(keys, RNG.integers(0, 1 << 20, q).astype(np.int32))


def test_bounds_hit_every_boundary():
    keys = np.repeat(np.arange(8) * 4, 256).astype(np.int32)
    _assert_bounds(keys, (np.arange(256) % 36).astype(np.int32))


@pytest.mark.parametrize("n,q", [(2048, 256), (1 << 14, 512)])  # searchsorted, fenced
def test_bounds_int32_max_guard(n, q):
    """Duplicate runs, a placebo tail and the extreme queries 0,
    MAX_USER_KEY, PLACEBO_KEY and INT32_MAX, which no stored key exceeds."""
    keys = np.sort(RNG.integers(0, 1 << 16, n - 256)).astype(np.int32)
    keys = np.concatenate([keys, np.full(256, sem.PLACEBO_KEY, np.int32)])
    queries = RNG.integers(0, 1 << 16, q).astype(np.int32)
    queries[:4] = [0, sem.MAX_USER_KEY, sem.PLACEBO_KEY, INT32_MAX]
    _assert_bounds(keys, queries)


# ---------------------------------------------------------------------------
# queries.lookup_runs
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("num_runs", [1, 2, 3, 5])
@pytest.mark.parametrize("key_hi", [8, 500, 1 << 20])
def test_lookup_runs_multi_run(num_runs, key_hi):
    runs = [_sorted_run(256 << i, key_hi) for i in range(num_runs)]
    _assert_lookup(runs, RNG.integers(0, key_hi + 2, 300))


def test_lookup_runs_empty_levels_are_invisible():
    # Placebo-only runs between real runs never win a query.
    real1 = _sorted_run(256, 100, tombstone_frac=0.0)
    real2 = _sorted_run(512, 100, tombstone_frac=0.5)
    _assert_lookup([real1, _placebo_run(256), real2, _placebo_run(512)], np.arange(0, 110))


def test_lookup_runs_buffer_only():
    _assert_lookup([_sorted_run(256, 40, tombstone_frac=0.3)], np.arange(0, 48))


def test_lookup_runs_all_placebo_finds_nothing():
    found, vals = lookup_runs([_placebo_run(512)], jnp.arange(256, dtype=jnp.int32))
    assert not np.asarray(found).any()
    assert (np.asarray(vals) == sem.EMPTY_VALUE).all()


def test_lookup_runs_dup_tombstone_heavy_newest_wins():
    # Tiny key space: every key occurs in several runs with mixed status.
    _assert_lookup([_sorted_run(256, 6, tombstone_frac=0.5) for _ in range(4)], np.arange(0, 8))


def test_lookup_runs_newer_run_shadows_older():
    # Same key everywhere: the first (newest) run's element wins ...
    k = 7
    runs = [(jnp.full((256,), (k << 1) | 1, jnp.int32), jnp.full((256,), 100 + i, jnp.int32))
            for i in range(3)]
    q = jnp.full((256,), k, jnp.int32)
    found, vals = lookup_runs(runs, q)
    assert np.asarray(found).all() and (np.asarray(vals) == 100).all()
    # ... and a newest tombstone shadows older inserts.
    runs[0] = (jnp.full((256,), k << 1, jnp.int32), jnp.zeros((256,), jnp.int32))
    found, vals = lookup_runs(runs, q)
    assert not np.asarray(found).any()
    assert (np.asarray(vals) == sem.EMPTY_VALUE).all()


def _buffer_run(n, key_hi):
    """A write-buffer run: lanes in arrival order, 1/4 placebo padding, sorted
    by the recency rule (newest first within a key, whatever its status)."""
    keys = RNG.integers(0, key_hi, n).astype(np.int32)
    kv = ((keys << 1) | (RNG.random(n) > 0.3)).astype(np.int32)
    kv[3 * n // 4:] = sem.PLACEBO_KV
    val = RNG.integers(1, 1 << 20, n).astype(np.int32)
    return ops.sort_pairs_recency(jnp.asarray(kv), jnp.asarray(val))


@pytest.mark.parametrize("buffer", [False, True])
@pytest.mark.parametrize("key_hi", [64, 1 << 12, 1 << 20])
def test_lookup_runs_across_the_fenced_threshold(key_hi, buffer):
    """Levels on both sides of `search.MIN_N` in one lookup: the smaller runs
    take `searchsorted`, the larger the fenced descent, and the newest run
    holding a key still decides it."""
    runs = [_sorted_run(n, key_hi) for n in
            (search.MIN_N // 4, search.MIN_N // 2, search.MIN_N, 2 * search.MIN_N)]
    if buffer:
        runs.insert(0, _buffer_run(1000, key_hi))
    queries = np.concatenate([RNG.integers(0, key_hi + 2, 2000), [0, key_hi, INT32_MAX]])
    with ops.record_paths() as paths:
        _assert_lookup(runs, queries)
    assert {path for _, path in paths} == {"xla", "xla_fenced"}


def test_lookup_runs_end_to_end():
    """Real LSM states at 0..max resident runs: the lookup matches a dict
    oracle replay exactly, including buffer-only and empty states."""
    from repro.core import LSMConfig, all_runs, lsm_init, lsm_update

    cfg = LSMConfig(batch_size=256, num_levels=3)
    state = lsm_init(cfg)
    oracle = {}
    queries = np.arange(0, 600, dtype=np.int32)

    def check(state, tag):
        found, vals = lookup_runs(all_runs(cfg, state), jnp.array(queries))
        found, vals = np.asarray(found), np.asarray(vals)
        exp_f = np.array([int(k) in oracle for k in queries])
        np.testing.assert_array_equal(found, exp_f, err_msg=tag)
        exp_v = np.array([oracle.get(int(k), 0) for k in queries])
        np.testing.assert_array_equal(
            np.where(found, vals, 0), np.where(exp_f, exp_v, 0), err_msg=tag
        )

    check(state, "empty")
    rng = np.random.default_rng(9)
    for step in range(5):  # fills levels through several cascades
        # Unique keys per batch: the core's in-batch rule (paper §3.3,
        # tombstone-first after the sort) differs from arrival order,
        # so duplicate keys inside ONE batch have no dict-oracle
        # meaning. Cross-batch duplicates still churn heavily.
        keys = rng.choice(500, 256, replace=False).astype(np.int32)
        dels = rng.random(256) < 0.3
        kv = jnp.array(((keys << 1) | (~dels).astype(np.int32)).astype(np.int32))
        vals = jnp.array(rng.integers(1, 1000, 256).astype(np.int32))
        state = lsm_update(cfg, state, kv, vals)
        for k, v, d in zip(keys.tolist(), np.asarray(vals).tolist(), dels.tolist()):
            if d:
                oracle.pop(k, None)
            else:
                oracle[k] = v
        check(state, f"after update {step} (r={int(state.r)})")


# ---------------------------------------------------------------------------
# record_paths: the path each dispatch takes, by shape alone
# ---------------------------------------------------------------------------


def _dispatch(op, n):
    keys = jnp.asarray(np.sort(RNG.integers(0, 1 << 16, n)).astype(np.int32))
    queries = jnp.asarray(RNG.integers(0, 1 << 16, 256).astype(np.int32))
    if op == "bounds":
        ops.lower_bound(keys, queries)
        ops.upper_bound(keys, queries)
    elif op == "lookup_level":
        ops.lookup_level(keys, keys, queries)
    elif op == "merge_cascade":
        ops.merge_cascade([(keys, keys), (keys, keys)])
    else:
        ops.sort_pairs(keys, keys)


@pytest.mark.parametrize("op,n,logged", [
    ("bounds", 2048, [("lower_bound", "xla"), ("upper_bound", "xla")]),
    ("bounds", search.MIN_N - 128, [("lower_bound", "xla"), ("upper_bound", "xla")]),
    ("bounds", search.MIN_N + 1, [("lower_bound", "xla"), ("upper_bound", "xla")]),  # not whole rows
    ("bounds", search.MIN_N, [("lower_bound", "xla_fenced"), ("upper_bound", "xla_fenced")]),
    ("bounds", 1 << 18, [("lower_bound", "xla_fenced"), ("upper_bound", "xla_fenced")]),
    ("lookup_level", search.MIN_N - 128, [("lower_bound", "xla")]),
    ("lookup_level", search.MIN_N + 1, [("lower_bound", "xla")]),
    ("lookup_level", search.MIN_N, [("lower_bound", "xla_fenced")]),
    ("lookup_level", 1 << 18, [("lower_bound", "xla_fenced")]),
    ("merge_cascade", search.MIN_N, [("merge_cascade", "xla")]),
    ("sort_pairs", search.MIN_N, [("sort", "xla")]),
])
def test_record_paths_reports_each_dispatch(op, n, logged):
    with ops.record_paths() as paths:
        _dispatch(op, n)
    assert paths == logged
