"""The XLA path's fenced row-gather search (kernels/search.py) against
`jnp.searchsorted`, bitwise, and the dictionaries that take it by shape
against the dict oracle."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import Dictionary, QueryPlan, dictionary
from repro.core import semantics as sem
from repro.kernels import ops, ref, search

from harness import boundary_keys, query_ranges, run_differential

INT32_MAX = np.iinfo(np.int32).max
EDGE_QUERIES = [INT32_MAX, -1, sem.PLACEBO_KEY, 0, sem.MAX_USER_KEY, INT32_MAX - 1]


def _random(rng, n):
    return np.sort(rng.integers(0, sem.MAX_USER_KEY + 1, n))


def _one_key_run(length):
    def make(rng, n):
        keys = _random(rng, n)
        at = int(rng.integers(0, n - length))
        keys[at:at + length] = keys[at]
        return np.sort(keys)
    return make


# name -> (n, keys(rng, n)): the run searched. Each holds original keys; the
# fenced path takes it iff n is a multiple of 128 and at least search.MIN_N.
RUNS = {
    "below_threshold": (search.MIN_N - 128, _random),
    "at_threshold": (search.MIN_N, _random),
    "2^18": (1 << 18, _random),
    "not_whole_rows": (search.MIN_N + 100, _random),
    "fences_padded": (search.LANES * (search.BASE + 5), _random),
    "one_key_over_a_row": (search.MIN_N, _one_key_run(3 * search.LANES + 7)),
    "one_key_over_128^2_slots": (1 << 18, _one_key_run(search.LANES ** 2 + 300)),
    "all_placebo": (1 << 16, lambda rng, n: np.full(n, sem.PLACEBO_KEY)),
    "duplicates_across_rows": (1 << 15, lambda rng, n: np.sort(rng.integers(0, 300, n))),
}


def _queries(rng, keys, q):
    """Edge queries first (below the minimum, above the maximum, the placebo
    key, INT32_MAX), then the run's own keys and their neighbours, then
    random ones."""
    own = rng.choice(keys, q)
    pool = np.concatenate([
        EDGE_QUERIES, [keys[0] - 1, keys[0], keys[-1], keys[-1] + 1],
        own, own + 1, own - 1, rng.integers(-8, sem.PLACEBO_KEY + 8, q),
    ])
    return np.clip(pool, -(1 << 31), INT32_MAX).astype(np.int32)[:q]


@pytest.mark.parametrize("q", [1, 1000, 1 << 12])
@pytest.mark.parametrize("run", sorted(RUNS) + ["mixed_status_key_variables"])
def test_fenced_search_equals_searchsorted(run, q):
    rng = np.random.default_rng(sum(map(ord, run)) + q)
    if run == "mixed_status_key_variables":
        # Sorted by original key only: the status bits of equal keys come in
        # any order, so the key variables themselves are not sorted.
        n = 1 << 16
        orig = np.sort(rng.integers(0, 5000, n))
        kv = ((orig << 1) | rng.integers(0, 2, n)).astype(np.int32)
        val = rng.integers(-(1 << 31), INT32_MAX, n, dtype=np.int64).astype(np.int32)
        queries = _queries(rng, orig, q)
        for side in ("left", "right"):
            got = search.lower_bound_fenced(jnp.asarray(kv), jnp.asarray(queries), side, shift=1)
            np.testing.assert_array_equal(np.asarray(got), np.searchsorted(orig, queries, side))
        with ops.record_paths() as paths:
            got = ops.lookup_level(jnp.asarray(kv), jnp.asarray(val), jnp.asarray(queries))
        want = ref.lookup_level_ref(jnp.asarray(kv), jnp.asarray(val), jnp.asarray(queries))
        for g, w in zip(got, want):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
        assert paths == [("lower_bound", "xla_fenced")]
        return

    n, make = RUNS[run]
    keys = make(rng, n).astype(np.int32)
    queries = _queries(rng, keys, q)
    with ops.record_paths() as paths:
        lo = ops.lower_bound(jnp.asarray(keys), jnp.asarray(queries))
        hi = ops.upper_bound(jnp.asarray(keys), jnp.asarray(queries))
    np.testing.assert_array_equal(np.asarray(lo), np.searchsorted(keys, queries, "left"))
    np.testing.assert_array_equal(np.asarray(hi), np.searchsorted(keys, queries, "right"))
    path = "xla_fenced" if n % 128 == 0 and n >= 1 << 14 else "xla"
    assert paths == [("lower_bound", path), ("upper_bound", path)]


B = 1 << 13
NUM_LEVELS = 3  # levels of 2^13, 2^14 and 2^15 slots: the last two are fenced
CAPACITY = B * ((1 << NUM_LEVELS) - 1)


@pytest.mark.parametrize("seed", [0, 1])
def test_dictionaries_with_fenced_runs_match_the_oracle(seed, monkeypatch):
    """lookup / size / count / range of `lsm` and `sorted_array` at a
    capacity whose larger runs take the fenced search, against the dict
    oracle and each other, through updates, deletes, flushes and cleanups."""
    # Paths are recorded while a program is traced: start from no program.
    monkeypatch.setattr(dictionary, "_EXEC_CACHE", {})
    rng = np.random.default_rng(seed)
    pool = np.unique(np.concatenate([
        boundary_keys(), rng.integers(0, 1 << 20, 4000), rng.integers(0, sem.MAX_USER_KEY, 1000)
    ]))

    def churn(n):  # keys drawn with replacement, 40% deletes
        return ("update", rng.choice(pool, n), rng.integers(-1000, 1000, n).astype(np.int32),
                rng.random(n) < 0.4)

    # At most 3·b + 1 + |pool| lanes between cleanups: no level overflows.
    steps = [("update", pool, np.arange(len(pool), dtype=np.int32), np.zeros(len(pool), bool)),
             churn(2 * B + 1), ("flush",), churn(B - 3), ("cleanup",), churn(3 * B), ("flush",)]
    k1, k2 = query_ranges(pool)
    queries = np.unique(np.concatenate([pool, np.clip(pool + 1, 0, sem.MAX_USER_KEY)]))
    dicts = {
        "lsm": Dictionary.create("lsm", batch_size=B, num_levels=NUM_LEVELS),
        "sorted_array": Dictionary.create("sorted_array", batch_size=B, capacity=CAPACITY),
    }
    with ops.record_paths() as paths:
        run_differential(
            dicts, steps, plan=QueryPlan(max_candidates=CAPACITY, max_results=CAPACITY),
            query_keys=queries, k1=k1, k2=k2, check_every=2,
        )
    assert {("lower_bound", "xla_fenced"), ("upper_bound", "xla_fenced"),
            ("lower_bound", "xla")} <= set(paths)
