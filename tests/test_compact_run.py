"""`cascade.compact_run` against a NumPy stable partition of the kept elements.

The merged runs follow the cleanup's input: ascending in original key, with
duplicates, tombstones and a placebo tail. Both callers' keep rules are used:
`survivor_mask` (cleanup) and the newest element per key with tombstones
retained (`_compact_prefix` below the compaction horizon). Every case checks
kv, val and the unclamped total bit for bit, and a placebo tail.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import cascade
from repro.core import semantics as sem
from repro.core.queries import survivor_mask

N = 4096


def _merged_run(seed, n=N, n_placebo=512, key_space=1500):
    """A run sorted by original key (stable: recency order within a key is
    the draw order), with shadowed duplicates, tombstones and placebos."""
    rng = np.random.default_rng(seed)
    keys = rng.integers(0, key_space, n - n_placebo)
    keys[:8] = sem.MAX_USER_KEY  # the largest real key variables
    tomb = rng.random(n - n_placebo) < 0.3
    kv = np.where(tomb, keys << 1, (keys << 1) | 1).astype(np.int32)
    val = np.where(tomb, sem.EMPTY_VALUE, rng.integers(1, 1 << 31, n - n_placebo))
    order = np.argsort(keys, kind="stable")
    kv = np.concatenate([kv[order], np.full(n_placebo, sem.PLACEBO_KV, np.int32)])
    val = np.concatenate([val[order], np.full(n_placebo, sem.EMPTY_VALUE)])
    return kv, val.astype(np.int32)


def _all_placebo(seed):
    return (
        np.full(N, sem.PLACEBO_KV, np.int32),
        np.full(N, sem.EMPTY_VALUE, np.int32),
    )


def _all_unique_regular(seed):
    rng = np.random.default_rng(seed)
    keys = np.sort(rng.choice(1 << 20, N, replace=False))
    return ((keys << 1) | 1).astype(np.int32), rng.integers(1, 1 << 31, N).astype(np.int32)


def _survivors(kv):
    return np.asarray(survivor_mask(jnp.asarray(kv)))


def _newest_per_key(kv):
    """`_compact_prefix`'s keep while deeper levels hold residents: the
    first element of each key's segment, tombstones included."""
    orig = kv >> 1
    prev = np.concatenate([[-1], orig[:-1]])
    return (orig != prev) & (orig != sem.PLACEBO_KEY)


def _reference(kv, val, keep, out_size):
    kept_kv, kept_val = kv[keep][:out_size], val[keep][:out_size]
    pad = out_size - len(kept_kv)
    return (
        np.concatenate([kept_kv, np.full(pad, sem.PLACEBO_KV, np.int32)]),
        np.concatenate([kept_val, np.full(pad, sem.EMPTY_VALUE, np.int32)]),
        int(keep.sum()),
    )


CASES = {
    # name: (run, keep rule, out_size as a function of the kept count)
    "survivor_mask": (_merged_run, _survivors, lambda kept: N),
    "prefix_keeps_tombstones": (_merged_run, _newest_per_key, lambda kept: N),
    "overflow_drops_largest": (_merged_run, _newest_per_key, lambda kept: kept - 100),
    "out_size_past_input": (_merged_run, _survivors, lambda kept: N + 300),
    "all_placebo": (_all_placebo, _survivors, lambda kept: N),
    "all_kept": (_all_unique_regular, _survivors, lambda kept: N),
    "all_kept_overflow": (_all_unique_regular, _survivors, lambda kept: N - 1),
}


@pytest.mark.parametrize("seed", [0, 1])
@pytest.mark.parametrize("case", sorted(CASES))
def test_compact_run_matches_stable_partition(case, seed):
    make, keep_rule, size_of = CASES[case]
    kv, val = make(seed)
    keep = keep_rule(kv)
    out_size = size_of(int(keep.sum()))
    want_kv, want_val, want_total = _reference(kv, val, keep, out_size)

    got_kv, got_val, got_total = cascade.compact_run(
        jnp.asarray(kv), jnp.asarray(val), jnp.asarray(keep), out_size
    )
    got_kv, got_val = np.asarray(got_kv), np.asarray(got_val)

    assert got_kv.shape == got_val.shape == (out_size,)
    np.testing.assert_array_equal(got_kv, want_kv)
    np.testing.assert_array_equal(got_val, want_val)
    assert int(got_total) == want_total  # unclamped, past out_size too
    tail = slice(min(want_total, out_size), None)
    assert (got_kv[tail] == sem.PLACEBO_KV).all()
    assert (got_val[tail] == sem.EMPTY_VALUE).all()
    if case == "all_placebo":
        assert want_total == 0
    if case.startswith("all_kept"):
        assert want_total == N
    if case == "overflow_drops_largest":
        assert want_total > out_size
        assert got_kv[-1] < kv[keep][out_size]  # the largest kept keys went
