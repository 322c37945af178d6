"""Pure-jnp merges, sorts and searches: the XLA path's merges and sorts,
and `jnp.searchsorted` for runs the fenced search does not take
(kernels/ops.py); also the reference the fenced search (kernels/search.py)
is tested against. Everything here is a sort or a binary search, with no
data-dependent control flow.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import semantics as sem


def merge_cascade_ref(runs_kv, runs_val):
    """K-way stable merge of sorted runs ordered NEWEST first, comparing
    ORIGINAL keys only.

    For equal original keys, elements of earlier (newer) runs precede those
    of later runs (paper §4.1 — "new levels merged into existing levels
    appear first in the merged result"); within a run, input order holds.
    That is exactly a stable sort of the runs concatenated newest first, so
    this is one `lax.sort` — no gathers or scatters, which the TPU executes
    slowly at the paper's sizes.
    """
    kv = jnp.concatenate([jnp.asarray(x, jnp.int32) for x in runs_kv])
    val = jnp.concatenate([jnp.asarray(x, jnp.int32) for x in runs_val])
    _, kv, val = jax.lax.sort(
        (sem.original_key(kv), kv, val), dimension=0, is_stable=True, num_keys=1
    )
    return kv, val


def sort_ref(key_vars, values):
    """Sort a batch by FULL key variable (status bit included), stable.

    Sorting by the full key variable puts a tombstone for key k before any
    regular element with key k from the same batch (paper §4.1), which makes
    same-batch insert-then-delete resolve to "deleted" (semantics item 6).
    """
    return jax.lax.sort((key_vars, values), dimension=0, is_stable=True, num_keys=1)


def lower_bound_ref(sorted_orig_keys, query_keys):
    """Index of the first element >= query (std::lower_bound)."""
    return jnp.searchsorted(sorted_orig_keys, query_keys, side="left").astype(jnp.int32)


def upper_bound_ref(sorted_orig_keys, query_keys):
    return jnp.searchsorted(sorted_orig_keys, query_keys, side="right").astype(jnp.int32)


def lookup_level_ref(level_kv, level_val, query_keys):
    """One level of the LSM lookup: lower-bound search + match/status check.

    Returns (hit, is_tomb, value): hit marks queries whose lower-bound element
    has a matching original key; is_tomb marks hits that are tombstones
    (resolve to "deleted"); value is the payload for regular hits.
    """
    orig = sem.original_key(level_kv)
    idx = jnp.searchsorted(orig, query_keys, side="left").astype(jnp.int32)
    idx_c = jnp.clip(idx, 0, level_kv.shape[0] - 1)
    found_kv = level_kv[idx_c]
    found_val = level_val[idx_c]
    in_range = idx < level_kv.shape[0]
    hit = in_range & (sem.original_key(found_kv) == query_keys)
    is_tomb = sem.is_tombstone(found_kv)
    return hit, is_tomb, found_val
