"""jit'd dispatch wrappers for the LSM compute hot-spots: one XLA path per op.

Merges and sorts are one `lax.sort` (kernels/ref.py). The search of a run is
chosen from its length alone: the fenced row-gather descent
(kernels/search.py, path "xla_fenced") when `search.viable` accepts it, else
`jnp.searchsorted` (kernels/ref.py, path "xla"). `record_paths()` reports
which path every dispatch took.
"""

from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp

from repro.kernels import ref, search

# Open `record_paths()` logs; every dispatch appends (op, path) to each.
_PATH_LOGS: list = []


@contextlib.contextmanager
def record_paths():
    """Collect `(op, path)` for every dispatch traced inside the block, path
    "xla_fenced" or "xla".

    Dispatch runs while a jitted program is traced, so a program that is
    already compiled records nothing: open the block around its first call.
    """
    log: list = []
    _PATH_LOGS.append(log)
    try:
        yield log
    finally:
        _PATH_LOGS.remove(log)


def _log(op: str, path: str) -> None:
    for log in _PATH_LOGS:
        log.append((op, path))


def _took_fenced(op: str, sorted_keys) -> bool:
    """Whether a run is searched by the fenced descent (`search.viable`),
    logged as "xla_fenced" if so and as "xla" if not."""
    fenced = search.viable(sorted_keys.shape[0])
    _log(op, "xla_fenced" if fenced else "xla")
    return fenced


def merge_cascade(runs):
    """K-way stable merge of sorted runs ordered NEWEST FIRST.

    runs: [(key_vars, values), ...]; ties on original key resolve to the
    earliest (newest) run, within a run to the earliest index.

    One binary-counter cascade step, a cleanup, and `valid_count_runs` are all
    K-way merges: one stable sort (`ref.merge_cascade_ref`).
    """
    runs = [(jnp.asarray(kv, jnp.int32), jnp.asarray(v, jnp.int32)) for kv, v in runs]
    if len(runs) == 1:
        return runs[0]
    _log("merge_cascade", "xla")
    return ref.merge_cascade_ref([kv for kv, _ in runs], [v for _, v in runs])


def sort_pairs(key_vars, values):
    """Sort (key_var, value) pairs by full key variable, stable."""
    _log("sort", "xla")
    return ref.sort_ref(key_vars, values)


def sort_pairs_recency(key_vars, values):
    """Sort by ORIGINAL key; within equal keys the later input lane sorts
    first (newest-first), regardless of status bit.

    This is the write-buffer batch-formation rule (docs/DESIGN.md §5): strict
    arrival order decides duplicates, unlike `sort_pairs`, whose full-key-
    variable ordering makes a tombstone beat any same-batch insert of its key
    (the paper's in-batch rule). Placebos sort last (maximum original key).
    """
    from repro.core import semantics as sem

    n = key_vars.shape[0]
    key_vars = jnp.asarray(key_vars, jnp.int32)
    values = jnp.asarray(values, jnp.int32)
    orig = sem.original_key(key_vars)
    rev = jnp.arange(n, 0, -1, dtype=jnp.int32)  # later lane -> smaller rev
    _, _, out_kv, out_val = jax.lax.sort(
        (orig, rev, key_vars, values), dimension=0, is_stable=True, num_keys=2
    )
    return out_kv, out_val


def lower_bound(sorted_orig_keys, query_keys):
    """Vectorized lower-bound (first index with key >= query)."""
    if _took_fenced("lower_bound", sorted_orig_keys):
        return search.lower_bound_fenced(sorted_orig_keys, query_keys, "left")
    return ref.lower_bound_ref(sorted_orig_keys, query_keys)


def upper_bound(sorted_orig_keys, query_keys):
    """Vectorized upper-bound (first index with key > query)."""
    if _took_fenced("upper_bound", sorted_orig_keys):
        return search.lower_bound_fenced(sorted_orig_keys, query_keys, "right")
    return ref.upper_bound_ref(sorted_orig_keys, query_keys)


def lookup_level(level_kv, level_val, query_keys):
    """One-level lookup probe: (hit, is_tomb, value) of the first slot whose
    original key is >= each query.

    A run the fenced descent accepts is searched by its key variables' rows
    (`search.descend` with shift 1): the probed slot is read from the key row
    the descent gathered and its value from one row gather of `level_val`,
    so the level is never shifted or gathered slot by slot. Other runs take
    `jnp.searchsorted` over the level's original keys.
    """
    from repro.core import semantics as sem

    query_keys = jnp.asarray(query_keys, jnp.int32)
    n = level_kv.shape[0]
    if _took_fenced("lower_bound", level_kv):
        idx, r, kv_rows = search.descend(level_kv, query_keys, "left", shift=1)
        val_rows = level_val.reshape(-1, search.LANES).at[r].get(mode="promise_in_bounds")
        # The probed slot's lane, the last one when idx == n.
        lane = jnp.minimum(idx - r * search.LANES, search.LANES - 1)
        probed = jnp.arange(search.LANES, dtype=jnp.int32) == lane[:, None]
        found_kv = jnp.sum(jnp.where(probed, kv_rows, 0), axis=1, dtype=jnp.int32)
        found_val = jnp.sum(jnp.where(probed, val_rows, 0), axis=1, dtype=jnp.int32)
    else:
        idx = ref.lower_bound_ref(sem.original_key(level_kv), query_keys)
        idx_c = jnp.clip(idx, 0, n - 1)
        found_kv = level_kv[idx_c]
        found_val = level_val[idx_c]
    hit = (idx < n) & (sem.original_key(found_kv) == query_keys)
    return hit, sem.is_tombstone(found_kv), found_val
