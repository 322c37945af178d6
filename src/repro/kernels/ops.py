"""jit'd dispatch wrappers for the LSM compute hot-spots.

Backends:
  "xla"    — plain XLA programs, the default: merges and sorts are one
             `lax.sort` (kernels/ref.py); the search of a run takes the fenced
             row-gather descent (kernels/search.py) when the run's length
             allows it (path "xla_fenced"), else `jnp.searchsorted`
             (kernels/ref.py, path "xla").
  "pallas" — the Pallas TPU kernels (merge_path / bitonic_sort / lsm_lookup)
             with explicit BlockSpec VMEM tiling. On non-TPU platforms the
             kernels execute in interpret mode (used by the test suite to
             validate the kernel bodies against the oracles).

Selection: `set_backend(...)` or the REPRO_KERNEL_BACKEND env var. A shape
that does not tile for a kernel takes the "xla" path even on the Pallas
backend; `record_paths()` reports which path every dispatch took.
"""

from __future__ import annotations

import contextlib
import os

import jax
import jax.numpy as jnp

from repro.kernels import ref, search

_BACKEND = os.environ.get("REPRO_KERNEL_BACKEND", "xla")
# Open `record_paths()` logs; every dispatch appends (op, path) to each.
_PATH_LOGS: list = []


def set_backend(name: str) -> None:
    global _BACKEND
    if name not in ("xla", "pallas"):
        raise ValueError(f"unknown kernel backend {name!r}")
    _BACKEND = name


def get_backend() -> str:
    return _BACKEND


def _interpret() -> bool:
    """Pallas kernels compile for a TPU and run in interpret mode elsewhere.

    Asked at dispatch (trace) time, not at import, so importing this module
    never initializes a JAX backend."""
    return jax.default_backend() != "tpu"


@contextlib.contextmanager
def record_paths():
    """Collect `(op, path)` for every dispatch traced inside the block, path
    one of "pallas", "pallas_interpret", "xla_fenced" or "xla".

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


def _took(op: str, kernel: bool) -> bool:
    _log(op, ("pallas_interpret" if _interpret() else "pallas") if kernel else "xla")
    return kernel


def _took_fenced(op: str, sorted_keys) -> bool:
    """The XLA backend searches a run by the fenced descent when its length
    allows (`search.viable`); `record_paths` names that path "xla_fenced"."""
    fenced = _BACKEND == "xla" and search.viable(sorted_keys.shape[0])
    if fenced:
        _log(op, "xla_fenced")
    return fenced


def _pallas_viable_search(sorted_orig_keys, query_keys) -> bool:
    from repro.kernels import lsm_lookup

    n, q = sorted_orig_keys.shape[0], query_keys.shape[0]
    return (
        _BACKEND == "pallas"
        and n % lsm_lookup.LEVEL_CHUNK == 0
        and q % lsm_lookup.QUERY_BLOCK == 0
    )


def merge_cascade(runs):
    """K-way stable merge of sorted runs ordered NEWEST FIRST.

    runs: [(key_vars, values), ...]; ties on original key resolve to the
    earliest (newest) run, within a run to the earliest index.

    One binary-counter cascade step, a cleanup, and `valid_count_runs` are all
    K-way merges; on the Pallas backend they stream every element through VMEM
    exactly once (`merge_path.merge_cascade_path`); on XLA they are one stable
    sort (`ref.merge_cascade_ref`).
    """
    runs = [(jnp.asarray(kv, jnp.int32), jnp.asarray(v, jnp.int32)) for kv, v in runs]
    if len(runs) == 1:
        return runs[0]
    from repro.kernels import merge_path

    viable = _BACKEND == "pallas" and all(
        kv.shape[0] % merge_path.BLOCK == 0 and kv.shape[0] >= merge_path.BLOCK
        for kv, _ in runs
    )
    if _took("merge_cascade", viable):
        return merge_path.merge_cascade_path(
            [kv for kv, _ in runs], [v for _, v in runs], interpret=_interpret()
        )
    return ref.merge_cascade_ref([kv for kv, _ in runs], [v for _, v in runs])


def sort_pairs(key_vars, values):
    """Sort (key_var, value) pairs by full key variable, stable."""
    from repro.kernels import bitonic_sort

    n = key_vars.shape[0]
    viable = _BACKEND == "pallas" and n >= bitonic_sort.MIN_N and (n & (n - 1)) == 0
    if _took("sort", viable):
        return bitonic_sort.bitonic_sort_pairs(key_vars, values, interpret=_interpret())
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
    if _took("lower_bound", _pallas_viable_search(sorted_orig_keys, query_keys)):
        from repro.kernels import lsm_lookup

        return lsm_lookup.lower_bound_streamed(
            sorted_orig_keys, query_keys, interpret=_interpret()
        )
    return ref.lower_bound_ref(sorted_orig_keys, query_keys)


def upper_bound(sorted_orig_keys, query_keys):
    """Vectorized upper-bound (first index with key > query).

    For integer keys, upper_bound(k) == lower_bound(k + 1), so the streamed
    Pallas lower-bound kernel accelerates both ends of the count/range
    window. Guard: k + 1 would wrap at INT32_MAX, but every key the structure
    can store (user keys plus the placebo key, all < 2**30) compares <= such
    a query, so the answer is simply n.
    """
    if _took_fenced("upper_bound", sorted_orig_keys):
        return search.lower_bound_fenced(sorted_orig_keys, query_keys, "right")
    if _took("upper_bound", _pallas_viable_search(sorted_orig_keys, query_keys)):
        from repro.kernels import lsm_lookup

        n = sorted_orig_keys.shape[0]
        qk = jnp.asarray(query_keys, jnp.int32)
        safe = qk < jnp.iinfo(jnp.int32).max
        lo = lsm_lookup.lower_bound_streamed(
            sorted_orig_keys, jnp.where(safe, qk + 1, qk), interpret=_interpret()
        )
        return jnp.where(safe, lo, jnp.asarray(n, jnp.int32))
    return ref.upper_bound_ref(sorted_orig_keys, query_keys)


def lookup_runs_fused(runs, query_keys):
    """Fused multi-run LOOKUP dispatch: (found, values) or None.

    Selected on the Pallas backend: concatenates the newest-first runs into
    one flat array (placebo-padded to the chunk size), pads the queries to the
    query-block size, and issues ONE fused streaming kernel instead of one
    `lower_bound` launch per run (`lsm_lookup.fused_lookup_runs`). Returns
    None when not selected — the caller (core/queries.py::lookup_runs) falls
    back to the per-run resolution loop.
    """
    if not _took("lookup", _BACKEND == "pallas"):
        return None
    from repro.core import semantics as sem
    from repro.kernels import lsm_lookup

    chunk = lsm_lookup.FUSED_CHUNK
    qb = lsm_lookup.FUSED_QUERY_BLOCK
    flat_kv = jnp.concatenate([jnp.asarray(kv, jnp.int32) for kv, _ in runs])
    flat_val = jnp.concatenate([jnp.asarray(v, jnp.int32) for _, v in runs])
    pad_n = -flat_kv.shape[0] % chunk
    if pad_n:
        flat_kv = jnp.concatenate([flat_kv, jnp.full((pad_n,), sem.PLACEBO_KV, jnp.int32)])
        flat_val = jnp.concatenate([flat_val, jnp.full((pad_n,), sem.EMPTY_VALUE, jnp.int32)])
    qk = jnp.asarray(query_keys, jnp.int32)
    nq = qk.shape[0]
    pad_q = -nq % qb
    qk_padded = jnp.concatenate([qk, jnp.full((pad_q,), sem.PLACEBO_KEY, jnp.int32)]) if pad_q else qk
    best_kv, best_val = lsm_lookup.fused_lookup_runs(
        flat_kv, flat_val, qk_padded, interpret=_interpret()
    )
    best_kv, best_val = best_kv[:nq], best_val[:nq]
    hit = sem.original_key(best_kv) == qk
    found = hit & ~sem.is_tombstone(best_kv)
    return found, jnp.where(found, best_val, sem.EMPTY_VALUE)


def lookup_level(level_kv, level_val, query_keys):
    """One-level lookup probe: (hit, is_tomb, value) of the first slot whose
    original key is >= each query.

    A run the fenced descent accepts is searched by its key variables' rows
    (`search.descend` with shift 1): the probed slot is read from the key row
    the descent gathered and its value from one row gather of `level_val`,
    so the level is never shifted or gathered slot by slot. Other runs take
    `lower_bound` over the level's original keys.
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
        idx = lower_bound(sem.original_key(level_kv), query_keys)
        idx_c = jnp.clip(idx, 0, n - 1)
        found_kv = level_kv[idx_c]
        found_val = level_val[idx_c]
    hit = (idx < n) & (sem.original_key(found_kv) == query_keys)
    return hit, sem.is_tombstone(found_kv), found_val
