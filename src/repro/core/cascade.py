"""Binary-counter cascade engine — the shared core of every LSM mutation.

`lsm_update`, `lsm_stage`, `lsm_flush` (all pushing a carry batch through the
binary counter), `lsm_bulk_build`, `lsm_cleanup`, and `lsm_maintain` (all
re-slicing a sorted survivor prefix into levels) previously each carried their
own copy of the merge/compact/redistribute machinery. This module is the
single implementation:

  * `push_batch` — one binary-counter increment. Where the old `_cascade`
    walked the levels with a chain of pairwise merges (each `lax.cond` step
    either merging or COPYING the carry past a non-participating level, so
    every update paid an O(b * 2^L) carry round trip regardless of where it
    landed), `push_batch` computes the placement level j = lowest zero bit of
    r up front and dispatches ONE `lax.switch` branch that performs a single
    fused K-way merge of [carry, level 0..j-1] (`ops.merge_cascade`). The
    executed program is O(b * 2^j) — the paper's amortized O(b log r) bound
    now holds per-branch, not just amortized over the cond chain.
  * `compact_run` — survivor compaction by one sort on the masked key: the
    survivors in key order, then placebos.
  * `redistribute` — slice a sorted, unique-key prefix into levels by the
    bits of the new resident count (generalized to a level prefix, which is
    what budgeted maintenance compacts).
  * `run_stale_count` — per-run compaction-debt measurement, taken on the
    merged run a cascade step just produced (the only moment cross-batch
    staleness inside that run is visible for free).

This module deliberately imports only `semantics` and `kernels.ops`, so
`core/lsm.py` can import it at module level without a cycle.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import semantics as sem
from repro.kernels import ops


def _placebo(n):
    return (
        jnp.full((n,), sem.PLACEBO_KV, dtype=jnp.int32),
        jnp.full((n,), sem.EMPTY_VALUE, dtype=jnp.int32),
    )


def merged_batches(r):
    """Batches one push into counter r merges: 2^t, t the trailing ones of r.

    The carry joins the t full levels below the placement level, which hold
    2^t - 1 batches; the result is the lowest zero bit of r (int32 scalar).
    """
    r = jnp.asarray(r, jnp.int32)
    return (~r) & (r + 1)


def placement_level(r):
    """Index of the lowest zero bit of r — the level a carry batch lands in.

    The binary-counter increment r -> r+1 clears exactly the trailing-ones
    block and sets the bit above it; that bit's level receives the merge of
    the carry with all the cleared (full) levels below.
    """
    return jax.lax.population_count(merged_batches(r) - 1).astype(jnp.int32)


def run_stale_count(run_kv):
    """Resident real elements of one sorted run that a compaction of that run
    alone could reclaim: duplicates shadowed within the run plus tombstones.

    This is the per-level compaction-debt measurement. It is an ESTIMATE of
    what maintenance will actually reclaim — tombstones must be retained while
    older levels still hold data, and duplicate pairs split across two
    not-yet-merged levels are invisible until a merge brings them into one
    run — but it is exact for the run in isolation, costs one mask sum on an
    array that was just materialized anyway, and queries never depend on it
    (docs/DESIGN.md §11).
    """
    from repro.core.queries import survivor_mask

    real = jnp.sum(sem.original_key(run_kv) != sem.PLACEBO_KEY).astype(jnp.int32)
    return real - jnp.sum(survivor_mask(run_kv)).astype(jnp.int32)


@jax.named_scope("lsm.push")
def push_batch(cfg, state, carry_kv, carry_val):
    """Push one pre-sorted b-wide batch through the binary-counter cascade.

    The carry must be ascending in original key with the newest element first
    within every equal-key segment (the run invariant every query assumes).
    Both batch-formation rules feed this: `lsm_update` sorts by full key
    variable (paper §4.1 — tombstone-first within a batch) and the write
    buffer sorts by arrival sequence (docs/DESIGN.md §5 — newest-first).

    Placement level j = lowest zero bit of r; levels 0..j-1 are full by
    construction, and [carry, level 0..j-1] (newest first) K-way merge into
    level j, which sizes exactly to b * 2^j. Levels above j pass through
    untouched (buffer donation forwards them), as do the write-buffer fields.
    On overflow (r == max_batches) the state is preserved and the latch set.
    `merged` counts the 2^j batches merged (none on overflow). Its ops run
    under the `lsm.push` name scope, which a device trace reports per op.
    """
    num_levels = cfg.num_levels
    would_overflow = state.r >= cfg.max_batches
    branch_idx = jnp.where(
        would_overflow, jnp.int32(num_levels), placement_level(state.r)
    )

    def make_branch(j):
        def branch(kvs, vals, debt, ckv, cval):
            merged_kv, merged_val = ops.merge_cascade(
                [(ckv, cval)] + [(kvs[i], vals[i]) for i in range(j)]
            )
            new_kvs, new_vals = [], []
            for i in range(num_levels):
                if i < j:
                    pk, pv = _placebo(cfg.level_size(i))
                    new_kvs.append(pk)
                    new_vals.append(pv)
                elif i == j:
                    new_kvs.append(merged_kv)
                    new_vals.append(merged_val)
                else:
                    new_kvs.append(kvs[i])
                    new_vals.append(vals[i])
            new_debt = jnp.concatenate(
                [
                    jnp.zeros((j,), jnp.int32),
                    run_stale_count(merged_kv)[None],
                    debt[j + 1 :],
                ]
            )
            return tuple(new_kvs), tuple(new_vals), new_debt

        return branch

    def overflow_branch(kvs, vals, debt, ckv, cval):
        return tuple(kvs), tuple(vals), debt

    branches = [make_branch(j) for j in range(num_levels)] + [overflow_branch]
    new_kvs, new_vals, new_debt = jax.lax.switch(
        branch_idx,
        branches,
        state.key_vars,
        state.values,
        state.lvl_debt,
        carry_kv,
        carry_val,
    )
    return state._replace(
        key_vars=new_kvs,
        values=new_vals,
        lvl_debt=new_debt,
        r=jnp.where(would_overflow, state.r, state.r + 1),
        overflowed=state.overflowed | would_overflow,
        merged=state.merged + jnp.where(would_overflow, 0, merged_batches(state.r)),
    )


@jax.named_scope("lsm.compact")
def compact_run(merged_kv, merged_val, keep, out_size: int):
    """Move the keep-masked elements of a merged run to the front, in key
    order, and pad the rest with placebos (the paper's "pad with placebo
    elements" step), giving arrays of length `out_size`.

    Precondition: the kept elements are ascending in original key, with
    unique original keys. Then one unstable 2-operand sort on the masked key
    variable is a stable partition: every kept key variable is below
    PLACEBO_KV, so the survivors land first in their own order, and every
    other element becomes an identical (PLACEBO_KV, EMPTY_VALUE) pair.

    Returns (kv, val, total) where total is the UNCLAMPED survivor count;
    survivors past out_size (the largest keys) are dropped, so the caller
    decides whether an overflow latches.
    """
    total = jnp.sum(keep).astype(jnp.int32)
    kv = jnp.where(keep, merged_kv, sem.PLACEBO_KV)
    val = jnp.where(keep, merged_val, sem.EMPTY_VALUE)
    kv, val = jax.lax.sort((kv, val), dimension=0, is_stable=False, num_keys=1)
    pad = out_size - kv.shape[0]
    if pad > 0:
        pk, pv = _placebo(pad)
        return jnp.concatenate([kv, pk]), jnp.concatenate([val, pv]), total
    return kv[:out_size], val[:out_size], total


def redistribute(cfg, compact_kv, compact_val, r_new, hi_level: int | None = None):
    """Slice a globally sorted, unique-key array into levels 0..hi_level.

    Level i (if bit i of r_new is set) receives the contiguous slice starting
    at b * (r_new & (2**i - 1)) — smallest keys land in the smallest levels
    (paper §4.5). With hi_level < num_levels - 1 this re-slices just a level
    PREFIX, which is exactly what budgeted maintenance rebuilds; full cleanup
    and bulk build use the default (all levels).

    Returns (kvs, vals) as tuples of length hi_level + 1.
    """
    if hi_level is None:
        hi_level = cfg.num_levels - 1
    b = cfg.batch_size
    kvs, vals = [], []
    for i in range(hi_level + 1):
        n = cfg.level_size(i)
        bit = ((r_new >> i) & 1) == 1
        src_start = b * (r_new & ((1 << i) - 1))
        sl_kv = jax.lax.dynamic_slice(compact_kv, (src_start,), (n,))
        sl_val = jax.lax.dynamic_slice(compact_val, (src_start,), (n,))
        pk, pv = _placebo(n)
        kvs.append(jnp.where(bit, sl_kv, pk))
        vals.append(jnp.where(bit, sl_val, pv))
    return tuple(kvs), tuple(vals)
