"""CLEANUP (paper §3.6 / §4.5) and budgeted incremental maintenance.

The paper's CLEANUP is stop-the-world: merge everything, drop stale elements,
re-slice the levels. That rebuild is O(capacity) no matter how little debt the
structure carries, which shows up as a latency spike in any serving loop
(LUDA's observation — compactions belong off the hot path, amortized into
bounded slices). Both operations here are built on the shared cascade engine
(core/cascade.py):

  * `lsm_cleanup(cfg, state)` — the full rebuild, unchanged contract:
      1. ONE fused K-way merge of the write buffer (newest) and every level
         (`ops.merge_cascade` — previously a pairwise chain);
      2. survivor mask: first of each equal-key segment, regular, not placebo;
      3. compact the survivors by one sort on the masked key (`compact_run`:
         non-survivors become placebos and sort last, which IS the paper's
         "pad with < b placebos" step);
      4. re-slice by the bits of the new resident count (`redistribute`).
    Folding the buffer into the merge empties it without burning a batch
    slot; because the buffer adds up to b elements beyond the level arenas,
    survivors can exceed capacity — the excess (largest keys) is dropped and
    the overflow latch set, same contract as an overflowing update.

  * `lsm_maintain(cfg, state, budget)` — incremental compaction bounded by a
    STATIC element budget per call. It compacts the deepest level PREFIX
    0..j whose total arena fits the budget (b * (2^(j+1) - 1) <= budget),
    with one fused merge + compact + prefix re-slice; levels above j and the
    write buffer are untouched. Correctness of the partial view:
      - within the prefix, only the newest element of each key survives —
        dropping older shadowed duplicates can never change a query, because
        every query already resolves to the newest match;
      - tombstones are PURGED only when no deeper level holds residents
        ((r >> (j+1)) == 0); otherwise they must survive to keep shadowing
        older elements below the compaction horizon;
      - prefix survivors stay newer than the untouched deeper levels, and
        keys are unique within the prefix, so the re-sliced levels satisfy
        the run invariant with no recency ambiguity.
    Survivors never exceed the prefix arena (no buffer is folded in), so
    maintenance can never overflow. `budget=None` (or >= capacity + b, i.e.
    enough for everything including the buffer) degrades to full
    `lsm_cleanup` — maintain(∞) IS cleanup. A budget below b is a no-op.

    The resident-batch counter keeps its high bits: r' = (r & ~mask) | ceil(
    survivors / b) with mask = 2^(j+1) - 1 — the binary counter simply shows
    fewer resident batches in the compacted prefix.

Maintenance debt is tracked per level in `LSMState.lvl_debt` (see
cascade.run_stale_count); `only_if_debt=True` gates the work behind a traced
prefix-debt check so piggybacked maintenance (facade update/flush paths) costs
one comparison when there is provably nothing to reclaim.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import cascade
from repro.core import semantics as sem
from repro.core.lsm import (
    LSMConfig,
    LSMState,
    _fresh_buffer,
    buffer_run,
    level_view,
)
from repro.kernels import ops


def merge_all_levels(cfg: LSMConfig, state: LSMState):
    """Stable newest-first merge of every level into one sorted run."""
    return ops.merge_cascade(
        [level_view(cfg, state, i) for i in range(cfg.num_levels)]
    )


def lsm_cleanup(cfg: LSMConfig, state: LSMState) -> LSMState:
    from repro.core.queries import survivor_mask

    b = cfg.batch_size
    runs = [buffer_run(cfg, state)] + [
        level_view(cfg, state, i) for i in range(cfg.num_levels)
    ]
    merged_kv, merged_val = ops.merge_cascade(runs)
    survives = survivor_mask(merged_kv)
    # Unique, ascending survivors: the first element of each equal-key segment.
    compact_kv, compact_val, total = cascade.compact_run(
        merged_kv, merged_val, survives, cfg.capacity
    )
    overflow = total > cfg.capacity
    total_kept = jnp.minimum(total, cfg.capacity)
    r_new = ((total_kept + b - 1) // b).astype(jnp.int32)
    kvs, vals = cascade.redistribute(cfg, compact_kv, compact_val, r_new)
    return LSMState(
        key_vars=kvs,
        values=vals,
        r=r_new,
        overflowed=state.overflowed | overflow,
        lvl_debt=jnp.zeros((cfg.num_levels,), dtype=jnp.int32),
        merged=state.merged + (1 << cfg.num_levels),  # every slot and the buffer
        **_fresh_buffer(b),
    )


def maintain_prefix_level(cfg: LSMConfig, budget: int) -> int:
    """Deepest level j whose prefix arena 0..j fits the budget
    (b * (2^(j+1) - 1) <= budget); -1 when even level 0 does not fit."""
    j = -1
    for i in range(cfg.num_levels):
        if cfg.batch_size * ((1 << (i + 1)) - 1) <= budget:
            j = i
    return j


def _compact_prefix(cfg: LSMConfig, state: LSMState, j: int) -> LSMState:
    b = cfg.batch_size
    prefix_n = b * ((1 << (j + 1)) - 1)
    merged_kv, merged_val = ops.merge_cascade(
        [level_view(cfg, state, i) for i in range(j + 1)]
    )
    orig = sem.original_key(merged_kv)
    prev = jnp.concatenate([jnp.full((1,), -1, jnp.int32), orig[:-1]])
    newest_per_key = (orig != prev) & (orig != sem.PLACEBO_KEY)
    # Tombstones may only be purged when nothing older exists below the
    # compaction horizon — otherwise they still shadow deeper elements. The
    # write buffer is NEWER than the prefix, so it never constrains this.
    covers_all = (state.r >> (j + 1)) == 0
    keep = jnp.where(
        covers_all, newest_per_key & ~sem.is_tombstone(merged_kv), newest_per_key
    )
    # Unique, ascending survivors: `keep` implies the newest element per key.
    compact_kv, compact_val, total = cascade.compact_run(
        merged_kv, merged_val, keep, prefix_n
    )
    # total <= prefix_n by construction: at most one survivor per prefix key.
    r_prefix = ((total + b - 1) // b).astype(jnp.int32)
    kvs, vals = cascade.redistribute(cfg, compact_kv, compact_val, r_prefix, hi_level=j)
    mask = (1 << (j + 1)) - 1
    return state._replace(
        key_vars=kvs + state.key_vars[j + 1 :],
        values=vals + state.values[j + 1 :],
        r=(state.r & ~mask) | r_prefix,
        # Prefix debt resets; retained tombstones re-enter the estimate the
        # next time a cascade merge re-materializes these levels.
        lvl_debt=jnp.concatenate(
            [jnp.zeros((j + 1,), jnp.int32), state.lvl_debt[j + 1 :]]
        ),
        merged=state.merged + (prefix_n // b),
    )


def lsm_maintain(
    cfg: LSMConfig,
    state: LSMState,
    budget: int | None = None,
    *,
    only_if_debt: bool = False,
) -> LSMState:
    """Budgeted incremental compaction: touch at most `budget` elements.

    budget is STATIC (a Python int or None). None — or any budget large
    enough for the whole structure plus the write buffer — performs a full
    `lsm_cleanup`. Otherwise the deepest affordable level prefix is compacted
    (see module docstring); a budget below b is a no-op. Queries are exact at
    every point of this spectrum — maintenance is observationally invisible,
    which the differential harness checks by interleaving random maintain
    ops into oracle-replayed sequences.

    only_if_debt=True skips the compaction (traced lax.cond) when the
    tracked prefix debt is zero — the cheap gate for piggybacked maintenance
    on facade update/flush paths.
    """
    if budget is None or budget >= cfg.capacity + cfg.batch_size:
        return lsm_cleanup(cfg, state)
    j = maintain_prefix_level(cfg, budget)
    if j < 0:
        return state
    if only_if_debt:
        return jax.lax.cond(
            jnp.sum(state.lvl_debt[: j + 1]) > 0,
            lambda st: _compact_prefix(cfg, st, j),
            lambda st: st,
            state,
        )
    return _compact_prefix(cfg, state, j)


def lsm_valid_count(cfg: LSMConfig, state: LSMState):
    """Number of live (visible) elements — what cleanup would retain
    (write-buffer residents included)."""
    from repro.core.queries import valid_count_runs
    from repro.core.lsm import all_runs

    return valid_count_runs(all_runs(cfg, state))
