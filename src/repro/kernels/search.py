"""Fenced lower-bound search of sorted runs: the XLA path's search.

A sorted run of n = R·LANES slots is viewed as R rows of LANES keys. The last
key of a row is its fence, and the number of fences below a query is the
number of rows wholly below it: the query's answer lies in the next row. That
count comes from the same descent applied to the fences (padded to whole rows
with INT32_MAX) until at most BASE fences remain, where a dense compare and
count against all of them takes the place of a search. Each round of the
descent is then one `(q, LANES)` row gather, the shape of an embedding lookup,
where `jnp.searchsorted` makes one dependent scalar gather per bit of n inside
a `while` loop. A run of 2^27 slots takes three row rounds instead of 28.

The answer is exact for any sorted run: duplicates across row boundaries,
runs of one key and runs of placebos included. `shift` searches key variables
by their original keys (`key >> shift`) without shifting the whole run: only
the fences and the gathered rows are shifted.
"""

from __future__ import annotations

import jax.numpy as jnp

LANES = 128            # keys per row: the lane width of a TPU vector register
BASE = 1 << 11         # at most this many fences: a dense count, no gather
MIN_N = 1 << 14        # below this, searchsorted takes 14 rounds or fewer
INT32_MAX = jnp.iinfo(jnp.int32).max


def viable(n: int) -> bool:
    """Whether a run of n slots takes the fenced search."""
    return n % LANES == 0 and n >= MIN_N


def _below(keys, queries, side):
    """Per query, which `keys` count before it: `<` for "left", `<=` for
    "right" (the broadcast of queries against the trailing axis of keys)."""
    q = queries[:, None]
    return keys < q if side == "left" else keys <= q


def _count_fences(fences, queries, side):
    """Per query, the number of sorted `fences` before it, or more when a
    "right" query counts the padding (below)."""
    m = fences.shape[0]
    if m <= BASE:
        return jnp.sum(_below(fences[None, :], queries, side), axis=1, dtype=jnp.int32)
    # The padding keeps the fences sorted. A "right" query of INT32_MAX counts
    # it too, but only when it counts every real fence: the caller's clamp to
    # the last row gives the same row either way.
    pad = -m % LANES
    if pad:
        fences = jnp.concatenate([fences, jnp.full((pad,), INT32_MAX, jnp.int32)])
    return descend(fences, queries, side)[0]


def descend(sorted_keys, queries, side="left", shift=0):
    """Rank each query in a sorted run of n = R·LANES keys.

    Returns `(idx, r, row)`: `idx[i]` the number of keys `key >> shift`
    before `queries[i]` (std::lower_bound for "left", upper_bound for
    "right"), `r[i]` the row of LANES keys it ends in, and `row[i]` that row
    as stored (unshifted), so the caller reads the slot at `idx - r·LANES`
    when it is below LANES (it is LANES only when idx == n).
    """
    rows = sorted_keys.reshape(-1, LANES)
    n_rows = rows.shape[0]
    queries = jnp.asarray(queries, jnp.int32)
    wholly_below = _count_fences(rows[:, -1] >> shift, queries, side)
    # Row j holds the answer, or every key is before the query (j == R): the
    # last row then counts all LANES, so r·LANES + count == n either way.
    r = jnp.minimum(wholly_below, n_rows - 1)
    row = rows.at[r].get(mode="promise_in_bounds")
    idx = r * LANES + jnp.sum(_below(row >> shift, queries, side), axis=1, dtype=jnp.int32)
    return idx, r, row


def lower_bound_fenced(sorted_keys, queries, side="left", shift=0):
    """`jnp.searchsorted(sorted_keys >> shift, queries, side)` as int32, for
    a run whose length `viable` accepts."""
    return descend(sorted_keys, queries, side, shift)[0]
