"""Bulk queries over the LSM: lookup, count, range (paper §3.4–3.5, §4.2–4.4).

All three queries are expressed over *runs*: a list of sorted (key_var, value)
arrays ordered newest-first. The LSM passes its levels (level 0 first); the
sorted-array baseline passes its single run — the validation logic is shared.

The count/range pipeline is the paper's five-stage bulk algorithm, adapted to
fixed shapes (TPU-native: no dynamic allocation):
  1. per-run lower/upper bound searches                   (paper stage 1)
  2. per-query candidate offsets via prefix sums          (paper stage 2)
  3. gather candidates into a [num_queries, max_candidates]
     padded tile, placebo-filled                          (paper stage 3)
  4. row-wise stable sort by original key — the segmented
     sort; recency order is preserved by stability        (paper stage 4)
  5. mask arithmetic validation: count/emit the first
     element of each equal-key segment iff it is regular  (paper stage 5)

The paper's warp-ballot counting in stage 5 has no TPU analogue; dense mask
arithmetic over the padded tile is the VPU-idiomatic equivalent
(docs/DESIGN.md §8).

The LSM entry points query `all_runs`: the write buffer (sorted on demand,
newest-first within equal keys — docs/DESIGN.md §5) is the newest run, so
staged sub-batch updates — including buffer-resident tombstones — are visible
to lookup/count/range/size before any flush.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import semantics as sem
from repro.core.lsm import LSMConfig, LSMState, all_runs
from repro.kernels import ops

INT32_MAX = jnp.iinfo(jnp.int32).max


# ---------------------------------------------------------------------------
# LOOKUP
# ---------------------------------------------------------------------------


def lookup_runs(runs, query_keys):
    """LOOKUP(k) over newest-first runs: first matching run wins; tombstone → ⊥.

    Each run is probed by `ops.lookup_level`: a run of whole 128-key rows and
    at least 2^14 slots by the fenced row-gather descent (kernels/search.py),
    any other by `jnp.searchsorted`.
    """
    query_keys = jnp.asarray(query_keys, jnp.int32)
    nq = query_keys.shape[0]
    resolved = jnp.zeros((nq,), dtype=bool)
    found = jnp.zeros((nq,), dtype=bool)
    result = jnp.full((nq,), sem.EMPTY_VALUE, dtype=jnp.int32)
    for kv, val in runs:
        hit, tomb, v = ops.lookup_level(kv, val, query_keys)
        newly = hit & ~resolved
        found = found | (newly & ~tomb)
        result = jnp.where(newly & ~tomb, v, result)
        resolved = resolved | newly
    return found, result


def lsm_lookup(cfg: LSMConfig, state: LSMState, query_keys):
    """Batched LOOKUP: returns (found: bool[nq], values: int32[nq])."""
    return lookup_runs(all_runs(cfg, state), query_keys)


# ---------------------------------------------------------------------------
# COUNT / RANGE candidate pipeline
# ---------------------------------------------------------------------------


def _gather_candidates(runs, k1, k2, max_candidates):
    """Stages 1–4: gather + segment-sort candidates for [k1, k2] queries.

    Returns (orig, kv, val, total, ok):
      orig/kv/val: [nq, max_candidates] row-sorted by original key, stable in
        recency (newest first within equal keys); placebo padding sorts last.
      total: exact number of candidates per query (before truncation).
      ok: total <= max_candidates (results are exact iff ok).
    """
    k1 = jnp.asarray(k1, jnp.int32)
    k2 = jnp.asarray(k2, jnp.int32)
    nq = k1.shape[0]
    n_runs = len(runs)

    lows, counts = [], []
    for kv, _ in runs:
        orig = sem.original_key(kv)
        lo = ops.lower_bound(orig, k1)
        hi = ops.upper_bound(orig, k2)
        lows.append(lo)
        counts.append(jnp.maximum(hi - lo, 0))
    counts_m = jnp.stack(counts, axis=0)          # [n_runs, nq]
    offsets = jnp.cumsum(counts_m, axis=0) - counts_m  # exclusive scan over runs
    total = jnp.sum(counts_m, axis=0)             # [nq]
    ok = total <= max_candidates

    # Stage 3: slot j of a query row maps to (run, within-run index).
    slots = jnp.arange(max_candidates, dtype=jnp.int32)[None, :]  # [1, M]
    gather_idx = jnp.zeros((nq, max_candidates), dtype=jnp.int32)
    valid_slot = jnp.zeros((nq, max_candidates), dtype=bool)
    flat_starts = []
    start = 0
    for kv, _ in runs:
        flat_starts.append(start)
        start += kv.shape[0]
    for r in range(n_runs):
        off = offsets[r][:, None]                 # [nq, 1]
        cnt = counts_m[r][:, None]
        sel = (slots >= off) & (slots < off + cnt)
        idx = flat_starts[r] + lows[r][:, None] + (slots - off)
        gather_idx = jnp.where(sel, idx, gather_idx)
        valid_slot = valid_slot | sel

    all_kv = jnp.concatenate([kv for kv, _ in runs])
    all_val = jnp.concatenate([val for _, val in runs])
    cand_kv = jnp.where(valid_slot, all_kv[gather_idx], sem.PLACEBO_KV)
    cand_val = jnp.where(valid_slot, all_val[gather_idx], sem.EMPTY_VALUE)

    # Stage 4: segmented (row-wise) stable sort by ORIGINAL key. Rows were
    # built newest-run-first, so stability preserves recency within segments.
    cand_orig = sem.original_key(cand_kv)
    sort_row = lambda o, kv, v: jax.lax.sort((o, kv, v), dimension=0, is_stable=True, num_keys=1)
    orig_s, kv_s, val_s = jax.vmap(sort_row)(cand_orig, cand_kv, cand_val)
    return orig_s, kv_s, val_s, total, ok


def _validate(orig_s, kv_s):
    """Stage 5: first element of each equal-key segment, iff regular."""
    nq, m = orig_s.shape
    prev = jnp.concatenate([jnp.full((nq, 1), -1, jnp.int32), orig_s[:, :-1]], axis=1)
    first_of_segment = orig_s != prev
    regular = ~sem.is_tombstone(kv_s)
    not_placebo = orig_s != sem.PLACEBO_KEY
    return first_of_segment & regular & not_placebo


def count_runs(runs, k1, k2, max_candidates):
    """COUNT(k1, k2) over runs. Returns (counts: int32[nq], ok: bool[nq])."""
    orig_s, kv_s, _, _, ok = _gather_candidates(runs, k1, k2, max_candidates)
    valid = _validate(orig_s, kv_s)
    return jnp.sum(valid, axis=1).astype(jnp.int32), ok


def range_runs(runs, k1, k2, max_candidates, max_results):
    """RANGE(k1, k2): compacted per-query results.

    Returns (keys [nq, max_results], values [nq, max_results], counts, ok).
    Rows are padded with PLACEBO_KEY / EMPTY_VALUE beyond `counts`.
    """
    orig_s, kv_s, val_s, _, ok = _gather_candidates(runs, k1, k2, max_candidates)
    valid = _validate(orig_s, kv_s)
    counts = jnp.sum(valid, axis=1).astype(jnp.int32)
    ok = ok & (counts <= max_results)

    nq, m = orig_s.shape
    tgt = jnp.cumsum(valid, axis=1) - 1
    tgt = jnp.where(valid & (tgt < max_results), tgt, max_results)  # drop slot
    rows = jnp.broadcast_to(jnp.arange(nq)[:, None], (nq, m))
    out_keys = jnp.full((nq, max_results), sem.PLACEBO_KEY, dtype=jnp.int32)
    out_vals = jnp.full((nq, max_results), sem.EMPTY_VALUE, dtype=jnp.int32)
    out_keys = out_keys.at[rows, tgt].set(orig_s, mode="drop")
    out_vals = out_vals.at[rows, tgt].set(val_s, mode="drop")
    return out_keys, out_vals, counts, ok


def survivor_mask(key_vars):
    """The CLEANUP survivor rule over one sorted run: an element is visible
    iff it is the first (most recent) element of its equal-key segment, is
    regular (not a tombstone), and is not a placebo. Single source of truth
    for cleanup (LSM and SA) and live-size accounting."""
    orig = sem.original_key(key_vars)
    prev = jnp.concatenate([jnp.full((1,), -1, jnp.int32), orig[:-1]])
    return (orig != prev) & (~sem.is_tombstone(key_vars)) & (orig != sem.PLACEBO_KEY)


def valid_count_runs(runs):
    """Number of live (visible) elements across newest-first runs.

    Shared by every run-based backend (`Dictionary.size`): one K-way stable
    newest-first merge of the runs, then count the survivors.
    """
    merged_kv, _ = ops.merge_cascade(runs)
    return jnp.sum(survivor_mask(merged_kv)).astype(jnp.int32)


def lsm_count(cfg: LSMConfig, state: LSMState, k1, k2, max_candidates: int):
    return count_runs(all_runs(cfg, state), k1, k2, max_candidates)


def lsm_range(cfg: LSMConfig, state: LSMState, k1, k2, max_candidates: int, max_results: int):
    return range_runs(all_runs(cfg, state), k1, k2, max_candidates, max_results)
