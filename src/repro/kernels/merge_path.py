"""Merge Path Pallas kernel — the LSM's cascade-merge hot-spot on TPU.

The paper uses moderngpu's Merge Path merge (diagonal partition + per-CTA
shared-memory merges). The TPU adaptation, generalised to K runs at once:

  * The partition (where every output tile boundary splits every run) is a
    vectorized XLA computation (`cascade_partition`). Its result is handed
    to the kernel as a *scalar prefetch* operand, the TPU analogue of reading
    partition points from global memory before the CTA starts.
  * Each grid step merges one BLOCK-sized output tile. Its run windows are
    data-dependent, so the BlockSpec index maps are driven by the prefetched
    partition: each run fetches the two consecutive BLOCK-blocks that cover
    its (unaligned, <= BLOCK long) window — HBM→VMEM copies stay block-aligned
    and coalesced, and the unaligned window is carved out in-register with a
    lane rotation.
  * The in-tile merge is rank-based and branch-free: an all-pairs comparison
    matrix ([BLOCK x BLOCK] int ops on the VPU) yields each element's local
    rank; a one-hot placement materializes the tile. No serial merge loop,
    no divergence — this replaces the warp-wide serial merges of the CUDA
    version, which have no SIMD-lockstep analogue on the VPU.

Semantics match `ref.merge_ref` / `ref.merge_cascade_ref`: compare original
keys (status bit ignored), stable, ties taken from the newer (earlier) run
first. With `compare_full=True` the comparison uses the full key variable
instead — used by the hierarchical large-batch sort in bitonic_sort.py.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK = 256
# Output tiles per pallas_call. The partition bounds of those tiles ride in
# SMEM as scalar prefetch ([K, SEG_TILES + 1] int32, well under its 1 MiB);
# longer merges loop over segments of the output.
SEG_TILES = 2048
_INT32_MAX = jnp.iinfo(jnp.int32).max


def _window(buf2, start, block0, length, fill):
    """Carve an unaligned window [start, start+BLOCK) out of two fetched blocks.

    buf2: [2, 2*BLOCK] (kv row 0, val row 1) — two adjacent BLOCK-blocks.
    The window's offset inside them is data-dependent, so it is carved with a
    lane rotation (one XLU op) rather than a gather or an unaligned slice,
    neither of which Mosaic lowers. Returns [1, BLOCK] kv / val rows with
    lanes >= length masked to `fill` (kv) / 0 (val).
    """
    shift = start - block0 * BLOCK  # in [0, BLOCK] — BLOCK only when length 0
    rolled = pltpu.roll(buf2, (2 * BLOCK - shift) % (2 * BLOCK), 1)[:, :BLOCK]
    valid = jax.lax.broadcasted_iota(jnp.int32, (1, BLOCK), 1) < length
    return (
        jnp.where(valid, rolled[0:1, :], fill),
        jnp.where(valid, rolled[1:2, :], 0),
    )


def _merge_rows(a_kv, a_val, la, b_kv, b_val, lb, shift):
    """Stable merge of two [1, BLOCK] rows; `a` is newer and wins ties.

    Lanes past la / lb are invalid: their comparison keys become INT32_MAX,
    so invalid `a` lanes rank at or past la + lb and invalid `b` lanes rank
    past BLOCK. Returns the first BLOCK outputs as [1, BLOCK] rows.

    All-pairs ranks need one operand down the sublanes: one transpose of an
    [8, BLOCK] stack turns the six rows into columns. Placement is a one-hot
    compare of the (unique) ranks against the output lane, reduced over the
    sublanes — the scatter without a scatter.
    """
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, BLOCK), 1)
    a_cmp = jnp.where(lane < la, a_kv >> shift, _INT32_MAX)
    b_cmp = jnp.where(lane < lb, b_kv >> shift, _INT32_MAX)
    rows = (a_cmp, b_cmp, a_kv, b_kv, a_val, b_val)
    sub = jax.lax.broadcasted_iota(jnp.int32, (8, BLOCK), 0)
    stack = jnp.zeros((8, BLOCK), jnp.int32)
    for i, r in enumerate(rows):
        stack = jnp.where(sub == i, r, stack)
    cols = stack.T  # [BLOCK, 8]
    a_cmp_c, b_cmp_c, a_kv_c, b_kv_c, a_val_c, b_val_c = (
        cols[:, i : i + 1] for i in range(len(rows))
    )
    idx = jax.lax.broadcasted_iota(jnp.int32, (BLOCK, 1), 0)
    # a[i] precedes b[j] iff a_cmp[i] <= b_cmp[j].
    rank_a = idx + jnp.sum((b_cmp < a_cmp_c).astype(jnp.int32), axis=1, keepdims=True)
    rank_b = idx + jnp.sum((a_cmp <= b_cmp_c).astype(jnp.int32), axis=1, keepdims=True)
    at_a = rank_a == lane  # [BLOCK, BLOCK] one-hot: input i -> output lane
    at_b = rank_b == lane

    def place(a_col, b_col):
        both = jnp.where(at_a, a_col, 0) + jnp.where(at_b, b_col, 0)
        return jnp.sum(both, axis=0, keepdims=True)

    return place(a_kv_c, b_kv_c), place(a_val_c, b_val_c)


def merge_path(a_kv, a_val, b_kv, b_val, *, compare_full=False, interpret=False):
    """Merge two sorted runs (a = newer). Shapes must be multiples of BLOCK.

    The two-run case of `merge_cascade_path`: one kernel serves both."""
    return merge_cascade_path(
        [a_kv, b_kv], [a_val, b_val], compare_full=compare_full, interpret=interpret
    )


# ---------------------------------------------------------------------------
# K-way cascade merge: stream K runs through VMEM in one pallas_call
# ---------------------------------------------------------------------------
#
# A binary-counter cascade step merges the carry batch with levels 0..j-1 —
# previously a CHAIN of pairwise merges, each round-tripping the growing
# intermediate through HBM (the carry is written and re-read j times). The
# K-way kernel generalizes Merge Path: the diagonal partition becomes a
# *key-space* binary search (`cascade_partition`) that splits ALL K runs at
# every output-tile boundary simultaneously, and each grid step merges its K
# windows in VMEM with K-1 rank-based all-pairs merges. Every input element
# crosses HBM exactly once, regardless of K.


def cascade_partition(runs_keys, diags):
    """K-way Merge-Path split: bounds[s, d] = #elements of run s among the
    first diags[d] outputs of the K-way merge.

    Runs are ordered newest first; ties on the comparison key resolve by run
    order (earlier run first), and within a run by index — identical to a
    left fold of pairwise merges with the accumulated (newer) side winning
    ties, which is what `ref.merge_cascade_ref` computes.

    Instead of searching each diagonal's simplex directly (K-dimensional), we
    binary-search the KEY SPACE: for diagonal d find the smallest key k* with
    N_leq(k*) >= d (31 halvings over the int32 key domain, each a vectorized
    searchsorted per run over all diagonals at once). The first d outputs are
    then all elements with key < k*, plus t = d - N_less(k*) elements of the
    key == k* segments taken in run order.
    """
    diags = jnp.asarray(diags, jnp.int32)

    def halve(_, lohi):
        lo, hi = lohi
        mid = lo + (hi - lo) // 2
        n_leq = sum(
            jnp.searchsorted(ks, mid, side="right").astype(jnp.int32)
            for ks in runs_keys
        )
        pred = n_leq >= diags
        return jnp.where(pred, lo, mid + 1), jnp.where(pred, mid, hi)

    # A loop, not 31 unrolled copies: a cascade step holds one partition per
    # placement level, and unrolled they dominate its compile time.
    lo, _ = jax.lax.fori_loop(
        0, 31, halve, (jnp.zeros_like(diags), jnp.full_like(diags, _INT32_MAX))
    )
    kstar = lo  # d == 0 degenerates to kstar == 0, bounds 0 (keys are >= 0)
    lbs = [jnp.searchsorted(ks, kstar, side="left").astype(jnp.int32) for ks in runs_keys]
    ubs = [jnp.searchsorted(ks, kstar, side="right").astype(jnp.int32) for ks in runs_keys]
    n_less = sum(lbs)
    t = diags - n_less  # elements still needed from the key == k* segments
    bounds = []
    prefix = jnp.zeros_like(diags)
    for lb, ub in zip(lbs, ubs):
        seg = ub - lb
        bounds.append(lb + jnp.clip(t - prefix, 0, seg))
        prefix = prefix + seg
    return jnp.stack(bounds)  # [K, len(diags)]


def _cascade_kernel(off_ref, bounds_ref, *refs, ns, shift):
    """Merge one BLOCK-wide output tile from K run windows.

    refs: 2 fetched blocks per run (adjacent BLOCK-blocks covering its
    window), the aliased output buffer (untouched here), then the output
    block. bounds_ref holds this segment's partition columns; off_ref its
    first output tile. The K windows (total length exactly BLOCK) fold
    left-to-right with the rank-based all-pairs merge `_merge_rows`; the
    accumulated side is the newer one (earlier runs), so it takes ties.
    Lanes past the accumulated length hold whatever invalid lanes ranked
    there, and `_merge_rows` masks them by length, so they never corrupt
    valid output lanes.
    """
    del off_ref
    o_ref = refs[-1]
    t = pl.program_id(0)
    acc_kv = acc_val = acc_len = None
    for s in range(len(ns)):
        start = bounds_ref[s, t]
        ln = bounds_ref[s, t + 1] - start
        blk = jnp.minimum(start // BLOCK, ns[s] // BLOCK - 1)
        buf = jnp.concatenate([refs[2 * s][...], refs[2 * s + 1][...]], axis=1)
        kv, val = _window(buf, start, blk, ln, _INT32_MAX)
        if acc_kv is None:
            acc_kv, acc_val, acc_len = kv, val, ln
            continue
        acc_kv, acc_val = _merge_rows(acc_kv, acc_val, acc_len, kv, val, ln, shift)
        acc_len = acc_len + ln
    o_ref[0:1, :] = acc_kv
    o_ref[1:2, :] = acc_val


def merge_cascade_path(runs_kv, runs_val, *, compare_full=False, interpret=False):
    """K-way merge of sorted runs, newest first. Lengths multiples of BLOCK.

    Semantics match a left fold of pairwise merges (`ref.merge_cascade_ref`),
    but each element crosses HBM once instead of once per fold step. The
    output is produced SEG_TILES tiles per kernel launch, in a loop that
    writes each segment in place into one output buffer.
    """
    k = len(runs_kv)
    assert k >= 1 and len(runs_val) == k
    if k == 1:
        return runs_kv[0], runs_val[0]
    ns = [kv.shape[0] for kv in runs_kv]
    assert all(n % BLOCK == 0 for n in ns), ns
    total = sum(ns)
    n_tiles = total // BLOCK
    seg = math.gcd(n_tiles, SEG_TILES)
    shift = 0 if compare_full else 1
    run_keys = [(kv >> shift) if shift else kv for kv in runs_kv]
    diags = jnp.arange(n_tiles + 1, dtype=jnp.int32) * BLOCK
    bounds = cascade_partition(run_keys, diags)  # [K, n_tiles + 1]

    stacks = [jnp.stack([kv, val]) for kv, val in zip(runs_kv, runs_val)]

    def make_idx(s, delta, nblocks):
        def idx(t, off, bounds):
            return (0, jnp.minimum(bounds[s, t] // BLOCK + delta, nblocks - 1))

        return idx

    in_specs = []
    operands = []
    for s in range(k):
        nblocks = ns[s] // BLOCK
        in_specs.append(pl.BlockSpec((2, BLOCK), make_idx(s, 0, nblocks)))
        in_specs.append(pl.BlockSpec((2, BLOCK), make_idx(s, 1, nblocks)))
        operands.extend([stacks[s], stacks[s]])
    in_specs.append(pl.BlockSpec(memory_space=pl.ANY))  # the output, aliased

    merge_segment = pl.pallas_call(
        functools.partial(_cascade_kernel, ns=tuple(ns), shift=shift),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(seg,),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((2, BLOCK), lambda t, off, bounds: (0, off[0] + t)),
        ),
        out_shape=jax.ShapeDtypeStruct((2, total), jnp.int32),
        input_output_aliases={2 + len(operands): 0},
        name="merge_cascade_path",
        interpret=interpret,
    )

    def body(i, out):
        first = i * seg
        seg_bounds = jax.lax.dynamic_slice(bounds, (0, first), (k, seg + 1))
        return merge_segment(first[None], seg_bounds, *operands, out)

    out = jax.lax.fori_loop(
        0, n_tiles // seg, body, jnp.zeros((2, total), jnp.int32)
    )
    return out[0], out[1]
