"""Bitonic sort Pallas kernel — the batch-sort hot-spot of LSM updates.

The paper uses CUB radix sort. Radix sort is scatter-heavy (per-pass bucket
scatters), which is hostile to the TPU's vector memory; the TPU-idiomatic
equivalent of "fast device sort of a VMEM-resident tile" is a bitonic
compare-exchange network: every stage is a branch-free pair of rotations
(lane or sublane, by the partner distance) + selects — zero gathers, zero
scatters, perfect for the 8x128 VPU.

The kernel sorts CHUNK-sized tiles entirely inside VMEM (grid over tiles).
Arbitrarily large batches are handled in ops.py by a hierarchical sort:
bitonic-sorted chunks are combined with the Merge-Path kernel in compare-full
mode — exactly the LSM trick, reused one level down.

Sorting compares the FULL 32-bit key variable (status bit included), so a
tombstone lands before the regular elements of its key within a batch, which
is what makes same-batch insert-then-delete resolve to "deleted" (§4.1).
Not stable among *identical* key variables (semantically immaterial: equal
key variable => same key and same status; which duplicate survives a lookup
is unspecified by semantics item 4).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

CHUNK = 1 << 10          # elements sorted in one VMEM tile
MIN_N = 8
_LANES = 128


def _partner(x, j, cols):
    """Value at flat index i ^ j, for a tile laid out [rows, cols] row-major.

    Partners closer than a row sit in the same row (lane rotation); farther
    ones sit j // cols rows away (sublane rotation). The lower lane of each
    pair (bit j clear) reads forward, the upper lane backward.
    """
    rows = x.shape[0]
    if j < cols:
        fwd, back, axis = cols - j, j, 1
    else:
        fwd, back, axis = rows - j // cols, j // cols, 0
    return pltpu.roll(x, fwd, axis), pltpu.roll(x, back, axis)


def _compare_exchange(kv, val, flat, j, k):
    """One bitonic stage: partner distance j within ascending-by-bit-k runs.

    Each lane reads its partner (i ^ j) by rotation and keeps its own or the
    partner's pair; the swap rule is evaluated once per pair, as seen from
    the lower lane `a` with its upper partner `b`.
    """
    cols = kv.shape[1]
    lower = (flat & j) == 0
    # Direction bit: ascending iff (flat_index & k) == 0; constant across the
    # pair (j < k).
    asc = (flat & k) == 0
    kv_f, kv_b = _partner(kv, j, cols)
    val_f, val_b = _partner(val, j, cols)
    p_kv = jnp.where(lower, kv_f, kv_b)
    p_val = jnp.where(lower, val_f, val_b)
    a_kv = jnp.where(lower, kv, p_kv)
    b_kv = jnp.where(lower, p_kv, kv)
    swap = (a_kv > b_kv) == asc  # out of order w.r.t. direction
    return jnp.where(swap, p_kv, kv), jnp.where(swap, p_val, val)


def _bitonic_kernel(kv_ref, val_ref, okv_ref, oval_ref, *, n):
    kv = kv_ref[...]
    val = val_ref[...]
    flat = (
        jax.lax.broadcasted_iota(jnp.int32, kv.shape, 0) * kv.shape[1]
        + jax.lax.broadcasted_iota(jnp.int32, kv.shape, 1)
    )
    k = 2
    while k <= n:
        j = k // 2
        while j >= 1:
            kv, val = _compare_exchange(kv, val, flat, j, k)
            j //= 2
        k *= 2
    okv_ref[...] = kv
    oval_ref[...] = val


def bitonic_sort_pairs(key_vars, values, *, interpret=False):
    """Sort (key_var, value) pairs by full key variable.

    n must be a power of two. n <= CHUNK sorts in a single VMEM tile;
    larger powers of two sort CHUNK tiles in parallel grid steps and are
    merged by the caller (ops.sort_pairs_hierarchical).
    """
    n = key_vars.shape[0]
    assert n & (n - 1) == 0 and n >= MIN_N, n
    tile = min(n, CHUNK)
    n_tiles = n // tile
    # Each tile is laid out [rows, cols] row-major: (8, 128) — one vreg — for
    # a full CHUNK, a single row for tiny sorts.
    cols = min(tile, _LANES)
    rows = tile // cols
    block = pl.BlockSpec((rows, cols), lambda i: (i, 0))
    shape = jax.ShapeDtypeStruct((n // cols, cols), jnp.int32)
    kv, val = pl.pallas_call(
        functools.partial(_bitonic_kernel, n=tile),
        grid=(n_tiles,),
        in_specs=[block, block],
        out_specs=[block, block],
        out_shape=[shape, shape],
        name="bitonic_sort_pairs",
        interpret=interpret,
    )(
        key_vars.astype(jnp.int32).reshape(n // cols, cols),
        values.astype(jnp.int32).reshape(n // cols, cols),
    )
    kv, val = kv.reshape(n), val.reshape(n)
    if n_tiles > 1:
        from repro.kernels import merge_path

        # Hierarchical combine: rounds of compare-full Merge Path, each round
        # merging every adjacent pair of sorted runs in one batched launch.
        merge = functools.partial(
            merge_path.merge_path, compare_full=True, interpret=interpret
        )
        width = tile
        while width < n:
            kv2, val2 = kv.reshape(-1, 2, width), val.reshape(-1, 2, width)
            kv, val = jax.vmap(merge)(kv2[:, 0], val2[:, 0], kv2[:, 1], val2[:, 1])
            kv, val = kv.reshape(n), val.reshape(n)
            width *= 2
    return kv, val
