"""Streamed lower-bound Pallas kernel — the LSM query hot-spot.

The paper's lookup bottleneck is random memory access during per-thread binary
search (§4.2). A literal port would issue data-dependent HBM gathers — the
single worst access pattern on TPU. The TPU-native reformulation:

    lower_bound(level, q) == #elements of `level` with key < q
                          == sum over chunks of per-chunk counts.

So instead of one pointer-chasing search per query, we *stream* the level
through VMEM in LEVEL_CHUNK tiles (perfectly coalesced, bandwidth-bound) and
accumulate per-chunk counts for a whole block of queries at once. The
per-chunk count is an all-pairs comparison matrix — [QUERY_BLOCK x
LEVEL_CHUNK] int ops per LEVEL_CHUNK loads, which the VPU retires faster than
HBM can feed the keys, i.e. the kernel stays memory-bound (the roofline
optimum for a search over data that is read once).

Grid = (query tiles, level chunks); the output tile is revisited across the
chunk axis (standard Pallas accumulator pattern, initialized at chunk 0).

Fused multi-run lookup (`fused_lookup_runs`)
--------------------------------------------
The paper's retrieval trade-off is that every LOOKUP must consult *every* run.
The per-run formulation above pays that cost as one kernel launch (and one
full output round trip) per run. The fused kernel collapses the whole read
path into ONE `pallas_call` per query block: the runs are concatenated
newest-first into a single flat (key_var, value) array and *streamed* through
VMEM with manually double-buffered DMA (`pltpu.make_async_copy` over a
`FUSED_DEPTH`-deep revolving scratch), so the next chunk is in flight while
the VPU scans the current one.

Correctness rests on one observation: with runs concatenated newest-first
(write buffer, then level 0..L-1) every run is sorted with the newest element
first within equal keys, so the winning element for query q — the one the
per-run resolution loop would report — is exactly the matching element with
the LOWEST flat index. Run boundaries therefore never matter inside the
kernel: it tracks "first match so far" per query and the chunk loop visits
flat indices in ascending order. A tombstone (or placebo) match resolves the
query without reporting it found, which falls out of returning the matched
key_var itself and letting the caller decode status bits.

The defaults below (FUSED_CHUNK / FUSED_DEPTH) come from the
`benchmarks/kernel_bench.py` block-size x buffer-depth sweep plus v5e DMA
arithmetic: chunk=1024 moves 8KB per DMA row (large enough to amortize DMA
issue, small enough that (depth, 2, chunk) VMEM scratch stays tiny), and
depth=2 is the minimum that overlaps the chunk-c compare with the chunk-c+1
copy. NOTE the sweep's CPU interpret-mode wall clock prefers smaller chunks
and depth=1 — interpreted DMA does not overlap anything, so per-chunk
interpreter overhead dominates there; the sweep records both (the winner row
flags the drift) and the defaults follow the hardware reasoning until a real
TPU run re-picks them (see BENCH_kernels.json / ROADMAP open item).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import semantics as sem

QUERY_BLOCK = 256
LEVEL_CHUNK = 2048

# Fused multi-run kernel tile geometry (see module docstring for how these
# were picked; kernel_bench re-records the sweep every run).
FUSED_QUERY_BLOCK = 256
FUSED_CHUNK = 1024
FUSED_DEPTH = 2


def _lower_bound_kernel(q_ref, chunk_ref, o_ref):
    c = pl.program_id(1)

    @pl.when(c == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    q = q_ref[...]          # [QUERY_BLOCK, 1] — queries down the sublanes
    keys = chunk_ref[...]   # [1, LEVEL_CHUNK] — keys across the lanes
    o_ref[...] += jnp.sum((keys < q).astype(jnp.int32), axis=1, keepdims=True)


def lower_bound_streamed(sorted_keys, query_keys, *, interpret=False):
    """Vectorized lower_bound over a sorted array (original keys).

    sorted_keys: int32[n], n % LEVEL_CHUNK == 0 (placebo-padded by the LSM).
    query_keys:  int32[q], q % QUERY_BLOCK == 0.

    Both operands enter the kernel 2-D — queries as a [q, 1] column, keys as
    a [1, n] row — because Mosaic tiles every VMEM block in (8, 128) vregs and
    rejects 1-D blocks whose HBM layout disagrees with its own.
    """
    n = sorted_keys.shape[0]
    q = query_keys.shape[0]
    assert n % LEVEL_CHUNK == 0 and q % QUERY_BLOCK == 0, (n, q)
    grid = (q // QUERY_BLOCK, n // LEVEL_CHUNK)
    out = pl.pallas_call(
        _lower_bound_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((QUERY_BLOCK, 1), lambda i, c: (i, 0)),
            pl.BlockSpec((1, LEVEL_CHUNK), lambda i, c: (0, c)),
        ],
        out_specs=pl.BlockSpec((QUERY_BLOCK, 1), lambda i, c: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((q, 1), jnp.int32),
        name="lower_bound_streamed",
        interpret=interpret,
    )(
        query_keys.astype(jnp.int32).reshape(q, 1),
        sorted_keys.astype(jnp.int32).reshape(1, n),
    )
    return out[:, 0]


def _fused_lookup_kernel(q_ref, flat_hbm, okv_ref, oval_ref, *, n, chunk, depth):
    """One query tile vs the whole flat run array, streamed chunk by chunk.

    flat_hbm stays in HBM (memory_space=ANY); `depth` revolving VMEM buffers
    overlap the DMA of chunk c+depth with the scan of chunk c. Per chunk the
    scan is an all-pairs match matrix (queries down the sublanes, chunk keys
    across the lanes) and a first-match select: the masked minimum of a lane
    iota names the lowest matching lane, and a one-hot on that lane picks its
    key variable and value.
    """
    num_chunks = n // chunk
    q = q_ref[...]                      # [query_block, 1]
    qb = q.shape[0]
    lane = jax.lax.broadcasted_iota(jnp.int32, (qb, chunk), 1)

    def body(bufs, sems):
        def dma(c, slot):
            return pltpu.make_async_copy(
                flat_hbm.at[:, pl.ds(pl.multiple_of(c * chunk, chunk), chunk)],
                bufs.at[slot],
                sems.at[slot],
            )

        for s in range(min(depth, num_chunks)):
            dma(s, s).start()

        def step(c, carry):
            best_kv, best_val = carry
            slot = jax.lax.rem(c, depth)
            dma(c, slot).wait()
            buf = bufs[slot]            # [2, chunk]: kv row, value row
            ckv = buf[0:1, :]
            cval = buf[1:2, :]
            match = (ckv >> 1) == q     # original keys; placebos stay maximal
            first = jnp.min(jnp.where(match, lane, chunk), axis=1, keepdims=True)
            hit = first < chunk
            pick = lane == first
            sel_kv = jnp.sum(jnp.where(pick, ckv, 0), axis=1, keepdims=True)
            sel_val = jnp.sum(jnp.where(pick, cval, 0), axis=1, keepdims=True)
            # A query is unresolved while its best is still the placebo
            # sentinel: no real element ever encodes to PLACEBO_KV (user keys
            # are < PLACEBO_KEY), and a legitimate placebo "match" (query ==
            # PLACEBO_KEY) leaves the sentinel in place, which decodes to the
            # same resolved-as-deleted answer.
            upd = hit & (best_kv == sem.PLACEBO_KV)
            best_kv = jnp.where(upd, sel_kv, best_kv)
            best_val = jnp.where(upd, sel_val, best_val)
            nxt = c + depth

            @pl.when(nxt < num_chunks)
            def _():
                dma(nxt, slot).start()

            return best_kv, best_val

        init = (
            jnp.full((qb, 1), sem.PLACEBO_KV, dtype=jnp.int32),
            jnp.full((qb, 1), sem.EMPTY_VALUE, dtype=jnp.int32),
        )
        best_kv, best_val = jax.lax.fori_loop(0, num_chunks, step, init)
        okv_ref[...] = best_kv
        oval_ref[...] = best_val

    pl.run_scoped(
        body,
        bufs=pltpu.VMEM((depth, 2, chunk), jnp.int32),
        sems=pltpu.SemaphoreType.DMA((depth,)),
    )


def fused_lookup_runs(
    flat_kv,
    flat_val,
    query_keys,
    *,
    chunk: int | None = None,
    query_block: int | None = None,
    depth: int | None = None,
    interpret: bool = False,
):
    """Fused multi-run LOOKUP: first flat match per query, one pallas_call.

    flat_kv/flat_val: int32[n] — all runs concatenated newest-first (write
      buffer, then levels), placebo-padded so n % chunk == 0.
    query_keys: int32[q], q % query_block == 0.
    Returns (best_kv, best_val): the winning element per query (PLACEBO_KV /
    EMPTY_VALUE when no run matches). Callers decode found/tombstone from the
    key variable — see `ops.lookup_runs_fused`.
    """
    chunk = FUSED_CHUNK if chunk is None else chunk
    query_block = FUSED_QUERY_BLOCK if query_block is None else query_block
    depth = FUSED_DEPTH if depth is None else depth
    n = flat_kv.shape[0]
    q = query_keys.shape[0]
    assert n % chunk == 0 and q % query_block == 0, (n, q, chunk, query_block)
    assert depth >= 1
    flat = jnp.stack(
        [jnp.asarray(flat_kv, jnp.int32), jnp.asarray(flat_val, jnp.int32)]
    )  # [2, n] — one DMA moves the kv and value rows of a chunk together
    grid = (q // query_block,)
    col = pl.BlockSpec((query_block, 1), lambda i: (i, 0))
    best_kv, best_val = pl.pallas_call(
        functools.partial(_fused_lookup_kernel, n=n, chunk=chunk, depth=depth),
        grid=grid,
        in_specs=[col, pl.BlockSpec(memory_space=pl.ANY)],  # flat: manual DMA
        out_specs=[col, col],
        out_shape=[
            jax.ShapeDtypeStruct((q, 1), jnp.int32),
            jax.ShapeDtypeStruct((q, 1), jnp.int32),
        ],
        name="fused_lookup_runs",
        interpret=interpret,
    )(query_keys.astype(jnp.int32).reshape(q, 1), flat)
    return best_kv[:, 0], best_val[:, 0]
