"""TPU LSM: the paper's data structure as a fixed-shape, jit-native JAX module.

Layout (per-level arrays)
-------------------------
A GPU allocates levels lazily; a jit/pjit program needs static shapes. We
preallocate `num_levels` exponentially sized levels as separate arrays —
level i holds exactly b * 2**i slots. Keeping levels as distinct buffers (not
one flat arena) matters for the complexity story: a batch update rewrites
ONLY the levels the binary-counter carry touches (lax.switch pass-through +
buffer donation forwards untouched levels), preserving the paper's
O(b log r) amortized insertion cost. A flat arena would force an O(capacity)
rewrite per batch.

Empty levels (and the tails of cleaned-up levels) hold *placebo* elements —
maximum original key + tombstone status (paper §4.5 fn. 6) — which sort last
and are invisible to every query. "Empty" and "full" levels are therefore
indistinguishable to query code: no control flow depends on occupancy.

The resident-batch counter `r` mirrors the paper exactly: level i is full iff
bit i of r is set, and a batch update is a binary-counter increment whose
carries are stable merges.

Write buffer ("level −1")
-------------------------
The paper's update path is rigidly b-wide; real workloads trickle in ragged
sub-batches. A b-slot staging buffer in front of the merge cascade (the
canonical LSM memtable, docs/DESIGN.md §5) absorbs encoded sub-batch updates
in arrival order without consuming a batch slot: `lsm_stage` appends up to b
encoded lanes, and only when more than b elements are pending does it flush
the *oldest* b through the binary-counter cascade, retaining the newest
remainder. The buffer is queried as the newest run (see `all_runs`) and its
recency rule is strictly sequence-ordered: a later lane/call beats an earlier
one even across the insert/tombstone status boundary — unlike the paper's
in-batch rule where a tombstone beats any same-batch insert of its key.
`buf_seq` records the arrival rank explicitly (invariant: seq == buffer
position; placebo lanes hold b), `buf_n` the occupancy.

Everything here is traceable: `LSMConfig` is static (hashable) and `LSMState`
is a pytree, so `jax.jit(lsm_update, static_argnums=0, donate_argnums=1)`
works, as does sharding each level with pjit/shard_map (core/distributed.py).
"""

from __future__ import annotations

import dataclasses
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from repro.core import semantics as sem
from repro.core import cascade
from repro.kernels import ops


@dataclasses.dataclass(frozen=True)
class LSMConfig:
    """Static configuration: batch size b and level count L (capacity b*(2^L-1))."""

    batch_size: int
    num_levels: int

    def __post_init__(self):
        if self.batch_size < 1:
            raise ValueError("batch_size must be >= 1")
        if self.num_levels < 1:
            raise ValueError("num_levels must be >= 1")

    @property
    def capacity(self) -> int:
        return self.batch_size * ((1 << self.num_levels) - 1)

    @property
    def max_batches(self) -> int:
        return (1 << self.num_levels) - 1

    def level_size(self, i: int) -> int:
        return self.batch_size * (1 << i)


class LSMState(NamedTuple):
    """Pytree state: per-level (key_var, value) arrays + counter + overflow
    latch + the write buffer ("level −1", docs/DESIGN.md §5)."""

    key_vars: Tuple[jax.Array, ...]  # level i: int32[b * 2**i]
    values: Tuple[jax.Array, ...]
    r: jax.Array                     # int32[] — number of resident batches
    overflowed: jax.Array            # bool[] — latches if an update overflowed
    buf_kv: jax.Array                # int32[b] — staged lanes, arrival order
    buf_val: jax.Array               # int32[b]
    # Explicit arrival-order witness (== position; b on placebo lanes).
    # Derivable from buf_n, but kept deliberately: it is the recency
    # authority the streaming design names, and variants that reorder the
    # raw buffer (e.g. a sorted-in-place memtable) would need the slot.
    # test_buffer_state_invariants pins it.
    buf_seq: jax.Array               # int32[b]
    buf_n: jax.Array                 # int32[] — buffer occupancy
    # Cached recency-sorted view of the buffer (ascending original key,
    # newest-first within equal keys): queries read it directly, so the
    # O(b log b) sort is paid once per stage/flush, not once per query.
    buf_sorted_kv: jax.Array         # int32[b]
    buf_sorted_val: jax.Array        # int32[b]
    # Compaction debt: per-level estimate of reclaimable (stale) residents,
    # measured on each run as a cascade step materializes it
    # (cascade.run_stale_count) and consumed by budgeted maintenance
    # (cleanup.lsm_maintain). A scheduling signal only — queries never read
    # it, and results are exact at any debt level (docs/DESIGN.md §11).
    lvl_debt: jax.Array              # int32[num_levels]
    # Elements merged, in batches of b: 2^t per push into a counter with t
    # trailing ones (a flush is a push), 2^L per cleanup (every slot and the
    # buffer), the prefix's batches per maintain. The LSM's write
    # amplification; int32, wraps modulo 2^32, so readers take differences.
    merged: jax.Array                # int32[]


def level_view(cfg: LSMConfig, state: LSMState, i: int):
    """Level i as a (sorted, possibly all-placebo) run."""
    return state.key_vars[i], state.values[i]


def level_runs(cfg: LSMConfig, state: LSMState):
    """All levels as (key_vars, values) runs, newest (level 0) first."""
    return [level_view(cfg, state, i) for i in range(cfg.num_levels)]


def buffer_run(cfg: LSMConfig, state: LSMState):
    """The write buffer as a sorted run: ascending original key, newest
    (highest arrival seq) first within equal keys, placebos last. This is the
    run every query treats as the newest — buffer-resident tombstones hide
    older level elements before any flush. The sorted view is maintained by
    `lsm_stage`/`lsm_flush`, so reading it here costs nothing."""
    return state.buf_sorted_kv, state.buf_sorted_val


def all_runs(cfg: LSMConfig, state: LSMState):
    """Every queryable run, newest first: write buffer, then levels 0..L-1.

    The buffer run is included unconditionally (an empty buffer is all
    placebo, hence invisible) — no control flow depends on occupancy, same
    as the level arrays."""
    return [buffer_run(cfg, state)] + level_runs(cfg, state)


def arena_view(state: LSMState):
    """All levels concatenated (debug/test helper; excludes the buffer)."""
    return jnp.concatenate(state.key_vars), jnp.concatenate(state.values)


# Single definition lives in the cascade engine; re-exported here because
# cleanup/distributed/facade code historically imports it from this module.
_placebo = cascade._placebo


def _fresh_buffer(b: int) -> dict:
    """Field dict for an empty write buffer (for LSMState(...)/._replace)."""
    kv, val = _placebo(b)
    # The sorted view of an empty (all-placebo) buffer is itself all-placebo,
    # but it must be a DISTINCT buffer: aliasing buf_kv would make donation
    # see the same device buffer twice.
    sorted_kv, sorted_val = _placebo(b)
    return dict(
        buf_kv=kv,
        buf_val=val,
        buf_seq=jnp.full((b,), b, dtype=jnp.int32),
        buf_n=jnp.zeros((), dtype=jnp.int32),
        buf_sorted_kv=sorted_kv,
        buf_sorted_val=sorted_val,
    )


def compact_real(key_vars, values, mask):
    """Stable-partition the `mask` lanes to the front, arrival order
    preserved; remaining lanes become placebos. Returns (kv, val, count).

    Shared by the facade's `valid=` path and the sharded owner filter:
    masked-out lanes must never occupy write-buffer slots."""
    n = key_vars.shape[0]
    mask = jnp.asarray(mask, bool)
    count = jnp.sum(mask).astype(jnp.int32)
    pos = jnp.where(mask, jnp.cumsum(mask.astype(jnp.int32)) - 1, n)  # n -> dropped
    pk, pv = _placebo(n)
    out_kv = pk.at[pos].set(jnp.asarray(key_vars, jnp.int32), mode="drop")
    out_val = pv.at[pos].set(jnp.asarray(values, jnp.int32), mode="drop")
    return out_kv, out_val, count


def lsm_init(cfg: LSMConfig) -> LSMState:
    kvs, vals = zip(*(_placebo(cfg.level_size(i)) for i in range(cfg.num_levels)))
    return LSMState(
        key_vars=tuple(kvs),
        values=tuple(vals),
        r=jnp.zeros((), dtype=jnp.int32),
        overflowed=jnp.zeros((), dtype=bool),
        lvl_debt=jnp.zeros((cfg.num_levels,), dtype=jnp.int32),
        merged=jnp.zeros((), dtype=jnp.int32),
        **_fresh_buffer(cfg.batch_size),
    )


# The binary-counter increment itself lives in the shared cascade engine
# (core/cascade.py): ONE lax.switch branch per placement level, each doing a
# single fused K-way merge of [carry, level 0..j-1] — the old pairwise
# cond-chain copied the carry past every level above the placement, making
# each update O(b * 2^L) regardless of where it landed.
_cascade = cascade.push_batch


def lsm_update(cfg: LSMConfig, state: LSMState, key_vars, values) -> LSMState:
    """Insert a mixed batch of b encoded updates (inserts and/or tombstones).

    Paper §3.2/§4.1: sort the batch by the full key variable, then cascade
    stable merges up the level hierarchy until an empty level receives the
    carry. Merges compare original keys only; newer runs win ties. Within the
    batch the full-key-variable sort makes a tombstone beat any same-batch
    insert of its key (paper invariant 2).

    This is the direct, paper-exact path: it bypasses the write buffer, so
    with a non-empty buffer the staged elements would (incorrectly) rank as
    newer than this batch — callers either keep the buffer empty (every
    direct-core user) or route through `lsm_stage` instead (the facade).
    """
    b = cfg.batch_size
    key_vars = jnp.asarray(key_vars, jnp.int32)
    values = jnp.asarray(values, jnp.int32)
    if key_vars.shape != (b,) or values.shape != (b,):
        raise ValueError(f"batch must have shape ({b},), got {key_vars.shape}/{values.shape}")
    carry_kv, carry_val = ops.sort_pairs(key_vars, values)
    return _cascade(cfg, state, carry_kv, carry_val)


@jax.named_scope("lsm.stage")
def lsm_stage(cfg: LSMConfig, state: LSMState, key_vars, values, count) -> LSMState:
    """Stage one encoded sub-batch into the write buffer ("level −1").

    key_vars/values: int32[b] with the `count` real lanes compacted to the
    front *in arrival order* (use `compact_real` for masked inputs); the rest
    placebo. count: int32 scalar (traced OK), 0 <= count <= b.

    The sub-batch appends after the current buffer contents. If the combined
    occupancy stays <= b nothing else happens — no batch slot is consumed.
    Otherwise the *oldest* b pending elements flush through the cascade as
    one full batch (sorted newest-first within equal keys, so strict arrival
    order decides duplicates — docs/DESIGN.md §5) and the newest remainder
    stays in the buffer. At most one cascade per call: count <= b. Its ops
    run under the `lsm.stage` name scope (a push inside it under `lsm.push`).
    """
    b = cfg.batch_size
    key_vars = jnp.asarray(key_vars, jnp.int32)
    values = jnp.asarray(values, jnp.int32)
    if key_vars.shape != (b,) or values.shape != (b,):
        raise ValueError(f"sub-batch must have shape ({b},), got {key_vars.shape}/{values.shape}")
    count = jnp.asarray(count, jnp.int32)
    lane = jnp.arange(b, dtype=jnp.int32)
    total = state.buf_n + count

    # Append into a 2b arena: [current buffer | placebo], incoming at buf_n+i.
    pk, pv = _placebo(b)
    pos = jnp.where(lane < count, state.buf_n + lane, 2 * b)  # 2b -> dropped
    arena_kv = jnp.concatenate([state.buf_kv, pk]).at[pos].set(key_vars, mode="drop")
    arena_val = jnp.concatenate([state.buf_val, pv]).at[pos].set(values, mode="drop")

    def no_flush(st):
        skv, sval = ops.sort_pairs_recency(arena_kv[:b], arena_val[:b])
        return st._replace(
            buf_kv=arena_kv[:b],
            buf_val=arena_val[:b],
            buf_seq=jnp.where(lane < total, lane, b),
            buf_n=total,
            buf_sorted_kv=skv,
            buf_sorted_val=sval,
        )

    def flush_oldest(st):
        # total > b => the first b arena lanes are all real, in arrival order.
        fk, fv = ops.sort_pairs_recency(arena_kv[:b], arena_val[:b])
        st = _cascade(cfg, st, fk, fv)
        rem = total - b
        skv, sval = ops.sort_pairs_recency(arena_kv[b:], arena_val[b:])
        return st._replace(
            buf_kv=arena_kv[b:],
            buf_val=arena_val[b:],
            buf_seq=jnp.where(lane < rem, lane, b),
            buf_n=rem,
            buf_sorted_kv=skv,
            buf_sorted_val=sval,
        )

    return jax.lax.cond(total > b, flush_oldest, no_flush, state)


def lsm_flush(cfg: LSMConfig, state: LSMState, min_pending: int = 1) -> LSMState:
    """Flush the write buffer through the cascade if it holds >= min_pending
    elements (no-op otherwise, and always a no-op when empty).

    A partial buffer is placebo-padded to a full batch — this consumes one
    batch slot for < b elements, exactly the facade's old pad-every-call
    cost, now paid only on explicit/threshold flushes."""
    def do(st):
        # The cached sorted view IS the cascade-ready batch.
        st = _cascade(cfg, st, st.buf_sorted_kv, st.buf_sorted_val)
        return st._replace(**_fresh_buffer(cfg.batch_size))

    pending = state.buf_n >= jnp.maximum(jnp.asarray(min_pending, jnp.int32), 1)
    return jax.lax.cond(pending, do, lambda st: st, state)


def lsm_insert(cfg: LSMConfig, state: LSMState, keys, values) -> LSMState:
    """Insert a batch of b (key, value) pairs (original keys, not encoded)."""
    return lsm_update(cfg, state, sem.encode_insert(keys), values)


def lsm_delete(cfg: LSMConfig, state: LSMState, keys) -> LSMState:
    """Delete a batch of b keys via tombstones (paper §3.3)."""
    kv = sem.encode_delete(keys)
    vals = jnp.full((cfg.batch_size,), sem.EMPTY_VALUE, dtype=jnp.int32)
    return lsm_update(cfg, state, kv, vals)


def lsm_update_mixed(cfg: LSMConfig, state: LSMState, keys, values, is_delete) -> LSMState:
    """Mixed batch: is_delete[i] selects tombstone vs regular insert."""
    kv = sem.encode(keys, is_delete)
    vals = jnp.where(jnp.asarray(is_delete), sem.EMPTY_VALUE, jnp.asarray(values, jnp.int32))
    return lsm_update(cfg, state, kv, vals)


def _redistribute(cfg: LSMConfig, compact_kv, compact_val, r_new):
    """Slice a globally sorted, deduplicated array into LSM levels.

    Level i (if bit i of r_new is set) receives the contiguous slice starting
    at b * (r_new & (2**i - 1)) — smallest keys land in the smallest levels
    (paper §4.5). Keys are unique after cleanup, so cross-level recency is
    irrelevant. (Thin alias of the engine's prefix-aware version.)
    """
    return cascade.redistribute(cfg, compact_kv, compact_val, r_new)


def lsm_bulk_build(cfg: LSMConfig, keys, values) -> LSMState:
    """Build from n unique keys: one sort + level segmentation (paper §5.2).

    n need not be a multiple of b: the tail of the last resident batch is
    placebo-padded, exactly the state CLEANUP produces for a non-multiple
    live count.
    """
    keys = jnp.asarray(keys, jnp.int32)
    values = jnp.asarray(values, jnp.int32)
    n = keys.shape[0]
    k = -(-n // cfg.batch_size)  # ceil: last batch may be placebo-padded
    if k > cfg.max_batches:
        raise ValueError("bulk build exceeds configured capacity")
    kv, vals = ops.sort_pairs(sem.encode_insert(keys), values)
    pad = cfg.capacity - n
    kv = jnp.concatenate([kv, _placebo(pad)[0]])
    vals = jnp.concatenate([vals, _placebo(pad)[1]])
    kvs, vals = _redistribute(cfg, kv, vals, jnp.asarray(k, jnp.int32))
    return LSMState(
        key_vars=kvs,
        values=vals,
        r=jnp.asarray(k, jnp.int32),
        overflowed=jnp.zeros((), dtype=bool),
        lvl_debt=jnp.zeros((cfg.num_levels,), dtype=jnp.int32),
        merged=jnp.zeros((), dtype=jnp.int32),
        **_fresh_buffer(cfg.batch_size),
    )


def lsm_num_elements(cfg: LSMConfig, state: LSMState):
    """Resident element count (including stale elements): r * b + staged."""
    return state.r * cfg.batch_size + state.buf_n


def lsm_debt(cfg: LSMConfig, state: LSMState):
    """Total compaction debt (int32 scalar): the per-level stale-resident
    estimate summed over levels. What `lsm_maintain` budgets against."""
    return jnp.sum(state.lvl_debt).astype(jnp.int32)


def lsm_flush_cost(cfg: LSMConfig, state: LSMState):
    """Elements the cascade would touch if the buffer flushed *now* (int32
    scalar; 0 when the buffer is empty).

    Pushing one batch into the binary counter merges it with the t full
    levels below the placement level (t = trailing_ones(r)), which hold
    2^t - 1 batches, so the merge reads and rewrites b * 2^t elements: what
    a push adds to `merged`. A scheduler can weigh it against buffer
    occupancy when deciding whether to flush early or keep absorbing
    trickles.
    """
    cost = cfg.batch_size * cascade.merged_batches(state.r)
    return jnp.where(state.buf_n > 0, cost, 0).astype(jnp.int32)
