"""Range-partitioned distributed LSM over a device mesh (shard_map).

Each device owns a contiguous key range (region-server model, as in
BigTable/HBase — chosen over hash partitioning because RANGE/COUNT queries
then touch only the owning shards). Every device runs a full local LSM over
its range:

  * UPDATE: the global batch is all-gathered; each shard filters the keys it
    owns and turns the rest into placebo padding — the batch-of-b invariant
    holds per shard, so the local binary-counter cascade is unchanged. (The
    all-gather is the TPU-native stand-in for a ragged all-to-all; bytes moved are
    identical up to the skew factor and the shapes stay static.)
  * STAGE (write buffer): same ownership filter, then owned lanes compact to
    the front (arrival order preserved) and append into the shard-LOCAL write
    buffer (`lsm_stage`) — zero communication beyond the already-replicated
    batch, and no batch slot consumed until a shard's own buffer overflows.
    Buffers fill at ownership-skew-dependent rates, so shards flush at
    different times; FLUSH is likewise purely shard-local.
  * LOOKUP: queries are broadcast; the owner answers; results combine with
    a psum using ⊥-identities (non-owners contribute 0/false, exactly one
    owner can report found, so the sum IS the owner's answer — unlike a max
    combine this stays correct for negative payload values).
  * COUNT: local counts + psum.
  * RANGE: local compacted results + per-shard counts; `assemble_range`
    turns the shard-major stack into globally compacted rows (offsets are
    an exclusive cumsum over shard counts).
  * CLEANUP: purely shard-local (no communication at all) — a nice property
    of range partitioning the paper's structure inherits for free.
  * SIZE / BULK_BUILD: local survivor count + psum; local build over the
    owned subset of a replicated key set.

The key space [0, MAX_USER_KEY] is split evenly; shard s owns
[s * range_size, (s+1) * range_size).

Two API layers:

  * `dist_update` / `dist_lookup` / ... are *traceable*: plain functions of
    (cfg, mesh, state, ...) that build their shard_map at trace time, so the
    `Dictionary` facade can call them inside its own jitted executables
    (backend "lsm_sharded" in repro.api.backends).
  * `make_dist_*` wrap them in standalone jitted callables with donation —
    the original surface, kept for direct core users and the distributed
    tests.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import semantics as sem
from repro.core.cleanup import lsm_cleanup, lsm_maintain
from repro.core.lsm import (
    LSMConfig,
    LSMState,
    _fresh_buffer,
    _placebo,
    _redistribute,
    compact_real,
    lsm_debt,
    lsm_flush,
    lsm_flush_cost,
    lsm_init,
    lsm_stage,
    lsm_update,
)
from repro.core.queries import count_runs, lookup_runs, range_runs, valid_count_runs
from repro.core.lsm import all_runs
from repro.kernels import ops


@dataclasses.dataclass(frozen=True)
class DistLSMConfig:
    local: LSMConfig          # per-shard LSM config (batch_size = global batch!)
    num_shards: int
    axis: str = "shard"

    @property
    def range_size(self) -> int:
        return (sem.PLACEBO_KEY + self.num_shards - 1) // self.num_shards


def owner_of(cfg: DistLSMConfig, keys):
    return jnp.clip(jnp.asarray(keys, jnp.int32) // cfg.range_size, 0, cfg.num_shards - 1)


def shard_bounds(cfg: DistLSMConfig, shard):
    """Inclusive [lo, hi] key range owned by `shard` (traced or static)."""
    lo = shard * cfg.range_size
    hi = lo + cfg.range_size - 1
    return lo, hi


def dist_lsm_init(cfg: DistLSMConfig, mesh) -> LSMState:
    """Per-shard LSM states, stacked on a leading sharded axis."""
    from repro.dist.sharding import stacked_shardings

    def init_all():
        return jax.vmap(lambda _: lsm_init(cfg.local))(jnp.arange(cfg.num_shards))

    # Built in place: each device fills only its own shard. Built eagerly
    # and then placed, all num_shards arenas would first sit on device 0.
    shardings = stacked_shardings(jax.eval_shape(init_all), mesh, cfg.axis)
    return jax.jit(init_all, out_shardings=shardings)()


def _local_state(stacked: LSMState) -> LSMState:
    """Strip the leading (size-1 per shard) stacking axis inside shard_map."""
    return jax.tree_util.tree_map(lambda x: x[0], stacked)


def _restack(state: LSMState) -> LSMState:
    return jax.tree_util.tree_map(lambda x: x[None], state)


# ---------------------------------------------------------------------------
# Traceable ops (safe to call inside an enclosing jit — the facade does)
# ---------------------------------------------------------------------------


def dist_update(cfg: DistLSMConfig, mesh, states, key_vars, values) -> LSMState:
    """Apply one b-wide encoded batch: each shard keeps its keys, placebos the
    rest, and runs the unchanged local binary-counter cascade."""
    state_spec = P(cfg.axis)

    def body(states, key_vars, values):
        st = _local_state(states)
        shard = jax.lax.axis_index(cfg.axis).astype(jnp.int32)
        owner = owner_of(cfg, sem.original_key(key_vars))
        mine = owner == shard
        kv = jnp.where(mine, key_vars, sem.PLACEBO_KV)
        val = jnp.where(mine, values, sem.EMPTY_VALUE)
        st = lsm_update(cfg.local, st, kv, val)
        return _restack(st)

    f = jax.shard_map(
        body, mesh=mesh,
        in_specs=(state_spec, P(), P()),
        out_specs=state_spec,
        check_vma=False,
    )
    return f(states, key_vars, values)


def dist_stage(cfg: DistLSMConfig, mesh, states, key_vars, values, count) -> LSMState:
    """Stage one encoded sub-batch into the shard-local write buffers.

    key_vars/values: int32[b] with the `count` real lanes front-compacted in
    arrival order (the facade's contract for `stage_encoded`). Each shard
    keeps its owned lanes, re-compacts them to the front (order preserved),
    and appends to its LOCAL buffer — no communication beyond the replicated
    input, and no batch slot consumed until that shard's buffer overflows.
    """
    state_spec = P(cfg.axis)

    @jax.named_scope("lsm.stage")
    def body(states, key_vars, values, count):
        st = _local_state(states)
        shard = jax.lax.axis_index(cfg.axis).astype(jnp.int32)
        lane = jnp.arange(cfg.local.batch_size, dtype=jnp.int32)
        owner = owner_of(cfg, sem.original_key(key_vars))
        mine = (lane < count) & (owner == shard)
        kv, val, cnt = compact_real(key_vars, values, mine)
        st = lsm_stage(cfg.local, st, kv, val, cnt)
        return _restack(st)

    f = jax.shard_map(
        body, mesh=mesh,
        in_specs=(state_spec, P(), P(), P()),
        out_specs=state_spec,
        check_vma=False,
    )
    return f(states, key_vars, values, count)


def dist_flush(cfg: DistLSMConfig, mesh, states, min_pending: int = 1) -> LSMState:
    """Flush shard-local write buffers holding >= min_pending elements.

    Purely shard-local (zero communication) — shards flush independently, so
    ownership skew never forces an empty shard to burn a batch slot."""
    state_spec = P(cfg.axis)

    def body(states):
        return _restack(lsm_flush(cfg.local, _local_state(states), min_pending))

    f = jax.shard_map(body, mesh=mesh, in_specs=(state_spec,), out_specs=state_spec,
                  check_vma=False)
    return f(states)


def dist_pending(cfg: DistLSMConfig, mesh, states):
    """Total write-buffer residents across shards (int32 scalar, psum)."""
    state_spec = P(cfg.axis)

    def body(states):
        return jax.lax.psum(_local_state(states).buf_n, cfg.axis)

    f = jax.shard_map(body, mesh=mesh, in_specs=(state_spec,), out_specs=P(),
                  check_vma=False)
    return f(states)


def dist_occupancy(cfg: DistLSMConfig, mesh, states):
    """(pending, resident, debt) int32 scalars summed across shards.

    Shard-local reads + three psums — cheap enough for a serving scheduler to
    poll between coalesced steps (no query machinery runs)."""
    state_spec = P(cfg.axis)

    def body(states):
        local = _local_state(states)
        pending = jax.lax.psum(local.buf_n, cfg.axis)
        resident = jax.lax.psum(local.r * cfg.local.batch_size, cfg.axis)
        debt = jax.lax.psum(lsm_debt(cfg.local, local), cfg.axis)
        return pending, resident, debt

    f = jax.shard_map(body, mesh=mesh, in_specs=(state_spec,),
                  out_specs=(P(), P(), P()), check_vma=False)
    return f(states)


def dist_flush_cost(cfg: DistLSMConfig, mesh, states):
    """Total elements every shard's cascade would touch on a flush now (psum
    of the shard-local `lsm_flush_cost`; shards flush independently, so the
    sum is the whole-device-step work estimate)."""
    state_spec = P(cfg.axis)

    def body(states):
        return jax.lax.psum(
            lsm_flush_cost(cfg.local, _local_state(states)), cfg.axis
        )

    f = jax.shard_map(body, mesh=mesh, in_specs=(state_spec,), out_specs=P(),
                  check_vma=False)
    return f(states)


def dist_lookup(cfg: DistLSMConfig, mesh, states, keys):
    """lookup(states, keys[q]) -> (found[q], values[q])."""
    state_spec = P(cfg.axis)

    def body(states, keys):
        st = _local_state(states)
        shard = jax.lax.axis_index(cfg.axis).astype(jnp.int32)
        mine = owner_of(cfg, keys) == shard
        found, vals = lookup_runs(all_runs(cfg.local, st), keys)
        found = found & mine
        vals = jnp.where(found, vals, 0)
        # ⊥-identity combine: exactly one shard can report found, everyone
        # else contributes 0, so psum reconstructs the owner's value exactly
        # (correct even for negative payloads, unlike a max combine).
        found = jax.lax.psum(found.astype(jnp.int32), cfg.axis) > 0
        vals = jax.lax.psum(vals, cfg.axis)
        return found[None], vals[None]

    f = jax.shard_map(
        body, mesh=mesh,
        in_specs=(state_spec, P()),
        out_specs=(P(), P()),
        check_vma=False,
    )
    found, vals = f(states, keys)
    return found[0], vals[0]


def dist_count(cfg: DistLSMConfig, mesh, states, k1, k2, max_candidates: int):
    """count(states, k1[q], k2[q]) -> (counts[q], ok[q]).

    Each shard counts the intersection of [k1, k2] with its own range;
    global count = psum. Clipping to the shard range keeps per-shard
    candidate buffers small (max_candidates is per shard).
    """
    state_spec = P(cfg.axis)

    def body(states, k1, k2):
        st = _local_state(states)
        shard = jax.lax.axis_index(cfg.axis).astype(jnp.int32)
        lo, hi = shard_bounds(cfg, shard)
        k1c = jnp.clip(k1, lo, hi + 1)
        k2c = jnp.clip(k2, lo - 1, hi)
        nonempty = k1c <= k2c
        counts, ok = count_runs(all_runs(cfg.local, st), k1c, k2c, max_candidates)
        counts = jnp.where(nonempty, counts, 0)
        ok = ok | ~nonempty
        counts = jax.lax.psum(counts, cfg.axis)
        ok = jax.lax.pmin(ok.astype(jnp.int32), cfg.axis) > 0
        return counts[None], ok[None]

    f = jax.shard_map(
        body, mesh=mesh,
        in_specs=(state_spec, P(), P()),
        out_specs=(P(), P()),
        check_vma=False,
    )
    c, ok = f(states, k1, k2)
    return c[0], ok[0]


def dist_range(cfg: DistLSMConfig, mesh, states, k1, k2,
               max_candidates: int, max_results: int):
    """range(states, k1[q], k2[q]) ->
    (keys [shards, q, max_results], vals, counts [shards, q], ok[q]).

    Results stay shard-major (keys within a shard ascending; shards ascending
    = globally ascending since partitioning is by range). Use
    `assemble_range` for globally compacted per-query rows.
    """
    state_spec = P(cfg.axis)

    def body(states, k1, k2):
        st = _local_state(states)
        shard = jax.lax.axis_index(cfg.axis).astype(jnp.int32)
        lo, hi = shard_bounds(cfg, shard)
        k1c = jnp.clip(k1, lo, hi + 1)
        k2c = jnp.clip(k2, lo - 1, hi)
        nonempty = (k1c <= k2c)
        keys, vals, counts, ok = range_runs(
            all_runs(cfg.local, st), k1c, k2c, max_candidates, max_results
        )
        counts = jnp.where(nonempty, counts, 0)
        ok = ok | ~nonempty
        ok = jax.lax.pmin(ok.astype(jnp.int32), cfg.axis) > 0
        return keys[None], vals[None], counts[None], ok[None]

    f = jax.shard_map(
        body, mesh=mesh,
        in_specs=(state_spec, P(), P()),
        out_specs=(state_spec, state_spec, state_spec, P()),
        check_vma=False,
    )
    keys, vals, counts, ok = f(states, k1, k2)
    return keys, vals, counts, ok[0]


def assemble_range(keys, vals, counts, ok, max_results: int):
    """Shard-major range output -> the facade's global contract.

    keys/vals: [S, nq, m] per-shard compacted rows (ascending, placebo-padded
    past counts[s, q]); counts: [S, nq] exact per-shard hit counts; ok: [nq].
    Returns (keys [nq, max_results], vals, counts [nq], ok) with rows globally
    ascending (shards are range-ordered) and placebo-padded past counts[q].
    Truncation — global totals past max_results, or a shard that clipped its
    own window — flips ok, never silently drops.
    """
    S, nq, m = keys.shape
    offsets = jnp.cumsum(counts, axis=0) - counts       # exclusive, over shards
    total = jnp.sum(counts, axis=0).astype(jnp.int32)
    ok = ok & (total <= max_results)

    j = jnp.arange(m, dtype=jnp.int32)[None, None, :]
    valid = j < counts[:, :, None]
    tgt = jnp.where(valid, offsets[:, :, None] + j, max_results)  # OOB -> drop
    rows = jnp.broadcast_to(jnp.arange(nq, dtype=jnp.int32)[None, :, None], (S, nq, m))
    out_k = jnp.full((nq, max_results), sem.PLACEBO_KEY, jnp.int32)
    out_v = jnp.full((nq, max_results), sem.EMPTY_VALUE, jnp.int32)
    out_k = out_k.at[rows, tgt].set(keys, mode="drop")
    out_v = out_v.at[rows, tgt].set(vals, mode="drop")
    return out_k, out_v, total, ok


def dist_cleanup(cfg: DistLSMConfig, mesh, states) -> LSMState:
    """Shard-local cleanup — zero communication."""
    state_spec = P(cfg.axis)

    def body(states):
        return _restack(lsm_cleanup(cfg.local, _local_state(states)))

    f = jax.shard_map(body, mesh=mesh, in_specs=(state_spec,), out_specs=state_spec,
                  check_vma=False)
    return f(states)


def dist_maintain(
    cfg: DistLSMConfig,
    mesh,
    states,
    budget: int | None = None,
    *,
    only_if_debt: bool = False,
) -> LSMState:
    """Shard-local budgeted maintenance — zero communication, same as
    cleanup/flush. `budget` is the PER-SHARD element budget (static); shards
    carry independent debt (ownership skew), so each compacts — or skips, with
    only_if_debt — on its own schedule."""
    state_spec = P(cfg.axis)

    def body(states):
        return _restack(
            lsm_maintain(cfg.local, _local_state(states), budget,
                         only_if_debt=only_if_debt)
        )

    f = jax.shard_map(body, mesh=mesh, in_specs=(state_spec,), out_specs=state_spec,
                  check_vma=False)
    return f(states)


def dist_size(cfg: DistLSMConfig, mesh, states):
    """Live (visible) element count across all shards, int32 scalar.

    Shards own disjoint key ranges, so per-shard survivor counts simply add —
    no cross-shard dedup pass is ever needed.
    """
    state_spec = P(cfg.axis)

    def body(states):
        st = _local_state(states)
        local = valid_count_runs(all_runs(cfg.local, st))
        return jax.lax.psum(local, cfg.axis)

    f = jax.shard_map(body, mesh=mesh, in_specs=(state_spec,), out_specs=P(),
                  check_vma=False)
    return f(states)


def dist_bulk_build(cfg: DistLSMConfig, mesh, keys, values) -> LSMState:
    """Build from n unique keys: each shard sorts its owned subset into the
    post-CLEANUP level layout (paper §5.2, per shard).

    The key set is replicated in; non-owned lanes become placebos, which sort
    last, so the owned prefix slices into levels exactly like a local bulk
    build of the owned subset. The per-shard resident-batch count r is a
    traced value (ownership skew is data-dependent), which `_redistribute`
    supports natively.
    """
    keys = jnp.asarray(keys, jnp.int32)
    values = jnp.asarray(values, jnp.int32)
    n = keys.shape[0]
    cap = cfg.local.capacity
    if n > cap:
        raise ValueError(
            f"bulk build of {n} keys exceeds per-shard capacity {cap} "
            "(one shard may own every key)"
        )
    state_spec = P(cfg.axis)
    b = cfg.local.batch_size

    def body(keys, values):
        shard = jax.lax.axis_index(cfg.axis).astype(jnp.int32)
        mine = owner_of(cfg, keys) == shard
        kv = jnp.where(mine, sem.encode_insert(keys), sem.PLACEBO_KV)
        val = jnp.where(mine, values, sem.EMPTY_VALUE)
        kv, val = ops.sort_pairs(kv, val)
        owned = jnp.sum(mine).astype(jnp.int32)
        r_new = (owned + b - 1) // b
        pk, pv = _placebo(cap - n)
        kv = jnp.concatenate([kv, pk])
        val = jnp.concatenate([val, pv])
        kvs, vals = _redistribute(cfg.local, kv, val, r_new)
        st = LSMState(
            key_vars=kvs, values=vals, r=r_new,
            overflowed=jnp.zeros((), dtype=bool),
            lvl_debt=jnp.zeros((cfg.local.num_levels,), dtype=jnp.int32),
            merged=jnp.zeros((), dtype=jnp.int32),
            **_fresh_buffer(b),
        )
        return _restack(st)

    f = jax.shard_map(body, mesh=mesh, in_specs=(P(), P()), out_specs=state_spec,
                  check_vma=False)
    return f(keys, values)


# ---------------------------------------------------------------------------
# Standalone jitted factories (original surface; donation where mutating)
# ---------------------------------------------------------------------------


def make_dist_update(cfg: DistLSMConfig, mesh):
    """Returns jitted update(states, key_vars[b], values[b]) -> states."""
    return jax.jit(functools.partial(dist_update, cfg, mesh), donate_argnums=0)


def make_dist_lookup(cfg: DistLSMConfig, mesh):
    """Returns jitted lookup(states, keys[q]) -> (found[q], values[q])."""
    return jax.jit(functools.partial(dist_lookup, cfg, mesh))


def make_dist_count(cfg: DistLSMConfig, mesh, max_candidates: int):
    """Returns jitted count(states, k1[q], k2[q]) -> (counts[q], ok[q])."""
    return jax.jit(
        functools.partial(dist_count, cfg, mesh, max_candidates=max_candidates)
    )


def make_dist_range(cfg: DistLSMConfig, mesh, max_candidates: int, max_results: int):
    """Returns jitted shard-major range(states, k1[q], k2[q])."""
    return jax.jit(functools.partial(
        dist_range, cfg, mesh, max_candidates=max_candidates, max_results=max_results
    ))


def make_dist_cleanup(cfg: DistLSMConfig, mesh):
    """Shard-local cleanup — zero communication."""
    return jax.jit(functools.partial(dist_cleanup, cfg, mesh), donate_argnums=0)


def make_dist_maintain(cfg: DistLSMConfig, mesh, budget: int | None = None):
    """Returns jitted maintain(states) -> states (shard-local, zero comm)."""
    return jax.jit(
        functools.partial(dist_maintain, cfg, mesh, budget=budget),
        donate_argnums=0,
    )


def make_dist_stage(cfg: DistLSMConfig, mesh):
    """Returns jitted stage(states, key_vars[b], values[b], count) -> states."""
    return jax.jit(functools.partial(dist_stage, cfg, mesh), donate_argnums=0)


def make_dist_flush(cfg: DistLSMConfig, mesh):
    """Returns jitted flush(states) -> states (shard-local, zero comm)."""
    return jax.jit(functools.partial(dist_flush, cfg, mesh), donate_argnums=0)


def make_dist_size(cfg: DistLSMConfig, mesh):
    """Returns jitted size(states) -> int32 scalar (live elements, all shards)."""
    return jax.jit(functools.partial(dist_size, cfg, mesh))
