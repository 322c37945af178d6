"""Built-in backends: the paper's three dictionary data structures adapted to
the `Backend` protocol (LSM §3-4, sorted array §5.1, cuckoo hash §5.1), plus
the range-partitioned multi-device LSM ("lsm_sharded").

Each adapter is a frozen dataclass wrapping the functional core's static
config; all array work stays in `repro.core.*` — these classes only translate
the uniform facade surface into the core's free-function calls.

Mesh/axis requirements (lsm_sharded)
------------------------------------
The sharded backend runs one full local LSM per device over a contiguous key
range (core/distributed.py). It needs a 1-D jax mesh whose named axis (default
``"shard"``) enumerates the shard devices:

  * ``Dictionary.create("lsm_sharded", num_shards=4)`` builds the mesh itself
    via `repro.launch.mesh.make_shard_mesh` over the first 4 visible devices
    (`num_shards=None` → every visible device);
  * or pass an existing mesh: ``create("lsm_sharded", mesh=m, axis="shard")``
    — the axis must exist in ``m.axis_names`` and its size becomes the shard
    count. Extra mesh axes are tolerated (the state is replicated over them).

The mesh is static backend identity: it rides in the frozen dataclass (jax
meshes are hashable), keys the facade's compiled-executable cache, and crosses
jit boundaries in the treedef. `batch_size` is the *global* update width —
every shard consumes the all-gathered batch with non-owned lanes turned into
placebos, so the per-shard batch-of-b invariant (and the unchanged local
binary-counter cascade) holds. `capacity` is likewise the guaranteed global
budget: each global batch ticks every shard's resident-batch counter, so the
per-shard arena must be able to hold every batch until a cleanup.

On CPU, spoof a multi-device pool with
``XLA_FLAGS=--xla_force_host_platform_device_count=4`` (before jax
initializes) — this is how the parity tests in tests/test_backend_parity.py
exercise 1/2/4 shards.
"""

from __future__ import annotations

import dataclasses
import math

import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec

from repro.api.backend import (
    Backend,
    Capabilities,
    OccupancyStats,
    register_backend,
)
from repro.api.plan import QueryPlan
from repro.core import cleanup as lsm_cleanup_mod
from repro.core import cuckoo as ck
from repro.core import distributed as dist
from repro.core import queries
from repro.core import sorted_array as sa
from repro.core.lsm import (
    LSMConfig,
    all_runs,
    lsm_bulk_build,
    lsm_debt,
    lsm_flush,
    lsm_flush_cost,
    lsm_init,
    lsm_stage,
    lsm_update,
)


def _levels_for(capacity: int, batch_size: int) -> int:
    """Smallest L with b * (2^L - 1) >= capacity."""
    batches = -(-capacity // batch_size)
    return max(1, math.ceil(math.log2(batches + 1)))


@register_backend
@dataclasses.dataclass(frozen=True)
class LSMBackend(Backend):
    """The paper's GPU LSM: amortized O(b log r) updates, ordered queries."""

    name = "lsm"
    caps = Capabilities(
        supports_updates=True,
        supports_deletes=True,
        supports_ordered_queries=True,
        supports_cleanup=True,
        supports_maintenance=True,
    )

    cfg: LSMConfig

    @classmethod
    def from_options(cls, *, capacity=None, batch_size=None, num_levels=None, **extra):
        if extra:
            raise TypeError(f"unknown options for backend 'lsm': {sorted(extra)}")
        b = int(batch_size) if batch_size is not None else 1024
        if num_levels is None:
            num_levels = _levels_for(int(capacity) if capacity else b * 1023, b)
        return cls(LSMConfig(batch_size=b, num_levels=int(num_levels)))

    @property
    def batch_size(self) -> int:
        return self.cfg.batch_size

    @property
    def capacity(self) -> int:
        return self.cfg.capacity

    @property
    def max_query_candidates(self) -> int:
        # Levels plus the b write-buffer slots a query window can overlap.
        return self.cfg.capacity + self.cfg.batch_size

    @property
    def has_write_buffer(self) -> bool:
        return True

    def init(self):
        return lsm_init(self.cfg)

    def bulk_build(self, keys, values):
        return lsm_bulk_build(self.cfg, keys, values)

    def update_encoded(self, state, key_vars, values):
        return lsm_update(self.cfg, state, key_vars, values)

    def stage_encoded(self, state, key_vars, values, count):
        return lsm_stage(self.cfg, state, key_vars, values, count)

    def flush_state(self, state, min_pending: int = 1):
        return lsm_flush(self.cfg, state, min_pending)

    def pending_count(self, state):
        return state.buf_n

    def occupancy(self, state):
        return OccupancyStats(
            pending=state.buf_n,
            resident=state.r * self.cfg.batch_size,
            debt=lsm_debt(self.cfg, state),
        )

    def flush_cost(self, state):
        return lsm_flush_cost(self.cfg, state)

    def lookup(self, state, keys):
        return queries.lookup_runs(all_runs(self.cfg, state), keys)

    def count(self, state, k1, k2, plan: QueryPlan):
        return queries.count_runs(all_runs(self.cfg, state), k1, k2, plan.max_candidates)

    def range(self, state, k1, k2, plan: QueryPlan):
        return queries.range_runs(
            all_runs(self.cfg, state), k1, k2, plan.max_candidates, plan.max_results
        )

    def cleanup(self, state):
        return lsm_cleanup_mod.lsm_cleanup(self.cfg, state)

    def maintain_state(self, state, budget, *, only_if_debt=False):
        return lsm_cleanup_mod.lsm_maintain(
            self.cfg, state, budget, only_if_debt=only_if_debt
        )

    def size(self, state):
        return queries.valid_count_runs(all_runs(self.cfg, state))

    def overflowed(self, state):
        return state.overflowed


@register_backend
@dataclasses.dataclass(frozen=True)
class ShardedLSMBackend(Backend):
    """Range-partitioned LSM over a device mesh: one local LSM per shard,
    routed by key ownership (core/distributed.py). Full capability row — the
    distributed structure loses nothing vs the single-device LSM; ordered
    queries stay shard-local + a psum/assembly combine.

    See the module docstring for mesh/axis requirements.
    """

    name = "lsm_sharded"
    caps = Capabilities(
        supports_updates=True,
        supports_deletes=True,
        supports_ordered_queries=True,
        supports_cleanup=True,
        supports_maintenance=True,
    )

    cfg: dist.DistLSMConfig
    mesh: object  # jax.sharding.Mesh — hashable, static backend identity

    @classmethod
    def from_options(
        cls, *, capacity=None, batch_size=None, num_levels=None,
        num_shards=None, mesh=None, axis="shard", **extra,
    ):
        if extra:
            raise TypeError(f"unknown options for backend 'lsm_sharded': {sorted(extra)}")
        if mesh is None:
            from repro.launch.mesh import make_shard_mesh

            mesh = make_shard_mesh(num_shards, axis=axis)
        if axis not in mesh.axis_names:
            raise ValueError(
                f"mesh has no axis {axis!r} (axes: {tuple(mesh.axis_names)})"
            )
        shards = int(mesh.shape[axis])
        if num_shards is not None and int(num_shards) != shards:
            raise ValueError(
                f"num_shards={num_shards} disagrees with mesh axis {axis!r} "
                f"of size {shards}"
            )
        b = int(batch_size) if batch_size is not None else 1024
        if num_levels is None:
            num_levels = _levels_for(int(capacity) if capacity else b * 1023, b)
        return cls(
            dist.DistLSMConfig(
                local=LSMConfig(batch_size=b, num_levels=int(num_levels)),
                num_shards=shards,
                axis=axis,
            ),
            mesh,
        )

    @property
    def batch_size(self) -> int:
        return self.cfg.local.batch_size

    @property
    def capacity(self) -> int:
        # Per-shard arena size == guaranteed global budget: every global
        # batch ticks every shard's resident-batch counter (placebo lanes
        # included), so one shard could end up holding all of it.
        return self.cfg.local.capacity

    @property
    def max_query_candidates(self) -> int:
        # max_candidates is applied per shard (queries clip to shard windows),
        # so the bound is the per-shard arena plus its local write buffer.
        return self.cfg.local.capacity + self.cfg.local.batch_size

    @property
    def num_shards(self) -> int:
        return self.cfg.num_shards

    @property
    def has_write_buffer(self) -> bool:
        return True

    @property
    def input_sharding(self):
        # Every shard reads the whole key set (dist_bulk_build); copying it
        # from the host to each device directly keeps device 0 from holding
        # a staged copy on top of its replica.
        return NamedSharding(self.mesh, PartitionSpec())

    def init(self):
        return dist.dist_lsm_init(self.cfg, self.mesh)

    def bulk_build(self, keys, values):
        return dist.dist_bulk_build(self.cfg, self.mesh, keys, values)

    def update_encoded(self, state, key_vars, values):
        return dist.dist_update(self.cfg, self.mesh, state, key_vars, values)

    def stage_encoded(self, state, key_vars, values, count):
        return dist.dist_stage(self.cfg, self.mesh, state, key_vars, values, count)

    def flush_state(self, state, min_pending: int = 1):
        return dist.dist_flush(self.cfg, self.mesh, state, min_pending)

    def pending_count(self, state):
        return dist.dist_pending(self.cfg, self.mesh, state)

    def occupancy(self, state):
        pending, resident, debt = dist.dist_occupancy(self.cfg, self.mesh, state)
        return OccupancyStats(pending=pending, resident=resident, debt=debt)

    def flush_cost(self, state):
        return dist.dist_flush_cost(self.cfg, self.mesh, state)

    def lookup(self, state, keys):
        return dist.dist_lookup(self.cfg, self.mesh, state, keys)

    def count(self, state, k1, k2, plan: QueryPlan):
        return dist.dist_count(self.cfg, self.mesh, state, k1, k2, plan.max_candidates)

    def range(self, state, k1, k2, plan: QueryPlan):
        keys, vals, counts, ok = dist.dist_range(
            self.cfg, self.mesh, state, k1, k2, plan.max_candidates, plan.max_results
        )
        return dist.assemble_range(keys, vals, counts, ok, plan.max_results)

    def cleanup(self, state):
        return dist.dist_cleanup(self.cfg, self.mesh, state)

    def maintain_state(self, state, budget, *, only_if_debt=False):
        # Shard-local (zero-communication): `budget` bounds each shard's
        # compaction independently, mirroring dist_cleanup's locality.
        return dist.dist_maintain(
            self.cfg, self.mesh, state, budget, only_if_debt=only_if_debt
        )

    def size(self, state):
        return dist.dist_size(self.cfg, self.mesh, state)

    def overflowed(self, state):
        return jnp.any(state.overflowed)


@register_backend
@dataclasses.dataclass(frozen=True)
class SortedArrayBackend(Backend):
    """One sorted run: O(n) per batch update (the Table 2 baseline), same
    query semantics as the LSM via the shared run-based pipelines."""

    name = "sorted_array"
    caps = Capabilities(
        supports_updates=True,
        supports_deletes=True,
        supports_ordered_queries=True,
        supports_cleanup=True,
    )

    cfg: sa.SAConfig
    b: int  # facade batch width; the SA core itself accepts any width

    @classmethod
    def from_options(cls, *, capacity=None, batch_size=None, **extra):
        if extra:
            raise TypeError(f"unknown options for backend 'sorted_array': {sorted(extra)}")
        cap = int(capacity) if capacity is not None else 1 << 20
        b = int(batch_size) if batch_size is not None else min(1024, cap)
        return cls(sa.SAConfig(capacity=cap), b)

    @property
    def batch_size(self) -> int:
        return self.b

    @property
    def capacity(self) -> int:
        return self.cfg.capacity

    def init(self):
        return sa.sa_init(self.cfg)

    def bulk_build(self, keys, values):
        return sa.sa_bulk_build(self.cfg, keys, values)

    def update_encoded(self, state, key_vars, values):
        return sa.sa_update_batch(self.cfg, state, key_vars, values)

    def stage_encoded(self, state, key_vars, values, count):
        # No staging buffer: apply immediately with the recency sort — staged
        # elements are the newest run either way, so queries agree with the
        # buffered LSM backends lane-for-lane (flush_state is a no-op).
        return sa.sa_stage(self.cfg, state, key_vars, values, count)

    def occupancy(self, state):
        # No buffer, no debt tracker: everything lives in the one run. n
        # counts stale duplicates until the next update's recency merge.
        zero = jnp.zeros((), jnp.int32)
        return OccupancyStats(pending=zero, resident=state.n, debt=zero)

    def _runs(self, state):
        return [(state.key_vars, state.values)]

    def lookup(self, state, keys):
        return queries.lookup_runs(self._runs(state), keys)

    def count(self, state, k1, k2, plan: QueryPlan):
        return queries.count_runs(self._runs(state), k1, k2, plan.max_candidates)

    def range(self, state, k1, k2, plan: QueryPlan):
        return queries.range_runs(
            self._runs(state), k1, k2, plan.max_candidates, plan.max_results
        )

    def cleanup(self, state):
        return sa.sa_cleanup(self.cfg, state)

    def size(self, state):
        return queries.valid_count_runs(self._runs(state))

    def overflowed(self, state):
        return state.n > self.cfg.capacity


@register_backend
@dataclasses.dataclass(frozen=True)
class CuckooBackend(Backend):
    """Static cuckoo hash (CUDPP-style): O(1) lookups, bulk build only, no
    ordered queries — the entire point of the paper's Table 1 comparison."""

    name = "cuckoo"
    caps = Capabilities(
        supports_updates=False,
        supports_deletes=False,
        supports_ordered_queries=False,
        supports_cleanup=False,
    )

    cfg: ck.CuckooConfig
    declared_capacity: int

    @classmethod
    def from_options(
        cls, *, capacity=None, load_factor=0.8, seed=0, max_rounds=100,
        batch_size=None, **extra,
    ):
        if extra:
            raise TypeError(f"unknown options for backend 'cuckoo': {sorted(extra)}")
        del batch_size  # accepted for create() symmetry; meaningless here
        cap = int(capacity) if capacity is not None else 1 << 20
        table_size = max(int(cap / float(load_factor)), 1)
        return cls(
            ck.CuckooConfig(table_size=table_size, max_rounds=int(max_rounds), seed=int(seed)),
            cap,
        )

    @property
    def batch_size(self) -> int:
        return 1  # no incremental updates; facade never chunks for cuckoo

    @property
    def capacity(self) -> int:
        return self.declared_capacity

    def init(self):
        m = self.cfg.table_size
        return ck.CuckooTable(
            slot_keys=jnp.full((m,), ck.EMPTY, jnp.int32),
            slot_vals=jnp.zeros((m,), jnp.int32),
            build_ok=jnp.asarray(True),
        )

    def bulk_build(self, keys, values):
        return ck.cuckoo_build(self.cfg, keys, values)

    def lookup(self, state, keys):
        return ck.cuckoo_lookup(self.cfg, state, keys)

    def size(self, state):
        return jnp.sum(state.slot_keys != ck.EMPTY).astype(jnp.int32)

    def overflowed(self, state):
        return ~state.build_ok
