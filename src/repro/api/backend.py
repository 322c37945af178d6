"""Backend protocol + registry for the unified `Dictionary` facade.

A backend is a *static* (frozen, hashable) description of one dictionary
implementation: it owns the functional core's config and adapts the core's
free functions to a uniform method surface over an opaque pytree state. The
facade keys its compiled-executable cache on the backend instance, so
hashability is load-bearing, not a style choice.

Capability flags make the paper's Table 1 machine-checkable: an op a backend
cannot answer (cuckoo COUNT/RANGE, cuckoo incremental insert) raises
`CapabilityError` up front with the list of backends that can — never a
silently missing feature.
"""

from __future__ import annotations

import abc
import dataclasses
from typing import Any, ClassVar, Dict, NamedTuple, Tuple, Type

from repro.api.plan import QueryPlan

# Backend state is an arbitrary pytree (LSMState, SAState, CuckooTable, ...).
BackendState = Any


class OccupancyStats(NamedTuple):
    """Cheap structural introspection for serving schedulers (int32 scalars).

    Unlike `size()` these never run query machinery — they read counters the
    state already carries, so a server can poll them between coalesced steps
    without paying a multi-run scan.
    """

    pending: Any   # staged write-buffer elements awaiting a flush
    resident: Any  # elements resident in the main structure (stale included)
    debt: Any      # estimated reclaimable stale elements (maintenance target)


class CapabilityError(NotImplementedError):
    """An operation the chosen backend cannot support (paper Table 1)."""


class KeyDomainError(ValueError):
    """Keys outside [0, MAX_USER_KEY] — they would alias the placebo key or
    flip sign under the `key << 1` status-bit encoding and silently corrupt
    ordering (core/semantics.py)."""


@dataclasses.dataclass(frozen=True)
class Capabilities:
    """What a backend can do. Flags mirror the paper's Table 1 columns."""

    supports_updates: bool          # incremental batch insert
    supports_deletes: bool          # incremental batch delete (tombstones)
    supports_ordered_queries: bool  # COUNT / RANGE
    supports_cleanup: bool          # stale-element purge
    supports_bulk_build: bool = True
    supports_maintenance: bool = False  # budgeted incremental compaction


class Backend(abc.ABC):
    """Adapter from one functional core to the facade's uniform surface.

    Implementations are frozen dataclasses; `name` and `caps` are class
    attributes. States flow through unchanged — the facade never inspects
    them beyond treating them as pytrees.
    """

    name: ClassVar[str]
    caps: ClassVar[Capabilities]

    # -- static geometry ----------------------------------------------------

    @property
    @abc.abstractmethod
    def batch_size(self) -> int:
        """Width b of one encoded update batch (facade pads/splits to this)."""

    @property
    @abc.abstractmethod
    def capacity(self) -> int:
        """Maximum resident encoded elements (incl. stale); for partitioned
        backends, the *guaranteed* global budget (worst-case ownership skew)."""

    @property
    def max_query_candidates(self) -> int:
        """Largest number of resident elements one [k1, k2] query window can
        overlap: capacity plus any write-buffer slots. QueryPlan auto-sizing
        clamps to this (clamping to bare capacity would leave a full
        structure's count/range permanently inexact once the buffer holds
        residents). Buffered backends override."""
        return self.capacity

    @property
    def has_write_buffer(self) -> bool:
        """Does this backend stage updates in a write buffer (flush/pending
        are meaningful) rather than applying them immediately? Serving
        schedulers gate their occupancy/flush policies on this."""
        return False

    @property
    def num_shards(self) -> int:
        """Device partitions behind this backend (1 = single-device).

        Partitioned backends (lsm_sharded) override this; the facade's
        pad/split update path is shard-agnostic either way — each b-wide
        chunk reaches `update_encoded` whole, and the backend routes lanes
        to owners itself.
        """
        return 1

    @property
    def input_sharding(self):
        """Where the facade places a host-side bulk-build input before the
        build program reads it; None leaves it on jax's default device."""
        return None

    # -- construction -------------------------------------------------------

    @classmethod
    @abc.abstractmethod
    def from_options(cls, **options) -> "Backend":
        """Build from `Dictionary.create(...)` keyword options."""

    @abc.abstractmethod
    def init(self) -> BackendState:
        """Empty state."""

    # -- ops (jit-traceable; called under the facade's compiled cache) ------

    def bulk_build(self, keys, values) -> BackendState:
        raise CapabilityError(self._no("bulk_build"))

    def update_encoded(self, state: BackendState, key_vars, values) -> BackendState:
        """Apply one b-wide encoded batch (key-variables + values)."""
        raise CapabilityError(self._no("update"))

    def stage_encoded(self, state: BackendState, key_vars, values, count) -> BackendState:
        """Stage one b-wide encoded sub-batch: the `count` real lanes are
        front-compacted in arrival order, the rest placebo.

        Contract: the later lane is the newer write — a later insert beats an
        earlier same-call tombstone (the write-buffer recency rule,
        docs/DESIGN.md §5) — and `count` bounds the occupancy a buffered
        backend may consume (placebo lanes never occupy buffer slots).
        Backends without a staging buffer apply immediately with an
        equivalent recency-sorted merge (see SortedArrayBackend)."""
        raise CapabilityError(self._no("update"))

    def flush_state(self, state: BackendState, min_pending: int = 1) -> BackendState:
        """Push staged (write-buffer) updates into the main structure when at
        least `min_pending` are buffered. Default: no buffer, nothing to do."""
        del min_pending
        return state

    def pending_count(self, state: BackendState):
        """Staged-but-unflushed element count (int32 scalar; 0 if unbuffered)."""
        del state
        import jax.numpy as jnp

        return jnp.zeros((), jnp.int32)

    def occupancy(self, state: BackendState) -> OccupancyStats:
        """Structural occupancy counters (see OccupancyStats). The default
        derives everything from pending_count — backends with richer state
        (resident batches, debt trackers) override with cheaper/fuller reads."""
        import jax.numpy as jnp

        zero = jnp.zeros((), jnp.int32)
        return OccupancyStats(
            pending=self.pending_count(state), resident=zero, debt=zero
        )

    def flush_cost(self, state: BackendState):
        """Estimated elements a `flush_state` would touch *now* (int32 scalar;
        0 when nothing is staged). Serving schedulers weigh this against
        buffer occupancy when choosing a flush point; backends without a
        buffer flush for free."""
        del state
        import jax.numpy as jnp

        return jnp.zeros((), jnp.int32)

    @abc.abstractmethod
    def lookup(self, state: BackendState, keys) -> Tuple[Any, Any]:
        """Batched LOOKUP -> (found, values)."""

    def count(self, state: BackendState, k1, k2, plan: QueryPlan):
        raise CapabilityError(self._no("count"))

    def range(self, state: BackendState, k1, k2, plan: QueryPlan):
        raise CapabilityError(self._no("range"))

    def cleanup(self, state: BackendState) -> BackendState:
        raise CapabilityError(self._no("cleanup"))

    def maintain_state(
        self,
        state: BackendState,
        budget: int | None,
        *,
        only_if_debt: bool = False,
    ) -> BackendState:
        """Budgeted incremental compaction: reclaim stale elements touching at
        most `budget` residents (STATIC int; None = full cleanup). Backends
        that never accumulate stale elements return the state unchanged, so
        maintenance is always safe to schedule."""
        del budget, only_if_debt
        return state

    @abc.abstractmethod
    def size(self, state: BackendState):
        """Live (visible) element count as an int32 scalar."""

    @abc.abstractmethod
    def overflowed(self, state: BackendState):
        """bool scalar — has any update exceeded static capacity?"""

    # -- diagnostics ---------------------------------------------------------

    def _no(self, op: str) -> str:
        alts = [n for n, c in _REGISTRY.items() if n != self.name and _op_supported(c, op)]
        return (
            f"backend {self.name!r} does not support {op!r}"
            + (f"; use backend={alts!r}" if alts else "")
        )


def _op_supported(cls: Type[Backend], op: str) -> bool:
    caps = cls.caps
    return {
        "update": caps.supports_updates,
        "insert": caps.supports_updates,
        "delete": caps.supports_deletes,
        "count": caps.supports_ordered_queries,
        "range": caps.supports_ordered_queries,
        "cleanup": caps.supports_cleanup,
        "maintain": caps.supports_maintenance,
        "bulk_build": caps.supports_bulk_build,
        "lookup": True,
    }.get(op, False)


_REGISTRY: Dict[str, Type[Backend]] = {}


def register_backend(cls: Type[Backend]) -> Type[Backend]:
    """Class decorator: make a Backend reachable via Dictionary.create(name)."""
    if not getattr(cls, "name", None):
        raise ValueError(f"backend class {cls.__name__} must define a name")
    _REGISTRY[cls.name] = cls
    return cls


def backend_class(name: str) -> Type[Backend]:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(
            f"unknown backend {name!r}; registered: {sorted(_REGISTRY)}"
        ) from None


def available_backends() -> Tuple[str, ...]:
    return tuple(sorted(_REGISTRY))
