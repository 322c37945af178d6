"""`Dictionary`: the jit-native facade over every dictionary backend.

Design:

* **Pytree-registered handle.** A `Dictionary` is (static backend, dynamic
  state). The backend (frozen dataclass) rides in the treedef, the state in
  the leaves, so a `Dictionary` can cross jit/scan/shard_map boundaries and
  live inside larger pytrees (e.g. the serving page table).

* **Compiled-executable cache.** Every op runs through one module-level
  cache keyed on (backend, op, static plan); `jax.jit` then specializes per
  input shape under that key. Mutating ops donate the incoming state
  buffers, so the facade matches the hand-rolled
  `jax.jit(functools.partial(...), donate_argnums=0)` plumbing it replaces —
  users never touch jit, partial, or donation. Mutators are *linear*: the
  receiving handle is consumed (its buffers are donated) and the returned
  handle must be used from then on.

* **Coalescing batch contract.** The paper's update is rigidly b-wide; the
  facade accepts any length and *stages* it: real lanes compact to the front
  (arrival order preserved), split into b-wide sub-batches, and feed the
  backend's write buffer (`stage_encoded`) through a `lax.scan` (single
  chunk: direct call). Sub-batch updates no longer consume a batch slot each
  — a slot is consumed only when a buffer overflows b pending elements, on
  explicit `flush()`, or when the `flush_threshold` policy triggers.
  Duplicate keys resolve in strict arrival order (the write-buffer recency
  rule, docs/DESIGN.md §5): the later lane/call wins, including a later
  insert over an earlier tombstone. Partial lanes can be masked per-call via
  `valid=`; masked lanes never occupy buffer slots.

* **Key-domain validation.** Keys outside [0, MAX_USER_KEY] alias the
  placebo key or flip sign under the status-bit encoding and silently
  corrupt ordering; the facade raises `KeyDomainError` at the boundary
  whenever inputs are concrete (inside a user's jit trace the check is
  skipped — values do not exist yet).
"""

from __future__ import annotations

import concurrent.futures
import functools
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.api.backend import (
    Backend,
    CapabilityError,
    KeyDomainError,
    get_backend_class,
)
from repro.api.plan import QueryPlan
from repro.core import semantics as sem
from repro.core.lsm import compact_real
from repro.kernels import ops

# (backend, op, statics, kernel backend) -> jitted executable. jax.jit keeps
# the per-shape specialization under each entry, so this stays small: one
# entry per (config, op) the process touches. The kernel backend is part of
# the key because it is read while tracing: after `ops.set_backend(...)` an
# op must trace anew, not reuse a program built on the other kernels.
_EXEC_CACHE: Dict[tuple, object] = {}

# `precompile` threads: XLA compiles each program on one core, so the
# programs of a run compile side by side.
_COMPILE_THREADS = 16


def _cached_exec(backend: Backend, op: str, fn, *, donate_state: bool = False, statics=()):
    key = (backend, op, statics, ops.get_backend())
    f = _EXEC_CACHE.get(key)
    if f is None:
        program = functools.partial(fn, backend, *statics)
        # jit names the program after this: `jit__exec_<op>` in a device trace.
        program.__name__, program.__qualname__ = fn.__name__, fn.__qualname__
        f = jax.jit(program, donate_argnums=(0,) if donate_state else ())
        _EXEC_CACHE[key] = f
    return f


def _span(method):
    """Open the host span `dictionary.<method>` around a public method: it
    records only while a profiler session is open."""
    name = "dictionary." + method.__name__

    @functools.wraps(method)
    def traced(*args, **kwargs):
        with jax.profiler.TraceAnnotation(name):
            return method(*args, **kwargs)

    return traced


# -- op bodies (backend bound statically via the cache) -----------------------


def _exec_update(backend, flush_threshold, maintenance_budget, state, keys,
                 values, is_delete, valid):
    """Encode, front-compact, pad to k*b, and stage the sub-batches (scan
    when k > 1), then apply the optional flush-threshold policy.

    Everything from encoding onward runs inside the jitted executable so the
    eager path does no array work (the Table 2 timing protocol measures this
    whole pipeline as the update cost, like the hand-rolled jit it replaced).

    Lanes reach `stage_encoded` in arrival order with a per-chunk real-lane
    count: duplicates resolve strictly by sequence (later lane/call wins —
    the write-buffer recency rule), and masked-out lanes are compacted away
    so they never occupy buffer slots.

    Sharded backends need no special casing here: each b-wide sub-batch
    reaches `stage_encoded` whole (all-gathered under shard_map); every
    shard re-compacts its owned lanes into its local buffer, so arrival
    order is preserved per key owner.
    """
    kv = sem.encode(keys, is_delete)
    vals = jnp.where(is_delete, sem.EMPTY_VALUE, values)
    b = backend.batch_size
    n = keys.shape[0]
    if valid is not None:
        # compact_real drops masked lanes (placebo-prefilled scatter), so no
        # pre-masking is needed.
        kv, vals, total_real = compact_real(kv, vals, valid)
    else:
        total_real = jnp.asarray(n, jnp.int32)
    k = -(-n // b)
    pad = k * b - n
    if pad:
        kv = jnp.concatenate([kv, jnp.full((pad,), sem.PLACEBO_KV, jnp.int32)])
        vals = jnp.concatenate([vals, jnp.full((pad,), sem.EMPTY_VALUE, jnp.int32)])
    kv = kv.reshape(k, b)
    vals = vals.reshape(k, b)
    counts = jnp.clip(total_real - jnp.arange(k, dtype=jnp.int32) * b, 0, b)
    if k == 1:
        state = backend.stage_encoded(state, kv[0], vals[0], counts[0])
    else:
        def body(st, chunk):
            ckv, cval, cnt = chunk
            return backend.stage_encoded(st, ckv, cval, cnt), None

        state, _ = jax.lax.scan(body, state, (kv, vals, counts))
    if flush_threshold is not None:
        state = backend.flush_state(state, flush_threshold)
    if maintenance_budget is not None:
        # Piggybacked budgeted compaction: only_if_debt gates the work behind
        # a traced prefix-debt check, so debt-free updates pay one comparison.
        state = backend.maintain_state(state, maintenance_budget, only_if_debt=True)
    return state


def _exec_flush(backend, maintenance_budget, state):
    state = backend.flush_state(state)
    if maintenance_budget is not None:
        state = backend.maintain_state(state, maintenance_budget, only_if_debt=True)
    return state


def _exec_pending(backend, state):
    return backend.pending_count(state)


def _exec_occupancy(backend, state):
    return backend.occupancy(state)


def _exec_flush_cost(backend, state):
    return backend.flush_cost(state)


def _exec_bulk_build(backend, keys, values):
    return backend.bulk_build(keys, values)


def _exec_lookup(backend, state, keys):
    return backend.lookup(state, keys)


def _exec_count(backend, plan, state, k1, k2):
    return backend.count(state, k1, k2, plan)


def _exec_range(backend, plan, state, k1, k2):
    return backend.range(state, k1, k2, plan)


def _exec_cleanup(backend, state):
    return backend.cleanup(state)


def _exec_maintain(backend, budget, state):
    return backend.maintain_state(state, budget)


def _exec_size(backend, state):
    return backend.size(state)


# -- input hygiene ------------------------------------------------------------


def _is_concrete(x) -> bool:
    return not isinstance(x, jax.core.Tracer)


def _check_key_domain(name: str, keys, valid=None) -> None:
    """Raise KeyDomainError for concrete keys outside [0, MAX_USER_KEY].

    Runs on the *original* input (before any int32 cast) so overflow can't
    wrap a bad key back into range. Lanes masked out by `valid` are exempt.
    """
    if not _is_concrete(keys) or (valid is not None and not _is_concrete(valid)):
        return
    a = np.asarray(keys)
    if a.dtype.kind not in "iu":
        raise KeyDomainError(f"{name} must be an integer array, got dtype {a.dtype}")
    bad = (a.astype(np.int64) < 0) | (a.astype(np.int64) > sem.MAX_USER_KEY)
    if valid is not None:
        bad = bad & np.asarray(valid)
    if bad.any():
        examples = np.asarray(a[bad]).ravel()[:5].tolist()
        raise KeyDomainError(
            f"{name} outside the key domain [0, {sem.MAX_USER_KEY}]: {examples} — "
            "out-of-domain keys alias the placebo key or flip sign under the "
            "status-bit encoding and would silently corrupt ordering"
        )


def _as_keys(name: str, x, sharding=None):
    """int32 1-D device array; host input goes straight to `sharding` when
    one is given."""
    if sharding is not None and _is_concrete(x) and not isinstance(x, jax.Array):
        arr = jax.device_put(np.asarray(x, np.int32), sharding)
    else:
        arr = jnp.asarray(x, jnp.int32)
    if arr.ndim == 0:
        arr = arr[None]
    if arr.ndim != 1:
        raise ValueError(f"{name} must be 1-D, got shape {arr.shape}")
    return arr


class Dictionary:
    """A dynamic dictionary handle: create once, thread through updates.

        d = Dictionary.create("lsm", capacity=1 << 20)
        d = d.insert(keys, values)      # consumes d's buffers (donation)
        found, vals = d.lookup(queries)

    All methods are jit-compiled internally and safe to call under an outer
    jit/scan (the handle is a pytree). Mutating methods return a NEW handle
    and donate the old one's buffers — keep only the returned handle.
    """

    __slots__ = ("_backend", "_state", "_validate", "_flush_threshold",
                 "_maintenance_budget")

    def __init__(self, backend: Backend, state, validate: bool = True,
                 flush_threshold: Optional[int] = None,
                 maintenance_budget: Optional[int] = None):
        self._backend = backend
        self._state = state
        self._validate = validate
        self._flush_threshold = flush_threshold
        self._maintenance_budget = maintenance_budget

    # -- construction --------------------------------------------------------

    @classmethod
    def create(cls, backend: str = "lsm", validate: bool = True,
               flush_threshold: Optional[int] = None,
               maintenance_budget: Optional[int] = None, **options) -> "Dictionary":
        """Empty dictionary:
        `create("lsm"|"lsm_sharded"|"sorted_array"|"cuckoo", ...)`.

        Common options: capacity, batch_size. Backend-specific: num_levels
        (lsm, lsm_sharded); num_shards, mesh, axis (lsm_sharded — see
        repro.api.backends for mesh/axis requirements); load_factor, seed,
        max_rounds (cuckoo). `validate=False` skips the host-side
        key-domain / uniqueness checks on concrete inputs (hot paths,
        benchmarks); capability errors always raise.

        `flush_threshold` (buffered backends): after every update, any write
        buffer holding >= flush_threshold staged elements is flushed into the
        main structure (1 = flush every call, the old pad-every-call
        latency/slot profile). Default None: buffers flush only on overflow,
        explicit `flush()`, or `cleanup()`.

        `maintenance_budget` (maintenance-capable backends): piggyback
        budgeted incremental compaction on every update/flush — at most
        `maintenance_budget` resident elements are touched per call, and a
        traced debt check skips the work entirely when there is nothing to
        reclaim. This keeps stale-element debt bounded without the
        stop-the-world `cleanup()` latency spike. `maintain()` can also be
        called explicitly at any time.
        """
        be = get_backend_class(backend).from_options(**options)
        if flush_threshold is not None:
            t = int(flush_threshold)
            if not 1 <= t <= be.batch_size:
                raise ValueError(
                    f"flush_threshold must be in [1, batch_size={be.batch_size}], got {t}"
                )
            flush_threshold = t
        if maintenance_budget is not None:
            if not be.caps.supports_maintenance:
                raise CapabilityError(be._no("maintain"))
            m = int(maintenance_budget)
            if m < 1:
                raise ValueError(f"maintenance_budget must be >= 1, got {m}")
            maintenance_budget = m
        return cls(be, be.init(), validate, flush_threshold, maintenance_budget)

    # -- static introspection ------------------------------------------------

    @property
    def backend(self) -> str:
        return self._backend.name

    @property
    def capabilities(self):
        return self._backend.caps

    @property
    def capacity(self) -> int:
        return self._backend.capacity

    @property
    def batch_size(self) -> int:
        return self._backend.batch_size

    @property
    def num_shards(self) -> int:
        """Device partitions behind this handle (1 unless backend is sharded)."""
        return self._backend.num_shards

    @property
    def buffered(self) -> bool:
        """Does this backend stage updates in a write buffer (pending/flush
        meaningful)? False for apply-immediately backends."""
        return self._backend.has_write_buffer

    @property
    def state(self):
        """The underlying core state (LSMState / SAState / CuckooTable)."""
        return self._state

    def __repr__(self) -> str:
        return (
            f"Dictionary(backend={self._backend.name!r}, "
            f"capacity={self.capacity}, batch_size={self.batch_size})"
        )

    # -- capability gate -----------------------------------------------------

    def _require(self, op: str, flag: bool) -> None:
        if not flag:
            raise CapabilityError(self._backend._no(op))

    def _evolve(self, new_state) -> "Dictionary":
        return Dictionary(self._backend, new_state, self._validate,
                          self._flush_threshold, self._maintenance_budget)

    # -- programs: the one place each op's statics and donation are decided --

    def _update_exec(self):
        return _cached_exec(
            self._backend, "update", _exec_update, donate_state=True,
            statics=(self._flush_threshold, self._maintenance_budget),
        )

    def _bulk_build_exec(self):
        return _cached_exec(self._backend, "bulk_build", _exec_bulk_build)

    def _flush_exec(self):
        return _cached_exec(
            self._backend, "flush", _exec_flush, donate_state=True,
            statics=(self._maintenance_budget,),
        )

    def _maintain_exec(self, budget: Optional[int]):
        if budget is None:
            budget = self._maintenance_budget
        else:
            budget = int(budget)
            if budget < 1:
                raise ValueError(f"maintain budget must be >= 1, got {budget}")
        return _cached_exec(
            self._backend, "maintain", _exec_maintain, donate_state=True,
            statics=(budget,),
        )

    def _cleanup_exec(self):
        return _cached_exec(self._backend, "cleanup", _exec_cleanup, donate_state=True)

    def _lookup_exec(self):
        return _cached_exec(self._backend, "lookup", _exec_lookup)

    def _window_exec(self, op: str, plan: Optional[QueryPlan]):
        fn = {"count": _exec_count, "range": _exec_range}[op]
        return _cached_exec(self._backend, op, fn, statics=(self._resolved_plan(plan),))

    def precompile(self, *, bulk: Optional[int] = None, lookups: Sequence[int] = (),
                   updates: Sequence[int] = (), windows: Sequence[int] = (),
                   plans: Sequence[Optional[QueryPlan]] = (None,),
                   maintain: Sequence[Optional[int]] = (), flush: bool = False,
                   cleanup: bool = False) -> dict:
        """Compile, concurrently, the programs that later calls on this
        handle's configuration will run, so that those calls do not compile.

        `bulk`: the key count of a later `bulk_build`. `lookups`, `updates`,
        `windows`: lane widths of later `lookup`, masked `update(...,
        valid=...)` and `count`/`range` calls; each window width compiles
        count and range under every plan in `plans`. `maintain`: budgets of
        later `maintain` calls. `flush`, `cleanup`: those programs too.

        Each is the very program the method runs (same statics, donation,
        kernel backend, argument shapes and placement); jax keys compiled
        programs on exactly that, so the later call finds it. Returns
        {(op, *shape or static): compiled program}.
        """
        st, sh = self._state, self._backend.input_sharding
        lanes = lambda w, dt=jnp.int32, where=None: jax.ShapeDtypeStruct((w,), dt, sharding=where)  # noqa: E731
        progs = {}
        if bulk is not None:
            progs[("bulk_build", bulk)] = (self._bulk_build_exec(),
                                           (lanes(bulk, where=sh), lanes(bulk, where=sh)))
        for w in lookups:
            progs[("lookup", w)] = (self._lookup_exec(), (st, lanes(w)))
        for w in updates:
            progs[("update", w)] = (self._update_exec(),
                                    (st, lanes(w), lanes(w), lanes(w, bool), lanes(w, bool)))
        for w in windows:
            for plan in plans:
                for op in ("count", "range"):
                    progs[(op, w, plan)] = (self._window_exec(op, plan), (st, lanes(w), lanes(w)))
        for budget in maintain:
            progs[("maintain", budget)] = (self._maintain_exec(budget), (st,))
        if flush:
            progs[("flush",)] = (self._flush_exec(), (st,))
        if cleanup:
            progs[("cleanup",)] = (self._cleanup_exec(), (st,))
        with concurrent.futures.ThreadPoolExecutor(_COMPILE_THREADS) as pool:
            futures = {name: pool.submit(lambda f, a: f.lower(*a).compile(), f, args)
                       for name, (f, args) in progs.items()}
            return {name: fut.result() for name, fut in futures.items()}

    # -- updates -------------------------------------------------------------

    @_span
    def update(self, keys, values=None, is_delete=None, valid=None) -> "Dictionary":
        """Mixed batch of any length: insert where ~is_delete, tombstone
        where is_delete; `valid=False` lanes are compacted away (they never
        occupy write-buffer slots).

        Updates are *staged*: sub-batches coalesce in the backend's write
        buffer and consume a batch slot only when more than batch_size
        elements are pending (or on `flush()` / the flush_threshold policy).
        Duplicate keys resolve in strict arrival order — the later lane or
        call wins, including a later insert over an earlier tombstone (the
        write-buffer recency rule; staged entries are immediately visible to
        every query). Returns the new handle (the old one's buffers are
        donated).
        """
        with jax.profiler.TraceAnnotation("dictionary.update.prepare"):
            caps = self._backend.caps
            self._require("update", caps.supports_updates)
            if self._validate:
                _check_key_domain("update keys", keys, valid)
            keys = _as_keys("keys", keys)
            n = keys.shape[0]
            if n == 0:
                return self

            if is_delete is None:
                is_delete = jnp.zeros((n,), bool)
            else:
                is_delete = jnp.asarray(is_delete, bool)
                if is_delete.ndim == 0:
                    is_delete = jnp.broadcast_to(is_delete, keys.shape)
                if _is_concrete(is_delete) and bool(np.asarray(is_delete).any()):
                    self._require("delete", caps.supports_deletes)
            if values is None:
                values = jnp.zeros((n,), jnp.int32)
            values = jnp.asarray(values, jnp.int32)
            if values.ndim == 0:
                values = jnp.broadcast_to(values, keys.shape)
            if values.shape != keys.shape or is_delete.shape != keys.shape:
                raise ValueError(
                    f"keys/values/is_delete shapes differ: {keys.shape}/"
                    f"{values.shape}/{is_delete.shape}"
                )
            if valid is not None:
                valid = jnp.asarray(valid, bool)

        new_state = self._update_exec()(self._state, keys, values, is_delete, valid)
        return self._evolve(new_state)

    def insert(self, keys, values, valid=None) -> "Dictionary":
        """Insert (key, value) pairs; newer values win on duplicate keys."""
        return self.update(keys, values, valid=valid)

    def delete(self, keys, valid=None) -> "Dictionary":
        """Delete keys via tombstones (paper §3.3).

        Keys are passed through unchanged so domain validation sees the
        original values (an early int32 cast would let out-of-range keys
        wrap silently and tombstone the wrong key).
        """
        # Gate on 'delete' here so the error names the op the user called
        # (update()'s own gate would report 'update' for e.g. cuckoo).
        self._require("delete", self._backend.caps.supports_deletes)
        return self.update(keys, is_delete=True, valid=valid)

    @_span
    def bulk_build(self, keys, values) -> "Dictionary":
        """Replace contents with n unique keys in one sort-and-segment pass
        (paper §5.2). n need not be a multiple of batch_size."""
        self._require("bulk_build", self._backend.caps.supports_bulk_build)
        if self._validate:
            _check_key_domain("bulk_build keys", keys)
        if self._validate and _is_concrete(keys):
            arr = np.asarray(keys)
            if len(np.unique(arr)) != arr.shape[0]:
                raise ValueError("bulk_build requires unique keys (paper §5.2)")
        placement = self._backend.input_sharding
        keys = _as_keys("keys", keys, placement)
        values = _as_keys("values", values, placement)
        return self._evolve(self._bulk_build_exec()(keys, values))

    @_span
    def cleanup(self) -> "Dictionary":
        """Purge stale elements and tombstones (paper §3.6/§4.5).

        Buffered backends fold staged updates into the compaction (the
        cleanup-boundary flush) — afterwards `pending()` is 0 and no batch
        slot was wasted on a partial batch."""
        self._require("cleanup", self._backend.caps.supports_cleanup)
        return self._evolve(self._cleanup_exec()(self._state))

    @_span
    def maintain(self, budget: Optional[int] = None) -> "Dictionary":
        """Budgeted incremental compaction: reclaim stale elements touching at
        most `budget` residents (STATIC Python int; each distinct budget
        compiles one executable).

        Precedence: an explicit `budget` wins; otherwise the handle's
        configured `maintenance_budget`; otherwise None — which degrades to a
        full `cleanup()` (maintain(∞) IS cleanup, minus the buffer fold).
        Queries are exact at every budget level — maintenance is
        observationally invisible. Sharded backends maintain shard-locally
        (zero communication; the budget bounds each shard independently).
        Returns the new handle (the old one's buffers are donated).
        """
        self._require("maintain", self._backend.caps.supports_maintenance)
        return self._evolve(self._maintain_exec(budget)(self._state))

    @_span
    def flush(self) -> "Dictionary":
        """Push staged (write-buffer) updates into the main structure.

        No-op for backends without a write buffer and for empty buffers. A
        partial buffer is placebo-padded to a full batch, consuming one batch
        slot — the cost the coalescing update path defers. Returns the new
        handle (the old one's buffers are donated)."""
        return self._evolve(self._flush_exec()(self._state))

    def pending(self):
        """Staged-but-unflushed element count (int32 scalar; 0 if unbuffered).

        For sharded backends this sums the shard-local buffers."""
        f = _cached_exec(self._backend, "pending", _exec_pending)
        return f(self._state)

    def occupancy(self):
        """OccupancyStats(pending, resident, debt) — structural counters for
        serving schedulers (repro.serve.server's admission/flush policy).

        Reads counters the state already carries (no query machinery), so
        polling between coalesced device steps is cheap: `pending` is the
        write-buffer occupancy, `resident` the main-structure elements (stale
        included — r*b for the LSM), `debt` the reclaimable-stale estimate
        that `maintain()` budgets against. Sharded backends psum all three."""
        f = _cached_exec(self._backend, "occupancy", _exec_occupancy)
        return f(self._state)

    def flush_cost_estimate(self):
        """Estimated elements a `flush()` would touch now (int32 scalar; 0
        when nothing is staged or the backend has no buffer).

        For the LSM this is the cascade merge the carried batch triggers —
        b * 2^trailing_ones(r) elements — so a scheduler can tell a cheap flush
        (empty low levels) from one that will cascade deep, and time forced
        flushes accordingly. Sharded backends sum the shard-local costs."""
        f = _cached_exec(self._backend, "flush_cost", _exec_flush_cost)
        return f(self._state)

    # -- queries -------------------------------------------------------------

    @_span
    def lookup(self, keys) -> Tuple[jax.Array, jax.Array]:
        """Batched LOOKUP -> (found: bool[nq], values: int32[nq])."""
        if self._validate:
            _check_key_domain("lookup keys", keys)
        keys = _as_keys("keys", keys)
        return self._lookup_exec()(self._state, keys)

    def _resolved_plan(self, plan: Optional[QueryPlan]) -> QueryPlan:
        return (plan or QueryPlan()).resolved(self._backend.max_query_candidates)

    @_span
    def count(self, k1, k2, plan: Optional[QueryPlan] = None):
        """COUNT(k1, k2) per query -> (counts: int32[nq], ok: bool[nq]).

        ok=False flags truncation by the plan — re-issue with an explicit
        larger QueryPlan for exactness.
        """
        self._require("count", self._backend.caps.supports_ordered_queries)
        if self._validate:
            _check_key_domain("count k1", k1)
            _check_key_domain("count k2", k2)
        k1, k2 = _as_keys("k1", k1), _as_keys("k2", k2)
        return self._window_exec("count", plan)(self._state, k1, k2)

    @_span
    def range(self, k1, k2, plan: Optional[QueryPlan] = None):
        """RANGE(k1, k2) -> (keys [nq, max_results], values, counts, ok).

        Rows are ascending by key and placebo-padded beyond counts[i].
        """
        self._require("range", self._backend.caps.supports_ordered_queries)
        if self._validate:
            _check_key_domain("range k1", k1)
            _check_key_domain("range k2", k2)
        k1, k2 = _as_keys("k1", k1), _as_keys("k2", k2)
        return self._window_exec("range", plan)(self._state, k1, k2)

    def size(self):
        """Live (visible) element count, int32 scalar (stale excluded)."""
        f = _cached_exec(self._backend, "size", _exec_size)
        return f(self._state)

    def overflowed(self):
        """bool scalar — did any update exceed the static capacity?"""
        return self._backend.overflowed(self._state)

    def counters(self) -> Dict[str, int]:
        """The work counters the state carries, read from its leaves with no
        program: {"merged_elements": elements merged by pushes, flushes,
        cleanups and maintains since the state was made}, summed over
        shards. It wraps modulo b * 2^32 (shard counts summed unwrapped), so
        take differences. {} for backends that keep no counter."""
        merged = getattr(self._state, "merged", None)
        if merged is None:
            return {}
        batches = int(np.asarray(merged).astype(np.uint32).sum(dtype=np.uint64))
        return {"merged_elements": batches * self.batch_size}


def _dict_flatten(d: Dictionary):
    return (d._state,), (
        d._backend, d._validate, d._flush_threshold, d._maintenance_budget
    )


def _dict_unflatten(aux, children):
    backend, validate, flush_threshold, maintenance_budget = aux
    obj = object.__new__(Dictionary)
    object.__setattr__(obj, "_backend", backend)
    object.__setattr__(obj, "_state", children[0])
    object.__setattr__(obj, "_validate", validate)
    object.__setattr__(obj, "_flush_threshold", flush_threshold)
    object.__setattr__(obj, "_maintenance_budget", maintenance_budget)
    return obj


jax.tree_util.register_pytree_node(Dictionary, _dict_flatten, _dict_unflatten)
