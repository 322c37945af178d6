#!/usr/bin/env python3
"""Drive the dictionary and its server once on a TPU at the paper's scale.

    python chip_smoke.py                # one chip: the `lsm` dictionary, n ~ 2^27
    python chip_smoke.py --chips 4      # four chips: `lsm_sharded`, n ~ 2^28

One chip (arXiv:1707.05354 §5: b = 2^16, n = 2^27, L = 12, about 2 GiB of
state), in one process, through the public entry points:

  load     bulk_build of ~2^27 distinct keys drawn from the 2^30 key domain
  updates  insert/delete full b-batches and masked sub-batches through the
           write buffer (the first flush cascades eleven levels into level
           11), then flush, maintain(budget) and cleanup
  queries  lookup of 2^16 keys (resident, written, absent, deleted); count
           and range at the paper's expected range lengths 8 and 1024
  server   DictionaryServer over the same dictionary: tenants above the bulk
           keys replay a "mixed" trace

Four chips run only the sharded load, update and query sequence, and check
that every state leaf spans the four devices and that no device holds the
bulk of the memory.

Every answer is checked against a NumPy oracle made from `--seed` (sorted
unique keys plus searchsorted). Each phase prints its wall and compile
seconds, the seconds of this script's own NumPy work (making data, keeping
the oracle), the device's peak bytes and the kernel path each op took. Every
program a run needs compiles once, concurrently, through
`Dictionary.precompile` before the bulk build. The last line is a JSON
object naming the device; the script exits non-zero, without that line, when
no TPU is present or any check fails.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import functools
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

import jax  # noqa: E402
from jax import monitoring  # noqa: E402

from repro.api import Dictionary, QueryPlan  # noqa: E402
from repro.compile_cache import enable_compile_cache  # noqa: E402
from repro.core import semantics as sem  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.serve import DictionaryServer, ServerConfig  # noqa: E402
from repro.serve.traffic import make_trace, replay_oracle, replay_server  # noqa: E402


@dataclasses.dataclass(frozen=True)
class Scale:
    """Sizes of one run. The defaults are the paper's; tests shrink them."""

    log_b: int = 16                 # batch size b
    num_levels: int = 12            # capacity b * (2^L - 1)
    bulk_batches: int = 2047        # resident batches after the load
    num_shards: int = 1
    lookups: int = 1 << 16
    windows: int = 1 << 12          # count/range queries per expected length
    range_lengths: tuple = (8, 1024)
    sub_batch: int = 1000           # lanes of a masked sub-batch
    maintain_budget: int = 1 << 20
    tenants: int = 16
    tenant_keys: int = 4096
    trace_events: int = 256

    @property
    def b(self) -> int:
        return 1 << self.log_b

    @property
    def n_bulk(self) -> int:
        return self.bulk_batches * self.b

    @property
    def tenant_base(self) -> int:
        """Bulk keys live below this; server tenants at and above it."""
        return sem.MAX_USER_KEY + 1 - self.tenants * self.tenant_keys


ONE_CHIP = Scale()
# About 2^26 keys per chip. One shard may own every key, so each shard's
# arena is sized for the whole set: L = 12 holds 4095 batches.
FOUR_CHIPS = Scale(bulk_batches=4095, num_shards=4)


class CheckFailed(AssertionError):
    pass


def check(cond, what: str) -> None:
    if not cond:
        raise CheckFailed(what)


# -- host work: the data and the oracle ------------------------------------------


class HostClock:
    """Seconds spent in this script's own NumPy work, which is not the
    system's: making the data and keeping the oracle. Nested timed calls
    count once."""

    seconds = 0.0
    _depth = 0

    @classmethod
    def timed(cls, fn):
        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            cls._depth += 1
            t0 = time.perf_counter()
            try:
                return fn(*args, **kwargs)
            finally:
                cls._depth -= 1
                if cls._depth == 0:
                    cls.seconds += time.perf_counter() - t0
        return wrapper


host = HostClock.timed


class Oracle:
    """Sorted unique live keys with their values; updates apply in order."""

    @host
    def __init__(self, keys: np.ndarray, values: np.ndarray):
        check(bool((keys[1:] > keys[:-1]).all()), "oracle: bulk keys not sorted and unique")
        self.keys = keys.astype(np.int64)
        self.values = values.astype(np.int32)

    @host
    def apply(self, keys, values, is_delete) -> None:
        """Arrival-ordered updates: the last write to a key wins."""
        keys = np.asarray(keys, np.int64)
        last = len(keys) - 1 - np.unique(keys[::-1], return_index=True)[1]
        k, v, d = keys[last], np.asarray(values)[last], np.asarray(is_delete)[last]
        pos = np.searchsorted(self.keys, k)
        hit = pos < len(self.keys)
        hit[hit] = self.keys[pos[hit]] == k[hit]
        base_k, base_v = np.delete(self.keys, pos[hit]), np.delete(self.values, pos[hit])
        ins = ~d
        at = np.searchsorted(base_k, k[ins])
        self.keys = np.insert(base_k, at, k[ins])
        self.values = np.insert(base_v, at, v[ins].astype(np.int32))

    @host
    def lookup(self, q):
        q = np.asarray(q, np.int64)
        idx = np.minimum(np.searchsorted(self.keys, q), len(self.keys) - 1)
        found = self.keys[idx] == q
        return found, np.where(found, self.values[idx], 0)

    @host
    def window(self, k1, k2):
        lo = np.searchsorted(self.keys, np.asarray(k1, np.int64), side="left")
        hi = np.searchsorted(self.keys, np.asarray(k2, np.int64), side="right")
        return lo, np.maximum(hi - lo, 0)

    @host
    def range(self, k1, k2, max_results):
        lo, cnt = self.window(k1, k2)
        j = np.arange(max_results)[None, :]
        valid = j < cnt[:, None]
        idx = np.minimum(lo[:, None] + j, len(self.keys) - 1)
        keys = np.where(valid, self.keys[idx], sem.PLACEBO_KEY)
        vals = np.where(valid, self.values[idx], sem.EMPTY_VALUE)
        return keys, vals, cnt


@host
def make_bulk(scale: Scale, rng):
    """~n distinct keys spread evenly over [0, tenant_base), one per stratum,
    in ascending order: the key domain itself is never materialised."""
    n, span = scale.n_bulk, scale.tenant_base
    stride = span // n
    keys = (np.arange(n, dtype=np.int64) * span) // n + rng.integers(0, stride, n)
    values = rng.integers(-(1 << 30), 1 << 30, n, dtype=np.int32)
    return keys.astype(np.int32), values


# -- checks against the oracle ---------------------------------------------------


def check_lookup(d: Dictionary, oracle: Oracle, q, what: str) -> None:
    found, vals = d.lookup(q)
    found, vals = np.asarray(found), np.asarray(vals)
    ef, ev = oracle.lookup(q)
    check(np.array_equal(found, ef), f"{what}: found differs on {int((found != ef).sum())} keys")
    check(np.array_equal(np.where(found, vals, 0), ev), f"{what}: values differ")


@host
def window_queries(scale: Scale, rng, length: int):
    """Windows whose expected population is `length` resident keys."""
    width = length * scale.tenant_base // scale.n_bulk
    k1 = rng.integers(0, scale.tenant_base - width, scale.windows)
    return k1, k1 + width - 1


def plan_for(length: int) -> QueryPlan:
    # Twice the expected population bounds a stratified window, plus the
    # update phase's new keys and any not yet compacted older versions.
    return QueryPlan(max_candidates=max(32, 2 * length), max_results=max(32, 2 * length))


def check_count_range(d: Dictionary, oracle: Oracle, scale: Scale, rng) -> None:
    for length in scale.range_lengths:
        k1, k2 = window_queries(scale, rng, length)
        plan = plan_for(length)
        counts, ok = d.count(k1, k2, plan)
        _, ecnt = oracle.window(k1, k2)
        check(np.asarray(ok).all(), f"count L={length} truncated")
        check(np.array_equal(np.asarray(counts), ecnt), f"count L={length} differs")
        keys, vals, rcnt, rok = d.range(k1, k2, plan)
        ekeys, evals, _ = oracle.range(k1, k2, plan.max_results)
        check(np.asarray(rok).all(), f"range L={length} truncated")
        check(np.array_equal(np.asarray(rcnt), ecnt), f"range L={length} counts differ")
        check(np.array_equal(np.asarray(keys), ekeys), f"range L={length} keys differ")
        check(np.array_equal(np.asarray(vals), evals), f"range L={length} values differ")
        print(f"  L={length} mean count {ecnt.mean():.3f}, max {ecnt.max()}", flush=True)


# -- phase bookkeeping -------------------------------------------------------------


class Phases:
    """Wall, compile and host seconds, persistent-cache hits, peak device
    bytes and kernel paths per phase. Compile seconds sum JAX's trace,
    lowering and backend-compile events (a cache hit is timed as its
    retrieval); host seconds are `HostClock`'s."""

    def __init__(self):
        self.compile_s = 0.0
        self.cache_hits = 0
        self.rows = []
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def _on_duration(self, name, secs, **_):
        if name.startswith("/jax/core/compile/"):
            self.compile_s += secs

    def _on_event(self, name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    @contextlib.contextmanager
    def __call__(self, name: str):
        c0, h0, t0 = self.compile_s, self.cache_hits, time.perf_counter()
        host0 = HostClock.seconds
        with ops.record_paths() as paths:
            yield
        row = {
            "phase": name,
            "wall_s": time.perf_counter() - t0,
            "compile_s": self.compile_s - c0,
            "host_s": HostClock.seconds - host0,
            "cache_hits": self.cache_hits - h0,
            "peak_bytes": [dev.memory_stats().get("peak_bytes_in_use") if dev.memory_stats() else None
                           for dev in jax.local_devices()],
            "paths": sorted({f"{op}:{path}" for op, path in paths}),
        }
        self.rows.append(row)
        print("phase " + json.dumps(row), flush=True)


def resident(d: Dictionary) -> int:
    occ = d.occupancy()
    return int(occ.resident) + int(occ.pending)


# -- the one-chip run --------------------------------------------------------------


@host
def update_batches(scale: Scale, oracle: Oracle, rng):
    """(keys, values, is_delete, valid) b-wide calls: full batches of new
    keys, overwrites and deletes, then masked sub-batches that re-insert a
    deleted key and tombstone fresh inserts."""
    b = scale.b
    absent = rng.integers(0, scale.tenant_base, 2 * b)
    fresh = absent[~oracle.lookup(absent)[0]][:b]
    fresh = np.unique(fresh)
    fresh = np.concatenate([fresh, fresh[: b - len(fresh)]])  # width b, dups allowed
    present = oracle.keys[rng.choice(len(oracle.keys), 2 * b, replace=False)]
    vals = lambda: rng.integers(-(1 << 30), 1 << 30, b, dtype=np.int32)  # noqa: E731
    ones, zeros = np.ones(b, bool), np.zeros(b, bool)
    doomed = np.concatenate([present[b : 2 * b - b // 4], fresh[: b // 4]])
    calls = [
        (fresh, vals(), zeros, ones),                 # new keys
        (present[:b], vals(), zeros, ones),           # overwrites
        (doomed, np.zeros(b, np.int32), ones, ones),  # deletes
    ]
    m = scale.sub_batch
    sub = np.zeros(b, bool)
    sub[:m] = True
    # Re-insert half of the deleted keys, tombstone some fresh ones.
    k = np.concatenate([doomed[: m // 2], fresh[b // 2 : b // 2 + m - m // 2]])
    d = np.concatenate([np.zeros(m // 2, bool), np.ones(m - m // 2, bool)])
    calls.append((np.resize(k, b), vals(), np.resize(d, b), sub))
    calls.append((np.resize(present[b // 2 : b // 2 + m], b), vals(), zeros, sub))
    return calls


def apply_calls(d: Dictionary, oracle: Oracle, scale: Scale, calls, rng):
    """Send the calls in order; returns the new handle and lookups that
    probe what they did (`lookup_queries`)."""
    for keys, values, is_delete, valid in calls:
        d = d.update(keys, values, is_delete=is_delete, valid=valid)
    keys, values, is_delete, valid = (np.concatenate(x) for x in zip(*calls))
    oracle.apply(keys[valid], values[valid], is_delete[valid])
    q = lookup_queries(scale, oracle, keys[valid & ~is_delete], keys[valid & is_delete], rng)
    return d, q


@host
def lookup_queries(scale: Scale, oracle: Oracle, written, deleted, rng):
    """A quarter each: resident keys, keys the updates wrote, keys absent
    from the start, and keys the updates deleted (whatever became of them
    later; the oracle knows)."""
    quarter = scale.lookups // 4
    return np.concatenate([
        oracle.keys[rng.integers(0, len(oracle.keys), quarter)],
        rng.choice(written, quarter),
        rng.integers(0, scale.tenant_base, quarter),
        rng.choice(deleted, scale.lookups - 3 * quarter),
    ])


def precompile(d: Dictionary, scale: Scale, **extra) -> dict:
    """Compile, concurrently, every program of `d`'s configuration that the
    run calls at these shapes."""
    return d.precompile(lookups=[scale.lookups], updates=[scale.b], windows=[scale.windows],
                        plans=[plan_for(length) for length in scale.range_lengths],
                        cleanup=True, **extra)


def load(scale: Scale, seed: int, phase, **options):
    """Make the data, compile every XLA program of the run, bulk-build."""
    rng = np.random.default_rng(seed)
    with phase("load"):
        keys, values = make_bulk(scale, rng)
        oracle = Oracle(keys, values)
        d = Dictionary.create(validate=False, batch_size=scale.b,
                              num_levels=scale.num_levels, **options)
        precompile(d, scale, bulk=scale.n_bulk, maintain=[scale.maintain_budget], flush=True)
        d = d.bulk_build(keys, values)
        del keys, values
        check(not bool(d.overflowed()), "overflow latch set at load")
        # Each shard rounds its owned share up to whole batches.
        check(scale.n_bulk <= resident(d) < scale.n_bulk + scale.num_shards * scale.b,
              "bulk build resident count")
    print(f"resident elements after load: {resident(d)}", flush=True)
    return d, oracle, rng


def run_updates_and_queries(d, oracle, scale, rng, phase):
    with phase("updates"):
        d, q = apply_calls(d, oracle, scale, update_batches(scale, oracle, rng), rng)
        check_lookup(d, oracle, q, "staged lookup")
        d = d.flush()
        d = d.maintain(scale.maintain_budget)
        check_lookup(d, oracle, q, "lookup after maintain")
        d = d.cleanup()
        check(not bool(d.overflowed()), "overflow latch set")
        check(resident(d) >= len(oracle.keys), "cleanup lost residents")
    print(f"resident elements after cleanup: {resident(d)} (oracle {len(oracle.keys)})", flush=True)
    with phase("queries"):
        check_lookup(d, oracle, q, "lookup")
        check_count_range(d, oracle, scale, rng)
    return d


def run_server(d, oracle, scale, seed, phase):
    with phase("server"):
        d = d.flush()  # the server's occupancy model starts from an empty buffer
        cfg = ServerConfig(
            backend=d.backend,
            batch_size=d.batch_size,
            lane_quantum=d.batch_size,   # one update and one lookup shape
            window_quantum=8,
            default_plan=QueryPlan(max_candidates=256),
        )
        srv = DictionaryServer(cfg, dictionary=d)
        srv.register_tenant("bulk", key_space=scale.tenant_base)
        tenants, trace = make_trace("mixed", scale.tenants, scale.tenant_keys,
                                    scale.trace_events, seed=seed)
        for t in tenants:
            srv.register_tenant(t, key_space=scale.tenant_keys)
        results = replay_server(srv, trace)
        check_trace(trace, results)
        # End state: every tenant key against replay_oracle, in one step.
        final = host(replay_oracle)(trace)
        every = np.arange(scale.tenant_keys)
        tickets = {t: srv.submit_lookup(t, every) for t in tenants}
        for t, ticket in tickets.items():
            found, vals = ticket.result()
            live = final.get(t, {})
            check(np.array_equal(found, np.isin(every, list(live))), f"server end state: {t} keys")
            check(np.array_equal(vals, [live.get(int(k), 0) for k in every]),
                  f"server end state: {t} values")
        d = srv.dictionary
        probe = oracle.keys[:: max(1, len(oracle.keys) // scale.b)][: scale.b]
        check_lookup(d, oracle, probe, "bulk keys after the server")
        print(f"  server: {len(trace)} ops, {srv.stats.device_steps} device steps", flush=True)
    return d


@host
def check_trace(trace, results) -> None:
    """Replay each tenant's ops in order against a dict and compare every
    answer the server gave."""
    state = {}
    for i, (op, res) in enumerate(zip(trace, results)):
        live = state.setdefault(op.tenant, {})
        if op.kind == "update":
            for k, v, dl in zip(op.keys.tolist(), op.values.tolist(), op.is_delete.tolist()):
                if dl:
                    live.pop(k, None)
                else:
                    live[k] = v
            continue
        if op.kind == "lookup":
            f, v = res
            ef = np.asarray([k in live for k in op.keys.tolist()])
            ev = np.asarray([live.get(k, 0) for k in op.keys.tolist()])
            check(np.array_equal(f, ef) and np.array_equal(v, ev), f"server op {i}: lookup")
            continue
        lo, hi = int(op.k1[0]), int(op.k2[0])
        inside = sorted(k for k in live if lo <= k <= hi)
        if op.kind == "count":
            counts, ok = res
            check(bool(ok[0]) and int(counts[0]) == len(inside), f"server op {i}: count")
        else:
            keys, vals, counts, ok = res
            n = int(counts[0])
            check(bool(ok[0]) and n == len(inside), f"server op {i}: range count")
            check(keys[0, :n].tolist() == inside, f"server op {i}: range keys")
            check(vals[0, :n].tolist() == [live[k] for k in inside], f"server op {i}: range values")


def run_one_chip(scale: Scale, seed: int, phase: Phases) -> None:
    d, oracle, rng = load(scale, seed, phase, backend="lsm")
    d = run_updates_and_queries(d, oracle, scale, rng, phase)
    run_server(d, oracle, scale, seed, phase)


# -- the four-chip run -------------------------------------------------------------


def run_sharded(scale: Scale, seed: int, phase: Phases) -> None:
    d, oracle, rng = load(scale, seed, phase, backend="lsm_sharded",
                          num_shards=scale.num_shards)
    d = run_updates_and_queries(d, oracle, scale, rng, phase)
    spans = {len(leaf.sharding.device_set) for leaf in jax.tree.leaves(d.state)}
    check(spans == {scale.num_shards}, f"state leaves span {spans} devices")
    peaks = phase.rows[-1]["peak_bytes"][: scale.num_shards]
    print(f"per-device peak bytes: {peaks}", flush=True)
    check(min(peaks) >= 0.8 * max(peaks), "per-device peak memory is unbalanced")


# -- entry point ---------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform}", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(devices)} device(s)", file=sys.stderr)
        return 2
    cache = enable_compile_cache()
    print(f"device: {dev.device_kind} x{len(devices)}; compile cache: {cache}", flush=True)
    phase = Phases()
    t0 = time.perf_counter()
    if args.chips == 4:
        run_sharded(FOUR_CHIPS, args.seed, phase)
    else:
        run_one_chip(ONE_CHIP, args.seed, phase)
    print(f"total wall {time.perf_counter() - t0:.3f} s, compile {phase.compile_s:.3f} s, "
          f"host {HostClock.seconds:.3f} s, cache hits {phase.cache_hits}", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
