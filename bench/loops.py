"""The closed loop a traffic mix asks for, by its `loop` key.

`batches`: one caller sends whole batches to a `Dictionary` in the order of
the mix's `pattern` ("update", "lookup"), each acknowledged before the next;
a cleanup follows every `cleanup_every_updates`-th update batch and is timed
with it. The paper's protocol. A mix with `"whole_cycles": true` ends its
window on a cycle boundary (just after a cleanup) once the seconds have
passed, so that every window holds the same share of cleanup work.

The loop makes its data in set-up (`setup`), runs the measured window
(`window`), and then compares what the window produced with the reference
(`checks`). Set-up compiles every program the window calls, through
`Dictionary.precompile` and warm-up calls that the reference also sees.
"""

from __future__ import annotations

import time
from typing import List, NamedTuple, Optional

import numpy as np

import jax
import jax.numpy as jnp

from bench import data, reference, work
from bench.trace import span

# Lookup calls whose answers the check keeps: the first, then one in
# SAMPLE_EVERY chosen from the seed, at most SAMPLE_MAX (each holds a batch
# of answers).
SAMPLE_EVERY, SAMPLE_MAX = 16, 64


class Check(NamedTuple):
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value <= self.limit


def _dictionary(cfg: dict):
    from repro.api import Dictionary

    opts = dict(batch_size=cfg["batch_size"], num_levels=cfg["num_levels"])
    if cfg["backend"] == "lsm_sharded":
        opts["num_shards"] = cfg["num_shards"]
    return Dictionary.create(cfg["backend"], validate=False, **opts)


def _replicated(d):
    """Where a bulk input lives: on every device of a sharded state."""
    sharding = d.state.r.sharding
    if len(sharding.device_set) == 1:
        return None
    return jax.sharding.NamedSharding(sharding.mesh, jax.sharding.PartitionSpec())


def _device_keys(keys: dict) -> dict:
    return {k: jnp.uint32(v) for k, v in keys.items()}


def _p99_ms(calls: List[tuple]) -> Optional[float]:
    return float(np.percentile([(c[2] - c[0]) * 1e3 for c in calls], 99)) if calls else None


class BatchLoop:
    def __init__(self, cfg: dict, traffic: dict, seed: int, tracing: bool):
        self.cfg, self.traffic, self.tracing = cfg, traffic, tracing
        self.keys = data.stream_keys(seed)
        self.calls: List[tuple] = []  # per call: (issued, dispatched, done), host seconds
        self.ops = 0
        self.t0 = self.t1 = 0.0
        self.spans = {}        # host span name -> seconds inside the window
        self.counters = {}
        self.setup_s = {}      # set-up phase -> seconds
        self._t = time.perf_counter()

    def _phase(self, name: str) -> None:
        """Close the set-up phase `name`: seconds since the last one closed."""
        t = time.perf_counter()
        self.setup_s[name] = t - self._t
        self._t = t

    def _span(self, name: str, seconds: float) -> None:
        self.spans[name] = self.spans.get(name, 0.0) + seconds

    @property
    def window_s(self) -> float:
        return self.t1 - self.t0

    def slowest(self, k: int = 5) -> List[list]:
        """The k slowest calls of the window: [index, ms in all, ms of it
        dispatching on the host, seconds into the window]. A stall in the
        dispatch is the host's; one after it, the device's or the runtime's."""
        order = sorted(range(len(self.calls)), key=lambda n: self.calls[n][0] - self.calls[n][2])
        return [[n, (c[2] - c[0]) * 1e3, (c[1] - c[0]) * 1e3, c[0] - self.t0]
                for n in order[:k] for c in [self.calls[n]]]

    def setup(self) -> None:
        cfg, tr = self.cfg, self.traffic
        b = cfg["batch_size"]
        self.pattern = tr["pattern"]
        n = self.n = cfg["bulk_batches"] * b
        self.strata = data.Strata(n, self.keys)
        self.shards = cfg.get("num_shards", 1)
        updates = "update" in self.pattern
        self.width = tr["lookup"]["width"] if "lookup" in self.pattern else b
        d = _dictionary(cfg)
        d.precompile(bulk=n, updates=[b] if updates else [],
                     lookups=sorted({self.width, b} if updates else {self.width}),
                     cleanup=updates)
        self._phase("precompile")

        def bulk(keys):
            s = data.Strata(n, keys)
            j = jnp.arange(n, dtype=jnp.int32)
            return s.resident(jnp, j), s.bulk_value(jnp, j)

        where = _replicated(d)
        dkeys = _device_keys(self.keys)
        bulk = jax.jit(bulk) if where is None else jax.jit(bulk, out_shardings=(where, where))
        d = d.bulk_build(*bulk(dkeys))
        jax.block_until_ready(d.state)
        self._phase("bulk_build")
        self.churn = None
        if updates:
            share, cycle = tr["update"]["insert_share"], cfg["cleanup_every_updates"]
            self.churn = data.Churn(self.strata, b, share, cycle)

            @jax.jit
            def gen(keys, i):
                c = data.Churn(data.Strata(n, keys), b, share, cycle)
                return [(k[0], v[0], dels) for k, v, dels in c.batches(jnp, i[None])]

            rows = [gen(dkeys, jnp.int32(i)) for i in range(cycle)]
            # Per cycle parity: (keys of each batch, values of each batch, is_delete).
            self.upd = [([r[p][0] for r in rows], [r[p][1] for r in rows], rows[0][p][2])
                        for p in (0, 1)]
            self.valid = jnp.ones((b,), bool)
        if "lookup" in self.pattern:
            lk = tr["lookup"]
            share, width = lk["resident_share"], self.width
            batch = jax.jit(lambda keys, call: data.lookup_batch(
                jnp, data.Strata(n, keys), call, width, share))
            self.pool = [batch(dkeys, jnp.int32(c)) for c in range(lk["pool"])]
            if not updates:
                for q in self.pool[:2]:  # the window's program, run once before it
                    jax.block_until_ready(d.lookup(q))
        jax.block_until_ready(d.state)
        self._phase("traffic")
        self.d = d
        if self.tracing:
            self._work_model()

    # -- the work model, for the roofline readers (traced runs only) ---------

    @property
    def _range(self) -> int:
        """Keys per shard: `lsm_sharded` splits [0, 2^30 - 1) into equal
        ranges, one per shard."""
        return -(-(data.MAX_USER_KEY + 1) // self.shards)

    def _owned(self, j: np.ndarray) -> np.ndarray:
        """Keys per shard among strata j."""
        owner = np.minimum(j * self.strata.stride // self._range, self.shards - 1)
        return np.bincount(owner, minlength=self.shards)

    def _work_model(self) -> None:
        b, s = self.cfg["batch_size"], self.strata
        first = np.minimum(-(-np.arange(self.shards + 1) * self._range // s.stride), self.n)
        first[-1] = self.n
        self.owned_live = np.diff(first)  # each shard's share of the bulk load
        self.counter = [work.LsmCounter(b, self.cfg["num_levels"], -(-int(o) // b))
                        for o in self.owned_live]
        self.lookup_runs = []
        if self.churn is not None:
            c = self.churn
            i = np.arange(c.cycle)
            j = np.concatenate([c._rows(np, i, c.h_d, 0), c._rows(np, i, c.h_i, 1)], axis=1)
            self.batch_owned = [self._owned(row) for row in j]

    def work(self) -> dict:
        """Least bytes per device in the window."""
        if not self.tracing:
            return {}
        upd = sum(c.update_bytes() for c in self.counter) / self.shards
        look = sum(work.lookup_bytes(self.width, r) for runs in self.lookup_runs for r in runs)
        return {"update_bytes": upd, "lookup_bytes": look / self.shards}

    # -- the window ------------------------------------------------------------

    def window(self, seconds: float) -> None:
        d, tracing = self.d, self.tracing
        cycle = self.cfg.get("cleanup_every_updates")
        whole = self.traffic.get("whole_cycles", False)
        self.m = 0            # update batches applied
        lookups = 0
        self.kept = []        # (m, queries, found, values) of sampled lookup calls
        keep = {0} | set(np.flatnonzero(data.hash32(np, np.arange(1 << 16), self.keys["probe"])
                                        % SAMPLE_EVERY == 0)[:SAMPLE_MAX - 1].tolist())
        self.t0 = t = time.perf_counter()
        deadline = self.t0 + seconds
        done = False
        with span("bench.window", tracing):
            while not done:
                for kind in self.pattern:
                    t_issue = time.perf_counter()
                    if kind == "update":
                        i = self.m % cycle
                        keys, values, dels = self.upd[(self.m // cycle) % 2]
                        with span("bench.dispatch", tracing):
                            d = d.update(keys[i], values[i], is_delete=dels, valid=self.valid)
                        t_sent = time.perf_counter()
                        self.m += 1
                        with span("bench.ack", tracing):
                            jax.block_until_ready(d.state)
                        if tracing:
                            for c, lanes in zip(self.counter, self.batch_owned[i]):
                                c.stage(int(lanes))
                        if self.m % cycle == 0:
                            t_c = time.perf_counter()
                            with span("bench.cleanup", tracing):
                                d = d.cleanup()
                                jax.block_until_ready(d.state)
                            self._span("cleanup", time.perf_counter() - t_c)
                            if tracing:
                                for c, live in zip(self.counter, self.owned_live):
                                    c.cleanup(int(live))
                        self.ops += self.cfg["batch_size"]
                    else:
                        q = self.pool[lookups % len(self.pool)]
                        with span("bench.dispatch", tracing):
                            found, vals = d.lookup(q)
                        t_sent = time.perf_counter()
                        with span("bench.ack", tracing):
                            jax.block_until_ready((found, vals))
                        if lookups in keep:
                            self.kept.append((self.m, q, found, vals))
                        if tracing:
                            self.lookup_runs.append([c.runs for c in self.counter])
                        lookups += 1
                        self.ops += self.width
                    t = time.perf_counter()
                    self.calls.append((t_issue, t_sent, t))
                    done = t >= deadline and not (whole and self.m % cycle)
                    if done:
                        break
        self.t1 = t
        self.d = d
        self.counters = {"update_batches": self.m, "lookup_calls": lookups}

    def end_to_end(self) -> dict:
        return {"ops_rate": self.ops / self.window_s, "batch_p99_ms": _p99_ms(self.calls)}

    # -- the comparison ---------------------------------------------------------

    def checks(self, control: bool = False) -> List[Check]:
        out = self._check_updates(control) if self.churn is not None else []
        if "lookup" in self.pattern:
            bad = 0
            for m, q, found, vals in self.kept:
                q = np.asarray(q)
                want = reference.churn_lookup(self.strata, self.churn, q, m)
                got = (reference.churn_lookup(self.strata, self.churn, q, m, True) if control
                       else (np.asarray(found), np.asarray(vals)))
                bad += reference.mismatches(*got, *want)
            out.append(Check("sampled_lookup_mismatch", bad, 0))
            out.append(Check("sampled_lookup_calls_missing", int(not self.kept), 0))
        return out

    def _check_updates(self, control: bool) -> List[Check]:
        """Probe the state the window left: the last batch written (it sits
        in the write buffer), and resident, fresh and absent keys of random
        strata; then the live count and the overflow latch."""
        b, s, m = self.cfg["batch_size"], self.strata, self.m
        cycle = self.churn.cycle
        probes = [np.asarray(self.upd[(m - 1) // cycle % 2][0][(m - 1) % cycle])]
        idx = np.arange(b, dtype=np.int64)
        for fn in (s.resident, s.fresh, s.absent):
            probes.append(fn(np, data.hash_mod(np, idx, self.keys["probe"], s.n)))
            idx = idx + b
        bad = 0
        for q in probes:
            want = reference.churn_lookup(s, self.churn, q, m)
            if control:
                got = reference.churn_lookup(s, self.churn, q, m, True)
            else:
                got = tuple(np.asarray(x) for x in self.d.lookup(q.astype(np.int32)))
            bad += reference.mismatches(*got, *want)
        live = self.churn.live_after(m)
        size = live if control else int(self.d.size())
        return [Check("probe_lookup_mismatch", bad, 0),
                Check("live_count_error", abs(size - live), 0),
                Check("overflowed", int(bool(self.d.overflowed())), 0)]


LOOPS = {"batches": BatchLoop}
