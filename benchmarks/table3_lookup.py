"""Paper Table 3: lookup rates (none-exist / all-exist) — LSM vs SA vs cuckoo.

Protocol: for fixed n and batch size b, build every possible LSM with
r = 1..n/b resident batches (we sample r over the range to bound CPU time),
issue n queries, report min/max/harmonic-mean M queries/s. All three
structures run through the unified `Dictionary` facade.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, hmean, time_fn


def run(log_n: int = 18, log_bs=(14, 16), r_samples: int = 6) -> None:
    from repro.api import Dictionary

    n = 1 << log_n
    rng = np.random.default_rng(1)
    keys = rng.choice(1 << 29, 2 * n, replace=False).astype(np.int32)
    present, absent = keys[:n], keys[n:]
    vals = (present % 1009).astype(np.int32)

    for log_b in log_bs:
        b = 1 << log_b
        num_batches = n // b
        d = Dictionary.create("lsm", batch_size=b, capacity=n, validate=False)

        rates = {"none": [], "all": []}
        sample_rs = set(np.linspace(1, num_batches, min(r_samples, num_batches), dtype=int))
        for r in range(1, num_batches + 1):
            d = d.insert(jnp.asarray(present[(r - 1) * b : r * b]),
                         jnp.asarray(vals[(r - 1) * b : r * b]))
            if r not in sample_rs:
                continue
            q_all = jnp.asarray(present[rng.integers(0, r * b, n)])
            q_none = jnp.asarray(absent[:n])
            t = time_fn(d.lookup, q_none, warmup=1, iters=3)
            rates["none"].append(n / t / 1e6)
            t = time_fn(d.lookup, q_all, warmup=1, iters=3)
            rates["all"].append(n / t / 1e6)
        for kind in ("none", "all"):
            rs = rates[kind]
            emit(f"table3/lookup_{kind}_b2^{log_b}", 1.0 / (hmean(rs) * 1e6) if rs else 0,
                 f"mean={hmean(rs):.1f}Mq/s min={min(rs):.1f} max={max(rs):.1f}")

    # SA baseline
    sa = Dictionary.create("sorted_array", capacity=n, validate=False)
    sa = sa.bulk_build(jnp.asarray(present), jnp.asarray(vals))
    t = time_fn(sa.lookup, jnp.asarray(absent[:n]), warmup=1, iters=3)
    emit("table3/sa_lookup_none", t / n, f"{n / t / 1e6:.1f}Mq/s")
    t = time_fn(sa.lookup, jnp.asarray(present), warmup=1, iters=3)
    emit("table3/sa_lookup_all", t / n, f"{n / t / 1e6:.1f}Mq/s")

    # cuckoo baseline (80% load)
    ck = Dictionary.create("cuckoo", capacity=n, load_factor=0.8, max_rounds=100,
                           validate=False)
    ck = ck.bulk_build(jnp.asarray(present), jnp.asarray(vals))
    t = time_fn(ck.lookup, jnp.asarray(absent[:n]), warmup=1, iters=3)
    emit("table3/cuckoo_lookup_none", t / n, f"{n / t / 1e6:.1f}Mq/s")
    t = time_fn(ck.lookup, jnp.asarray(present), warmup=1, iters=3)
    emit("table3/cuckoo_lookup_all", t / n, f"{n / t / 1e6:.1f}Mq/s")


if __name__ == "__main__":
    run()
