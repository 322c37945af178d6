"""Distributed (range-partitioned, shard_map) LSM vs the single-device LSM.

Runs with 4 forced host devices — tests/conftest.py sets
--xla_force_host_platform_device_count=4 before jax initializes (a
per-test-module guard runs too late: conftest's own jax import wins).
The owner_of partitioning tests are pure config math and need no devices."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import LSMConfig, lsm_init, lsm_update, lsm_lookup, lsm_count
from repro.core import semantics as sem
from repro.core.distributed import (
    DistLSMConfig,
    dist_lsm_init,
    make_dist_cleanup,
    make_dist_count,
    make_dist_lookup,
    make_dist_range,
    make_dist_size,
    make_dist_update,
    owner_of,
    shard_bounds,
)

NEEDS_DEVICES = pytest.mark.skipif(
    len(jax.devices()) < 4, reason="needs 4 forced host devices"
)

B = 16


class TestOwnerOf:
    """Regression coverage for DistLSMConfig.range_size edge cases: keys at
    MAX_USER_KEY and keys straddling s*range_size - 1 / s*range_size must
    land on exactly one owner, for even and ragged partitions alike."""

    @staticmethod
    def _reference_owner(cfg, keys):
        """Modulo-free reference: the owner of k is the number of shard
        boundaries at or below it."""
        owner = np.zeros(len(keys), dtype=np.int64)
        for s in range(1, cfg.num_shards):
            owner += keys >= s * cfg.range_size
        return owner

    @staticmethod
    def _fuzz_keys(cfg, rng, n_random=512):
        keys = {0, 1, sem.MAX_USER_KEY - 1, sem.MAX_USER_KEY}
        for s in range(1, cfg.num_shards + 1):
            for d in (-1, 0, 1):
                k = s * cfg.range_size + d
                if 0 <= k <= sem.MAX_USER_KEY:
                    keys.add(k)
        keys |= {int(k) for k in rng.integers(0, sem.MAX_USER_KEY + 1, n_random)}
        return np.array(sorted(keys), dtype=np.int64)

    @pytest.mark.parametrize("num_shards", [1, 2, 3, 4, 5, 7, 8])
    def test_owner_matches_modulo_free_reference(self, num_shards):
        cfg = DistLSMConfig(local=LSMConfig(batch_size=8, num_levels=2),
                            num_shards=num_shards)
        keys = self._fuzz_keys(cfg, np.random.default_rng(num_shards))
        got = np.asarray(owner_of(cfg, keys))
        np.testing.assert_array_equal(got, self._reference_owner(cfg, keys))
        assert got.min() >= 0 and got.max() <= num_shards - 1

    @pytest.mark.parametrize("num_shards", [1, 2, 3, 4, 5, 7, 8])
    def test_every_key_covered_by_exactly_one_shard_interval(self, num_shards):
        """The [lo, hi] windows the COUNT/RANGE clipping uses (shard_bounds)
        must tile the key domain: each key inside exactly one window, and
        that window's shard must equal owner_of."""
        cfg = DistLSMConfig(local=LSMConfig(batch_size=8, num_levels=2),
                            num_shards=num_shards)
        keys = self._fuzz_keys(cfg, np.random.default_rng(100 + num_shards))
        lows, highs = zip(*(shard_bounds(cfg, s) for s in range(num_shards)))
        lows, highs = np.array(lows), np.array(highs)
        inside = (keys[:, None] >= lows[None, :]) & (keys[:, None] <= highs[None, :])
        np.testing.assert_array_equal(inside.sum(axis=1), np.ones(len(keys)))
        np.testing.assert_array_equal(
            np.argmax(inside, axis=1), np.asarray(owner_of(cfg, keys))
        )

    def test_max_user_key_owned_by_last_shard_window(self):
        for num_shards in (1, 2, 4, 6):
            cfg = DistLSMConfig(local=LSMConfig(batch_size=8, num_levels=2),
                                num_shards=num_shards)
            lo, hi = shard_bounds(cfg, num_shards - 1)
            assert lo <= sem.MAX_USER_KEY <= hi
            owner = int(np.asarray(owner_of(cfg, np.array([sem.MAX_USER_KEY])))[0])
            assert owner == num_shards - 1


@pytest.fixture()
def setup():
    # Function-scoped: make_dist_update donates its state argument, so every
    # test needs fresh buffers.
    from jax.sharding import AxisType

    mesh = jax.make_mesh((4,), ("shard",), axis_types=(AxisType.Auto,))
    cfg = DistLSMConfig(local=LSMConfig(batch_size=B, num_levels=4), num_shards=4)
    states = dist_lsm_init(cfg, mesh)
    return mesh, cfg, states


@NEEDS_DEVICES
def test_dist_matches_single_device_reference(setup):
    mesh, cfg, states = setup
    rng = np.random.default_rng(0)
    update = make_dist_update(cfg, mesh)
    lookup = make_dist_lookup(cfg, mesh)
    count = make_dist_count(cfg, mesh, max_candidates=cfg.local.capacity)

    # Single-device oracle with the same global batches.
    ref_cfg = LSMConfig(batch_size=B, num_levels=6)
    ref = lsm_init(ref_cfg)

    all_keys = []
    for step in range(6):
        keys = rng.choice(sem.MAX_USER_KEY, B, replace=False).astype(np.int32)
        dels = rng.random(B) < 0.25
        kv = jnp.asarray(np.where(dels, keys * 2, keys * 2 + 1).astype(np.int32))
        vals = jnp.asarray(np.where(dels, 0, keys % 997).astype(np.int32))
        states = update(states, kv, vals)
        ref = lsm_update(ref_cfg, ref, kv, vals)
        all_keys.extend(keys.tolist())

    q = jnp.asarray(np.array(all_keys + [1, 2, 3], dtype=np.int32))
    f_d, v_d = lookup(states, q)
    f_r, v_r = lsm_lookup(ref_cfg, ref, q)
    np.testing.assert_array_equal(np.asarray(f_d), np.asarray(f_r))
    np.testing.assert_array_equal(
        np.where(np.asarray(f_d), np.asarray(v_d), 0),
        np.where(np.asarray(f_r), np.asarray(v_r), 0),
    )

    k1 = jnp.asarray(np.array([0, 10_000, 0], dtype=np.int32))
    k2 = jnp.asarray(np.array([sem.MAX_USER_KEY, 20_000_000, 1000], dtype=np.int32))
    c_d, ok_d = count(states, k1, k2)
    c_r, ok_r = lsm_count(ref_cfg, ref, k1, k2, ref_cfg.capacity)
    assert bool(ok_d.all()) and bool(ok_r.all())
    np.testing.assert_array_equal(np.asarray(c_d), np.asarray(c_r))


@NEEDS_DEVICES
def test_dist_range_is_globally_sorted(setup):
    mesh, cfg, states = setup
    rng = np.random.default_rng(7)
    update = make_dist_update(cfg, mesh)
    rquery = make_dist_range(cfg, mesh, max_candidates=64, max_results=64)

    keys = rng.choice(sem.MAX_USER_KEY, B, replace=False).astype(np.int32)
    kv = jnp.asarray((keys * 2 + 1).astype(np.int32))
    states = update(states, kv, jnp.asarray(keys % 97, jnp.int32))

    k1 = jnp.zeros((2,), jnp.int32)
    k2 = jnp.full((2,), sem.MAX_USER_KEY, jnp.int32)
    out_keys, out_vals, counts, ok = rquery(states, k1, k2)
    assert bool(ok.all())
    # Assemble shard-major results for query 0: must equal sorted global keys.
    got = []
    for s in range(cfg.num_shards):
        c = int(counts[s, 0])
        got.extend(np.asarray(out_keys[s, 0, :c]).tolist())
    np.testing.assert_array_equal(np.array(got), np.sort(keys))


@NEEDS_DEVICES
def test_dist_size_counts_live_elements_across_shards(setup):
    mesh, cfg, states = setup
    update = make_dist_update(cfg, mesh)
    size = make_dist_size(cfg, mesh)
    assert int(size(states)) == 0
    keys = np.arange(B, dtype=np.int32) * 60_000_000  # spans all 4 shard ranges
    states = update(states, jnp.asarray(keys * 2 + 1), jnp.asarray(keys % 97))
    assert int(size(states)) == B
    states = update(states, jnp.asarray(keys * 2), jnp.zeros(B, jnp.int32))  # tombstones
    assert int(size(states)) == 0


@NEEDS_DEVICES
def test_dist_cleanup_local_and_transparent(setup):
    mesh, cfg, states = setup
    rng = np.random.default_rng(9)
    update = make_dist_update(cfg, mesh)
    lookup = make_dist_lookup(cfg, mesh)
    cleanup = make_dist_cleanup(cfg, mesh)

    keys = rng.choice(1000, B, replace=False).astype(np.int32)
    states = update(states, jnp.asarray(keys * 2 + 1), jnp.asarray(keys, jnp.int32))
    states = update(states, jnp.asarray(keys * 2 + 1), jnp.asarray(keys + 5, jnp.int32))
    q = jnp.asarray(keys)
    f1, v1 = lookup(states, q)
    states = cleanup(states)
    f2, v2 = lookup(states, q)
    np.testing.assert_array_equal(np.asarray(f1), np.asarray(f2))
    np.testing.assert_array_equal(np.asarray(v1), np.asarray(v2))


def _bulk_keys(n):
    rng = np.random.default_rng(3)
    return np.sort(rng.choice(sem.MAX_USER_KEY, n, replace=False)).astype(np.int32)


@NEEDS_DEVICES
def test_dist_bulk_build_fills_one_shards_capacity():
    """A bulk build takes up to the per-shard capacity (one shard may own
    every key); at exactly that size every key is found."""
    from repro.api import Dictionary

    d = Dictionary.create("lsm_sharded", num_shards=4, batch_size=256, num_levels=2)
    keys = _bulk_keys(d.capacity)
    d = d.bulk_build(keys, keys % 1000)
    found, vals = d.lookup(keys)
    assert not bool(d.overflowed())
    assert bool(np.asarray(found).all())
    np.testing.assert_array_equal(np.asarray(vals), keys % 1000)


@NEEDS_DEVICES
def test_dist_bulk_build_refuses_more_than_one_shards_capacity():
    from repro.api import Dictionary

    d = Dictionary.create("lsm_sharded", num_shards=4, batch_size=256, num_levels=2)
    keys = _bulk_keys(d.capacity + 1)
    with pytest.raises(ValueError, match="per-shard capacity"):
        d.bulk_build(keys, keys % 1000)
