"""Unified `Dictionary` facade: backend parity, capabilities, key domain.

The headline property (paper Table 1): LSM and sorted-array are *the same
dictionary* behind the facade — a randomized mixed op sequence (insert /
delete / mixed update / cleanup, arbitrary non-multiple-of-b lengths) must
produce identical lookup/count/range answers from both, and both must agree
with a Python-dict oracle. Cuckoo must answer lookups and *refuse* everything
else with a CapabilityError instead of silently lacking the feature.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import (
    CapabilityError,
    Dictionary,
    KeyDomainError,
    QueryPlan,
    available_backends,
)
from repro.core import semantics as sem

B = 8
KEY_SPACE = 100


def _mk(backend):
    # Same explicit geometry for both run-based backends so explicit plans
    # and capacities line up exactly.
    if backend == "lsm":
        return Dictionary.create("lsm", batch_size=B, num_levels=5)  # capacity 248
    return Dictionary.create("sorted_array", capacity=248, batch_size=B)


PLAN = QueryPlan(max_candidates=248, max_results=32)


class TestBackendParity:
    @pytest.mark.parametrize("seed", [0, 1])
    def test_randomized_mixed_ops_match_oracle_and_each_other(self, seed):
        rng = np.random.default_rng(seed)
        lsm, sa = _mk("lsm"), _mk("sorted_array")
        oracle = {}

        for step in range(12):
            op = rng.choice(["insert", "delete", "mixed", "cleanup"], p=[0.45, 0.2, 0.25, 0.1])
            if op == "cleanup":
                lsm, sa = lsm.cleanup(), sa.cleanup()
            else:
                n = int(rng.integers(1, 3 * B))  # deliberately not a multiple of B
                keys = rng.choice(KEY_SPACE, n, replace=False).astype(np.int32)
                vals = rng.integers(0, 1000, n).astype(np.int32)
                if op == "insert":
                    dels = np.zeros(n, bool)
                elif op == "delete":
                    dels = np.ones(n, bool)
                else:
                    dels = rng.random(n) < 0.4
                lsm = lsm.update(keys, vals, is_delete=jnp.asarray(dels))
                sa = sa.update(keys, vals, is_delete=jnp.asarray(dels))
                for k, v, t in zip(keys.tolist(), vals.tolist(), dels.tolist()):
                    if t:
                        oracle.pop(k, None)
                    else:
                        oracle[k] = v

            # lookups: all keys + some misses
            q = np.arange(KEY_SPACE, dtype=np.int32)
            fl, vl = lsm.lookup(q)
            fs, vs = sa.lookup(q)
            np.testing.assert_array_equal(np.asarray(fl), np.asarray(fs))
            np.testing.assert_array_equal(
                np.where(np.asarray(fl), np.asarray(vl), -1),
                np.where(np.asarray(fs), np.asarray(vs), -1),
            )
            exp_found = np.array([k in oracle for k in q])
            np.testing.assert_array_equal(np.asarray(fl), exp_found)
            exp_vals = np.array([oracle.get(k, -1) for k in q])
            np.testing.assert_array_equal(np.where(exp_found, np.asarray(vl), -1), exp_vals)

            # counts + sizes
            k1 = rng.integers(0, KEY_SPACE, 4).astype(np.int32)
            k2 = np.minimum(k1 + rng.integers(0, 40, 4), KEY_SPACE - 1).astype(np.int32)
            cl, okl = lsm.count(k1, k2, PLAN)
            cs, oks = sa.count(k1, k2, PLAN)
            assert bool(okl.all()) and bool(oks.all())
            np.testing.assert_array_equal(np.asarray(cl), np.asarray(cs))
            exp = [sum(1 for k in oracle if a <= k <= b) for a, b in zip(k1, k2)]
            np.testing.assert_array_equal(np.asarray(cl), exp)
            assert int(lsm.size()) == len(oracle) == int(sa.size())

            # ranges: contents, not just counts
            rkl, rvl, rcl, rokl = lsm.range(k1, k2, PLAN)
            rks, rvs, rcs, roks = sa.range(k1, k2, PLAN)
            assert bool(rokl.all()) and bool(roks.all())
            np.testing.assert_array_equal(np.asarray(rkl), np.asarray(rks))
            np.testing.assert_array_equal(np.asarray(rvl), np.asarray(rvs))
            for i, (a, b) in enumerate(zip(k1, k2)):
                exp_keys = sorted(k for k in oracle if a <= k <= b)
                got = np.asarray(rkl[i][: int(rcl[i])]).tolist()
                assert got == exp_keys
                assert np.asarray(rvl[i][: int(rcl[i])]).tolist() == [oracle[k] for k in exp_keys]

    def test_bulk_build_matches_incremental(self):
        rng = np.random.default_rng(3)
        keys = rng.choice(KEY_SPACE, 37, replace=False).astype(np.int32)  # not multiple of B
        vals = (keys * 3).astype(np.int32)
        built = _mk("lsm").bulk_build(keys, vals)
        inc = _mk("lsm").insert(keys, vals)
        q = np.arange(KEY_SPACE, dtype=np.int32)
        fb, vb = built.lookup(q)
        fi, vi = inc.lookup(q)
        np.testing.assert_array_equal(np.asarray(fb), np.asarray(fi))
        np.testing.assert_array_equal(
            np.where(np.asarray(fb), np.asarray(vb), -1),
            np.where(np.asarray(fi), np.asarray(vi), -1),
        )
        assert int(built.size()) == 37

    def test_valid_mask_lanes_are_invisible(self):
        d = _mk("lsm").update(
            np.asarray([1, 2, 3, 4]), np.asarray([10, 20, 30, 40]),
            valid=np.asarray([True, False, True, False]),
        )
        f, v = d.lookup(np.asarray([1, 2, 3, 4]))
        assert f.tolist() == [True, False, True, False]
        assert int(d.size()) == 2
        # masked lanes are compacted away: they never occupy buffer slots
        assert int(d.pending()) == 2

    def test_recency_rule_tombstone_loses_to_later_insert(self):
        """The write-buffer recency rule (docs/DESIGN.md §5): strict arrival
        order decides duplicates even across the insert/tombstone status
        boundary — unlike the paper's in-batch tombstone-first rule, which
        still governs the direct core path (test_lsm_semantics item 6)."""
        for backend in ("lsm", "sorted_array"):
            d = _mk(backend).update(
                np.asarray([5, 5]), np.asarray([0, 55]),
                is_delete=np.asarray([True, False]),
            )
            f, v = d.lookup(np.asarray([5]))
            assert bool(f[0]) and int(v[0]) == 55, backend
            d = d.update(np.asarray([5, 5]), np.asarray([66, 0]),
                         is_delete=np.asarray([False, True]))
            assert not bool(d.lookup(np.asarray([5]))[0][0]), backend

    def test_mixed_update_masked_lanes_skip_buffer(self):
        d = _mk("lsm").insert(np.asarray([1, 2]), np.asarray([10, 20])).flush()
        d = d.update(
            np.asarray([1, 2, 3]), np.asarray([0, 0, 30]),
            is_delete=np.asarray([True, True, False]),
            valid=np.asarray([True, False, True]),
        )
        assert int(d.pending()) == 2  # staged: tombstone(1) + insert(3)
        f, v = d.lookup(np.asarray([1, 2, 3]))
        assert f.tolist() == [False, True, True]
        assert int(d.size()) == 2


class TestCapabilities:
    def test_registry_lists_builtins(self):
        assert set(available_backends()) >= {
            "lsm", "lsm_sharded", "sorted_array", "cuckoo",
        }

    def test_cuckoo_lookup_works_but_ordered_queries_raise(self):
        keys = np.arange(50, dtype=np.int32)
        ck = Dictionary.create("cuckoo", capacity=64).bulk_build(keys, keys * 2)
        f, v = ck.lookup(np.asarray([7, 99]))
        assert f.tolist() == [True, False] and int(v[0]) == 14
        assert not ck.capabilities.supports_ordered_queries
        with pytest.raises(CapabilityError, match="does not support 'count'"):
            ck.count(0, 10)
        with pytest.raises(CapabilityError, match="does not support 'range'"):
            ck.range(0, 10)
        with pytest.raises(CapabilityError, match="does not support 'update'"):
            ck.insert(np.asarray([1]), np.asarray([1]))
        with pytest.raises(CapabilityError, match="does not support 'cleanup'"):
            ck.cleanup()

    def test_capability_error_names_alternatives(self):
        ck = Dictionary.create("cuckoo", capacity=16)
        with pytest.raises(CapabilityError, match="lsm"):
            ck.count(0, 1)

    def test_capability_errors_name_lsm_sharded_as_alternative(self):
        """The sharded backend has the full capability row, so every
        cuckoo-style unsupported-op error must list it among the backends
        that can (paper Table 1, now with four columns)."""
        ck = Dictionary.create("cuckoo", capacity=16)
        ops = [
            lambda: ck.count(0, 1),
            lambda: ck.range(0, 1),
            lambda: ck.cleanup(),
            lambda: ck.insert(np.asarray([1]), np.asarray([1])),
            lambda: ck.delete(np.asarray([1])),
        ]
        for op in ops:
            with pytest.raises(CapabilityError, match="lsm_sharded"):
                op()

    def test_lsm_sharded_capability_row_is_full(self):
        from repro.api import backend_class

        caps = backend_class("lsm_sharded").caps
        assert caps.supports_updates and caps.supports_deletes
        assert caps.supports_ordered_queries and caps.supports_cleanup
        assert caps.supports_bulk_build

    def test_unknown_backend_raises(self):
        with pytest.raises(KeyError, match="unknown backend"):
            Dictionary.create("btree")


class TestKeyDomain:
    """Regression: out-of-domain keys used to alias the placebo key or flip
    sign after `key << 1` (core/semantics.py) and silently corrupt ordering."""

    @pytest.mark.parametrize("bad", [-1, sem.PLACEBO_KEY, sem.MAX_USER_KEY + 1, 1 << 31])
    def test_update_rejects_out_of_domain(self, bad):
        d = _mk("lsm")
        with pytest.raises(KeyDomainError):
            d.insert(np.asarray([1, bad], dtype=np.int64), np.asarray([0, 0]))

    def test_query_keys_are_validated_too(self):
        d = _mk("lsm")
        with pytest.raises(KeyDomainError):
            d.lookup(np.asarray([-5]))
        with pytest.raises(KeyDomainError):
            d.count(np.asarray([0]), np.asarray([sem.PLACEBO_KEY]))

    def test_masked_out_lanes_are_exempt(self):
        d = _mk("lsm")
        d = d.update(np.asarray([1, -1]), np.asarray([5, 5]),
                     valid=np.asarray([True, False]))
        f, _ = d.lookup(np.asarray([1]))
        assert bool(f[0])

    def test_max_user_key_is_accepted(self):
        d = _mk("lsm").insert(np.asarray([sem.MAX_USER_KEY]), np.asarray([9]))
        f, v = d.lookup(np.asarray([sem.MAX_USER_KEY]))
        assert bool(f[0]) and int(v[0]) == 9

    def test_float_keys_rejected(self):
        with pytest.raises(KeyDomainError, match="integer"):
            _mk("lsm").insert(np.asarray([1.5]), np.asarray([0]))

    def test_delete_validates_before_int32_wrap(self):
        """Regression: delete() used to cast to int32 before validation, so
        1 << 35 wrapped to key 0 and silently tombstoned it."""
        d = _mk("lsm").insert(np.asarray([0]), np.asarray([42]))
        with pytest.raises(KeyDomainError):
            d = d.delete(np.asarray([1 << 35], dtype=np.int64))
        f, v = d.lookup(np.asarray([0]))
        assert bool(f[0]) and int(v[0]) == 42

    def test_validate_false_skips_host_checks(self):
        d = Dictionary.create("lsm", batch_size=B, num_levels=4, validate=False)
        d = d.insert(np.asarray([1]), np.asarray([2]))  # no error paths hit
        assert bool(d.lookup(np.asarray([1]))[0][0])


class TestQueryPlan:
    def test_auto_plan_is_exact_for_small_dictionaries(self):
        p = QueryPlan().resolved(248)
        assert p.max_candidates == 248 and p.max_results == 248

    def test_auto_plan_bounds_large_dictionaries(self):
        p = QueryPlan().resolved(1 << 20)
        assert 4096 <= p.max_candidates < (1 << 20)

    def test_explicit_plan_overrides(self):
        p = QueryPlan(max_candidates=7, max_results=3).resolved(1 << 20)
        assert (p.max_candidates, p.max_results) == (7, 3)

    def test_plan_bound_covers_write_buffer_residents(self):
        """Regression: clamping plans to bare capacity made a full structure
        plus buffer residents permanently inexact — no explicit plan could
        restore ok=True. The bound must include the buffer slots."""
        d = Dictionary.create("lsm", batch_size=4, num_levels=1)  # capacity 4
        keys = np.arange(8, dtype=np.int32)
        d = d.insert(keys, keys)  # 4 flushed into the level + 4 buffer-resident
        assert not bool(d.overflowed())
        counts, ok = d.count(np.asarray([0]), np.asarray([7]))  # auto plan
        assert bool(ok[0]) and int(counts[0]) == 8
        counts, ok = d.count(np.asarray([0]), np.asarray([7]),
                             QueryPlan(max_candidates=8))  # explicit, unclamped
        assert bool(ok[0]) and int(counts[0]) == 8

    def test_truncation_is_flagged_not_silent(self):
        keys = np.arange(64, dtype=np.int32)
        d = _mk("lsm").insert(keys, keys)
        counts, ok = d.count(np.asarray([0]), np.asarray([63]),
                             QueryPlan(max_candidates=16))
        assert not bool(ok[0])  # truncated -> flagged

    def test_invalid_plan_rejected(self):
        with pytest.raises(ValueError):
            QueryPlan(max_candidates=0)


class TestFacadeMechanics:
    def test_pytree_roundtrip_preserves_backend_and_state(self):
        d = _mk("lsm").insert(np.asarray([4, 5]), np.asarray([40, 50]))
        leaves, treedef = jax.tree_util.tree_flatten(d)
        d2 = jax.tree_util.tree_unflatten(treedef, leaves)
        assert d2.backend == "lsm"
        f, v = d2.lookup(np.asarray([4, 5]))
        assert f.tolist() == [True, True] and v.tolist() == [40, 50]

    def test_executable_cache_is_shared_across_handles(self):
        from repro.api.dictionary import _EXEC_CACHE

        d1 = _mk("lsm").insert(np.asarray([1]), np.asarray([1]))
        n_before = len(_EXEC_CACHE)
        d2 = _mk("lsm").insert(np.asarray([2]), np.asarray([2]))  # same config
        assert len(_EXEC_CACHE) == n_before
        del d1, d2

    def test_multi_chunk_update_scans(self):
        # 3*B + 5 elements -> 4 chunks through one scanned executable.
        n = 3 * B + 5
        keys = np.arange(n, dtype=np.int32)
        d = _mk("lsm").insert(keys, keys * 2)
        assert int(d.size()) == n
        f, v = d.lookup(keys)
        assert bool(f.all())
        np.testing.assert_array_equal(np.asarray(v), keys * 2)

    @pytest.mark.parametrize("backend", ["lsm", "sorted_array"])
    def test_duplicate_keys_in_one_call_last_wins(self, backend):
        """Regression: within-chunk duplicates used to resolve to the OLDEST
        lane while across-chunk duplicates resolved to the newest — the
        winner depended on where the pad/split placed chunk boundaries."""
        # same chunk (n < B)
        d = _mk(backend).insert(np.asarray([5, 5]), np.asarray([111, 222]))
        assert int(d.lookup(np.asarray([5]))[1][0]) == 222
        # across chunks (n > B, duplicate straddles the boundary)
        keys = np.r_[np.asarray([5]), np.arange(B - 1) + 10, np.asarray([5])].astype(np.int32)
        vals = np.r_[np.asarray([111]), np.zeros(B - 1), np.asarray([222])].astype(np.int32)
        d = _mk(backend).insert(keys, vals)
        assert int(d.lookup(np.asarray([5]))[1][0]) == 222

    def test_empty_update_is_noop(self):
        d = _mk("lsm")
        d2 = d.update(np.zeros((0,), np.int32))
        assert d2 is d

    def test_scalar_keys_promote(self):
        d = _mk("lsm").insert(7, 70)
        f, v = d.lookup(7)
        assert bool(f[0]) and int(v[0]) == 70

    def test_overflow_is_latched_not_silent(self):
        d = Dictionary.create("lsm", batch_size=4, num_levels=1)  # capacity 4
        d = d.insert(np.asarray([1, 2, 3, 4]), np.zeros(4, np.int32))  # staged only
        assert not bool(d.overflowed())
        # Flushes the first batch (r -> max) and stages the second: the write
        # buffer grants up to b elements of grace beyond the level arenas.
        d = d.insert(np.asarray([5, 6, 7, 8]), np.zeros(4, np.int32))
        assert not bool(d.overflowed())
        # One more element forces a flush past the last batch slot: latched.
        d = d.insert(np.asarray([9]), np.zeros(1, np.int32))
        assert bool(d.overflowed())

    @pytest.mark.parametrize("backend", ["lsm", "lsm_sharded"])
    def test_precompile_serves_the_later_calls(self, backend):
        """precompile() builds the very programs the methods run: the calls
        it prepared compile nothing, and their answers are right."""
        from jax import monitoring

        extra = {"num_shards": 4} if backend == "lsm_sharded" else {}
        d = Dictionary.create(backend, batch_size=B, num_levels=4, **extra)
        plan = QueryPlan(max_candidates=64, max_results=16)
        keys = np.arange(0, 60, 3, dtype=np.int32)
        compiled = d.precompile(bulk=len(keys), lookups=[16], updates=[B], windows=[4],
                                plans=[plan], maintain=[32], flush=True, cleanup=True)
        assert len(compiled) == 8
        compiles = []

        def on_duration(name, secs, **_):
            if name == "/jax/core/compile/backend_compile_duration":
                compiles.append(name)

        q = np.arange(16, dtype=np.int32)
        k1, k2 = np.asarray([0, 10, 20, 50], np.int32), np.asarray([9, 30, 20, 59], np.int32)
        monitoring.register_event_duration_secs_listener(on_duration)
        try:
            d = d.bulk_build(keys, keys * 2)
            d = d.update(np.full(B, 7, np.int32), np.full(B, 70, np.int32),
                         is_delete=np.zeros(B, bool), valid=np.arange(B) < 1)
            found, vals = d.lookup(q)
            counts, _ = d.count(k1, k2, plan)
            d = d.flush().maintain(32).cleanup()
            rkeys, _, rcounts, _ = d.range(k1, k2, plan)
            found, vals, counts, rkeys, rcounts = jax.device_get((found, vals, counts, rkeys, rcounts))
        finally:
            monitoring.unregister_event_duration_listener(on_duration)
        assert compiles == []
        live = {int(k): 2 * int(k) for k in keys} | {7: 70}
        assert found.tolist() == [k in live for k in q.tolist()]
        assert vals.tolist() == [live.get(k, 0) for k in q.tolist()]
        expect = [sum(a <= k <= b for k in live) for a, b in zip(k1.tolist(), k2.tolist())]
        assert counts.tolist() == expect and rcounts.tolist() == expect
        assert rkeys[1, :expect[1]].tolist() == sorted(k for k in live if 10 <= k <= 30)
