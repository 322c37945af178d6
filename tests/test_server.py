"""DictionaryServer: coalescing differential, tenant namespacing, policies.

The load-bearing test is the differential: a multi-tenant op trace replayed
through the coalescing server must produce per-tenant results bit-identical
to replaying each tenant call-at-a-time on its own private Dictionary —
coalescing, lane padding, scheduling order, and namespace packing must all be
observationally invisible. Runs for lsm, sorted_array, and lsm_sharded
(conftest forces 4 host devices).
"""

import numpy as np
import pytest

from repro.api import Dictionary, KeyDomainError, QueryPlan
from repro.core import semantics as sem
from repro.serve.server import DictionaryServer, ServerConfig
from repro.serve.traffic import (
    TrafficGen,
    make_trace,
    replay_direct,
    replay_oracle,
    replay_server,
)

BACKENDS = [
    pytest.param({"backend": "lsm", "num_levels": 8}, id="lsm"),
    pytest.param({"backend": "sorted_array", "capacity": 4096}, id="sorted_array"),
    pytest.param({"backend": "lsm_sharded", "num_levels": 8, "num_shards": 2},
                 id="lsm_sharded"),
]


def _assert_results_equal(trace, got, want):
    assert len(got) == len(want) == len(trace)
    for i, (g, w) in enumerate(zip(got, want)):
        op = trace[i]
        if op.kind == "update":
            assert g == w, f"op{i} update lanes"
        elif op.kind == "lookup":
            np.testing.assert_array_equal(g[0], w[0], err_msg=f"op{i} found")
            np.testing.assert_array_equal(g[1], w[1], err_msg=f"op{i} values")
        elif op.kind == "count":
            np.testing.assert_array_equal(g[0], w[0], err_msg=f"op{i} counts")
            np.testing.assert_array_equal(g[1], w[1], err_msg=f"op{i} ok")
        else:  # range: server slices rows to the op's own max_results
            mr = op.max_results
            np.testing.assert_array_equal(g[2], w[2], err_msg=f"op{i} range counts")
            np.testing.assert_array_equal(g[3], w[3], err_msg=f"op{i} range ok")
            np.testing.assert_array_equal(g[0], w[0][:, :mr], err_msg=f"op{i} range keys")
            np.testing.assert_array_equal(g[1], w[1][:, :mr], err_msg=f"op{i} range vals")


class TestDifferential:
    @pytest.mark.parametrize("opts", BACKENDS)
    @pytest.mark.parametrize("mix", ["decode_trickle", "mixed"])
    def test_server_matches_per_tenant_direct(self, opts, mix):
        tenants, trace = make_trace(
            mix, num_tenants=4, key_space=256, events=24, seed=11)
        cfg = ServerConfig(batch_size=64, **opts)
        srv = DictionaryServer(cfg)
        for t in tenants:
            srv.register_tenant(t, key_space=256)
        got = replay_server(srv, trace, step_every=16)
        want = replay_direct(cfg.make_dictionary, tenants, trace)
        _assert_results_equal(trace, got, want)

    def test_end_state_matches_oracle(self):
        """After a draining replay, per-tenant lookups over the whole local
        key space reproduce the python-dict oracle exactly."""
        tenants, trace = make_trace(
            "mixed", num_tenants=3, key_space=128, events=30, seed=3)
        srv = DictionaryServer(ServerConfig(batch_size=32, num_levels=8))
        for t in tenants:
            srv.register_tenant(t, key_space=128)
        replay_server(srv, trace, step_every=8)
        oracles = replay_oracle(trace)
        all_keys = np.arange(128, dtype=np.int64)
        tickets = {t: srv.submit_lookup(t, all_keys) for t in tenants}
        for t in tenants:
            found, vals = tickets[t].result()
            o = oracles.get(t, {})
            exp_found = np.array([int(k) in o for k in all_keys])
            np.testing.assert_array_equal(found, exp_found, err_msg=f"{t} found")
            exp_vals = np.array([o.get(int(k), 0) for k in all_keys])
            np.testing.assert_array_equal(
                np.where(found, vals, 0), exp_vals, err_msg=f"{t} vals")

    def test_single_step_coalesces_homogeneous_phase(self):
        """N tenants all submitting one small update = ONE device step; the
        coalescing ratio is the whole point of the server."""
        srv = DictionaryServer(ServerConfig(batch_size=256, num_levels=8))
        for i in range(8):
            srv.register_tenant(f"t{i}", key_space=64)
        for i in range(8):
            srv.submit_update(f"t{i}", np.arange(4), np.full(4, i, np.int32))
        before = srv.stats.device_steps
        srv.step()
        assert srv.stats.device_steps - before == 1
        # And the staged lanes are all visible.
        tk = [srv.submit_lookup(f"t{i}", np.arange(4)) for i in range(8)]
        for i, t in enumerate(tk):
            found, vals = t.result()
            assert found.all()
            assert (vals == i).all()
        assert srv.stats.ops_per_device_step >= 8.0


class TestTenantNamespacing:
    def test_registration_overflow_raises(self):
        srv = DictionaryServer(ServerConfig(batch_size=32, num_levels=6))
        srv.register_tenant("big", key_space=sem.MAX_USER_KEY - 100)
        with pytest.raises(KeyDomainError, match="overflow MAX_USER_KEY"):
            srv.register_tenant("straw", key_space=1024)
        # A small tenant still fits in the remaining tail.
        srv.register_tenant("small", key_space=64)

    def test_local_domain_checked_at_submit(self):
        srv = DictionaryServer(ServerConfig(batch_size=32, num_levels=6))
        srv.register_tenant("a", key_space=100)
        with pytest.raises(KeyDomainError, match="key space"):
            srv.submit_update("a", np.asarray([100]), np.asarray([1], np.int32))
        with pytest.raises(KeyDomainError, match="key space"):
            srv.submit_lookup("a", np.asarray([-1]))
        with pytest.raises(KeyDomainError, match="integers"):
            srv.submit_lookup("a", np.asarray([1.5]))
        with pytest.raises(KeyError, match="unknown tenant"):
            srv.submit_lookup("nobody", np.asarray([0]))

    def test_cross_tenant_isolation(self):
        """A's queries never see B's keys, even at identical local values."""
        srv = DictionaryServer(ServerConfig(batch_size=64, num_levels=8))
        srv.register_tenant("a", key_space=512)
        srv.register_tenant("b", key_space=512)
        keys = np.arange(0, 512, 7, dtype=np.int64)
        srv.submit_update("a", keys, (keys + 1).astype(np.int32))
        srv.submit_update("b", keys[:3], np.full(3, 99, np.int32))
        ca = srv.submit_count("a", np.asarray([0]), np.asarray([511]))
        cb = srv.submit_count("b", np.asarray([0]), np.asarray([511]))
        ra = srv.submit_range("a", np.asarray([0]), np.asarray([511]),
                              max_results=128)
        lb = srv.submit_lookup("b", keys[3:10])   # a-only keys, b's namespace
        counts_a, _ = ca.result()
        counts_b, _ = cb.result()
        assert int(counts_a[0]) == len(keys)
        assert int(counts_b[0]) == 3
        rk, rv, rc, _ = ra.result()
        assert int(rc[0]) == len(keys)
        np.testing.assert_array_equal(rk[0, : len(keys)], keys)
        np.testing.assert_array_equal(rv[0, : len(keys)], keys + 1)
        found, _ = lb.result()
        assert not found.any()

    def test_deregistration_tombstones_full_range(self):
        srv = DictionaryServer(ServerConfig(batch_size=32, num_levels=8))
        a = srv.register_tenant("a", key_space=256)
        srv.register_tenant("keep", key_space=256)
        keys = np.arange(0, 256, 5, dtype=np.int64)
        srv.submit_update("a", keys, np.ones(len(keys), np.int32))
        srv.submit_update("keep", keys, np.full(len(keys), 7, np.int32))
        srv.drain()
        size_before = int(srv.dictionary.size())
        removed = srv.deregister_tenant("a", chunk=16)   # multiple scan rounds
        assert removed == len(keys)
        assert int(srv.dictionary.size()) == size_before - len(keys)
        assert "a" not in srv.tenants
        # The freed extent is reused (first-fit) and arrives empty.
        b = srv.register_tenant("reborn", key_space=256)
        assert b.base == a.base
        c = srv.submit_count("reborn", np.asarray([0]), np.asarray([255]))
        counts, _ = c.result()
        assert int(counts[0]) == 0
        # The survivor is untouched.
        f, v = srv.submit_lookup("keep", keys).result()
        assert f.all() and (v == 7).all()

    def test_extent_reuse_after_fragmentation(self):
        """Adjacent freed extents coalesce; the high-water tail is reclaimed
        so the domain cannot be fragmented into uselessness by churn."""
        srv = DictionaryServer(ServerConfig(batch_size=32, num_levels=6))
        ts = [srv.register_tenant(f"t{i}", key_space=1000) for i in range(3)]
        for name in ("t0", "t1", "t2"):
            srv.deregister_tenant(name)
        big = srv.register_tenant("big", key_space=3000)
        assert big.base == ts[0].base


class TestAdmissionPolicy:
    def test_pending_model_exact_single_shard(self):
        """The host-side occupancy model tracks device pending() exactly for
        the single-shard lsm backend — the policy can run sync-free."""
        srv = DictionaryServer(ServerConfig(
            backend="lsm", batch_size=64, num_levels=8, flush_at_fraction=0.8))
        srv.register_tenant("a", key_space=4096)
        rng = np.random.default_rng(0)
        for i in range(12):
            n = int(rng.integers(1, 90))
            keys = rng.choice(4096, n, replace=False).astype(np.int64)
            srv.submit_update("a", keys, np.ones(n, np.int32))
            srv.step()
            assert srv.pending_estimate() == int(srv.dictionary.pending()), (
                f"model diverged after update {i}")

    def test_flush_policy_fires(self):
        srv = DictionaryServer(ServerConfig(
            backend="lsm", batch_size=64, num_levels=8, flush_at_fraction=0.5))
        srv.register_tenant("a", key_space=4096)
        srv.submit_update("a", np.arange(40, dtype=np.int64),
                          np.ones(40, np.int32))
        srv.step()
        assert srv.stats.flushes == 1          # 40 >= 0.5 * 64
        assert srv.pending_estimate() == 0
        assert int(srv.dictionary.pending()) == 0

    def test_sorted_array_never_flushes(self):
        srv = DictionaryServer(ServerConfig(
            backend="sorted_array", capacity=1024, batch_size=64,
            flush_at_fraction=0.1))
        srv.register_tenant("a", key_space=512)
        srv.submit_update("a", np.arange(50, dtype=np.int64),
                          np.ones(50, np.int32))
        srv.step()
        assert srv.stats.flushes == 0
        assert srv.pending_estimate() == 0

    def test_drain_runs_idle_maintenance(self):
        srv = DictionaryServer(ServerConfig(
            backend="lsm", batch_size=32, num_levels=8, maintenance_budget=64))
        srv.register_tenant("a", key_space=4096)
        keys = np.arange(256, dtype=np.int64)
        srv.submit_update("a", keys, np.ones(256, np.int32))
        srv.submit_update("a", keys, np.ones(256, np.int32),
                          is_delete=np.ones(256, bool))
        stats = srv.drain()
        assert stats.maintains >= 1


class TestIntrospectionHooks:
    def test_occupancy_lsm(self):
        d = Dictionary.create("lsm", batch_size=32, num_levels=8)
        assert d.buffered
        d = d.insert(np.arange(10, dtype=np.int64), np.ones(10, np.int32))
        occ = d.occupancy()
        assert int(occ.pending) == 10
        assert int(occ.resident) == 0
        d = d.flush()
        occ = d.occupancy()
        assert int(occ.pending) == 0
        assert int(occ.resident) == 32        # one padded batch resident
        assert int(occ.debt) == 0             # distinct live keys: no debt
        # Tombstones resident in a run are compaction debt.
        d = d.delete(np.arange(100, 110, dtype=np.int64)).flush()
        assert int(d.occupancy().debt) >= 10

    def test_flush_cost_tracks_cascade(self):
        b = 32
        d = Dictionary.create("lsm", batch_size=b, num_levels=8)
        assert int(d.flush_cost_estimate()) == 0   # empty buffer: free
        ks = np.arange(100, dtype=np.int64)
        d = d.insert(ks[:10], np.ones(10, np.int32))
        # r=0 -> one batch write
        assert int(d.flush_cost_estimate()) == b
        d = d.flush()                               # r=1
        d = d.insert(ks[10:20], np.ones(10, np.int32))
        # r=1 (trailing ones = 1) -> merge into level 1: cost 2b
        assert int(d.flush_cost_estimate()) == 2 * b
        d = d.flush()                               # r=2
        d = d.insert(ks[20:30], np.ones(10, np.int32))
        assert int(d.flush_cost_estimate()) == b    # r=2: no carry
        d = d.flush()                               # r=3
        d = d.insert(ks[30:40], np.ones(10, np.int32))
        # r=3: the batch merges with levels 0 and 1 (b + 2b) into level 2
        assert int(d.flush_cost_estimate()) == 4 * b

    def test_occupancy_sorted_array(self):
        d = Dictionary.create("sorted_array", capacity=256, batch_size=32)
        assert not d.buffered
        d = d.insert(np.arange(10, dtype=np.int64), np.ones(10, np.int32))
        occ = d.occupancy()
        assert int(occ.pending) == 0
        assert int(occ.resident) == 10
        assert int(occ.debt) == 0
        assert int(d.flush_cost_estimate()) == 0

    def test_occupancy_sharded(self):
        d = Dictionary.create("lsm_sharded", batch_size=32, num_levels=8,
                              num_shards=2)
        assert d.buffered
        d = d.insert(np.arange(10, dtype=np.int64), np.ones(10, np.int32))
        occ = d.occupancy()
        assert int(occ.pending) == 10
        d = d.flush()
        occ = d.occupancy()
        assert int(occ.pending) == 0
        assert int(occ.resident) >= 10


class TestServerPageTable:
    def test_page_table_as_tenant(self):
        from repro.serve.kvcache import ServerPageTable

        srv = DictionaryServer(ServerConfig(batch_size=32, num_levels=8))
        pt = ServerPageTable(srv, num_pages=64, num_seqs=8)
        slots, _ = pt.allocate([1, 1, 1, 2], [0, 1, 2, 0])
        assert len(set(slots.tolist())) == 4
        found, got = pt.lookup([1, 1, 1, 2], [0, 1, 2, 0]).result()
        assert found.all()
        np.testing.assert_array_equal(got, slots)
        counts, ok = pt.seq_page_count([1, 2, 3]).result()
        assert ok.all()
        np.testing.assert_array_equal(counts, [3, 1, 0])
        pages, pslots, pcounts, _ = pt.seq_pages([1], max_pages=8).result()
        np.testing.assert_array_equal(pages[0, :3], [0, 1, 2])
        assert (pages[0, 3:] == -1).all()
        free_before = pt.free_count
        assert pt.evict([1, 1, 7], [0, 1, 0]) == 2   # seq 7 never existed
        assert pt.free_count == free_before + 2
        found, _ = pt.lookup([1, 1, 1], [0, 1, 2]).result()
        np.testing.assert_array_equal(found, [False, False, True])

    def test_page_table_coexists_with_other_tenants(self):
        from repro.serve.kvcache import ServerPageTable

        srv = DictionaryServer(ServerConfig(batch_size=64, num_levels=8))
        pt = ServerPageTable(srv, num_pages=32, num_seqs=4)
        srv.register_tenant("app", key_space=1024)
        pt.allocate([0, 1], [0, 0])
        srv.submit_update("app", np.asarray([5]), np.asarray([50], np.int32))
        c = pt.seq_page_count([0, 1])
        f = srv.submit_lookup("app", np.asarray([5]))
        counts, _ = c.result()
        np.testing.assert_array_equal(counts, [1, 1])
        found, vals = f.result()
        assert found.all() and int(vals[0]) == 50

    def test_pool_exhaustion(self):
        from repro.serve.kvcache import ServerPageTable

        srv = DictionaryServer(ServerConfig(batch_size=32, num_levels=6))
        pt = ServerPageTable(srv, num_pages=2, num_seqs=2)
        pt.allocate([0], [0])
        with pytest.raises(RuntimeError, match="exhausted"):
            pt.allocate([0, 0], [1, 2])


class TestTrafficGen:
    def test_trace_deterministic(self):
        _, a = make_trace("mixed", num_tenants=3, key_space=64, events=20, seed=5)
        _, b = make_trace("mixed", num_tenants=3, key_space=64, events=20, seed=5)
        assert len(a) == len(b)
        for x, y in zip(a, b):
            assert x.tenant == y.tenant and x.kind == y.kind
            if x.keys is not None:
                np.testing.assert_array_equal(x.keys, y.keys)

    def test_keys_stay_local(self):
        gen = TrafficGen(["t"], key_space=64, seed=1, window=16)
        for op in gen.make("mixed", 40):
            for arr in (op.keys, op.k1, op.k2):
                if arr is not None:
                    assert (np.asarray(arr) >= 0).all()
                    assert (np.asarray(arr) < 64).all()

    def test_bad_mix_rejected(self):
        gen = TrafficGen(["t"], key_space=64)
        with pytest.raises(ValueError, match="unknown mix"):
            gen.make("nope", 1)
