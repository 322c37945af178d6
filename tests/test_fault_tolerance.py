"""Checkpoint/restart, async save, elastic restore, straggler monitor,
gradient compression, and the supervised training loop (failure injection)."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint.checkpoint import CheckpointManager
from repro.dist.compression import compressed_tree_psum, init_error_state
from repro.dist.fault_tolerance import StragglerMonitor, TrainSupervisor


def _tree(seed=0):
    k = jax.random.PRNGKey(seed)
    return {
        "w": jax.random.normal(k, (8, 16), jnp.float32),
        "nested": {"b": jnp.arange(16, dtype=jnp.int32), "s": jnp.asarray(3, jnp.int32)},
    }


class TestCheckpoint:
    def test_save_restore_roundtrip(self, tmp_path):
        cm = CheckpointManager(str(tmp_path), keep=2)
        tree = _tree()
        cm.save(7, tree)
        spec = jax.tree_util.tree_map(lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype), tree)
        out = cm.restore(7, spec)
        for a, b in zip(jax.tree_util.tree_leaves(tree), jax.tree_util.tree_leaves(out)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_retention_gc(self, tmp_path):
        cm = CheckpointManager(str(tmp_path), keep=2)
        for s in (1, 2, 3, 4):
            cm.save(s, _tree())
        assert cm.all_steps() == [3, 4]

    def test_async_save(self, tmp_path):
        cm = CheckpointManager(str(tmp_path), keep=3, async_save=True)
        cm.save(1, _tree())
        cm.wait()
        assert cm.latest_step() == 1

    def test_atomicity_no_tmp_left(self, tmp_path):
        cm = CheckpointManager(str(tmp_path))
        cm.save(5, _tree())
        assert not any(n.endswith(".tmp") for n in os.listdir(tmp_path))

    def test_shape_mismatch_raises(self, tmp_path):
        cm = CheckpointManager(str(tmp_path))
        cm.save(1, _tree())
        bad = jax.tree_util.tree_map(lambda l: jax.ShapeDtypeStruct((l.shape[0] + 1,) + l.shape[1:] if l.ndim else (2,), l.dtype), _tree())
        with pytest.raises((ValueError, KeyError)):
            cm.restore(1, bad)


class TestSupervisor:
    def test_restart_after_injected_failure(self, tmp_path):
        cm = CheckpointManager(str(tmp_path), keep=3)
        sup = TrainSupervisor(cm, save_every=2, max_restarts=2)
        fail_at = {5}

        def step_fn(state, step):
            if step in fail_at:
                fail_at.clear()  # fail once
                raise RuntimeError("injected node failure")
            return {"x": state["x"] + 1}

        state0 = {"x": jnp.zeros((), jnp.int32)}
        final, done = sup.run(state0, step_fn, num_steps=8)
        assert done == 8
        assert int(final["x"]) == 8  # restart replays steps 4..: value consistent
        assert sup.restarts == 1
        assert any("FAILURE" in line for line in sup.log)

    def test_straggler_monitor_flags(self):
        mon = StragglerMonitor(alpha=0.5, threshold=2.0)
        assert not mon.observe(1.0)
        assert not mon.observe(1.1)
        assert mon.observe(10.0)
        assert mon.flagged_steps == 1


class TestCompression:
    def test_compressed_psum_matches_mean(self):
        if len(jax.devices()) < 1:
            pytest.skip("needs a device")
        from jax.sharding import AxisType, PartitionSpec as P

        mesh = jax.make_mesh((1,), ("d",), axis_types=(AxisType.Auto,))
        g = jax.random.normal(jax.random.PRNGKey(0), (64,), jnp.float32)
        tree = {"g": g}
        err = init_error_state(tree)

        def body(t, e):
            return compressed_tree_psum(t, "d", e)

        f = jax.shard_map(body, mesh=mesh, in_specs=(P(), P()), out_specs=(P(), P()),
                      check_vma=False)
        mean, new_err = f(tree, err)
        # single shard: mean == dequantized value; error feedback captures residual
        np.testing.assert_allclose(
            np.asarray(mean["g"]) + np.asarray(new_err["g"]), np.asarray(g), rtol=0, atol=1e-5
        )
        # quantization error bounded by scale/2
        scale = float(jnp.max(jnp.abs(g))) / 127.0
        assert float(jnp.max(jnp.abs(new_err["g"]))) <= scale * 0.5 + 1e-6

    def test_error_feedback_converges(self):
        """Repeated compression of a constant gradient averages to the truth."""
        from jax.sharding import AxisType, PartitionSpec as P

        mesh = jax.make_mesh((1,), ("d",), axis_types=(AxisType.Auto,))
        g = {"g": jnp.asarray([0.001, -1.0, 0.5, 0.3333], jnp.float32)}
        err = init_error_state(g)
        f = jax.shard_map(lambda t, e: compressed_tree_psum(t, "d", e), mesh=mesh,
                      in_specs=(P(), P()), out_specs=(P(), P()), check_vma=False)
        acc = np.zeros(4, np.float32)
        for i in range(64):
            mean, err = f(g, err)
            acc += np.asarray(mean["g"])
        np.testing.assert_allclose(acc / 64, np.asarray(g["g"]), atol=1e-3)
