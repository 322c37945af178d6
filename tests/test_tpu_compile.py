"""Compile the facade's lookup and the cleanup for a described TPU v5e.

No chip is needed: the TPU compiler is installed and compiles for a topology
that is described, not attached. This shows what the TPU compiler makes of
the programs at the shapes of the paper-scale main path (b = 2^16, n up to
2^28), which a compile for the CPU or at a smaller size does not. Nothing
runs, so these tests say nothing about results or speed.

The topology is described inside a fixture, never at import: only one process
may load the TPU library, and every test worker imports this file.
"""

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

B = 1 << 16


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """A v5e device to compile for, with the persistent compilation cache off:
    entries written for a described chip cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _spec(sharding, *shape):
    return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=sharding)


def _hlo(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def test_facade_lookup_on_xla_gathers_rows(one_chip):
    """The `lsm` facade's lookup at the benchmark's shapes (b = 2^16, L = 12)
    searches every run by row gathers of 128 keys and runs no `while` loop:
    no run falls back to the scalar binary search."""
    from repro.api import Dictionary

    d = Dictionary.create("lsm", batch_size=B, num_levels=12)
    abstract = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip), d
    )
    hlo = _hlo(lambda d, q: d.lookup(q), abstract, _spec(one_chip, B))
    assert "slice_sizes={1,128}" in hlo
    assert "while" not in hlo


def test_cleanup_compacts_without_scatter(one_chip):
    """`lsm_cleanup` at the benchmark's shapes (b = 2^16, L = 12) holds two
    sorts of all 2^28 slots, the 4-operand merge and the 2-operand
    compaction, and no scatter. At this size the TPU compiler lowers a
    scatter as a hidden sort of its indices plus a scatter fusion, which a
    compile at 2^20 slots does not show."""
    from repro.core import LSMConfig, lsm_cleanup, lsm_init

    cfg = LSMConfig(batch_size=B, num_levels=12)
    state = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        jax.eval_shape(lambda: lsm_init(cfg)),
    )
    hlo = _hlo(lambda s: lsm_cleanup(cfg, s), state)
    # A scatter op, or the index sort and kCustom fusion it became: those
    # carry the scatter's op name.
    assert not re.search(r"\sscatter\(|/scatter\"", hlo)
    sorts = re.findall(r"= \((.*?)\) sort\(", hlo)
    assert sorted(shapes.count("s32[") for shapes in sorts) == [2, 4]
