"""Compile the Pallas kernels, the facade's lookup and the cleanup for a
described TPU v5e.

No chip is needed: the TPU compiler is installed and compiles for a topology
that is described, not attached. This catches what interpret mode cannot —
block shapes Mosaic does not tile, ops it does not lower, VMEM overuse — at
the shapes of the paper-scale main path (b = 2^16, n up to 2^28). Nothing
runs, so these tests say nothing about results or speed.

The topology is described inside a fixture, never at import: only one process
may load the TPU library, and every test worker imports this file.
"""

import functools
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import bitonic_sort, lsm_lookup, merge_path, ops

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


def _lowered(fn, *args) -> str:
    return jax.jit(fn).lower(*args).as_text()


def _cascade(*xs):
    k = len(xs) // 2
    return merge_path.merge_cascade_path(list(xs[:k]), list(xs[k:]))


# name -> (kernel, operand shapes): each at a shape of the main path. The
# kernel's `pallas_call` carries the same name, so a trace finds it; the
# pairwise merge is the two-run case of the cascade kernel.
KERNELS = {
    # count/range: 2^12 windows against the deepest level (2^27 slots)
    "lower_bound_streamed": (lsm_lookup.lower_bound_streamed, [(1 << 27,), (1 << 12,)]),
    # lookup: 2^16 queries against every run concatenated (~2^28 slots)
    "fused_lookup_runs": (lsm_lookup.fused_lookup_runs, [(1 << 28,), (1 << 28,), (B,)]),
    # the pairwise merge the bitonic sort's combine rounds use
    "merge_path": (functools.partial(merge_path.merge_path, compare_full=True),
                   [(B,), (B,), (B,), (B,)]),
    # a cascade step: carry batch + levels 0 and 1
    "merge_cascade_path": (_cascade, [(B,), (B,), (2 * B,)] * 2),
    # one VMEM tile plus two batched Merge Path rounds
    "bitonic_sort_pairs": (bitonic_sort.bitonic_sort_pairs, [(4096,), (4096,)]),
}


KERNEL_NAMES = {"merge_path": "merge_cascade_path"}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, shapes = KERNELS[name]
    args = [_spec(one_chip, *s) for s in shapes]
    assert "tpu_custom_call" in _hlo(fn, *args)
    assert f'kernel_name = "{KERNEL_NAMES.get(name, name)}"' in _lowered(fn, *args)


def test_facade_lookup_compiles_with_pallas_kernel(one_chip, monkeypatch):
    """The `lsm` facade's lookup at b = 2^16, L = 4 on the Pallas backend.

    Dispatch asks the running backend whether to interpret the kernels; this
    process runs on the CPU, so the test steers it to compile them for the
    described chip instead."""
    from repro.api import Dictionary

    monkeypatch.setattr(ops, "_interpret", lambda: False)
    monkeypatch.setattr(ops, "_BACKEND", "pallas")
    d = Dictionary.create("lsm", batch_size=B, num_levels=4)
    abstract = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip), d
    )
    hlo = _hlo(lambda d, q: d.lookup(q), abstract, _spec(one_chip, B))
    assert "tpu_custom_call" in hlo


def test_facade_lookup_on_xla_gathers_rows(one_chip, monkeypatch):
    """The `lsm` facade's lookup at the benchmark's shapes (b = 2^16, L = 12)
    on the XLA backend searches every run by row gathers of 128 keys and
    runs no `while` loop: no run falls back to the scalar binary search."""
    from repro.api import Dictionary

    monkeypatch.setattr(ops, "_BACKEND", "xla")
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
