"""Production mesh construction (assignment: MULTI-POD DRY-RUN item 1).

Also owns the 1-D dictionary-shard mesh used by the `lsm_sharded` backend
(repro.api.backends): backends never call jax.make_mesh directly — mesh
construction stays in launch/.
"""

from __future__ import annotations

from typing import Optional

import jax
from jax import make_mesh
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 chips per pod; multi_pod adds a leading 2-pod axis (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_shard_mesh(num_shards: Optional[int] = None, *, axis: str = "shard"):
    """1-D mesh over the first `num_shards` devices for the sharded dictionary.

    `num_shards=None` takes every visible device. On CPU the device pool can
    be widened with XLA_FLAGS=--xla_force_host_platform_device_count=N (set
    before jax initializes — tests/conftest.py does this for the suite).
    """
    devices = jax.devices()
    if num_shards is None:
        num_shards = len(devices)
    num_shards = int(num_shards)
    if num_shards < 1:
        raise ValueError(f"num_shards must be >= 1, got {num_shards}")
    if num_shards > len(devices):
        raise ValueError(
            f"num_shards={num_shards} exceeds the {len(devices)} visible "
            "device(s); on CPU, force more host devices with "
            "XLA_FLAGS=--xla_force_host_platform_device_count"
        )
    return make_mesh(
        (num_shards,), (axis,),
        axis_types=(AxisType.Auto,),
        devices=devices[:num_shards],
    )


def make_debug_mesh(data: int = 2, model: int = 2, pod: int = 0):
    """Small host-device mesh for tests (requires forced host device count)."""
    if pod:
        return make_mesh(
            (pod, data, model), ("pod", "data", "model"),
            axis_types=(AxisType.Auto,) * 3,
        )
    return make_mesh(
        (data, model), ("data", "model"),
        axis_types=(AxisType.Auto,) * 2,
    )
