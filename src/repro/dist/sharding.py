"""Sharding hints and sharding-spec construction for the training stack.

Two layers:

* `hint(x, *spec)` / `regather_params_tp(params)` — *in-graph* layout
  constraints used inside model code. They consult the ambient mesh at trace
  time and degrade to identity when there is none (CPU tests, single-device
  runs), so model code never branches on the environment. Axis names absent
  from the ambient mesh and axes that do not divide the dimension are dropped
  rather than erroring — a hint is advice to the partitioner, not a contract.

* `params_shardings` / `batch_shardings` / `replicated` — *out-of-graph*
  NamedSharding trees handed to jit's in/out_shardings by the launch layer.
  The parameter rule is tensor-parallel-greedy: shard the last mesh-divisible
  dimension of every >=2D leaf over the "model" axis, replicate the rest.
  Batches shard their leading (batch) dimension over "data" (and "pod" when
  present).
"""

from __future__ import annotations

import math

import jax
from jax.sharding import NamedSharding, PartitionSpec as P


def _ambient_mesh():
    """The mesh of the enclosing `jax.set_mesh(...)` scope, or None."""
    mesh = jax.sharding.get_abstract_mesh()
    return None if mesh.empty else mesh


def _clean_entry(mesh, entry, dim: int):
    """Keep only mesh-resident axis names whose product divides `dim`."""
    if entry is None:
        return None
    names = (entry,) if isinstance(entry, str) else tuple(entry)
    names = tuple(n for n in names if n in mesh.axis_names)
    if not names:
        return None
    size = math.prod(mesh.shape[n] for n in names)
    if size <= 1 or dim % size != 0:
        return None
    return names[0] if len(names) == 1 else names


def hint(x, *spec):
    """Soft sharding constraint: `hint(x, ("pod", "data"), "model", None)`.

    One spec entry per array dimension (missing trailing entries mean
    replicated). Off-mesh this is the identity, which is what makes the
    PerfOptions equivalence tests meaningful on CPU.
    """
    mesh = _ambient_mesh()
    if mesh is None or not hasattr(x, "ndim"):
        return x
    entries = list(spec[: x.ndim]) + [None] * (x.ndim - len(spec))
    cleaned = tuple(_clean_entry(mesh, e, x.shape[i]) for i, e in enumerate(entries))
    if all(e is None for e in cleaned):
        return x
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P(*cleaned)))


def regather_params_tp(params):
    """ZeRO-3-style regather: constrain a (scanned-unit) param tree to fully
    replicated so the partitioner materializes each unit's weights just before
    use and frees them after. Identity off-mesh."""
    mesh = _ambient_mesh()
    if mesh is None:
        return params
    repl = NamedSharding(mesh, P())
    return jax.tree_util.tree_map(
        lambda l: jax.lax.with_sharding_constraint(l, repl) if hasattr(l, "ndim") else l,
        params,
    )


def replicated(mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def stacked_shardings(tree, mesh, axis: str):
    """NamedSharding tree splitting each leaf's leading (stacking) axis.

    The distributed dictionary keeps per-shard states stacked on a leading
    axis of size num_shards (core/distributed.py); every leaf of the state
    pytree gets P(axis, None, ...) so shard s's slice lives on device s.
    """
    return jax.tree_util.tree_map(
        lambda l: NamedSharding(mesh, P(axis, *([None] * (l.ndim - 1)))), tree
    )


def _model_spec(shape, mesh) -> P:
    """Shard the last model-divisible dim of a >=2D leaf over "model"."""
    if "model" not in mesh.axis_names or len(shape) < 2:
        return P()
    m = mesh.shape["model"]
    for d in range(len(shape) - 1, 0, -1):  # never the leading (scan/stack) axis
        if m > 1 and shape[d] % m == 0:
            return P(*([None] * d + ["model"] + [None] * (len(shape) - d - 1)))
    return P()


def params_shardings(cfg, params, mesh, serve: bool = False):
    """NamedSharding tree for a parameter tree (or ShapeDtypeStruct specs).

    `serve=True` uses the same layout — decode-time layouts only diverge once
    weight-stationary serving optimizations land; keeping one code path keeps
    checkpoints portable between the two.
    """
    del cfg, serve
    return jax.tree_util.tree_map(
        lambda l: NamedSharding(mesh, _model_spec(getattr(l, "shape", ()), mesh)), params
    )


def _batch_spec(shape, mesh) -> P:
    names = [n for n in ("pod", "data") if n in mesh.axis_names and mesh.shape[n] > 1]
    if not shape or not names:
        return P()
    size = math.prod(mesh.shape[n] for n in names)
    if shape[0] % size != 0:
        return P()
    entry = names[0] if len(names) == 1 else tuple(names)
    return P(*([entry] + [None] * (len(shape) - 1)))


def batch_shardings(batch, mesh):
    """Data-parallel sharding for a batch tree: leading dim over data axes."""
    return jax.tree_util.tree_map(
        lambda l: NamedSharding(mesh, _batch_spec(getattr(l, "shape", ()), mesh)), batch
    )
