"""Parameter abstraction: models declare *abstract* trees of ``ParamDef``
(shape + logical axis names + init); the same tree materializes to arrays
(``materialize``), to ShapeDtypeStructs for the dry-run (``abstract``), and
to PartitionSpecs via the sharding rules (``sharding/rules.py``).
"""
from __future__ import annotations

from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


class ParamDef(NamedTuple):
    shape: Tuple[int, ...]
    axes: Tuple[Optional[str], ...]   # logical axis name per dim (None = never sharded)
    init: str = "normal"              # normal | zeros | ones | embed | const
    scale: float = -1.0               # -1 -> 1/sqrt(fan_in) for "normal"
    dtype: Any = jnp.float32


def is_def(x) -> bool:
    return isinstance(x, ParamDef)


def _tree_map_defs(f, tree):
    return jax.tree_util.tree_map(f, tree, is_leaf=is_def)


def stack(tree, n: int, axis_name: str = "layers"):
    """Add a leading stacked-layer dim of size n to every ParamDef."""
    def add(d: ParamDef) -> ParamDef:
        return d._replace(shape=(n,) + d.shape, axes=(axis_name,) + d.axes)
    return _tree_map_defs(add, tree)


def materialize(tree, rng: jax.Array):
    """Deterministically initialize every leaf (path-hashed rng folds)."""
    paths = jax.tree_util.tree_flatten_with_path(tree, is_leaf=is_def)[0]

    def init_one(path, d: ParamDef):
        key = jax.random.fold_in(rng, _path_hash(path))
        if d.init == "zeros":
            return jnp.zeros(d.shape, d.dtype)
        if d.init == "ones":
            return jnp.ones(d.shape, d.dtype)
        if d.init == "const":
            return jnp.full(d.shape, d.scale, d.dtype)
        if d.init == "arange_log":  # mamba A_log init: log(uniform[1, 16])
            u = jax.random.uniform(key, d.shape, jnp.float32, 1.0, 16.0)
            return jnp.log(u).astype(d.dtype)
        scale = d.scale
        if scale < 0:
            scale = 1.0 / np.sqrt(_fan_in(d))
        return (scale * jax.random.normal(key, d.shape, jnp.float32)).astype(d.dtype)

    leaves = [init_one(p, d) for p, d in paths]
    treedef = jax.tree_util.tree_structure(tree, is_leaf=is_def)
    return jax.tree_util.tree_unflatten(treedef, leaves)


def abstract(tree):
    """ShapeDtypeStruct tree — zero-allocation stand-in for the dry-run."""
    return _tree_map_defs(lambda d: jax.ShapeDtypeStruct(d.shape, d.dtype), tree)


def logical_axes(tree):
    """Tree of logical-axis tuples, mirroring the param tree."""
    return _tree_map_defs(lambda d: d.axes, tree)


STACKED_AXES = ("layers", "experts")


def _fan_in(d: ParamDef) -> float:
    """Fan-in from the logical-axis layout, after the leading stacked
    axes (``layers`` from ``stack``, ``experts``), which hold independent
    copies and are not contracted: 2D mats are (in, out); 3D projections
    back to the residual stream (last axis "embed", e.g. wo (H, hd, d))
    contract everything before it; other 3D projections (wq (d, H, hd),
    wk_b (lora, H, hd)) contract their first dim."""
    shape, axes = d.shape, d.axes
    while len(shape) > 1 and axes and axes[0] in STACKED_AXES:
        shape, axes = shape[1:], axes[1:]
    if len(shape) < 2:
        return float(shape[-1])
    if len(shape) == 2:
        return float(shape[0])
    if axes and axes[-1] == "embed":
        return float(np.prod(shape[:-1]))
    return float(shape[0])


def _path_hash(path) -> int:
    # zlib.crc32, NOT hash(): python str hashing is salted per process,
    # which would make "seeded" init non-reproducible across runs
    import zlib
    s = "/".join(str(p) for p in path)
    return zlib.crc32(s.encode()) & 0x7FFFFFFF


def param_bytes(tree) -> int:
    leaves = jax.tree_util.tree_leaves(abstract(tree))
    return sum(int(np.prod(l.shape)) * l.dtype.itemsize for l in leaves)


def count(tree) -> int:
    leaves = jax.tree_util.tree_leaves(abstract(tree))
    return sum(int(np.prod(l.shape)) for l in leaves)
