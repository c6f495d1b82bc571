"""Weights made from the seed, on the device, by the benchmark.

Each architecture's module names its weights (``spec`` in
``bench/archs/``) with their shapes and scales; each is drawn from a key
folded from the seed and its name, so a leaf can be made alone (the
reference remakes them one at a time) and equals the same leaf made with
all the others.  The program receives them in its own tree
(``to_program_tree``), matched by path."""
from __future__ import annotations

import zlib

import numpy as np

from bench import common


def make_leaf(key, name, shape, std, dtype):
    import jax
    import jax.numpy as jnp
    if std == "ones":
        return jnp.ones(shape, dtype)
    k = jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)
    return (std * jax.random.normal(k, shape, jnp.float32)).astype(dtype)


def make_all(config, key, dtype="float32"):
    """Every weight, in one jitted call on the default device."""
    import jax
    sp = common.arch(config).spec(config)

    @jax.jit
    def run(key):
        return {n: make_leaf(key, n, s, std, dtype)
                for n, (s, std) in sp.items()}
    return run(key)


def make_one(config, key, name, dtype="float32"):
    import jax
    shape, std = common.arch(config).spec(config)[name]
    return jax.jit(lambda k: make_leaf(k, name, shape, std, dtype))(key)


def path_name(path) -> str:
    parts = []
    for p in path:
        parts.append(str(getattr(p, "key", getattr(p, "name", p))))
    return "/".join(parts)


def to_program_tree(flat, abstract_tree):
    """Place the named weights into the program's parameter tree; every
    leaf must be named, with the program's shape."""
    import jax
    leaves, treedef = jax.tree_util.tree_flatten_with_path(abstract_tree)
    out = []
    for path, a in leaves:
        name = path_name(path)
        if name not in flat:
            raise KeyError(f"bench: the program has a parameter {name!r} "
                           f"that the configuration does not name")
        w = flat.pop(name)
        if tuple(w.shape) != tuple(a.shape):
            raise ValueError(f"bench: {name} is {a.shape} in the program, "
                             f"{w.shape} here")
        out.append(w)
    if flat:
        raise KeyError(f"bench: weights the program has no place for: "
                       f"{sorted(flat)}")
    return jax.tree_util.tree_unflatten(treedef, out)


def n_params(config) -> int:
    return int(sum(np.prod(s) for s, _ in
                   common.arch(config).spec(config).values()))
