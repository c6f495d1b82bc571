"""Weights made from the seed, on the device, by the benchmark.

A llama-style decoder's weights are named here (``spec``) with their
shapes and scales; each is drawn from a key folded from the seed and
its name, so a leaf can be made alone (the reference remakes them one at
a time) and equals the same leaf made with all the others.  The program
receives them in its own tree (``to_program_tree``), matched by path."""
from __future__ import annotations

import zlib

import numpy as np


def dims(config):
    """The sizes the reference and the cost functions need, from a
    configuration file (Hugging Face key names)."""
    d = config["hidden_size"]
    h = config["num_attention_heads"]
    return {"d": d, "h": h, "k": config["num_key_value_heads"],
            "hd": config.get("head_dim") or d // h,
            "ff": config["intermediate_size"],
            "layers": config["num_hidden_layers"],
            "vocab": config["vocab_size"],
            "eps": float(config["rms_norm_eps"]),
            "theta": float(config["rope_theta"])}


def spec(config):
    """name -> (shape, std or "ones").  Per-layer weights carry a leading
    layer axis."""
    m = dims(config)
    d, h, k, hd, ff, L, V = (m["d"], m["h"], m["k"], m["hd"], m["ff"],
                             m["layers"], m["vocab"])
    return {
        "embed": ((V, d), 0.02),
        "unembed": ((d, V), 0.02),
        "final_norm/scale": ((d,), "ones"),
        "blocks/L0/attn_norm/scale": ((L, d), "ones"),
        "blocks/L0/attn/wq": ((L, d, h, hd), d ** -0.5),
        "blocks/L0/attn/wk": ((L, d, k, hd), d ** -0.5),
        "blocks/L0/attn/wv": ((L, d, k, hd), d ** -0.5),
        "blocks/L0/attn/wo": ((L, h, hd, d), (h * hd) ** -0.5),
        "blocks/L0/ffn_norm/scale": ((L, d), "ones"),
        "blocks/L0/ffn/wg": ((L, d, ff), d ** -0.5),
        "blocks/L0/ffn/wu": ((L, d, ff), d ** -0.5),
        "blocks/L0/ffn/wd": ((L, ff, d), ff ** -0.5),
    }


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
    sp = spec(config)

    @jax.jit
    def run(key):
        return {n: make_leaf(key, n, s, std, dtype)
                for n, (s, std) in sp.items()}
    return run(key)


def make_one(config, key, name, dtype="float32"):
    import jax
    shape, std = spec(config)[name]
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
    return int(sum(np.prod(s) for s, _ in spec(config).values()))
