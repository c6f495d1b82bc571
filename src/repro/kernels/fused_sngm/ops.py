"""jit'd tree-level wrapper used by ``repro.core.optim.sngm(use_pallas=True)``.

On the CPU backend the kernel runs in interpret mode (correctness path);
numerics match ref.py / the jnp optimizer exactly (float32 math).
"""
from __future__ import annotations

import jax

from repro.kernels import interpret_mode, record_launches
from repro.kernels.fused_sngm.kernel import fused_sngm_update


def fused_sngm_tree(params, grads, momentum, inv_norm, beta: float, lr):
    interp = interpret_mode()
    new_p, new_u = {}, {}
    flat_p = jax.tree_util.tree_flatten_with_path(params)[0]
    treedef = jax.tree_util.tree_structure(params)
    flat_g = jax.tree_util.tree_leaves(grads)
    flat_u = jax.tree_util.tree_leaves(momentum)
    ps, us = [], []
    for (path, p), g, u in zip(flat_p, flat_g, flat_u):
        record_launches(1)
        pn, un = fused_sngm_update(p, g, u, inv_norm, lr, beta=beta,
                                   interpret=interp)
        ps.append(pn)
        us.append(un)
    return (jax.tree_util.tree_unflatten(treedef, ps),
            jax.tree_util.tree_unflatten(treedef, us))
