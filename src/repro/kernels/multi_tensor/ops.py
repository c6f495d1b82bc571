"""Backend-dispatching wrappers for the multi-tensor kernels.

On the CPU backend the kernels run in interpret mode (correctness path);
``backend="ref"`` bypasses Pallas entirely with the bit-identical jnp
oracle.  Launch counts are recorded at trace time for the overhead
benchmark — note the ref backend records zero.
"""
from __future__ import annotations

from repro.kernels import interpret_mode, record_launches
from repro.kernels.multi_tensor import kernel, ref


def chunk_sumsq(x, p=None, *, wd: float = 0.0, backend: str = "pallas"):
    if backend == "ref":
        return ref.chunk_sumsq_ref(x, p, wd=wd)
    record_launches(1)
    return kernel.chunk_sumsq(x, p, wd=wd, interpret=interpret_mode())


def fused_update(p, g, u, a_chunk, c, *, beta: float, wd: float,
                 cast_g_first: bool = False, nesterov: bool = False,
                 apply: bool = True, backend: str = "pallas"):
    if backend == "ref":
        return ref.fused_update_ref(p, g, u, a_chunk, c, beta=beta, wd=wd,
                                    cast_g_first=cast_g_first,
                                    nesterov=nesterov, apply=apply)
    record_launches(1)
    return kernel.fused_update(p, g, u, a_chunk, c, beta=beta, wd=wd,
                               cast_g_first=cast_g_first, nesterov=nesterov,
                               apply=apply, interpret=interpret_mode())


def scale_apply(p, g, a_chunk, c, *, backend: str = "pallas"):
    if backend == "ref":
        return ref.scale_apply_ref(p, g, a_chunk, c)
    record_launches(1)
    return kernel.scale_apply(p, g, a_chunk, c, interpret=interpret_mode())


def adam_update(p, g, m, v, bc1, bc2, *, b1: float, b2: float, eps: float,
                wd: float = 0.0, backend: str = "pallas"):
    if backend == "ref":
        return ref.adam_update_ref(p, g, m, v, bc1, bc2, b1=b1, b2=b2,
                                   eps=eps, wd=wd)
    record_launches(1)
    return kernel.adam_update(p, g, m, v, bc1, bc2, b1=b1, b2=b2,
                              eps=eps, wd=wd, interpret=interpret_mode())
