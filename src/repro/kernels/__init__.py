"""repro.kernels — Pallas kernels for the optimizer/attention hot spots.

Each kernel package is <name>/{kernel,ops,ref}.py: the Pallas kernel, the
backend-dispatching wrapper, and the jnp oracle used by tests.

Also home to the kernel-launch counter: every ops-layer wrapper calls
``record_launches(n)`` at TRACE time, so tracing one optimizer step inside
``count_pallas_launches()`` reports exactly how many ``pallas_call``s that
step will issue per execution (the number bench_optimizer_overhead.py uses
to show O(1) multi-tensor launches vs O(n_leaves) per-leaf launches).

``interpret_mode()`` is the one place a wrapper decides how a kernel
runs: compiled by Mosaic on TPU, in Pallas interpret mode on the CPU
backend (what the tests use).  Any other backend raises, so a machine
whose chip failed to come up cannot run the hot paths on the host and
report success.
"""
from __future__ import annotations

import contextlib

import jax

_LAUNCHES = {"n": 0}


def interpret_mode() -> bool:
    """False on TPU, True on the CPU backend; any other backend raises."""
    backend = jax.default_backend()
    if backend not in ("tpu", "cpu"):
        raise RuntimeError(f"Pallas kernels run on TPU (or interpreted on "
                           f"the CPU backend), not on {backend!r}")
    return backend == "cpu"


def record_launches(n: int = 1) -> None:
    """Called by ops wrappers once per pallas_call they trace."""
    _LAUNCHES["n"] += n


@contextlib.contextmanager
def count_pallas_launches():
    """Count pallas_call sites traced inside the block.

        with count_pallas_launches() as c:
            jax.jit(opt.step).lower(g, state, p)
        print(c["launches"])   # kernel launches per executed step
    """
    start = _LAUNCHES["n"]
    box = {"launches": 0}
    try:
        yield box
    finally:
        box["launches"] = _LAUNCHES["n"] - start
