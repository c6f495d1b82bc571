"""Grouped matrix products over the experts a device holds: JAX's Pallas
megablox ``gmm`` (forward; its custom VJP runs the transposed ``gmm`` and
the per-group ``tgmm`` backward), compiled by Mosaic on TPU and run in
interpret mode on the CPU backend.

``lhs`` (m, k) holds each group's rows one group after another; group g
multiplies rows [offset_g, offset_g + sizes[g]) by ``rhs[g]`` (k, n).
The kernel's grid walks only the row tiles that some group touches, so
the rows multiplied are the groups' rows plus, per group, the rest of a
tile it shares with its neighbour: at most one tile of padding a group,
whatever m is.  Rows past the last group are neither read nor written
(their output is undefined: callers mask them)."""
from __future__ import annotations

import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu.megablox import ops as _megablox

from repro.kernels import interpret_mode

# rows per tile; m must be a multiple of it
TM = 256


def row_tile(m: int) -> int:
    return min(TM, m)


def _cols(x: int) -> int:
    """A column tile: 512 where it divides, else the whole dimension
    (d_expert 1408 = 11 x 128 has no other tile that is whole lanes)."""
    return 512 if x % 512 == 0 else x


def _tiling(m: int, k: int, n: int):
    return (row_tile(m), _cols(k), _cols(n))


def gmm(lhs, rhs, group_sizes, out_dtype=None):
    """(m, k) x (G, k, n) -> (m, n): each group's rows times its matrix."""
    assert lhs.shape[0] % row_tile(lhs.shape[0]) == 0, lhs.shape
    return _megablox.gmm(lhs, rhs, group_sizes.astype(jnp.int32),
                         out_dtype or lhs.dtype, _tiling, None, None, False,
                         interpret_mode())


def active_rows(group_sizes, tm: int):
    """Rows the grid multiplies: each non-empty group's span rounded out
    to whole tiles (a tile two groups share is visited by both)."""
    ends = jnp.cumsum(group_sizes)
    starts = ends - group_sizes
    tiles = (ends + tm - 1) // tm - starts // tm
    return jnp.sum(jnp.where(group_sizes > 0, tiles, 0)) * tm
