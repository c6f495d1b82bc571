"""Multi-tensor fused optimizer engine.

The per-leaf Pallas path (``kernels/fused_sngm``) launches one kernel per
parameter tensor, so optimizer overhead is O(n_leaves).  This engine
flattens the parameter/gradient/momentum pytrees into dtype-bucketed
contiguous flat buffers, computes global AND per-segment squared norms in
one Pallas reduction pass per bucket, then applies momentum + update for
the whole bucket in one fused second pass — O(1) kernel launches per step
regardless of tree size.  One coefficient parameterization covers the four
momentum optimizers (see ``kernels/multi_tensor/kernel.py``): SNGM (global
norm), SNGM[per_tensor] and LARS (per-segment norms), and MSGD.  The Adam
family (LAMB) gets its own two-pass pipeline — a fused Adam-moment pass
plus the same apply pass — and ``clip_by_global_norm``-prefixed chains
add one raw-norm round (``_clip_round``) whose scalar scale is applied
inside the later kernels, keeping everything O(1) launches per step.

Numerics are bit-identical to the pure-jnp optimizer paths in
``core.optim`` because both sides share one canonical reduction order:
``leaf_sumsq`` below (CHUNK-sized row partials, then a single reduction
over partials) is used by ``tree_squared_norm``/the per-leaf jnp norms,
and every segment starts on a CHUNK boundary in the flat buffer, so the
kernel's row partials are the same numbers in the same order.

Sharding: flat buffers block 1-D over EVERY axis of a device mesh
(ZeRO-style — optimizer state has no tensor structure left, so the full
device count divides it).  ``build_layout(..., shards=S)`` pads buckets
so each local block is a whole number of kernel tiles, and the kernel
passes run shard-wise under ``shard_map`` with two-level norms:
per-shard Pallas chunk partials, then an ``all_gather`` of the partial
vectors so every shard folds the SAME canonical pairwise reduction —
sharded==unsharded stays bitwise in fp32 (see the mesh section below).
That one small collective per norm pass is exactly the
one-collective-per-step property that makes SNGM cheap to distribute
(paper §5).

Flat-buffer residency: ``multi_tensor_step`` rebuilds all three buffer
sets (params/grads/momentum) from the leaf pytrees every step.
``FlatOptState`` + ``multi_tensor_step_flat`` instead keep params and
momentum *resident* as flat buffers across steps, so steady state packs
only the gradients — 1/3 of the per-step packing traffic on an fp32 tree
(measured via ``count_packed_bytes``).  The pytree view is materialized
only where leaves are actually needed: ``loss_fn``, logging, and
checkpointing.  Both paths are bit-identical: segment padding is zero at
init and every kernel pass maps zero pads to zero pads (g-pad is always
zero because gradients are re-flattened with zero padding each step), so
a resident buffer is exactly what re-flattening its pytree view would
produce.
"""
from __future__ import annotations

import contextlib
import dataclasses
from typing import Any, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.kernels.multi_tensor.kernel import CHUNK, TILE
from repro.kernels.multi_tensor import ops as _ops

PyTree = Any


# ---------------------------------------------------------------------------
# packing accounting (the resident path's reason to exist)
# ---------------------------------------------------------------------------

_PACKED = {"bytes": 0, "buffers": 0}


def _record_packed(flats: Sequence[jnp.ndarray]) -> None:
    """Called by ``flatten`` once per call, at TRACE time under jit — so
    tracing one optimizer step inside ``count_packed_bytes`` reports the
    bytes that step packs into flat buffers per execution."""
    for f in flats:
        _PACKED["bytes"] += f.size * jnp.dtype(f.dtype).itemsize
        _PACKED["buffers"] += 1


@contextlib.contextmanager
def count_packed_bytes():
    """Count bytes packed into flat buffers inside the block.

        with count_packed_bytes() as c:
            jax.jit(opt.step).lower(grads, state, params)
        print(c["bytes"])   # buffer bytes packed per executed step

    The resident path (FlatOptState) packs only the gradients; the
    per-step path re-packs params+grads+momentum every step."""
    start = dict(_PACKED)
    box = {"bytes": 0, "buffers": 0}
    try:
        yield box
    finally:
        box["bytes"] = _PACKED["bytes"] - start["bytes"]
        box["buffers"] = _PACKED["buffers"] - start["buffers"]


# ---------------------------------------------------------------------------
# canonical chunked reduction (shared with the jnp optimizer paths)
# ---------------------------------------------------------------------------

def _fold_sum(x: jnp.ndarray) -> jnp.ndarray:
    """Sum a 1-D f32 array by explicit pairwise halving.

    The associativity is fixed by the graph itself (log2(n) explicit adds),
    so the result is bitwise reproducible in ANY fusion context — unlike
    ``jnp.sum(jnp.sum(..., axis=1))``, which XLA's simplifier merges into a
    single differently-ordered reduction depending on what surrounds it.
    Both the jnp optimizer paths and the fused engine reduce norm partials
    with this, which is what makes them bit-identical."""
    n = x.shape[0]
    while n > 1:
        if n % 2:
            x = jnp.pad(x, (0, 1))
            n += 1
        x = x[:n // 2] + x[n // 2:]
        n //= 2
    return x[0]


def leaf_sumsq(x) -> jnp.ndarray:
    """Sum of squared entries of one array, f32 accumulate, in the engine's
    canonical order: CHUNK-sized row partials, then a pairwise fold over the
    partials.  ``tree_squared_norm`` and the per-tensor jnp norms use this
    so the fused path is bit-identical to the jnp path.  A size-0 leaf
    contributes exactly 0.0 (one all-zero pad chunk), matching its empty
    segment in the flat buffer."""
    xf = x.astype(jnp.float32).ravel()
    pad = -xf.size % CHUNK
    if pad or xf.size == 0:
        xf = jnp.pad(xf, (0, pad or CHUNK))
    return _fold_sum(jnp.sum(jnp.square(xf.reshape(-1, CHUNK)), axis=1))


def tree_squared_norm(tree: PyTree) -> jnp.ndarray:
    """Sum of squared entries over the whole pytree (fp32 accumulate), in
    the canonical chunked order — the one reduction every optimizer path
    (jnp, gradient-transform interpreter, fused engine) shares, which is
    what keeps their norms bit-identical."""
    leaves = jax.tree_util.tree_leaves(tree)
    return sum(leaf_sumsq(l) for l in leaves)


def global_norm(tree: PyTree) -> jnp.ndarray:
    return jnp.sqrt(tree_squared_norm(tree))


# ---------------------------------------------------------------------------
# layout: dtype buckets of chunk-aligned segments
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Segment:
    """One leaf's slice of its bucket buffer ([offset, offset+size) holds
    the raveled leaf; the segment is padded out to chunk_hi*CHUNK)."""
    index: int                  # position in the original leaf order
    offset: int                 # element offset, always a CHUNK multiple
    size: int
    shape: Tuple[int, ...]
    dtype: Any
    chunk_lo: int               # [chunk_lo, chunk_hi) partial-row range
    chunk_hi: int


@dataclasses.dataclass(frozen=True)
class Bucket:
    dtype: Any
    segments: Tuple[Segment, ...]
    n_elems: int                # padded buffer length, TILE multiple
    n_chunks: int


@dataclasses.dataclass(frozen=True)
class TreeLayout:
    treedef: Any
    n_leaves: int
    buckets: Tuple[Bucket, ...]
    # bucket lengths are padded to shards*TILE multiples, so every mesh
    # shard of a flat buffer is a whole number of kernel tiles; 1 = the
    # single-device layout.  Tail padding is numerically invisible (all
    # canonical folds are per-segment), so layouts built for different
    # shard counts produce bitwise-identical steps.
    shards: int = 1


def build_layout(tree: PyTree, shards: int = 1) -> TreeLayout:
    """Static (shape/dtype-only) bucketing of a pytree.  Leaves keep their
    original relative order within a bucket; buckets are ordered by dtype
    name for determinism.  ``shards`` pads every bucket to a
    ``shards*TILE`` multiple so the buffers divide evenly over a mesh of
    that many devices (each local block a whole number of kernel
    tiles)."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    align = int(shards) * TILE
    by_dtype = {}
    for i, leaf in enumerate(leaves):
        by_dtype.setdefault(jnp.dtype(leaf.dtype).name, []).append(i)
    buckets = []
    for dname in sorted(by_dtype):
        segs, off = [], 0
        for i in by_dtype[dname]:
            leaf = leaves[i]
            size = leaf.size
            n_chunks = max(1, -(-size // CHUNK))
            segs.append(Segment(index=i, offset=off, size=size,
                                shape=tuple(leaf.shape),
                                dtype=jnp.dtype(leaf.dtype),
                                chunk_lo=off // CHUNK,
                                chunk_hi=off // CHUNK + n_chunks))
            off += n_chunks * CHUNK
        n_elems = -(-off // align) * align
        buckets.append(Bucket(dtype=jnp.dtype(dname), segments=tuple(segs),
                              n_elems=n_elems, n_chunks=n_elems // CHUNK))
    return TreeLayout(treedef=treedef, n_leaves=len(leaves),
                      buckets=tuple(buckets), shards=int(shards))


def flatten(tree: PyTree, layout: TreeLayout,
            cast_to: Optional[Any] = None) -> List[jnp.ndarray]:
    """Pack a pytree (mirroring the layout's tree) into one flat buffer per
    bucket.  ``cast_to`` overrides the buffer dtype (momentum is always
    f32 regardless of the parameter storage dtype)."""
    leaves = jax.tree_util.tree_leaves(tree)
    assert len(leaves) == layout.n_leaves, (len(leaves), layout.n_leaves)
    flats = []
    for b in layout.buckets:
        dt = jnp.dtype(cast_to) if cast_to is not None else b.dtype
        pieces, off = [], 0
        for s in b.segments:
            x = leaves[s.index].astype(dt).ravel()
            seg_len = (s.chunk_hi - s.chunk_lo) * CHUNK
            pieces.append(jnp.pad(x, (0, seg_len - s.size)))
            off += seg_len
        if b.n_elems > off:
            pieces.append(jnp.zeros((b.n_elems - off,), dt))
        flats.append(jnp.concatenate(pieces) if len(pieces) > 1
                     else pieces[0])
    _record_packed(flats)
    return flats


def unflatten(flats: Sequence[jnp.ndarray], layout: TreeLayout,
              keep_dtype: bool = False) -> PyTree:
    """Inverse of ``flatten``: slice each segment back out and rebuild the
    tree.  ``keep_dtype=True`` keeps the buffer dtype (momentum buffers are
    f32 even when the layout says bf16)."""
    leaves = [None] * layout.n_leaves
    for b, flat in zip(layout.buckets, flats):
        for s in b.segments:
            x = flat[s.offset:s.offset + s.size].reshape(s.shape)
            leaves[s.index] = x if keep_dtype else x.astype(s.dtype)
    return jax.tree_util.tree_unflatten(layout.treedef, leaves)


def _segment_sums(partials: jnp.ndarray, bucket: Bucket) -> List[jnp.ndarray]:
    """Reduce per-chunk partials to one scalar per segment — same fold as
    ``leaf_sumsq``'s final reduction, hence bit-identical."""
    return [_fold_sum(partials[s.chunk_lo:s.chunk_hi])
            for s in bucket.segments]


def _per_chunk(bucket: Bucket, seg_vals: Sequence[jnp.ndarray],
               fill=0.0) -> jnp.ndarray:
    """Expand per-segment scalars to the (n_chunks,) coefficient array the
    update kernel consumes (tail-padding chunks get ``fill``)."""
    pieces = [jnp.full((s.chunk_hi - s.chunk_lo,), v, jnp.float32)
              for s, v in zip(bucket.segments, seg_vals)]
    used = bucket.segments[-1].chunk_hi if bucket.segments else 0
    if bucket.n_chunks > used:
        pieces.append(jnp.full((bucket.n_chunks - used,), fill, jnp.float32))
    return jnp.concatenate(pieces) if len(pieces) > 1 else pieces[0]


# ---------------------------------------------------------------------------
# mesh sharding: flat buffers blocked over ALL mesh axes, two-level norms
# ---------------------------------------------------------------------------
#
# A flat buffer has no tensor structure left, so it shards 1-D over the
# whole device set (data AND model axes — ZeRO-style optimizer-state
# partitioning).  Each kernel pass then runs on the LOCAL block inside
# ``shard_map``, and the norm passes become two-level: per-shard Pallas
# chunk partials, then an ``all_gather`` of the (tiny) partial vectors so
# every shard folds the SAME canonical pairwise reduction over the same
# numbers in the same order.  Gathering partials instead of psum-ing
# per-shard folded scalars is what keeps sharded==unsharded bitwise in
# fp32: a psum of partial sums would re-associate the fold.  The gather
# moves n_chunks f32 scalars (4 bytes per 1024 parameter elements) — the
# one small collective per norm pass the paper's SNGM cost model prices
# in (§5).

def mesh_shards(mesh) -> int:
    """Total device count of a mesh (1 for None) — the shard count flat
    buffers divide into."""
    return 1 if mesh is None else int(mesh.size)


def flat_sharding(mesh):
    """NamedSharding blocking a 1-D flat buffer over every mesh axis."""
    from jax.sharding import NamedSharding, PartitionSpec
    return NamedSharding(mesh, PartitionSpec(tuple(mesh.axis_names)))


def _engine_mesh(layout: TreeLayout, mesh):
    """The mesh the engine may actually run sharded on, or None.

    Sharded dispatch requires the layout to have been built for exactly
    this mesh's device count — only then is every local block a whole
    number of kernel tiles.  A resident state built (or restored) for a
    different shard count silently falls back to the unsharded ops,
    which compute the same values (XLA then inserts the collectives it
    needs); re-place the state via ``optim.from_pytree(..., mesh=...)``
    to get the sharded fast path."""
    if mesh is None:
        return None
    s = mesh_shards(mesh)
    return mesh if (s > 1 and layout.shards == s) else None


def _shmap(mesh, f, in_specs, out_specs):
    # check_vma=False: outputs include all_gather-ed partial vectors that
    # ARE replicated, but the Pallas calls inside the body carry no
    # varying-manual-axes annotation, so the checker cannot prove it.
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def _chunk_sumsq(x, p=None, *, wd: float = 0.0, backend: str = "pallas",
                 mesh=None) -> jnp.ndarray:
    """Per-chunk squared-norm partials of a flat buffer; with a mesh, each
    shard reduces its local tiles and the full (n_chunks,) partial vector
    is gathered back, bitwise equal to the unsharded launch (the gather
    is pure concatenation in shard order)."""
    if mesh is None or backend == "ref":
        if p is None:
            return _ops.chunk_sumsq(x, wd=wd, backend=backend)
        return _ops.chunk_sumsq(x, p, wd=wd, backend=backend)
    from jax.sharding import PartitionSpec as P
    ax = tuple(mesh.axis_names)
    spec = P(ax)

    if p is None:
        def local(xs):
            return jax.lax.all_gather(
                _ops.chunk_sumsq(xs, wd=wd, backend=backend), ax, tiled=True)
        return _shmap(mesh, local, (spec,), P())(x)

    def local(xs, ps):
        return jax.lax.all_gather(
            _ops.chunk_sumsq(xs, ps, wd=wd, backend=backend), ax, tiled=True)
    return _shmap(mesh, local, (spec, spec), P())(x, p)


def _fused_update(pf, gf, uf, ac, c, *, beta: float, wd: float,
                  cast_g_first: bool, nesterov: bool, apply: bool,
                  backend: str = "pallas", mesh=None):
    """Momentum+apply pass; with a mesh, p/g/u and the per-chunk
    coefficient array are consumed blockwise (the replicated (n_chunks,)
    coefficients auto-slice under ``in_specs``) and the update-norm
    partials come back gathered."""
    if mesh is None or backend == "ref":
        return _ops.fused_update(pf, gf, uf, ac, c, beta=beta, wd=wd,
                                 cast_g_first=cast_g_first,
                                 nesterov=nesterov, apply=apply,
                                 backend=backend)
    from jax.sharding import PartitionSpec as P
    ax = tuple(mesh.axis_names)
    spec = P(ax)

    def local(pf, gf, uf, ac, c):
        po, uo, usq = _ops.fused_update(pf, gf, uf, ac, c, beta=beta, wd=wd,
                                        cast_g_first=cast_g_first,
                                        nesterov=nesterov, apply=apply,
                                        backend=backend)
        return po, uo, jax.lax.all_gather(usq, ax, tiled=True)
    return _shmap(mesh, local, (spec, spec, spec, spec, P()),
                  (spec, spec, P()))(pf, gf, uf, ac, c)


def _scale_apply(pf, ud, ac, c, *, backend: str = "pallas", mesh=None):
    """Coefficient-scaled apply pass, blockwise under a mesh (see
    ``_fused_update``)."""
    if mesh is None or backend == "ref":
        return _ops.scale_apply(pf, ud, ac, c, backend=backend)
    from jax.sharding import PartitionSpec as P
    ax = tuple(mesh.axis_names)
    spec = P(ax)

    def local(pf, ud, ac, c):
        po, ssq = _ops.scale_apply(pf, ud, ac, c, backend=backend)
        return po, jax.lax.all_gather(ssq, ax, tiled=True)
    return _shmap(mesh, local, (spec, spec, spec, P()), (spec, P()))(
        pf, ud, ac, c)


def _adam_update(pf, gf, mf, vf, bc1, bc2, *, b1: float, b2: float,
                 eps: float, wd: float = 0.0, backend: str = "pallas",
                 mesh=None):
    """Fused Adam-moment pass, blockwise under a mesh; the three partial
    vectors (direction/param/grad sumsq) come back gathered."""
    if mesh is None or backend == "ref":
        return _ops.adam_update(pf, gf, mf, vf, bc1, bc2, b1=b1, b2=b2,
                                eps=eps, wd=wd, backend=backend)
    from jax.sharding import PartitionSpec as P
    ax = tuple(mesh.axis_names)
    spec = P(ax)

    def local(pf, gf, mf, vf, bc1, bc2):
        mo, vo, ud, usq, psq, gsq = _ops.adam_update(
            pf, gf, mf, vf, bc1, bc2, b1=b1, b2=b2, eps=eps, wd=wd,
            backend=backend)
        gather = lambda t: jax.lax.all_gather(t, ax, tiled=True)
        return mo, vo, ud, gather(usq), gather(psq), gather(gsq)
    return _shmap(mesh, local, (spec,) * 4 + (P(), P()),
                  (spec, spec, spec, P(), P(), P()))(pf, gf, mf, vf, bc1, bc2)


# ---------------------------------------------------------------------------
# flat-buffer-resident optimizer state
# ---------------------------------------------------------------------------

@jax.tree_util.register_pytree_with_keys_class
@dataclasses.dataclass(frozen=True)
class FlatOptState:
    """Optimizer state kept resident in the engine's flat-buffer form.

    ``p_flats`` hold the parameters in their bucket (storage) dtype, one
    buffer per layout bucket.  The per-leaf slots depend on the engine
    family: momentum kinds (sngm/msgd/lars) carry the f32 momentum in
    ``u_flats``; the Adam family (lamb) instead carries the f32 first and
    second moments in ``m_flats``/``v_flats`` (``u_flats`` is empty).
    ``e_flats`` hold the resident EMA shadow parameters of
    ``ema_params`` stages compiled by the segment planner: one tuple of
    per-bucket f32 buffers PER ema stage (empty for chains without one),
    updated elementwise on the flats each step (zero launches) and
    materialized to pytrees only via ``.ema_views`` / ``to_pytree``.
    ``layout`` and ``form`` ride along as static pytree aux data, so a
    jitted step never rebuilds or re-packs them; ``form`` records which
    family — ``"momentum"``, ``("lamb", n_prefix, n_mid)``, or a
    segment-compiled chain's ``("chain", slots)`` with one per-stage
    state tag ("empty"|"trace"|"sched"|"adam"|"ema") — so ``to_pytree``
    can rebuild the matching pytree-form state.  The resident buffers
    are authoritative: materialize pytree views via ``.params`` /
    ``.momentum`` / ``.moments`` only for ``loss_fn``, logging, and
    checkpointing.
    """
    step: jnp.ndarray                    # scalar int32
    p_flats: Tuple[jnp.ndarray, ...]
    u_flats: Tuple[jnp.ndarray, ...]
    layout: TreeLayout
    m_flats: Tuple[jnp.ndarray, ...] = ()
    v_flats: Tuple[jnp.ndarray, ...] = ()
    e_flats: Tuple[Tuple[jnp.ndarray, ...], ...] = ()
    form: Any = "momentum"               # static; "momentum" | ("lamb", ...)
    #                                    #         | ("chain", slots)

    def tree_flatten_with_keys(self):
        G = jax.tree_util.GetAttrKey
        return (((G("step"), self.step),
                 (G("p_flats"), tuple(self.p_flats)),
                 (G("u_flats"), tuple(self.u_flats)),
                 (G("m_flats"), tuple(self.m_flats)),
                 (G("v_flats"), tuple(self.v_flats)),
                 (G("e_flats"), tuple(tuple(e) for e in self.e_flats))),
                (self.layout, self.form))

    @classmethod
    def tree_unflatten(cls, aux, children):
        step, p_flats, u_flats, m_flats, v_flats, e_flats = children
        layout, form = aux
        return cls(step=step, p_flats=tuple(p_flats),
                   u_flats=tuple(u_flats), layout=layout,
                   m_flats=tuple(m_flats), v_flats=tuple(v_flats),
                   e_flats=tuple(tuple(e) for e in e_flats), form=form)

    @property
    def params(self) -> PyTree:
        return unflatten(self.p_flats, self.layout)

    @property
    def momentum(self) -> PyTree:
        return unflatten(self.u_flats, self.layout, keep_dtype=True)

    @property
    def moments(self) -> Tuple[PyTree, PyTree]:
        """(m, v) pytree views of the Adam moments (f32)."""
        return (unflatten(self.m_flats, self.layout, keep_dtype=True),
                unflatten(self.v_flats, self.layout, keep_dtype=True))

    @property
    def ema_views(self) -> Tuple[PyTree, ...]:
        """One f32 pytree view per resident EMA stage."""
        return tuple(unflatten(e, self.layout, keep_dtype=True)
                     for e in self.e_flats)


def place_flat_state(state: FlatOptState, mesh) -> FlatOptState:
    """Commit every flat buffer of a resident state to the mesh's 1-D
    block sharding (all axes) and replicate the step scalar.  No-op for
    ``mesh=None``.  Pure placement — values are untouched, so a placed
    state steps bitwise-identically to the single-device one."""
    if mesh is None:
        return state
    from jax.sharding import NamedSharding, PartitionSpec
    fs = flat_sharding(mesh)
    rep = NamedSharding(mesh, PartitionSpec())

    def put(flats):
        return tuple(jax.device_put(f, fs) for f in flats)
    return dataclasses.replace(
        state, step=jax.device_put(state.step, rep),
        p_flats=put(state.p_flats), u_flats=put(state.u_flats),
        m_flats=put(state.m_flats), v_flats=put(state.v_flats),
        e_flats=tuple(put(e) for e in state.e_flats))


def init_flat_state(params: PyTree, mesh=None) -> FlatOptState:
    """Build the resident state: params packed once, momentum zeros (f32).
    With a mesh, buckets are padded so they divide over all its devices
    and every buffer is committed to the 1-D block sharding."""
    layout = build_layout(params, shards=mesh_shards(mesh))
    state = FlatOptState(
        step=jnp.zeros((), jnp.int32),
        p_flats=tuple(flatten(params, layout)),
        u_flats=tuple(jnp.zeros((b.n_elems,), jnp.float32)
                      for b in layout.buckets),
        layout=layout)
    return place_flat_state(state, mesh)


def init_flat_adam_state(params: PyTree, form: Any = ("lamb", 0, 2),
                         mesh=None) -> FlatOptState:
    """Resident state for the Adam family: params packed once, both
    moments zeros (f32), no momentum slot.  ``form`` encodes the compiled
    chain's shape — ("lamb", n stateless transforms before scale_by_adam,
    n stateless transforms between it and scale_by_schedule) — which is
    exactly what ``optim.to_pytree`` needs to rebuild the interpreter's
    ``ChainOptState`` layout."""
    layout = build_layout(params, shards=mesh_shards(mesh))

    def zeros():
        # m and v must be DISTINCT buffers: sharing one zeros array
        # between them donates the same buffer twice under the donated
        # TrainState step (XLA rejects `f(donate(a), donate(a))`)
        return tuple(jnp.zeros((b.n_elems,), jnp.float32)
                     for b in layout.buckets)

    state = FlatOptState(
        step=jnp.zeros((), jnp.int32),
        p_flats=tuple(flatten(params, layout)),
        u_flats=(), layout=layout,
        m_flats=zeros(), v_flats=zeros(), form=form)
    return place_flat_state(state, mesh)


def init_ema_flats(params: PyTree, layout: TreeLayout, mesh=None
                   ) -> Tuple[jnp.ndarray, ...]:
    """Resident shadow-parameter buffers for ONE ``ema_params`` stage:
    the params packed to f32, copied so the EMA slot never aliases
    ``p_flats`` (double donation).  Matches the interpreter's
    ``jnp.array(p, dtype=f32, copy=True)`` init leaf-for-leaf."""
    flats = tuple(jnp.array(f, copy=True)
                  for f in flatten(params, layout, cast_to=jnp.float32))
    if mesh is not None:
        fs = flat_sharding(mesh)
        flats = tuple(jax.device_put(f, fs) for f in flats)
    return flats


def ema_flats_update(e_flats: Sequence[jnp.ndarray],
                     p_flats: Sequence[jnp.ndarray],
                     decay: float) -> Tuple[jnp.ndarray, ...]:
    """One EMA advance on the resident flats, elementwise (zero launches):
    ``e <- decay*e + (1-decay)*p`` with the PRE-step params, the
    interpreter's exact ``ema_params`` expression.  Zero padding maps to
    zero, so the buffers stay bit-equal to re-flattening the leafwise
    EMA."""
    return tuple(decay * e + (1.0 - decay) * pf.astype(jnp.float32)
                 for e, pf in zip(e_flats, p_flats))


@jax.tree_util.register_pytree_with_keys_class
@dataclasses.dataclass(frozen=True)
class FlatGrads:
    """Gradients already packed into the engine's per-bucket flat buffers
    (the layout rides along as static aux data).

    ``training/step.py`` accumulates micro-batch gradients directly in
    this form when the optimizer state is resident: each micro-batch
    flattens and adds into the per-bucket buffers inside the backward
    ``lax.scan``, so the data-parallel gradient reduction happens as one
    bucketed collective per micro-batch (overlapped with the next
    backward) instead of one monolithic tree reduce at the end.  The
    resident steps consume the buffers as-is — no re-flatten — and the
    values are bitwise what flattening the accumulated tree would give
    (same per-leaf casts and adds, zero pads stay zero)."""
    flats: Tuple[jnp.ndarray, ...]
    layout: TreeLayout

    def tree_flatten_with_keys(self):
        G = jax.tree_util.GetAttrKey
        return (((G("flats"), tuple(self.flats)),), (self.layout,))

    @classmethod
    def tree_unflatten(cls, aux, children):
        (flats,) = children
        return cls(flats=tuple(flats), layout=aux[0])

    @property
    def tree(self) -> PyTree:
        """Leaf-pytree view (sliced out of the buffers) for non-engine
        consumers."""
        return unflatten(self.flats, self.layout)


def _require_matching_layout(grads: FlatGrads, layout: TreeLayout) -> None:
    if grads.layout != layout:
        raise ValueError(
            "FlatGrads were packed with a different TreeLayout than the "
            "resident optimizer state carries (shard padding or bucketing "
            "mismatch); pack gradients with state.layout.")


def flat_squared_norm(flats: Sequence[jnp.ndarray],
                      layout: TreeLayout) -> jnp.ndarray:
    """Canonical squared norm straight off flat buffers, zero launches:
    CHUNK-row partials per bucket, per-segment pairwise folds, summed in
    ORIGINAL leaf order — bitwise equal to
    ``tree_squared_norm(unflatten(flats, layout))``.  (Folding a whole
    bucket at once would associate differently; per-segment is the
    canonical order.)"""
    parts = [jnp.sum(jnp.square(f.astype(jnp.float32).reshape(-1, CHUNK)),
                     axis=1) for f in flats]
    return sum(_leaf_values(parts, layout))


def flat_global_norm(flats: Sequence[jnp.ndarray],
                     layout: TreeLayout) -> jnp.ndarray:
    return jnp.sqrt(flat_squared_norm(flats, layout))


def _clip_flats_round(g_flats, layout: TreeLayout, clip: float,
                      backend: str, mesh=None):
    """``_clip_tree_round`` for gradients already in flat-buffer form:
    same raw-norm launch per bucket, same leafwise clip expression applied
    elementwise on the buffers (bitwise: the scale is one broadcast
    scalar, and zero pads map to zero).  Returns (clipped_flats,
    raw_gnorm)."""
    parts = [_chunk_sumsq(gf, backend=backend, mesh=mesh) for gf in g_flats]
    gnorm = jnp.sqrt(sum(_leaf_values(parts, layout)))
    scale = clip / jnp.maximum(gnorm, clip)
    clipped = [(gf.astype(jnp.float32) * scale).astype(gf.dtype)
               for gf in g_flats]
    return clipped, gnorm


def resident_step(kind: str, grads: PyTree, state: FlatOptState, *, lr,
                  beta: float, weight_decay: float = 0.0, eps: float = 1e-12,
                  trust: float = 0.001, clip: Optional[float] = None,
                  nesterov: bool = False,
                  materialize_view: bool = True, mesh=None
                  ) -> Tuple[Optional[PyTree], FlatOptState, dict]:
    """The resident fast path: flatten ONLY the gradients; params and
    momentum stay in the buffers carried by ``state``.  Returns
    ``(params_view, new_state, stats)`` where the pytree view is bit-equal
    to what the per-step path returns (buffer padding is invariantly
    zero, see module docstring).  ``materialize_view=False`` returns
    ``None`` instead of the view — the donation-safe ``TrainState`` path
    uses this so the step's OUTPUTS hold the parameters exactly once
    (in ``new_state.p_flats``), letting jit donation alias the update
    fully in place.  ``mesh``: run the kernel passes shard-wise over the
    mesh the state was placed on (see ``_engine_mesh`` for the
    fallback)."""
    layout = state.layout
    mesh = _engine_mesh(layout, mesh)
    stat_gnorm = None
    if isinstance(grads, FlatGrads):
        _require_matching_layout(grads, layout)
        g_flats = list(grads.flats)
        if clip is not None:
            g_flats, stat_gnorm = _clip_flats_round(
                g_flats, layout, float(clip), "pallas", mesh=mesh)
    else:
        check_grad_dtypes(grads, layout)
        if clip is not None:
            grads, stat_gnorm = _clip_tree_round(grads, layout, float(clip),
                                                 "pallas", mesh=mesh)
        g_flats = flatten(grads, layout)
    po, uo, stats = multi_tensor_step_flat(
        kind, layout, state.p_flats, g_flats, state.u_flats, lr=lr,
        beta=beta, weight_decay=weight_decay, eps=eps, trust=trust,
        nesterov=nesterov, stat_gnorm=stat_gnorm, mesh=mesh)
    new_state = FlatOptState(step=state.step + 1, p_flats=tuple(po),
                             u_flats=tuple(uo), layout=layout,
                             form=state.form)
    view = unflatten(po, layout) if materialize_view else None
    return view, new_state, stats


def resident_lamb_step(grads: PyTree, state: FlatOptState, *, lr, b1: float,
                       b2: float, eps: float, weight_decay: float = 0.0,
                       trust_eps: float = 0.0, clip: Optional[float] = None,
                       materialize_view: bool = True, mesh=None
                       ) -> Tuple[Optional[PyTree], FlatOptState, dict]:
    """Resident fast path for the Adam family: flatten ONLY the gradients;
    params and both moments stay in the buffers carried by ``state``.
    ``materialize_view=False`` skips the pytree params view (see
    ``resident_step``) for the donation-safe ``TrainState`` path."""
    layout = state.layout
    mesh = _engine_mesh(layout, mesh)
    stat_gnorm = None
    if isinstance(grads, FlatGrads):
        _require_matching_layout(grads, layout)
        g_flats = list(grads.flats)
        if clip is not None:
            g_flats, stat_gnorm = _clip_flats_round(
                g_flats, layout, float(clip), "pallas", mesh=mesh)
    else:
        check_grad_dtypes(grads, layout)
        if clip is not None:
            grads, stat_gnorm = _clip_tree_round(grads, layout, float(clip),
                                                 "pallas", mesh=mesh)
        g_flats = flatten(grads, layout)
    po, mo, vo, stats = multi_tensor_lamb_step_flat(
        layout, state.p_flats, g_flats, state.m_flats, state.v_flats,
        count=state.step, lr=lr, b1=b1, b2=b2, eps=eps,
        weight_decay=weight_decay, trust_eps=trust_eps,
        stat_gnorm=stat_gnorm, mesh=mesh)
    new_state = FlatOptState(step=state.step + 1, p_flats=tuple(po),
                             u_flats=(), layout=layout, m_flats=tuple(mo),
                             v_flats=tuple(vo), form=state.form)
    view = unflatten(po, layout) if materialize_view else None
    return view, new_state, stats


def check_grad_dtypes(grads: PyTree, layout: TreeLayout) -> None:
    """The engine buckets by PARAM dtype, so gradients must match their
    parameter's dtype leaf-for-leaf (what training/step.py's accumulator
    produces).  A silent cast here (e.g. fp32 grads over bf16 params)
    would quietly diverge from the jnp path's promote-to-f32 semantics."""
    leaves = jax.tree_util.tree_leaves(grads)
    assert len(leaves) == layout.n_leaves, (len(leaves), layout.n_leaves)
    for b in layout.buckets:
        for s in b.segments:
            if leaves[s.index].dtype != s.dtype:
                raise ValueError(
                    f"multi_tensor fused path requires grads to match the "
                    f"parameter dtype per leaf; got grad "
                    f"{leaves[s.index].dtype} for param {s.dtype}. Cast the "
                    f"gradients (or use the jnp path, fused=None, which "
                    f"promotes to f32).")


# ---------------------------------------------------------------------------
# the engine step
# ---------------------------------------------------------------------------

KINDS = ("sngm_global", "sngm_per_tensor", "msgd", "lars")


def _leaf_values(parts_per_bucket, layout: TreeLayout) -> List[jnp.ndarray]:
    """Fold per-chunk partials to one scalar per LEAF, indexed in the
    original leaf order (the order every canonical reduction sums in)."""
    out = [None] * layout.n_leaves
    for b, parts in zip(layout.buckets, parts_per_bucket):
        for s, v in zip(b.segments, _segment_sums(parts, b)):
            out[s.index] = v
    return out


def _clip_tree_round(grads: PyTree, layout: TreeLayout, clip: float,
                     backend: str, cast_to: Optional[Any] = None, mesh=None):
    """Round 0 of a clip-prefixed chain: pack the raw gradients and reduce
    their global norm in one ``chunk_sumsq`` launch per bucket, then apply
    the interpreter's exact ``clip_by_global_norm`` expression LEAF-WISE on
    the gradient tree.  Clipping at the tree level (rather than on the
    flat buffer) keeps the downstream kernels' input producers — a
    pad/concat of per-leaf casts — the same graph shape as the un-clipped
    chains', which is what keeps their last-ulp contraction behaviour
    under XLA fusion (and hence bit-identity against the per-leaf jnp
    reference) stable.  Costs one extra gradient packing per step.
    ``cast_to`` overrides the packing dtype for the norm round — the
    segment planner passes f32 when the clip sits MID-chain on updates an
    earlier stage already promoted (packing them at the bucket dtype
    would silently round).  Returns (clipped_grads, raw_gnorm)."""
    parts = [_chunk_sumsq(gf, backend=backend, mesh=mesh)
             for gf in flatten(grads, layout, cast_to=cast_to)]
    gnorm = jnp.sqrt(sum(_leaf_values(parts, layout)))
    scale = clip / jnp.maximum(gnorm, clip)
    clipped = jax.tree.map(
        lambda g: (g.astype(jnp.float32) * scale).astype(g.dtype), grads)
    return clipped, gnorm


def multi_tensor_step(kind: str, params: PyTree, grads: PyTree,
                      momentum: PyTree, *, lr, beta: float,
                      weight_decay: float = 0.0, eps: float = 1e-12,
                      trust: float = 0.001, clip: Optional[float] = None,
                      nesterov: bool = False,
                      backend: str = "pallas") -> Tuple[PyTree, PyTree, dict]:
    """One fused optimizer step over the whole tree (pytree in/out).

    Packs params+grads+momentum into flat buffers, runs the flat engine
    core, and unpacks the results.  Returns (new_params, new_momentum,
    stats) with the same stats keys as the jnp paths in ``core.optim``
    ({grad_norm, lr, update_norm}), all bit-identical to them.
    ``backend``: "pallas" (interpret mode off-TPU) or "ref" (pure-jnp
    oracle, zero kernel launches).  Steady-state training should prefer
    the resident form (``FlatOptState`` + ``multi_tensor_step_flat``),
    which packs only the gradients.
    """
    layout = build_layout(params)
    check_grad_dtypes(grads, layout)
    stat_gnorm = None
    if clip is not None:
        grads, stat_gnorm = _clip_tree_round(grads, layout, float(clip),
                                             backend)
    p_flats = flatten(params, layout)
    g_flats = flatten(grads, layout)
    u_flats = flatten(momentum, layout, cast_to=jnp.float32)
    po_flats, uo_flats, stats = multi_tensor_step_flat(
        kind, layout, p_flats, g_flats, u_flats, lr=lr, beta=beta,
        weight_decay=weight_decay, eps=eps, trust=trust, nesterov=nesterov,
        stat_gnorm=stat_gnorm, backend=backend)
    return (unflatten(po_flats, layout),
            unflatten(uo_flats, layout, keep_dtype=True), stats)


def multi_tensor_step_flat(kind: str, layout: TreeLayout,
                           p_flats: Sequence[jnp.ndarray],
                           g_flats: Sequence[jnp.ndarray],
                           u_flats: Sequence[jnp.ndarray], *, lr, beta: float,
                           weight_decay: float = 0.0, eps: float = 1e-12,
                           trust: float = 0.001, nesterov: bool = False,
                           suffix_clip: Optional[float] = None,
                           stat_gnorm: Optional[jnp.ndarray] = None,
                           backend: str = "pallas", mesh=None
                           ) -> Tuple[List[jnp.ndarray], List[jnp.ndarray],
                                      dict]:
    """The engine core: flat-in/flat-out, one (p, g, u) buffer triple per
    layout bucket.  Returns (new_p_flats, new_u_flats, stats) without ever
    materializing a pytree — the resident path calls this with the buffers
    held in ``FlatOptState`` and only the gradients freshly packed.

    Clip-prefixed chains are compiled by the TREE-level wrappers
    (``multi_tensor_step`` / ``resident_step``): they run the raw-norm
    round (``_clip_tree_round``), pass the CLIPPED gradients in here, and
    supply ``stat_gnorm`` — the raw norm the interpreter's clip stage
    reported.  For msgd a supplied ``stat_gnorm`` also skips pass 1
    entirely (its coefficients are constant and its chain has no
    norm-emitting stage after the clip, so the decayed norm is never
    needed); sngm/lars ignore ``stat_gnorm`` for stats because their
    chains re-report the norm downstream of the clip.

    ``nesterov=True`` runs the look-ahead momentum variant of the update
    kernel (``trace(nesterov=True)`` fused).  ``suffix_clip`` compiles a
    TRAILING ``clip_by_global_norm`` (the segment planner's
    clip-at-suffix position): the update pass defers the parameter write
    and emits the effective f32 direction, whose lr-scaled norm feeds
    the interpreter's clip expression, and a third ``scale_apply``
    launch applies the clipped step — one extra launch, agreement with
    the interpreter at the documented "close" tolerance (the clip norm
    associates ``lr * ||u||`` where the interpreter folds
    ``||lr * u||``, the same lr-product association LARS already has).
    """
    if kind not in KINDS:
        raise ValueError(f"unknown kind {kind!r}; expected one of {KINDS}")
    wd = float(weight_decay)

    # ---- pass 1: squared-norm partials per bucket -------------------------
    # sngm/msgd norm the coupled-decayed gradient (g + wd*w, computed inside
    # the kernel); lars needs raw ||g|| and ||w|| per tensor instead.
    # msgd's constant coefficients need no norm at all — pass 1 runs there
    # only for the grad_norm stat, so it is skipped whenever a later (or
    # earlier) clip stage supplies that stat instead.
    g_parts = []
    w_parts = []
    if not (kind == "msgd" and (stat_gnorm is not None
                                or suffix_clip is not None)):
        for b, pf, gf in zip(layout.buckets, p_flats, g_flats):
            if kind == "lars":
                g_parts.append(_chunk_sumsq(gf, backend=backend, mesh=mesh))
                w_parts.append(_chunk_sumsq(pf, backend=backend, mesh=mesh))
            else:
                g_parts.append(_chunk_sumsq(gf, pf, wd=wd, backend=backend,
                                            mesh=mesh))

    # per-segment and global sums, in ORIGINAL leaf order so the sequential
    # accumulation matches tree_squared_norm exactly
    if g_parts:
        gsq_by_leaf = _leaf_values(g_parts, layout)
        gnorm = jnp.sqrt(sum(gsq_by_leaf))
    else:
        gsq_by_leaf, gnorm = None, stat_gnorm
    wsq_by_leaf = _leaf_values(w_parts, layout) if kind == "lars" else None

    # ---- coefficients ----------------------------------------------------
    lr = jnp.asarray(lr, jnp.float32)
    cast_g_first = False
    if kind == "sngm_global":
        inv = 1.0 / (gnorm + eps)
        a_chunks = [jnp.full((b.n_chunks,), inv, jnp.float32)
                    for b in layout.buckets]
        c = lr
    elif kind == "sngm_per_tensor":
        a_chunks = [
            _per_chunk(b, [1.0 / (jnp.sqrt(gsq_by_leaf[s.index]) + eps)
                           for s in b.segments])
            for b in layout.buckets]
        c = lr
    elif kind == "msgd":
        a_chunks = [jnp.ones((b.n_chunks,), jnp.float32)
                    for b in layout.buckets]
        c = lr
    else:  # lars
        def local_lr(s):
            wn = jnp.sqrt(wsq_by_leaf[s.index])
            gn = jnp.sqrt(gsq_by_leaf[s.index])
            local = trust * wn / (gn + wd * wn + eps)
            return lr * jnp.where(wn > 0, local, 1.0)
        a_chunks = [_per_chunk(b, [local_lr(s) for s in b.segments])
                    for b in layout.buckets]
        c = jnp.float32(1.0)
        cast_g_first = True

    # ---- pass 2: fused momentum + apply per bucket -----------------------
    po_flats, uo_flats, usq_parts = [], [], []
    apply_now = suffix_clip is None
    for b, pf, gf, uf, ac in zip(layout.buckets, p_flats, g_flats, u_flats,
                                 a_chunks):
        po, uo, usq = _fused_update(pf, gf, uf, ac, c, beta=beta, wd=wd,
                                    cast_g_first=cast_g_first,
                                    nesterov=nesterov, apply=apply_now,
                                    backend=backend, mesh=mesh)
        po_flats.append(po)
        uo_flats.append(uo)
        usq_parts.append(usq)

    unorm = jnp.sqrt(sum(_leaf_values(usq_parts, layout)))
    if suffix_clip is None:
        stats = {"grad_norm": gnorm, "lr": lr, "update_norm": unorm}
        return po_flats, uo_flats, stats

    # ---- pass 3 (suffix clip): rescale the deferred direction + apply ----
    # With apply=False pass 2 returned the effective f32 direction in
    # ``po_flats``; the interpreter's trailing clip sees the lr-scaled
    # step, so its norm is lr * ||direction|| (up to the documented
    # lr-product association) and its scale feeds one scale_apply launch:
    # ``p <- p - c*(cscale * direction)`` with c carrying the schedule lr.
    snorm = lr * unorm
    cscale = suffix_clip / jnp.maximum(snorm, suffix_clip)
    out_flats, ssq_parts = [], []
    for b, pf, eff in zip(layout.buckets, p_flats, po_flats):
        ac = jnp.full((b.n_chunks,), cscale, jnp.float32)
        po, ssq = _scale_apply(pf, eff, ac, lr, backend=backend, mesh=mesh)
        out_flats.append(po)
        ssq_parts.append(ssq)
    del ssq_parts   # the chain's update_norm stat is sched's (pre-clip)
    # stats mirror the interpreter's left-to-right merge: the trailing
    # clip re-reports grad_norm as the norm of ITS input (the lr-scaled
    # update), overriding any earlier reporter; update_norm stays the
    # schedule stage's pre-scaling report.
    stats = {"grad_norm": snorm, "lr": lr, "update_norm": unorm}
    return out_flats, uo_flats, stats


# ---------------------------------------------------------------------------
# the LAMB/Adam engine step
# ---------------------------------------------------------------------------

def multi_tensor_lamb_step(params: PyTree, grads: PyTree, count, m: PyTree,
                           v: PyTree, *, lr, b1: float, b2: float,
                           eps: float, weight_decay: float = 0.0,
                           trust_eps: float = 0.0,
                           clip: Optional[float] = None,
                           backend: str = "pallas"
                           ) -> Tuple[PyTree, PyTree, PyTree, dict]:
    """One fused LAMB step, pytree in/out (the per-step packing path).
    ``count`` is the Adam step counter BEFORE this step (bias correction
    uses t = count + 1).  Returns (new_params, new_m, new_v, stats)."""
    layout = build_layout(params)
    check_grad_dtypes(grads, layout)
    stat_gnorm = None
    if clip is not None:
        grads, stat_gnorm = _clip_tree_round(grads, layout, float(clip),
                                             backend)
    p_flats = flatten(params, layout)
    g_flats = flatten(grads, layout)
    m_flats = flatten(m, layout, cast_to=jnp.float32)
    v_flats = flatten(v, layout, cast_to=jnp.float32)
    po, mo, vo, stats = multi_tensor_lamb_step_flat(
        layout, p_flats, g_flats, m_flats, v_flats, count=count, lr=lr,
        b1=b1, b2=b2, eps=eps, weight_decay=weight_decay,
        trust_eps=trust_eps, stat_gnorm=stat_gnorm, backend=backend)
    return (unflatten(po, layout), unflatten(mo, layout, keep_dtype=True),
            unflatten(vo, layout, keep_dtype=True), stats)


def multi_tensor_lamb_step_flat(layout: TreeLayout,
                                p_flats: Sequence[jnp.ndarray],
                                g_flats: Sequence[jnp.ndarray],
                                m_flats: Sequence[jnp.ndarray],
                                v_flats: Sequence[jnp.ndarray], *, count,
                                lr, b1: float, b2: float, eps: float,
                                weight_decay: float = 0.0,
                                trust_eps: float = 0.0,
                                stat_gnorm: Optional[jnp.ndarray] = None,
                                backend: str = "pallas", mesh=None
                                ) -> Tuple[List[jnp.ndarray],
                                           List[jnp.ndarray],
                                           List[jnp.ndarray], dict]:
    """The LAMB engine core: two launches per bucket (Adam-moment pass +
    apply pass); the tree-level wrappers add the round-0 raw-norm launch
    and pass clipped gradients + ``stat_gnorm`` for clip-prefixed chains.

    The Adam pass advances both f32 moments and forms the bias-corrected,
    decoupled-decayed direction in one kernel, emitting the per-chunk
    sumsq partials of direction / params / grads; the host folds them
    per segment (canonical order) into the LAMB trust ratios, and the
    ``scale_apply`` pass applies the per-segment ratio and the lr — so
    ``p <- p - lr*(ratio*u)`` and the ``update_norm`` partials come out
    of the same launch, with no momentum operand read.  ``eps`` must
    be > 0 (zero-pad invariance; the chain compiler enforces this).
    Numerics mirror the chain interpreter's
    ``scale_by_adam -> add_decayed_weights -> scale_by_trust_ratio ->
    scale_by_schedule`` stages expression-for-expression.
    """
    assert eps > 0.0, "fused lamb requires adam eps > 0 (pad invariance)"
    wd = float(weight_decay)
    t = jnp.asarray(count).astype(jnp.float32) + 1.0
    bc1 = 1 - b1 ** t          # the interpreter's exact bias-correction
    bc2 = 1 - b2 ** t

    # ---- pass 1: fused Adam moments + direction + norm partials ----------
    mo_flats, vo_flats, u_flats = [], [], []
    usq_parts, psq_parts, gsq_parts = [], [], []
    for pf, gf, mf, vf in zip(p_flats, g_flats, m_flats, v_flats):
        mo, vo, ud, usq, psq, gsq = _adam_update(
            pf, gf, mf, vf, bc1, bc2, b1=b1, b2=b2, eps=eps,
            wd=wd, backend=backend, mesh=mesh)
        mo_flats.append(mo)
        vo_flats.append(vo)
        u_flats.append(ud)
        usq_parts.append(usq)
        psq_parts.append(psq)
        gsq_parts.append(gsq)

    # grad_norm stat: the interpreter chain reports the raw-gradient norm
    # (the clip stage's report, or the fallback default) — never the
    # decayed one.  For clip chains the raw norm arrives as stat_gnorm.
    if stat_gnorm is not None:
        gnorm = stat_gnorm
    else:
        gnorm = jnp.sqrt(sum(_leaf_values(gsq_parts, layout)))

    # ---- per-segment trust ratios ----------------------------------------
    usq_by_leaf = _leaf_values(usq_parts, layout)
    wsq_by_leaf = _leaf_values(psq_parts, layout)

    def ratio(s):
        wn = jnp.sqrt(wsq_by_leaf[s.index])
        un = jnp.sqrt(usq_by_leaf[s.index])
        return jnp.where((wn > 0) & (un > 0), wn / (un + trust_eps), 1.0)

    a_chunks = [_per_chunk(b, [ratio(s) for s in b.segments])
                for b in layout.buckets]

    # ---- pass 2: trust-scale + apply -------------------------------------
    lr = jnp.asarray(lr, jnp.float32)
    po_flats, ssq_parts = [], []
    for pf, ud, ac in zip(p_flats, u_flats, a_chunks):
        po, ssq = _scale_apply(pf, ud, ac, lr, backend=backend, mesh=mesh)
        po_flats.append(po)
        ssq_parts.append(ssq)

    stats = {"grad_norm": gnorm, "lr": lr,
             "update_norm": jnp.sqrt(sum(_leaf_values(ssq_parts, layout)))}
    return po_flats, mo_flats, vo_flats, stats
