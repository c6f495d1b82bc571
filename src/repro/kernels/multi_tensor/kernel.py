"""Multi-tensor fused optimizer kernels — Pallas TPU.

The per-leaf kernels (``fused_sngm``, ``fused_lars``) launch one kernel
per parameter tensor, so optimizer overhead grows with tree size.  These
kernels instead operate on ONE dtype-bucketed flat buffer holding every
leaf (built by ``repro.core.multi_tensor``), giving O(1) launches per
optimizer step:

  pass 1  ``chunk_sumsq``   — squared-norm partials, one f32 per CHUNK-sized
                              row of the buffer.  Segment (= per-tensor) and
                              global norms are tiny reductions over these
                              partials; because every segment starts on a
                              CHUNK boundary the per-segment results are
                              bit-identical to a per-leaf chunked reduction.
  pass 2  ``fused_update``  — momentum + apply for the whole buffer, with a
                              per-chunk normalization coefficient ``a`` (a
                              broadcast scalar for SNGM's global norm, a
                              per-segment scalar for SNGM[per_tensor]/LARS,
                              1 for MSGD).  Also emits sumsq partials of the
                              new momentum so ``update_norm`` stats need no
                              third pass.

One (a, c, wd, beta, cast_g_first) parameterization covers the four
momentum optimizers:

    u_new = beta * u + a * decay(g, p)        decay = g + wd*p (coupled wd)
    p_new = (p - c * u_new).astype(p.dtype)

    sngm             a = 1/(||g_dec||+eps)  broadcast        c = lr
    sngm[per_tensor] a = 1/(||g_dec||_seg+eps) per segment   c = lr
    lars             a = lr * local_lr_seg  per segment      c = 1
    msgd             a = 1                                   c = lr

LAMB/Adam adds two kernels.  ``adam_update`` (one launch per bucket)
advances both fp32 Adam moments, materializes the bias-corrected (and
decoupled-weight-decayed) direction ``u``, and emits per-chunk sumsq
partials of ``u``, ``p`` and ``g`` — so the host can form the
per-segment trust ratios and the stats norms without extra passes.
``scale_apply`` (the second launch) scales by the per-segment ratio and
applies, emitting the scaled direction's sumsq partials (the
``update_norm`` stat) — no momentum operand, no dead outputs:

    u     = m_hat / (sqrt(v_hat) + eps) + wd * p     (adam_update)
    p_new = (p - lr * (ratio_seg * u)).astype(p.dtype)  (scale_apply)

Clip-prefixed chains add a raw-norm ``chunk_sumsq`` round BEFORE these
kernels; the host then rescales the flat gradient buffers with the
interpreter's exact clip expression (a fused jnp elementwise op, zero
extra launches) and runs the unchanged passes on the clipped buffers —
see ``core.multi_tensor``.  The kernels themselves are clip-agnostic,
which keeps their op graphs (and therefore their last-ulp contraction
behaviour under XLA fusion) byte-stable across all chain variants.

Layout: buffers are viewed as (n_chunks, CHUNK) rows; the grid walks
tiles of TILE_ROWS rows.  Coefficients/partials ride in (TILE_ROWS, 1)
blocks, which Mosaic lowers with a masked relayout (the TPU compile of
every kernel is kept in tests/test_tpu_compile.py).

In-place residency: the update passes declare ``input_output_aliases``
(p->p_new, u->u_new, m->m_new, v->v_new), so when the caller's buffers
are donated (the ``TrainState`` train step jitted with
``donate_argnums``) XLA updates the resident flat buffers in place
instead of double-buffering them; when an input is still live elsewhere
XLA inserts the copy itself, so numerics and non-donated callers are
unaffected.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

CHUNK = 1024        # elements per row == per-coefficient granularity
TILE_ROWS = 64      # rows per grid step: 64*1024*4B = 256 KiB f32 per operand
TILE = TILE_ROWS * CHUNK


def _tile_rows(n_chunks: int, interpret: bool) -> int:
    """Grid tiling: TILE_ROWS rows per step on TPU (VMEM-bounded); the whole
    buffer in ONE grid step under interpret mode, where each extra grid step
    costs a full-buffer dynamic-update-slice instead of a VMEM tile swap.
    Per-row math is identical either way, so numerics don't change."""
    return n_chunks if interpret else TILE_ROWS


def _decay(g, p, *, wd: float, cast_g_first: bool):
    """g + wd*p in f32, replicating the reference paths' cast order exactly:
    SNGM/MSGD decay in the gradient dtype then cast (``_decayed``); LARS
    casts the gradient first.  wd == 0 must be a true no-op (not ``+0*p``,
    which flips the sign of -0.0)."""
    if wd == 0.0:
        return g.astype(jnp.float32)
    if cast_g_first:
        return g.astype(jnp.float32) + wd * p
    return (g + wd * p).astype(jnp.float32)


# ---------------------------------------------------------------------------
# pass 1: squared-norm partials
# ---------------------------------------------------------------------------

def _sumsq_raw_kernel(x_ref, o_ref):
    x = x_ref[...].astype(jnp.float32)
    o_ref[...] = jnp.sum(jnp.square(x), axis=1, keepdims=True)


def _sumsq_decayed_kernel(g_ref, p_ref, o_ref, *, wd):
    ge = _decay(g_ref[...], p_ref[...], wd=wd, cast_g_first=False)
    o_ref[...] = jnp.sum(jnp.square(ge), axis=1, keepdims=True)


@functools.partial(jax.jit, static_argnames=("wd", "interpret"))
def chunk_sumsq(x, p=None, *, wd: float = 0.0, interpret: bool = False):
    """Per-chunk sum of squares of ``x`` (or of ``x + wd*p`` when ``p`` is
    given).  ``x``: flat (n,) with n % TILE == 0.  Returns f32 (n/CHUNK,)."""
    assert x.ndim == 1 and x.size % TILE == 0, x.shape
    x2 = x.reshape(-1, CHUNK)
    n_chunks = x2.shape[0]
    rows = _tile_rows(n_chunks, interpret)
    grid = n_chunks // rows
    tile = pl.BlockSpec((rows, CHUNK), lambda i: (i, 0))
    otile = pl.BlockSpec((rows, 1), lambda i: (i, 0))
    out_shape = jax.ShapeDtypeStruct((n_chunks, 1), jnp.float32)
    if p is None or wd == 0.0:
        out = pl.pallas_call(
            _sumsq_raw_kernel, grid=(grid,),
            in_specs=[tile], out_specs=otile, out_shape=out_shape,
            interpret=interpret,
        )(x2)
    else:
        out = pl.pallas_call(
            functools.partial(_sumsq_decayed_kernel, wd=wd), grid=(grid,),
            in_specs=[tile, tile], out_specs=otile, out_shape=out_shape,
            interpret=interpret,
        )(x2, p.reshape(-1, CHUNK))
    return out.ravel()


# ---------------------------------------------------------------------------
# pass 2: fused momentum + apply
# ---------------------------------------------------------------------------

def _update_kernel(c_ref, a_ref, p_ref, g_ref, u_ref,
                   po_ref, uo_ref, usq_ref, *, beta, wd, cast_g_first,
                   nesterov, apply):
    ge = _decay(g_ref[...], p_ref[...], wd=wd, cast_g_first=cast_g_first)
    a = a_ref[...]                       # (TILE_ROWS, 1), broadcasts per row
    u_new = beta * u_ref[...] + a * ge
    # nesterov look-ahead: the applied direction re-adds the scaled
    # gradient on top of the NEW momentum (the interpreter's second
    # tree.map in ``trace(nesterov=True)``); the stored slot stays u_new
    out = beta * u_new + a * ge if nesterov else u_new
    uo_ref[...] = u_new
    if apply:
        po_ref[...] = (p_ref[...] - c_ref[0] * out).astype(po_ref.dtype)
    else:
        # deferred apply (a suffix stage — e.g. a trailing clip — still
        # reads the effective direction): first output carries ``out``
        po_ref[...] = out
    usq_ref[...] = jnp.sum(jnp.square(out), axis=1, keepdims=True)


@functools.partial(jax.jit, static_argnames=("beta", "wd", "cast_g_first",
                                             "nesterov", "apply",
                                             "interpret"))
def fused_update(p, g, u, a_chunk, c, *, beta: float, wd: float,
                 cast_g_first: bool = False, nesterov: bool = False,
                 apply: bool = True, interpret: bool = False):
    """Whole-bucket fused optimizer update.

    p: flat (n,) in the bucket dtype; g: flat (n,) gradient buffer (bucket
    dtype, or f32 for the LAMB apply where ``g`` carries the pre-formed
    Adam direction); u: flat (n,) f32; a_chunk: (n/CHUNK,) f32 per-chunk
    coefficient; c: scalar.
    Returns (p_new [p.dtype], u_new [f32], u_sumsq_partials [(n/CHUNK,) f32]).
    ``p -> p_new`` and ``u -> u_new`` are declared input/output aliases,
    so donated resident buffers update in place.

    ``nesterov=True`` applies (and reports in the sumsq partials) the
    look-ahead direction ``beta*u_new + a*ge`` while still storing
    ``u_new`` in the momentum slot — the fused form of
    ``trace(nesterov=True)``.  ``apply=False`` skips the parameter write:
    the first output instead carries the f32 effective direction (for a
    suffix stage such as a trailing clip, which rescales it and applies
    via ``scale_apply``); ``p`` is NOT aliased in that mode since a later
    pass still reads it.
    """
    assert p.ndim == 1 and p.size % TILE == 0, p.shape
    n_chunks = p.size // CHUNK
    assert a_chunk.shape == (n_chunks,), a_chunk.shape
    rows = _tile_rows(n_chunks, interpret)
    grid = n_chunks // rows
    tile = pl.BlockSpec((rows, CHUNK), lambda i: (i, 0))
    ctile = pl.BlockSpec((rows, 1), lambda i: (i, 0))
    cs = jnp.reshape(c, (1,)).astype(jnp.float32)
    po_dtype = p.dtype if apply else jnp.float32
    aliases = {2: 0, 4: 1} if apply else {4: 1}
    po, uo, usq = pl.pallas_call(
        functools.partial(_update_kernel, beta=beta, wd=wd,
                          cast_g_first=cast_g_first, nesterov=nesterov,
                          apply=apply),
        grid=(grid,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  ctile, tile, tile, tile],
        out_specs=[tile, tile, ctile],
        out_shape=[jax.ShapeDtypeStruct((n_chunks, CHUNK), po_dtype),
                   jax.ShapeDtypeStruct((n_chunks, CHUNK), jnp.float32),
                   jax.ShapeDtypeStruct((n_chunks, 1), jnp.float32)],
        input_output_aliases=aliases,          # p -> p_new, u -> u_new
        interpret=interpret,
    )(cs, a_chunk.reshape(-1, 1), p.reshape(-1, CHUNK),
      g.reshape(-1, CHUNK), u.reshape(-1, CHUNK))
    return po.ravel(), uo.ravel(), usq.ravel()


def _scale_apply_kernel(c_ref, a_ref, p_ref, g_ref, po_ref, ssq_ref):
    """Per-chunk-scaled apply (LAMB's second launch): the expression
    mirrors the interpreter's scale_by_trust_ratio (ratio * u) ->
    scale_by_schedule (lr * .) -> apply (w - .) stages exactly."""
    s = a_ref[...] * g_ref[...]          # (TILE_ROWS, 1) a broadcasts
    po_ref[...] = (p_ref[...] - c_ref[0] * s).astype(po_ref.dtype)
    ssq_ref[...] = jnp.sum(jnp.square(s), axis=1, keepdims=True)


@functools.partial(jax.jit, static_argnames=("interpret",))
def scale_apply(p, g, a_chunk, c, *, interpret: bool = False):
    """Whole-bucket scale-and-apply: ``p <- (p - c * (a * g)).astype``.

    p: flat (n,) in the bucket dtype; g: flat (n,) f32 direction;
    a_chunk: (n/CHUNK,) f32 per-chunk coefficient; c: scalar.
    Returns (p_new [p.dtype], s_sumsq_partials [(n/CHUNK,) f32]) where
    s = a * g is the scaled direction (its folded norm is LAMB's
    pre-lr ``update_norm`` stat).  ``p -> p_new`` is an input/output
    alias, so a donated resident buffer updates in place.
    """
    assert p.ndim == 1 and p.size % TILE == 0, p.shape
    n_chunks = p.size // CHUNK
    assert a_chunk.shape == (n_chunks,), a_chunk.shape
    rows = _tile_rows(n_chunks, interpret)
    grid = n_chunks // rows
    tile = pl.BlockSpec((rows, CHUNK), lambda i: (i, 0))
    ctile = pl.BlockSpec((rows, 1), lambda i: (i, 0))
    cs = jnp.reshape(c, (1,)).astype(jnp.float32)
    po, ssq = pl.pallas_call(
        _scale_apply_kernel,
        grid=(grid,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  ctile, tile, tile],
        out_specs=[tile, ctile],
        out_shape=[jax.ShapeDtypeStruct((n_chunks, CHUNK), p.dtype),
                   jax.ShapeDtypeStruct((n_chunks, 1), jnp.float32)],
        input_output_aliases={2: 0},           # p -> p_new
        interpret=interpret,
    )(cs, a_chunk.reshape(-1, 1), p.reshape(-1, CHUNK),
      g.reshape(-1, CHUNK))
    return po.ravel(), ssq.ravel()


# ---------------------------------------------------------------------------
# LAMB/Adam pass: moments + bias-corrected direction + norm partials
# ---------------------------------------------------------------------------

def _adam_kernel(b_ref, p_ref, g_ref, m_ref, v_ref,
                 mo_ref, vo_ref, uo_ref, usq_ref, psq_ref, gsq_ref,
                 *, b1, b2, eps, wd):
    """One fused pass: advance both Adam moments, form the bias-corrected
    (decoupled-decayed) direction, and emit the three per-chunk sumsq
    partial sets (direction, params, grads) the host needs for the
    trust ratios and the stats norms.  Every expression mirrors the chain
    interpreter's ``scale_by_adam`` / ``add_decayed_weights`` stages,
    including the cast orders (wd*p in the param dtype, then f32 add)."""
    g = g_ref[...]
    g32 = g.astype(jnp.float32)
    gsq_ref[...] = jnp.sum(jnp.square(g32), axis=1, keepdims=True)
    m_new = b1 * m_ref[...] + (1 - b1) * g32
    v_new = b2 * v_ref[...] + (1 - b2) * jnp.square(g32)
    u = (m_new / b_ref[0]) / (jnp.sqrt(v_new / b_ref[1]) + eps)
    if wd != 0.0:
        u = u + wd * p_ref[...]
    mo_ref[...] = m_new
    vo_ref[...] = v_new
    uo_ref[...] = u
    usq_ref[...] = jnp.sum(jnp.square(u), axis=1, keepdims=True)
    psq_ref[...] = jnp.sum(jnp.square(p_ref[...].astype(jnp.float32)),
                           axis=1, keepdims=True)


@functools.partial(jax.jit, static_argnames=("b1", "b2", "eps", "wd",
                                             "interpret"))
def adam_update(p, g, m, v, bc1, bc2, *, b1: float, b2: float,
                eps: float, wd: float = 0.0, interpret: bool = False):
    """Whole-bucket fused Adam-moment pass (LAMB's first launch).

    p, g: flat (n,) in the bucket dtype; m, v: flat (n,) f32 moments;
    bc1, bc2: scalar bias corrections ``1 - b^t`` (computed host-side so
    they match the interpreter's expression exactly).  ``eps`` must be
    > 0 so zero padding maps to zero direction (0 / (0 + eps)); the
    chain compiler refuses eps <= 0.
    Returns (m_new, v_new, u [all f32 flat], and f32 (n/CHUNK,) sumsq
    partials of u, p, g).  ``m -> m_new`` and ``v -> v_new`` are
    input/output aliases, so donated resident moment buffers update in
    place (``p`` cannot alias — the apply pass still reads it).
    """
    assert p.ndim == 1 and p.size % TILE == 0, p.shape
    n_chunks = p.size // CHUNK
    rows = _tile_rows(n_chunks, interpret)
    grid = n_chunks // rows
    tile = pl.BlockSpec((rows, CHUNK), lambda i: (i, 0))
    ctile = pl.BlockSpec((rows, 1), lambda i: (i, 0))
    bs = jnp.stack([jnp.asarray(bc1, jnp.float32),
                    jnp.asarray(bc2, jnp.float32)])
    flat = jax.ShapeDtypeStruct((n_chunks, CHUNK), jnp.float32)
    part = jax.ShapeDtypeStruct((n_chunks, 1), jnp.float32)
    mo, vo, uo, usq, psq, gsq = pl.pallas_call(
        functools.partial(_adam_kernel, b1=b1, b2=b2, eps=eps, wd=wd),
        grid=(grid,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  tile, tile, tile, tile],
        out_specs=[tile, tile, tile, ctile, ctile, ctile],
        out_shape=[flat, flat, flat, part, part, part],
        input_output_aliases={3: 0, 4: 1},     # m -> m_new, v -> v_new
        interpret=interpret,
    )(bs, p.reshape(-1, CHUNK), g.reshape(-1, CHUNK),
      m.reshape(-1, CHUNK), v.reshape(-1, CHUNK))
    return (mo.ravel(), vo.ravel(), uo.ravel(),
            usq.ravel(), psq.ravel(), gsq.ravel())
