"""The plain reference in ``jax.numpy``, float32 at ``highest`` matmul
precision: the arithmetic every architecture shares (RMSNorm, rotary
positions in the split-half convention, causal attention), the loss,
gradients and the SNGM update (Algorithm 1 of arXiv:2007.13985).  Each
architecture's layers (``hidden``) are in its module in ``bench/archs/``,
found by ``m["arch"]``.  It imports nothing of the program and reads
only the weights that ``bench/weights.py`` makes from the seed.

``prec`` selects the arithmetic of every matrix product: "fp32" is the
reference; "fp8" (e4m3, one scale per tensor) is the control: the same
mathematics in the precision below the bf16 the configurations compute
in, whose readings a sound limit must fail.  Its backward pass rounds
the cotangents to fp8 too."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bench import common

F8 = jnp.float8_e4m3fn
F8_MAX = 448.0


def _round_f8(x):
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
    return (x / s).astype(F8).astype(jnp.float32) * s


@jax.custom_vjp
def _q8(x):
    return _round_f8(x)


def _q8_fwd(x):
    return _round_f8(x), None


def _q8_bwd(_, g):
    return (_round_f8(g),)


_q8.defvjp(_q8_fwd, _q8_bwd)


def _q(x, prec):
    return _q8(x) if prec == "fp8" else x


def mm(spec_, a, b, prec):
    return jnp.einsum(spec_, _q(a, prec), _q(b, prec),
                      precision=jax.lax.Precision.HIGHEST)


def rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def rope(x, pos, theta):
    """x (B, S, n, hd); pos (S,).  Split-half rotation."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None].astype(jnp.float32) * freqs            # (S, half)
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


Q_BLOCK = 512


def attention(q, k, v, prec):
    """Causal attention, queries in blocks so that no (S, S) score tensor
    of every head lives at once.  q (B,S,H,hd), k/v (B,S,K,hd)."""
    B, S, H, hd = q.shape
    G = H // k.shape[2]
    k = jnp.repeat(k, G, axis=2)
    v = jnp.repeat(v, G, axis=2)
    outs = []
    for s0 in range(0, S, Q_BLOCK):
        qb = q[:, s0:s0 + Q_BLOCK]
        n = qb.shape[1]
        s = mm("bqhd,bthd->bhqt", qb, k, prec) * hd ** -0.5
        ok = (jnp.arange(S)[None, :] <= (s0 + jnp.arange(n))[:, None])
        s = jnp.where(ok[None, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        outs.append(mm("bhqt,bthd->bqhd", p, v, prec))
    return jnp.concatenate(outs, axis=1)


def logits(w, h, prec="fp32"):
    return mm("bsd,dv->bsv", h, w["unembed"], prec)


def loss_sum(w, tokens, m, prec="fp32"):
    """Summed next-token cross-entropy over positions 0..S-2 of every row
    (the last position has no target)."""
    h, aux = common.arch_named(m["arch"]).hidden(w, tokens, m, prec)
    h = h[:, :-1]
    tgt = tokens[:, 1:]
    # the architecture's own term of the mean token loss over these rows
    # (an MoE's router balance loss) weighs as many positions
    total = aux * h.shape[0] * h.shape[1]
    for s0 in range(0, h.shape[1], Q_BLOCK):
        lg = logits(w, h[:, s0:s0 + Q_BLOCK], prec)
        lse = jax.nn.logsumexp(lg, axis=-1)
        picked = jnp.take_along_axis(lg, tgt[:, s0:s0 + Q_BLOCK, None],
                                     -1)[..., 0]
        total = total + jnp.sum(lse - picked)
    return total


@functools.partial(jax.jit, static_argnames=("mkey", "prec"))
def _loss_grad_rows(w, tokens, mkey, prec):
    m = dict(mkey)
    return jax.value_and_grad(loss_sum)(w, tokens, m, prec)


@functools.partial(jax.jit, donate_argnums=(0,))
def _add(a, b):
    return jax.tree.map(jnp.add, a, b)


def loss_and_grad(w, tokens, m, prec="fp32", rows=1):
    """Mean loss and its gradient over a batch, ``rows`` rows at a time
    (the sum is taken block by block, then divided once)."""
    mkey = tuple(sorted(m.items()))
    B, S = tokens.shape
    total, g = 0.0, None
    for r0 in range(0, B, rows):
        l, gb = _loss_grad_rows(w, tokens[r0:r0 + rows], mkey, prec)
        total = total + float(l)
        g = gb if g is None else _add(g, gb)
        del gb
    n = B * (S - 1)
    return total / n, jax.tree.map(lambda x: x / n, g)


@functools.partial(jax.jit, static_argnames=("beta", "wd", "eps"),
                   donate_argnums=(0, 2))
def sngm_step(w, g, u, lr, *, beta, wd, eps=1e-12):
    """u <- beta u + d/||d||, w <- w - lr u, with d = g + wd w and one
    Euclidean norm over every weight.  Returns (w, u, ||d||)."""
    d = jax.tree.map(lambda gi, wi: gi + wd * wi, g, w)
    norm = jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree.leaves(d)))
    u = jax.tree.map(lambda ui, di: beta * ui + di / (norm + eps), u, d)
    w = jax.tree.map(lambda wi, ui: wi - lr * ui, w, u)
    return w, u, norm


def poly_power(lr0, total_steps, power, t):
    frac = min(max(t / total_steps, 0.0), 1.0)
    return lr0 * (1.0 - frac) ** power


def token_gaps(w, prompt, served, m, ctx, n_max, prec="fp32"):
    """For each served token, how far its reference logit lies below the
    reference's best at that position (0 when it is the best); for a
    control precision, the same gap of the token that the control puts
    first.  prompt (S0,), served (n,) int arrays; the sequence is padded
    to ``ctx + n_max`` and ``n_max`` positions are read, so one program serves
    every request of a cell (causal attention: padding after a position
    never reaches it)."""
    import numpy as np
    S0, n = len(prompt), len(served)
    toks = np.zeros((ctx + n_max,), np.int32)
    toks[:S0] = prompt
    toks[S0:S0 + n - 1] = served[:-1]
    mkey = tuple(sorted(m.items()))
    lg = _positions_logits(w, jnp.asarray(toks), jnp.int32(S0 - 1), n_max,
                           mkey, "fp32")[:n]
    best = jnp.max(lg, axis=-1)
    if prec == "fp32":
        pick = jnp.asarray(served, jnp.int32)
    else:
        lc = _positions_logits(w, jnp.asarray(toks), jnp.int32(S0 - 1),
                               n_max, mkey, prec)[:n]
        pick = jnp.argmax(lc, axis=-1)
    got = jnp.take_along_axis(lg, pick[:, None], -1)[:, 0]
    return np.asarray(best - got)


@functools.partial(jax.jit, static_argnames=("n_max", "mkey", "prec"))
def _positions_logits(w, toks, first, n_max, mkey, prec):
    m = dict(mkey)
    h = common.arch_named(m["arch"]).hidden(w, toks[None], m, prec)[0][0]
    h = jax.lax.dynamic_slice_in_dim(h, first, n_max, axis=0)
    return logits(w, h[None], prec)[0]
