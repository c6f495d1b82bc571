"""Mixture-of-Experts FFN with capacity-based dispatch.

Two distribution modes, one math:

* ``a2a``       — expert parallelism: experts shard over the ``ep`` axis
                  (data axis); tokens are scattered into per-(expert)
                  capacity buffers and exchanged with ``lax.all_to_all``
                  inside ``shard_map`` (DeepSeek-style EP).  Used when the
                  local token count is large (train / prefill).
* ``allreduce`` — for tiny token counts (decode, batch <= mesh): tokens
                  are replicated, every shard computes its local experts
                  and the contributions are psum'd over the ep axis.  No
                  all_to_all, no divisibility constraint on batch.

The reference oracle (``moe_ref``) computes every expert densely on every
token — exact, drop-free; tests compare against it with a high capacity
factor.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models.param import ParamDef
from repro.models.runtime import Runtime
from repro.models import layers

MIN_CAPACITY = 4


# ---------------------------------------------------------------------------
# params
# ---------------------------------------------------------------------------

def moe_defs(cfg: ModelConfig):
    m = cfg.moe
    d, E, f = cfg.d_model, m.n_experts, m.d_expert
    defs = {
        "router": ParamDef((d, E), (None, None), scale=0.02),  # tiny: replicate
        "wg": ParamDef((E, d, f), ("experts", "embed", "ffn")),
        "wu": ParamDef((E, d, f), ("experts", "embed", "ffn")),
        "wd": ParamDef((E, f, d), ("experts", "ffn", "embed")),
    }
    if m.n_shared:
        defs["shared"] = layers.mlp_defs(cfg, m.n_shared * f, gated=True)
    return defs


# ---------------------------------------------------------------------------
# routing
# ---------------------------------------------------------------------------

def route(logits: jnp.ndarray, cfg: ModelConfig) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """logits (T,E) -> weights (T,k), ids (T,k), aux_loss (scalar)."""
    m = cfg.moe
    lf = logits.astype(jnp.float32)
    if m.router_mode == "softmax_topk":      # DeepSeek-V2
        probs = jax.nn.softmax(lf, axis=-1)
        weights, ids = jax.lax.top_k(probs, m.top_k)
    else:                                     # Mixtral / Jamba: topk then softmax
        top_logits, ids = jax.lax.top_k(lf, m.top_k)
        weights = jax.nn.softmax(top_logits, axis=-1)
        probs = jax.nn.softmax(lf, axis=-1)
    # switch-style load-balance loss: E * sum_e (frac dispatched_e * mean prob_e)
    T = logits.shape[0]
    dispatch = jnp.zeros_like(probs).at[jnp.arange(T)[:, None], ids].add(1.0)
    frac = dispatch.mean(axis=0) / m.top_k
    aux = m.n_experts * jnp.sum(frac * probs.mean(axis=0))
    return weights, ids, aux


# ---------------------------------------------------------------------------
# per-shard body
# ---------------------------------------------------------------------------

def _positions(flat_ids: jnp.ndarray, E: int, cap: int):
    """Position of each assignment within its expert's capacity buffer."""
    oh = jax.nn.one_hot(flat_ids, E, dtype=jnp.int32)          # (A,E)
    pos = (jnp.cumsum(oh, axis=0) - 1)                         # running count
    pos = jnp.take_along_axis(pos, flat_ids[:, None], axis=1)[:, 0]
    keep = pos < cap
    return pos, keep


def _expert_ffn(cfg: ModelConfig, wg, wu, wd, xs, n_model: int, model_axis):
    """xs: (E_loc, C, d); weights sharded on ffn over the model axis."""
    cdt = jnp.dtype(cfg.compute_dtype)
    xs = xs.astype(cdt)
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xs, wg.astype(cdt)))
    h = h * jnp.einsum("ecd,edf->ecf", xs, wu.astype(cdt))
    y = jnp.einsum("ecf,efd->ecd", h, wd.astype(cdt))
    if n_model > 1:  # partial sum over the sharded ffn dim
        y = jax.lax.psum(y, model_axis)
    return y


def _moe_body(router, wg, wu, wd, x, *, cfg: ModelConfig, n_ep: int,
              ep_axis, model_axis, n_model: int, mode: str):
    """Runs per device (or directly when unsharded). x: (T_loc, d)."""
    m = cfg.moe
    T, d = x.shape
    E = m.n_experts
    E_loc = E // n_ep
    k = m.top_k

    logits = x.astype(jnp.float32) @ router.astype(jnp.float32)
    weights, ids, aux = route(logits, cfg)
    flat_ids = ids.reshape(-1)                                  # (T*k,)
    x_rep = jnp.repeat(x, k, axis=0)                            # (T*k, d)

    cap = max(MIN_CAPACITY, math.ceil(T * k / E * m.capacity_factor))
    pos, keep = _positions(flat_ids, E, cap)

    if mode == "allreduce":
        # tiny T: tokens replicated; each shard computes its local experts
        # for every token and the results are psum'd over the ep axis.
        idx = jax.lax.axis_index(ep_axis) if n_ep > 1 else 0
        local = (flat_ids // E_loc) == idx
        buf = jnp.zeros((E, cap, d), x.dtype)
        buf = buf.at[flat_ids, pos].add(jnp.where((keep & local)[:, None], x_rep, 0))
        buf_loc = jax.lax.dynamic_slice(buf, (idx * E_loc, 0, 0), (E_loc, cap, d))
        y_loc = _expert_ffn(cfg, wg, wu, wd, buf_loc, n_model, model_axis)
        y_full = jnp.zeros((E, cap, d), y_loc.dtype)
        y_full = jax.lax.dynamic_update_slice(y_full, y_loc, (idx * E_loc, 0, 0))
        if n_ep > 1:
            y_full = jax.lax.psum(y_full, ep_axis)
        rows = y_full[flat_ids, pos] * keep[:, None]
    else:  # mode == "a2a": expert parallelism with all_to_all
        buf = jnp.zeros((E, cap, d), x.dtype)
        buf = buf.at[flat_ids, pos].add(jnp.where(keep[:, None], x_rep, 0))
        if n_ep > 1:
            buf = buf.reshape(n_ep, E_loc, cap, d)
            buf = jax.lax.all_to_all(buf, ep_axis, 0, 0)        # (n_ep src, E_loc, cap, d)
            xs = jnp.moveaxis(buf, 0, 1).reshape(E_loc, n_ep * cap, d)
        else:
            xs = buf
        y = _expert_ffn(cfg, wg, wu, wd, xs, n_model, model_axis)
        if n_ep > 1:
            y = jnp.moveaxis(y.reshape(E_loc, n_ep, cap, d), 1, 0)
            y = jax.lax.all_to_all(y, ep_axis, 0, 0)            # back to source
            y = y.reshape(E, cap, d)
        rows = y[flat_ids, pos] * keep[:, None]

    rows = rows.reshape(T, k, d)
    out = jnp.sum(weights[..., None].astype(rows.dtype) * rows, axis=1)
    return out.astype(x.dtype), aux.reshape(1)


# ---------------------------------------------------------------------------
# public apply
# ---------------------------------------------------------------------------

def moe_apply(p, x, cfg: ModelConfig, rt: Runtime):
    """x: (B, S, d) -> (out (B,S,d), aux loss scalar)."""
    B, S, d = x.shape
    m = cfg.moe
    T_global = B * S

    if rt.mesh is None:
        body = partial(_moe_body, cfg=cfg, n_ep=1, ep_axis=None,
                       model_axis=None, n_model=1, mode="a2a")
        y, aux = body(p["router"], p["wg"], p["wu"], p["wd"], x.reshape(T_global, d))
        y = y.reshape(B, S, d)
        aux = aux[0]
    else:
        n_ep = rt.mesh.shape[rt.ep_axis]
        n_model = rt.mesh.shape[rt.model_axis]
        n_batch_shards = 1
        for a in rt.data_axes:
            n_batch_shards *= rt.mesh.shape[a]
        # token-sharded a2a when the flattened token dim divides evenly and
        # is large; replicated allreduce mode otherwise (tiny decode batches)
        a2a_ok = (B % n_batch_shards == 0)
        mode = "a2a" if a2a_ok else "allreduce"
        tok_spec = P(rt.data_axes, None) if a2a_ok else P(None, None)
        body = partial(_moe_body, cfg=cfg, n_ep=n_ep, ep_axis=rt.ep_axis,
                       model_axis=rt.model_axis, n_model=n_model, mode=mode)
        wspec = P(rt.ep_axis, None, rt.model_axis)
        # check_vma=False: in allreduce mode y is replicated by the body's
        # own psum over the ep and model axes, and the router aux is the
        # same on every model shard; out_specs state both, the checker
        # cannot infer them through the capacity scatter.
        y, aux = jax.shard_map(
            body, mesh=rt.mesh,
            in_specs=(P(None, None), wspec, wspec,
                      P(rt.ep_axis, rt.model_axis, None), tok_spec),
            out_specs=(tok_spec, P(rt.data_axes if a2a_ok else None)),
            check_vma=False,
        )(p["router"], p["wg"], p["wu"], p["wd"], x.reshape(T_global, d))
        y = y.reshape(B, S, d)
        aux = jnp.mean(aux)

    if m.n_shared:
        y = y + layers.mlp(p["shared"], x, cfg)
    return y, aux * m.router_aux_weight


# ---------------------------------------------------------------------------
# dense oracle (tests): every expert on every token, no capacity drops
# ---------------------------------------------------------------------------

def moe_ref(p, x, cfg: ModelConfig):
    B, S, d = x.shape
    m = cfg.moe
    xf = x.reshape(-1, d)
    logits = xf.astype(jnp.float32) @ p["router"].astype(jnp.float32)
    weights, ids, aux = route(logits, cfg)
    cdt = jnp.dtype(cfg.compute_dtype)
    xs = xf.astype(cdt)
    h = jax.nn.silu(jnp.einsum("td,edf->tef", xs, p["wg"].astype(cdt)))
    h = h * jnp.einsum("td,edf->tef", xs, p["wu"].astype(cdt))
    y_all = jnp.einsum("tef,efd->ted", h, p["wd"].astype(cdt))   # (T,E,d)
    sel = jnp.take_along_axis(y_all, ids[:, :, None], axis=1)    # (T,k,d)
    y = jnp.sum(weights[..., None].astype(sel.dtype) * sel, axis=1)
    y = y.reshape(B, S, d).astype(x.dtype)
    if m.n_shared:
        y = y + layers.mlp(p["shared"], x, cfg)
    return y, aux * m.router_aux_weight
