"""Mixture-of-Experts FFN: drop-free on one device, capacity-based
dispatch across an expert-parallel mesh.

* one device (``mesh=None``): the layer holds a share of the routed
  experts (``MoEConfig.first_held``/``n_held``; all of them by default),
  routes over all of them, and computes its held experts' part for every
  assignment routed to them, dropping nothing: assignments are sorted by
  held expert and run through grouped matrix products
  (``kernels/grouped_matmul``) whose rows are the held assignments plus
  at most one tile of padding per expert.
* ``a2a``       — expert parallelism: experts shard over the ``ep`` axis
                  (data axis); tokens are scattered into per-(expert)
                  capacity buffers and exchanged with ``lax.all_to_all``
                  inside ``shard_map`` (DeepSeek-style EP).  Used when the
                  local token count is large (train / prefill).
* ``allreduce`` — for tiny token counts (decode, batch <= mesh): tokens
                  are replicated, every shard computes its local experts
                  and the contributions are psum'd over the ep axis.  No
                  all_to_all, no divisibility constraint on batch.

The routing and its bookkeeping run under ``jax.named_scope("moe_route")``,
the grouped products under ``"moe_experts"``.  The reference oracle
(``moe_ref``) computes every held expert densely on every token — exact,
drop-free; tests compare against it.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.kernels import grouped_matmul
from repro.models.param import ParamDef
from repro.models.runtime import Runtime
from repro.models import layers

MIN_CAPACITY = 4


# ---------------------------------------------------------------------------
# params
# ---------------------------------------------------------------------------

def moe_defs(cfg: ModelConfig):
    m = cfg.moe
    d, E, f = cfg.d_model, m.held, m.d_expert
    defs = {
        # scores every expert, held or not; tiny: replicate
        "router": ParamDef((d, m.n_experts), (None, None), scale=0.02),
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

def router_logits(x, router):
    """(T, d) -> (T, E) in float32 at full precision (DeepSeek-V2 scores
    its experts in fp32)."""
    return jnp.dot(x.astype(jnp.float32), router.astype(jnp.float32),
                   precision=jax.lax.Precision.HIGHEST)


def route(logits: jnp.ndarray, cfg: ModelConfig, seq: int = 0
          ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """logits (T,E) -> weights (T,k), ids (T,k), aux_loss (scalar).

    The balance loss is E * sum_e f_e P_e, f_e the share of assignments
    to e and P_e its mean probability: over all T tokens, or with
    ``seq_aux`` per sequence of ``seq`` tokens and then the mean over
    sequences."""
    m = cfg.moe
    lf = logits.astype(jnp.float32)
    if m.router_mode == "softmax_topk":      # DeepSeek-V2
        probs = jax.nn.softmax(lf, axis=-1)
        weights, ids = jax.lax.top_k(probs, m.top_k)
    else:                                     # Mixtral / Jamba: topk then softmax
        top_logits, ids = jax.lax.top_k(lf, m.top_k)
        weights = jax.nn.softmax(top_logits, axis=-1)
        probs = jax.nn.softmax(lf, axis=-1)
    T, E = logits.shape
    dispatch = jnp.zeros_like(probs).at[jnp.arange(T)[:, None], ids].add(1.0)
    if m.seq_aux:
        rows = T // seq
        frac = dispatch.reshape(rows, seq, E).mean(axis=1) / m.top_k
        p_mean = probs.reshape(rows, seq, E).mean(axis=1)
        aux = E * jnp.mean(jnp.sum(frac * p_mean, axis=-1))
    else:
        # switch-style: E * sum_e (frac dispatched_e * mean prob_e)
        frac = dispatch.mean(axis=0) / m.top_k
        aux = E * jnp.sum(frac * probs.mean(axis=0))
    return weights, ids, aux


def _held_key(ids, cfg: ModelConfig):
    """(T, k) expert ids -> (T*k,) each assignment's held expert, counted
    from the first held, or n_held where the expert is not held."""
    m = cfg.moe
    local = ids.reshape(-1) - m.first_held
    return jnp.where((local >= 0) & (local < m.held), local, m.held)


def held_counts(ids, cfg: ModelConfig):
    """(T, k) expert ids -> (n_held,) assignments to each held expert."""
    m = cfg.moe
    return jnp.zeros((m.held + 1,), jnp.int32).at[_held_key(ids, cfg)].add(
        1)[:m.held]


def load_stats(counts):
    """The step's counters of one layer: assignments to held experts,
    and the largest held expert's load over the mean held load."""
    total = jnp.sum(counts)
    mean = total.astype(jnp.float32) / counts.shape[0]
    return {"moe_held_rows": total,
            "moe_load_max_over_mean":
                jnp.max(counts).astype(jnp.float32) / jnp.maximum(mean, 1.0)}


def zero_stats():
    return load_stats(jnp.zeros((1,), jnp.int32))


# ---------------------------------------------------------------------------
# one device: the held experts, drop-free
# ---------------------------------------------------------------------------

def _held_experts(x, weights, ids, counts, wg, wu, wd, cfg: ModelConfig):
    """The held experts' part of the routed output, (T, d) float32, for
    every assignment routed to them.  Assignments are sorted by held
    expert (the rest last); every token names distinct experts, so at
    most min(k, n_held) of its assignments are held, and a buffer of
    T * min(k, n_held) rows (rounded to whole row tiles) holds them all."""
    m = cfg.moe
    T, d = x.shape
    k, E = m.top_k, m.held
    cdt = jnp.dtype(cfg.compute_dtype)
    n = T * min(k, E)
    tm = grouped_matmul.row_tile(n)
    rows = -(-n // tm) * tm
    with jax.named_scope("moe_route"):
        order = jnp.argsort(_held_key(ids, cfg), stable=True)
        order = jnp.pad(order[:n], (0, rows - n))
        valid = jnp.arange(rows) < jnp.sum(counts)
        src = order // k                                        # token of each row
        xs = jnp.where(valid[:, None], x[src].astype(cdt), 0)
    with jax.named_scope("moe_experts"):
        g = grouped_matmul.gmm(xs, wg.astype(cdt), counts)
        u = grouped_matmul.gmm(xs, wu.astype(cdt), counts)
    h = jax.nn.silu(g) * u
    with jax.named_scope("moe_experts"):
        y = grouped_matmul.gmm(h, wd.astype(cdt), counts)
    with jax.named_scope("moe_route"):
        w = jnp.where(valid, weights.reshape(-1)[order], 0.0)
        y = jnp.where(valid[:, None], y.astype(jnp.float32), 0.0) * w[:, None]
        return jnp.zeros((T, d), jnp.float32).at[src].add(y)


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
              ep_axis, model_axis, n_model: int, mode: str, seq: int):
    """Runs per device (or directly when unsharded). x: (T_loc, d)."""
    m = cfg.moe
    T, d = x.shape
    E = m.n_experts
    E_loc = E // n_ep
    k = m.top_k

    logits = x.astype(jnp.float32) @ router.astype(jnp.float32)
    weights, ids, aux = route(logits, cfg, seq)
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
    return out.astype(x.dtype), aux.reshape(1), held_counts(ids, cfg)[None]


# ---------------------------------------------------------------------------
# public apply
# ---------------------------------------------------------------------------

def moe_apply(p, x, cfg: ModelConfig, rt: Runtime):
    """x: (B, S, d) -> (out (B,S,d), aux loss scalar, stats): stats are
    the layer's counters (``load_stats``)."""
    B, S, d = x.shape
    m = cfg.moe
    T_global = B * S
    xf = x.reshape(T_global, d)

    if rt.mesh is None:
        with jax.named_scope("moe_route"):
            weights, ids, aux = route(router_logits(xf, p["router"]), cfg, S)
            counts = held_counts(ids, cfg)
        y = _held_experts(xf, weights, ids, counts, p["wg"], p["wu"],
                          p["wd"], cfg).reshape(B, S, d).astype(x.dtype)
    else:
        if m.held != m.n_experts:
            raise ValueError(
                f"{cfg.name}: a share of the experts ({m.held} of "
                f"{m.n_experts}) runs on one device only (mesh=None); the "
                f"expert-parallel paths hold every expert")
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
                       model_axis=rt.model_axis, n_model=n_model, mode=mode,
                       seq=S)
        wspec = P(rt.ep_axis, None, rt.model_axis)
        # check_vma=False: in allreduce mode y is replicated by the body's
        # own psum over the ep and model axes, and the router aux and the
        # routing's counts (capacity drops aside; one row per token shard)
        # are the same on every model shard; out_specs state both, the
        # checker cannot infer them through the capacity scatter.
        shard_spec = P(rt.data_axes if a2a_ok else None)
        y, aux, counts = jax.shard_map(
            body, mesh=rt.mesh,
            in_specs=(P(None, None), wspec, wspec,
                      P(rt.ep_axis, rt.model_axis, None), tok_spec),
            out_specs=(tok_spec, shard_spec, shard_spec),
            check_vma=False,
        )(p["router"], p["wg"], p["wu"], p["wd"], xf)
        y = y.reshape(B, S, d)
        aux = jnp.mean(aux)
        counts = jnp.sum(counts, axis=0)

    if m.n_shared:
        y = y + layers.mlp(p["shared"], x, cfg)
    return y, aux * m.router_aux_weight, load_stats(counts)


# ---------------------------------------------------------------------------
# dense oracle (tests): every expert on every token, no capacity drops
# ---------------------------------------------------------------------------

def moe_ref(p, x, cfg: ModelConfig):
    B, S, d = x.shape
    m = cfg.moe
    xf = x.reshape(-1, d)
    weights, ids, aux = route(router_logits(xf, p["router"]), cfg, S)
    cdt = jnp.dtype(cfg.compute_dtype)
    xs = xf.astype(cdt)
    h = jax.nn.silu(jnp.einsum("td,edf->tef", xs, p["wg"].astype(cdt)))
    h = h * jnp.einsum("td,edf->tef", xs, p["wu"].astype(cdt))
    y_all = jnp.einsum("tef,efd->ted", h, p["wd"].astype(cdt))   # (T,E,d)
    # an assignment to an expert not held adds nothing
    local = ids - m.first_held
    held = (local >= 0) & (local < m.held)
    sel = jnp.take_along_axis(y_all, jnp.clip(local, 0, m.held - 1)[:, :, None],
                              axis=1) * held[:, :, None]        # (T,k,d)
    y = jnp.sum(weights[..., None].astype(sel.dtype) * sel, axis=1)
    y = y.reshape(B, S, d).astype(x.dtype)
    if m.n_shared:
        y = y + layers.mlp(p["shared"], x, cfg)
    return y, aux * m.router_aux_weight
