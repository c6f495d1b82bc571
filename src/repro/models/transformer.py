"""Model assembly: decoder-only stacks (dense / MoE / SSM / hybrid / VLM)
and the Whisper encoder-decoder, built from the layer library.

Compile tractability (DESIGN.md §7): the repeating layer *period* is
stacked and iterated with ``lax.scan`` — HLO size is O(period), not
O(n_layers).  Heterogeneous patterns (jamba 1:7+MoE, gemma2 local/global)
unroll the period inside the scan body.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import LayerSpec, ModelConfig, layer_pattern
from repro.models import layers, mamba, moe
from repro.models.param import ParamDef, stack
from repro.models.runtime import Runtime


# ---------------------------------------------------------------------------
# parameter trees
# ---------------------------------------------------------------------------

def _gated(cfg: ModelConfig) -> bool:
    return not cfg.is_encoder_decoder     # whisper: 2-matrix GELU MLP


def block_defs(cfg: ModelConfig, spec: LayerSpec, with_cross: bool = False):
    d: Dict[str, Any] = {}
    if spec.mixer in ("attn", "attn_local"):
        d["attn_norm"] = layers.norm_defs(cfg)
        d["attn"] = layers.attention_defs(cfg)
    else:
        d["mixer_norm"] = layers.norm_defs(cfg)
        d["mamba"] = mamba.mamba_defs(cfg)
    if with_cross:
        d["cross_norm"] = layers.norm_defs(cfg)
        d["cross"] = layers.attention_defs(cfg, cross=True)
    if spec.ffn == "dense":
        d["ffn_norm"] = layers.norm_defs(cfg)
        d["ffn"] = layers.mlp_defs(cfg, cfg.d_ff, gated=_gated(cfg))
    elif spec.ffn == "moe":
        d["ffn_norm"] = layers.norm_defs(cfg)
        d["moe"] = moe.moe_defs(cfg)
    return d


def model_defs(cfg: ModelConfig):
    prefix, period, n_periods = layer_pattern(cfg)
    cross = cfg.is_encoder_decoder
    defs: Dict[str, Any] = {
        # the table's vocab dim stays unsharded ("vocab_table" rule): XLA
        # partitions token-gathers from a vocab-sharded table by full
        # replication (involuntary remat) — d_model sharding is enough.
        "embed": ParamDef((cfg.vocab_size, cfg.d_model), ("vocab_table", "embed"),
                          "embed", scale=0.02),
        "final_norm": layers.norm_defs(cfg),
    }
    if prefix:
        defs["prefix"] = {f"P{i}": block_defs(cfg, s, cross) for i, s in enumerate(prefix)}
    defs["blocks"] = stack({f"L{i}": block_defs(cfg, s, cross)
                            for i, s in enumerate(period)}, n_periods)
    if not cfg.tie_embeddings:
        defs["unembed"] = ParamDef((cfg.d_model, cfg.vocab_size),
                                   ("embed", "vocab"), scale=0.02)
    if cfg.is_encoder_decoder:
        enc_spec = LayerSpec("attn", "dense")
        defs["encoder"] = {
            "blocks": stack({"L0": block_defs(cfg, enc_spec)}, cfg.n_encoder_layers),
            "final_norm": layers.norm_defs(cfg),
        }
    if cfg.param_dtype != "float32":
        # mixed-precision storage (jamba-398B: fp32 state = 4.8 TB exceeds a
        # 256-chip pod's 4 TB HBM — params/grads bf16, momentum fp32)
        import jax.numpy as _jnp
        from repro.models.param import ParamDef as _PD, is_def as _is_def
        dt = _jnp.dtype(cfg.param_dtype)
        defs = jax.tree_util.tree_map(
            lambda d: d._replace(dtype=dt), defs, is_leaf=_is_def)
    return defs


# ---------------------------------------------------------------------------
# cross attention helper (whisper decoder)
# ---------------------------------------------------------------------------

def _cross_kv(p, enc, cfg):
    cdt = jnp.dtype(cfg.compute_dtype)
    ck = jnp.einsum("btd,dkh->btkh", enc.astype(cdt), p["wk"].astype(cdt))
    cv = jnp.einsum("btd,dkh->btkh", enc.astype(cdt), p["wv"].astype(cdt))
    return ck, cv


def _cross_attend(p, x, cfg, ck, cv):
    cdt = jnp.dtype(cfg.compute_dtype)
    H, K, hd = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    B, S = x.shape[:2]
    q = jnp.einsum("bsd,dkh->bskh", x.astype(cdt), p["wq"].astype(cdt))
    o = layers._sdpa_seq(q, ck.astype(cdt), cv.astype(cdt),
                         False, 0, 0.0, hd ** -0.5, bf16_mm=cfg.sdpa_bf16)
    return jnp.einsum("bshd,hdo->bso", o, p["wo"].astype(cdt))


# ---------------------------------------------------------------------------
# one block
# ---------------------------------------------------------------------------

def block_apply(p, spec: LayerSpec, h, cfg: ModelConfig, rt: Runtime, *,
                pos, cache=None, build_cache: bool, encoder_out=None):
    """Returns (h, new_cache_or_None, aux): aux is ``_zero_aux(cfg)``'s
    dict, the block's router balance loss and MoE counters."""
    new_cache: Dict[str, Any] = {}
    aux = _zero_aux(cfg)

    from jax.ad_checkpoint import checkpoint_name

    def _name_tp(x):     # mark post-TP-collective activations for remat
        return checkpoint_name(x, "tp_out") if rt.remat_policy == "save_tp" else x

    if spec.mixer in ("attn", "attn_local"):
        with jax.named_scope("attention"):
            xin = layers.apply_norm(cfg, p["attn_norm"], h)
            fn = (layers.mla_attention if cfg.mla is not None
                  else layers.gqa_attention)
            a, c = fn(p["attn"], xin, cfg, local=(spec.mixer == "attn_local"),
                      pos=pos, cache=(cache or {}).get("attn"))
            h = h + _name_tp(a).astype(h.dtype)
        if build_cache:
            new_cache["attn"] = c
    else:
        xin = layers.apply_norm(cfg, p["mixer_norm"], h)
        a, c = mamba.mamba_block(p["mamba"], xin, cfg,
                                 cache=(cache or {}).get("mamba"), pos=pos)
        h = h + a.astype(h.dtype)
        if build_cache:
            new_cache["mamba"] = c

    if "cross" in p and encoder_out is not None or (cache and "cross" in cache):
        xin = layers.apply_norm(cfg, p["cross_norm"], h)
        if cache and "cross" in cache:
            ck, cv = cache["cross"]["ck"], cache["cross"]["cv"]
        else:
            ck, cv = _cross_kv(p["cross"], encoder_out, cfg)
        h = h + _cross_attend(p["cross"], xin, cfg, ck, cv).astype(h.dtype)
        if build_cache:
            new_cache["cross"] = {"ck": ck, "cv": cv}

    if spec.ffn == "dense":
        with jax.named_scope("mlp"):
            xin = layers.apply_norm(cfg, p["ffn_norm"], h)
            h = h + _name_tp(layers.mlp(p["ffn"], xin, cfg)).astype(h.dtype)
    elif spec.ffn == "moe":
        with jax.named_scope("mlp"):
            xin = layers.apply_norm(cfg, p["ffn_norm"], h)
            y, a_loss, stats = moe.moe_apply(p["moe"], xin, cfg, rt)
            h = h + _name_tp(y).astype(h.dtype)
        aux = {"aux_loss": a_loss, **stats}

    return h, (new_cache if build_cache else None), aux


def _zero_aux(cfg: ModelConfig):
    """What a block adds up besides its states: the router balance loss,
    and for a model with MoE layers their counters (``moe.load_stats``)."""
    aux = {"aux_loss": jnp.zeros((), jnp.float32)}
    if cfg.moe is not None:
        aux.update(moe.zero_stats())
    return aux


def _add(a, b):
    return jax.tree.map(jnp.add, a, b)


# ---------------------------------------------------------------------------
# whisper encoder
# ---------------------------------------------------------------------------

def _remat_group(n_periods: int) -> int:
    """Group size for sqrt-remat: ~sqrt(n), only worth it for deep stacks."""
    if n_periods < 12:
        return 1
    import math
    return max(2, round(math.sqrt(n_periods)))


def _sinusoid(T: int, d: int):
    pos = jnp.arange(T, dtype=jnp.float32)[:, None]
    i = jnp.arange(d // 2, dtype=jnp.float32)[None, :]
    ang = pos / (10000.0 ** (2 * i / d))
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


def encode(params, cfg: ModelConfig, rt: Runtime, encoder_embeds):
    """Stub-frontend encoder: (B, T, d) frame embeddings -> (B, T, d)."""
    h = encoder_embeds.astype(jnp.dtype(cfg.compute_dtype))
    h = h + _sinusoid(h.shape[1], cfg.d_model).astype(h.dtype)
    spec = LayerSpec("attn", "dense")

    def body(h, p_layer):
        xin = layers.apply_norm(cfg, p_layer["L0"]["attn_norm"], h)
        a, _ = layers.gqa_attention(p_layer["L0"]["attn"], xin, cfg, local=False,
                                    pos=jnp.arange(h.shape[1])[None], causal=False)
        h = h + a.astype(h.dtype)
        xin = layers.apply_norm(cfg, p_layer["L0"]["ffn_norm"], h)
        h = h + layers.mlp(p_layer["L0"]["ffn"], xin, cfg).astype(h.dtype)
        return h, None

    if rt.remat:
        body = jax.checkpoint(body)
    h, _ = jax.lax.scan(body, h, params["encoder"]["blocks"])
    return layers.apply_norm(cfg, params["encoder"]["final_norm"], h)


# ---------------------------------------------------------------------------
# full forward
# ---------------------------------------------------------------------------

def forward(params, cfg: ModelConfig, rt: Runtime, tokens, *,
            mode: str = "train", cache=None, pos=None, encoder_embeds=None,
            last_pos=None):
    """mode: "train" | "prefill" | "decode".

    train:   tokens (B,S)             -> (h, None, aux)
    prefill: tokens (B,S)             -> (logits, cache, aux)
    decode:  tokens (B,1), pos (B,)   -> (logits, cache', aux)

    ``aux`` is a dict of scalars: ``aux_loss``, the router balance loss
    summed over layers, and in a model with MoE layers ``moe_held_rows``
    (assignments to held experts, summed over layers) and
    ``moe_load_max_over_mean`` (its mean over the MoE layers).

    ``last_pos`` (B,), prefill only: per-row position whose logits to
    return instead of the last one — bucket-padded batched prefill
    right-pads each prompt to a shared length, and causal masking keeps
    every position <= last_pos bitwise independent of the padding.
    (SSM layers scan left-to-right through the padding, so bucketed
    prefill is only valid for attention-only stacks; the scheduler
    falls back to exact lengths when ``cfg.has_ssm_layers``.)
    """
    prefix, period, n_periods = layer_pattern(cfg)
    build_cache = mode != "train"
    B, S = tokens.shape
    cdt = jnp.dtype(cfg.compute_dtype)

    batch_sharded = mode != "decode" or (rt.mesh is None) or all(
        (B % rt.mesh.shape[a] == 0) for a in rt.data_axes)
    hspec = (rt.data_axes if batch_sharded else None, None, None)
    with jax.named_scope("embed"):
        h = params["embed"][tokens].astype(cdt)
        if cfg.embed_scale:
            h = h * jnp.asarray(cfg.d_model ** 0.5, cdt)
        h = rt.constrain(h, *hspec)

    if mode == "decode":
        rope_pos = pos
    else:
        rope_pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))

    encoder_out = None
    if cfg.is_encoder_decoder and encoder_embeds is not None:
        encoder_out = encode(params, cfg, rt, encoder_embeds)

    aux_total = _zero_aux(cfg)
    new_cache: Dict[str, Any] = {}

    # --- unrolled prefix layers ---
    for i, spec in enumerate(prefix):
        c_in = (cache or {}).get("prefix", {}).get(f"P{i}")
        h, c, aux = block_apply(params["prefix"][f"P{i}"], spec, h, cfg, rt,
                                pos=rope_pos, cache=c_in, build_cache=build_cache,
                                encoder_out=encoder_out)
        aux_total = _add(aux_total, aux)
        if build_cache:
            new_cache.setdefault("prefix", {})[f"P{i}"] = c

    # --- scanned periods ---
    remat = rt.remat and mode == "train"

    def body(carry, xs):
        hh, aux_acc = carry
        p_period, c_period = xs
        cs_out = {}
        for i, spec in enumerate(period):
            c_in = c_period[f"L{i}"] if c_period is not None else None

            def run_block(pp, hin, spec=spec, c_in=c_in):
                return block_apply(pp, spec, hin, cfg, rt, pos=rope_pos,
                                   cache=c_in, build_cache=build_cache,
                                   encoder_out=encoder_out)
            if remat:   # per-block remat: one block's internals live in bwd
                policy = None
                if rt.remat_policy == "save_tp":
                    from jax.ad_checkpoint import checkpoint_policies
                    policy = checkpoint_policies.save_only_these_names("tp_out")
                run_block = jax.checkpoint(run_block, policy=policy)
            hh, c, aux = run_block(p_period[f"L{i}"], hh)
            aux_acc = _add(aux_acc, aux)
            if build_cache:
                cs_out[f"L{i}"] = c
        hh = rt.constrain(hh, *hspec)
        return (hh, aux_acc), (cs_out if build_cache else None)

    scan_cache = (cache or {}).get("blocks")
    n_periods = jax.tree_util.tree_leaves(params["blocks"])[0].shape[0]
    group = _remat_group(n_periods) if remat else 1

    if group <= 1 or build_cache:
        (h, aux_total), cache_out = jax.lax.scan(
            body, (h, aux_total), (params["blocks"], scan_cache))
        if build_cache:
            new_cache["blocks"] = cache_out
    else:
        # sqrt-remat: outer scan over groups of `group` periods with the
        # group body checkpointed — the inter-period h stash shrinks from
        # n_periods entries to n_groups (+ one group recompute in bwd).
        # Remainder periods (prime n_periods) run in a flat scan.
        n_g, rem = divmod(n_periods, group)

        def group_body(carry, xs_group):
            return jax.lax.scan(body, carry, (xs_group, None))[0], None

        group_body = jax.checkpoint(group_body)
        head = jax.tree.map(
            lambda a: a[:n_g * group].reshape(n_g, group, *a.shape[1:]),
            params["blocks"])
        (h, aux_total), _ = jax.lax.scan(group_body, (h, aux_total), head)
        if rem:
            tail = jax.tree.map(lambda a: a[n_g * group:], params["blocks"])
            (h, aux_total), _ = jax.lax.scan(body, (h, aux_total),
                                             (tail, None))

    h = layers.apply_norm(cfg, params["final_norm"], h)
    if cfg.moe is not None:
        n_moe = sum(sp.ffn == "moe" for sp in prefix) + n_periods * sum(
            sp.ffn == "moe" for sp in period)
        aux_total["moe_load_max_over_mean"] /= max(n_moe, 1)

    if mode == "train":
        # Return hidden states; the loss computes the vocab projection in
        # sequence chunks so (B,S,vocab) logits never materialize
        # (vocab up to 256k -> full fp32 logits would be tens of GB).
        return h, None, aux_total

    with jax.named_scope("unembed"):
        if mode == "prefill":
            # serving only needs one position's logits per row: the last,
            # or the per-row prompt end under bucket-padded batched prefill
            h = (h[:, -1:, :] if last_pos is None
                 else h[jnp.arange(B), last_pos.astype(jnp.int32)][:, None])
        logits = jnp.einsum("bsd,dv->bsv", h.astype(jnp.float32),
                            unembed_matrix(params).astype(jnp.float32))
        logits = layers.softcap(logits, cfg.final_softcap)
    return logits, new_cache, aux_total


def unembed_matrix(params):
    u = params.get("unembed")
    return u if u is not None else params["embed"].T
