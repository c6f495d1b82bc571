"""Core layers: norms, RoPE, attention (GQA / MLA / sliding-window /
softcap / QK-norm / cross), gated & ungated MLPs.

All functions are pure: ``(params_subtree, inputs, cfg, ...) -> outputs``.
Abstract parameter trees are built by the ``*_defs`` functions.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig, RopeScaling
from repro.kernels import interpret_mode
from repro.models.param import ParamDef

NEG_INF = -2.0e38  # large-negative for masking (fp32-safe)


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------

def rmsnorm_defs(dim: int):
    return {"scale": ParamDef((dim,), ("norm",), "ones")}


def rmsnorm(p, x, eps: float = 1e-6):
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps) * p["scale"].astype(jnp.float32)
    return out.astype(x.dtype)


def layernorm_defs(dim: int):
    return {"scale": ParamDef((dim,), ("norm",), "ones"),
            "bias": ParamDef((dim,), ("norm",), "zeros")}


def layernorm(p, x, eps: float = 1e-5):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    out = (xf - mu) * jax.lax.rsqrt(var + eps)
    out = out * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    return out.astype(x.dtype)


def norm_defs(cfg: ModelConfig):
    return layernorm_defs(cfg.d_model) if cfg.act == "gelu" and cfg.is_encoder_decoder \
        else rmsnorm_defs(cfg.d_model)


def apply_norm(cfg: ModelConfig, p, x):
    if "bias" in p:
        return layernorm(p, x)
    return rmsnorm(p, x, cfg.norm_eps)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def yarn_mscale(factor: float, m: float) -> float:
    """YaRN's magnitude correction, 0.1 m ln(factor) + 1 (1 when not
    scaled)."""
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def yarn_ramp(dim: int, theta: float, s: RopeScaling) -> np.ndarray:
    """(dim/2,) share of each rotary frequency that YaRN divides by the
    factor: 0 above the ``beta_fast`` correction dim, 1 below the
    ``beta_slow`` one, linear between; the correction dim of n rotations
    over the original context is dim ln(ctx / (2 pi n)) / (2 ln theta)."""
    def corr(n):
        return (dim * math.log(s.original_max_position / (2 * math.pi * n))
                / (2 * math.log(theta)))
    low = max(math.floor(corr(s.beta_fast)), 0)
    high = min(math.ceil(corr(s.beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    i = np.arange(dim // 2, dtype=np.float32)
    return np.clip((i - low) / (high - low), 0, 1).astype(np.float32)


def rope(x: jnp.ndarray, pos: jnp.ndarray, theta: float,
         scaling: Optional[RopeScaling] = None) -> jnp.ndarray:
    """Rotary embedding, llama split-half convention, YaRN-scaled where
    ``scaling`` is given.

    x: (..., S, n_heads_or_1, hd) ; pos: broadcastable to (..., S)."""
    hd = x.shape[-1]
    half = hd // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    if scaling is not None:
        ramp = jnp.asarray(yarn_ramp(hd, theta, scaling))
        freqs = freqs / scaling.factor * ramp + freqs * (1 - ramp)
    angles = pos[..., None, None].astype(jnp.float32) * freqs  # (...,S,1,half)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    if scaling is not None:
        m = (yarn_mscale(scaling.factor, scaling.mscale)
             / yarn_mscale(scaling.factor, scaling.mscale_all_dim))
        if m != 1.0:
            cos, sin = cos * m, sin * m
    x1, x2 = x[..., :half], x[..., half:]
    x1f, x2f = x1.astype(jnp.float32), x2.astype(jnp.float32)
    out = jnp.concatenate([x1f * cos - x2f * sin, x2f * cos + x1f * sin], axis=-1)
    return out.astype(x.dtype)


def softcap(x: jnp.ndarray, cap: float) -> jnp.ndarray:
    if cap <= 0:
        return x
    return cap * jnp.tanh(x / cap)


# ---------------------------------------------------------------------------
# MLP (gated SwiGLU/GeGLU and ungated whisper-style)
# ---------------------------------------------------------------------------

def mlp_defs(cfg: ModelConfig, d_ff: int, gated: bool = True):
    d = cfg.d_model
    if gated:
        return {"wg": ParamDef((d, d_ff), ("embed", "ffn")),
                "wu": ParamDef((d, d_ff), ("embed", "ffn")),
                "wd": ParamDef((d_ff, d), ("ffn", "embed"))}
    return {"w1": ParamDef((d, d_ff), ("embed", "ffn")),
            "b1": ParamDef((d_ff,), ("ffn",), "zeros"),
            "w2": ParamDef((d_ff, d), ("ffn", "embed")),
            "b2": ParamDef((d,), ("norm",), "zeros")}


def _act(cfg: ModelConfig, x):
    return jax.nn.gelu(x) if cfg.act == "gelu" else jax.nn.silu(x)


def mlp(p, x, cfg: ModelConfig):
    cdt = jnp.dtype(cfg.compute_dtype)
    xc = x.astype(cdt)
    if "wg" in p:
        h = _act(cfg, xc @ p["wg"].astype(cdt)) * (xc @ p["wu"].astype(cdt))
        return h @ p["wd"].astype(cdt)
    h = _act(cfg, xc @ p["w1"].astype(cdt) + p["b1"].astype(cdt))
    return h @ p["w2"].astype(cdt) + p["b2"].astype(cdt)


# ---------------------------------------------------------------------------
# attention — GQA (+ sliding window, softcap, qk-norm) and MLA
# ---------------------------------------------------------------------------

def attention_defs(cfg: ModelConfig, cross: bool = False):
    d, H, K = cfg.d_model, cfg.n_heads, cfg.n_kv_heads
    hd = cfg.resolved_head_dim
    if cfg.mla is not None and not cross:
        m = cfg.mla
        qk_hd = m.qk_nope_dim + m.qk_rope_dim
        defs = {
            "wkv_a": ParamDef((d, m.kv_lora_rank + m.qk_rope_dim), ("embed", "kv_lora_in")),
            "kv_norm": ParamDef((m.kv_lora_rank,), ("norm",), "ones"),
            "wk_b": ParamDef((m.kv_lora_rank, H, m.qk_nope_dim), ("kv_lora", "heads", "head_dim")),
            "wv_b": ParamDef((m.kv_lora_rank, H, m.v_head_dim), ("kv_lora", "heads", "head_dim")),
            "wo": ParamDef((H, m.v_head_dim, d), ("heads", "head_dim", "embed")),
        }
        if m.q_lora_rank:
            defs["wq_a"] = ParamDef((d, m.q_lora_rank), ("embed", "q_lora"))
            defs["q_norm"] = ParamDef((m.q_lora_rank,), ("norm",), "ones")
            defs["wq_b"] = ParamDef((m.q_lora_rank, H, qk_hd), ("q_lora", "heads", "head_dim"))
        else:
            defs["wq"] = ParamDef((d, H, qk_hd), ("embed", "heads", "head_dim"))
        return defs
    defs = {
        "wq": ParamDef((d, H, hd), ("embed", "heads", "head_dim")),
        "wk": ParamDef((d, K, hd), ("embed", "kv_heads", "head_dim")),
        "wv": ParamDef((d, K, hd), ("embed", "kv_heads", "head_dim")),
        "wo": ParamDef((H, hd, d), ("heads", "head_dim", "embed")),
    }
    if cfg.qk_norm and not cross:
        defs["qn"] = ParamDef((hd,), ("norm",), "ones")
        defs["kn"] = ParamDef((hd,), ("norm",), "ones")
    return defs


def _qk_rms(x, scale, eps):
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)).astype(x.dtype)


def ring_cache(entries, S: int, window: int):
    """Compress full-seq cache entries {name: (B,S,...)} + implicit positions
    arange(S) into a ring buffer of size ``window`` (slot = pos % window),
    so a windowed layer's decode state is O(W) not O(S)."""
    B = next(iter(entries.values())).shape[0]
    if window <= 0 or S <= window:
        sp = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
        return {**entries, "slot_pos": sp}
    pos = jnp.arange(S - window, S, dtype=jnp.int32)       # kept positions
    slots = pos % window                                    # a permutation of 0..W-1
    inv = jnp.zeros((window,), jnp.int32).at[slots].set(jnp.arange(window))
    out = {k: v[:, -window:][:, inv] for k, v in entries.items()}
    out["slot_pos"] = jnp.broadcast_to(pos[inv], (B, window))
    return out


def _chunk_mask(q0: int, Qc: int, T: int, causal: bool, window: int):
    """(Qc,T) additive mask for the q-rows [q0, q0+Qc)."""
    i = q0 + jnp.arange(Qc)[:, None]
    j = jnp.arange(T)[None, :]
    ok = jnp.ones((Qc, T), bool)
    if causal:
        ok &= j <= i
    if window > 0:
        ok &= j > i - window
    return jnp.where(ok, 0.0, NEG_INF).astype(jnp.float32)


def _repeat_kv(k, H: int):
    """(B,T,K,hd) -> (B,T,H,hd).  With heads sharded over "model" each
    device materializes only its own heads' K/V — the repeat is free in
    per-device memory, and FLAT head layout (no (K,G) reshape) lets the
    SPMD partitioner keep q/scores head-sharded (a (K,G) factored reshape
    of a 16-way-sharded 64-head dim is unrepresentable when K=8)."""
    K = k.shape[2]
    if K == H:
        return k
    return jnp.repeat(k, H // K, axis=2)


def _softmax_rowmax_apart(s):
    """Softmax over the last axis with the row max taken in a pass of its
    own.  Fused with the subtraction, XLA's TPU backend turns max-then-
    broadcast into a reduce-window two rows wide, whose cost grows with
    the square of the row: 94 ms for one (2, 16, 1024, 8192) chunk of
    scores on a v5e, against ~1 ms for a pass over it."""
    m = jax.lax.optimization_barrier(jnp.max(s, axis=-1, keepdims=True))
    e = jnp.exp(s - jax.lax.stop_gradient(m))
    return e / jnp.sum(e, axis=-1, keepdims=True)


def _sdpa(q, k, v, mask, cap, scale, bf16_mm: bool = False,
          softmax=jax.nn.softmax):
    """q: (B,S,H,hd)  k,v: (B,T,K,hd), K | H.  mask: broadcast (B,H,S,T).

    bf16_mm (§Perf): QK^T and PV run bf16-in/f32-accumulate (the MXU's
    native mode) instead of fully-f32 operands — softmax math stays f32."""
    H = q.shape[2]
    k = _repeat_kv(k, H)
    v = _repeat_kv(v, H)
    if bf16_mm:
        s = jnp.einsum("bshd,bthd->bhst",
                       (q.astype(jnp.float32) * scale).astype(jnp.bfloat16),
                       k.astype(jnp.bfloat16),
                       preferred_element_type=jnp.float32)
    else:
        s = jnp.einsum("bshd,bthd->bhst", q.astype(jnp.float32) * scale,
                       k.astype(jnp.float32))
    s = softcap(s, cap) + mask
    p = softmax(s)
    o = jnp.einsum("bhst,bthd->bshd", p.astype(v.dtype), v)
    return o


# Route paged decode attention through the Pallas paged-attention
# kernel: None = auto (the kernel on TPU, the gather path on the CPU
# backend, any other backend raises), True/False = force.  The jnp
# gather path below is the bitwise reference against the dense decode
# engine; the kernel is the TPU fast path (agrees to ~1e-6 atol in
# fp32 — online vs two-pass softmax reassociates the reduction).
PAGED_DECODE_KERNEL: Optional[bool] = None


def _use_paged_kernel() -> bool:
    if PAGED_DECODE_KERNEL is None:
        return not interpret_mode()
    return PAGED_DECODE_KERNEL


def _paged_write(pool, new, bt, pos):
    """Write this step's entry into the block pool through the table:
    pool (nb, bs, *tail) <- new (B, 1, *tail) at absolute position
    pos (B,).  Active slots always target a private (refcount-1) block;
    inactive slots target the reserved scratch block 0."""
    bs = pool.shape[1]
    B = bt.shape[0]
    bid = bt[jnp.arange(B), (pos // bs).astype(jnp.int32)]
    off = (pos % bs).astype(jnp.int32)
    return pool.at[bid, off].set(new[:, 0].astype(pool.dtype))


def _paged_gather(pool, bt):
    """Dense (B, nbmax*bs, *tail) view of a slot's entries gathered
    through its block table.  Positions t <= pos hold real entries in
    position order (identical to the unrotated dense cache layout);
    everything else is garbage that the caller masks with NEG_INF."""
    B, nbmax = bt.shape
    bs = pool.shape[1]
    return pool[bt].reshape((B, nbmax * bs) + pool.shape[2:])


def _paged_valid(pos, T: int, window: int):
    """(B, T) validity mask for gathered entries: written and causal
    (t <= pos), inside the sliding window when one applies."""
    t_ids = jnp.arange(T, dtype=jnp.int32)[None, :]
    valid = t_ids <= pos[:, None]
    if window > 0:
        valid &= t_ids > pos[:, None] - window
    return valid


Q_CHUNK = 1024


def _sdpa_seq(q, k, v, causal: bool, window: int, cap, scale,
              bf16_mm: bool = False):
    """Full-sequence attention, chunked over the query dim: scores exist
    only per (Q_CHUNK, T) block (XLA-level flash attention; a (S,T) score
    tensor or mask at 32k would be tens of GB).  Each chunk is
    ``jax.checkpoint``ed so backward recomputes its scores, and takes its
    softmax's row max apart (``_softmax_rowmax_apart``)."""
    B, S, H, hd = q.shape
    hd_v = v.shape[-1]          # MLA: qk dim (192) != v head dim (128)
    T = k.shape[1]
    if S <= Q_CHUNK or S % Q_CHUNK != 0:  # small or indivisible (enc 1500)
        return _sdpa(q, k, v, _chunk_mask(0, S, T, causal, window)
                     if (causal or window) else jnp.zeros((), jnp.float32),
                     cap, scale, bf16_mm)
    nc = S // Q_CHUNK

    def chunk(c, q_c):
        mask = (_chunk_mask(c * Q_CHUNK, Q_CHUNK, T, causal, window)
                if (causal or window) else jnp.zeros((), jnp.float32))
        return _sdpa(q_c, k, v, mask, cap, scale, bf16_mm,
                     softmax=_softmax_rowmax_apart)

    chunk = jax.checkpoint(chunk, static_argnums=())

    def body(_, xs):
        c, q_c = xs
        return None, chunk(c, q_c)

    qs = jnp.moveaxis(q.reshape(B, nc, Q_CHUNK, H, hd), 1, 0)
    _, outs = jax.lax.scan(body, None, (jnp.arange(nc), qs))
    return jnp.moveaxis(outs, 0, 1).reshape(B, S, H, hd_v)


def gqa_attention(p, x, cfg: ModelConfig, *, local: bool, pos, cache=None,
                  causal: bool = True, kv_input=None):
    """General attention. Modes:
      * full-seq (train/prefill): cache=None, pos (B,S) absolute positions.
      * decode: cache={"k","v","slot_pos"}, x (B,1,d), pos (B,) current index.
      * cross: kv_input (B,T,d) (encoder output); no rope, no cache mutation.
    Returns (out, new_cache)."""
    cdt = jnp.dtype(cfg.compute_dtype)
    H, K, hd = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    G = H // K
    B = x.shape[0]
    xc = x.astype(cdt)
    cross = kv_input is not None
    assert cfg.rope_scaling is None, "rope scaling is implemented for MLA only"
    window = (cfg.window if local else 0)

    q = jnp.einsum("bsd,dkh->bskh", xc, p["wq"].astype(cdt))
    src = kv_input.astype(cdt) if cross else xc
    k = jnp.einsum("bsd,dkh->bskh", src, p["wk"].astype(cdt))
    v = jnp.einsum("bsd,dkh->bskh", src, p["wv"].astype(cdt))
    if cfg.qk_norm and not cross:
        q = _qk_rms(q, p["qn"], cfg.norm_eps)
        k = _qk_rms(k, p["kn"], cfg.norm_eps)
    if not cross:
        q = rope(q, pos if pos.ndim == 2 else pos[:, None], cfg.rope_theta)
        k = rope(k, pos if pos.ndim == 2 else pos[:, None], cfg.rope_theta)
    scale = hd ** -0.5

    if cache is None:  # full-sequence
        S = x.shape[1]
        o = _sdpa_seq(q, k, v, causal and not cross, window,
                      cfg.attn_softcap, scale, bf16_mm=cfg.sdpa_bf16)
        new_cache = None
        if not cross and causal:
            new_cache = ring_cache({"k": k, "v": v}, S, window)
        return jnp.einsum("bshd,hdo->bso", o, p["wo"].astype(cdt)), new_cache

    # ---- decode (x is (B,1,d)) ----
    if "kp" in cache:  # paged: write/read through the block table
        kp = _paged_write(cache["kp"], k, cache["bt"], pos)
        vp = _paged_write(cache["vp"], v, cache["bt"], pos)
        new_cache = {"kp": kp, "vp": vp, "bt": cache["bt"]}
        if _use_paged_kernel():
            from repro.kernels.paged_attention.ops import paged_attention
            o = paged_attention(q[:, 0], kp, vp, cache["bt"], pos,
                                window=window,
                                softcap=cfg.attn_softcap)[:, None]
        else:
            kd = _paged_gather(kp, cache["bt"])
            vd = _paged_gather(vp, cache["bt"])
            valid = _paged_valid(pos, kd.shape[1], window)
            mask = jnp.where(valid, 0.0, NEG_INF)[:, None, None, :]
            o = _sdpa(q, kd.astype(cdt), vd.astype(cdt), mask,
                      cfg.attn_softcap, scale, cfg.sdpa_bf16)
        out = jnp.einsum("bshd,hdo->bso", o.astype(cdt), p["wo"].astype(cdt))
        return out, new_cache

    Sc = cache["k"].shape[1]
    slot = (pos % Sc).astype(jnp.int32)                      # ring-buffer slot
    upd = jax.vmap(lambda c, n, s: jax.lax.dynamic_update_slice_in_dim(c, n, s, 0))
    new_k = upd(cache["k"], k.astype(cache["k"].dtype), slot)
    new_v = upd(cache["v"], v.astype(cache["v"].dtype), slot)
    new_sp = jax.vmap(lambda spv, s, pp: jax.lax.dynamic_update_slice(
        spv, pp[None].astype(jnp.int32), (s,)))(cache["slot_pos"], slot, pos)
    valid = new_sp >= 0
    valid &= new_sp[:, :] <= pos[:, None]
    if window > 0:
        valid &= new_sp > (pos[:, None] - window)
    mask = jnp.where(valid, 0.0, NEG_INF)[:, None, None, :]     # (B,1,1,T)
    o = _sdpa(q, new_k.astype(cdt), new_v.astype(cdt), mask, cfg.attn_softcap,
              scale, cfg.sdpa_bf16)
    out = jnp.einsum("bshd,hdo->bso", o, p["wo"].astype(cdt))
    return out, {"k": new_k, "v": new_v, "slot_pos": new_sp}


# ---------------------------------------------------------------------------
# MLA (DeepSeek-V2 multi-head latent attention)
# ---------------------------------------------------------------------------

def _mla_q(p, xc, cfg, cdt):
    m = cfg.mla
    if m.q_lora_rank:
        ql = rmsnorm({"scale": p["q_norm"]}, xc @ p["wq_a"].astype(cdt), cfg.norm_eps)
        q = jnp.einsum("bsr,rkh->bskh", ql.astype(cdt), p["wq_b"].astype(cdt))
    else:
        q = jnp.einsum("bsd,dkh->bskh", xc, p["wq"].astype(cdt))
    return q[..., :m.qk_nope_dim], q[..., m.qk_nope_dim:]


def mla_softmax_scale(cfg: ModelConfig) -> float:
    """(qk_nope + qk_rope)^-0.5, times mscale(factor, mscale_all_dim)^2
    under YaRN (DeepSeek-V2's ``softmax_scale``)."""
    m, s = cfg.mla, cfg.rope_scaling
    scale = (m.qk_nope_dim + m.qk_rope_dim) ** -0.5
    if s is not None and s.mscale_all_dim:
        scale *= yarn_mscale(s.factor, s.mscale_all_dim) ** 2
    return scale


def mla_attention(p, x, cfg: ModelConfig, *, local: bool, pos, cache=None):
    """MLA: full-seq path decompresses K/V; decode path runs *absorbed*
    attention directly in the kv_lora latent space, caching only
    (c_kv, k_rope) — the technique's memory win."""
    cdt = jnp.dtype(cfg.compute_dtype)
    m = cfg.mla
    H = cfg.n_heads
    B = x.shape[0]
    xc = x.astype(cdt)
    scale = mla_softmax_scale(cfg)
    window = (cfg.window if local else 0)

    q_nope, q_rope = _mla_q(p, xc, cfg, cdt)
    kv_a = xc @ p["wkv_a"].astype(cdt)                      # (B,S,lora+rope)
    ckv = rmsnorm({"scale": p["kv_norm"]}, kv_a[..., :m.kv_lora_rank], cfg.norm_eps).astype(cdt)
    k_rope = kv_a[..., m.kv_lora_rank:]                     # shared across heads

    pos2 = pos if pos.ndim == 2 else pos[:, None]
    q_rope = rope(q_rope, pos2, cfg.rope_theta, cfg.rope_scaling)
    k_rope = rope(k_rope[..., None, :], pos2, cfg.rope_theta,
                  cfg.rope_scaling)[..., 0, :]

    if cache is None:  # full-sequence: decompress (standard MHA form)
        S = x.shape[1]
        k_nope = jnp.einsum("bsr,rkh->bskh", ckv, p["wk_b"].astype(cdt))
        v = jnp.einsum("bsr,rkh->bskh", ckv, p["wv_b"].astype(cdt))
        k = jnp.concatenate([k_nope, jnp.broadcast_to(k_rope[:, :, None, :],
                                                      (B, S, H, m.qk_rope_dim))], axis=-1)
        q = jnp.concatenate([q_nope, q_rope], axis=-1)          # (B,S,H,qk)
        o = _sdpa_seq(q, k, v, True, window, cfg.attn_softcap, scale,
                      bf16_mm=cfg.sdpa_bf16)
        out = jnp.einsum("bshd,hdo->bso", o, p["wo"].astype(cdt))
        new_cache = ring_cache({"ckv": ckv, "krope": k_rope}, S, window)
        return out, new_cache

    # ---- absorbed decode ----
    if "ckvp" in cache:  # paged: latent pools through the block table
        ckvp = _paged_write(cache["ckvp"], ckv, cache["bt"], pos)
        kropep = _paged_write(cache["kropep"], k_rope, cache["bt"], pos)
        ckv_d = _paged_gather(ckvp, cache["bt"])           # (B, T, r)
        kr_d = _paged_gather(kropep, cache["bt"])          # (B, T, rr)
        q_lat = jnp.einsum("bskh,rkh->bskr", q_nope, p["wk_b"].astype(cdt))
        s = jnp.einsum("bskr,btr->bkst", q_lat.astype(jnp.float32),
                       ckv_d.astype(jnp.float32))
        s = s + jnp.einsum("bskh,bth->bkst", q_rope.astype(jnp.float32),
                           kr_d.astype(jnp.float32))
        s = s * scale
        valid = _paged_valid(pos, ckv_d.shape[1], window)
        s = softcap(s, cfg.attn_softcap) + \
            jnp.where(valid, 0.0, NEG_INF)[:, None, None, :]
        prob = jax.nn.softmax(s, axis=-1)
        ctx = jnp.einsum("bkst,btr->bskr", prob.astype(cdt),
                         ckv_d.astype(cdt))
        o = jnp.einsum("bskr,rkh->bskh", ctx, p["wv_b"].astype(cdt))
        out = jnp.einsum("bshd,hdo->bso", o, p["wo"].astype(cdt))
        return out, {"ckvp": ckvp, "kropep": kropep, "bt": cache["bt"]}

    Sc = cache["ckv"].shape[1]
    slot = (pos % Sc).astype(jnp.int32)
    upd2 = jax.vmap(lambda c, n, s: jax.lax.dynamic_update_slice_in_dim(c, n, s, 0))
    new_ckv = upd2(cache["ckv"], ckv.astype(cache["ckv"].dtype), slot)
    new_kr = upd2(cache["krope"], k_rope.astype(cache["krope"].dtype), slot)
    new_sp = jax.vmap(lambda spv, s, pp: jax.lax.dynamic_update_slice(
        spv, pp[None].astype(jnp.int32), (s,)))(cache["slot_pos"], slot, pos)

    # absorb wk_b into the query:  q_lat = q_nope @ wk_b  (B,1,H,lora)
    q_lat = jnp.einsum("bskh,rkh->bskr", q_nope, p["wk_b"].astype(cdt))
    s = jnp.einsum("bskr,btr->bkst", q_lat.astype(jnp.float32),
                   new_ckv.astype(jnp.float32))
    s = s + jnp.einsum("bskh,bth->bkst", q_rope.astype(jnp.float32),
                       new_kr.astype(jnp.float32))
    s = s * scale
    valid = (new_sp >= 0) & (new_sp <= pos[:, None])
    if window > 0:
        valid &= new_sp > (pos[:, None] - window)
    s = softcap(s, cfg.attn_softcap) + jnp.where(valid, 0.0, NEG_INF)[:, None, None, :]
    prob = jax.nn.softmax(s, axis=-1)
    ctx = jnp.einsum("bkst,btr->bskr", prob.astype(cdt), new_ckv.astype(cdt))
    o = jnp.einsum("bskr,rkh->bskh", ctx, p["wv_b"].astype(cdt))   # (B,1,H,vhd)
    out = jnp.einsum("bshd,hdo->bso", o, p["wo"].astype(cdt))
    return out, {"ckv": new_ckv, "krope": new_kr, "slot_pos": new_sp}
