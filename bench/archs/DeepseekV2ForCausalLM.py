"""DeepSeek-V2 (arXiv:2405.04434), as a configuration file in Hugging Face
key names describes it: multi-head latent attention (no query
compression) with YaRN rotary scaling, leading dense layers, then MoE
layers of routed experts (softmax over all of them, greedy top-k, weights
not renormalized) beside always-on shared experts, and the sequence-wise
router balance loss (``seq_aux``).

One chip's share of a deployment that divides each layer over several
chips: the file's ``n_routed_experts`` is how many experts are held here
(``reduced`` gives the published count, which the router keeps), and the
program's ``first_held_expert`` says which.  Assignments to experts not
held add nothing, in the program and in the reference alike; the shared
experts and the balance loss are computed whole, as every chip computes
them.

Every function is a pure function of the configuration file (or of the
sizes ``dims`` reads from it).  The reference's layers import nothing of
the program.  One departure from the published model: rotary positions
use the split-half convention, where Hugging Face's DeepSeek-V2 rotates
interleaved pairs; the two differ by a fixed permutation of the rope
columns of ``wq`` and ``wkv_a``, which seeded weights do not see."""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

from bench.reference import mm, rmsnorm

# the keys a configuration's ``reduced`` may cut
CUTS = ("num_hidden_layers", "n_routed_experts", "vocab_size")
# widths the CPU rehearsal cuts (``smoke``)
SMOKE = ("hidden_size", "intermediate_size", "moe_intermediate_size",
         "num_attention_heads", "num_key_value_heads", "kv_lora_rank",
         "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "vocab_size")
# keys whose one value the program implements (no bias, greedy top-k
# without groups, routed output unscaled); the file must state that value
FIXED = {"attention_bias": False, "topk_method": "greedy", "n_group": 1,
         "topk_group": 1, "routed_scaling_factor": 1}


def _experts(config) -> int:
    """The router's width: the published expert count."""
    cut = config.get("reduced", {}).get("n_routed_experts")
    return cut[0] if cut else config["n_routed_experts"]


def dims(config):
    """The sizes the reference and the cost functions need."""
    y = config["rope_scaling"]
    return {"arch": config["architectures"][0],
            "d": config["hidden_size"], "h": config["num_attention_heads"],
            "layers": config["num_hidden_layers"],
            "dense": config["first_k_dense_replace"],
            "vocab": config["vocab_size"],
            "ff": config["intermediate_size"],
            "ffe": config["moe_intermediate_size"],
            "experts": _experts(config),
            "held": config["n_routed_experts"],
            "first_held": config["program"].get("first_held_expert", 0),
            "topk": config["num_experts_per_tok"],
            "shared": config["n_shared_experts"],
            "alpha": float(config["aux_loss_alpha"]),
            "lora": config["kv_lora_rank"],
            "nope": config["qk_nope_head_dim"],
            "rope": config["qk_rope_head_dim"],
            "vhd": config["v_head_dim"],
            "eps": float(config["rms_norm_eps"]),
            "theta": float(config["rope_theta"]),
            "yarn_factor": float(y["factor"]),
            "yarn_ctx": int(y["original_max_position_embeddings"]),
            "yarn_fast": float(y["beta_fast"]),
            "yarn_slow": float(y["beta_slow"]),
            "yarn_mscale": float(y["mscale"]),
            "yarn_mscale_all": float(y["mscale_all_dim"])}


def _attn_spec(m, pre, L):
    d, h, r, qk = m["d"], m["h"], m["lora"], m["nope"] + m["rope"]
    lead = (L,) if L else ()
    return {
        pre + "attn_norm/scale": (lead + (d,), "ones"),
        pre + "attn/wq": (lead + (d, h, qk), d ** -0.5),
        pre + "attn/wkv_a": (lead + (d, r + m["rope"]), d ** -0.5),
        pre + "attn/kv_norm": (lead + (r,), "ones"),
        pre + "attn/wk_b": (lead + (r, h, m["nope"]), r ** -0.5),
        pre + "attn/wv_b": (lead + (r, h, m["vhd"]), r ** -0.5),
        pre + "attn/wo": (lead + (h, m["vhd"], d), (h * m["vhd"]) ** -0.5),
        pre + "ffn_norm/scale": (lead + (d,), "ones"),
    }


def _swiglu_spec(pre, lead, d, f):
    return {pre + "wg": (lead + (d, f), d ** -0.5),
            pre + "wu": (lead + (d, f), d ** -0.5),
            pre + "wd": (lead + (f, d), f ** -0.5)}


def spec(config):
    """name -> (shape, std or "ones"), in the program's tree: the dense
    layers unrolled (``prefix/P<i>``), the MoE layers stacked on a
    leading axis (``blocks/L0``)."""
    m = dims(config)
    d, V, L = m["d"], m["vocab"], m["layers"] - m["dense"]
    out = {"embed": ((V, d), 0.02), "unembed": ((d, V), 0.02),
           "final_norm/scale": ((d,), "ones")}
    for i in range(m["dense"]):
        pre = f"prefix/P{i}/"
        out.update(_attn_spec(m, pre, 0))
        out.update(_swiglu_spec(pre + "ffn/", (), d, m["ff"]))
    pre = "blocks/L0/"
    out.update(_attn_spec(m, pre, L))
    E, f = m["held"], m["ffe"]
    out.update({pre + "moe/router": ((L, d, m["experts"]), d ** -0.5),
                pre + "moe/wg": ((L, E, d, f), d ** -0.5),
                pre + "moe/wu": ((L, E, d, f), d ** -0.5),
                pre + "moe/wd": ((L, E, f, d), f ** -0.5)})
    out.update(_swiglu_spec(pre + "moe/shared/", (L,), d,
                            m["shared"] * f))
    return out


def _as_file(cfg):
    """The program's ModelConfig in the file's key names; each is checked
    against the file."""
    e, a, y = cfg.moe, cfg.mla, cfg.rope_scaling
    return {
        "hidden_size": cfg.d_model, "num_attention_heads": cfg.n_heads,
        "num_key_value_heads": cfg.n_kv_heads,
        "intermediate_size": cfg.d_ff, "vocab_size": cfg.vocab_size,
        "num_hidden_layers": cfg.n_layers, "rms_norm_eps": cfg.norm_eps,
        "rope_theta": cfg.rope_theta,
        "tie_word_embeddings": cfg.tie_embeddings,
        "moe_intermediate_size": e.d_expert, "n_routed_experts": e.held,
        "num_experts_per_tok": e.top_k, "n_shared_experts": e.n_shared,
        "first_k_dense_replace": e.n_dense_prefix,
        "moe_layer_freq": e.moe_every, "seq_aux": e.seq_aux,
        "aux_loss_alpha": e.router_aux_weight,
        "scoring_func": "softmax",
        "norm_topk_prob": e.router_mode != "softmax_topk",
        "kv_lora_rank": a.kv_lora_rank, "q_lora_rank": a.q_lora_rank or None,
        "qk_nope_head_dim": a.qk_nope_dim, "qk_rope_head_dim": a.qk_rope_dim,
        "v_head_dim": a.v_head_dim,
        "rope_scaling": None if y is None else {
            "type": "yarn", "factor": y.factor,
            "original_max_position_embeddings": y.original_max_position,
            "beta_fast": y.beta_fast, "beta_slow": y.beta_slow,
            "mscale": y.mscale, "mscale_all_dim": y.mscale_all_dim},
        "hidden_act": cfg.act}


def program_config(config):
    """The program's ModelConfig for a configuration file: each key in
    ``reduced`` applied (a key this module cannot apply raises), the CPU
    cut's widths applied where the file is a smoke cut; then every width,
    the router's width (the published expert count), top-k, the held
    experts, the shared experts, the dense prefix, the latent attention
    and the rope scaling checked against the file."""
    from repro.configs import get_config
    run = config["program"]
    base = get_config(run["arch"])
    cut = list(config.get("reduced", {}))
    unknown = sorted(set(cut) - set(CUTS))
    if unknown:
        raise SystemExit(
            f"bench: {config['architectures'][0]} cannot cut {unknown}; "
            f"its module in bench/archs/ applies only {list(CUTS)}")
    stated = {k: v[1] for k, v in config.get("reduced", {}).items()
              if config[k] != v[1] and not (run.get("smoke") and k in SMOKE)}
    if stated:
        raise SystemExit(f"bench: the configuration file's keys differ "
                         f"from what its ``reduced`` cuts them to: "
                         f"{ {k: (config[k], v) for k, v in stated.items()} }")
    if run.get("smoke"):                        # CPU rehearsal only
        cut += SMOKE
    c = {k: config[k] for k in cut}
    top = {"num_hidden_layers": "n_layers", "vocab_size": "vocab_size",
           "hidden_size": "d_model", "intermediate_size": "d_ff",
           "num_attention_heads": "n_heads",
           "num_key_value_heads": "n_kv_heads"}
    mla = {"kv_lora_rank": "kv_lora_rank", "qk_nope_head_dim": "qk_nope_dim",
           "qk_rope_head_dim": "qk_rope_dim", "v_head_dim": "v_head_dim"}
    moe = dataclasses.replace(
        base.moe, first_held=run.get("first_held_expert", 0),
        **({"n_held": c["n_routed_experts"]}
           if "n_routed_experts" in c else {}),
        **({"d_expert": c["moe_intermediate_size"]}
           if "moe_intermediate_size" in c else {}))
    cfg = dataclasses.replace(
        base, param_dtype=run["param_dtype"],
        compute_dtype=run["compute_dtype"], moe=moe,
        mla=dataclasses.replace(base.mla, **{f: c[k] for k, f in mla.items()
                                             if k in c}),
        **{f: c[k] for k, f in top.items() if k in c})
    got = _as_file(cfg)
    want = {k: config[k] for k in got}
    got.update(router=cfg.moe.n_experts, **FIXED)
    want.update(router=_experts(config),
                **{k: config[k] for k in FIXED})
    if got != want:
        diff = {k: (got[k], want[k]) for k in want if got[k] != want[k]}
        raise SystemExit(f"bench: the program's {run['arch']} differs from "
                         f"the configuration file (program, file): {diff}")
    return cfg


# ---------------------------------------------------------------------------
# the reference's layers
# ---------------------------------------------------------------------------

Q_BLOCK = 512


def _mscale(factor, m):
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def yarn_rope(x, pos, m):
    """x (B, S, n, dim); pos (S,).  YaRN's frequencies (DeepSeek-V2's
    ``DeepseekV2YarnRotaryEmbedding``): f_extra = theta^(-2i/dim),
    f_inter = f_extra / factor, a linear ramp from the fast to the slow
    correction dim c(n) = dim ln(ctx / (2 pi n)) / (2 ln theta); cos and
    sin scaled by mscale(factor, mscale) / mscale(factor, mscale_all)."""
    dim = x.shape[-1]
    half = dim // 2
    theta, factor = m["theta"], m["yarn_factor"]

    def corr(n):
        return dim * math.log(m["yarn_ctx"] / (2 * math.pi * n)) / (
            2 * math.log(theta))
    low = max(math.floor(corr(m["yarn_fast"])), 0)
    high = min(math.ceil(corr(m["yarn_slow"])), dim - 1)
    i = jnp.arange(half, dtype=jnp.float32)
    ramp = jnp.clip((i - low) / (high - low), 0.0, 1.0)
    f_extra = theta ** (-2.0 * i / dim)
    inv = f_extra / factor * ramp + f_extra * (1.0 - ramp)
    scale = _mscale(factor, m["yarn_mscale"]) / _mscale(
        factor, m["yarn_mscale_all"])
    ang = pos[:, None].astype(jnp.float32) * inv                # (S, half)
    cos = (jnp.cos(ang) * scale)[None, :, None]
    sin = (jnp.sin(ang) * scale)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def softmax_scale(m) -> float:
    """(nope + rope)^-0.5 times mscale(factor, mscale_all)^2."""
    return (m["nope"] + m["rope"]) ** -0.5 * _mscale(
        m["yarn_factor"], m["yarn_mscale_all"]) ** 2


def _attention(q, k, v, scale, prec):
    """Causal attention over query blocks: one block's program scanned
    over the blocks, each block recomputed in the backward pass so that
    no (S, S) score tensor outlives it.  The softmax's row max is taken in
    a pass of its own: fused with the subtraction, XLA on TPU makes it a
    reduce-window whose cost grows with the square of the row."""
    B, S, H, D = q.shape
    bq = min(Q_BLOCK, S)
    nb = -(-S // bq)
    qs = jnp.pad(q, ((0, 0), (0, nb * bq - S), (0, 0), (0, 0)))
    qs = jnp.moveaxis(qs.reshape(B, nb, bq, H, D), 1, 0)

    @jax.checkpoint
    def block(carry, xs):
        qb, s0 = xs
        s = mm("bqhd,bthd->bhqt", qb, k, prec) * scale
        ok = jnp.arange(S)[None, :] <= (s0 + jnp.arange(bq))[:, None]
        s = jnp.where(ok[None, None], s, -jnp.inf)
        e = jnp.exp(s - jax.lax.stop_gradient(jax.lax.optimization_barrier(
            jnp.max(s, axis=-1, keepdims=True))))
        p = e / jnp.sum(e, axis=-1, keepdims=True)
        return carry, mm("bhqt,bthd->bqhd", p, v, prec)
    _, o = jax.lax.scan(block, None, (qs, jnp.arange(nb) * bq))
    return jnp.moveaxis(o, 0, 1).reshape(B, nb * bq, H, -1)[:, :S]


def _mla(x, W, pos, m, prec):
    """Latent attention, decompressed: K and V from the normed latent."""
    B, S, _ = x.shape
    q = mm("bsd,dnh->bsnh", x, W["attn/wq"], prec)
    kv = mm("bsd,dr->bsr", x, W["attn/wkv_a"], prec)
    ckv = rmsnorm(kv[..., :m["lora"]], W["attn/kv_norm"], m["eps"])
    k_rope = yarn_rope(kv[..., None, m["lora"]:], pos, m)     # (B,S,1,rope)
    q = jnp.concatenate([q[..., :m["nope"]],
                         yarn_rope(q[..., m["nope"]:], pos, m)], -1)
    k = jnp.concatenate([mm("bsr,rnh->bsnh", ckv, W["attn/wk_b"], prec),
                         jnp.broadcast_to(k_rope, (B, S, m["h"], m["rope"]))],
                        -1)
    v = mm("bsr,rnh->bsnh", ckv, W["attn/wv_b"], prec)
    o = _attention(q, k, v, softmax_scale(m), prec)
    return mm("bsnh,nhd->bsd", o, W["attn/wo"], prec)


def _swiglu(x, W, pre, prec):
    a = jax.nn.silu(mm("bsd,df->bsf", x, W[pre + "wg"], prec))
    a = a * mm("bsd,df->bsf", x, W[pre + "wu"], prec)
    return mm("bsf,fd->bsd", a, W[pre + "wd"], prec)


def _moe(x, W, m, prec):
    """Routed experts held here, each on every token and weighted by its
    routing weight where the token chose it (else 0), plus the shared
    experts; and the sequence-wise balance loss over all experts:
    alpha * mean over rows of sum_i (E count_i / (S k)) * mean_s p_i."""
    B, S, _ = x.shape
    E, k = m["experts"], m["topk"]
    probs = jax.nn.softmax(mm("bsd,de->bse", x, W["moe/router"], prec), -1)
    weights, ids = jax.lax.top_k(probs, k)                      # (B,S,k)
    count = jnp.sum(jax.nn.one_hot(ids, E), axis=(1, 2))        # (B,E)
    aux = m["alpha"] * jnp.mean(jnp.sum(
        count * E / (S * k) * jnp.mean(probs, axis=1), -1))
    held = m["first_held"] + jnp.arange(m["held"])
    gate = jnp.sum(weights[..., None] * (ids[..., None] == held), -2)
    a = jax.nn.silu(mm("bsd,edf->bsef", x, W["moe/wg"], prec))
    a = a * mm("bsd,edf->bsef", x, W["moe/wu"], prec)
    y = mm("bsef,efd->bsd", a * gate[..., None], W["moe/wd"], prec)
    return y + _swiglu(x, W, "moe/shared/", prec), aux


def _layer(h, W, pos, m, prec, kind):
    h = h + _mla(rmsnorm(h, W["attn_norm/scale"], m["eps"]), W, pos, m, prec)
    x = rmsnorm(h, W["ffn_norm/scale"], m["eps"])
    if kind == "dense":
        return h + _swiglu(x, W, "ffn/", prec), 0.0
    y, aux = _moe(x, W, m, prec)
    return h + y, aux


def hidden(w, tokens, m, prec="fp32"):
    """Final normed hidden states (B, S, d) of token ids (B, S), and the
    router balance loss summed over the MoE layers (the term the
    architecture adds to the mean token loss).  The MoE layers are one
    layer's program scanned over their stacked weights; each layer is
    recomputed in the backward pass."""
    pos = jnp.arange(tokens.shape[1])
    h = w["embed"][tokens]
    for i in range(m["dense"]):
        pre = f"prefix/P{i}/"
        W = {n[len(pre):]: v for n, v in w.items() if n.startswith(pre)}
        h, _ = jax.checkpoint(
            lambda h, W: _layer(h, W, pos, m, prec, "dense"))(h, W)
    pre = "blocks/L0/"
    W = {n[len(pre):]: v for n, v in w.items() if n.startswith(pre)}
    h, aux = jax.lax.scan(jax.checkpoint(
        lambda h, W: _layer(h, W, pos, m, prec, "moe")), h, W)
    return rmsnorm(h, w["final_norm/scale"], m["eps"]), jnp.sum(aux)


# ---------------------------------------------------------------------------
# costs
# ---------------------------------------------------------------------------

def params_without_input_embedding(m: dict) -> float:
    """Parameters that do arithmetic per token: every weight but the
    input embedding, each routed expert's weights counted at the share of
    tokens that reach it, k / E, over the experts held here."""
    d, h, r = m["d"], m["h"], m["lora"]
    attn = (d * h * (m["nope"] + m["rope"]) + d * (r + m["rope"]) + r
            + r * h * (m["nope"] + m["vhd"]) + h * m["vhd"] * d + 2 * d)
    dense = attn + 3 * d * m["ff"]
    routed = 3 * d * m["ffe"] * m["held"] * m["topk"] / m["experts"]
    moe = attn + d * m["experts"] + 3 * d * m["shared"] * m["ffe"] + routed
    return (m["dense"] * dense + (m["layers"] - m["dense"]) * moe + d
            + d * m["vocab"])


def train_flops_per_token(m: dict, seq: int) -> float:
    """6 N + 6 L H (d_qk + d_v) S: forward and backward of every weight a
    token uses (routed experts as above) and of causal attention over
    (nope + rope)-wide scores and v-wide values.  Recomputation is not
    counted."""
    return (6.0 * params_without_input_embedding(m)
            + 6.0 * m["layers"] * m["h"] * (m["nope"] + m["rope"] + m["vhd"])
            * seq)


def moe_experts_flops_per_token(m: dict) -> float:
    """Forward and backward of the held routed experts' three products,
    per token of the batch: 6 * 3 d f * k * held / E, each MoE layer."""
    return (6.0 * 3 * m["d"] * m["ffe"] * m["topk"] * m["held"]
            / m["experts"] * (m["layers"] - m["dense"]))


def decode_flops(m: dict, ctx: int) -> float:
    """One generated token at context length ``ctx``: 2 N for the
    weights, and per layer absorbed latent attention over ``ctx``
    positions: scores against latent and rope values, then the latent
    context, 2 H (2 lora + rope) ctx."""
    return (2.0 * params_without_input_embedding(m)
            + 2.0 * m["layers"] * m["h"] * (2 * m["lora"] + m["rope"]) * ctx)


def decode_cache_bytes(m: dict, n_keys: int, cache_bytes: int = 2) -> float:
    """What latent decode attention reads and writes for one token of one
    sequence attending ``n_keys`` positions, every layer: the latent (lora)
    and rope values of each position, the absorbed query (lora + rope per
    head) and the latent context (lora per head)."""
    per_layer = (n_keys * (m["lora"] + m["rope"])
                 + m["h"] * (2 * m["lora"] + m["rope"]))
    return m["layers"] * per_layer * cache_bytes


def smoke(config):
    """The CPU rehearsal's cut: every width cut; the router's width, top-k,
    the held experts and the depth kept.  It computes in float32: at
    these widths, 256 tokens a step and five layers, the bf16 residual
    stream's rounding alone reads 2-9e-4 in ``loss_gap`` and 5-6e-3 in
    ``change_gap`` (two seeds), above the limits the train driver's CPU
    cut was read for (a one-layer llama); in float32 the program has to
    match the reference to rounding (1.6e-7 and 2.6e-6)."""
    return dict(config, hidden_size=128, intermediate_size=256,
                moe_intermediate_size=64, num_attention_heads=4,
                num_key_value_heads=4, kv_lora_rank=64, qk_nope_head_dim=32,
                qk_rope_head_dim=16, v_head_dim=32, vocab_size=512,
                program=dict(config["program"], smoke=True,
                             compute_dtype="float32"))
