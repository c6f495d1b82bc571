"""The llama-style decoder (RMSNorm, rotary positions in the split-half
convention, grouped-query causal attention, SwiGLU), as a configuration
file in Hugging Face key names describes it: its sizes, its weights in
the program's tree names, the program's ModelConfig, the reference's
layers, the work its costs count, and its CPU cut.

Every function is a pure function of the configuration file (or of the
sizes ``dims`` reads from it).  The reference's layers import nothing of
the program."""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from bench.reference import attention, mm, rmsnorm, rope

# Hugging Face key -> the program's ModelConfig field; every one is
# checked against the program
FIELDS = {"hidden_size": "d_model", "num_attention_heads": "n_heads",
          "num_key_value_heads": "n_kv_heads", "intermediate_size": "d_ff",
          "vocab_size": "vocab_size", "num_hidden_layers": "n_layers",
          "rms_norm_eps": "norm_eps", "rope_theta": "rope_theta",
          "tie_word_embeddings": "tie_embeddings"}
# the keys a configuration's ``reduced`` may cut
CUTS = ("num_hidden_layers",)
# the widths that the CPU rehearsal cuts (``smoke``)
SMOKE = ("hidden_size", "intermediate_size", "num_attention_heads",
         "num_key_value_heads", "vocab_size")


def dims(config):
    """The sizes the reference and the cost functions need."""
    d = config["hidden_size"]
    h = config["num_attention_heads"]
    return {"arch": config["architectures"][0],
            "d": d, "h": h, "k": config["num_key_value_heads"],
            "hd": config.get("head_dim") or d // h,
            "ff": config["intermediate_size"],
            "layers": config["num_hidden_layers"],
            "vocab": config["vocab_size"],
            "eps": float(config["rms_norm_eps"]),
            "theta": float(config["rope_theta"])}


def spec(config):
    """name -> (shape, std or "ones").  Per-layer weights carry a leading
    layer axis."""
    m = dims(config)
    d, h, k, hd, ff, L, V = (m["d"], m["h"], m["k"], m["hd"], m["ff"],
                             m["layers"], m["vocab"])
    return {
        "embed": ((V, d), 0.02),
        "unembed": ((d, V), 0.02),
        "final_norm/scale": ((d,), "ones"),
        "blocks/L0/attn_norm/scale": ((L, d), "ones"),
        "blocks/L0/attn/wq": ((L, d, h, hd), d ** -0.5),
        "blocks/L0/attn/wk": ((L, d, k, hd), d ** -0.5),
        "blocks/L0/attn/wv": ((L, d, k, hd), d ** -0.5),
        "blocks/L0/attn/wo": ((L, h, hd, d), (h * hd) ** -0.5),
        "blocks/L0/ffn_norm/scale": ((L, d), "ones"),
        "blocks/L0/ffn/wg": ((L, d, ff), d ** -0.5),
        "blocks/L0/ffn/wu": ((L, d, ff), d ** -0.5),
        "blocks/L0/ffn/wd": ((L, ff, d), ff ** -0.5),
    }


def program_config(config):
    """The program's ModelConfig for a configuration file: each key in
    ``reduced`` applied (a key this module cannot apply raises), the CPU
    cut's widths applied where the file is a smoke cut, and then every
    key of ``FIELDS`` and the head size checked against the file."""
    from repro.configs import get_config
    run = config["program"]
    cfg = dataclasses.replace(get_config(run["arch"]),
                              param_dtype=run["param_dtype"],
                              compute_dtype=run["compute_dtype"])
    cut = list(config.get("reduced", {}))
    unknown = sorted(set(cut) - set(CUTS))
    if unknown:
        raise SystemExit(
            f"bench: {config['architectures'][0]} cannot cut {unknown}; "
            f"its module in bench/archs/ applies only {list(CUTS)}")
    if run.get("smoke"):                        # CPU rehearsal only
        cut += SMOKE
    cfg = dataclasses.replace(cfg, **{FIELDS[k]: config[k] for k in cut})
    want = {f: config[k] for k, f in FIELDS.items()}
    got = {f: getattr(cfg, f) for f in want}
    want["head_dim"], got["head_dim"] = dims(config)["hd"], \
        cfg.resolved_head_dim
    if got != want:
        raise SystemExit(f"bench: the program's {run['arch']} differs from "
                         f"the configuration file: {got} != {want}")
    return cfg


def hidden(w, tokens, m, prec="fp32"):
    """Final normed hidden states (B, S, d) of token ids (B, S), and the
    term the architecture adds to the mean token loss (none here)."""
    B, S = tokens.shape
    pos = jnp.arange(S)
    h = w["embed"][tokens]
    for l in range(m["layers"]):
        x = rmsnorm(h, w["blocks/L0/attn_norm/scale"][l], m["eps"])
        q = mm("bsd,dnh->bsnh", x, w["blocks/L0/attn/wq"][l], prec)
        k = mm("bsd,dnh->bsnh", x, w["blocks/L0/attn/wk"][l], prec)
        v = mm("bsd,dnh->bsnh", x, w["blocks/L0/attn/wv"][l], prec)
        q, k = rope(q, pos, m["theta"]), rope(k, pos, m["theta"])
        o = attention(q, k, v, prec)
        h = h + mm("bsnh,nhd->bsd", o, w["blocks/L0/attn/wo"][l], prec)
        x = rmsnorm(h, w["blocks/L0/ffn_norm/scale"][l], m["eps"])
        a = jax.nn.silu(mm("bsd,df->bsf", x, w["blocks/L0/ffn/wg"][l], prec))
        a = a * mm("bsd,df->bsf", x, w["blocks/L0/ffn/wu"][l], prec)
        h = h + mm("bsf,fd->bsd", a, w["blocks/L0/ffn/wd"][l], prec)
    return rmsnorm(h, w["final_norm/scale"], m["eps"]), 0.0


def params_without_input_embedding(m: dict) -> int:
    """Parameters that do arithmetic per token: every weight but the
    input embedding table, which is a lookup (the unembedding counts)."""
    d, h, k, hd, ff, L, V = (m["d"], m["h"], m["k"], m["hd"], m["ff"],
                             m["layers"], m["vocab"])
    per_layer = d * h * hd + 2 * d * k * hd + h * hd * d + 3 * d * ff + 2 * d
    return L * per_layer + d + d * V


def train_flops_per_token(m: dict, seq: int) -> float:
    """6 N + 12 L d S (PaLM, arXiv:2204.02311, appendix B): forward and
    backward of every weight and of causal attention.  Recomputation
    under remat is not counted."""
    return (6.0 * params_without_input_embedding(m)
            + 12.0 * m["layers"] * m["h"] * m["hd"] * seq)


def decode_flops(m: dict, ctx: int) -> float:
    """One generated token at context length ``ctx`` (keys attended):
    2 N for the weights, 4 ctx H hd per layer for scores and values."""
    return (2.0 * params_without_input_embedding(m)
            + 4.0 * m["layers"] * m["h"] * m["hd"] * ctx)


def decode_cache_bytes(m: dict, n_keys: int, cache_bytes: int = 2) -> float:
    """What paged decode attention must read and write for one token of
    one sequence that attends ``n_keys`` positions: K and V of those
    positions, the query and the output, for every layer."""
    kv = 2.0 * n_keys * m["k"] * m["hd"] * cache_bytes
    qo = 2.0 * m["h"] * m["hd"] * cache_bytes
    return m["layers"] * (kv + qo)


def smoke(config):
    """The CPU rehearsal's cut: every width cut, grouped-query attention
    kept grouped."""
    kv = 2 if config["num_key_value_heads"] < \
        config["num_attention_heads"] else 4
    return dict(config, hidden_size=128, intermediate_size=256,
                num_attention_heads=4, num_key_value_heads=kv,
                vocab_size=512, program=dict(config["program"], smoke=True))
