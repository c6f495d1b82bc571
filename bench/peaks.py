"""Peaks of the chips the benchmark runs on, and the operations and bytes
the measured work needs, computed from its shapes.

Peaks come from one table keyed by ``device_kind``; a kind that is not
in it is an error, never a default.  The cost functions count the work
the algorithm requires, not what an implementation happens to do, so a
share of a peak reads the same whatever implements the work."""
from __future__ import annotations

# Google Cloud documentation, "TPU v5e" (system architecture): per chip
# 197 TFLOP/s bf16, 16 GB HBM at 819 GB/s, 1,600 Gbit/s interchip links.
PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9, "ici_bits_per_s": 1600e9,
                    "source": "Google Cloud documentation, TPU v5e"},
}


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no peaks known for device kind {device_kind!r}; "
                       f"add it to bench/peaks.py with its source")
    return PEAKS[device_kind]


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


def sngm_min_bytes(n_params: int) -> int:
    """The least an SNGM update moves: read gradient, parameter and
    momentum and write parameter and momentum, all float32."""
    return 20 * n_params


def decode_flops(m: dict, ctx: int) -> float:
    """One generated token at context length ``ctx`` (keys attended):
    2 N for the weights, 4 ctx H hd per layer for scores and values."""
    return (2.0 * params_without_input_embedding(m)
            + 4.0 * m["layers"] * m["h"] * m["hd"] * ctx)


def paged_decode_bytes(m: dict, n_keys: int, cache_bytes: int = 2) -> float:
    """What paged decode attention must read and write for one token of
    one sequence that attends ``n_keys`` positions: K and V of those
    positions, the query and the output, for every layer."""
    kv = 2.0 * n_keys * m["k"] * m["hd"] * cache_bytes
    qo = 2.0 * m["h"] * m["hd"] * cache_bytes
    return m["layers"] * (kv + qo)
