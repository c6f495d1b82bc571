"""Peaks of the chips the benchmark runs on, and the operations and bytes
the measured work needs, computed from its shapes.

Peaks come from one table keyed by ``device_kind``; a kind that is not
in it is an error, never a default.  The cost functions count the work
the algorithm requires, not what an implementation happens to do, so a
share of a peak reads the same whatever implements the work; each
architecture's module in ``bench/archs/`` counts its own, found by
``m["arch"]``."""
from __future__ import annotations

from bench import common

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


def train_flops_per_token(m: dict, seq: int) -> float:
    """FLOPs of one trained token at sequence length ``seq``: forward and
    backward of the weights and of causal attention, as the
    architecture's module counts them.  Recomputation is not counted."""
    return common.arch_named(m["arch"]).train_flops_per_token(m, seq)


def sngm_min_bytes(n_params: int) -> int:
    """The least an SNGM update moves: read gradient, parameter and
    momentum and write parameter and momentum, all float32."""
    return 20 * n_params


def decode_flops(m: dict, ctx: int) -> float:
    """One generated token at context length ``ctx`` (keys attended), as
    the architecture's module counts it."""
    return common.arch_named(m["arch"]).decode_flops(m, ctx)


def paged_decode_bytes(m: dict, n_keys: int, cache_bytes: int = 2) -> float:
    """What paged decode attention must read and write for one token of
    one sequence that attends ``n_keys`` positions, as the architecture's
    module counts it (its cache of those positions, query and output)."""
    return common.arch_named(m["arch"]).decode_cache_bytes(m, n_keys,
                                                          cache_bytes)
