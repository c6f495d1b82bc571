import os
import sys

import pytest

# tests see ONE device (the dry-run sets its own 512-device flag in a
# separate process); keep any user XLA_FLAGS out of the way.
os.environ.setdefault("JAX_PLATFORMS", "cpu")
# XLA:CPU codegen without FMA instructions (AVX has none): whether LLVM
# contracts a*b+c into one fma, and which product it folds, depends on
# the fusion around the expression, so the fused engine's interpret-mode
# kernels and the jnp reference could round the same update differently
# in the last ulp.  Without FMA both round every product and sum.
if "--xla_cpu_max_isa" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_cpu_max_isa=AVX").strip()

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
# repo root too, so the benchmarks/ namespace package (bench harness,
# artifact schema, check_bench gate) is importable from the suite
sys.path.insert(1, os.path.join(os.path.dirname(__file__), ".."))

# Parametrized cases that individually exceed ~10s on the CI CPU runner.
# Whole long-running modules carry ``pytestmark = pytest.mark.slow`` instead;
# this hook catches the heavyweight archs inside otherwise-fast sweeps so the
# tier-1 lane (``pytest -m "not slow"``) stays well under a minute.
_SLOW_PARAM_TOKENS = (
    "jamba-1.5-large-398b",
    "gemma2-27b",
    "whisper-large-v3",
    "deepseek-v2-236b",
    "deepseek-v2-lite-16b",
    "chameleon-34b",
    "mamba2-1.3b",
    "yi-9b",
    "512-4-2-64",   # longest flash-attention sweep cases
)


def pytest_collection_modifyitems(config, items):
    for item in items:
        if any(tok in item.nodeid for tok in _SLOW_PARAM_TOKENS):
            item.add_marker(pytest.mark.slow)
