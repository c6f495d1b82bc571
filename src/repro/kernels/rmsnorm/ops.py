"""jit'd wrapper: Pallas on TPU, interpret mode on the CPU backend."""
from repro.kernels import interpret_mode
from repro.kernels.rmsnorm.kernel import rmsnorm_pallas


def rmsnorm(x, scale, eps: float = 1e-6):
    return rmsnorm_pallas(x, scale, eps, interpret=interpret_mode())
