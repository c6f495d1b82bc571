"""jit'd wrapper: Pallas flash attention on TPU, interpret mode on the
CPU backend."""
from repro.kernels import interpret_mode
from repro.kernels.flash_attention.kernel import flash_attention


def attention(q, k, v, **kw):
    return flash_attention(q, k, v, interpret=interpret_mode(), **kw)
