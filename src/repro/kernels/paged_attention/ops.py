"""jit'd wrapper: Pallas paged decode attention on TPU, interpret mode
on the CPU backend (the kernel body runs in Python)."""
from repro.kernels import interpret_mode
from repro.kernels.paged_attention.kernel import paged_decode_attention


def paged_attention(q, kp, vp, bt, pos, **kw):
    return paged_decode_attention(q, kp, vp, bt, pos,
                                  interpret=interpret_mode(), **kw)
