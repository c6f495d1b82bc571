from repro.kernels import interpret_mode, record_launches
from repro.kernels.fused_lars.kernel import fused_lars_update


def lars_update(w, g, v, lr, **kw):
    record_launches(3)   # two _sqnorm passes + one fused update per tensor
    return fused_lars_update(w, g, v, lr,
                             interpret=interpret_mode(), **kw)
