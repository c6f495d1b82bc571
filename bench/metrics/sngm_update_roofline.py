"""The SNGM update kernels' share of their HBM roofline: the least time
an SNGM update can take (20 bytes per parameter at the HBM peak) over
the device time per step of the optimizer engine's Pallas kernels."""
from bench import peaks

KERNELS = ("chunk_sumsq", "fused_update")


def read(run):
    from bench import trace
    steps = run["trace"]["modules"].get("jit_train_step", 0.0)
    secs = trace.kernel_seconds(run["trace"], KERNELS)
    if not steps or not secs:
        return None
    least = (peaks.sngm_min_bytes(run["n_params"])
             / peaks.peaks(run["kind"])["hbm_bytes_per_s"])
    return 100.0 * least / (secs / steps)
