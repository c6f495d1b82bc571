"""Device milliseconds per train step of the optimizer engine's Pallas
kernels (the squared-norm pass and the fused update)."""

KERNELS = ("chunk_sumsq", "fused_update")


def read(run):
    from bench import trace
    steps = run["trace"]["modules"].get("jit_train_step", 0.0)
    secs = trace.kernel_seconds(run["trace"], KERNELS)
    if not steps or not secs:
        return None
    return 1e3 * secs / steps
