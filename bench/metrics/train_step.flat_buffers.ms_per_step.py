"""Device milliseconds per train step of the flat-buffer work round the
optimizer engine's kernels: the step's ``params_view``, ``grad_pack`` and
``grad_accum`` phases, and its ``sngm_update`` phase less the engine's
Pallas kernels (the squared-norm pass and the fused update), by the
device self time of each op's scope in the traced window."""

PHASES = ("params_view", "grad_pack", "grad_accum", "sngm_update")
KERNELS = ("chunk_sumsq", "fused_update")


def read(run):
    from bench import phases, trace
    got = phases.find(run)
    steps = run["trace"]["modules"].get("jit_train_step", 0.0)
    scope = (got or {}).get("scope_s", {}).get("jit_train_step", {})
    if not steps or not any(scope.get(p) for p in PHASES):
        return None
    secs = (sum(scope.get(p, 0.0) for p in PHASES)
            - trace.kernel_seconds(run["trace"], KERNELS))
    return 1e3 * secs / steps
