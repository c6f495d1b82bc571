"""Device milliseconds per train step of the MoE layers' routing and its
bookkeeping (router, top-k, balance loss, the sort by expert, the gather
of rows and the weighted scatter back): the self time of the ops under
the ``moe_route`` scope."""

SCOPE = "moe_route"


def read(run):
    from bench import scope_time
    steps = run["trace"]["modules"].get("jit_train_step", 0.0)
    secs = steps and scope_time.seconds(run, SCOPE)
    if not secs:
        return None
    return 1e3 * secs / steps
