"""Training traffic: the launcher's jitted, donated train step
(``make_train_step`` over ``make_optimizer(spec)``, resident
``TrainState``), fed a bigram-chain token stream made on the device from
the seed.

Set-up builds the step and its state once, and drives them through the
first ``check_steps`` steps with the window's own call and feed; the
window then continues with the same object.  ``correct`` compares those
first steps with the plain reference: each step's loss, each weight's
share of the first gradient as the optimizer took it (its momentum after
one step, times the step's reported norm), and each weight's change
after the first steps."""
from __future__ import annotations

import os

import numpy as np

from bench import common, reference, trace, weights

TRACE_SECONDS = 4.0


def smoke(mix):
    """The CPU rehearsal's cut of the mix: a small batch of short rows.
    ``change_gap`` was read at this size on the CPU (program against the
    fp32 reference, and the fp8 control): bf16 rounding weighs more at
    smoke widths than at the cell's, so the cell's own limit would not
    fit."""
    return dict(mix, batch=4, seq=64, reference_rows=2,
                limits=dict(mix["limits"], change_gap=2e-3))


def bigram_stream(key, vocab, batch, seq, branching):
    """batch(t) -> {"tokens", "loss_mask"}: each row walks a random
    bigram chain (every token has ``branching`` successors, fixed by the
    seed) from its own random start; one jitted program for every t."""
    import jax
    import jax.numpy as jnp
    table = jax.random.randint(jax.random.fold_in(key, 0),
                               (vocab, branching), 0, vocab, jnp.int32)

    # key and table are arguments, not constants of the program, so one
    # compiled program serves every seed
    @jax.jit
    def make(key, table, t):
        k0, k1 = jax.random.split(jax.random.fold_in(
            jax.random.fold_in(key, 1), t))
        tok0 = jax.random.randint(k0, (batch,), 0, vocab, jnp.int32)
        ch = jax.random.randint(k1, (seq, batch), 0, branching, jnp.int32)

        def walk(tok, c):
            return table[tok, c], tok
        _, toks = jax.lax.scan(walk, tok0, ch)
        return {"tokens": toks.T,
                "loss_mask": jnp.ones((batch, seq), jnp.float32)}
    return lambda t: make(key, table, jnp.int32(t))


def _leaf_norms(tree):
    import jax
    import jax.numpy as jnp
    leaves = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {weights.path_name(p): jnp.sqrt(jnp.sum(jnp.square(
        x.astype(jnp.float32)))) for p, x in leaves}


class Job:
    """The program under test for one seed: its step, state and feed."""

    def __init__(self, config, mix, seed):
        import jax
        from repro.core.optim import (OptimizerSpec, TrainState,
                                      make_optimizer)
        from repro.models import model_defs
        from repro.models.param import abstract
        from repro.models.runtime import Runtime
        from repro.training import make_train_step

        self.config, self.mix, self.seed = config, mix, seed
        self.arch = common.arch(config)
        self.m = self.arch.dims(config)
        self.cfg = self.arch.program_config(config)
        self.wkey = common.seed_key(seed, 1)
        flat = weights.make_all(config, self.wkey)
        params = weights.to_program_tree(flat, abstract(model_defs(
            self.cfg)))
        rt = Runtime(mesh=None, data_axes=("data",),
                     remat=config["program"]["remat"])
        # the launcher's spec (launch/train.py) for --optimizer sngm
        spec = OptimizerSpec("sngm", {
            "schedule": {"name": "poly_power", "kwargs": {
                "lr0": mix["lr"], "total_steps": mix["total_steps"],
                "power": 1.1}},
            "beta": mix["beta"], "weight_decay": mix["weight_decay"],
            "nesterov": False, "fused": mix["fused"]})
        opt = make_optimizer(spec)
        self.state = TrainState.wrap(params, opt.init(params))
        del params
        self.step = jax.jit(make_train_step(self.cfg, rt, opt,
                                            n_micro=mix["n_micro"]),
                            donate_argnums=(0,))
        self.batch = bigram_stream(common.seed_key(seed, 2),
                                   self.m["vocab"], mix["batch"],
                                   mix["seq"], mix["branching"])
        self.t = 0

    def advance(self):
        self.state, stats = self.step(self.state, self.batch(self.t))
        self.t += 1
        return stats

    def first_steps(self):
        """The first ``check_steps`` steps, and what they leave to
        compare: losses, the first gradient per weight, the change."""
        import jax
        import jax.numpy as jnp
        n = self.mix["check_steps"]
        losses = []
        stats = self.advance()
        losses.append(stats["loss"])
        # per-weight norms of the momentum, read inside one program so no
        # unflattened copy of it outlives the call
        norms = jax.jit(lambda st: _leaf_norms(st.opt_state.momentum))(
            self.state)
        grad = {k: v * stats["grad_norm"] for k, v in norms.items()}
        for _ in range(n - 1):
            losses.append(self.advance()["loss"])

        sp = self.arch.spec(self.config)

        @jax.jit
        def change(state, key):
            out = {}
            flat = jax.tree_util.tree_flatten_with_path(state.params_view)[0]
            for p, x in flat:
                name = weights.path_name(p)
                shape, std = sp[name]
                w0 = weights.make_leaf(key, name, shape, std, x.dtype)
                out[name] = jnp.sqrt(jnp.sum(jnp.square(
                    (x - w0).astype(jnp.float32))))
            return out
        moved = change(self.state, self.wkey)
        return {"loss": [float(x) for x in losses],
                "grad": {k: float(v) for k, v in grad.items()},
                "change": {k: float(v) for k, v in moved.items()}}

    def free(self):
        import jax
        for x in jax.tree.leaves(self.state):
            x.delete()
        self.state = None


def reference_steps(config, mix, seed, prec="fp32", rows=None):
    """The same first steps by the plain reference (or, for a control,
    by it in a lower precision; ``rows`` keeps only the first rows of
    each batch, to read the fault of a step that drops the rest)."""
    import jax
    import jax.numpy as jnp
    m = common.arch(config).dims(config)
    wkey = common.seed_key(seed, 1)
    batch = bigram_stream(common.seed_key(seed, 2), m["vocab"],
                          mix["batch"], mix["seq"], mix["branching"])
    w = weights.make_all(config, wkey)
    u = jax.tree.map(jnp.zeros_like, w)
    losses, grad, raw = [], None, None
    for t in range(mix["check_steps"]):
        tokens = batch(t)["tokens"][:rows]
        loss, g = reference.loss_and_grad(w, tokens, m, prec,
                                          rows=mix["reference_rows"])
        losses.append(loss)
        if t == 0:
            wd = mix["weight_decay"]
            norms = jax.jit(lambda g, w: (
                {k: jnp.sqrt(jnp.sum(jnp.square(g[k] + wd * w[k])))
                 for k in g},
                {k: jnp.sqrt(jnp.sum(jnp.square(g[k]))) for k in g}))(g, w)
            grad = {k: float(v) for k, v in norms[0].items()}
            raw = {k: float(v) for k, v in norms[1].items()}
        lr = reference.poly_power(mix["lr"], mix["total_steps"], 1.1, t)
        w, u, _ = reference.sngm_step(w, g, u, jnp.float32(lr),
                                      beta=mix["beta"],
                                      wd=mix["weight_decay"])
        del g
    del u
    change = {}
    for name in list(w):
        w0 = weights.make_one(config, wkey, name)
        change[name] = float(jnp.sqrt(jnp.sum(jnp.square(w[name] - w0))))
        del w0
    del w
    return {"loss": losses, "grad": grad, "change": change, "raw": raw}


def compare(got, ref):
    """The three numbers compared, each by its worst case (see the
    module docstring); weights whose reference gradient is under a
    thousandth of the median weight's move by rounding alone and are
    left out of the per-weight numbers."""
    loss = max(abs(a - b) / abs(b) for a, b in zip(got["loss"], ref["loss"]))
    med_raw = float(np.median(list(ref["raw"].values())))
    keep = [k for k, v in ref["raw"].items() if v >= 1e-3 * med_raw]

    def worst(key):
        r = ref[key]
        med = float(np.median([r[k] for k in keep]))
        return max(abs(got[key][k] - r[k]) / max(r[k], med) for k in keep)
    return {"loss_gap": loss, "grad_gap": worst("grad"),
            "change_gap": worst("change")}


def judge(readings, limits, checks):
    ok = True
    for name, value in readings.items():
        ok &= common.check(name, value, limits[name], checks)
    return ok


def run(ctx):
    import jax
    args, config, mix = ctx["args"], ctx["config"], ctx["mix"]
    devs = ctx["devs"]
    job = Job(config, mix, args.seed)
    got = job.first_steps()
    jax.block_until_ready(job.state)
    setup_s = common.now() - ctx["t_start"]
    n_c, secs_c, hits = ctx["clock"].snapshot()
    common.log(f"[setup] {setup_s:.1f}s: {n_c} compiles ({hits} from the "
               f"cache) took {secs_c:.1f}s")
    tokens_per_step = mix["batch"] * mix["seq"]

    def window(seconds):
        """Steps until ``seconds`` have passed, at most two in flight;
        returns (steps, seconds) of all work, ending in the device.  The
        spans cost next to nothing when no trace is taken."""
        pending = []
        n, t0 = 0, common.now()
        while common.now() - t0 < seconds or not n:
            with jax.profiler.TraceAnnotation("bench.dispatch"):
                pending.append(job.advance()["loss"])
            n += 1
            if len(pending) > 2:
                with jax.profiler.TraceAnnotation("bench.sync"):
                    pending.pop(0).block_until_ready()
        with jax.profiler.TraceAnnotation("bench.sync"):
            jax.block_until_ready(job.state)
        return n, common.now() - t0

    compiles0 = ctx["clock"].snapshot()[0]
    metrics, breakdown, device = {}, None, common.device_info(devs)
    if args.trace:
        tdir = os.path.join(common.OUT_DIR, "traces",
                            f"{args.workload}-{args.seed}")
        with trace.traced(tdir):
            n, secs = window(min(args.seconds, TRACE_SECONDS))
        red = trace.reduce(trace.load(tdir), len(devs))
        run_ = {"trace": red, "dims": job.m, "mix": mix, "config": config,
                "kind": devs[0].device_kind, "chips": len(devs),
                "n_params": weights.n_params(config)}
        for m in ctx["per_layer"]:
            v = common.reader(m["name"]).read(run_)
            if v is not None:
                metrics[m["name"]] = common.metric(v, m["unit"])
        device.update(busy_s=red["busy_s"], window_s=red["window_s"])
        breakdown = trace.breakdown(red)
    else:
        n, secs = window(args.seconds)
        metrics["train_tokens_per_s"] = common.metric(
            n * tokens_per_step / secs, "tokens/s")
        metrics["setup_s"] = common.metric(setup_s, "s")
    common.log(f"window: {n} steps in {secs:.3f}s, "
               f"{ctx['clock'].snapshot()[0] - compiles0} compiles inside")
    device["memory_peak_bytes"] = common.peak_bytes(devs)
    job.free()
    ref = reference_steps(config, mix, args.seed)
    readings = compare(got, ref)
    checks = {}
    ok = judge(readings, mix["limits"], checks)
    common.emit(ok, n, 0, metrics, device, checks, breakdown)
