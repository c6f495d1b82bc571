"""Run both hot paths once on a TPU, through the launchers a user calls.

    python3 chip_smoke.py               # one chip: device, train, serve
    python3 chip_smoke.py --four-chips  # one 2x2 host: sharded training
                                        # against the same steps on one chip

One process, nothing started beside it.  Phases, in order:

  device  exits non-zero unless JAX's first device is a TPU.
  train   ``repro.launch.train.main`` on yi-9b at its published widths,
          depth cut to one layer (the most one v5e chip holds at B=8,
          S=1024 with the resident SNGM state and remat), on the fused
          multi-tensor engine and then on the jnp reference
          (``--fused none``), a few steps each; the program handed to
          the compiler must hold the Pallas kernels as
          ``tpu_custom_call``s.  Then the update itself is held: one
          fused step is checkpointed, both engines resume from it for one
          step, and their parameters and momentum must agree leaf by leaf
          within UPDATE_RTOL of the step's own size.
  serve   ``repro.launch.serve.main`` on yi-9b cut to two layers: the
          paged engine, block size 16, 8 requests over 4 slots, with the
          Pallas paged decode kernel engaged.  One decode step is then
          run with the kernel and with the jnp gather path on the same
          cache; their logits must agree within SERVE_RTOL.

``--four-chips`` runs only ``train.main`` with ``--data-axis 2
--model-axis 2`` against ``--data-axis 1 --model-axis 1`` (one chip of
the host), compared as the train phase compares its two engines.

The last line of standard output is the result, and only when every
phase passed: ``{"ok": true, "device": {"platform", "kind", "count"}}``.
Outputs too long for the log (the train step's module, metrics) go to
``chiprun_out/chip_smoke/``; the checkpoints of the held update go to
``.chip_smoke_ckpt/`` and are removed after it.
"""
from __future__ import annotations

import argparse
import glob
import json
import math
import os
import shutil
import sys
import time
import types

HERE = os.path.dirname(os.path.abspath(__file__))
OUT = os.path.join(HERE, "chiprun_out", "chip_smoke")
CKPT = os.path.join(HERE, ".chip_smoke_ckpt")

# The platform the run must find.  Tests rehearse this script on the CPU
# backend by setting it (and the sizes below) on the imported module.
PLATFORM = "tpu"

# Sizes of the phases (yi-9b widths: d_model 4096, 32 q / 4 kv heads,
# d_ff 11008, vocabulary 64000).
TRAIN = dict(arch="yi-9b", n_layers=1, batch=8, seq=1024, n_micro=2,
             steps=4, lr=1.6, reduced=False)
FOUR_CHIP_TRAIN = dict(TRAIN, seq=256, steps=3)
SERVE = dict(arch="yi-9b", n_layers=2, slots=4, requests=8, prompt_len=24,
             max_new=16, block_size=16, reduced=False)

# Two programs (fused engine vs jnp, 2x2 mesh vs one chip) run the same
# math in another order, in bf16.  Every step's loss and gradient norm is
# held.  Measured on one v5e (yi-9b, 8x1024 tokens, fused vs jnp): loss
# within 1.2e-5 and gradient norm within 9.6e-6 over four steps, while a
# step moves the loss by 1e-3 to 2.5e-3; the 2x2 mesh (data-parallel
# micro-batches round their bf16 partial gradients differently) reached
# 6.7e-5 and 2.0e-4 in the CPU rehearsal.  The bounds sit above both and
# below one step's movement.
LOSS_RTOL = 2e-4
GNORM_RTOL = 1e-3
# Losses alone cannot tell a wrong update from a right one, so the update
# is held too: both programs resume from one checkpoint for one step and
# their parameters and momentum are compared leaf by leaf,
# ||a - b|| / ||b - start|| for the parameters and ||a - b|| / ||b|| for
# the momentum.  The step is lr * (beta * u + g / ||g||), so this reads
# the programs' bf16 gradient differences (1.3% between a bf16 and an
# fp32 gradient on one v5e; 7.2e-3 for the 2x2 mesh in the CPU
# rehearsal).  A planted beta = 0 in the fused kernel reads 0.67 (CPU
# rehearsal), a dropped normalization 0.89.
UPDATE_RTOL = 5e-2
# Paged kernel vs jnp gather path: kernels/paged_attention/kernel.py
# documents ~3e-2 between its online softmax and the two-pass reference
# on bf16 inputs (the model's compute dtype).  Measured on logits,
# relative to the largest logit magnitude.
SERVE_RTOL = 3e-2


class CompileClock:
    """Sums JAX's backend-compile durations (a persistent-cache hit
    counts its load time) and counts cache hits, per phase."""

    def __init__(self):
        import jax
        self.secs, self.hits = 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, secs, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.secs += secs

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def take(self):
        out = (self.secs, self.hits)
        self.secs, self.hits = 0.0, 0
        return out


def check_device(count=None):
    import jax
    devs = jax.devices()
    d = devs[0]
    if d.platform != PLATFORM:
        raise SystemExit(f"chip_smoke: found {d.platform!r} devices "
                         f"({len(devs)}), needs {PLATFORM!r}")
    if count is not None and len(devs) != count:
        raise SystemExit(f"chip_smoke: found {len(devs)} devices, "
                         f"needs {count}")
    import jaxlib
    from importlib.metadata import PackageNotFoundError, version
    try:
        libtpu = version("libtpu")
    except PackageNotFoundError:          # printed as such
        libtpu = "not installed"
    print(f"[device] {d.platform} {d.device_kind} x{len(devs)}; "
          f"jax {jax.__version__}, jaxlib {jaxlib.__version__}, "
          f"libtpu {libtpu}")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


def _rel(a, b):
    return abs(a - b) / max(abs(b), 1e-30)


def run_train(tag, size, extra, steps=None):
    """One ``train.main`` run; returns the metric records of the steps it
    ran (``steps`` overrides the size's, e.g. to stop after one)."""
    from repro.launch import train
    from repro.tracker import read_jsonl
    path = os.path.join(OUT, f"train_{tag}.jsonl")
    if os.path.exists(path):
        os.remove(path)
    steps = size["steps"] if steps is None else steps
    argv = ["--arch", size["arch"], "--n-layers", str(size["n_layers"]),
            "--batch", str(size["batch"]), "--seq", str(size["seq"]),
            "--n-micro", str(size["n_micro"]), "--steps", str(steps),
            "--optimizer", "sngm", "--lr", str(size["lr"]),
            "--log-every", "1",
            "--metrics-jsonl", path] + (["--reduced"] if size["reduced"]
                                        else []) + extra
    print(f"[train:{tag}] train.main {' '.join(argv)}")
    train.main(argv)
    recs = [r for r in read_jsonl(path) if "step" in r]
    for r in recs:
        assert math.isfinite(r["loss"]) and math.isfinite(r["grad_norm"]), r
    return recs


def compare_steps(name, got, ref):
    """Loss and gradient norm of two programs, step by step."""
    assert [r["step"] for r in got] == [r["step"] for r in ref], (got, ref)
    worst = {"loss": 0.0, "grad_norm": 0.0}
    for a, b in zip(got, ref):
        gaps = {k: _rel(a[k], b[k]) for k in worst}
        worst = {k: max(worst[k], gaps[k]) for k in worst}
        print(f"[{name}] step {a['step']}: loss {a['loss']!r} vs "
              f"{b['loss']!r} gap {gaps['loss']:.3e}; grad_norm "
              f"{a['grad_norm']!r} vs {b['grad_norm']!r} gap "
              f"{gaps['grad_norm']:.3e}")
    print(f"[{name}] largest gaps: loss {worst['loss']:.3e} (bound "
          f"{LOSS_RTOL}), grad_norm {worst['grad_norm']:.3e} (bound "
          f"{GNORM_RTOL})")
    assert worst["loss"] <= LOSS_RTOL, (name, worst)
    assert worst["grad_norm"] <= GNORM_RTOL, (name, worst)


def _norm(x):
    import numpy as np
    return float(np.sqrt(np.sum(np.square(x, dtype=np.float64))))


def _held_runs(name, size, base_extra, extra_a, extra_b):
    import numpy as np
    base = os.path.join(CKPT, "base")
    run_train(f"{name}_base", size, base_extra + [
        "--ckpt", base, "--total-steps", str(size["steps"])], steps=1)
    recs, shard = {}, "shard_00000.npz"
    for tag, extra in (("a", extra_a), ("b", extra_b)):
        # hard links: the resumed run replaces its checkpoint directory
        # with a new one, so the base's files stay as they were
        path = os.path.join(CKPT, tag)
        shutil.copytree(base, path, copy_function=os.link)
        (recs[tag],) = run_train(f"{name}_resumed_{tag}", size,
                                 extra + ["--ckpt", path, "--resume"],
                                 steps=2)
    gaps = {}
    with np.load(os.path.join(base, shard)) as z0, \
            np.load(os.path.join(CKPT, "a", shard)) as za, \
            np.load(os.path.join(CKPT, "b", shard)) as zb:
        for k in zb.files:
            if k.startswith("params/"):        # relative to the step
                b = zb[k]
                gaps[k] = _norm(za[k] - b) / max(_norm(b - z0[k]), 1e-30)
            elif k.startswith("opt/.momentum/"):
                b = zb[k]
                gaps[k] = _norm(za[k] - b) / max(_norm(b), 1e-30)
    return recs, gaps


def held_update(name, size, base_extra, extra_a, extra_b):
    """One step from init (``base_extra``) is checkpointed; ``extra_a``
    and ``extra_b`` resume from it for one step each.  Holds their step
    records at that one state and their parameters and momentum after it,
    leaf by leaf."""
    shutil.rmtree(CKPT, ignore_errors=True)
    os.makedirs(CKPT)
    try:
        recs, gaps = _held_runs(name, size, base_extra, extra_a, extra_b)
    finally:
        shutil.rmtree(CKPT, ignore_errors=True)
    compare_steps(f"{name}:resumed", [recs["a"]], [recs["b"]])
    for k in sorted(gaps, key=gaps.get, reverse=True)[:4]:
        print(f"[{name}:update] {k}: gap {gaps[k]:.3e} of the "
              f"{'step' if k.startswith('params/') else 'momentum'}")
    worst = max(gaps.values())
    print(f"[{name}:update] largest per-leaf gap after one step from one "
          f"checkpoint: {worst:.3e} (bound {UPDATE_RTOL}) over "
          f"{len(gaps)} leaves")
    assert worst <= UPDATE_RTOL, (name, worst)


def step_time(recs):
    ts = [r["step_time_s"] for r in recs[1:]]       # step 0 compiles
    return sum(ts) / len(ts)


def peak_bytes():
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def count_kernels(ir_dir):
    """tpu_custom_calls in the train step's module as JAX hands it to the
    compiler.  JAX writes it before the persistent-cache lookup, so it is
    there on a cache hit too (XLA's own dump is not)."""
    paths = glob.glob(os.path.join(ir_dir, "*jit_train_step_compile.mlir"))
    assert len(paths) == 1, paths
    for p in glob.glob(os.path.join(ir_dir, "*")):
        if p not in paths:
            os.remove(p)
    with open(paths[0]) as f:
        return f.read().count("tpu_custom_call")


def train_phase(clock):
    import jax
    ir_dir = os.path.join(OUT, "ir")
    shutil.rmtree(ir_dir, ignore_errors=True)
    jax.config.update("jax_dump_ir_to", ir_dir)
    try:
        fused = run_train("multi_tensor", TRAIN, ["--fused", "multi_tensor"])
    finally:
        jax.config.update("jax_dump_ir_to", "")
    secs, hits = clock.take()
    print(f"[train:multi_tensor] compile {secs:.2f}s, cache hits {hits}; "
          f"step {step_time(fused) * 1e3:.1f} ms (steps 1..; step 0 "
          f"compiles); peak_bytes_in_use {peak_bytes()}")
    n_kernels = count_kernels(ir_dir)
    print(f"[train:multi_tensor] train step module holds {n_kernels} "
          f"tpu_custom_call(s)")
    if PLATFORM == "tpu":
        # chunk_sumsq + fused_update for the one fp32 bucket
        assert n_kernels >= 2, n_kernels

    ref = run_train("jnp", TRAIN, ["--fused", "none"])
    secs, hits = clock.take()
    print(f"[train:jnp] compile {secs:.2f}s, cache hits {hits}; "
          f"step {step_time(ref) * 1e3:.1f} ms; "
          f"peak_bytes_in_use {peak_bytes()}")
    compare_steps("train", fused, ref)
    fused_args = ["--fused", "multi_tensor"]
    held_update("train", TRAIN, fused_args, fused_args, ["--fused", "none"])
    secs, hits = clock.take()
    print(f"[train:update] compile {secs:.2f}s, cache hits {hits}")


def four_chip_phase(clock):
    mesh = ["--fused", "multi_tensor", "--data-axis", "2", "--model-axis",
            "2"]
    one = ["--fused", "multi_tensor", "--data-axis", "1", "--model-axis",
           "1"]
    sharded = run_train("mesh2x2", FOUR_CHIP_TRAIN, mesh)
    secs, hits = clock.take()
    print(f"[train:mesh2x2] compile {secs:.2f}s, cache hits {hits}; "
          f"step {step_time(sharded) * 1e3:.1f} ms")
    single = run_train("one_chip", FOUR_CHIP_TRAIN, one)
    secs, hits = clock.take()
    print(f"[train:one_chip] compile {secs:.2f}s, cache hits {hits}; "
          f"step {step_time(single) * 1e3:.1f} ms")
    compare_steps("mesh2x2", sharded, single)
    held_update("mesh2x2", FOUR_CHIP_TRAIN, one, mesh, one)


def serve_phase(clock):
    import jax
    import numpy as np

    from repro.launch import serve
    from repro.launch.common import model_config
    from repro.models import layers, model_defs
    from repro.models.param import materialize
    from repro.models.runtime import CPU_RUNTIME
    from repro.serving import make_serve_step
    from repro.serving.paged_cache import n_blocks_for
    from repro.serving.scheduler import PagedScheduler, ServeRequest

    # auto routing: the kernel on TPU, the gather path on the CPU backend
    layers.PAGED_DECODE_KERNEL = None
    assert layers._use_paged_kernel() == (PLATFORM == "tpu")
    s = SERVE
    argv = ["--arch", s["arch"], "--n-layers", str(s["n_layers"]),
            "--engine", "paged", "--block-size", str(s["block_size"]),
            "--slots", str(s["slots"]), "--requests", str(s["requests"]),
            "--prompt-len", str(s["prompt_len"]),
            "--max-new", str(s["max_new"])] + (["--reduced"] if s["reduced"]
                                               else [])
    print(f"[serve] serve.main {' '.join(argv)}")
    finished = serve.main(argv)
    secs, hits = clock.take()
    assert len(finished) == s["requests"], len(finished)
    for r in sorted(finished, key=lambda r: r.rid):
        assert len(r.out) == s["max_new"], (r.rid, len(r.out))
        print(f"[serve] request {r.rid}: {len(r.out)} tokens "
              f"{r.out[:6]}..., first token "
              f"{(r.t_first - r.t_submit) * 1e3:.1f} ms, done "
              f"{(r.t_done - r.t_submit) * 1e3:.1f} ms")
    print(f"[serve] compile {secs:.2f}s, cache hits {hits}")

    # one decode step, kernel vs gather path, on the same paged cache
    cfg = model_config(types.SimpleNamespace(
        arch=s["arch"], reduced=s["reduced"], n_layers=s["n_layers"]),
        "serve")
    params = materialize(model_defs(cfg), jax.random.PRNGKey(0))
    ctx = s["prompt_len"] + s["max_new"]
    sched = PagedScheduler(
        cfg, params, CPU_RUNTIME, n_slots=s["slots"],
        block_size=s["block_size"],
        n_blocks=1 + s["slots"] * n_blocks_for(ctx, s["block_size"]),
        ctx_max=ctx)
    rng = np.random.RandomState(1)
    for i in range(s["slots"]):
        sched.submit(ServeRequest(rid=i, max_new=s["max_new"], prompt=rng
                                  .randint(0, cfg.vocab_size,
                                           (s["prompt_len"],))
                                  .astype(np.int32)))
    sched.admit()
    logits = {}
    for use_kernel in (True, False):
        layers.PAGED_DECODE_KERNEL = use_kernel
        step = make_serve_step(cfg, CPU_RUNTIME)
        exe = jax.jit(lambda p, c, t, q: step(p, c, t, q)).lower(
            params, sched.paged, sched.tok, sched.pos).compile()
        n = exe.as_text().count('custom_call_target="tpu_custom_call"')
        print(f"[serve] decode step, paged kernel {use_kernel}: "
              f"{n} tpu_custom_call(s)")
        if PLATFORM == "tpu":
            assert (n > 0) == use_kernel, (use_kernel, n)
        _, lg, _ = exe(params, sched.paged, sched.tok, sched.pos)
        logits[use_kernel] = np.asarray(lg, np.float32)
    layers.PAGED_DECODE_KERNEL = None
    k, g = logits[True], logits[False]
    assert np.all(np.isfinite(k)) and k.shape == g.shape, k.shape
    gap = float(np.max(np.abs(k - g)) / np.max(np.abs(g)))
    agree = float(np.mean(np.argmax(k, -1) == np.argmax(g, -1)))
    print(f"[serve] decode logits {k.shape}: kernel vs gather largest gap "
          f"{gap:.3e} of max |logit| (bound {SERVE_RTOL}), argmax "
          f"agreement {agree:.2f}")
    assert gap <= SERVE_RTOL, gap


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded 2x2 training path and the "
                         "one-chip run it is compared with")
    args = ap.parse_args(argv)
    device = check_device(4 if args.four_chips else None)

    sys.path.insert(0, os.path.join(HERE, "src"))
    from repro.launch.common import enable_compile_cache
    print(f"[cache] {enable_compile_cache()}")
    os.makedirs(OUT, exist_ok=True)
    clock = CompileClock()
    t0 = time.perf_counter()
    if args.four_chips:
        four_chip_phase(clock)
    else:
        train_phase(clock)
        serve_phase(clock)
    print(f"[done] phases took {time.perf_counter() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()
