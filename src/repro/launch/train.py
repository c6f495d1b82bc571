"""Production training launcher.

On real hardware this runs under `jax.distributed.initialize()` with the
production mesh; on the CPU container it runs the same code path on a
host mesh (all devices present).  The step function, sharding rules and
optimizer are identical to the dry-run's — `dryrun.py` IS this launcher's
compile-only mode.

    PYTHONPATH=src python -m repro.launch.train --arch yi-9b \
        --steps 50 --batch 8 --seq 128 --reduced

``--n-layers N`` cuts depth at the published widths (what fits one
chip: ``--arch yi-9b --n-layers 1`` at B=8, S=1024).

Memory residency: the training loop threads ONE donated ``TrainState``
through ``jax.jit(step, donate_argnums=(0,))``.  On the resident fast
path (``--fused multi_tensor``) the flat buffers are the single owner of
the parameters — device memory holds ~1x parameter bytes instead of the
2x the old (params pytree, FlatOptState) pairing kept live — and XLA
aliases params/momentum/moments in place across steps (README: "Memory
residency & donation").

Checkpoint/resume: ``--ckpt DIR`` saves {"params", "opt"} at the end,
reading both from the live ``TrainState`` (atomic commit: temp dir +
rename + ``COMMIT`` marker); ``--resume`` restores from DIR (either
optimizer state form — OptState pytree or flat-buffer-resident
FlatOptState), rejects torn saves without the marker, and continues from
the saved step, with ``--total-steps`` pinning the schedule horizon
across the save/resume split (README: "Checkpoint format and resume").
``--save-every K`` switches to periodic step-named saves under DIR
(``step_00000010/`` + ``latest``/retention via ``--keep-last-n``), and
``--async-save`` moves the commit I/O off the training thread
(``AsyncCheckpointer``: the step pays only the device→host copy).
``--resume`` accepts either layout — ``resolve_checkpoint`` follows
``latest`` when DIR is the base of a step-named family.

Data: the default input is the synthetic ``batch_at(t)`` stream.
``--data-dir`` trains from an on-disk ``repro-data-pack`` dataset
through the ``StreamingLoader`` (per-process sharded, seekable) with
``--prefetch``-deep host→device prefetch; the loader cursor
(``LoaderState``) rides every checkpoint, so ``--resume`` re-seeks the
stream and batch ``t`` after resume is bitwise the batch ``t`` of an
uninterrupted run (README: "Data pipeline & resumable input").
"""
from __future__ import annotations

import argparse
import json
import os

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.checkpoint import (AsyncCheckpointer, check_loadable,
                              load_checkpoint, load_loader_state,
                              resolve_checkpoint, save_checkpoint, step_dir)
from repro.core import make_optimizer
from repro.core.optim import (FlatOptState, OptState, OptimizerSpec,
                              TrainState, builder_accepts, from_pytree,
                              optimizer_names, to_pytree)
from repro.core.transform import ChainOptState, place_chain_state
from repro.data import (DiskShardedSource, LoaderState, PrefetchIterator,
                        StreamingLoader, SyntheticLM, device_put_batch)
from repro.launch.common import (add_model_args, enable_compile_cache,
                                 model_config)
from repro.launch.mesh import (data_axes_of, init_distributed,
                               is_main_process, make_train_mesh,
                               process_count)
from repro.models import model_defs
from repro.models.param import count, materialize
from repro.models.runtime import Runtime
from repro.sharding import batch_spec, param_shardings, param_specs
from repro.tracker import (CompositeTracker, JsonlTracker, MemoryTracker,
                           StdoutTracker)
from repro.tracker.callbacks import PrefetchMonitor, StepTimer
from repro.training import make_train_step, run_steps


def _restore(path: str, params, state, mesh=None):
    """Restore {"params", "opt"} into the shape-only templates ``params``
    and ``state`` regardless of which STATE FORM the
    checkpoint holds (pytree form — OptState, or a ChainOptState from
    lamb / a segment-compiled chain — vs flat-buffer-resident
    FlatOptState): detect the saved form from the archive's key set, load
    via a matching template, and convert to the live form with
    to_pytree/from_pytree (both lossless, including the Adam-moment
    slots of a fused-lamb FlatOptState and the EMA shadow slots of a
    ``("chain", slots)`` segment-plan state).  ChainOptState for
    interpreter-run NOVEL compositions has one form and loads directly.

    A torn directory (no ``COMMIT`` marker and not a demonstrably
    complete legacy save) is rejected up front — resuming from half a
    shard set would silently corrupt the run.  Complete pre-marker
    checkpoints keep working, and a crash-interrupted swap is recovered
    from its surviving committed staging/backup dir first."""
    import os

    import numpy as np
    try:
        check_loadable(path)
    except ValueError as e:
        raise SystemExit(f"--resume: {e}") from e
    shard = os.path.join(path, f"shard_{jax.process_index():05d}.npz")
    saved_flat = any("p_flats" in k for k in np.load(shard).files)
    want_flat = isinstance(state, FlatOptState)
    if saved_flat == want_flat:
        return load_checkpoint(path, {"params": params, "opt": state})
    alt = jax.eval_shape(to_pytree if want_flat else from_pytree,
                         *((state,) if want_flat else (state, params)))
    restored, step = load_checkpoint(path, {"params": params, "opt": alt})
    opt_state = (from_pytree(restored["opt"], restored["params"], mesh=mesh)
                 if want_flat else to_pytree(restored["opt"]))
    return {"params": restored["params"], "opt": opt_state}, step


# steps of a run that --profile-dir traces, counted from its first:
# after the first step's compile, before the run's end
PROFILE_STEPS = (3, 5)


class _StepProfile:
    """A step hook that wraps ``hook`` with a profiler trace of steps
    ``first``..``last``: started after step ``first - 1``, stopped once
    step ``last`` has run on the device, or by ``close()`` where the run
    ends sooner."""

    def __init__(self, log_dir: str, first: int, last: int, hook=None):
        self.log_dir, self.first, self.last, self.hook = (log_dir, first,
                                                          last, hook)
        self.live = False

    def __call__(self, t, state_ts):
        if self.hook is not None:
            self.hook(t, state_ts)
        if t == self.first - 1 and self.first <= self.last:
            jax.profiler.start_trace(self.log_dir)
            self.live = True
        elif t == self.last and self.live:
            jax.block_until_ready(state_ts)
            self.close()

    def close(self):
        if self.live:
            jax.profiler.stop_trace()
            self.live = False


def main(argv=None):
    ap = argparse.ArgumentParser()
    add_model_args(ap, "yi-9b")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--n-micro", type=int, default=2)
    ap.add_argument("--optimizer", default="sngm",
                    choices=list(optimizer_names()))
    ap.add_argument("--fused", default="none",
                    choices=["none", "per_leaf", "multi_tensor"],
                    help="optimizer execution path: pure jnp (none), one "
                         "Pallas kernel per tensor (per_leaf), or the "
                         "dtype-bucketed multi-tensor engine (multi_tensor; "
                         "O(1) kernel launches per step)")
    ap.add_argument("--lr", type=float, default=1.6)
    ap.add_argument("--beta", type=float, default=0.9)
    ap.add_argument("--weight-decay", type=float, default=1e-4)
    ap.add_argument("--nesterov", action="store_true",
                    help="look-ahead momentum (optimizers that accept it); "
                         "the engine fuses it into the update pass, so the "
                         "launch count is unchanged")
    ap.add_argument("--ema-decay", type=float, default=0.0,
                    help="keep an exponential moving average of the params "
                         "(0 = off); on the resident path the shadow params "
                         "live in the flat f32 EMA slots and ride the "
                         "checkpoint like any other optimizer state")
    ap.add_argument("--data-axis", type=int, default=0,
                    help="data-mesh size (0 = all devices)")
    ap.add_argument("--model-axis", type=int, default=1)
    ap.add_argument("--pod-axis", type=int, default=1,
                    help="outer pure-DP pod axis size (1 = no pod axis); "
                         ">1 builds the (pod, data, model) production mesh")
    ap.add_argument("--coordinator", default="",
                    help="multi-process JAX coordinator address host:port "
                         "(jax.distributed.initialize); also picked up from "
                         "JAX_COORDINATOR_ADDRESS / COORDINATOR_ADDRESS")
    ap.add_argument("--num-processes", type=int, default=0,
                    help="multi-process world size (0 = single process "
                         "unless the environment configures one)")
    ap.add_argument("--process-id", type=int, default=-1,
                    help="this process's rank for --coordinator runs "
                         "(-1 = from the environment)")
    ap.add_argument("--ckpt", default="")
    ap.add_argument("--resume", action="store_true",
                    help="restore {params, opt} from --ckpt (either state "
                         "form) and continue from the saved step, so the "
                         "schedule picks up at the right t")
    ap.add_argument("--total-steps", type=int, default=0,
                    help="schedule horizon (0 = --steps); set this when a "
                         "run is split across save/resume segments so every "
                         "segment builds the same poly_power schedule")
    ap.add_argument("--data-dir", default="",
                    help="train from an on-disk repro-data-pack dataset "
                         "(python -m repro.data.pack) via the sharded "
                         "StreamingLoader; its LoaderState rides every "
                         "checkpoint for exact-batch resume.  Default: the "
                         "synthetic batch_at stream")
    ap.add_argument("--prefetch", type=int, default=2,
                    help="host->device prefetch depth for --data-dir runs "
                         "(0 = synchronous next(); 2 = double buffering)")
    ap.add_argument("--save-every", type=int, default=0,
                    help="checkpoint every K steps into step-named dirs "
                         "under --ckpt (step_00000010/, latest symlink); "
                         "0 = a single final save at --ckpt itself")
    ap.add_argument("--keep-last-n", type=int, default=0,
                    help="with --save-every: prune committed step_* dirs "
                         "beyond the newest N (0 = keep all; symlink "
                         "targets survive)")
    ap.add_argument("--async-save", action="store_true",
                    help="commit checkpoints on a background thread — the "
                         "step only pays the device->host copy, never the "
                         "commit I/O")
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--profile-dir", default="",
                    help="write a profiler trace of steps 3-5 of the run "
                         "(after the first step's compile) to this "
                         "directory: each step's 'train' span, and on the "
                         "device the step's phase scopes")
    ap.add_argument("--metrics-jsonl", default="",
                    help="append per-step metrics (loss, grad_norm, lr, "
                         "wall-clock, tokens/sec) as JSON lines to this "
                         "path via the repro.tracker JSONL backend")
    args = ap.parse_args(argv)

    cfg = model_config(args, "train")

    # multi-process init FIRST — jax.devices() below must see the global
    # device set; a guarded no-op for single-process runs
    init_distributed(
        coordinator_address=args.coordinator or None,
        num_processes=args.num_processes or None,
        process_id=args.process_id if args.process_id >= 0 else None)
    enable_compile_cache(profiled=bool(args.profile_dir))
    main_proc = is_main_process()

    n_dev = len(jax.devices())
    mesh = make_train_mesh(args.data_axis, args.model_axis, args.pod_axis)
    rt = Runtime(mesh=mesh,
                 data_axes=data_axes_of(mesh) if mesh is not None
                 else ("data",),
                 remat=not args.reduced)

    defs = model_defs(cfg)
    params = materialize(defs, jax.random.PRNGKey(0))
    if main_proc:
        print(f"[train] {cfg.name}: {count(defs):,} params on {n_dev} "
              f"device(s) across {process_count()} process(es)"
              f"{f' mesh={dict(mesh.shape)}' if mesh else ''}")

    gspecs = None
    if mesh is not None:
        psh = param_shardings(defs, mesh)
        params = jax.device_put(params, psh)
        gspecs = param_specs(defs, mesh)

    fused = None if args.fused == "none" else args.fused
    horizon = args.total_steps or args.steps
    saved_meta = {}
    resume_path = ""
    if args.resume and args.ckpt:
        # --ckpt may be the checkpoint itself or the BASE of a
        # --save-every step_* family; follow latest/newest committed
        resume_path = resolve_checkpoint(args.ckpt)
        # the schedule horizon is part of the run's identity: adopt the
        # saved one when --total-steps is omitted, warn on a mismatch —
        # otherwise poly_power silently decays on a different horizon and
        # the resumed lr diverges from the uninterrupted run
        tm_path = os.path.join(args.ckpt, "train_meta.json")
        if os.path.exists(tm_path):
            with open(tm_path) as f:
                saved_meta = json.load(f)
            saved_horizon = saved_meta.get("total_steps")
            if saved_horizon:
                if not args.total_steps:
                    horizon = saved_horizon
                elif saved_horizon != horizon:
                    print(f"[train] WARNING: --total-steps {horizon} != "
                          f"checkpoint horizon {saved_horizon}; the lr "
                          f"schedule will not match the original run")
    if args.resume and saved_meta.get("optimizer_spec"):
        # the optimizer's identity travels with the run: reconstruct it
        # from the saved spec so the resumed steps are bit-identical to
        # an uninterrupted run.  Only the execution mode (--fused) stays
        # a per-run hardware choice; the schedule horizon is re-pinned
        # in case the user forced a different --total-steps above.
        spec = OptimizerSpec.from_json(saved_meta["optimizer_spec"])
        if spec.name != args.optimizer and \
                args.optimizer != ap.get_default("optimizer"):
            print(f"[train] WARNING: --optimizer {args.optimizer} ignored; "
                  f"resuming the checkpoint's {spec.name!r} spec")
        kwargs = dict(spec.kwargs)
        if builder_accepts(spec.name, "fused"):
            kwargs["fused"] = fused
        sched = dict(kwargs["schedule"])
        skw = dict(sched.get("kwargs", {}))
        if "total_steps" in skw and skw["total_steps"] != horizon:
            skw["total_steps"] = horizon
            sched["kwargs"] = skw
            kwargs["schedule"] = sched
        spec = OptimizerSpec(spec.name, kwargs)
    else:
        kwargs = {"schedule": {"name": "poly_power",
                               "kwargs": {"lr0": args.lr,
                                          "total_steps": horizon,
                                          "power": 1.1}}}
        for k, v in (("beta", args.beta),
                     ("weight_decay", args.weight_decay),
                     ("nesterov", args.nesterov),
                     ("ema_decay", args.ema_decay or None),
                     ("fused", fused)):
            if builder_accepts(args.optimizer, k):
                kwargs[k] = v
        spec = OptimizerSpec(args.optimizer, kwargs)
    # the spec stays mesh-free (it is the run's serializable identity);
    # the mesh is a per-run hardware choice injected at build time, so the
    # resident flat buffers come up sharded across the whole device set
    opt = make_optimizer(spec, mesh=mesh)
    state = opt.init(params)
    start = 0
    if args.resume:
        if not args.ckpt:
            raise SystemExit("--resume requires --ckpt")
        # the checkpoint loads into shape-only templates: the init
        # buffers are released first, so a model that fills most of the
        # chip resumes without holding two copies of its state
        params, state = jax.eval_shape(lambda: (params, state))
        restored, start = _restore(resume_path, params, state, mesh)
        params, state = restored["params"], restored["opt"]
        del restored
        if mesh is not None:
            # re-place onto the mesh: load_checkpoint materialized every
            # leaf on the default device.  Resident flat buffers were
            # packed for the mesh's shard count as they were built (or
            # loaded so); placing them needs no second copy on the
            # default device (bitwise-identical values, same placement as
            # an unresumed opt.init).
            params = jax.device_put(params, psh)
            if isinstance(state, FlatOptState):
                state = from_pytree(state, params, mesh=mesh)
            elif isinstance(state, OptState):
                state = OptState(state.step,
                                 jax.device_put(state.momentum, psh))
            elif isinstance(state, ChainOptState):
                # interpreter-run chains (lamb with --fused none, novel
                # compositions): every sub-state tree mirroring the params
                # (moments, EMA shadows) takes the param shardings
                state = place_chain_state(state, psh)
        if main_proc:
            print(f"[train] resumed {resume_path} at step {start}")
    # unify into the donated TrainState: on the resident path the flat
    # buffers own the params (single copy on device) and the params
    # pytree reference is dropped here
    ts = TrainState.wrap(params, state)
    del params, state
    # donate the state through jit: XLA aliases params/momentum/moments
    # in place across steps instead of double-buffering them
    step = jax.jit(make_train_step(cfg, rt, opt, n_micro=args.n_micro,
                                   grad_specs=gspecs),
                   donate_argnums=(0,))
    loader = None
    prefetcher = None
    seq = args.seq
    if args.data_dir:
        source = DiskShardedSource(args.data_dir)
        v = source.meta.get("vocab_size")
        if v is not None and v != cfg.vocab_size:
            raise SystemExit(f"--data-dir vocab_size {v} != model vocab "
                             f"{cfg.vocab_size} ({cfg.name})")
        if cfg.is_encoder_decoder and "encoder_embeds" not in source.fields:
            raise SystemExit("--data-dir: encoder-decoder archs need an "
                             "'encoder_embeds' field in the dataset")
        seq = int(source.meta.get("seq_len", args.seq))
        ls = load_loader_state(resume_path) if resume_path else None
        if args.resume and ls is None:
            print("[train] WARNING: checkpoint carries no loader_state; "
                  "the data stream restarts from the beginning")
        loader = StreamingLoader(
            source, args.batch,
            state=LoaderState.from_dict(ls) if ls else None)
        batches = loader
        if args.prefetch > 0:
            bsh = (NamedSharding(mesh, batch_spec(mesh, 2))
                   if mesh is not None else None)
            prefetcher = PrefetchIterator(
                loader, depth=args.prefetch,
                place=lambda b: device_put_batch(b, bsh))
            batches = prefetcher
    else:
        data = SyntheticLM(cfg.vocab_size, args.seq, args.batch, branching=4)

        def batch_at(t):
            batch = data.batch_at(t)
            if cfg.is_encoder_decoder:
                batch["encoder_embeds"] = jax.random.normal(
                    jax.random.PRNGKey(t),
                    (args.batch, cfg.encoder_len, cfg.d_model))
            return batch

        batches = batch_at

    def loader_state_now():
        """Cursor of the next batch TRAINING will consume: the
        prefetcher's snapshot under run-ahead, the loader's otherwise."""
        it = prefetcher if prefetcher is not None else loader
        return None if it is None else it.state

    # tracker stack: in-memory (the returned loss curve), rate-limited
    # stdout progress, and optionally a durable JSONL metrics file.  The
    # run_steps loop keeps stats as device scalars between log-boundary
    # drains, so logging never serializes dispatch (retained buffers stay
    # bounded by --log-every).
    def fmt(t, m):
        return (f"  step {t:5d} loss={m['loss']:.4f} "
                f"||g||={m.get('grad_norm', float('nan')):.3f} "
                f"lr={m.get('lr', float('nan')):.4f} "
                f"({m.get('it_per_s', 0.0):.2f} it/s)")

    mem = MemoryTracker()
    backends = [mem]
    # per-host guards: stdout progress and the metrics file come from
    # process 0 only; every process keeps the in-memory curve (the
    # return value) since stats are replicated scalars
    if main_proc:
        backends.append(StdoutTracker(every=args.log_every, fmt=fmt))
        if args.metrics_jsonl:
            backends.append(JsonlTracker(args.metrics_jsonl))
    tracker = CompositeTracker(backends)
    callbacks = [StepTimer(tokens_per_step=args.batch * seq)]
    if prefetcher is not None:
        callbacks.append(PrefetchMonitor(prefetcher))

    def train_meta():
        return {"total_steps": horizon, "optimizer": spec.name,
                "lr": args.lr, "optimizer_spec": spec.to_json()}

    # periodic (optionally async) checkpointing: the hook runs after each
    # step with the NEW TrainState, and saves it together with the data
    # cursor of the NEXT batch — the pair that makes resume exact
    saver = AsyncCheckpointer() if (args.ckpt and args.async_save) else None

    def save_step(step_no, state_ts):
        tree = {"params": state_ts.params_view,
                "opt": to_pytree(state_ts.opt_state)}
        # keep_last_n=0 still maintains the latest/best symlinks (no
        # pruning) — step-named families always carry their pointers
        kw = dict(loader_state=loader_state_now(),
                  keep_last_n=args.keep_last_n)
        dest = step_dir(args.ckpt, step_no)
        if saver is not None:
            saver.save(dest, tree, step_no, **kw)
        else:
            save_checkpoint(dest, tree, step_no, **kw)

    step_hook = None
    if args.ckpt and args.save_every > 0:
        # train_meta.json up front (base dir), so an interrupted run is
        # already resumable from its newest periodic save; one writer
        # (process 0) on a shared filesystem
        os.makedirs(args.ckpt, exist_ok=True)
        if main_proc:
            with open(os.path.join(args.ckpt, "train_meta.json"), "w") as f:
                json.dump(train_meta(), f)

        def step_hook(t, state_ts):
            if (t + 1) % args.save_every == 0:
                save_step(t + 1, state_ts)

    profile = None
    if args.profile_dir:
        profile = step_hook = _StepProfile(
            args.profile_dir, start + PROFILE_STEPS[0],
            min(start + PROFILE_STEPS[1], args.steps - 1), step_hook)
    try:
        ts = run_steps(step, ts, batches, args.steps, start=start,
                       tracker=tracker, log_every=args.log_every,
                       callbacks=callbacks, step_hook=step_hook)
    finally:
        if profile is not None:
            profile.close()
    losses = mem.series("loss")
    if args.ckpt:
        # checkpoint from the LIVE TrainState.  A FlatOptState holds the
        # params in its flat buffers (bit-equal to the view by the
        # padding invariant), so persist the pytree form — halves the
        # checkpoint; --resume rebuilds the resident buffers losslessly
        final_step = max(start, args.steps)
        in_family = args.save_every > 0 or (
            os.path.isdir(args.ckpt)
            and resolve_checkpoint(args.ckpt) != args.ckpt)
        if in_family:
            # step-named family: periodic mode, or a resume whose --ckpt
            # is the BASE of one (don't clobber the base — join it)
            hook_saved = (args.save_every > 0 and final_step > start
                          and final_step % args.save_every == 0)
            if not hook_saved:
                save_step(final_step, ts)
        else:
            save_checkpoint(args.ckpt,
                            {"params": ts.params_view,
                             "opt": to_pytree(ts.opt_state)},
                            step=final_step, loader_state=loader_state_now())
        if main_proc:
            with open(os.path.join(args.ckpt, "train_meta.json"), "w") as f:
                json.dump(train_meta(), f)
            print(f"[train] checkpoint -> {args.ckpt}")
    if saver is not None:
        saver.close()                  # drain pending commits, re-raise errors
    if prefetcher is not None:
        c = prefetcher.counters()
        if main_proc:
            print(f"[train] input stall "
                  f"{c['input_stall_s_per_step']*1e3:.2f} ms/step, "
                  f"prefetch depth avg {c['prefetch_depth_avg']:.2f}")
        prefetcher.close()             # also closes the loader + source
    elif loader is not None:
        loader.close()
    return losses


if __name__ == "__main__":
    main()
