"""End-to-end distributed training driver: a multi-million-parameter LM
trained for a few hundred steps with SNGM and large-batch gradient
accumulation, on whatever devices exist (host mesh), with checkpointing.

    PYTHONPATH=src python examples/train_lm.py --steps 200 --d-model 256

Scale notes: the default (~20M params, B=32x128 tokens) trains in
minutes on the CPU container; on a real mesh raise --d-model/--layers
and the mesh shape — the code path (pjit + sharding rules + grad accum)
is identical to the production dry-run's.
"""
import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.checkpoint import save_checkpoint
from repro.configs import ARCHS
from repro.core import make_optimizer
from repro.core.optim import OptState, builder_accepts, optimizer_names
from repro.core.schedules import poly_power
from repro.data import (DiskShardedSource, PrefetchIterator, StreamingLoader,
                        SyntheticLM, device_put_batch)
from repro.launch.mesh import make_mesh
from repro.models import model_defs
from repro.models.param import count, materialize
from repro.models.runtime import Runtime
from repro.sharding import batch_spec, param_shardings
from repro.training import make_train_step


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="deepseek-7b")
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--d-model", type=int, default=256)
    ap.add_argument("--vocab", type=int, default=512)
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--n-micro", type=int, default=4)
    ap.add_argument("--optimizer", default="sngm",
                    choices=list(optimizer_names()))
    ap.add_argument("--lr", type=float, default=0.3)
    ap.add_argument("--ckpt", default="")
    ap.add_argument("--data-dir", default="",
                    help="train from a packed on-disk dataset "
                         "(python -m repro.data.pack) instead of the "
                         "synthetic stream")
    ap.add_argument("--prefetch", type=int, default=2,
                    help="host->device prefetch depth for --data-dir")
    args = ap.parse_args()

    base = ARCHS[args.arch]
    cfg = dataclasses.replace(
        base, n_layers=args.layers, d_model=args.d_model,
        n_heads=max(4, args.d_model // 64), n_kv_heads=max(2, args.d_model // 128),
        head_dim=64, d_ff=args.d_model * 4, vocab_size=args.vocab,
        moe=None, mla=None)  # dense variant of the chosen family

    defs = model_defs(cfg)
    params = materialize(defs, jax.random.PRNGKey(0))
    print(f"arch={cfg.name} params={count(defs):,} devices={len(jax.devices())}")

    n_dev = len(jax.devices())
    mesh = make_mesh((n_dev, 1), ("data", "model")) if n_dev > 1 else None
    rt = Runtime(mesh=mesh, remat=False) if mesh else Runtime(mesh=None, remat=False)
    if mesh:
        psh = param_shardings(defs, mesh)
        params = jax.device_put(params, psh)

    kw = {k: v for k, v in (("beta", 0.9), ("weight_decay", 1e-4))
          if builder_accepts(args.optimizer, k)}
    opt = make_optimizer(args.optimizer, poly_power(args.lr, args.steps, 1.1),
                         **kw)
    # donated TrainState: params + optimizer slots alias in place across
    # steps (on the resident fused path, ~1x parameter bytes live)
    state = opt.init_state(params)
    del params
    step = jax.jit(make_train_step(cfg, rt, opt, n_micro=args.n_micro),
                   donate_argnums=(0,))
    seq, it = args.seq, None
    if args.data_dir:
        # on-disk dataset through the streaming pipeline: sharded loader
        # + background host->device prefetch (batches arrive resident)
        source = DiskShardedSource(args.data_dir)
        v = source.meta.get("vocab_size")
        if v is not None and v != cfg.vocab_size:
            raise SystemExit(f"--data-dir vocab_size {v} != model vocab "
                             f"{cfg.vocab_size} (pass --vocab {v})")
        seq = int(source.meta.get("seq_len", args.seq))
        loader = StreamingLoader(source, args.batch)
        bsh = NamedSharding(mesh, batch_spec(mesh, 2)) if mesh else None
        it = (PrefetchIterator(loader, depth=args.prefetch,
                               place=lambda b: device_put_batch(b, bsh))
              if args.prefetch > 0 else loader)
        next_batch = lambda t: next(it)  # noqa: E731
        floor = float(source.meta.get("optimal_loss", float("nan")))
    else:
        data = SyntheticLM(cfg.vocab_size, seq, args.batch, branching=8)
        next_batch = data.batch_at
        floor = float(data.optimal_loss())

    t0 = time.time()
    for t in range(args.steps):
        state, stats = step(state, next_batch(t))
        if t % 20 == 0 or t == args.steps - 1:
            tok_s = args.batch * seq * (t + 1) / (time.time() - t0)
            print(f"step {t:4d}  loss={float(stats['loss']):.4f}  "
                  f"||g||={float(stats['grad_norm']):.2f}  "
                  f"lr={float(stats['lr']):.4f}  tok/s={tok_s:,.0f}")
    if it is not None:
        it.close()
    print(f"entropy floor ~{floor:.3f} nats; total {time.time()-t0:.0f}s")
    if args.ckpt:
        save_checkpoint(args.ckpt, {"params": state.params_view},
                        step=args.steps)
        print(f"checkpoint saved to {args.ckpt}")


if __name__ == "__main__":
    main()
