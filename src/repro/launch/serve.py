"""Production serving launcher: continuous batching on either engine.

  * ``--engine paged`` (default): ``serving.scheduler.PagedScheduler`` —
    paged KV blocks, COW prefix sharing, bucket-padded batched prefill,
    chunked on-device decode, preemption under memory pressure.
  * ``--engine dense``: the slot-spliced ``ContinuousBatcher`` baseline
    (O(n_slots x ctx) cache, per-length prefill compiles, one host sync
    per token).

    PYTHONPATH=src python -m repro.launch.serve --arch deepseek-7b \
        --reduced --slots 4 --requests 10 --max-new 12 --temperature 0.7

``--reduced`` serves the smoke-scale variant; ``--n-layers N`` cuts depth
at the published widths (``--arch yi-9b --n-layers 2`` fits one chip).
``main`` returns the finished requests.
"""
from __future__ import annotations

import argparse
import time
from dataclasses import dataclass, field
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.launch.common import (add_model_args, enable_compile_cache,
                                 model_config)
from repro.models import model_defs
from repro.models.param import materialize
from repro.models.runtime import CPU_RUNTIME
from repro.serving import make_prefill_step, make_serve_step
from repro.serving.engine import cache_batch_axes, pad_cache, sample_logits


@dataclass
class Request:
    rid: int
    prompt: jnp.ndarray
    max_new: int
    out: List[int] = field(default_factory=list)
    done: bool = False
    t_submit: float = 0.0
    t_first: float = 0.0
    t_done: float = 0.0


class ContinuousBatcher:
    """Slot-based continuous batching over the DENSE cache: one shared
    ring of `n_slots` sequences decoded in lockstep; empty slots are
    refilled from the queue via a fresh prefill whose cache is spliced
    into slot state.  Kept as the baseline the paged engine is gated
    against (benchmarks/bench_serving.py)."""

    def __init__(self, cfg, params, n_slots: int, ctx_len: int,
                 temperature: float = 0.0, top_k: int = 0, seed: int = 0):
        self.cfg, self.params = cfg, params
        self.n = n_slots
        self.ctx = ctx_len
        self.temperature, self.top_k = temperature, top_k
        self.prefill = jax.jit(make_prefill_step(cfg, CPU_RUNTIME))
        self.step = jax.jit(make_serve_step(cfg, CPU_RUNTIME,
                                            temperature=temperature,
                                            top_k=top_k))
        self.slots: List[Optional[Request]] = [None] * n_slots
        self.cache = None
        # explicit per-leaf batch-axis metadata (a pytree of ints) —
        # replaces the old first-size-1-axis sniffing, which guessed
        # wrong whenever a genuine size-1 period/state dim preceded the
        # batch dim
        self.batch_axes = cache_batch_axes(cfg)
        self.tok = jnp.zeros((n_slots, 1), jnp.int32)
        self.pos = jnp.zeros((n_slots,), jnp.int32)
        self._key = jax.random.PRNGKey(seed)
        self._rng_ctr = 0
        self.prefill_shapes = set()

    def _next_rng(self):
        rng = jax.random.fold_in(self._key, self._rng_ctr)
        self._rng_ctr += 1
        return rng

    def _admit(self, req: Request, slot: int):
        """Prefill the request alone, splice its cache row into the slot."""
        S0 = req.prompt.shape[1]
        self.prefill_shapes.add((1, S0))
        logits, cache1 = self.prefill(self.params, req.prompt)
        cache1 = pad_cache(cache1, self.ctx - S0)
        if self.cache is None:
            def widen(l, ax):
                return jnp.zeros(l.shape[:ax] + (self.n,) + l.shape[ax + 1:],
                                 l.dtype)
            self.cache = jax.tree.map(widen, cache1, self.batch_axes)
        def splice(full, one, ax):
            idx = (slice(None),) * ax + (slot,)
            return full.at[idx].set(jnp.squeeze(one, axis=ax))
        self.cache = jax.tree.map(splice, self.cache, cache1, self.batch_axes)
        self.slots[slot] = req
        if self.temperature == 0.0:
            nxt = int(jnp.argmax(logits[0, -1]))
        else:
            nxt = int(sample_logits(logits[:, -1], self._next_rng(),
                                    self.temperature, self.top_k)[0])
        req.out.append(nxt)
        req.t_first = time.monotonic()
        self.tok = self.tok.at[slot, 0].set(nxt)
        self.pos = self.pos.at[slot].set(S0)

    def decode_step(self) -> List[Request]:
        """One lockstep decode step.  Returns the requests that finished
        on this step (their slots are freed before returning, so callers
        must use the returned list — inspecting ``slots`` afterwards
        finds them already evicted)."""
        nxt, _, self.cache = self.step(self.params, self.cache,
                                       self.tok, self.pos,
                                       self._next_rng())
        self.pos = self.pos + 1
        finished: List[Request] = []
        now = time.monotonic()
        for s, req in enumerate(self.slots):
            if req is None or req.done:
                continue
            req.out.append(int(nxt[s]))
            if len(req.out) >= req.max_new:
                req.done = True
                req.t_done = now
                finished.append(req)
                self.slots[s] = None
        self.tok = nxt[:, None]
        return finished

    def free_slots(self):
        return [i for i, r in enumerate(self.slots) if r is None]


def _report(finished, dt: float, steps: int, label: str):
    total_tokens = sum(len(r.out) for r in finished)
    lats = [r.t_done - r.t_submit for r in finished if r.t_done]
    print(f"[serve:{label}] {len(finished)} requests, {total_tokens} tokens, "
          f"{steps} decode steps, {total_tokens / dt:.1f} tok/s, {dt:.2f}s")
    if lats:
        print(f"[serve:{label}] request latency "
              f"p50 {np.percentile(lats, 50) * 1e3:.0f}ms "
              f"p99 {np.percentile(lats, 99) * 1e3:.0f}ms "
              f"mean {np.mean(lats) * 1e3:.0f}ms")


def main(argv=None):
    ap = argparse.ArgumentParser()
    add_model_args(ap, "deepseek-7b")
    ap.add_argument("--engine", default="paged", choices=["paged", "dense"])
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=12)
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="0 = greedy (bitwise-reproducible); >0 samples")
    ap.add_argument("--top-k", type=int, default=0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--block-size", type=int, default=16)
    ap.add_argument("--blocks", type=int, default=0,
                    help="KV pool blocks (0 = enough for all slots)")
    ap.add_argument("--decode-chunk", type=int, default=4)
    ap.add_argument("--profile-dir", default="",
                    help="paged engine: write a profiler trace of the run "
                         "after its first round (whose compiles it leaves "
                         "out) to this directory, with the scheduler's "
                         "serve.* spans")
    args = ap.parse_args(argv)
    if args.profile_dir and args.engine != "paged":
        ap.error("--profile-dir traces the paged engine's scheduler")

    enable_compile_cache(profiled=bool(args.profile_dir))
    cfg = model_config(args, "serve")
    params = materialize(model_defs(cfg), jax.random.PRNGKey(0))
    ctx = args.prompt_len + args.max_new

    rng = np.random.RandomState(args.seed)
    prompts = [rng.randint(0, cfg.vocab_size, (args.prompt_len,))
               .astype(np.int32) for _ in range(args.requests)]

    if args.engine == "paged":
        from repro.serving.paged_cache import n_blocks_for
        from repro.serving.scheduler import PagedScheduler, ServeRequest
        n_blocks = args.blocks or (
            1 + args.slots * n_blocks_for(ctx, args.block_size))
        sched = PagedScheduler(
            cfg, params, CPU_RUNTIME, n_slots=args.slots,
            block_size=args.block_size, n_blocks=n_blocks, ctx_max=ctx,
            decode_chunk=args.decode_chunk, temperature=args.temperature,
            top_k=args.top_k, seed=args.seed)
        t0 = time.monotonic()
        for i, p in enumerate(prompts):
            sched.submit(ServeRequest(rid=i, prompt=p, max_new=args.max_new))
        if args.profile_dir:
            sched.step()
            with jax.profiler.trace(args.profile_dir):
                finished = sched.run()
        else:
            finished = sched.run()
        _report(finished, time.monotonic() - t0,
                sched.stats["decode_steps"], "paged")
        print(f"[serve:paged] peak blocks {sched.stats['peak_used_blocks']}"
              f"/{n_blocks - 1}, preemptions {sched.stats['preemptions']}, "
              f"compiles {sched.compile_counts()}")
        return finished

    queue = [Request(i, jnp.asarray(p)[None], args.max_new,
                     t_submit=time.monotonic()) for i, p in enumerate(prompts)]
    finished: List[Request] = []
    b = ContinuousBatcher(cfg, params, args.slots, ctx,
                          temperature=args.temperature, top_k=args.top_k,
                          seed=args.seed)
    t0 = time.monotonic()
    steps = 0
    while queue or any(s is not None for s in b.slots):
        for s in b.free_slots():
            if queue:
                b._admit(queue.pop(0), s)
        if any(s is not None for s in b.slots):
            finished += b.decode_step()
            steps += 1
    _report(finished, time.monotonic() - t0, steps, "dense")
    return finished


if __name__ == "__main__":
    main()
