"""Train-step builder: gradient accumulation (lax.scan over micro-batches,
paper §5 trains B=4096/8192 by accumulating 128-sized micro-batches) +
any ``repro.core`` optimizer.  The optimizer sees the *accumulated
global-batch* gradient, so SNGM normalizes once per global batch —
exactly Algorithm 1.

Fused optimizers (``fused="multi_tensor"``/``"per_leaf"``) slot in here
unchanged: the accumulator below keeps gradients in the parameter storage
dtype, which is exactly the per-leaf dtype contract the multi-tensor
engine buckets by (core/multi_tensor.py), so ``make_train_step`` works
identically for jnp and fused optimizers — including under pjit, where
the flat-buffer build is plain jnp and SPMD inserts the one scalar
all-reduce for the norm.

The step consumes/produces the unified ``TrainState`` and is
donation-safe: on the resident path (``TrainState.params is None``) the
``FlatOptState.p_flats`` buffers are the SINGLE owner of the parameters
— ``loss_fn`` reads a temporary unflattened view that XLA frees inside
the step, and the optimizer update writes the buffers without ever
materializing a second pytree output.  Jit it with
``donate_argnums=(0,)`` (what ``launch/train.py`` does) and the whole
params+momentum update aliases in place across steps.

Each phase of the step runs under a ``jax.named_scope`` —
``params_view``, ``fwd_bwd``, ``grad_pack``, ``grad_accum`` and
``sngm_update`` (the model adds ``embed``, ``attention``, ``mlp`` and
``loss`` inside ``fwd_bwd``) — so a device trace attributes each op to
a phase through its ``op_name`` metadata.  The micro-batch loop runs
under ``grad_accum`` with the other phases nested inside it, so an op's
phase is the innermost one on its path.  Scopes change metadata only,
never an op or a fusion.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.multi_tensor import (FlatGrads, FlatOptState, flatten,
                                     mesh_shards)
from repro.core.multi_tensor import flat_sharding as _flat_sharding
from repro.core.optim import Optimizer, TrainState
from repro.core.transform import as_optimizer
from repro.models.runtime import Runtime
from repro.models.transformer import forward, unembed_matrix
from repro.training.loss import lm_loss


def loss_fn(params, batch: Dict[str, Any], cfg: ModelConfig, rt: Runtime):
    if rt.gather_dtype != "float32":
        # §Perf: cast matrices to the compute dtype BEFORE use so the FSDP
        # all-gather (inserted by SPMD at first use) moves bf16, not fp32;
        # the cast itself is shard-local.  1D params (norm scales, biases)
        # keep fp32.
        gd = jnp.dtype(rt.gather_dtype)
        with jax.named_scope("params_view"):
            params = jax.tree.map(
                lambda p: p.astype(gd)
                if (p.ndim >= 2 and p.dtype == jnp.float32) else p, params)
    h, _, aux = forward(params, cfg, rt, batch["tokens"], mode="train",
                        encoder_embeds=batch.get("encoder_embeds"))
    with jax.named_scope("loss"):
        loss, ntok = lm_loss(h, unembed_matrix(params), batch["tokens"],
                             batch["loss_mask"], cfg)
    return loss + aux["aux_loss"], {"ce_loss": loss, **aux, "ntok": ntok}


# How loss_fn's aux metrics combine across micro-batches, so logged stats
# keep their global-batch semantics at any n_micro.  COUNT_METRICS sum to
# the global total; TOKEN_WEIGHTED_METRICS are per-token means and combine
# weighted by ntok (an unweighted mean of per-micro means diverges when
# loss_mask density is ragged across micro-batches); everything else is a
# plain mean.  Extend these when adding a metric to loss_fn, or it will
# be silently averaged under gradient accumulation.
COUNT_METRICS = ("ntok", "moe_held_rows")
TOKEN_WEIGHTED_METRICS = ("ce_loss",)


def make_train_step(cfg: ModelConfig, rt: Runtime, opt: Optimizer,
                    n_micro: int = 1, grad_specs=None):
    """Returns train_step(state, batch) -> (state', stats) over the
    unified ``TrainState`` (build one with ``opt.init_state(params)``).

    ``opt`` is an ``Optimizer`` — or a raw ``GradientTransform`` chain,
    which is compiled on the spot (``core.transform.as_optimizer``): a
    recognized shape lands on the fused-kind implementation, a novel
    composition trains through the jnp chain interpreter.

    batch["tokens"]: (B, S) global batch; accumulated over ``n_micro``
    micro-batches of size B/n_micro inside one jit step.

    grad_specs (PartitionSpec tree mirroring params): pins the gradient /
    accumulator sharding to the parameter sharding so the per-micro
    gradient reduction lowers as reduce-scatter instead of a full
    all-reduce (§Perf: 16x collective-bytes difference at n_micro=16).

    Donation contract: the returned step is safe to jit with
    ``donate_argnums=(0,)`` — the state's buffers (params or resident
    flats, momentum, Adam moments) appear exactly once in the outputs,
    so XLA aliases them in place instead of double-buffering.
    """
    opt = as_optimizer(opt)
    grad_fn = jax.value_and_grad(partial(loss_fn, cfg=cfg, rt=rt), has_aux=True)

    def constrain_g(g):
        if grad_specs is None or rt.mesh is None:
            return g
        from jax.sharding import NamedSharding
        return jax.tree.map(
            lambda x, s: jax.lax.with_sharding_constraint(
                x, NamedSharding(rt.mesh, s)), g, grad_specs)

    def train_step(state: TrainState, batch):
        # resident path: a read-only pytree view of the flat buffers,
        # materialized for loss_fn only (never threaded back as a live
        # second copy — the update below reads state.opt_state.p_flats)
        with jax.named_scope("params_view"):
            params = state.params_view
        B = batch["tokens"].shape[0]
        assert B % n_micro == 0, (B, n_micro)

        # flat accumulation: with a resident FlatOptState whose layout
        # matches the runtime mesh's shard count, accumulate straight into
        # the dtype-bucketed flat buffers.  Each micro-batch packs its
        # gradient and adds per bucket under the flat sharding constraint,
        # so SPMD overlaps the bucketed gradient reduce with the NEXT
        # micro-batch's backward inside the scan — and the optimizer gets
        # pre-packed ``FlatGrads``, skipping the re-flatten.  Packing is a
        # pure reshape/pad/concat at the bucket (= parameter storage)
        # dtype, so the summed buckets are bitwise the packed tree sum.
        flat_layout = None
        if n_micro > 1 and isinstance(state.opt_state, FlatOptState):
            lo = state.opt_state.layout
            if rt.mesh is None or lo.shards in (1, mesh_shards(rt.mesh)):
                flat_layout = lo

        def constrain_flats(flats):
            if rt.mesh is None or flat_layout.shards == 1:
                return flats
            fs = _flat_sharding(rt.mesh)
            return tuple(jax.lax.with_sharding_constraint(f, fs)
                         for f in flats)

        if n_micro == 1:
            with jax.named_scope("fwd_bwd"):
                (loss, metrics), grads = grad_fn(params, batch)
            grads = constrain_g(grads)
        else:
            micro = jax.tree.map(
                lambda x: x.reshape(n_micro, B // n_micro, *x.shape[1:]),
                batch)

            if flat_layout is not None:
                def body(acc, mb):
                    g_acc, l_acc = acc
                    with jax.named_scope("fwd_bwd"):
                        (l, m), g = grad_fn(params, mb)
                    with jax.named_scope("grad_pack"):
                        gf = flatten(constrain_g(g), flat_layout)
                    with jax.named_scope("grad_accum"):
                        g_acc = constrain_flats(tuple(
                            a + b for a, b in zip(g_acc, gf)))
                    return (g_acc, l_acc + l), m

                with jax.named_scope("grad_accum"):
                    g0 = constrain_flats(tuple(
                        jnp.zeros((b.n_elems,), b.dtype)
                        for b in flat_layout.buckets))
            else:
                def body(acc, mb):
                    g_acc, l_acc = acc
                    with jax.named_scope("fwd_bwd"):
                        (l, m), g = grad_fn(params, mb)
                    g = constrain_g(g)
                    with jax.named_scope("grad_accum"):
                        g_acc = jax.tree.map(
                            lambda a, b: a + b.astype(a.dtype), g_acc, g)
                    return (constrain_g(g_acc), l_acc + l), m

                # accumulator in the parameter storage dtype: fp32 models
                # get exact accumulation; bf16-param models (jamba-398B)
                # trade ~0.5% gradient noise for fitting the accumulator
                # in HBM
                with jax.named_scope("grad_accum"):
                    g0 = jax.tree.map(
                        lambda p: jnp.zeros(p.shape, p.dtype), params)
            # the loop's own carry and counter are accumulation too; the
            # phases inside the body nest deeper and name their ops
            with jax.named_scope("grad_accum"):
                (g_sum, l_sum), m_stack = jax.lax.scan(
                    body, (g0, jnp.zeros((), jnp.float32)), micro)
            with jax.named_scope("grad_accum"):
                if flat_layout is not None:
                    grads = FlatGrads(tuple(f / n_micro for f in g_sum),
                                      flat_layout)
                else:
                    grads = jax.tree.map(lambda g: g / n_micro, g_sum)
            loss = l_sum / n_micro
            # every aux metric (scalar or not) keeps its global-batch
            # semantics regardless of n_micro — so `metrics` has the same
            # keys and shapes as the n_micro=1 branch
            def combine(k, v):
                if k in COUNT_METRICS:
                    return jnp.sum(v, axis=0)
                if k in TOKEN_WEIGHTED_METRICS and "ntok" in m_stack:
                    w = m_stack["ntok"].astype(jnp.float32)
                    w = w.reshape(w.shape[:1] + (1,) * (v.ndim - 1))
                    return jnp.sum(v * w, axis=0) / jnp.sum(m_stack["ntok"])
                return jnp.mean(v, axis=0)

            metrics = {k: combine(k, v) for k, v in m_stack.items()}

        with jax.named_scope("sngm_update"):
            new_state, stats = opt.step_state(grads, state)
        stats = dict(stats)
        stats["loss"] = loss
        stats.update({k: v for k, v in metrics.items() if jnp.ndim(v) == 0})
        return new_state, stats

    return train_step


def run_steps(step_fn, state: TrainState, batches, n_steps: int, *,
              start: int = 0, tracker=None, callbacks=(), log_every: int = 1,
              summary: Optional[Dict[str, Any]] = None,
              step_hook=None) -> TrainState:
    """Host-side training loop around a (possibly jitted, possibly
    donated) ``train_step(state, batch) -> (state', stats)``: threads the
    state, buffers the per-step device stats, and drains them into the
    tracker every ``log_every`` steps (stats stay device scalars between
    drains, so logging never serializes dispatch — the same pending-drain
    discipline the launcher documents).

    ``batches`` is either the historical ``batch_at(t)`` callable (the
    batch for step ``t``) or any ITERATOR/ITERABLE of batches — e.g. a
    ``repro.data.StreamingLoader`` or the ``PrefetchIterator`` wrapping
    one.  An iterator that exhausts (``StopIteration``) ends the run
    early and cleanly — with ``max_epochs`` set on the loader that is
    the epoch bound; ``n_steps`` stays the step bound.

    ``callbacks`` (``repro.tracker.callbacks.Callback``) run in
    registration order at each drain and may add derived metrics
    (wall-clock, tokens/sec); their ``on_end`` summaries merge with
    ``summary`` into one ``tracker.log_summary`` record before the
    tracker is finished.

    ``step_hook(t, state)`` — when given — runs after every step with
    the NEW state, outside the metrics pump: the launcher uses it for
    periodic (async) checkpointing, which must see the post-step state
    and the data iterator's post-step cursor together.

    Each step (batch, dispatch, metrics push; not the hook) runs under
    ``jax.profiler.StepTraceAnnotation("train", step_num=t)``, so a
    profile taken round the loop shows its steps.

    This is the ONE loop the launcher, the benchmark harness, and the
    sweep share — so every run emits the same record stream regardless
    of entry point.
    """
    from repro.tracker.callbacks import CallbackRunner
    runner = CallbackRunner(tracker, callbacks, flush_every=log_every)
    if callable(batches) and not hasattr(batches, "__next__"):
        next_batch = batches                      # batch_at(t) form
    else:
        it = iter(batches)
        next_batch = lambda t: next(it)           # noqa: E731
    for t in range(start, n_steps):
        with jax.profiler.StepTraceAnnotation("train", step_num=t):
            try:
                batch = next_batch(t)
            except StopIteration:
                break
            state, stats = step_fn(state, batch)
            runner.push(t, stats)
        if step_hook is not None:
            step_hook(t, state)
    runner.close(summary)
    return state
