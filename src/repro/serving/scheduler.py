"""Continuous-batching request scheduler over the paged KV cache.

Replaces the ad-hoc slot loop of ``launch.serve.ContinuousBatcher``
(one prefill compile per distinct prompt length, one host sync per
decoded token, O(n_slots x ctx) cache) with:

  * **Admission by free-block budget** — a request is admitted only
    when the ``BlockAllocator`` can cover its prompt; copy-on-write
    prefix sharing (``plan_prompt``) retains already-resident blocks
    instead of re-writing them, so identical prompt prefixes cost one
    set of blocks no matter how many slots share them.
  * **Bucket-padded batched prefill** — admitted prompts are grouped,
    right-padded to a bucket length and to ``n_slots`` rows, and
    prefilled in ONE call per bucket; ``last_pos`` picks each row's
    true last-token logits.  Causal masking makes positions
    ``t <= last_pos`` bitwise independent of right padding, so padded
    group prefill equals a solo prefill exactly.  SSM architectures
    scan *through* padding (state would see the pad tokens), so for
    ``cfg.has_ssm_layers`` buckets degrade to exact prompt lengths.
    The compile count is bounded by the number of buckets, not by the
    number of distinct prompt lengths.
  * **Chunked on-device decode** — ``lax.scan`` of ``decode_chunk``
    serve steps per host round-trip (one compile total); requests that
    finish mid-chunk have their overshoot tokens discarded host-side.
    Inactive slots point their block table at the scratch block and
    hold ``pos = 0``, so lockstep writes land harmlessly.
  * **Preemption & requeue** — when decode growth needs blocks the
    pool cannot supply, the latest-admitted victim releases its blocks
    and re-enters the queue for full recomputation (prompt + tokens
    generated so far), bounding memory at O(used blocks) with no
    reserved worst-case allocation.

Every round runs under host spans on the profiler's clock
(``jax.profiler.TraceAnnotation``, about a microsecond each with no
profiler attached): ``serve.round`` with its phases as direct children
— ``serve.admit`` (planning and allocation), ``serve.prefill`` (args
``bucket``, ``rows``, ``real_tokens``; one ``serve.splice`` per request
inside), ``serve.grow_blocks``, ``serve.chunk`` (dispatch),
``serve.sync`` (the token read-back) and ``serve.emit`` — and
``serve.sync`` (arg ``what``) round every device-to-host read.
``stats`` counts rounds, host syncs, block-table writes, prefill real
and slot tokens, decoded tokens and queue wait.

Token streams are bitwise equal to the dense engine's at matched
geometry (gathered length == dense context; see layers.py paged
branches), independent of arrival order, grouping, or preemption —
prefill is deterministic and RoPE positions are absolute.  Sampling
(``temperature > 0``) is driven by a fold_in-counted PRNG key, so a
fixed seed and workload reproduce exactly.
"""
from __future__ import annotations

import contextlib
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Deque, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models.runtime import Runtime
from repro.serving.engine import (cache_abstract, make_prefill_step,
                                  make_serve_step, sample_logits)
from repro.serving.paged_cache import (BlockAllocator, PoolExhausted,
                                       n_blocks_for, paged_cache_init,
                                       set_block_table, splice_prefill)


@dataclass
class ServeRequest:
    """One generation request and its lifecycle record."""
    rid: int
    prompt: np.ndarray                  # (S0,) int32 token ids
    max_new: int
    out: List[int] = field(default_factory=list)
    done: bool = False
    # timeline (host wall clock, for latency reporting)
    t_submit: float = 0.0
    t_admit: float = 0.0                # first admission
    t_first: float = 0.0
    t_done: float = 0.0
    token_times: List[float] = field(default_factory=list)
    preemptions: int = 0
    # tokens already folded back into ``prompt`` by preemption recompute
    n_folded: int = 0

    @property
    def n_generated(self) -> int:
        return len(self.out)


def default_buckets(ctx_max: int, lo: int = 8) -> List[int]:
    """Power-of-two prompt-length buckets up to ``ctx_max``."""
    out, b = [], lo
    while b < ctx_max:
        out.append(b)
        b *= 2
    return out + [ctx_max]


class PagedScheduler:
    """Continuous batching over ``n_slots`` lockstep decode lanes backed
    by a shared pool of ``n_blocks`` KV blocks (see module docstring)."""

    def __init__(self, cfg: ModelConfig, params, rt: Runtime, *,
                 n_slots: int, block_size: int, n_blocks: int, ctx_max: int,
                 decode_chunk: int = 4, buckets: Optional[Sequence[int]] = None,
                 temperature: float = 0.0, top_k: int = 0, seed: int = 0):
        assert not cfg.is_encoder_decoder, "paged serving is decoder-only"
        if cfg.window:
            assert ctx_max <= cfg.window, \
                "paged serving keeps windowed caches unrotated (ctx <= window)"
        self.cfg, self.params, self.rt = cfg, params, rt
        self.n_slots, self.block_size = n_slots, block_size
        self.ctx_max = ctx_max
        self.decode_chunk = decode_chunk
        self.temperature, self.top_k = temperature, top_k
        self.nbmax = n_blocks_for(ctx_max, block_size)
        self.buckets = sorted(buckets) if buckets else default_buckets(ctx_max)

        self.alloc = BlockAllocator(n_blocks, block_size)
        self.paged = paged_cache_init(cfg, n_slots, block_size, n_blocks,
                                      self.nbmax)
        self._prefill = jax.jit(make_prefill_step(cfg, rt))
        step = make_serve_step(cfg, rt, temperature=temperature, top_k=top_k)

        def chunk(params, cache, tok, pos, active, rngs):
            # active: (k, n_slots) per-step mask — a slot whose request
            # finishes mid-chunk freezes (pos held, token pinned 0), so
            # lockstep never writes past a request's own quota and pos
            # never overruns the block table.
            def body(carry, xs):
                tok, pos, cache = carry
                rng, act = xs
                nxt, _, cache = step(params, cache, tok, pos, rng)
                nxt = jnp.where(act, nxt, tok[:, 0])
                pos = jnp.where(act, pos + 1, pos)
                return (nxt[:, None], pos, cache), nxt
            (tok, pos, cache), toks = jax.lax.scan(
                body, (tok, pos, cache), (rngs, active))
            return tok, pos, cache, toks      # toks: (k, n_slots)
        self._chunk = jax.jit(chunk)

        self.queue: Deque[ServeRequest] = deque()
        self.slots: List[Optional[ServeRequest]] = [None] * n_slots
        self.blocks: Dict[int, List[int]] = {}      # slot -> owned block ids
        self._admit_order: List[tuple] = []         # (slot, rid), oldest first
        self.tok = jnp.zeros((n_slots, 1), jnp.int32)
        self.pos = jnp.zeros((n_slots,), jnp.int32)
        self._key = jax.random.PRNGKey(seed)
        self._rng_ctr = 0

        self.finished: List[ServeRequest] = []
        self.stats = {"prefill_shapes": set(), "decode_shapes": set(),
                      "peak_used_blocks": 0, "preemptions": 0,
                      "decode_steps": 0, "prefill_calls": 0,
                      "rounds": 0, "host_syncs": 0, "table_writes": 0,
                      "prefill_real_tokens": 0, "prefill_slot_tokens": 0,
                      "decode_tokens": 0, "queue_wait_s": 0.0}

    # -- submission --------------------------------------------------------

    def submit(self, req: ServeRequest) -> None:
        S0 = len(req.prompt)
        assert S0 + req.max_new <= self.ctx_max, \
            f"request {req.rid}: {S0}+{req.max_new} exceeds ctx_max"
        req.t_submit = req.t_submit or time.monotonic()
        self.queue.append(req)

    @property
    def idle(self) -> bool:
        return not self.queue and all(r is None for r in self.slots)

    # -- admission (bucket-padded group prefill) ---------------------------

    def _bucket(self, S0: int) -> int:
        if self.cfg.has_ssm_layers:
            return S0            # Mamba scans through padding: exact length
        for b in self.buckets:
            if b >= S0:
                return b
        return self.ctx_max

    @contextlib.contextmanager
    def _sync(self, what: str):
        """Round a device-to-host read: counted, and spanned."""
        self.stats["host_syncs"] += 1
        with jax.profiler.TraceAnnotation("serve.sync", what=what):
            yield

    def _set_table(self, slot: int, block_ids) -> None:
        self.stats["table_writes"] += 1
        self.paged = set_block_table(self.paged, slot, block_ids)

    def _next_rng(self):
        rng = jax.random.fold_in(self._key, self._rng_ctr)
        self._rng_ctr += 1
        return rng

    def admit(self) -> int:
        """Admit as many queued requests as free slots and the block
        budget allow; one batched prefill per occupied bucket.  Returns
        the number of requests admitted."""
        staged: Dict[int, List[tuple]] = {}      # bucket -> [(slot, req, plan)]
        with jax.profiler.TraceAnnotation("serve.admit"):
            free = [i for i, r in enumerate(self.slots) if r is None]
            while self.queue and free:
                req = self.queue[0]
                S0 = len(req.prompt)
                shared, keys = self.alloc.plan_prompt(req.prompt)
                need = n_blocks_for(S0, self.block_size) - len(shared)
                if self.alloc.n_free < need:
                    for bid in shared:           # abandon: undo retains
                        self.alloc.release(bid)
                    break                        # admission never preempts
                self.queue.popleft()
                if not req.t_admit:
                    req.t_admit = time.monotonic()
                    self.stats["queue_wait_s"] += req.t_admit - req.t_submit
                ids = shared + [self.alloc.alloc() for _ in range(need)]
                slot = free.pop(0)
                staged.setdefault(self._bucket(S0), []).append(
                    (slot, req, ids, keys, len(shared)))
        for bucket, group in sorted(staged.items()):
            self._prefill_group(bucket, group)
        return sum(len(g) for g in staged.values())

    def _prefill_group(self, bucket: int, group) -> None:
        real = sum(len(g[1].prompt) for g in group)
        with jax.profiler.TraceAnnotation("serve.prefill", bucket=bucket,
                                          rows=len(group), real_tokens=real):
            toks = np.zeros((self.n_slots, bucket), np.int32)
            last = np.zeros((self.n_slots,), np.int32)
            for i, (_, req, *_rest) in enumerate(group):
                S0 = len(req.prompt)
                toks[i, :S0] = req.prompt
                last[i] = S0 - 1
            self.stats["prefill_shapes"].add((self.n_slots, bucket))
            self.stats["prefill_calls"] += 1
            logits, dense = self._prefill(self.params, jnp.asarray(toks),
                                          last_pos=jnp.asarray(last))
            rng = self._next_rng()
            if self.temperature == 0.0:
                first = jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)
            else:
                first = sample_logits(logits[:, -1, :], rng, self.temperature,
                                      self.top_k)
            with self._sync("first"):
                first = np.asarray(first)
            now = time.monotonic()
            for i, (slot, req, ids, keys, n_shared) in enumerate(group):
                with jax.profiler.TraceAnnotation("serve.splice"):
                    self._set_table(slot, ids)
                    self.paged = splice_prefill(self.paged, dense, i, slot,
                                                ids, skip_blocks=n_shared)
                    for j in range(n_shared, len(keys)):   # publish (COW)
                        self.alloc.register(keys[j], ids[j])
                    self.slots[slot] = req
                    self.blocks[slot] = ids
                    self._admit_order.append((slot, req.rid))
                    req.out.append(int(first[i]))
                    req.t_first = now
                    req.token_times.append(now)
                    self.tok = self.tok.at[slot, 0].set(int(first[i]))
                    self.pos = self.pos.at[slot].set(len(req.prompt))
                    self._finish_if_done(slot, now)
        self.stats["prefill_real_tokens"] += real
        self.stats["prefill_slot_tokens"] += self.n_slots * bucket
        self.stats["peak_used_blocks"] = max(self.stats["peak_used_blocks"],
                                             self.alloc.used_blocks)

    # -- preemption --------------------------------------------------------

    def _preempt_one(self) -> bool:
        """Evict the latest-admitted active request: release its blocks
        and requeue it (front) for full recompute of prompt+generated."""
        while self._admit_order:
            slot, rid = self._admit_order.pop()
            req = self.slots[slot]
            if req is not None and req.rid == rid:   # skip stale entries
                break
        else:
            return False
        for bid in self.blocks.pop(slot):
            self.alloc.release(bid)
        self._clear_slot(slot)
        # recompute path: tokens emitted since the last admission become
        # prompt again (``out`` keeps the full emitted record)
        req.prompt = np.concatenate(
            [req.prompt, np.asarray(req.out[req.n_folded:], np.int32)])
        req.n_folded = len(req.out)
        req.preemptions += 1
        self.stats["preemptions"] += 1
        self.queue.appendleft(req)
        return True

    def _clear_slot(self, slot: int) -> None:
        self.slots[slot] = None
        # point the table at scratch and park pos at 0
        self._set_table(slot, [])
        self.pos = self.pos.at[slot].set(0)
        self.tok = self.tok.at[slot, 0].set(0)

    def _grow_blocks(self) -> None:
        """Ensure every active slot owns blocks covering its next
        ``decode_chunk`` writes, preempting (latest first) on demand."""
        with jax.profiler.TraceAnnotation("serve.grow_blocks"):
            for slot, req in enumerate(self.slots):
                if req is None:
                    continue
                take = min(self.decode_chunk, req.max_new - req.n_generated)
                with self._sync("pos"):
                    pos = int(self.pos[slot])
                need = n_blocks_for(pos + take, self.block_size)
                while len(self.blocks.get(slot, [])) < need:
                    try:
                        self.blocks[slot].append(self.alloc.alloc())
                    except PoolExhausted:
                        # never preempt the slot we are growing unless it
                        # is the only active one (its requeue frees us)
                        if not self._preempt_one():
                            raise
                        if self.slots[slot] is None:   # evicted ourselves
                            break
                        continue
                if self.slots[slot] is not None:
                    self._set_table(slot, self.blocks[slot])

    # -- decode ------------------------------------------------------------

    def _finish_if_done(self, slot: int, now: float) -> None:
        req = self.slots[slot]
        if req is not None and req.n_generated >= req.max_new:
            req.done = True
            req.t_done = now
            self.finished.append(req)
            for bid in self.blocks.pop(slot):
                self.alloc.release(bid)
            self._clear_slot(slot)

    def decode(self) -> None:
        """One chunk of ``decode_chunk`` lockstep steps fully on device."""
        self._grow_blocks()
        takes = [0 if r is None else min(self.decode_chunk,
                                         r.max_new - r.n_generated)
                 for r in self.slots]
        if not any(takes):
            return
        with jax.profiler.TraceAnnotation("serve.chunk"):
            active = jnp.asarray([[i < t for t in takes]
                                  for i in range(self.decode_chunk)])
            rngs = jnp.stack([self._next_rng()
                              for _ in range(self.decode_chunk)])
            self.stats["decode_shapes"].add((self.n_slots, self.decode_chunk))
            self.tok, self.pos, self.paged, toks = self._chunk(
                self.params, self.paged, self.tok, self.pos, active, rngs)
        self.stats["decode_steps"] += self.decode_chunk
        self.stats["decode_tokens"] += sum(takes)
        with self._sync("toks"):
            toks = np.asarray(toks)                 # (k, n_slots)
        with jax.profiler.TraceAnnotation("serve.emit"):
            now = time.monotonic()
            for slot, req in enumerate(self.slots):
                if req is None:
                    continue
                take = takes[slot]
                req.out.extend(int(t) for t in toks[:take, slot])
                req.token_times.extend([now] * take)  # chunk-granular stamps
                self._finish_if_done(slot, now)
        self.stats["peak_used_blocks"] = max(self.stats["peak_used_blocks"],
                                             self.alloc.used_blocks)

    # -- driver ------------------------------------------------------------

    def step(self) -> None:
        """One scheduler round: admit what fits, then decode a chunk."""
        self.stats["rounds"] += 1
        with jax.profiler.TraceAnnotation("serve.round"):
            self.admit()
            self.decode()

    def run(self) -> List[ServeRequest]:
        """Drain queue and slots to completion; returns finished requests."""
        while not self.idle:
            self.step()
        return self.finished

    def compile_counts(self) -> Dict[str, int]:
        """Distinct jitted shapes — deterministic stand-ins for XLA
        compile counts (each distinct shape is exactly one jit miss)."""
        return {"prefill": len(self.stats["prefill_shapes"]),
                "decode": len(self.stats["decode_shapes"])}
