"""Paged decode attention — Pallas TPU kernel.

Decode-time attention for one new token per sequence against a paged
KV cache: K/V live in a global pool of fixed-size blocks and each
sequence names its blocks through an int32 block-table row
(serving/paged_cache.py).  The kernel never touches a dense
(B, ctx, ...) cache: the block table and per-sequence positions are
scalar-prefetched (``PrefetchScalarGridSpec``) and the kernel copies
exactly the pool blocks each sequence attends.

Layout: the pools stay in HBM as they are stored, (n_blocks, bs, K, hd),
and enter the ``pallas_call`` untouched.  One pool block (bs, K, hd) is
contiguous, so one DMA moves all K kv heads of bs positions.  q and the
output keep their (B, H, hd) layout; the q heads of kv head k are rows
k*G .. k*G+G-1.  A DMA addresses whole tiles of the pool's HBM layout
(128 lanes of hd; 8 sublanes of K, fewer for a small K, at least the
dtype's packing): a pool whose (K, hd) are not whole tiles is padded
to them in the wrapper — a copy, which only such pools pay.

Grid: (B,), one step per sequence.  A step walks the sequence's live
table columns in runs of P consecutive columns, with manual
double-buffered async copies: run r+1's blocks are in flight while run
r is attended.  A run's P blocks are viewed as (P*bs*K, hd) rows, row
(i*bs + t)*K + k; the scores of all q heads against all rows are one
MXU product and a head mask keeps row ...*K + k for the q heads of kv
head k only.  The online softmax keeps a max and a sum per q head (so
per (K, G) head pair) and an fp32 (H, hd) accumulator.

Skip: a column is live when some position of it is at or before the
frontier (t0 <= pos) and inside the sliding window.  Runs start at the
first live column's run and end at the last's, and a column of those
runs outside the live range is never copied: its buffer keeps an
earlier pool block (or the zeros the first step writes), and the mask
hides it.  No grid step exists for a dead run.

VMEM budget: P comes from what the kernel sees, the block's bytes
bs*K*hd*itemsize: the double-buffered K and V blocks of a run (4*P
blocks) stay within ``KV_VMEM_BUDGET``, at most ``MAX_COLS`` columns
and never more than the table has.  At deepseek-7b widths (bs 16, K 32,
hd 128, bf16: 128 KB a block) that is 4 columns; at yi-9b's (K 4:
16 KB a block) 8, by ``MAX_COLS``.

Numerics: scores take the inputs' dtype with an fp32 accumulator (bf16
K/V and q give exact products); p stays fp32 and multiplies V upcast
to fp32.  Tolerance policy (same as flash_attention): the online
softmax reassociates the reduction, so it is NOT bitwise against the
two-pass ref — fp32 agrees to ~1e-6 atol (few-ulp), bf16 inputs to
~3e-2.  The model's jnp gather path (layers.py) is the bitwise-parity
reference against the dense engine; this kernel is the TPU fast path,
gated differentially in tests/test_kernels.py and BENCH_serving.json.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.0e38
KV_VMEM_BUDGET = 2 << 20      # bytes of K/V copy buffers
MAX_COLS = 8                  # table columns per run, at most


def cols_per_run(block_bytes: int, n_bt: int) -> int:
    """Table columns one run copies and attends (P): the double-buffered
    K and V blocks of a run stay within ``KV_VMEM_BUDGET``."""
    return max(1, min(n_bt, MAX_COLS, KV_VMEM_BUDGET // (4 * block_bytes)))


def pool_tiles(K: int, hd: int, itemsize: int) -> tuple[int, int]:
    """(K, hd) rounded up to whole tiles of a pool's HBM layout on the
    TPU: 128 lanes; sublanes the dtype's packing, doubled while under
    min(K, 8)."""
    sub = 4 // itemsize
    while sub < min(K, 8):
        sub *= 2
    return -(-K // sub) * sub, -(-hd // 128) * 128


def _live(pos, bs, n_bt, window):
    """First and last table column holding a position the token at
    ``pos`` attends."""
    hi = jnp.minimum(pos // bs, n_bt - 1)
    lo = 0 if window <= 0 else jnp.maximum(pos - window + 1, 0) // bs
    return lo, hi


def _idiv(x, n):
    return x >> (n.bit_length() - 1) if n & (n - 1) == 0 else x // n


def _imod(x, n):
    return x & (n - 1) if n & (n - 1) == 0 else x % n


def _kernel(bt_ref, pos_ref, q_ref, kp_hbm, vp_hbm, o_ref, kbuf, vbuf, sem,
            *, P, scale, window, softcap, bs, n_bt, K, G):
    b = pl.program_id(0)
    pos = pos_ref[b]
    lo, hi = _live(pos, bs, n_bt, window)
    last_t = jnp.minimum(pos, n_bt * bs - 1)
    q = q_ref[0]                                           # (H, hd)
    H, hd = q.shape

    @pl.when(b == 0)
    def _zero():
        # a run's dead columns keep what the buffer held: pool blocks
        # copied earlier, or these zeros, never NaN garbage
        kbuf[...] = jnp.zeros_like(kbuf)
        vbuf[...] = jnp.zeros_like(vbuf)

    def copies(r, slot, op):
        for i in range(P):
            c = r * P + i

            @pl.when((c >= lo) & (c <= hi))
            def _():
                blk = bt_ref[b, c]
                for x, (pool, buf) in enumerate(((kp_hbm, kbuf),
                                                 (vp_hbm, vbuf))):
                    getattr(pltpu.make_async_copy(
                        pool.at[blk], buf.at[slot, i], sem.at[x, slot, i]),
                        op)()

    def attend(r, slot, carry):
        m_prev, l_prev, acc = carry
        k = kbuf[slot].reshape(P * bs * K, hd)             # row (i, t, k)
        ct = jnp.promote_types(q.dtype, k.dtype)
        s = jax.lax.dot_general(
            q.astype(ct), k.astype(ct), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale    # (H, P*bs*K)
        if softcap > 0:
            s = softcap * jnp.tanh(s / softcap)
        row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        t = r * (P * bs) + _idiv(col, K)
        mask = (_imod(col, K) == _idiv(row, G)) & (t <= last_t)
        if window > 0:
            mask &= t > pos - window
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        v = vbuf[slot].reshape(P * bs * K, hd).astype(jnp.float32)
        return (m_new, alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True),
                alpha * acc + jax.lax.dot(p, v,
                                          preferred_element_type=jnp.float32))

    def run(r, carry):
        slot = (r - lo // P) % 2

        @pl.when(r < hi // P)
        def _prefetch():
            copies(r + 1, 1 - slot, "start")

        copies(r, slot, "wait")
        return attend(r, slot, carry)

    copies(lo // P, 0, "start")
    init = (jnp.full((H, 1), NEG_INF, jnp.float32),
            jnp.zeros((H, 1), jnp.float32), jnp.zeros((H, hd), jnp.float32))
    _, l, acc = jax.lax.fori_loop(lo // P, hi // P + 1, run, init)
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("window", "softcap",
                                             "interpret"))
def paged_decode_attention(q, kp, vp, bt, pos, *, window: int = 0,
                           softcap: float = 0.0, interpret: bool = False):
    """q: (B, H, hd) — one query token per sequence.
    kp/vp: (n_blocks, bs, K, hd) block pools, H % K == 0.
    bt: (B, nbmax) int32 block table; pos: (B,) int32 position of the
    entry just written (reads are masked to t <= pos).
    Returns (B, H, hd) in q.dtype."""
    B, H, hd = q.shape
    _, bs, K, _ = kp.shape
    n_bt = bt.shape[1]
    Kt, hdt = pool_tiles(K, hd, kp.dtype.itemsize)
    if (Kt, hdt) != (K, hd):
        pad = ((0, 0), (0, 0), (0, Kt - K), (0, hdt - hd))
        kp, vp = jnp.pad(kp, pad), jnp.pad(vp, pad)
        q = jnp.pad(q, ((0, 0), (0, 0), (0, hdt - hd)))
    P = cols_per_run(bs * Kt * hdt * kp.dtype.itemsize, n_bt)
    q_spec = pl.BlockSpec((1, H, hdt), lambda b, bt_, pos_: (b, 0, 0))
    hbm = pl.BlockSpec(memory_space=pltpu.HBM)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B,),
        in_specs=[q_spec, hbm, hbm],
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((2, P, bs, Kt, hdt), kp.dtype),
            pltpu.VMEM((2, P, bs, Kt, hdt), vp.dtype),
            pltpu.SemaphoreType.DMA((2, 2, P)),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, P=P, scale=hd ** -0.5, window=window,
                          softcap=softcap, bs=bs, n_bt=n_bt, K=Kt,
                          G=H // K),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, hdt), q.dtype),
        # in order: the first step zeroes the buffers for all
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="paged_decode_attention",
    )(bt.astype(jnp.int32), pos.astype(jnp.int32), q, kp, vp)
    return out[..., :hd]
