"""Pallas kernel validation: shape/dtype sweeps against ref.py oracles,
all in interpret mode (the kernel body executes in Python on CPU)."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.fused_sngm.kernel import fused_sngm_update
from repro.kernels.fused_sngm.ref import sngm_update_ref
from repro.kernels.fused_lars.kernel import fused_lars_update
from repro.kernels.fused_lars.ref import lars_update_ref
from repro.kernels.rmsnorm.kernel import rmsnorm_pallas
from repro.kernels.rmsnorm.ref import rmsnorm_ref
from repro.kernels.flash_attention.kernel import flash_attention
from repro.kernels.flash_attention.ref import attention_ref

KEY = jax.random.PRNGKey(0)


def _rand(shape, dtype=jnp.float32, i=0):
    return jax.random.normal(jax.random.fold_in(KEY, i), shape, jnp.float32).astype(dtype)


# ---------------------------------------------------------------------------
# fused SNGM
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape", [(17,), (128,), (100, 37), (8, 16, 33),
                                   (1024, 128)])
@pytest.mark.parametrize("gdtype", [jnp.float32, jnp.bfloat16])
def test_fused_sngm_shapes_dtypes(shape, gdtype):
    p = _rand(shape, i=1)
    g = _rand(shape, gdtype, i=2) * 30
    u = _rand(shape, i=3)
    inv, lr = jnp.float32(0.03), jnp.float32(0.7)
    pn, un = fused_sngm_update(p, g, u, inv, lr, beta=0.9, interpret=True)
    pr, ur = sngm_update_ref(p, g, u, inv, lr, beta=0.9)
    np.testing.assert_allclose(np.asarray(pn), np.asarray(pr), atol=1e-6)
    np.testing.assert_allclose(np.asarray(un), np.asarray(ur), atol=1e-6)


# ---------------------------------------------------------------------------
# fused LARS
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape", [(64,), (513, 97), (32, 32, 9)])
def test_fused_lars_shapes(shape):
    w = _rand(shape, i=4)
    g = _rand(shape, i=5) * 5
    v = _rand(shape, i=6) * 0.1
    lr = jnp.float32(0.5)
    wo, vo = fused_lars_update(w, g, v, lr, beta=0.9, wd=1e-4, interpret=True)
    wr, vr = lars_update_ref(w, g, v, lr, beta=0.9, wd=1e-4)
    np.testing.assert_allclose(np.asarray(wo), np.asarray(wr), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(vo), np.asarray(vr), rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# RMSNorm
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape", [(4, 128), (3, 7, 256), (2, 33, 300),
                                   (16, 2048)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm_shapes_dtypes(shape, dtype):
    x = _rand(shape, dtype, i=7)
    s = _rand(shape[-1:], i=8)
    o = rmsnorm_pallas(x, s, interpret=True)
    r = rmsnorm_ref(x, s)
    atol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(r, np.float32), atol=atol)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("S,H,K,hd", [(256, 4, 4, 64), (512, 4, 2, 64),
                                      (256, 8, 1, 128)])
@pytest.mark.parametrize("kw", [dict(causal=True),
                                dict(causal=True, window=128),
                                dict(causal=True, softcap=50.0),
                                dict(causal=False)])
def test_flash_attention_sweep(S, H, K, hd, kw):
    B = 2
    q = _rand((B, S, H, hd), i=9)
    k = _rand((B, S, K, hd), i=10)
    v = _rand((B, S, K, hd), i=11)
    o = flash_attention(q, k, v, q_blk=128, kv_blk=128, interpret=True, **kw)
    r = attention_ref(q, k, v, **kw)
    np.testing.assert_allclose(np.asarray(o), np.asarray(r), atol=2e-5)


def test_flash_attention_bf16():
    B, S, H, hd = 1, 256, 2, 64
    q = _rand((B, S, H, hd), jnp.bfloat16, i=12)
    k = _rand((B, S, H, hd), jnp.bfloat16, i=13)
    v = _rand((B, S, H, hd), jnp.bfloat16, i=14)
    o = flash_attention(q, k, v, q_blk=128, kv_blk=128, interpret=True)
    r = attention_ref(q, k, v)
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(r, np.float32), atol=3e-2)


def test_flash_attention_matches_model_sdpa():
    """The kernel must agree with the model's _sdpa_seq path (the jnp
    implementation the dry-run lowers), including window+softcap."""
    from repro.models import layers
    B, S, H, K, hd = 1, 256, 4, 2, 64
    q = _rand((B, S, H, hd), i=15)
    k = _rand((B, S, K, hd), i=16)
    v = _rand((B, S, K, hd), i=17)
    o_kernel = flash_attention(q, k, v, q_blk=128, kv_blk=128, window=64,
                               softcap=30.0, interpret=True)
    o_model = layers._sdpa_seq(q, k, v, True, 64, 30.0, hd ** -0.5)
    np.testing.assert_allclose(np.asarray(o_kernel), np.asarray(o_model),
                               atol=2e-5)


# ---------------------------------------------------------------------------
# paged decode attention
# ---------------------------------------------------------------------------

from repro.kernels.paged_attention import kernel as paged_kernel
from repro.kernels.paged_attention.kernel import paged_decode_attention
from repro.kernels.paged_attention.ref import paged_attention_ref

# (H, K, hd): the old small MHA and GQA cases, the chat cell's MHA head
# shape (deepseek-7b: K 32, hd 128) and yi-9b's GQA (K 4, G 8)
PAGED_HEADS = [pytest.param(4, 4, 64, id="4-4"),
               pytest.param(8, 2, 64, id="8-2"),
               pytest.param(32, 32, 128, id="mha-k32"),
               pytest.param(32, 4, 128, id="gqa-k4g8")]


def _paged_case(B, H, K, hd, bs, nbt, i):
    """Random pools + a block table of distinct non-scratch blocks."""
    nb = 1 + B * nbt + 3          # scratch + owned + spare
    q = _rand((B, H, hd), i=i)
    kp = _rand((nb, bs, K, hd), i=i + 1)
    vp = _rand((nb, bs, K, hd), i=i + 2)
    ids = np.random.RandomState(i).permutation(
        np.arange(1, nb))[:B * nbt].reshape(B, nbt).astype(np.int32)
    return q, kp, vp, jnp.asarray(ids)


def _frontiers(bs, nbt):
    """Frontier at the first entry, at a block boundary, mid-block in
    the last column, at the very last slot; and an inactive slot: its
    whole table row on the scratch block 0, pos 0."""
    pos = jnp.asarray([0, bs, (nbt - 1) * bs + bs // 2, nbt * bs - 1, 0],
                      jnp.int32)
    return pos, lambda bt: bt.at[4].set(0)


@pytest.mark.parametrize("H,K,hd", PAGED_HEADS)
@pytest.mark.parametrize("bs,nbt", [(8, 4), (16, 2), (16, 7)])
def test_paged_attention_matches_ref(H, K, hd, bs, nbt):
    q, kp, vp, bt = _paged_case(5, H, K, hd, bs, nbt, i=20)
    pos, inactive = _frontiers(bs, nbt)
    bt = inactive(bt)
    o = paged_decode_attention(q, kp, vp, bt, pos, interpret=True)
    r = paged_attention_ref(q, kp, vp, bt, pos)
    np.testing.assert_allclose(np.asarray(o), np.asarray(r), atol=2e-5)


def test_paged_cases_cover_a_partial_last_run():
    """At the chat cell's head shape in fp32 a run copies fewer columns
    than 7, and not a divisor of 7: the (16, 7) cases above end in a run
    the table does not fill."""
    P = paged_kernel.cols_per_run(16 * 32 * 128 * 4, 7)
    assert 1 < P < 7 and 7 % P


@pytest.mark.parametrize("kw", [dict(window=10), dict(softcap=30.0),
                                dict(window=7, softcap=20.0)])
@pytest.mark.parametrize("H,K,hd,bs,nbt,pos", [
    pytest.param(8, 2, 64, 8, 4, (5, 17, 31), id="gqa"),
    pytest.param(32, 32, 128, 16, 7, (5, 40, 100), id="mha-k32")])
def test_paged_attention_window_softcap(kw, H, K, hd, bs, nbt, pos):
    q, kp, vp, bt = _paged_case(3, H, K, hd, bs, nbt, i=30)
    pos = jnp.asarray(pos, jnp.int32)
    o = paged_decode_attention(q, kp, vp, bt, pos, interpret=True, **kw)
    r = paged_attention_ref(q, kp, vp, bt, pos, **kw)
    np.testing.assert_allclose(np.asarray(o), np.asarray(r), atol=2e-5)


@pytest.mark.parametrize("H,K,hd,bs,nbt", [
    pytest.param(4, 2, 64, 8, 3, id="gqa"),
    pytest.param(32, 32, 128, 16, 5, id="mha-k32"),
    pytest.param(32, 4, 128, 16, 5, id="gqa-k4g8")])
def test_paged_attention_bf16(H, K, hd, bs, nbt):
    q, kp, vp, bt = _paged_case(5, H, K, hd, bs, nbt, i=40)
    q, kp, vp = (x.astype(jnp.bfloat16) for x in (q, kp, vp))
    pos, inactive = _frontiers(bs, nbt)
    bt = inactive(bt)
    o = paged_decode_attention(q, kp, vp, bt, pos, interpret=True)
    r = paged_attention_ref(q, kp, vp, bt, pos)
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(r, np.float32), atol=3e-2)


@pytest.mark.parametrize("H,K,hd,dtype", [(32, 32, 128, jnp.bfloat16),
                                          (32, 4, 128, jnp.bfloat16),
                                          (8, 2, 128, jnp.float32)])
def test_paged_kernel_reads_the_pool_in_place(H, K, hd, dtype):
    """The pallas_call's K/V operands are the pools themselves, in
    their own (n_blocks, bs, K, hd) shape: the wrapper transposes and
    pads neither, so no copy of the layer pool precedes the kernel."""
    bs, nbt = 16, 7
    q, kp, vp, bt = _paged_case(2, H, K, hd, bs, nbt, i=60)
    q, kp, vp = (x.astype(dtype) for x in (q, kp, vp))
    pos = jnp.asarray([20, 100], jnp.int32)
    closed = jax.make_jaxpr(functools.partial(paged_decode_attention,
                                              interpret=True))(
        q, kp, vp, bt, pos)
    (call,) = closed.jaxpr.eqns                 # the wrapper's jit
    inner = call.params["jaxpr"].jaxpr
    _, k_in, v_in, _, _ = inner.invars
    (kern,) = [e for e in inner.eqns if e.primitive.name == "pallas_call"]
    assert kern.invars[-2:] == [k_in, v_in]
    assert k_in.aval.shape == v_in.aval.shape == kp.shape
    for e in inner.eqns:
        if e.primitive.name in ("transpose", "pad"):
            assert not {k_in, v_in} & set(e.invars), e


@pytest.mark.parametrize("window", [0, 20])
def test_paged_kernel_never_reads_dead_columns(window):
    """The blocks of table columns past the frontier or wholly outside
    the window are never copied: NaN in them does not reach the output,
    though runs of P columns end inside the table."""
    B, H, K, hd, bs, nbt = 4, 32, 32, 128, 16, 7
    q, kp, vp, bt = _paged_case(B, H, K, hd, bs, nbt, i=70)
    pos = [0, 37, 100, nbt * bs - 1]
    r = paged_attention_ref(q, kp, vp, bt, jnp.asarray(pos), window=window)
    dead = [int(bt[b, c]) for b, p in enumerate(pos) for c in range(nbt)
            if not (c * bs <= p and (window == 0 or c * bs + bs > p - window
                                     + 1))]
    assert dead
    kp, vp = (x.at[jnp.asarray(dead)].set(jnp.nan) for x in (kp, vp))
    o = paged_decode_attention(q, kp, vp, bt, jnp.asarray(pos),
                               window=window, interpret=True)
    np.testing.assert_allclose(np.asarray(o), np.asarray(r), atol=2e-5)


def test_paged_ref_matches_model_gather_path():
    """ref.py must equal the model's jnp paged decode math (_paged_gather
    + _sdpa), which is itself the bitwise-parity reference vs the dense
    engine — chaining kernel -> ref -> model -> dense."""
    from repro.models import layers
    B, H, K, hd, bs, nbt = 2, 8, 2, 64, 8, 3
    q, kp, vp, bt = _paged_case(B, H, K, hd, bs, nbt, i=50)
    pos = jnp.asarray([9, 21], jnp.int32)
    r = paged_attention_ref(q, kp, vp, bt, pos)
    kd = layers._paged_gather(kp, bt)
    vd = layers._paged_gather(vp, bt)
    valid = layers._paged_valid(pos, kd.shape[1], 0)
    mask = jnp.where(valid, 0.0, layers.NEG_INF)[:, None, None, :]
    o = layers._sdpa(q[:, None], kd, vd, mask, 0.0, hd ** -0.5)[:, 0]
    np.testing.assert_allclose(np.asarray(r), np.asarray(o), atol=2e-5)


# ---------------------------------------------------------------------------
# backend choice: interpret mode / gather path on the CPU backend only
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend,interpret", [("cpu", True), ("tpu", False),
                                               ("gpu", None)])
def test_kernels_run_on_tpu_or_interpreted_on_cpu(monkeypatch, backend,
                                                  interpret):
    """No silent fallback: a backend that is neither the chip nor the
    CPU the tests use raises instead of running the hot paths on it."""
    from repro import kernels
    from repro.models import layers
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    monkeypatch.setattr(layers, "PAGED_DECODE_KERNEL", None)
    if interpret is None:
        with pytest.raises(RuntimeError, match="not on 'gpu'"):
            kernels.interpret_mode()
        with pytest.raises(RuntimeError):
            layers._use_paged_kernel()
    else:
        assert kernels.interpret_mode() is interpret
        assert layers._use_paged_kernel() is (not interpret)
