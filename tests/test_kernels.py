"""Pallas kernel validation: shape/dtype sweeps against ref.py oracles,
all in interpret mode (the kernel body executes in Python on CPU)."""
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

from repro.kernels.paged_attention.kernel import paged_decode_attention
from repro.kernels.paged_attention.ref import paged_attention_ref


def _paged_case(B, H, K, hd, bs, nbt, i):
    """Random pools + a block table of distinct non-scratch blocks."""
    nb = 1 + B * nbt + 3          # scratch + owned + spare
    q = _rand((B, H, hd), i=i)
    kp = _rand((nb, bs, K, hd), i=i + 1)
    vp = _rand((nb, bs, K, hd), i=i + 2)
    ids = np.random.RandomState(i).permutation(
        np.arange(1, nb))[:B * nbt].reshape(B, nbt).astype(np.int32)
    return q, kp, vp, jnp.asarray(ids)


@pytest.mark.parametrize("H,K", [(4, 4), (8, 2)])          # MHA and GQA
@pytest.mark.parametrize("bs,nbt", [(8, 4), (16, 2)])
def test_paged_attention_matches_ref(H, K, bs, nbt):
    B, hd = 3, 64
    q, kp, vp, bt = _paged_case(B, H, K, hd, bs, nbt, i=20)
    # frontier at a block boundary, mid-block, and the very last slot
    pos = jnp.asarray([0, bs, nbt * bs - 1], jnp.int32)
    o = paged_decode_attention(q, kp, vp, bt, pos, interpret=True)
    r = paged_attention_ref(q, kp, vp, bt, pos)
    np.testing.assert_allclose(np.asarray(o), np.asarray(r), atol=2e-5)


@pytest.mark.parametrize("kw", [dict(window=10), dict(softcap=30.0),
                                dict(window=7, softcap=20.0)])
def test_paged_attention_window_softcap(kw):
    B, H, K, hd, bs, nbt = 3, 8, 2, 64, 8, 4
    q, kp, vp, bt = _paged_case(B, H, K, hd, bs, nbt, i=30)
    pos = jnp.asarray([5, 17, 31], jnp.int32)
    o = paged_decode_attention(q, kp, vp, bt, pos, interpret=True, **kw)
    r = paged_attention_ref(q, kp, vp, bt, pos, **kw)
    np.testing.assert_allclose(np.asarray(o), np.asarray(r), atol=2e-5)


def test_paged_attention_bf16():
    B, H, K, hd, bs, nbt = 2, 4, 2, 64, 8, 3
    q, kp, vp, bt = _paged_case(B, H, K, hd, bs, nbt, i=40)
    q, kp, vp = (x.astype(jnp.bfloat16) for x in (q, kp, vp))
    pos = jnp.asarray([6, 19], jnp.int32)
    o = paged_decode_attention(q, kp, vp, bt, pos, interpret=True)
    r = paged_attention_ref(q, kp, vp, bt, pos)
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(r, np.float32), atol=3e-2)


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
