"""YaRN rope scaling in latent attention (DeepSeek-V2's
``DeepseekV2YarnRotaryEmbedding`` and ``softmax_scale``), against the
published formulas computed here independently, and rope without
scaling unchanged bit for bit."""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, RopeScaling
from repro.models import layers

LITE = ARCHS["deepseek-v2-lite-16b"]


def yarn_inv_freq(dim, base, factor, ctx, beta_fast, beta_slow):
    """f_extra = base^(-2i/dim), f_inter = f_extra / factor;
    c(n) = dim ln(ctx / (2 pi n)) / (2 ln base); low = floor(c(fast)),
    high = ceil(c(slow)); ramp = clip((i - low) / (high - low), 0, 1);
    inv_freq = f_inter ramp + f_extra (1 - ramp)."""
    i = np.arange(dim // 2, dtype=np.float64)
    f_extra = base ** (-2 * i / dim)
    f_inter = f_extra / factor

    def c(n):
        return dim * math.log(ctx / (2 * math.pi * n)) / (2 * math.log(base))
    low, high = math.floor(c(beta_fast)), math.ceil(c(beta_slow))
    ramp = np.clip((i - low) / (high - low), 0, 1)
    return f_inter * ramp + f_extra * (1 - ramp), low, high


def test_yarn_frequencies_and_softmax_scale_of_deepseek_v2_lite():
    s = LITE.rope_scaling
    assert (s.factor, s.original_max_position, s.beta_fast, s.beta_slow,
            s.mscale, s.mscale_all_dim) == (40, 4096, 32, 1, 0.707, 0.707)
    want, low, high = yarn_inv_freq(64, 10000.0, 40, 4096, 32, 1)
    assert (low, high) == (10, 23)
    # the program's frequencies, read back from the rotation of a unit
    # vector at position 1: angle_i = inv_freq_i
    x = jnp.zeros((1, 1, 1, 64)).at[..., :32].set(1.0)
    out = layers.rope(x, jnp.ones((1, 1), jnp.int32), 10000.0, s)
    got = np.arctan2(np.asarray(out[0, 0, 0, 32:]),
                     np.asarray(out[0, 0, 0, :32]))
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-7)
    # cos and sin keep unit length: mscale / mscale_all_dim = 1 here
    np.testing.assert_allclose(np.hypot(np.asarray(out[0, 0, 0, 32:]),
                                        np.asarray(out[0, 0, 0, :32])),
                               1.0, rtol=1e-6)
    mscale = 0.1 * 0.707 * math.log(40) + 1
    assert layers.mla_softmax_scale(LITE) == pytest.approx(
        192 ** -0.5 * mscale ** 2, rel=1e-12)
    assert layers.mla_softmax_scale(LITE) == pytest.approx(0.114721,
                                                           abs=1e-6)


def test_yarn_cos_sin_scale_where_the_two_mscales_differ():
    s = RopeScaling(factor=4.0, original_max_position=2048, mscale=1.0,
                    mscale_all_dim=0.0)
    x = jnp.zeros((1, 1, 1, 16)).at[..., :8].set(1.0)
    out = np.asarray(layers.rope(x, jnp.zeros((1, 1), jnp.int32), 500.0, s))
    assert out[0, 0, 0, :8] == pytest.approx(0.1 * math.log(4) + 1,
                                             rel=1e-6)


def _rope_before_scaling(x, pos, theta):
    """The split-half rope as it was before rope scaling existed."""
    hd = x.shape[-1]
    half = hd // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    angles = pos[..., None, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = x[..., :half], x[..., half:]
    x1f, x2f = x1.astype(jnp.float32), x2.astype(jnp.float32)
    out = jnp.concatenate([x1f * cos - x2f * sin, x2f * cos + x1f * sin],
                          axis=-1)
    return out.astype(x.dtype)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rope_without_scaling_is_unchanged_bit_for_bit(dtype):
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 48, 4, 128), dtype)
    pos = jnp.broadcast_to(jnp.arange(48)[None] * 97, (2, 48))
    want = jax.jit(_rope_before_scaling, static_argnums=2)(x, pos, 10000.0)
    got = jax.jit(layers.rope, static_argnums=(2, 3))(x, pos, 10000.0)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_mla_without_scaling_keeps_its_plain_softmax_scale():
    cfg = dataclasses.replace(LITE, rope_scaling=None)
    assert layers.mla_softmax_scale(cfg) == 192 ** -0.5
