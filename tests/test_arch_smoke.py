"""Per-architecture smoke tests: a REDUCED variant of each assigned arch
(2 layers, d_model<=256, <=4 experts) runs one forward + one train step on
CPU; output shapes and finiteness asserted.  Decode consistency (prefill
vs step-by-step with every cache type) is covered in test_decode.py.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, SHAPES, input_specs, smoke_variant
from repro.core import sngm
from repro.core.schedules import constant
from repro.models import CPU_RUNTIME, forward, model_defs
from repro.models.param import (STACKED_AXES, ParamDef, _fan_in,
                                materialize)
from repro.training import make_train_step

ALL_ARCHS = sorted(ARCHS)


@pytest.fixture(scope="module")
def built():
    cache = {}

    def get(name):
        if name not in cache:
            cfg = smoke_variant(ARCHS[name])
            defs = model_defs(cfg)
            params = materialize(defs, jax.random.PRNGKey(0))
            cache[name] = (cfg, params)
        return cache[name]
    return get


def _batch(cfg, B=2, S=32):
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0,
                                cfg.vocab_size, jnp.int32)
    batch = {"tokens": tokens, "loss_mask": jnp.ones((B, S), jnp.float32)}
    if cfg.is_encoder_decoder:
        batch["encoder_embeds"] = jax.random.normal(
            jax.random.PRNGKey(2), (B, cfg.encoder_len, cfg.d_model))
    return batch


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_forward_shapes_and_finite(built, arch):
    cfg, params = built(arch)
    B, S = 2, 32
    batch = _batch(cfg, B, S)
    h, cache, aux = forward(params, cfg, CPU_RUNTIME, batch["tokens"],
                            mode="train",
                            encoder_embeds=batch.get("encoder_embeds"))
    assert h.shape == (B, S, cfg.d_model)
    assert np.all(np.isfinite(np.asarray(h, np.float32)))
    assert cache is None
    assert all(np.isfinite(float(v)) for v in aux.values())


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_train_step(built, arch):
    cfg, params = built(arch)
    batch = _batch(cfg)
    opt = sngm(constant(0.01), beta=0.9, weight_decay=1e-4)
    state = opt.init_state(params)
    step = jax.jit(make_train_step(cfg, CPU_RUNTIME, opt, n_micro=2))
    new_state, stats = step(state, batch)
    assert np.isfinite(float(stats["loss"]))
    assert float(stats["grad_norm"]) > 0
    assert int(new_state.step) == 1
    # at least one parameter must actually change
    moved = any(
        not np.allclose(np.asarray(a), np.asarray(b))
        for a, b in zip(jax.tree.leaves(params),
                        jax.tree.leaves(new_state.params_view)))
    assert moved


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_prefill_logits_shape(built, arch):
    cfg, params = built(arch)
    B, S = 2, 32
    batch = _batch(cfg, B, S)
    logits, cache, _ = forward(params, cfg, CPU_RUNTIME, batch["tokens"],
                               mode="prefill",
                               encoder_embeds=batch.get("encoder_embeds"))
    assert logits.shape == (B, 1, cfg.vocab_size)
    assert cache is not None
    assert np.all(np.isfinite(np.asarray(logits)))


def test_smoke_variant_limits():
    for name, cfg in ARCHS.items():
        s = smoke_variant(cfg)
        assert s.d_model <= 512
        assert s.n_layers <= 8
        if s.moe:
            assert s.moe.n_experts <= 4
        # the reduced variant must preserve the family
        assert s.family == cfg.family


def test_input_specs_all_combos():
    for name, cfg in ARCHS.items():
        for sname, shape in SHAPES.items():
            specs = input_specs(cfg, shape)
            assert "tokens" in specs
            if shape.kind == "decode":
                assert specs["tokens"].shape == (shape.global_batch, 1)
            else:
                assert specs["tokens"].shape == (shape.global_batch,
                                                 shape.seq_len)
            if cfg.is_encoder_decoder:
                assert specs["encoder_embeds"].shape[1] == cfg.encoder_len


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_input_projections_init_at_one_over_sqrt_d(built, arch):
    """Every default-scale matrix that contracts the residual stream
    (first axis "embed" after the stacked ``layers``/``experts`` axes)
    starts with std 1/sqrt(d_model).  A stacked axis taken as the fan-in
    gives std 1: saturated attention from step 0, and gradients whose
    rounding differences grow by orders of magnitude per step."""
    cfg, params = built(arch)
    defs = model_defs(cfg)
    leaves = jax.tree_util.tree_flatten_with_path(
        defs, is_leaf=lambda x: isinstance(x, ParamDef))[0]
    checked = 0
    for path, d in leaves:
        axes = [a for a in d.axes if a not in STACKED_AXES]
        if d.init != "normal" or d.scale >= 0 or len(axes) < 2 \
                or axes[0] != "embed":
            continue
        w = params
        for k in path:
            w = w[k.key]
        std = float(np.std(np.asarray(w, np.float32)))
        assert abs(std * np.sqrt(cfg.d_model) - 1.0) < 0.1, (path, std)
        checked += 1
    assert checked > 0


@pytest.mark.parametrize("shape,axes,fan_in", [
    ((2, 256, 4, 64), ("layers", "embed", "heads", "head_dim"), 256),
    ((2, 4, 64, 256), ("layers", "heads", "head_dim", "embed"), 256),
    ((2, 4, 256, 128), ("layers", "experts", "embed", "ffn"), 256),
    ((2, 4, 128, 256), ("layers", "experts", "ffn", "embed"), 128),
    ((256, 512), ("embed", "ffn"), 256),
])
def test_fan_in_skips_stacked_axes(shape, axes, fan_in):
    assert _fan_in(ParamDef(shape, axes)) == fan_in
