"""MoE correctness: the one-device drop-free layer and a share of its
experts vs the dense oracle, capacity dispatch under a mesh and its
drops, router modes, the balance loss (whole batch and per sequence),
and the layer's counters."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import MoEConfig, ModelConfig
from repro.models import layers, moe
from repro.models.param import materialize
from repro.models.runtime import CPU_RUNTIME


def make_cfg(router_mode="softmax_topk", cf=8.0, n_shared=0, E=4, k=2,
             **moe_kw):
    return ModelConfig(
        name="t", family="moe", n_layers=2, d_model=64, n_heads=4,
        n_kv_heads=4, d_ff=128, vocab_size=128,
        moe=MoEConfig(n_experts=E, top_k=k, d_expert=96, n_shared=n_shared,
                      capacity_factor=cf, router_mode=router_mode, **moe_kw))


def one_device_mesh():
    from repro.launch.mesh import make_mesh
    from repro.models.runtime import Runtime
    return Runtime(mesh=make_mesh((1, 1), ("data", "model")),
                   data_axes=("data",))


def setup(cfg, B=2, S=16, seed=0):
    p = materialize(moe.moe_defs(cfg), jax.random.PRNGKey(seed))
    x = jax.random.normal(jax.random.fold_in(jax.random.PRNGKey(seed), 1),
                          (B, S, cfg.d_model), jnp.float32)
    return p, x


@pytest.mark.parametrize("router_mode", ["softmax_topk", "topk_softmax"])
@pytest.mark.parametrize("n_shared", [0, 1])
def test_capacity_dispatch_matches_dense_oracle(router_mode, n_shared):
    """One device drops nothing: the sorted dispatch through the grouped
    products must equal computing every expert densely (in float32: the
    two compute each expert's rows with different kernels, so bf16
    rounding would differ in the last bit)."""
    cfg = dataclasses.replace(make_cfg(router_mode, cf=8.0,
                                       n_shared=n_shared),
                              compute_dtype="float32")
    p, x = setup(cfg)
    y, aux, _ = moe.moe_apply(p, x, cfg, CPU_RUNTIME)
    yr, auxr = moe.moe_ref(p, x, cfg)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), atol=1e-5)
    np.testing.assert_allclose(float(aux), float(auxr), rtol=1e-5)


def test_router_weights_normalized_topk_softmax():
    cfg = make_cfg("topk_softmax")
    logits = jax.random.normal(jax.random.PRNGKey(2), (32, cfg.moe.n_experts))
    w, ids, aux = moe.route(logits, cfg)
    np.testing.assert_allclose(np.asarray(w.sum(-1)), 1.0, rtol=1e-5)


def test_router_softmax_topk_weights_below_one():
    cfg = make_cfg("softmax_topk")
    logits = jax.random.normal(jax.random.PRNGKey(2), (32, cfg.moe.n_experts))
    w, ids, aux = moe.route(logits, cfg)
    assert np.all(np.asarray(w.sum(-1)) <= 1.0 + 1e-6)
    # ids are distinct per token
    for row in np.asarray(ids):
        assert len(set(row.tolist())) == len(row)


def test_aux_loss_balanced_is_one():
    """Perfectly uniform router -> switch aux loss == n_experts * (1/E) = 1."""
    cfg = make_cfg()
    logits = jnp.zeros((64, cfg.moe.n_experts))
    _, _, aux = moe.route(logits, cfg)
    np.testing.assert_allclose(float(aux), 1.0, rtol=1e-5)


def test_capacity_drops_zero_contribution():
    """cf tiny under a mesh (the capacity path; one device drops
    nothing) -> dropped tokens contribute 0 from routed experts; the
    output must stay finite and bounded by the no-drop output."""
    cfg = make_cfg(cf=0.05)
    p, x = setup(cfg)
    y, _, _ = moe.moe_apply(p, x, cfg, one_device_mesh())
    assert np.all(np.isfinite(np.asarray(y)))
    # some token-expert pairs must actually have been dropped
    y_full, _ = moe.moe_ref(p, x, cfg)
    assert not np.allclose(np.asarray(y), np.asarray(y_full))


def test_moe_grads_flow():
    cfg = make_cfg()
    p, x = setup(cfg)

    def loss(p):
        y, aux, _ = moe.moe_apply(p, x, cfg, CPU_RUNTIME)
        return jnp.sum(y ** 2) + aux

    g = jax.grad(loss)(p)
    gnorms = {k: float(jnp.linalg.norm(v)) for k, v in
              jax.tree_util.tree_flatten_with_path(g)[0] and
              [(str(path), jnp.linalg.norm(leaf)) for path, leaf in
               jax.tree_util.tree_flatten_with_path(g)[0]]}
    assert all(np.isfinite(v) for v in gnorms.values())
    assert gnorms["(DictKey(key='router'),)"] > 0  # router receives gradient


# ---------------------------------------------------------------------------
# a share of the experts on one device, drop-free
# ---------------------------------------------------------------------------

def share(cfg, p, rank, n_held):
    """The config and weights of the device that holds experts
    [rank * n_held, (rank + 1) * n_held)."""
    c = dataclasses.replace(cfg, moe=dataclasses.replace(
        cfg.moe, first_held=rank * n_held, n_held=n_held))
    sl = slice(rank * n_held, (rank + 1) * n_held)
    return c, dict(p, wg=p["wg"][sl], wu=p["wu"][sl], wd=p["wd"][sl])


@pytest.mark.parametrize("n_held", [2, 4], ids=["held_below_k",
                                                "held_above_k"])
def test_held_shares_sum_to_the_uncut_layer(n_held):
    """Each device of E / n_held computes its held experts' part; the
    parts, with the shared expert (every device computes it alike)
    counted once, add up to the uncut oracle; the balance loss is the
    whole router's on every device."""
    cfg = dataclasses.replace(make_cfg(E=8, k=3, n_shared=1),
                              compute_dtype="float32")
    p, x = setup(cfg, B=2, S=24)
    y_ref, aux_ref = moe.moe_ref(p, x, cfg)
    shared = layers.mlp(p["shared"], x, cfg)
    total, held = -shared * (8 // n_held - 1), 0
    for rank in range(8 // n_held):
        c, pr = share(cfg, p, rank, n_held)
        y, aux, stats = moe.moe_apply(pr, x, c, CPU_RUNTIME)
        np.testing.assert_allclose(float(aux), float(aux_ref), rtol=1e-6)
        yr, _ = moe.moe_ref(pr, x, c)
        np.testing.assert_allclose(np.asarray(y), np.asarray(yr), atol=1e-5)
        total, held = total + y, held + int(stats["moe_held_rows"])
    np.testing.assert_allclose(np.asarray(total), np.asarray(y_ref),
                               atol=2e-5)
    assert held == 2 * 24 * 3                   # every assignment, once


def test_no_assignment_dropped_when_every_token_routes_to_one_expert():
    """The router sends every token to held expert 1 (and its other
    choices to held experts too): all T*k assignments are computed,
    T of them by one expert, against the oracle."""
    cfg = dataclasses.replace(make_cfg(E=8, k=2), compute_dtype="float32")
    p, x = setup(cfg, B=2, S=32)
    bias = jnp.zeros((8,)).at[1].set(50.0).at[2].set(25.0)
    x = x.at[..., 0].set(1.0)                   # a feature every token has
    p = dict(p, router=p["router"].at[0].set(bias))
    c, pr = share(cfg, p, 0, 4)
    y, _, stats = moe.moe_apply(pr, x, c, CPU_RUNTIME)
    _, ids, _ = moe.route(moe.router_logits(x.reshape(-1, 64), p["router"]),
                          cfg, 32)
    assert np.all(np.asarray(ids)[:, 0] == 1)
    assert int(stats["moe_held_rows"]) == 64 * 2
    yr, _ = moe.moe_ref(pr, x, c)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), atol=1e-5)
    assert float(stats["moe_load_max_over_mean"]) == pytest.approx(
        64 / (128 / 4))


def test_rows_multiplied_are_the_held_assignments_plus_tile_padding():
    """The grouped products visit each non-empty group's rows rounded
    out to whole row tiles, as the kernel's own grid counts them: at
    most one tile of padding a group, however large the buffer."""
    from jax.experimental.pallas.ops.tpu.megablox.gmm import (
        make_group_metadata)
    from repro.kernels.grouped_matmul import active_rows
    tm = 8
    for sizes in ([3, 0, 17, 8], [8, 8, 8, 8], [0, 0, 1, 0], [30, 2, 0, 0]):
        g = jnp.asarray(sizes, jnp.int32)
        tiles, start = set(), 0
        for e, n in enumerate(sizes):           # (tile, group) pairs visited
            tiles |= {(r // tm, e) for r in range(start, start + n)}
            start += n
        rows = int(active_rows(g, tm))
        assert rows == len(tiles) * tm
        assert sum(sizes) <= rows <= sum(sizes) + tm * len(sizes)
        _, n_tiles = make_group_metadata(
            group_sizes=g, m=96, tm=tm, start_group=jnp.int32(0),
            num_nonzero_groups=4, visit_empty_groups=False)
        assert rows == int(n_tiles) * tm


def test_held_share_under_a_mesh_is_refused():
    cfg = make_cfg(E=8, k=2)
    p, x = setup(cfg)
    c, pr = share(cfg, p, 1, 4)
    with pytest.raises(ValueError, match="one device only"):
        moe.moe_apply(pr, x, c, one_device_mesh())


def test_held_rows_counter_matches_the_reference_count():
    """``moe_held_rows`` is the number of assignments to held experts:
    per layer from the oracle's routing, and in the train step's stats
    summed over layers and micro-batches."""
    from repro.configs import ARCHS, smoke_variant
    from repro.core import sngm
    from repro.core.schedules import constant
    from repro.models import model_defs
    from repro.training import make_train_step
    from repro.training.step import loss_fn
    cfg = make_cfg(E=8, k=3)
    p, x = setup(cfg, B=2, S=16)
    c, pr = share(cfg, p, 1, 4)
    _, _, stats = moe.moe_apply(pr, x, c, CPU_RUNTIME)
    _, ids, _ = moe.route(moe.router_logits(x.reshape(-1, 64), p["router"]),
                          cfg, 16)
    want = int(np.sum((np.asarray(ids) >= 4) & (np.asarray(ids) < 8)))
    assert int(stats["moe_held_rows"]) == want

    base = smoke_variant(ARCHS["deepseek-v2-lite-16b"])
    cfg = dataclasses.replace(base, moe=dataclasses.replace(
        base.moe, n_experts=8, first_held=4, n_held=4))
    params = materialize(model_defs(cfg), jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0,
                                cfg.vocab_size, jnp.int32)
    batch = {"tokens": tokens, "loss_mask": jnp.ones((4, 16), jnp.float32)}
    per_micro = [loss_fn(params, {k: v[i:i + 2] for k, v in batch.items()},
                         cfg, CPU_RUNTIME)[1] for i in (0, 2)]
    opt = sngm(constant(0.01), beta=0.9)
    _, st = jax.jit(make_train_step(cfg, CPU_RUNTIME, opt, n_micro=2))(
        opt.init_state(params), batch)
    assert int(st["moe_held_rows"]) == sum(int(m["moe_held_rows"])
                                           for m in per_micro) > 0
    assert float(st["moe_load_max_over_mean"]) == pytest.approx(np.mean(
        [float(m["moe_load_max_over_mean"]) for m in per_micro]))


def test_seq_aux_matches_a_direct_per_row_computation():
    """DeepSeek-V2's sequence-wise balance loss: per row b,
    f_i = E count_i / (S k) and P_i the row's mean probability; the loss
    is the mean over rows of sum_i f_i P_i."""
    E, k, rows, S = 8, 3, 3, 20
    cfg = make_cfg(E=E, k=k, seq_aux=True)
    logits = jax.random.normal(jax.random.PRNGKey(5), (rows * S, E)) * 2
    _, ids, aux = moe.route(logits, cfg, S)
    lg = np.asarray(logits, np.float64).reshape(rows, S, E)
    prob = np.exp(lg - lg.max(-1, keepdims=True))
    prob /= prob.sum(-1, keepdims=True)
    want = []
    for b in range(rows):
        count = np.bincount(np.asarray(ids).reshape(rows, S * k)[b],
                            minlength=E)
        want.append(np.sum(E * count / (S * k) * prob[b].mean(0)))
    np.testing.assert_allclose(float(aux), np.mean(want), rtol=1e-5)
    # one row: the whole-batch form
    whole = moe.route(logits[:S], make_cfg(E=E, k=k), S)[2]
    np.testing.assert_allclose(float(moe.route(logits[:S], cfg, S)[2]),
                               float(whole), rtol=1e-6)
