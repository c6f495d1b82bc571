"""End-to-end system behaviour tests: training actually learns; the
paper's central claim holds on a real (small) model; data pipeline and
checkpointing round-trip."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytestmark = pytest.mark.slow  # excluded from the tier-1 fast lane

from repro.configs import ARCHS, smoke_variant
from repro.core import msgd, sngm
from repro.core.optim import TrainState, to_pytree
from repro.core.schedules import poly_power
from repro.data import SyntheticLM, synthetic_images
from repro.models import CPU_RUNTIME, model_defs
from repro.models.param import materialize
from repro.training import make_train_step


def _train(opt, cfg, steps, batch=8, seq=32, seed=0):
    params = materialize(model_defs(cfg), jax.random.PRNGKey(seed))
    data = SyntheticLM(cfg.vocab_size, seq, batch, branching=4)
    state = opt.init_state(params)
    # donated, like the production launcher
    step = jax.jit(make_train_step(cfg, CPU_RUNTIME, opt, n_micro=2),
                   donate_argnums=(0,))
    losses = []
    for t in range(steps):
        state, stats = step(state, data.batch_at(t))
        losses.append(float(stats["loss"]))
    return losses


@pytest.fixture(scope="module")
def tiny_cfg():
    import dataclasses
    return dataclasses.replace(smoke_variant(ARCHS["deepseek-7b"]),
                               vocab_size=64, compute_dtype="float32")


def test_training_learns_the_chain(tiny_cfg):
    """SNGM training must make real progress toward the bigram-chain
    entropy floor (log 4 ~ 1.386 nats) from the ~log(64) start."""
    losses = _train(sngm(poly_power(2.0, 80, 1.1), beta=0.9), tiny_cfg, 80)
    assert losses[0] > 3.8
    assert losses[-1] < losses[0] - 1.0, (losses[0], losses[-1])


def test_sngm_stays_finite_at_any_lr(tiny_cfg):
    """Lemma 4 consequence on a real model: the SNGM update is bounded by
    lr/(1-beta) regardless of gradient scale, so even an absurd lr never
    produces NaN/inf — unlike unnormalized methods (covered analytically
    in test_optim_theory.py::test_sngm_converges_on_sharp_quadratic)."""
    losses = _train(sngm(poly_power(100.0, 15, 1.1), beta=0.9), tiny_cfg, 15)
    assert all(np.isfinite(l) for l in losses), losses


def test_stats_keys_consistent_across_n_micro(tiny_cfg):
    """Regression: the scan branch used to drop ce_loss/aux_loss/ntok
    (metrics = {}), so logged stats silently changed shape with n_micro.
    Metrics must survive accumulation with global-batch semantics:
    ce_loss combines token-weighted (a plain mean of per-micro means
    diverges when mask density is ragged), ntok sums to the total."""
    params = materialize(model_defs(tiny_cfg), jax.random.PRNGKey(0))
    data = SyntheticLM(tiny_cfg.vocab_size, 32, 8, branching=4)
    batch = dict(data.batch_at(0))
    # ragged mask density across the micro-batch split: rows 0-3 keep 1/4
    # of their tokens, rows 4-7 all of them
    mask = np.ones((8, 32), np.float32)
    mask[:4, 8:] = 0.0
    batch["loss_mask"] = jnp.asarray(mask)
    stats_by_n = {}
    for n_micro in (1, 4):
        opt = sngm(poly_power(0.1, 10, 1.1), beta=0.9)
        step = jax.jit(make_train_step(tiny_cfg, CPU_RUNTIME, opt,
                                       n_micro=n_micro))
        _, stats = step(opt.init_state(params), batch)
        stats_by_n[n_micro] = stats
    assert set(stats_by_n[1]) == set(stats_by_n[4])
    assert {"ce_loss", "aux_loss", "ntok"} <= set(stats_by_n[1])
    np.testing.assert_allclose(float(stats_by_n[1]["ce_loss"]),
                               float(stats_by_n[4]["ce_loss"]), rtol=1e-4)
    assert float(stats_by_n[1]["ntok"]) == float(stats_by_n[4]["ntok"])


def test_grad_accumulation_equals_full_batch(tiny_cfg):
    """n_micro=4 accumulated gradient == single full-batch gradient
    (the optimizer sees the SAME global-batch gradient, Algorithm 1)."""
    params = materialize(model_defs(tiny_cfg), jax.random.PRNGKey(0))
    data = SyntheticLM(tiny_cfg.vocab_size, 32, 8, branching=4)
    batch = data.batch_at(0)
    outs = []
    for n_micro in (1, 4):
        opt = sngm(poly_power(0.1, 10, 1.1), beta=0.9)
        step = jax.jit(make_train_step(tiny_cfg, CPU_RUNTIME, opt,
                                       n_micro=n_micro))
        ts, stats = step(opt.init_state(params), batch)
        outs.append((ts.params_view, float(stats["grad_norm"])))
    (pa, ga), (pb, gb) = outs
    assert abs(ga - gb) < 1e-3 * max(ga, 1.0)
    for a, b in zip(jax.tree.leaves(pa), jax.tree.leaves(pb)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_flat_accumulation_bitwise_and_donation_stable(tiny_cfg):
    """The flat gradient accumulator (resident FlatOptState + n_micro>1:
    each micro-gradient packs into the dtype-bucketed buffers inside the
    scan, and the optimizer gets pre-packed FlatGrads) is bitwise stable
    under state donation (the launcher's production configuration) over
    three steps, and agrees with the tree-accumulating jnp path.

    Against the jnp path the step is not bitwise.  Run from one state,
    loss and gradient norm are, and only the embedding leaf (its gradient
    is a scatter-add over the tokens) lands apart, by at most 2**-27 in
    the momentum.  Run free for three steps, the two trajectories stay
    within a few 1e-7 of each other."""
    params = materialize(model_defs(tiny_cfg), jax.random.PRNGKey(0))
    data = SyntheticLM(tiny_cfg.vocab_size, 32, 8, branching=4)

    def make(fused, donate):
        opt = sngm(poly_power(0.5, 10, 1.1), beta=0.9, fused=fused)
        step = make_train_step(tiny_cfg, CPU_RUNTIME, opt, n_micro=4)
        return opt, (jax.jit(step, donate_argnums=(0,)) if donate
                     else jax.jit(step))

    opt, flat = make("multi_tensor", donate=False)
    _, flat_d = make("multi_tensor", donate=True)
    tree_opt, tree = make(None, donate=False)
    state, state_d = opt.init_state(params), opt.init_state(params)
    free = tree_opt.init_state(params)
    for t in range(3):
        batch = data.batch_at(t)
        ref, s_tree = tree(TrainState.wrap(
            jax.tree.map(jnp.array, state.params_view),
            to_pytree(state.opt_state)), batch)
        free, s_free = tree(free, batch)
        state, s_flat = flat(state, batch)
        state_d, s_flat_d = flat_d(state_d, batch)
        for a, b in zip(jax.tree.leaves(state.params_view),
                        jax.tree.leaves(state_d.params_view)):
            assert bool(jnp.array_equal(a, b))
        assert float(s_flat["grad_norm"]) == float(s_flat_d["grad_norm"])

        assert float(s_tree["loss"]) == float(s_flat["loss"])
        assert float(s_tree["grad_norm"]) == float(s_flat["grad_norm"])
        for a, b in zip(jax.tree.leaves(ref.params_view),
                        jax.tree.leaves(state.params_view)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-6, atol=1e-8)
        for a, b in zip(jax.tree.leaves(ref.opt_state.momentum),
                        jax.tree.leaves(to_pytree(state.opt_state).momentum)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-6, atol=1e-8)
        np.testing.assert_allclose(float(s_free["loss"]),
                                   float(s_flat["loss"]), rtol=1e-6)
        np.testing.assert_allclose(float(s_free["grad_norm"]),
                                   float(s_flat["grad_norm"]), rtol=1e-6)
    for a, b in zip(jax.tree.leaves(free.params_view),
                    jax.tree.leaves(state.params_view)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                                   atol=1e-7)


# ---------------------------------------------------------------------------
# data pipeline
# ---------------------------------------------------------------------------

def test_synthetic_lm_deterministic():
    d1 = SyntheticLM(128, 16, 4, seed=7)
    d2 = SyntheticLM(128, 16, 4, seed=7)
    np.testing.assert_array_equal(np.asarray(d1.batch_at(3)["tokens"]),
                                  np.asarray(d2.batch_at(3)["tokens"]))
    assert not np.array_equal(np.asarray(d1.batch_at(3)["tokens"]),
                              np.asarray(d1.batch_at(4)["tokens"]))


def test_synthetic_lm_is_learnable_chain():
    d = SyntheticLM(64, 16, 4, branching=4, seed=0)
    toks = np.asarray(d.batch_at(0)["tokens"])
    table = np.asarray(d.table)
    for b in range(toks.shape[0]):
        for t in range(toks.shape[1] - 1):
            assert toks[b, t + 1] in table[toks[b, t]]


def test_synthetic_images_class_structure():
    x, y = synthetic_images(256, seed=0)
    assert x.shape == (256, 32, 32, 3)
    yn = np.asarray(y)
    x0 = np.asarray(x[yn == 0])
    x1 = np.asarray(x[yn == 1])
    if len(x0) > 1 and len(x1) > 0:
        d_in = np.linalg.norm(x0[0] - x0[1])
        d_out = np.linalg.norm(x0[0] - x1[0])
        assert d_in < d_out * 1.5


# ---------------------------------------------------------------------------
# checkpointing
# ---------------------------------------------------------------------------

def test_checkpoint_roundtrip(tmp_path, tiny_cfg):
    from repro.checkpoint import load_checkpoint, save_checkpoint
    params = materialize(model_defs(tiny_cfg), jax.random.PRNGKey(0))
    save_checkpoint(str(tmp_path / "ck"), {"params": params}, step=17)
    restored, step = load_checkpoint(str(tmp_path / "ck"), {"params": params})
    assert step == 17
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(restored["params"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_train_launcher_save_resume_loss_continuity(tmp_path):
    """End-to-end --resume: a 12-step run must equal 6 steps + save +
    resume for 6 more — including across STATE FORMS (a FlatOptState
    checkpoint resumed on the jnp path), since poly_power picks up at the
    restored t and the engine paths are bit-identical."""
    from repro.launch.train import main as train_main

    def run(extra):
        return train_main(
            ["--arch", "gemma-2b", "--reduced", "--batch", "4", "--seq", "16",
             "--n-micro", "2", "--optimizer", "sngm", "--fused",
             "multi_tensor", "--lr", "0.5", "--total-steps", "12",
             "--log-every", "100"] + extra)

    full = run(["--steps", "12"])
    part1 = run(["--steps", "6", "--ckpt", str(tmp_path / "ck1")])
    part1b = run(["--steps", "6", "--ckpt", str(tmp_path / "ck2")])
    np.testing.assert_allclose(part1, full[:6], rtol=1e-6)
    np.testing.assert_allclose(part1b, part1, rtol=0)   # deterministic

    resumed = run(["--steps", "12", "--ckpt", str(tmp_path / "ck1"),
                   "--resume"])
    assert len(resumed) == 6
    np.testing.assert_allclose(resumed, full[6:], rtol=1e-5, atol=1e-6)

    # cross-form resume: FlatOptState checkpoint -> jnp (OptState) run
    resumed_jnp = run(["--steps", "12", "--ckpt", str(tmp_path / "ck2"),
                       "--resume", "--fused", "none"])
    np.testing.assert_allclose(resumed_jnp, full[6:], rtol=1e-4, atol=1e-5)


def test_resume_holds_one_copy_of_the_state(tmp_path, monkeypatch):
    """--resume starts its first step holding no more device memory than
    a fresh run: the init state is released before the checkpoint loads,
    and no pytree copy outlives the resident buffers.  A resume that held
    both did not fit one chip at yi-9b widths."""
    from repro.launch import train
    live, loop = [], train.run_steps

    def run_steps(*a, **k):
        live.append(sum(x.nbytes for x in jax.live_arrays()))
        return loop(*a, **k)

    monkeypatch.setattr(train, "run_steps", run_steps)
    args = ["--arch", "gemma-2b", "--reduced", "--batch", "4", "--seq", "16",
            "--n-micro", "2", "--optimizer", "sngm", "--fused",
            "multi_tensor", "--total-steps", "4", "--log-every", "100",
            "--ckpt", str(tmp_path / "ck")]
    train.main(args + ["--steps", "1"])
    train.main(args + ["--steps", "2", "--resume"])
    assert live[1] <= live[0], live


def test_lamb_fused_save_resume_loss_continuity(tmp_path):
    """--resume continuity for FUSED lamb: the Adam-moment flat slots
    survive the checkpoint (saved in ChainOptState pytree form, rebuilt
    resident on restore), so 6 + save/resume + 6 equals an uninterrupted
    12-step run — including resuming onto the interpreter path
    (--fused none), since fused lamb is bit-identical to it."""
    from repro.launch.train import main as train_main

    def run(extra):
        return train_main(
            ["--arch", "gemma-2b", "--reduced", "--batch", "4", "--seq", "16",
             "--n-micro", "2", "--optimizer", "lamb", "--fused",
             "multi_tensor", "--lr", "0.05", "--weight-decay", "1e-4",
             "--total-steps", "12", "--log-every", "100"] + extra)

    full = run(["--steps", "12"])
    part1 = run(["--steps", "6", "--ckpt", str(tmp_path / "ck1")])
    part1b = run(["--steps", "6", "--ckpt", str(tmp_path / "ck2")])
    np.testing.assert_allclose(part1, full[:6], rtol=1e-6)
    np.testing.assert_allclose(part1b, part1, rtol=0)   # deterministic

    resumed = run(["--steps", "12", "--ckpt", str(tmp_path / "ck1"),
                   "--resume"])
    assert len(resumed) == 6
    np.testing.assert_allclose(resumed, full[6:], rtol=1e-5, atol=1e-6)

    # cross-form resume: ChainOptState checkpoint -> interpreter run
    resumed_interp = run(["--steps", "12", "--ckpt", str(tmp_path / "ck2"),
                          "--resume", "--fused", "none"])
    np.testing.assert_allclose(resumed_interp, full[6:], rtol=1e-4,
                               atol=1e-5)


def test_segment_plan_save_resume_loss_continuity(tmp_path):
    """--resume continuity for a SEGMENT-COMPILED chain (nesterov sngm
    with a resident EMA slot — no whole-chain match, the plan executor
    runs it): the ("chain", slots) FlatOptState is saved in ChainOptState
    pytree form and rebuilt resident on restore, so 6 + save/resume + 6
    equals an uninterrupted 12-step run — and the same checkpoint also
    resumes onto the jnp interpreter (--fused none), the fused->interp
    cross-form continuity the compiler's tolerance policy promises."""
    from repro.launch.train import main as train_main

    def run(extra):
        return train_main(
            ["--arch", "gemma-2b", "--reduced", "--batch", "4", "--seq", "16",
             "--n-micro", "2", "--optimizer", "sngm", "--fused",
             "multi_tensor", "--lr", "0.5", "--nesterov", "--ema-decay",
             "0.999", "--total-steps", "12", "--log-every", "100"] + extra)

    full = run(["--steps", "12"])
    part1 = run(["--steps", "6", "--ckpt", str(tmp_path / "ck1")])
    part1b = run(["--steps", "6", "--ckpt", str(tmp_path / "ck2")])
    np.testing.assert_allclose(part1, full[:6], rtol=1e-6)
    np.testing.assert_allclose(part1b, part1, rtol=0)   # deterministic

    resumed = run(["--steps", "12", "--ckpt", str(tmp_path / "ck1"),
                   "--resume"])
    assert len(resumed) == 6
    np.testing.assert_allclose(resumed, full[6:], rtol=1e-5, atol=1e-6)

    # cross-form resume: segment-plan checkpoint -> interpreter run
    resumed_interp = run(["--steps", "12", "--ckpt", str(tmp_path / "ck2"),
                          "--resume", "--fused", "none"])
    np.testing.assert_allclose(resumed_interp, full[6:], rtol=1e-4,
                               atol=1e-5)


def test_optimizer_spec_round_trips_through_resume(tmp_path):
    """The OptimizerSpec saved in train_meta.json is the optimizer's
    identity: --resume reconstructs from it (conflicting CLI hyperparams
    are ignored), and the resumed steps are bit-identical to the
    uninterrupted run."""
    import json
    from repro.launch.train import main as train_main

    base = ["--arch", "gemma-2b", "--reduced", "--batch", "4", "--seq", "16",
            "--n-micro", "2", "--total-steps", "12", "--log-every", "100"]

    full = train_main(base + ["--steps", "12", "--optimizer", "sngm",
                              "--lr", "0.5", "--weight-decay", "1e-3"])
    train_main(base + ["--steps", "6", "--optimizer", "sngm", "--lr", "0.5",
                       "--weight-decay", "1e-3",
                       "--ckpt", str(tmp_path / "ck")])

    meta = json.load(open(tmp_path / "ck" / "train_meta.json"))
    spec = meta["optimizer_spec"]
    assert spec["name"] == "sngm"
    assert spec["kwargs"]["weight_decay"] == pytest.approx(1e-3)
    assert spec["kwargs"]["schedule"] == {
        "name": "poly_power",
        "kwargs": {"lr0": 0.5, "total_steps": 12, "power": 1.1}}

    # resume with WRONG CLI hyperparams: the saved spec must win
    resumed = train_main(base + ["--steps", "12", "--lr", "999.0",
                                 "--weight-decay", "0.7",
                                 "--ckpt", str(tmp_path / "ck"), "--resume"])
    assert len(resumed) == 6
    np.testing.assert_array_equal(np.asarray(resumed),
                                  np.asarray(full[6:]))


def test_train_state_save_resume_continuity(tmp_path, tiny_cfg):
    """Save→resume THROUGH the donated TrainState, resident path: the
    launcher persists {params_view, to_pytree(opt_state)} from the live
    state; rebuilding a TrainState from the restored forms and continuing
    (donated) matches an uninterrupted donated run bitwise."""
    from repro.checkpoint import load_checkpoint, save_checkpoint
    from repro.core import to_pytree, from_pytree
    from repro.core.optim import TrainState
    from repro.core.schedules import poly_power as pp

    def mk_opt():
        return sngm(pp(0.5, 8, 1.1), beta=0.9, weight_decay=1e-4,
                    fused="multi_tensor")

    def fresh():
        return materialize(model_defs(tiny_cfg), jax.random.PRNGKey(0))

    data = SyntheticLM(tiny_cfg.vocab_size, 32, 8, branching=4)
    opt = mk_opt()
    step = jax.jit(make_train_step(tiny_cfg, CPU_RUNTIME, opt, n_micro=2),
                   donate_argnums=(0,))

    # uninterrupted 8-step donated run
    ts_full = opt.init_state(fresh())
    for t in range(8):
        ts_full, _ = step(ts_full, data.batch_at(t))

    # 4 steps, checkpoint from the LIVE TrainState, rebuild, 4 more
    ts = opt.init_state(fresh())
    for t in range(4):
        ts, _ = step(ts, data.batch_at(t))
    assert ts.params is None          # resident: flats own the params
    save_checkpoint(str(tmp_path / "ck"),
                    {"params": ts.params_view,
                     "opt": to_pytree(ts.opt_state)}, step=4)

    like = {"params": fresh(), "opt": to_pytree(mk_opt().init(fresh()))}
    restored, t0 = load_checkpoint(str(tmp_path / "ck"), like)
    assert t0 == 4
    ts2 = TrainState(params=None,
                     opt_state=from_pytree(restored["opt"],
                                           restored["params"]))
    for t in range(4, 8):
        ts2, _ = step(ts2, data.batch_at(t))

    for a, b in zip(jax.tree.leaves(ts_full), jax.tree.leaves(ts2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_resume_rejects_torn_checkpoint(tmp_path):
    """A torn checkpoint directory (no COMMIT marker AND no complete
    legacy meta/shard pair — what an interrupted legacy-writer save
    leaves) must be refused by --resume rather than half-loaded; a
    markerless-but-complete legacy checkpoint still resumes."""
    import os
    from repro.launch.train import main as train_main

    args = ["--arch", "gemma-2b", "--reduced", "--batch", "4", "--seq", "16",
            "--n-micro", "2", "--optimizer", "sngm", "--lr", "0.5",
            "--total-steps", "8", "--log-every", "100"]
    train_main(args + ["--steps", "4", "--ckpt", str(tmp_path / "ck")])
    # markerless but complete == pre-marker legacy save: must resume
    os.remove(tmp_path / "ck" / "COMMIT")
    legacy = train_main(args + ["--steps", "8", "--ckpt",
                                str(tmp_path / "ck"), "--resume"])
    assert len(legacy) == 4
    # torn: no marker AND the meta sidecar never landed
    os.remove(tmp_path / "ck" / "COMMIT")
    os.remove(tmp_path / "ck" / "meta.json")
    with pytest.raises(SystemExit, match="COMMIT"):
        train_main(args + ["--steps", "8", "--ckpt", str(tmp_path / "ck"),
                           "--resume"])
