"""Multi-device integration (subprocess: 8 host devices).

Checks that the distributed execution paths — pjit with the production
sharding rules, expert-parallel all_to_all MoE, gradient accumulation —
produce the SAME numbers as single-device execution.
"""
import os
import subprocess
import sys
import textwrap

import pytest

pytestmark = pytest.mark.slow  # excluded from the tier-1 fast lane

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import dataclasses
    import jax, jax.numpy as jnp, numpy as np
    from repro.launch.mesh import make_mesh
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.configs import ARCHS, smoke_variant
    from repro.core import sngm
    from repro.core.schedules import constant
    from repro.models import model_defs, forward
    from repro.models.param import materialize
    from repro.models.runtime import Runtime, CPU_RUNTIME
    from repro.sharding import param_shardings, batch_spec
    from repro.training import make_train_step
    from repro.core.optim import OptState, TrainState

    # f32 so single- vs multi-device results are comparable tightly;
    # capacity_factor=16 so no token drops: EP computes capacity per shard,
    # so at low cf drop PATTERNS legitimately differ from single-device
    cfg = dataclasses.replace(smoke_variant(ARCHS["deepseek-v2-lite-16b"]),
                              compute_dtype="float32")
    cfg = dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=16.0))
    defs = model_defs(cfg)
    params = materialize(defs, jax.random.PRNGKey(0))
    B, S = 8, 32
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0,
                                cfg.vocab_size, jnp.int32)
    batch = {"tokens": tokens, "loss_mask": jnp.ones((B, S), jnp.float32)}

    opt = sngm(constant(0.01), beta=0.9, weight_decay=1e-4)

    # --- single device reference ---
    step_ref = jax.jit(make_train_step(cfg, CPU_RUNTIME, opt, n_micro=2))
    ts_ref, stats_ref = step_ref(opt.init_state(params), batch)

    # --- 4x2 mesh (data=4 with EP, model=2 TP) ---
    mesh = make_mesh((4, 2), ("data", "model"))
    rt = Runtime(mesh=mesh, data_axes=("data",), remat=True)
    psh = param_shardings(defs, mesh)
    params_sharded = jax.device_put(params, psh)
    ts_sh = TrainState(params=psh,
                       opt_state=OptState(step=NamedSharding(mesh, P()),
                                          momentum=psh))
    step_dist = jax.jit(make_train_step(cfg, rt, opt, n_micro=2),
                        in_shardings=(ts_sh,
                                      {k: NamedSharding(mesh, batch_spec(mesh, v.ndim))
                                       for k, v in batch.items()}),
                        out_shardings=(ts_sh, None))
    ts_dist, stats_dist = step_dist(opt.init_state(params_sharded), batch)

    # the cross-entropy: the router's load-balance aux loss is a
    # statistic of each expert-parallel shard's tokens (moe.py averages
    # the shards' values), so the total loss differs from one device's
    # by design
    l1, l2 = float(stats_ref["ce_loss"]), float(stats_dist["ce_loss"])
    g1, g2 = float(stats_ref["grad_norm"]), float(stats_dist["grad_norm"])
    print("LOSS", l1, l2, "GNORM", g1, g2)
    assert abs(l1 - l2) < 1e-4 * max(1, abs(l1)), (l1, l2)
    assert abs(g1 - g2) < 1e-3 * max(1, abs(g1)), (g1, g2)
    # parameters agree after one update
    for a, b in zip(jax.tree.leaves(ts_ref.params_view),
                    jax.tree.leaves(ts_dist.params_view)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(jax.device_get(b)),
                                   atol=5e-5)
    print("MULTIDEVICE-OK")
""")

MOE_EP_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import dataclasses
    import jax, jax.numpy as jnp, numpy as np
    from repro.launch.mesh import make_mesh
    from repro.configs import MoEConfig, ModelConfig
    from repro.models import moe
    from repro.models.param import materialize
    from repro.models.runtime import Runtime, CPU_RUNTIME

    cfg = ModelConfig(name="t", family="moe", n_layers=2, d_model=64,
                      n_heads=4, n_kv_heads=4, d_ff=128, vocab_size=128,
                      compute_dtype="float32",
                      moe=MoEConfig(n_experts=8, top_k=2, d_expert=64,
                                    capacity_factor=8.0))
    p = materialize(moe.moe_defs(cfg), jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 16, 64), jnp.float32)

    y_ref, aux_ref = moe.moe_ref(p, x, cfg)

    mesh = make_mesh((4, 2), ("data", "model"))
    rt = Runtime(mesh=mesh, data_axes=("data",))
    def counters(x):
        # every expert is held on the mesh: the routing's own counts
        ids = jax.lax.top_k(x.reshape(-1, 64) @ p["router"], 2)[1]
        return moe.load_stats(moe.held_counts(ids, cfg))

    y_ep, aux_ep, st = jax.jit(lambda p, x: moe.moe_apply(p, x, cfg, rt))(p, x)
    print("AUX", float(aux_ref), float(aux_ep))
    np.testing.assert_allclose(np.asarray(y_ep), np.asarray(y_ref), atol=1e-4)
    want = counters(x)
    assert int(st["moe_held_rows"]) == int(want["moe_held_rows"]) == 8 * 16 * 2
    np.testing.assert_allclose(float(st["moe_load_max_over_mean"]),
                               float(want["moe_load_max_over_mean"]))

    # allreduce mode: batch=2 tokens, not divisible by data=4
    x2 = x[:2, :1]
    y_ref2, _ = moe.moe_ref(p, x2, cfg)
    y_ep2, _, st2 = jax.jit(lambda p, x: moe.moe_apply(p, x, cfg, rt))(p, x2)
    np.testing.assert_allclose(np.asarray(y_ep2), np.asarray(y_ref2), atol=1e-4)
    want2 = counters(x2)
    assert int(st2["moe_held_rows"]) == int(want2["moe_held_rows"]) == 2 * 2
    np.testing.assert_allclose(float(st2["moe_load_max_over_mean"]),
                               float(want2["moe_load_max_over_mean"]))
    print("MOE-EP-OK")
""")


FUSED_OPT_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from repro.launch.mesh import make_mesh
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core import sngm
    from repro.core.schedules import constant

    # a transformer-ish tree: 2D matrices shard over the mesh, 1D stay
    # replicated — the multi-tensor engine must give the same numbers as
    # the jnp path when the flat buffers are built from sharded leaves
    k = jax.random.PRNGKey(0)
    shapes = {"wq": (256, 128), "wk": (256, 128), "scale": (256,),
              "emb": (1000, 64), "bias": (7,)}
    params = {n: jax.random.normal(jax.random.fold_in(k, i), s)
              for i, (n, s) in enumerate(sorted(shapes.items()))}
    grads = {n: 3.0 * jax.random.normal(jax.random.fold_in(k, 100 + i), s)
             for i, (n, s) in enumerate(sorted(shapes.items()))}

    mesh = make_mesh((8,), ("data",))
    shard = {n: NamedSharding(mesh, P("data") if len(s) == 2 else P())
             for n, s in shapes.items()}
    params_s = jax.device_put(params, shard)
    grads_s = jax.device_put(grads, shard)

    outs = {}
    for fused in (None, "multi_tensor"):
        opt = sngm(constant(0.3), beta=0.9, weight_decay=1e-4, fused=fused)
        state = opt.init(params_s)
        step = jax.jit(opt.step)
        p, s = params_s, state
        for _ in range(2):
            p, s, stats = step(grads_s, s, p)
        outs[fused] = (p, s, stats)
    (p_r, s_r, st_r), (p_m, s_m, st_m) = outs[None], outs["multi_tensor"]
    for a, b in zip(jax.tree.leaves(p_r), jax.tree.leaves(p_m)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-7)
    for a, b in zip(jax.tree.leaves(s_r.momentum),
                    jax.tree.leaves(s_m.momentum)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-7)
    np.testing.assert_allclose(float(st_r["grad_norm"]),
                               float(st_m["grad_norm"]), rtol=1e-6)
    print("FUSED-SHARDED-OK")
""")


RESIDENT_BF16_SHARDED_SCRIPT = textwrap.dedent("""
    import os, tempfile
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from repro.launch.mesh import make_mesh
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core import sngm
    from repro.core.multi_tensor import FlatOptState
    from repro.core.optim import to_pytree
    from repro.core.schedules import constant
    from repro.checkpoint import load_checkpoint, save_checkpoint

    def bit_eq(a, b):
        return all(bool(jnp.array_equal(x, y)) and x.dtype == y.dtype
                   for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))

    # bf16 params sharded over the mesh (2D leaves), replicated 1D leaves
    k = jax.random.PRNGKey(0)
    shapes = {"wq": (256, 128), "wk": (256, 128), "scale": (256,),
              "emb": (1000, 64), "bias": (7,)}
    params = {n: jax.random.normal(jax.random.fold_in(k, i), s)
                 .astype(jnp.bfloat16)
              for i, (n, s) in enumerate(sorted(shapes.items()))}
    grads = {n: (3.0 * jax.random.normal(jax.random.fold_in(k, 100 + i), s))
                .astype(jnp.bfloat16)
             for i, (n, s) in enumerate(sorted(shapes.items()))}
    mesh = make_mesh((8,), ("data",))
    shard = {n: NamedSharding(mesh, P("data") if len(s) == 2 else P())
             for n, s in shapes.items()}
    params_s = jax.device_put(params, shard)
    grads_s = jax.device_put(grads, shard)

    opt = sngm(constant(0.3), beta=0.9, weight_decay=1e-4,
               fused="multi_tensor")
    opt_jnp = sngm(constant(0.3), beta=0.9, weight_decay=1e-4)

    s_res = opt.init(params_s)
    assert isinstance(s_res, FlatOptState)
    s_per = to_pytree(s_res)
    s_ref = opt_jnp.init(params_s)
    step, step_ref = jax.jit(opt.step), jax.jit(opt_jnp.step)
    p_res = p_per = p_ref = params_s
    for _ in range(2):
        p_res, s_res, st_res = step(grads_s, s_res, p_res)
        p_per, s_per, st_per = step(grads_s, s_per, p_per)
        p_ref, s_ref, st_ref = step_ref(grads_s, s_ref, p_ref)

    # resident == per-step fused == jnp, bitwise, on sharded bf16 params
    assert bit_eq(p_res, p_per)
    assert bit_eq(s_res.momentum, s_per.momentum)
    assert bit_eq(p_res, p_ref), "resident vs jnp params differ"
    assert bit_eq(s_res.momentum, s_ref.momentum)
    assert bool(jnp.array_equal(st_res["grad_norm"], st_ref["grad_norm"]))
    print("RESIDENT-SHARDED-BF16-OK")

    # sharded bf16 checkpoint round-trip, both state forms
    for tag, state in (("flat", s_res), ("tree", s_per)):
        d = tempfile.mkdtemp()
        save_checkpoint(d, {"params": p_res, "opt": state}, step=2)
        like = {"params": params_s, "opt": opt.init(params_s) if tag == "flat"
                else to_pytree(opt.init(params_s))}
        restored, t = load_checkpoint(d, like, shardings=None)
        assert t == 2
        assert bit_eq(restored["params"], p_res)
        assert bit_eq(restored["opt"], state)
    print("SHARDED-CKPT-OK")
""")


TWO_LEVEL_NORM_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from repro.launch.mesh import make_mesh

    from repro.core.multi_tensor import (_chunk_sumsq, _engine_mesh,
                                         _leaf_values, build_layout, flatten,
                                         flat_squared_norm, mesh_shards,
                                         place_flat_state, tree_squared_norm,
                                         init_flat_state)

    mesh = make_mesh((2, 2), ("data", "model"))
    S = mesh_shards(mesh)
    k = jax.random.PRNGKey(0)

    # both dtype buckets: 2D f32 leaves + bf16 leaves + a ragged 1D leaf
    tree = {
        "a": jax.random.normal(jax.random.fold_in(k, 0), (300, 170)),
        "b": jax.random.normal(jax.random.fold_in(k, 1), (999,)),
        "c": (7.0 * jax.random.normal(jax.random.fold_in(k, 2), (128, 256))
              ).astype(jnp.bfloat16),
        "d": jax.random.normal(jax.random.fold_in(k, 3), (64, 64)
              ).astype(jnp.bfloat16),
    }
    layout = build_layout(tree, shards=S)
    assert layout.shards == S and _engine_mesh(layout, mesh) is mesh
    flats = flatten(tree, layout)
    st = place_flat_state(init_flat_state(tree, mesh=mesh), mesh)
    flats_sh = st.p_flats  # placed flat buffers (values untouched)

    # (a) two-level norm, level 1: per-shard Pallas partials + tiled
    # gather must reproduce the unsharded partial vector BITWISE, per
    # bucket — fp32 and bf16 buckets alike
    parts_un, parts_sh = [], []
    for i, (f_un, f_sh) in enumerate(zip(flats, flats_sh)):
        pu = _chunk_sumsq(f_un, backend="pallas", mesh=None)
        ps = jax.jit(
            lambda f: _chunk_sumsq(f, backend="pallas", mesh=mesh))(f_sh)
        assert bool(jnp.array_equal(pu, ps)), f"bucket {i} partials"
        parts_un.append(pu)
        parts_sh.append(ps)
    print("TWO-LEVEL-PARTIALS-OK")

    # level 2: the canonical per-segment fold of the gathered partials ==
    # the fold of the unsharded partials == the tree reduction, bitwise
    n_tree = tree_squared_norm(tree)
    for parts in (parts_un, parts_sh):
        n = sum(_leaf_values(parts, layout))
        assert bool(jnp.array_equal(n, n_tree)), (n, n_tree)

    # and the zero-launch jnp flat norm agrees on unsharded AND sharded
    # (placed) buffers — the global-norm numerics contract end to end
    n_flat = flat_squared_norm(flats, layout)
    assert bool(jnp.array_equal(n_flat, n_tree)), (n_flat, n_tree)
    n_flat_sh = jax.jit(lambda fs: flat_squared_norm(fs, layout))(flats_sh)
    assert bool(jnp.array_equal(n_flat_sh, n_tree)), (n_flat_sh, n_tree)
    print("TWO-LEVEL-NORM-OK")
""")


SHARDED_RESIDENT_PARITY_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from repro.launch.mesh import make_mesh

    from repro.core import lamb, msgd, sngm
    from repro.core.multi_tensor import FlatOptState, mesh_shards, unflatten
    from repro.core.schedules import constant
    from repro.tracker.counters import (capture_donation_warnings,
                                        launches_per_step)

    def state_trees(st):
        # unflatten against the state's OWN layout: shard padding differs
        # between shards=1 and shards=4 buffers, but the segment contents
        # (params + every slot) must be bitwise identical
        lo = st.layout
        slots = [st.p_flats, st.u_flats, st.m_flats, st.v_flats]
        return [unflatten(f, lo, keep_dtype=True) for f in slots if f]

    def assert_bitwise(st_a, st_b, tag):
        for ta, tb in zip(state_trees(st_a), state_trees(st_b)):
            for a, b in zip(jax.tree.leaves(ta), jax.tree.leaves(tb)):
                assert bool(jnp.array_equal(a, b)), tag

    mesh = make_mesh((2, 2), ("data", "model"))
    k = jax.random.PRNGKey(0)
    shapes = {"wq": (256, 128), "wk": (256, 128), "scale": (256,),
              "emb": (1000, 64), "bias": (7,)}
    params = {n: jax.random.normal(jax.random.fold_in(k, i), s)
              for i, (n, s) in enumerate(sorted(shapes.items()))}
    grads3 = [{n: 3.0 * jax.random.normal(jax.random.fold_in(k, 100 + 10*t + i), s)
               for i, (n, s) in enumerate(sorted(shapes.items()))}
              for t in range(3)]

    BUILDERS = {
        "sngm": lambda **kw: sngm(constant(0.3), beta=0.9,
                                  weight_decay=1e-4,
                                  fused="multi_tensor", **kw),
        "msgd": lambda **kw: msgd(constant(0.1), beta=0.9,
                                  fused="multi_tensor", **kw),
        "lamb": lambda **kw: lamb(constant(0.05), weight_decay=1e-4,
                                  fused="multi_tensor", **kw),
    }
    EXPECT_LAUNCHES = {"sngm": 2, "msgd": 2, "lamb": 2}

    for name, mk in BUILDERS.items():
        # single-device reference: UNDONATED steps — the canonical
        # numerics.  (Donation on the unsharded path can shift msgd by
        # one ulp via XLA fusion re-association; the sharded shard_map
        # path below is donation-stable and must match the canonical.)
        opt_1 = mk()
        st_1 = opt_1.init(params)
        step_1 = jax.jit(opt_1.step)
        for g in grads3:
            _, st_1, stats_1 = step_1(g, st_1, None)

        # sharded resident: same optimizer built WITH the mesh
        opt_s = mk(mesh=mesh)
        st_s = opt_s.init(params)
        assert isinstance(st_s, FlatOptState)
        assert st_s.layout.shards == mesh_shards(mesh) == 4
        # every flat slot actually sharded over all mesh axes
        for f in st_s.p_flats:
            spec = f.sharding.spec
            assert tuple(spec) == (("data", "model"),), spec
        step_s = jax.jit(opt_s.step, donate_argnums=(1,))
        # zero donation warnings under sharding
        (_, st_s, stats_s), msgs = capture_donation_warnings(
            step_s, grads3[0], st_s, None)
        assert not msgs, msgs
        for g in grads3[1:]:
            _, st_s, stats_s = step_s(g, st_s, None)

        # bitwise fp32 parity: params AND every slot AND stats
        assert_bitwise(st_1, st_s, name)
        for key in ("grad_norm", "update_norm"):
            if key in stats_1:
                assert bool(jnp.array_equal(stats_1[key], stats_s[key])), \
                    (name, key)

        # launch counts unchanged under sharding
        n1 = launches_per_step(opt_1, grads3[0], opt_1.init(params), None)
        ns = launches_per_step(opt_s, grads3[0], opt_s.init(params), None)
        assert n1 == ns == EXPECT_LAUNCHES[name], (name, n1, ns)
        print(name, "OK launches", ns)

    # clip_sngm: the 3-launch clip-prefixed chain, sharded vs single
    from repro.core import transform as T
    def mk_clip(mesh=None):
        tx = T.chain(T.clip_by_global_norm(1.0),
                     T.add_decayed_weights(1e-4),
                     T.normalize_by_global_norm(),
                     T.trace(0.9),
                     T.scale_by_schedule(constant(0.3)))
        return T.compile_chain(tx, fused="multi_tensor", mesh=mesh)
    opt_1, opt_s = mk_clip(), mk_clip(mesh)
    st_1, st_s = opt_1.init(params), opt_s.init(params)
    s1 = jax.jit(opt_1.step)                       # canonical reference
    ss = jax.jit(opt_s.step, donate_argnums=(1,))
    for g in grads3:
        _, st_1, stats_1 = s1(g, st_1, None)
        _, st_s, stats_s = ss(g, st_s, None)
    assert_bitwise(st_1, st_s, "clip_sngm")
    n1 = launches_per_step(opt_1, grads3[0], opt_1.init(params), None)
    ns = launches_per_step(opt_s, grads3[0], opt_s.init(params), None)
    assert n1 == ns == 3, (n1, ns)
    print("clip_sngm OK launches", ns)
    print("SHARDED-RESIDENT-PARITY-OK")
""")


LAUNCHER_MESH_RESUME_SCRIPT = textwrap.dedent("""
    import os, tempfile
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import numpy as np
    from repro.launch.train import main as train_main

    tmp = tempfile.mkdtemp()

    def run(extra):
        # the CI multi-process smoke lane: the launcher end to end on a
        # 2x2 data x model mesh (host devices), multi-process flags routed
        # through init_distributed (single-process no-op here)
        return train_main(
            ["--arch", "gemma-2b", "--reduced", "--batch", "4",
             "--seq", "16", "--n-micro", "2", "--optimizer", "sngm",
             "--fused", "multi_tensor", "--lr", "0.5",
             "--data-axis", "2", "--model-axis", "2",
             "--num-processes", "0", "--process-id", "-1",
             "--total-steps", "8", "--log-every", "100"] + extra)

    full = run(["--steps", "8"])
    part = run(["--steps", "4", "--ckpt", os.path.join(tmp, "ck")])
    np.testing.assert_allclose(part, full[:4], rtol=1e-6)
    print("LAUNCHER-MESH-OK")

    # --resume re-packs the resident FlatOptState at the mesh's shard
    # count and continues bitwise-continuously with the full run
    resumed = run(["--steps", "8", "--ckpt", os.path.join(tmp, "ck"),
                   "--resume"])
    assert len(resumed) == 4, len(resumed)
    np.testing.assert_allclose(resumed, full[4:], rtol=1e-5, atol=1e-6)
    print("LAUNCHER-MESH-RESUME-OK")
""")


def _run(script):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env.pop("JAX_PLATFORMS", None)
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=560)


def test_distributed_train_step_matches_single_device():
    r = _run(SCRIPT)
    assert "MULTIDEVICE-OK" in r.stdout, r.stdout[-2000:] + r.stderr[-3000:]


def test_moe_expert_parallel_matches_oracle():
    r = _run(MOE_EP_SCRIPT)
    assert "MOE-EP-OK" in r.stdout, r.stdout[-2000:] + r.stderr[-3000:]


def test_multi_tensor_engine_matches_jnp_on_sharded_params():
    r = _run(FUSED_OPT_SCRIPT)
    assert "FUSED-SHARDED-OK" in r.stdout, r.stdout[-2000:] + r.stderr[-3000:]


def test_resident_state_bitwise_and_checkpoint_on_sharded_bf16():
    r = _run(RESIDENT_BF16_SHARDED_SCRIPT)
    assert "RESIDENT-SHARDED-BF16-OK" in r.stdout, \
        r.stdout[-2000:] + r.stderr[-3000:]
    assert "SHARDED-CKPT-OK" in r.stdout, \
        r.stdout[-2000:] + r.stderr[-3000:]


def test_two_level_norm_sharded_matches_canonical_fold_bitwise():
    r = _run(TWO_LEVEL_NORM_SCRIPT)
    assert "TWO-LEVEL-PARTIALS-OK" in r.stdout, \
        r.stdout[-2000:] + r.stderr[-3000:]
    assert "TWO-LEVEL-NORM-OK" in r.stdout, \
        r.stdout[-2000:] + r.stderr[-3000:]


def test_sharded_resident_steps_bitwise_with_launch_counts():
    r = _run(SHARDED_RESIDENT_PARITY_SCRIPT)
    assert "SHARDED-RESIDENT-PARITY-OK" in r.stdout, \
        r.stdout[-2000:] + r.stderr[-3000:]


def test_launcher_mesh_e2e_and_resume():
    r = _run(LAUNCHER_MESH_RESUME_SCRIPT)
    assert "LAUNCHER-MESH-OK" in r.stdout, \
        r.stdout[-2000:] + r.stderr[-3000:]
    assert "LAUNCHER-MESH-RESUME-OK" in r.stdout, \
        r.stdout[-2000:] + r.stderr[-3000:]
