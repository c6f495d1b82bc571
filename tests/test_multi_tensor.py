"""Multi-tensor fused optimizer engine (core/multi_tensor + kernels/multi_tensor).

The headline guarantees under test:
  * flatten/unflatten is a lossless round trip for any pytree;
  * the fused path is BIT-identical to the pure-jnp optimizer paths
    (params, momentum, and stats) for sngm / sngm[per_tensor] / msgd /
    lars, fp32 and bf16, across multiple steps — and with fused init now
    returning a flat-buffer-resident FlatOptState, those asserts cover
    the RESIDENT path;
  * the resident path is bit-identical to the per-step (OptState) fused
    path and packs only gradient-sized buffers in steady state;
  * per-segment norms from the single reduction pass match
    jnp.linalg.norm per tensor;
  * the engine issues O(1) kernel launches per step vs O(n_leaves) for
    the per-leaf path.
"""
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import lars, msgd, sngm
from repro.core.multi_tensor import (
    CHUNK, FlatOptState, build_layout, count_packed_bytes, flatten,
    init_flat_state, leaf_sumsq, multi_tensor_step, unflatten,
    _fold_sum, _segment_sums)
from repro.core.optim import OptState, from_pytree, to_pytree
from repro.core.schedules import constant
from repro.kernels import count_pallas_launches
from repro.kernels.multi_tensor import ops as mt_ops
from repro.kernels.multi_tensor import ref as mt_ref

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

KEY = jax.random.PRNGKey(0)

# odd sizes, scalars, exact chunk multiples, one-past-chunk, >1 tile
SHAPES = [(300, 17), (1025,), (), (4,), (2000,), (64, 64), (3, 5, 7), (1024,)]


def make_tree(seed, dtype=jnp.float32, scale=1.0, shapes=SHAPES):
    k = jax.random.fold_in(KEY, seed)
    return {f"p{i}": (scale * jax.random.normal(jax.random.fold_in(k, i), s)
                      ).astype(dtype)
            for i, s in enumerate(shapes)}


def tree_bitwise_equal(a, b):
    return all(bool(jnp.array_equal(x, y)) and x.dtype == y.dtype
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


# ---------------------------------------------------------------------------
# flatten / unflatten round trip
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flatten_unflatten_roundtrip(dtype):
    tree = make_tree(0, dtype)
    layout = build_layout(tree)
    assert tree_bitwise_equal(unflatten(flatten(tree, layout), layout), tree)


def test_roundtrip_mixed_dtypes():
    tree = make_tree(1)
    tree.update({f"b{i}": v.astype(jnp.bfloat16)
                 for i, v in enumerate(make_tree(2).values())})
    layout = build_layout(tree)
    assert len(layout.buckets) == 2
    assert tree_bitwise_equal(unflatten(flatten(tree, layout), layout), tree)
    # momentum convention: f32 buffers regardless of param dtype
    mom = jax.tree.map(lambda p: jnp.ones(p.shape, jnp.float32), tree)
    flats = flatten(mom, layout, cast_to=jnp.float32)
    assert all(f.dtype == jnp.float32 for f in flats)
    assert tree_bitwise_equal(unflatten(flats, layout, keep_dtype=True), mom)


def test_layout_segments_chunk_aligned():
    layout = build_layout(make_tree(0))
    for b in layout.buckets:
        assert b.n_elems % CHUNK == 0
        for s in b.segments:
            assert s.offset % CHUNK == 0
            assert s.chunk_hi * CHUNK >= s.offset + s.size


if HAVE_HYPOTHESIS:
    @settings(max_examples=15, deadline=None)
    @given(shapes=st.lists(
        st.lists(st.integers(1, 40), min_size=0, max_size=3), min_size=1,
        max_size=6),
        bf16_mask=st.integers(0, 63))
    def test_roundtrip_property(shapes, bf16_mask):
        """Any tree of shapes/dtypes survives flatten->unflatten bitwise."""
        tree = {
            f"p{i}": (jax.random.normal(jax.random.fold_in(KEY, i + 1),
                                        tuple(s))
                      .astype(jnp.bfloat16 if (bf16_mask >> i) & 1
                              else jnp.float32))
            for i, s in enumerate(shapes)}
        layout = build_layout(tree)
        assert tree_bitwise_equal(unflatten(flatten(tree, layout), layout),
                                  tree)


# ---------------------------------------------------------------------------
# norms: fold_sum, segment sums, kernel vs ref
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n", [1, 3, 64, 129])
def test_fold_sum_matches_numpy(n):
    x = jax.random.normal(jax.random.fold_in(KEY, n), (n,))
    np.testing.assert_allclose(float(_fold_sum(x)), float(np.sum(np.asarray(x), dtype=np.float64)),
                               rtol=1e-6)


def test_segment_norms_match_linalg():
    """One reduction pass over the flat buffer == per-tensor jnp.linalg.norm."""
    tree = make_tree(3, scale=2.5)
    layout = build_layout(tree)
    (flat,) = flatten(tree, layout)
    parts = mt_ops.chunk_sumsq(flat)
    leaves = jax.tree_util.tree_leaves(tree)
    for b in layout.buckets:
        for s, sq in zip(b.segments, _segment_sums(parts, b)):
            ref = jnp.linalg.norm(leaves[s.index].astype(jnp.float32).ravel())
            np.testing.assert_allclose(float(jnp.sqrt(sq)), float(ref),
                                       rtol=1e-6)
            # and bit-identical to the canonical chunked leaf reduction
            assert bool(jnp.array_equal(sq, leaf_sumsq(leaves[s.index])))


# NB: the ref side is jitted because bitwise parity requires the same
# compilation context — eager op-by-op execution skips the FMA contraction
# XLA applies inside a jit, which moves the last ulp.

@pytest.mark.parametrize("wd", [0.0, 1e-4])
def test_chunk_sumsq_kernel_matches_ref(wd):
    layout = build_layout(make_tree(4))
    (g,) = flatten(make_tree(5, scale=3.0), layout)
    (p,) = flatten(make_tree(4), layout)
    out_k = mt_ops.chunk_sumsq(g, p, wd=wd)                 # pallas interpret
    out_r = jax.jit(partial(mt_ref.chunk_sumsq_ref, wd=wd))(g, p)
    assert bool(jnp.array_equal(out_k, out_r))


@pytest.mark.parametrize("cast_g_first", [False, True])
def test_fused_update_kernel_matches_ref(cast_g_first):
    layout = build_layout(make_tree(4))
    (p,) = flatten(make_tree(4), layout)
    (g,) = flatten(make_tree(5, scale=3.0), layout)
    (u,) = flatten(make_tree(6), layout, cast_to=jnp.float32)
    a = jnp.abs(jax.random.normal(jax.random.fold_in(KEY, 9),
                                  (p.size // CHUNK,)))
    c = jnp.float32(0.7)
    outs_k = mt_ops.fused_update(p, g, u, a, c, beta=0.9, wd=1e-4,
                                 cast_g_first=cast_g_first)
    outs_r = jax.jit(partial(mt_ref.fused_update_ref, beta=0.9, wd=1e-4,
                             cast_g_first=cast_g_first))(p, g, u, a, c)
    for k, r in zip(outs_k, outs_r):
        assert bool(jnp.array_equal(k, r)) and k.dtype == r.dtype


@pytest.mark.parametrize("wd", [0.0, 1e-4])
def test_adam_update_kernel_matches_ref(wd):
    """The LAMB Adam-moment pass: Pallas (interpret) == jnp oracle,
    bitwise, for all six outputs (moments, direction, three partial sets),
    at the extreme t=1 bias correction."""
    layout = build_layout(make_tree(4))
    (p,) = flatten(make_tree(4), layout)
    (g,) = flatten(make_tree(5, scale=3.0), layout)
    (m,) = flatten(make_tree(6), layout, cast_to=jnp.float32)
    (v,) = flatten(jax.tree.map(jnp.abs, make_tree(7, scale=0.1)), layout,
                   cast_to=jnp.float32)
    bc1, bc2 = jnp.float32(1 - 0.9), jnp.float32(1 - 0.999)   # t = 1
    outs_k = mt_ops.adam_update(p, g, m, v, bc1, bc2, b1=0.9, b2=0.999,
                                eps=1e-6, wd=wd)
    outs_r = jax.jit(partial(mt_ref.adam_update_ref, b1=0.9, b2=0.999,
                             eps=1e-6, wd=wd))(p, g, m, v, bc1, bc2)
    for k, r in zip(outs_k, outs_r):
        assert bool(jnp.array_equal(k, r)) and k.dtype == r.dtype


def test_scale_apply_kernel_matches_ref():
    """The LAMB apply pass: Pallas (interpret) == jnp oracle, bitwise."""
    layout = build_layout(make_tree(4))
    (p,) = flatten(make_tree(4), layout)
    (g,) = flatten(make_tree(5, scale=0.5), layout, cast_to=jnp.float32)
    a = jnp.abs(jax.random.normal(jax.random.fold_in(KEY, 11),
                                  (p.size // CHUNK,)))
    outs_k = mt_ops.scale_apply(p, g, a, jnp.float32(0.7))
    outs_r = jax.jit(mt_ref.scale_apply_ref)(p, g, a, jnp.float32(0.7))
    for k, r in zip(outs_k, outs_r):
        assert bool(jnp.array_equal(k, r)) and k.dtype == r.dtype


def test_adam_update_preserves_zero_padding():
    """Zero pads map to zero moments AND zero direction (eps > 0), the
    invariant that keeps the resident Adam buffers equal to re-flattened
    pytree views."""
    tree = {"w": jnp.ones((100,))}          # 924 pad elements in the chunk
    layout = build_layout(tree)
    (p,) = flatten(tree, layout)
    (g,) = flatten({"w": 2.0 * jnp.ones((100,))}, layout)
    z = jnp.zeros_like(p)
    mo, vo, ud, *_ = mt_ops.adam_update(p, g, z, z, jnp.float32(0.1),
                                        jnp.float32(0.001), b1=0.9,
                                        b2=0.999, eps=1e-6, wd=1e-4)
    for buf in (mo, vo, ud):
        assert bool(jnp.array_equal(buf[100:], jnp.zeros((buf.size - 100,))))


# ---------------------------------------------------------------------------
# numerics equality: multi-tensor vs per-leaf vs pure jnp
# ---------------------------------------------------------------------------

OPTIMIZERS = {
    "sngm": lambda **kw: sngm(constant(0.3), beta=0.9, weight_decay=1e-4, **kw),
    "sngm_wd0": lambda **kw: sngm(constant(0.3), beta=0.9, **kw),
    "sngm_per_tensor": lambda **kw: sngm(constant(0.3), beta=0.9,
                                         weight_decay=1e-4,
                                         norm_mode="per_tensor", **kw),
    "msgd": lambda **kw: msgd(constant(0.3), beta=0.9, weight_decay=1e-4, **kw),
    "lars": lambda **kw: lars(constant(0.3), beta=0.9, weight_decay=1e-4, **kw),
}


def _run_steps(opt, params, grads, n=2):
    state = opt.init(params)
    step = jax.jit(opt.step)
    stats = None
    for _ in range(n):
        params, state, stats = step(grads, state, params)
    return params, state, stats


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("name", sorted(OPTIMIZERS))
def test_multi_tensor_bit_identical_to_jnp(name, dtype):
    """The acceptance bar: fused engine == jnp path, bitwise, every output."""
    params = make_tree(0, dtype)
    grads = make_tree(1, dtype, scale=3.0)
    p_r, s_r, st_r = _run_steps(OPTIMIZERS[name](), params, grads)
    p_m, s_m, st_m = _run_steps(OPTIMIZERS[name](fused="multi_tensor"),
                                params, grads)
    assert tree_bitwise_equal(p_r, p_m)
    assert tree_bitwise_equal(s_r.momentum, s_m.momentum)
    for k in st_r:
        assert bool(jnp.array_equal(st_r[k], st_m[k])), k


def test_use_pallas_routes_to_multi_tensor_bit_identical():
    """sngm(use_pallas=True) now IS the multi-tensor engine."""
    params, grads = make_tree(0), make_tree(1, scale=3.0)
    p_r, s_r, _ = _run_steps(OPTIMIZERS["sngm"](), params, grads)
    p_p, s_p, _ = _run_steps(OPTIMIZERS["sngm"](use_pallas=True),
                             params, grads)
    assert tree_bitwise_equal(p_r, p_p)
    assert tree_bitwise_equal(s_r.momentum, s_p.momentum)


@pytest.mark.slow
def test_multi_tensor_matches_per_leaf_kernels():
    """Engine == the original one-kernel-per-tensor path (sngm and lars)."""
    params, grads = make_tree(0), make_tree(1, scale=3.0)
    for name in ("sngm", "lars"):
        p_l, s_l, _ = _run_steps(OPTIMIZERS[name](fused="per_leaf"),
                                 params, grads)
        p_m, s_m, _ = _run_steps(OPTIMIZERS[name](fused="multi_tensor"),
                                 params, grads)
        for a, b in zip(jax.tree.leaves(p_l), jax.tree.leaves(p_m)):
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(b, np.float32), atol=1e-6)


def test_multi_tensor_mixed_dtype_tree():
    params = make_tree(0)
    params.update({f"b{i}": v.astype(jnp.bfloat16)
                   for i, v in enumerate(make_tree(2).values())})
    grads = jax.tree.map(
        lambda p: (3.0 * jax.random.normal(
            jax.random.fold_in(KEY, p.size), p.shape)).astype(p.dtype), params)
    p_r, s_r, st_r = _run_steps(OPTIMIZERS["sngm"](), params, grads)
    p_m, s_m, st_m = _run_steps(OPTIMIZERS["sngm"](fused="multi_tensor"),
                                params, grads)
    assert tree_bitwise_equal(p_r, p_m)
    assert tree_bitwise_equal(s_r.momentum, s_m.momentum)
    assert bool(jnp.array_equal(st_r["grad_norm"], st_m["grad_norm"]))


def test_multi_tensor_ref_backend_bit_identical():
    """backend='ref' (pure jnp oracle, zero pallas calls) == backend='pallas'."""
    params, grads = make_tree(0), make_tree(1, scale=3.0)
    mom = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
    kw = dict(lr=jnp.float32(0.3), beta=0.9, weight_decay=1e-4)
    outs = {}
    for backend in ("pallas", "ref"):
        outs[backend] = jax.jit(
            lambda p, g, u: multi_tensor_step("sngm_global", p, g, u,
                                              backend=backend, **kw)
        )(params, grads, mom)
    (p_a, u_a, st_a), (p_b, u_b, st_b) = outs["pallas"], outs["ref"]
    assert tree_bitwise_equal(p_a, p_b) and tree_bitwise_equal(u_a, u_b)
    assert bool(jnp.array_equal(st_a["grad_norm"], st_b["grad_norm"]))


def test_multi_tensor_rejects_unknown_kind():
    params = make_tree(0)
    mom = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
    with pytest.raises(ValueError):
        multi_tensor_step("adamw", params, params, mom, lr=0.1, beta=0.9)


def test_multi_tensor_rejects_grad_dtype_mismatch():
    """fp32 grads over bf16 params must fail loudly, not silently truncate
    to the bf16 bucket dtype (the jnp path promotes to f32 instead)."""
    params = make_tree(0, jnp.bfloat16)
    grads = make_tree(1, jnp.float32, scale=3.0)
    mom = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
    with pytest.raises(ValueError, match="match the parameter dtype"):
        multi_tensor_step("sngm_global", params, grads, mom, lr=0.1, beta=0.9)


# ---------------------------------------------------------------------------
# flat-buffer residency: FlatOptState vs per-step path
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("name", sorted(OPTIMIZERS))
def test_resident_state_bit_identical_to_per_step(name, dtype):
    """FlatOptState (flatten grads only, buffers carried across steps)
    == OptState into the same fused optimizer (re-pack p+g+u each step),
    bitwise, for every optimizer kind, fp32 and bf16, multi-step."""
    params = make_tree(0, dtype)
    grads = make_tree(1, dtype, scale=3.0)
    opt = OPTIMIZERS[name](fused="multi_tensor")
    s_flat = opt.init(params)
    assert isinstance(s_flat, FlatOptState)
    s_tree = to_pytree(s_flat)
    assert isinstance(s_tree, OptState)
    step = jax.jit(opt.step)
    pf, pt = params, params
    for _ in range(3):
        pf, s_flat, st_f = step(grads, s_flat, pf)
        pt, s_tree, st_t = step(grads, s_tree, pt)
    assert isinstance(s_flat, FlatOptState) and isinstance(s_tree, OptState)
    assert tree_bitwise_equal(pf, pt)
    assert tree_bitwise_equal(s_flat.momentum, s_tree.momentum)
    for k in st_f:
        assert bool(jnp.array_equal(st_f[k], st_t[k])), k


def test_resident_params_view_matches_loop_params():
    """state.p_flats are authoritative; the pytree view handed back for
    loss_fn must stay bit-equal to them every step."""
    params, grads = make_tree(0), make_tree(1, scale=3.0)
    opt = OPTIMIZERS["sngm"](fused="multi_tensor")
    state = opt.init(params)
    step = jax.jit(opt.step)
    for _ in range(2):
        params, state, _ = step(grads, state, params)
        assert tree_bitwise_equal(params, state.params)


def test_state_form_conversion_lossless():
    """to_pytree / from_pytree round-trip bitwise (incl. zero padding),
    on a mixed fp32+bf16 tree with non-zero momentum."""
    params = make_tree(0)
    params.update({f"b{i}": v.astype(jnp.bfloat16)
                   for i, v in enumerate(make_tree(2).values())})
    grads = jax.tree.map(
        lambda p: (3.0 * jax.random.normal(
            jax.random.fold_in(KEY, p.size), p.shape)).astype(p.dtype), params)
    opt = OPTIMIZERS["sngm"](fused="multi_tensor")
    params, state, _ = jax.jit(opt.step)(grads, opt.init(params), params)
    back = from_pytree(to_pytree(state), params)
    assert back.layout == state.layout
    assert tree_bitwise_equal(tuple(back.p_flats), tuple(state.p_flats))
    assert tree_bitwise_equal(tuple(back.u_flats), tuple(state.u_flats))


def test_flat_state_accepted_by_jnp_path():
    """State-form dispatch: a FlatOptState fed to the pure-jnp optimizer
    materializes its momentum view and produces the same numbers."""
    params, grads = make_tree(0), make_tree(1, scale=3.0)
    opt_jnp = OPTIMIZERS["sngm"]()
    flat = init_flat_state(params)
    p_a, s_a, _ = jax.jit(opt_jnp.step)(grads, flat, params)
    p_b, s_b, _ = jax.jit(opt_jnp.step)(grads, opt_jnp.init(params), params)
    assert isinstance(s_a, OptState)
    assert tree_bitwise_equal(p_a, p_b)
    assert tree_bitwise_equal(s_a.momentum, s_b.momentum)


def test_resident_path_packs_only_gradients():
    """The residency win: steady-state steps pack gradient-sized buffers
    only — exactly 1/3 of the per-step path on an all-fp32 tree."""
    params, grads = make_tree(0), make_tree(1, scale=3.0)
    opt = OPTIMIZERS["sngm"](fused="multi_tensor")
    s_flat = opt.init(params)
    s_tree = to_pytree(s_flat)

    def packed(state):
        with count_packed_bytes() as c:
            # fresh lambda: a cached jit would skip tracing and recording
            jax.jit(lambda g, s, p: opt.step(g, s, p)).lower(
                grads, state, params)
        return c["bytes"]

    n_bytes = sum(b.n_elems * 4 for b in s_flat.layout.buckets)
    assert packed(s_flat) == n_bytes           # grads only
    assert packed(s_tree) == 3 * n_bytes       # params + grads + momentum


def test_resident_rejects_grad_dtype_mismatch():
    params = make_tree(0, jnp.bfloat16)
    grads = make_tree(1, jnp.float32, scale=3.0)
    opt = OPTIMIZERS["sngm"](fused="multi_tensor")
    with pytest.raises(ValueError, match="match the parameter dtype"):
        opt.step(grads, opt.init(params), params)


# ---------------------------------------------------------------------------
# launch counts: the reason the engine exists
# ---------------------------------------------------------------------------

def _launches_per_step(opt, params, grads):
    state = opt.init(params)
    with count_pallas_launches() as c:
        jax.jit(opt.step).lower(grads, state, params)
    return c["launches"]


def test_engine_launches_O1_per_leaf_launches_On():
    params, grads = make_tree(0), make_tree(1, scale=3.0)
    n_leaves = len(jax.tree.leaves(params))
    mt = _launches_per_step(OPTIMIZERS["sngm"](fused="multi_tensor"),
                            params, grads)
    pl = _launches_per_step(OPTIMIZERS["sngm"](fused="per_leaf"),
                            params, grads)
    # one norm pass + one update pass for the single f32 bucket
    assert mt == 2, mt
    assert pl == n_leaves, (pl, n_leaves)
    # lars: two raw-norm passes + one update pass per bucket
    assert _launches_per_step(OPTIMIZERS["lars"](fused="multi_tensor"),
                              params, grads) == 3
    # launches stay O(buckets) when the tree grows
    big = {f"x{i}": jnp.ones((65, 3)) for i in range(40)}
    gbig = {k: 2.0 * v for k, v in big.items()}
    assert _launches_per_step(OPTIMIZERS["sngm"](fused="multi_tensor"),
                              big, gbig) == 2


# ---------------------------------------------------------------------------
# shard-padded layouts + FlatGrads (fast lane for the distributed engine)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_shard_padded_layout_roundtrip_and_norm(dtype):
    """A layout built for 4 shards (padding every bucket to a multiple of
    4 tiles) must round-trip and fold norms bitwise like the shards=1
    layout — shard padding is zeros and the canonical per-segment fold
    never sees it."""
    from repro.core.multi_tensor import flat_squared_norm, tree_squared_norm
    tree = make_tree(0, dtype)
    lo1 = build_layout(tree, shards=1)
    lo4 = build_layout(tree, shards=4)
    for b in lo4.buckets:
        assert b.n_elems % 4 == 0
    f1, f4 = flatten(tree, lo1), flatten(tree, lo4)
    assert tree_bitwise_equal(unflatten(f4, lo4), tree)
    n_ref = tree_squared_norm(tree)
    assert bool(jnp.array_equal(flat_squared_norm(f1, lo1), n_ref))
    assert bool(jnp.array_equal(flat_squared_norm(f4, lo4), n_ref))


@pytest.mark.parametrize("name", ["sngm_global", "msgd"])
def test_shard_padded_resident_state_bit_identical(name):
    """An optimizer stepping a shards=4 FlatOptState WITHOUT a mesh (the
    restored-on-fewer-devices fallback) is bitwise the shards=1 run."""
    import dataclasses

    from repro.core.multi_tensor import init_flat_state, resident_step

    params = make_tree(1)
    grads = [make_tree(10 + t, scale=3.0) for t in range(2)]
    kw = dict(lr=0.3, beta=0.9, weight_decay=1e-4)

    st1 = init_flat_state(params)
    st4 = init_flat_state(params)
    lo4 = build_layout(params, shards=4)
    st4 = FlatOptState(step=st4.step, p_flats=tuple(flatten(params, lo4)),
                       u_flats=tuple(jnp.zeros((b.n_elems,), jnp.float32)
                                     for b in lo4.buckets), layout=lo4)
    for g in grads:
        p1, st1, s1 = resident_step(name, g, st1, **kw)
        p4, st4, s4 = resident_step(name, g, st4, **kw)
        assert tree_bitwise_equal(p1, p4)
        for key in ("grad_norm", "update_norm"):
            if key in s1:
                assert bool(jnp.array_equal(s1[key], s4[key])), key


@pytest.mark.parametrize("name", ["sngm_global", "msgd"])
def test_flat_grads_input_bit_identical_to_tree(name):
    """Pre-packed FlatGrads (what the flat-accumulating train step hands
    the engine) must step bitwise like the same gradients as a pytree."""
    from repro.core.multi_tensor import FlatGrads, init_flat_state, \
        resident_step

    params = make_tree(2)
    kw = dict(lr=0.3, beta=0.9, weight_decay=1e-4)
    st_t = init_flat_state(params)
    st_f = init_flat_state(params)
    for t in range(2):
        g = make_tree(20 + t, scale=3.0)
        gf = FlatGrads(tuple(flatten(g, st_f.layout)), st_f.layout)
        p_t, st_t, s_t = resident_step(name, g, st_t, **kw)
        p_f, st_f, s_f = resident_step(name, gf, st_f, **kw)
        assert tree_bitwise_equal(p_t, p_f)
        for key in ("grad_norm", "update_norm"):
            if key in s_t:
                assert bool(jnp.array_equal(s_t[key], s_f[key])), key


def test_flat_grads_layout_mismatch_rejected():
    """FlatGrads packed against a different layout (wrong shard padding)
    must be rejected loudly, not silently mis-sliced."""
    from repro.core.multi_tensor import FlatGrads, init_flat_state, \
        resident_step

    params = make_tree(3)
    st = init_flat_state(params)                 # shards=1 layout
    lo4 = build_layout(params, shards=4)
    g = make_tree(30, scale=3.0)
    gf = FlatGrads(tuple(flatten(g, lo4)), lo4)
    with pytest.raises(ValueError, match="different TreeLayout"):
        resident_step("sngm_global", gf, st, lr=0.3, beta=0.9)
