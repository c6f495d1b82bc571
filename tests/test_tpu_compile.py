"""The main-path Pallas kernels compile for a TPU v5e at yi-9b widths.

Compiled for a v5e:2x2 topology that is described, not attached: the
TPU compiler refuses here what it would refuse on the chip (unaligned
blocks, too much VMEM), at no chip time.  Each test checks the kernel
is in the compiled program as a ``tpu_custom_call``, i.e. compiled by
Mosaic and not interpreted.

The topology is described inside a fixture: only the worker that runs
these tests loads the TPU library.  They skip only where libtpu is not
installed (it is pinned in requirements.txt); any other failure to
describe the topology fails them.  The persistent compile cache is off
around the compiles: an entry compiled here cannot be read back without
a chip.
"""
import importlib.util
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.multi_tensor import kernel as mt
from repro.kernels.paged_attention.kernel import paged_decode_attention

# one yi-9b FFN matrix (4096 x 11008) as a flat bucket, rounded to TILE
BUCKET = -(-4096 * 11008 // mt.TILE) * mt.TILE
# yi-9b attention: 32 query heads over 4 kv heads of width 128
B, H, K, HD, BS, NBT = 4, 32, 4, 128, 16, 8
# deepseek-7b's chat cell: 32 slots, 32 heads of 128 without grouping,
# block 16, 96 table columns, a 3073-block layer pool
CHAT = dict(B=32, H=32, HD=128, BS=16, NBT=96, NB=3073)
DTYPES = [jnp.float32, jnp.bfloat16]
KERNEL = 'custom_call_target="tpu_custom_call"'
CUSTOM_CALL = re.compile(r"^\s*(?:ROOT )?%([\w.\-]+) = \S+ custom-call\(",
                         re.MULTILINE)


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    if importlib.util.find_spec("libtpu") is None:
        pytest.skip("libtpu is not installed: no TPU compiler here")
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _shape(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiled_text(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("dtype", DTYPES)
def test_chunk_sumsq_compiles(one_chip, dtype):
    x = _shape((BUCKET,), dtype, one_chip)
    text = _compiled_text(
        lambda g, p: mt.chunk_sumsq(g, p, wd=1e-4), x, x)
    assert KERNEL in text


@pytest.mark.parametrize("dtype", DTYPES)
def test_fused_update_compiles(one_chip, dtype):
    p = _shape((BUCKET,), dtype, one_chip)
    u = _shape((BUCKET,), jnp.float32, one_chip)
    a = _shape((BUCKET // mt.CHUNK,), jnp.float32, one_chip)
    c = _shape((), jnp.float32, one_chip)
    text = _compiled_text(
        lambda p, g, u, a, c: mt.fused_update(p, g, u, a, c, beta=0.9,
                                              wd=1e-4),
        p, p, u, a, c)
    assert KERNEL in text


@pytest.mark.parametrize("dtype", DTYPES)
def test_adam_update_compiles(one_chip, dtype):
    p = _shape((BUCKET,), dtype, one_chip)
    m = _shape((BUCKET,), jnp.float32, one_chip)
    bc = _shape((), jnp.float32, one_chip)
    text = _compiled_text(
        lambda p, g, m, v, b1, b2: mt.adam_update(
            p, g, m, v, b1, b2, b1=0.9, b2=0.999, eps=1e-6, wd=1e-4),
        p, p, m, m, bc, bc)
    assert KERNEL in text


@pytest.mark.parametrize("dtype", DTYPES)
def test_scale_apply_compiles(one_chip, dtype):
    p = _shape((BUCKET,), dtype, one_chip)
    g = _shape((BUCKET,), jnp.float32, one_chip)
    a = _shape((BUCKET // mt.CHUNK,), jnp.float32, one_chip)
    c = _shape((), jnp.float32, one_chip)
    text = _compiled_text(mt.scale_apply, p, g, a, c)
    assert KERNEL in text


@pytest.mark.parametrize("dtype", DTYPES)
def test_paged_decode_attention_compiles(one_chip, dtype):
    nb = 1 + B * NBT
    q = _shape((B, H, HD), dtype, one_chip)
    pool = _shape((nb, BS, K, HD), dtype, one_chip)
    bt = _shape((B, NBT), jnp.int32, one_chip)
    pos = _shape((B,), jnp.int32, one_chip)
    text = _compiled_text(paged_decode_attention, q, pool, pool, bt, pos)
    assert KERNEL in text


@pytest.mark.parametrize("h,k,hd", [(8, 1, 256),       # gemma-2b
                                    (20, 20, 64)])     # whisper-large-v3
def test_paged_decode_attention_compiles_padded_pools(one_chip, h, k, hd):
    """Pools whose (K, hd) are not whole tiles of the HBM layout, which
    a DMA cannot address in place, are padded first and still compile."""
    q = _shape((B, h, hd), jnp.bfloat16, one_chip)
    pool = _shape((1 + B * NBT, BS, k, hd), jnp.bfloat16, one_chip)
    bt = _shape((B, NBT), jnp.int32, one_chip)
    pos = _shape((B,), jnp.int32, one_chip)
    text = _compiled_text(paged_decode_attention, q, pool, pool, bt, pos)
    assert KERNEL in text


def test_paged_decode_attention_compiles_at_chat_size(one_chip):
    """The whole call is one Mosaic kernel named paged_decode_attention
    (the name the benchmark's roofline reads): no copy or transpose of
    the pool, no second custom call beside it."""
    c = CHAT
    q = _shape((c["B"], c["H"], c["HD"]), jnp.bfloat16, one_chip)
    pool = _shape((c["NB"], c["BS"], c["H"], c["HD"]), jnp.bfloat16,
                  one_chip)
    bt = _shape((c["B"], c["NBT"]), jnp.int32, one_chip)
    pos = _shape((c["B"],), jnp.int32, one_chip)
    text = _compiled_text(paged_decode_attention, q, pool, pool, bt, pos)
    assert KERNEL in text
    (name,) = CUSTOM_CALL.findall(text)
    assert re.fullmatch(r"paged_decode_attention(\.\d+)?", name), name


# deepseek-v2-lite's held experts: 8 of width 1408 over d_model 2048, a
# buffer of 2 x 8192 tokens x 6 assignments (train-dsv2lite-8k)
MOE = dict(E=8, D=2048, F=1408, ROWS=2 * 8192 * 6)


@pytest.mark.parametrize("k,n", [(MOE["D"], MOE["F"]),     # gate and up
                                 (MOE["F"], MOE["D"])],    # down
                         ids=["d_to_f", "f_to_d"])
def test_grouped_matmul_compiles_forward_and_backward(one_chip, monkeypatch,
                                                      k, n):
    """The held experts' grouped products at deepseek-v2-lite widths, and
    their backward (the transposed product and the per-expert weight
    gradient), each a Mosaic kernel."""
    from repro.kernels.grouped_matmul import ops
    monkeypatch.setattr(ops, "interpret_mode", lambda: False)
    x = _shape((MOE["ROWS"], k), jnp.bfloat16, one_chip)
    w = _shape((MOE["E"], k, n), jnp.bfloat16, one_chip)
    sizes = _shape((MOE["E"],), jnp.int32, one_chip)

    def loss(x, w, sizes):
        return jnp.sum(jnp.square(ops.gmm(x, w, sizes).astype(jnp.float32)))
    text = _compiled_text(jax.value_and_grad(loss, argnums=(0, 1)), x, w,
                          sizes)
    assert text.count(KERNEL) == 3
