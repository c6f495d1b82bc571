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
DTYPES = [jnp.float32, jnp.bfloat16]
KERNEL = 'custom_call_target="tpu_custom_call"'


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
