"""Compile the main path's device programs for a described TPU v5e, with no chip.

The TPU compiler refuses what Pallas interpret mode accepts (block shapes off the
(8, 128) tiling, unaligned slices, too much VMEM), so each kernel of the main path is
compiled here at real widths for one chip of a ``v5e:2x2`` topology. The topology is
described inside a fixture, never at import: only one process may hold the TPU
library, and every pytest-xdist worker imports this file.
"""
import importlib
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from petastorm_tpu.ops.image_decode import dct_decode_images_jax
from petastorm_tpu.ops.raw_decode import stored_inflate

# the module, not the same-named function petastorm_tpu.ops re-exports
fa = importlib.import_module('petastorm_tpu.ops.flash_attention')

BH, T, D, HEADS = 8, 2048, 128, 4
# the tiles 'auto' can pick at T = 2,048 on the main path
BLOCKS = pytest.mark.parametrize('block', [256, 512], ids=['block256', 'block512'])


@pytest.fixture(scope='module')
def topo():
    os.environ.setdefault('TPU_LOG_DIR', 'disabled')
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform='tpu', topology_name='v5e:2x2')
    except Exception as e:  # noqa: BLE001 - no TPU compiler here: skip, never fail
        pytest.skip('no v5e:2x2 topology can be described here: {}'.format(e))
    # a described chip's executable cannot be read back from the persistent cache
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update('jax_enable_compilation_cache', False)
    yield topo
    jax.config.update('jax_enable_compilation_cache', cache_was)


@pytest.fixture(scope='module')
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


# float32 inputs contract at fp32 (Precision.HIGHEST), bf16 in one pass
DTYPES = pytest.mark.parametrize('dtype', [jnp.bfloat16, jnp.float32])


@BLOCKS
@DTYPES
def test_flash_forward_compiles(one_chip, dtype, block):
    q = _spec(one_chip, (BH, T, D), dtype)
    hlo = _compile(lambda q, k, v: fa._flash_forward(q, k, v, True, block, block, False),
                   q, q, q)
    assert 'tpu_custom_call' in hlo


@BLOCKS
@DTYPES
def test_flash_backward_compiles(one_chip, dtype, block):
    q = _spec(one_chip, (BH, T, D), dtype)
    lse = _spec(one_chip, (BH, T, 1), jnp.float32)
    hlo = _compile(lambda q, k, v, o, lse, do: fa._flash_backward(
        q, k, v, o, lse, do, True, block, block, False), q, q, q, q, lse, q)
    assert hlo.count('tpu_custom_call') >= 2  # the dQ and the dK/dV kernels


@BLOCKS
def test_segmented_flash_forward_compiles(one_chip, block):
    q = _spec(one_chip, (BH, T, D), jnp.bfloat16)
    segments = _spec(one_chip, (BH // HEADS, T), jnp.int32)
    hlo = _compile(lambda q, k, v, s: fa._flash_forward(
        q, k, v, True, block, block, False, segments=s, heads=HEADS), q, q, q, segments)
    assert 'tpu_custom_call' in hlo


@BLOCKS
def test_segmented_flash_backward_compiles(one_chip, block):
    """The segment ids ride both grid orders: (bh, q, k) for dQ, (bh, k, q) for dK/dV."""
    q = _spec(one_chip, (BH, T, D), jnp.bfloat16)
    lse = _spec(one_chip, (BH, T, 1), jnp.float32)
    segments = _spec(one_chip, (BH // HEADS, T), jnp.int32)
    hlo = _compile(lambda q, k, v, o, lse, do, s: fa._flash_backward(
        q, k, v, o, lse, do, True, block, block, False, segments=s, heads=HEADS),
        q, q, q, q, lse, q, segments)
    assert hlo.count('tpu_custom_call') >= 2


@BLOCKS
def test_flash_kernels_keep_the_roofline_signatures(one_chip, monkeypatch, block):
    """One attention call's forward and backward, compiled for the chip, hold exactly
    one forward, one dq and one dk/dv kernel that the ``flash_roofline`` metric's
    ``kernel_call`` recognises by their operand and result shapes."""
    from benchmarks.metrics.flash_roofline import kernel_call
    monkeypatch.setattr(fa, 'pallas_interpret', lambda: False)  # compile for the chip
    x = _spec(one_chip, (BH // HEADS, T, HEADS, D), jnp.bfloat16)

    def loss(q, k, v):
        return fa.flash_attention(q, k, v, True, block, block).astype(jnp.float32).sum()
    hlo = _compile(jax.grad(loss, argnums=(0, 1, 2)), x, x, x)
    calls = [kernel_call(line.strip()) for line in hlo.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert sorted(calls) == [(kind, BH, T, D, 2) for kind in ('bwd_dkv', 'bwd_dq', 'fwd')]


def test_stored_inflate_compiles(one_chip):
    """An XLA gather, not a kernel: Mosaic cannot slice uint8 HBM at byte offsets."""
    out_len = 4 << 20
    src = _spec(one_chip, (out_len + (64 << 10),), jnp.uint8)
    segments = _spec(one_chip, (1024, 3), jnp.int32)
    hlo = _compile(lambda src, segs: stored_inflate(src, segs, out_len), src, segments)
    assert 'gather' in hlo


def test_dct_decode_program_compiles(one_chip):
    coeffs = _spec(one_chip, (64, 28, 28, 8, 8, 3), jnp.int16)
    hlo = _compile(lambda c: dct_decode_images_jax(c, quality=90), coeffs)
    assert 'u8[64,224,224,3]' in hlo
