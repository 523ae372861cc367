"""The grouped matmul of rows sorted by group (megablox's kernels, interpret mode
on the CPU) against ``lax.ragged_dot``: results and both gradients."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from petastorm_tpu.ops import grouped_matmul as gm

LAYOUTS = pytest.mark.parametrize('sizes', [
    [100, 0, 37, 200],        # groups off the tiles, an empty one, rows left past them
    [512, 0, 0, 0],           # every row in one group
    [128, 128, 128, 128],     # whole tiles
    [0, 0, 0, 0],             # nothing routed here
], ids=['partial', 'one_group', 'whole_tiles', 'empty'])


def _inputs(sizes, m=512, k=256, n=384):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)
    valid = (jnp.arange(m) < sum(sizes))[:, None]
    lhs = jnp.where(valid, jax.random.normal(k1, (m, k), jnp.float32), 0)
    rhs = jax.random.normal(k2, (len(sizes), k, n), jnp.float32)
    cotangent = jax.random.normal(k3, (m, n), jnp.float32)
    return lhs, rhs, jnp.asarray(sizes, jnp.int32), valid, cotangent


def _ragged(lhs, rhs, group_sizes):
    return lax.ragged_dot(lhs, rhs, group_sizes, precision=lax.Precision.HIGHEST)


def _loss(matmul, lhs, rhs, group_sizes, valid, cotangent):
    """Rows past the groups are masked, as the routed layer masks them."""
    out = jnp.where(valid, matmul(lhs, rhs, group_sizes), 0)
    return jnp.sum(out * cotangent)


@LAYOUTS
def test_matches_ragged_dot_with_gradients(sizes):
    lhs, rhs, group_sizes, valid, cotangent = _inputs(sizes)
    grads = {}
    for name, matmul in (('want', _ragged), ('got', gm.grouped_matmul)):
        grads[name] = jax.value_and_grad(functools.partial(_loss, matmul), argnums=(0, 1))(
            lhs, rhs, group_sizes, valid, cotangent)
    (want, (want_lhs, want_rhs)), (got, (got_lhs, got_rhs)) = grads['want'], grads['got']
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-3)
    np.testing.assert_allclose(np.where(valid, got_lhs, 0), np.where(valid, want_lhs, 0),
                               rtol=1e-4, atol=1e-3)
    np.testing.assert_allclose(got_rhs, want_rhs, rtol=1e-4, atol=1e-3)


def test_rows_past_the_groups_add_nothing_to_the_weight_gradient():
    """The weight gradient reads only the groups' rows, whatever lies past them."""
    lhs, rhs, group_sizes, valid, cotangent = _inputs([100, 0, 37, 200])
    noisy = jnp.where(valid, lhs, 1e3)
    grad = jax.grad(functools.partial(_loss, gm.grouped_matmul), argnums=1)
    np.testing.assert_array_equal(grad(noisy, rhs, group_sizes, valid, cotangent),
                                  grad(lhs, rhs, group_sizes, valid, cotangent))


@pytest.mark.parametrize('dim,cap,tile', [
    (2048, 1408, 1024), (2816, 1408, 1408), (1408, 1408, 1408), (1408, 512, 128),
    (2048, 512, 512), (64, 1408, 64), (200, 1408, 200)])
def test_lane_tile_is_the_widest_aligned_divisor(dim, cap, tile):
    assert gm._lane_tile(dim, cap) == tile


@pytest.mark.parametrize('m,tile', [(49152, 512), (1024, 512), (384, 128), (32, 32), (12, 12)])
def test_row_tile_divides_the_rows(m, tile):
    assert gm._row_tile(m) == tile
