"""Grouped matmul of rows sorted by group: megablox's Pallas kernels.

``grouped_matmul(lhs, rhs, group_sizes)`` multiplies each group of ``lhs``'s
rows (``lhs [M, K]``, sorted by group, ``group_sizes [G]`` rows each, in order)
by its group's matrix of ``rhs [G, K, N]``: the grouped matmul of a dropless
mixture of experts. The forward and the input gradient run megablox's ``gmm``
kernel, the weight gradient its ``tgmm``; each visits only the row tiles that
some group's rows touch, so its time follows the rows in the groups, and rows
past ``sum(group_sizes)`` are left unwritten in the output and the input
gradient (mask them). On the CPU the kernels run in Pallas interpret mode
(:func:`~petastorm_tpu.ops.flash_attention.pallas_interpret`).

Tiles: 512 rows where ``M`` allows, and the widest multiple of 128 lanes up to
1,408 that divides each width (a width no multiple of 128 divides is taken
whole); the weight gradient's f32 accumulator stays within 1,408 x 512. At the
Moonlight experts' widths (2,048 to 2 x 1,408 and 1,408 to 2,048, 49,152
rows), these tiles took a SwiGLU expert's forward and backward from 0.52 to
0.29 us a routed row on a TPU v5e, against ``lax.ragged_dot``'s.
"""

import importlib

import jax

# the modules, not the same-named functions their packages export
flash_attention = importlib.import_module('petastorm_tpu.ops.flash_attention')
megablox = importlib.import_module('jax.experimental.pallas.ops.tpu.megablox.gmm')

_ROW_TILES = (512, 256, 128, 64, 32, 16, 8)
_WIDE = 1408
_NARROW = 512


def _lane_tile(dim, cap):
    """The widest multiple of 128 that divides ``dim`` and is at most ``cap``;
    ``dim`` itself where none does."""
    for tile in range(min(cap, dim) // 128 * 128, 0, -128):
        if dim % tile == 0:
            return tile
    return dim


def _row_tile(m):
    return next((tile for tile in _ROW_TILES if m % tile == 0), m)


def _gmm(lhs, rhs, group_sizes, transpose_rhs=False):
    k, n = (rhs.shape[2], rhs.shape[1]) if transpose_rhs else rhs.shape[1:]
    tiling = (_row_tile(lhs.shape[0]), _lane_tile(k, _WIDE), _lane_tile(n, _WIDE))
    return megablox.gmm(lhs, rhs, group_sizes, preferred_element_type=lhs.dtype,
                        tiling=tiling, transpose_rhs=transpose_rhs,
                        interpret=flash_attention.pallas_interpret())


def _tgmm(lhs, grad, group_sizes, num_groups, dtype):
    """``lhs [M, K]``, ``grad [M, N]`` -> each group's ``lhs^T grad``, ``[G, K, N]``."""
    k, n = lhs.shape[1], grad.shape[1]
    tk, tn = max(((_lane_tile(k, _NARROW), _lane_tile(n, _WIDE)),
                  (_lane_tile(k, _WIDE), _lane_tile(n, _NARROW))),
                 key=lambda tile: tile[0] * tile[1])
    return megablox.tgmm(lhs.swapaxes(0, 1), grad, group_sizes, preferred_element_type=dtype,
                         tiling=(_row_tile(lhs.shape[0]), tk, tn),
                         num_actual_groups=num_groups,
                         interpret=flash_attention.pallas_interpret())


@jax.custom_vjp
def grouped_matmul(lhs, rhs, group_sizes):
    """``lhs [M, K]`` (rows sorted by group) times ``rhs [G, K, N]`` by the
    ``group_sizes [G]`` (int32): ``[M, N]`` in ``lhs``'s dtype, accumulated in
    float32. Rows past the groups are unwritten."""
    return _gmm(lhs, rhs, group_sizes)


def _forward(lhs, rhs, group_sizes):
    return _gmm(lhs, rhs, group_sizes), (lhs, rhs, group_sizes)


def _backward(residuals, grad):
    lhs, rhs, group_sizes = residuals
    return (_gmm(grad, rhs, group_sizes, transpose_rhs=True),
            _tgmm(lhs, grad, group_sizes, rhs.shape[0], rhs.dtype), None)


grouped_matmul.defvjp(_forward, _backward)
