"""The readers of the Moonlight cell's kernels, on trace records whose event
names are HLO instructions of the compiled step (shapes as the chip's compiler
writes them, kernel bodies left out)."""
import pytest

from benchmarks import flops
from benchmarks.metrics import flash_roofline, mla_flash_roofline

PEAK = {'bf16_flops_per_s': 197e12, 'hbm_bytes_per_s': 819e9}
COL = 'f32[16,8192,1]{2,1,0}'
QK, V = 'bf16[16,8192,192]{2,1,0}', 'bf16[16,8192,128]{2,1,0}'


def _call(name, out, ins, attrs='kernel_metadata={}'):
    return ('%{} = {} custom-call(%a, %b), custom_call_target="tpu_custom_call", '
            'operand_layout_constraints={{{}}}, frontend_attributes={{{}}}'
            .format(name, out, ', '.join(ins), attrs))


MLA = {
    'fwd': _call('mla.30', '({}, {})'.format(V, COL), [QK, QK, V]),
    'bwd_dq': _call('mla.31', QK, [QK, QK, V, V, COL, COL]),
    'bwd_dkv': _call('mla.32', '({}, {})'.format(QK, V), [QK, QK, V, V, COL, COL]),
}
META = ['s32[1]{0}', 's32[9]{0}', 's32[103]{0}', 's32[103]{0}', 's32[1]{0}']
TILING = 'mosaic_fusion_entry_point="true",ragged_dot_tiling="512,512,256"'
ROWS_IN, ROWS_OUT = 'bf16[49152,2048]{1,0}', 'bf16[49152,2816]{1,0}'
WGRAD = _call('ragged-dot-none.1', 'bf16[8,2048,2816]{2,1,0}', META + [ROWS_IN, ROWS_OUT],
              TILING)                                   # w_in's weight gradient
ATTENTION = {'causal': True, 'q_head': 192, 'v_head': 128}


def _run(op_seconds, op_counts):
    return {'trace': {'op_seconds': op_seconds, 'op_counts': op_counts}, 'peak': PEAK,
            'flash': ATTENTION, 'work_per_step': 8192, 'chips': 1}


def test_mla_kernels_are_found_by_their_shapes():
    assert {k: mla_flash_roofline.kernel_call(v) for k, v in MLA.items()} == {
        kind: (kind, 16, 8192, 192, 128, 2) for kind in MLA}
    # the equal-head reader does not take them, nor this one the grouped matmuls
    assert all(flash_roofline.kernel_call(v) is None for v in MLA.values())
    assert mla_flash_roofline.kernel_call(WGRAD) is None


def test_mla_roofline_counts_both_heads():
    # forward: q.k at 192 and p.v at 128 over the causal half
    assert mla_flash_roofline.kernel_flops('fwd', 16, 8192, 192, 128) == 16 * 8192 ** 2 * 320
    assert mla_flash_roofline.kernel_flops('bwd_dkv', 16, 8192, 192, 128, causal=False) == (
        2 * 16 * 8192 ** 2 * (2 * 192 + 2 * 128))
    # with equal heads the counts are the equal-head reader's
    for kind in MLA:
        assert mla_flash_roofline.kernel_flops(kind, 32, 2048, 128, 128) == (
            flops.flash_kernel_flops(kind, 32, 2048, 128))
        assert mla_flash_roofline.kernel_bytes(kind, 32, 2048, 128, 128) == (
            flops.flash_kernel_bytes(kind, 32, 2048, 128))
    # each event at twice its least time reads 50%
    seconds, counts = {}, {}
    for kind, name in MLA.items():
        least, _ = flops.roofline_seconds(
            mla_flash_roofline.kernel_flops(kind, 16, 8192, 192, 128),
            mla_flash_roofline.kernel_bytes(kind, 16, 8192, 192, 128), PEAK)
        seconds[name], counts[name] = 2 * 6 * least, 6
    run = _run(seconds, counts)
    assert mla_flash_roofline.read(run) == pytest.approx(50.0)
    assert run['notes']['mla_flash_roofline_bound'] == ['compute']
    assert mla_flash_roofline.read(dict(run, flash={'causal': True})) is None

