"""The flash kernels' least time on the chip over their measured time. For each
forward, dq and dk/dv kernel event in the traced window, the least time is the
larger of its matmul operations over the causal half at peak FLOP/s and its
least HBM bytes at peak bandwidth, both from the shapes in the event; the share
is their sum over the summed device durations of those events."""
import re

from benchmarks import flops

_SHAPE = re.compile(r'(bf16|f32|f16)\[([\d,]+)\]')
_ITEMSIZE = {'bf16': 2, 'f16': 2, 'f32': 4}


def read(run):
    trace, attention = run['trace'], run.get('flash')
    if trace is None or attention is None or not run['peak']:
        return None
    least, spent, bounds = 0.0, 0.0, set()
    for name, seconds in trace['op_seconds'].items():
        call = kernel_call(name)
        if call is None:
            continue
        kind, bh, t, d, itemsize = call
        each, bound = flops.roofline_seconds(
            flops.flash_kernel_flops(kind, bh, t, d, attention['causal']),
            flops.flash_kernel_bytes(kind, bh, t, d, itemsize), run['peak'])
        least += trace['op_counts'][name] * each
        spent += seconds
        bounds.add(bound)
    if not spent:
        return None
    run.setdefault('notes', {})['flash_roofline_bound'] = sorted(bounds)
    return 100.0 * least / spent


def kernel_call(op_name):
    """``(kind, batch x heads, T, D, itemsize)`` for a flash kernel's trace event
    (an HLO ``tpu_custom_call``), else None. The forward takes q, k, v and gives
    o and the log-sum-exp column; dq takes q, k, v, do and two columns and gives
    dq; dk/dv takes the same and gives dk and dv."""
    if 'custom_call_target="tpu_custom_call"' not in op_name or ' = ' not in op_name:
        return None
    out = _shapes(op_name.split(' = ', 1)[1].split(' custom-call(', 1)[0])
    constraints = op_name.split('operand_layout_constraints={', 1)
    if len(constraints) < 2:
        return None
    ins = _shapes(constraints[1].split('}, frontend_attributes', 1)[0])
    if not ins or len(ins[0][1]) != 3:
        return None
    dtype, (bh, t, d) = ins[0]
    tensor, column = (dtype, (bh, t, d)), ('f32', (bh, t, 1))
    if ins == [tensor] * 3 and out == [tensor, column]:
        kind = 'fwd'
    elif ins == [tensor] * 4 + [column] * 2 and out == [tensor]:
        kind = 'bwd_dq'
    elif ins == [tensor] * 4 + [column] * 2 and out == [tensor, tensor]:
        kind = 'bwd_dkv'
    else:
        return None
    return kind, bh, t, d, _ITEMSIZE[dtype]


def _shapes(text):
    return [(dt, tuple(int(x) for x in dims.split(','))) for dt, dims in _SHAPE.findall(text)]
