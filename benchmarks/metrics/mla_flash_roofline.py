"""Multi-head latent attention's flash kernels: least time on the chip over
their measured time. The q/k head (``Dq``, 192 in MLA) and the value head
(``Dv``, 128) differ; the run's ``flash`` record (``Program.attention()``) gives
both, and the kernel events are found by their shapes: the forward takes q, k
[BH, T, Dq] and v [BH, T, Dv] and gives o [BH, T, Dv] and the log-sum-exp
column; dq takes q, k, v, do and two columns and gives dq; dk/dv takes the same
and gives dk and dv. Each event's least time is the larger of its matmul
operations over the causal half at peak FLOP/s (q.k at Dq, p.v at Dv) and its
least HBM bytes (every operand read once and every result written once, at its
own shape) at peak bandwidth; the share is their sum over the summed device
durations of those events."""
import re

from benchmarks import flops

_SHAPE = re.compile(r'(bf16|f32|f16)\[([\d,]+)\]')
_ITEMSIZE = {'bf16': 2, 'f16': 2, 'f32': 4}
#: the head each [T, T] matmul of a kernel contracts or produces: the forward
#: forms S = q k^T (Dq) and P v (Dv); dq replays S, forms dP = dO v^T (Dv) and
#: dS k (Dq); dk/dv replays S, forms dP, P^T dO (Dv) and dS^T q (Dq)
MATMUL_HEADS = {'fwd': ('q', 'v'), 'bwd_dq': ('q', 'v', 'q'),
                'bwd_dkv': ('q', 'v', 'v', 'q')}


def read(run):
    trace, attention = run['trace'], run.get('flash')
    if trace is None or not run['peak'] or not attention or 'v_head' not in attention:
        return None
    least, spent, bounds = 0.0, 0.0, set()
    for name, seconds in trace['op_seconds'].items():
        call = kernel_call(name)
        if call is None:
            continue
        kind, bh, t, dq, dv, itemsize = call
        if (dq, dv) != (attention['q_head'], attention['v_head']):
            continue
        each, bound = flops.roofline_seconds(
            kernel_flops(kind, bh, t, dq, dv, attention['causal']),
            kernel_bytes(kind, bh, t, dq, dv, itemsize), run['peak'])
        least += trace['op_counts'][name] * each
        spent += seconds
        bounds.add(bound)
    if not spent:
        return None
    run.setdefault('notes', {})['mla_flash_roofline_bound'] = sorted(bounds)
    return 100.0 * least / spent


def kernel_call(op_name):
    """``(kind, batch x heads, T, Dq, Dv, itemsize)`` for a flash kernel's trace
    event (an HLO ``tpu_custom_call``) whose value head may differ from its q/k
    head, else None."""
    if 'custom_call_target="tpu_custom_call"' not in op_name or ' = ' not in op_name:
        return None
    out = _shapes(op_name.split(' = ', 1)[1].split(' custom-call(', 1)[0])
    constraints = op_name.split('operand_layout_constraints={', 1)
    if len(constraints) < 2:
        return None
    ins = _shapes(constraints[1].split('}, frontend_attributes', 1)[0])
    if len(ins) < 3 or len(ins[0][1]) != 3 or len(ins[2][1]) != 3:
        return None
    (dtype, (bh, t, dq)), (_, (_, _, dv)) = ins[0], ins[2]
    qk, v, column = (dtype, (bh, t, dq)), (dtype, (bh, t, dv)), ('f32', (bh, t, 1))
    if ins == [qk, qk, v] and out == [v, column]:
        kind = 'fwd'
    elif ins == [qk, qk, v, v, column, column] and out == [qk]:
        kind = 'bwd_dq'
    elif ins == [qk, qk, v, v, column, column] and out == [qk, v]:
        kind = 'bwd_dkv'
    else:
        return None
    return kind, bh, t, dq, dv, _ITEMSIZE[dtype]


def kernel_flops(kind, bh, t, dq, dv, causal=True):
    """Matmul operations of one call over ``bh`` sequences of ``t``, the causal
    half only where ``causal``: each [T, T] matmul is ``2 bh t^2 d``."""
    heads = {'q': dq, 'v': dv}
    full = sum(2 * bh * t * t * heads[h] for h in MATMUL_HEADS[kind])
    return full // 2 if causal else full


def kernel_bytes(kind, bh, t, dq, dv, itemsize=2):
    """The least HBM traffic of one call: every operand read once and every
    result written once, at its own shape; row statistics are float32 columns."""
    qk, v, column = bh * t * dq * itemsize, bh * t * dv * itemsize, bh * t * 4
    if kind == 'fwd':
        return 2 * qk + v + v + column              # q k v in; o, lse out
    if kind == 'bwd_dq':
        return 2 * qk + 2 * v + 2 * column + qk     # q k v do, lse delta in; dq out
    if kind == 'bwd_dkv':
        return 2 * qk + 2 * v + 2 * column + qk + v  # ... in; dk dv out
    raise ValueError(kind)


def _shapes(text):
    return [(dt, tuple(int(x) for x in dims.split(','))) for dt, dims in _SHAPE.findall(text)]
