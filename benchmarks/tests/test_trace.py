"""The trace reduction, on a short trace recorded on one TPU v5e (a traced run of
``cgpt1p3b.tokens_stream``): the CPU reduces it to the numbers the chip run
printed, and the per-layer readers read them."""
import json
import os

import pytest

from benchmarks import trace
from benchmarks.metrics import flash_roofline

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), 'data')
TRACE = os.path.join(DATA, 'tokens_stream.xplane.pb')


@pytest.fixture(scope='module')
def reduced():
    if not os.path.exists(TRACE):
        pytest.skip('no recorded trace')
    return trace.reduce(TRACE)


def test_reduces_to_the_chip_runs_numbers(reduced):
    with open(os.path.join(DATA, 'tokens_stream.reduced.json')) as f:
        want = json.load(f)
    for key in ('window_s', 'busy_s', 'busiest_busy_s', 'devices'):
        assert reduced[key] == pytest.approx(want[key], rel=1e-9), key
    assert reduced['device_ops'] == want['device_ops']
    assert reduced['idle_gaps'] == want['idle_gaps']


def test_busy_is_inside_the_window(reduced):
    assert 0 < reduced['busy_s'] <= reduced['window_s']
    assert sum(s for _, s in reduced['idle_gaps']) <= reduced['window_s'] - reduced['busy_s'] + 1e-9


def test_flash_kernels_are_found_and_under_their_roofline(reduced):
    calls = {flash_roofline.kernel_call(n) for n in reduced['op_seconds']} - {None}
    assert calls == {(kind, 32, 2048, 128, 2) for kind in ('fwd', 'bwd_dq', 'bwd_dkv')}
    with open(os.path.join(os.path.dirname(os.path.dirname(DATA)), 'peaks.json')) as f:
        peak = json.load(f)['TPU v5 lite']
    run = {'trace': reduced, 'peak': peak, 'flash': {'causal': True}}
    share = flash_roofline.read(run)
    with open(os.path.join(DATA, 'tokens_stream.reduced.json')) as f:
        assert share == pytest.approx(json.load(f)['flash_roofline'], rel=1e-12)
    assert 0 < share <= 100
    assert run['notes']['flash_roofline_bound'] == ['compute']


def test_gap_names_come_from_host_spans():
    ops = {0: [('a', 100, 200), ('b', 600, 700)]}
    host = [('bench.window', 0, 1000), ('petastorm_tpu.loader.wait_input', 200, 590),
            ('bench.dispatch', 700, 1000)]
    out = trace.reduce_events(ops, host)
    assert out['busy_s'] == pytest.approx(200e-9)
    assert out['idle_gaps'][0] == ['wait_input', pytest.approx(400e-9)]
    assert out['idle_gaps'][1] == ['dispatch', pytest.approx(300e-9)]
