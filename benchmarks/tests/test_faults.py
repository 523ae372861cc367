"""The check catches each fault that a cell's timed path can have: a run with the
fault planted underneath the harness comes out not correct."""
import os

import pytest

from conftest import fault_cases
from benchmarks import harness


@pytest.mark.parametrize('name,fault', fault_cases())
def test_fault_fails_the_check(tiny, name, fault):
    import jax
    cell = tiny(name)
    if len(jax.devices()) < cell.chips:
        pytest.skip('needs {} devices'.format(cell.chips))
    # in float32 the sound program reads round-off, so what the fault reads is its own
    cell.cfg = dict(cell.cfg, compute_dtype='float32')
    result = harness.run(cell, 13, 0.3, fault=fault, cache_root=os.path.join(tiny.root, 'cache'))
    assert not result['correct'], result['checks']
