"""The control: the reference put in the program's place in fp8, at a test size.
Through the harness's own comparison and limits it has to come out not
correct."""
import os

import pytest

from conftest import rehearsed_cells
from benchmarks import harness


@pytest.mark.parametrize('name', [w['name'] for w in rehearsed_cells()])
def test_control_fails_the_limits(tiny, name):
    import jax
    cell = tiny(name)
    if len(jax.devices()) < cell.chips:
        pytest.skip('needs {} devices'.format(cell.chips))
    # in float32 the tiny program reads round-off: the control's gaps are its own
    cell.cfg = dict(cell.cfg, compute_dtype='float32')
    result = harness.run(cell, 17, 0.3, control=True,
                         cache_root=os.path.join(tiny.root, 'cache'))
    assert result['correct'], result['checks']
    assert set(result['_control_checks']) == set(cell.cfg['checks'])
    assert result['_control_correct'] is False, result['_control_checks']
