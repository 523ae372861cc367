"""Tiny copies of the benchmark's configurations and mixes, for CPU tests.

A configuration's tiny sizes are ``tiny/configs/<config>.json`` and a mix's
``tiny/traffic/<mix>.json`` (blocks of the mix, merged into it), found by the
names ``BENCHMARK.json`` gives. The files are written into a temporary directory
laid out like the checkout (``BENCHMARK.json``, config and traffic files); the
config modules, the metric readers and the generator are the real ones. The
tests' cases are derived from the spec when they are collected, so a new
configuration, mix or cell is rehearsed once its tiny files exist."""
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import harness  # noqa: E402

TINY = os.path.join('benchmarks', 'tests', 'tiny')
#: the CPU tests' reader pool, whatever the mix's
TINY_WORKERS = 2

#: paths that the benchmark has no cell for yet (the 4-device mesh, device
#: decode); a cell of the same name in BENCHMARK.json takes its place
EXTRA_CELLS = [
    {'name': 'resnet50.jpeg_dp4', 'config': 'resnet50_imagenet', 'traffic': 'jpeg_dp4',
     'chips': 4, 'why': 'data parallelism over a 4-device mesh fed by one pool'},
    {'name': 'resnet50.dct_device', 'config': 'resnet50_imagenet', 'traffic': 'dct_device',
     'chips': 1, 'why': 'DCT rows decoded on the device'},
]
#: an extra cell reports the metrics of this cell, but the host decode it bypasses
EXTRA_METRICS_OF = 'resnet50.jpeg_stream'


def tiny_file(kind, name, checkout=ROOT):
    """The tiny file of configuration or mix ``name`` (``kind`` 'configs' or
    'traffic'), or None where there is none."""
    path = os.path.join(checkout, TINY, kind, name + '.json')
    return path if os.path.exists(path) else None


def spec_with_extra_cells(checkout=ROOT):
    """The spec, with :data:`EXTRA_CELLS` beside the benchmark's own workloads
    and reporting their metrics."""
    spec = harness.load_spec(checkout)
    names = {w['name'] for w in spec['workloads']}
    extra = [c for c in EXTRA_CELLS if c['name'] not in names]
    spec['workloads'] += extra
    for metric in spec['end_to_end'] + spec['per_layer']:
        if EXTRA_METRICS_OF in metric.get('workloads', []) and (
                metric['name'] != 'host_decode_ms_per_row'):
            metric['workloads'] += [cell['name'] for cell in extra]
    return spec


def rehearsed_cells(checkout=ROOT):
    """Every cell whose configuration and mix have tiny files, in the spec's order."""
    return [w for w in spec_with_extra_cells(checkout)['workloads']
            if tiny_file('configs', w['config'], checkout)
            and tiny_file('traffic', w['traffic'], checkout)]


def first_one_chip_cells():
    """The first one-chip cell of each configuration."""
    seen, out = set(), []
    for w in rehearsed_cells():
        if w['chips'] == 1 and w['config'] not in seen:
            seen.add(w['config'])
            out.append(w['name'])
    return out


def fault_cases():
    """``(cell, fault)``: every fault that each cell can have; the exchange
    between chips only where there are several."""
    return [(w['name'], fault) for w in rehearsed_cells() for fault in harness.FAULTS
            if fault != 'no_exchange' or w['chips'] > 1]


def load_json(path):
    with open(path) as f:
        return json.load(f)


def tiny_tree(root, checkout=ROOT):
    """Write a tiny benchmark tree under ``root`` from the files of ``checkout``;
    returns the spec (:func:`spec_with_extra_cells`). A configuration or mix
    without a tiny file is left out of the tree."""
    spec = spec_with_extra_cells(checkout)
    os.makedirs(os.path.join(root, 'configs'), exist_ok=True)
    os.makedirs(os.path.join(root, 'traffic'), exist_ok=True)
    for config in spec['configs']:
        tiny = tiny_file('configs', config['name'], checkout)
        if tiny is None:
            continue
        source = os.path.join(checkout, config['file'])
        cfg = load_json(source)
        cfg.update(load_json(tiny))
        cfg['module'] = os.path.join(os.path.dirname(source), cfg['module'])
        config['file'] = os.path.join('configs', config['name'] + '.json')
        with open(os.path.join(root, config['file']), 'w') as f:
            json.dump(cfg, f)
    for mix_name in sorted({w['traffic'] for w in spec['workloads']}):
        tiny = tiny_file('traffic', mix_name, checkout)
        if tiny is None:
            continue
        mix = load_json(os.path.join(checkout, 'benchmarks', 'traffic', mix_name + '.json'))
        for block, values in load_json(tiny).items():
            mix[block].update(values)
        mix['reader']['workers'] = TINY_WORKERS
        with open(os.path.join(root, 'traffic', mix_name + '.json'), 'w') as f:
            json.dump(mix, f)
    return spec


def tiny_rig(root, checkout=ROOT):
    """``cell(name)`` -> :class:`harness.Cell` of the tiny tree written under
    ``root`` from ``checkout``; ``cell.root`` and ``cell.spec`` are the tree's."""
    spec = tiny_tree(root, checkout)

    def cell(name):
        return harness.Cell(spec, name, root=root, traffic_dir=os.path.join(root, 'traffic'),
                            metrics_dir=os.path.join(checkout, 'benchmarks', 'metrics'),
                            kinds_dir=os.path.join(checkout, 'benchmarks', 'kinds'))

    cell.root = root
    cell.spec = spec
    return cell


@pytest.fixture(scope='session')
def tiny(tmp_path_factory):
    return tiny_rig(str(tmp_path_factory.mktemp('bench')))
