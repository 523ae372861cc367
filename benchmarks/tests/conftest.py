"""Tiny copies of the benchmark's configurations and mixes, for CPU tests.

The files are written into a temporary directory laid out like the checkout
(``BENCHMARK.json``, config and traffic files); the config modules, the metric
readers and the generator are the real ones."""
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import harness  # noqa: E402

TINY_CONFIGS = {
    'resnet50_imagenet': {'stage_sizes': [1, 1, 1, 1], 'num_filters': 8, 'num_classes': 10,
                          'image_hw': 32, 'batch_per_chip': 8},
    'cerebras_gpt_1p3b': {'n_embd': 128, 'n_head': 1, 'head_dim': 128, 'n_inner': 512,
                          'n_layer': 2, 'vocab_size': 512, 'n_positions': 256,
                          'seq_len': 256, 'batch_per_chip': 2},
}
TINY_STORES = {
    'jpeg_stream': {'rows': 48, 'sides': [[48, 64], [64, 48]], 'labels': 10,
                    'rowgroup_size_mb': 1, 'files': 4},
    'dct_device': {'rows': 48, 'hw': 32, 'labels': 10, 'rowgroup_size_mb': 1, 'files': 4},
    'tokens_stream': {'rows': 32, 'seq_len': 256, 'vocab': 512, 'rowgroup_size_mb': 1,
                      'files': 4},
}
TINY_TRANSFORM = {'out_hw': 32}


#: paths that the benchmark has no cell for yet: the 4-device mesh, device decode
EXTRA_CELLS = [
    {'name': 'resnet50.jpeg_dp4', 'config': 'resnet50_imagenet', 'traffic': 'jpeg_stream',
     'chips': 4, 'why': 'data parallelism over a 4-device mesh fed by one pool'},
    {'name': 'resnet50.dct_device', 'config': 'resnet50_imagenet', 'traffic': 'dct_device',
     'chips': 1, 'why': 'DCT rows decoded on the device'},
]


def tiny_tree(root):
    """Write a tiny benchmark tree under ``root``; returns the spec, with
    :data:`EXTRA_CELLS` beside the benchmark's own."""
    spec = harness.load_spec()
    spec['workloads'] += EXTRA_CELLS
    for metric in spec['end_to_end'] + spec['per_layer']:
        if 'resnet50.jpeg_stream' in metric.get('workloads', []) and (
                metric['name'] != 'host_decode_ms_per_row'):
            metric['workloads'] += [cell['name'] for cell in EXTRA_CELLS]
    os.makedirs(os.path.join(root, 'configs'), exist_ok=True)
    os.makedirs(os.path.join(root, 'traffic'), exist_ok=True)
    for config in spec['configs']:
        with open(os.path.join(ROOT, config['file'])) as f:
            cfg = json.load(f)
        cfg.update(TINY_CONFIGS[config['name']])
        cfg['module'] = os.path.join(harness.BENCH_DIR, 'configs', cfg['module'])
        config['file'] = os.path.join('configs', config['name'] + '.json')
        with open(os.path.join(root, config['file']), 'w') as f:
            json.dump(cfg, f)
    for mix_name, store in TINY_STORES.items():
        mix = harness.stores.load_mix(os.path.join(harness.BENCH_DIR, 'traffic'), mix_name)
        mix['store'].update(store)
        mix['reader']['workers'] = 2
        if 'transform' in mix:
            mix['transform'].update(TINY_TRANSFORM)
        with open(os.path.join(root, 'traffic', mix_name + '.json'), 'w') as f:
            json.dump(mix, f)
    return spec


@pytest.fixture(scope='session')
def tiny(tmp_path_factory):
    root = str(tmp_path_factory.mktemp('bench'))
    spec = tiny_tree(root)

    def cell(name):
        return harness.Cell(spec, name, root=root,
                            traffic_dir=os.path.join(root, 'traffic'))

    cell.root = root
    cell.spec = spec
    return cell
