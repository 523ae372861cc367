"""CPU rehearsal: every cell's configuration and mix at tiny sizes, through the
harness's pieces (the command itself refuses the CPU)."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from conftest import (ROOT, TINY, first_one_chip_cells, load_json, rehearsed_cells,
                      spec_with_extra_cells, tiny_file, tiny_rig)
from benchmarks import harness


def _devices(cell):
    import jax
    devices = jax.devices()
    if len(devices) < cell.chips:
        pytest.skip('needs {} devices (XLA_FLAGS=--xla_force_host_platform_device_count=4)'
                    .format(cell.chips))
    return devices[:cell.chips]


@pytest.mark.parametrize('name', [w['name'] for w in rehearsed_cells()])
def test_cell_runs_and_metrics_read(tiny, name):
    cell = tiny(name)
    result = harness.run(cell, 2 ** 31 + 7, 1.0, devices=_devices(cell),
                         cache_root=os.path.join(tiny.root, 'cache'))
    line = json.loads(harness.result_line(result))
    assert list(line) == ['correct', 'attempted', 'failed', 'metrics', 'device', 'checks']
    assert line['attempted'] > 0 and line['failed'] == 0
    assert set(line['metrics']) == {m['name'] for m in cell.end_to_end} - {'peak_hbm_gib'}
    assert line['device']['count'] == cell.chips
    assert line['checks']['rows_max_diff']['value'] <= cell.mix['check']['rows_max_diff']
    for metric in cell.per_layer:  # every per-layer reader runs on the record
        cell.reader(metric['name']).read(result['_run'])


@pytest.mark.parametrize('name', first_one_chip_cells())
def test_program_matches_reference_in_float32(tiny, tmp_path, name):
    """With the program computing in float32 the check's numbers fall to
    round-off: the reference follows the program's equations."""
    cell = tiny(name)
    cell.cfg = dict(cell.cfg, compute_dtype='float32')
    result = harness.run(cell, 11, 0.5, cache_root=os.path.join(tiny.root, 'cache'))
    gaps = result['_readings']['program']
    assert result['_readings']['rows_max_diff'] == 0
    assert gaps['loss_gap'] < 1e-4 and gaps['grad_gap'] < 1e-4, gaps
    # three steps of a tiny net amplify round-off in a few small leaves
    assert gaps['change_gap'] < 2e-2 and gaps['change_median_gap'] < 1e-4, gaps
    assert result['correct'], result['checks']


def test_traced_run_reads_host_metrics(tiny):
    """On the CPU no device plane is traced: the trace's metrics stay silent and
    the host clock's are read."""
    cell = tiny('resnet50.jpeg_stream')
    result = harness.run(cell, 5, 1.0, trace=True, cache_root=os.path.join(tiny.root, 'cache'))
    assert result['_run']['trace'] is None
    assert {'input_wait_share.rows', 'host_decode_ms_per_row'} <= set(result['metrics'])
    assert 'device_idle_share.rows' not in result['metrics']


#: a kind of store that the benchmark does not have: PNGs at the model's side,
#: read without a transform
PNG_KIND = '''
import numpy as np

from benchmarks import images

COLUMNS = ('label', 'image')
alter = images.alter_pixel


def fields(store):
    from petastorm_tpu.codecs import CompressedImageCodec, ScalarCodec
    from petastorm_tpu.unischema import UnischemaField
    hw = store['hw']
    return [UnischemaField('label', np.int32, (), ScalarCodec(), False),
            UnischemaField('image', np.uint8, (hw, hw, 3), CompressedImageCodec('png'), False)]


def rows(store):
    return images.photo_rows(store, lambda rng: (store['hw'], store['hw']))


def reader_kwargs(mix, seeds):
    return {}


def plain_rows(mix, table, ids, seeds):
    import cv2
    decode = lambda b: cv2.imdecode(np.frombuffer(b, np.uint8), cv2.IMREAD_COLOR)[..., ::-1]
    return {'label': np.asarray([table['label'][i] for i in ids], np.int32),
            'image': np.stack([decode(table['image'][i]) for i in ids])}
'''


def _throwaway_cell(tiny, tmp_path, name, mix, kinds_dir=None):
    traffic = tmp_path / 'traffic'
    traffic.mkdir(exist_ok=True)
    (traffic / (name + '.json')).write_text(json.dumps(mix))
    spec = json.loads(json.dumps(tiny.spec))
    cell_name = 'resnet50.' + name
    spec['workloads'].append({'name': cell_name, 'config': 'resnet50_imagenet',
                              'traffic': name, 'chips': 1, 'why': 'a throwaway mix'})
    for metric in spec['end_to_end'] + spec['per_layer']:
        if 'resnet50.jpeg_stream' in metric.get('workloads', []):
            metric['workloads'].append(cell_name)
    kwargs = {'kinds_dir': str(kinds_dir)} if kinds_dir else {}
    cell = harness.Cell(spec, cell_name, root=tiny.root, traffic_dir=str(traffic), **kwargs)
    # in float32 the tiny program reads round-off against the reference
    cell.cfg = dict(cell.cfg, compute_dtype='float32')
    return cell


@pytest.mark.parametrize('kind', ['jpeg', 'png'])
def test_new_mix_needs_new_files_only(tiny, tmp_path, kind):
    """A later mix is a data file and a workload entry, and a new kind of store
    one more module: no existing file changes. The check still compares it."""
    with open(os.path.join(tiny.root, 'traffic', 'jpeg_stream.json')) as f:
        mix = json.load(f)
    kinds_dir = None
    if kind == 'png':
        kinds_dir = tmp_path / 'kinds'
        kinds_dir.mkdir()
        (kinds_dir / 'png.py').write_text(PNG_KIND)
        mix.pop('transform')
        mix['reader']['pool'] = 'thread'
        mix['store'] = {'kind': 'png', 'version': 1, 'seed': 98, 'rows': 24, 'labels': 10,
                        'hw': tiny('resnet50.jpeg_stream').cfg['image_hw'], 'mid_amp': 80,
                        'tex_amp': 12, 'rowgroup_size_mb': 1, 'files': 2}
    else:
        mix['store'].update(seed=99, rows=24, quality=75)
    cell = _throwaway_cell(tiny, tmp_path, kind + '_new', mix, kinds_dir)
    result = harness.run(cell, 3, 0.5, cache_root=str(tmp_path / 'cache'))
    assert result['attempted'] > 0 and 'rows_per_s' in result['metrics']
    assert result['checks']['rows_max_diff']['value'] == 0 and result['correct']
    altered = harness.run(cell, 3, 0.3, fault='altered', cache_root=str(tmp_path / 'cache'))
    assert altered['checks']['rows_max_diff']['value'] > 0 and not altered['correct']


def _command(args, cwd, env_extra):
    env = dict(os.environ, **env_extra)
    return subprocess.run([sys.executable, 'benchmarks/run.py'] + args, cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_command_refuses_the_cpu():
    proc = _command(['--workload', 'resnet50.jpeg_stream', '--seed', '1', '--seconds', '1',
                     '--trace', '0'], ROOT, {'JAX_PLATFORMS': 'cpu'})
    assert proc.returncode != 0
    assert proc.stdout.strip() == ''


def _benchmark_copy(dst):
    """``BENCHMARK.json`` and the benchmark's files, copied under ``dst``."""
    shutil.copy(os.path.join(ROOT, 'BENCHMARK.json'), dst)
    shutil.copytree(harness.BENCH_DIR, os.path.join(dst, 'benchmarks'),
                    ignore=shutil.ignore_patterns('.cache', '__pycache__'))


def test_command_fails_without_the_program(tmp_path):
    """A directory with only BENCHMARK.json and the benchmark's files is no system."""
    _benchmark_copy(tmp_path)
    proc = _command(['--workload', 'resnet50.jpeg_stream', '--seed', '1', '--seconds', '1',
                     '--trace', '0'], str(tmp_path), {'JAX_PLATFORMS': ''})
    assert proc.returncode != 0
    assert proc.stdout.strip() == ''


def test_every_config_and_mix_has_a_tiny_file():
    spec = spec_with_extra_cells()
    missing = ['configs/' + c['name'] for c in spec['configs']
               if not tiny_file('configs', c['name'])]
    missing += ['traffic/' + mix for mix in sorted({w['traffic'] for w in spec['workloads']})
                if not tiny_file('traffic', mix)]
    assert not missing, 'no tiny file under {} for {}'.format(TINY, missing)


def test_tiny_configs_keep_every_key():
    """A tiny file only changes sizes that the configuration or mix has."""
    spec = spec_with_extra_cells()
    for config in spec['configs']:
        tiny = tiny_file('configs', config['name'])
        if tiny:
            full = load_json(os.path.join(ROOT, config['file']))
            assert set(load_json(tiny)) <= set(full), tiny
    for mix_name in {w['traffic'] for w in spec['workloads']}:
        tiny = tiny_file('traffic', mix_name)
        if tiny:
            mix = harness.stores.load_mix(os.path.join(harness.BENCH_DIR, 'traffic'), mix_name)
            for block, values in load_json(tiny).items():
                assert set(values) <= set(mix[block]), (tiny, block)


def _named(entries, name):
    return next(e for e in entries if e['name'] == name)


def _tree_state(root):
    """Size and modification time of every file of the benchmark under ``root``."""
    state = {'BENCHMARK.json': os.stat(os.path.join(root, 'BENCHMARK.json'))}
    for d, dirs, files in os.walk(os.path.join(root, 'benchmarks')):
        dirs[:] = [x for x in dirs if x not in ('__pycache__', '.cache')]
        for f in files:
            path = os.path.join(d, f)
            state[os.path.relpath(path, root)] = os.stat(path)
    return {k: (st.st_size, st.st_mtime_ns) for k, st in state.items()}


def test_new_config_needs_new_files_only(tmp_path):
    """A later configuration is its file (and module) beside the others, its tiny
    file and entries in BENCHMARK.json: no existing file changes, and its cell is
    rehearsed and checked. Here a copy of the LM's configuration under another name."""
    before = _tree_state(ROOT)
    checkout = str(tmp_path / 'checkout')
    os.makedirs(checkout)
    _benchmark_copy(checkout)
    source = 'cerebras_gpt_1p3b'
    for kind in ('benchmarks/configs', TINY + '/configs'):
        shutil.copy(os.path.join(ROOT, kind, source + '.json'),
                    os.path.join(checkout, kind, 'throwaway_lm.json'))
    spec = harness.load_spec(checkout)
    config = dict(_named(spec['configs'], source), name='throwaway_lm',
                  file='benchmarks/configs/throwaway_lm.json')
    spec['configs'].append(config)
    cell_name = 'throwaway.tokens_stream'
    spec['workloads'].append({'name': cell_name, 'config': 'throwaway_lm',
                              'traffic': 'tokens_stream', 'chips': 1,
                              'why': 'a throwaway configuration'})
    unlisted = harness.Cell(spec, cell_name, root=checkout)
    assert 'tokens_per_s' not in {m['name'] for m in unlisted.end_to_end}
    _named(spec['end_to_end'], 'tokens_per_s')['workloads'].append(cell_name)
    with open(os.path.join(checkout, 'BENCHMARK.json'), 'w') as f:
        json.dump(spec, f)

    assert cell_name in [w['name'] for w in rehearsed_cells(checkout)]
    rig = tiny_rig(str(tmp_path / 'tiny'), checkout)
    cell = rig(cell_name)
    # in float32 the tiny program reads round-off against the reference
    cell.cfg = dict(cell.cfg, compute_dtype='float32')
    result = harness.run(cell, 19, 0.5, cache_root=str(tmp_path / 'cache'))
    assert result['attempted'] > 0 and 'tokens_per_s' in result['metrics']
    assert result['correct'], result['checks']
    assert _tree_state(ROOT) == before
