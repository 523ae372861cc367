"""chip_smoke.py on the CPU: it refuses to run there, and each of its phases works
at a tiny size when a test calls it directly (the chip run uses the full sizes).
Also the compile-cache helper chip_smoke.py and bench.py share."""
import importlib.util
import os
import subprocess
import sys

import jax
import pytest

from petastorm_tpu.benchmark import compile_cache

REPO = os.path.join(os.path.dirname(os.path.abspath(__file__)), '..')


@pytest.fixture(scope='module')
def chip_smoke():
    spec = importlib.util.spec_from_file_location(
        'chip_smoke', os.path.join(REPO, 'chip_smoke.py'))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def test_refuses_cpu_before_any_phase(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS='cpu')
    out = subprocess.run([sys.executable, os.path.join(REPO, 'chip_smoke.py'),
                          '--data-dir', str(tmp_path / 'data')],
                         capture_output=True, text=True, env=env, timeout=240)
    assert out.returncode != 0
    assert out.stdout.strip() == ''
    assert 'TPU only' in out.stderr
    assert not (tmp_path / 'data').exists()


TINY = dict(image_hw=32, resnet_stages=(1, 1, 1, 1), resnet_filters=8,
            resnet_rows_per_class=16, resnet_batch=8, resnet_steps=2, workers=2,
            dct_rows_per_class=8, dct_batch=8, vector_width=64, mnist_rows=512,
            mnist_batch=64, flash_t=256, flash_batch=1, flash_embed=256, flash_heads=2,
            flash_layers=1, flash_steps=1, flash_segmented_t=256, dp_rows_per_class=12,
            dp_batch=8, dp_steps=2)


@pytest.mark.parametrize('phase', ['resnet_stream', 'device_decode', 'mnist_inmem',
                                   'flash', 'resnet_dp'])
def test_phase_runs_at_tiny_size(chip_smoke, phase, tmp_path, monkeypatch):
    # the CPU backend decodes device fields on the host unless forced
    monkeypatch.setenv('PETASTORM_TPU_DEVICE_DECODE_FORCE', '1')
    sizes = chip_smoke.Sizes(**TINY)
    data_dir = str(tmp_path)
    if phase == 'resnet_stream':
        fields = chip_smoke.phase_resnet_stream(sizes, data_dir, 0)
        assert fields['first_loss'] == pytest.approx(fields['cpu_reference_loss'], rel=2e-2)
        assert len(fields['losses']) == 1 + sizes.resnet_steps
    elif phase == 'device_decode':
        fields = chip_smoke.phase_device_decode(sizes, data_dir, 0)
        assert fields['recipes'] == ['dct', 'stored']
    elif phase == 'mnist_inmem':
        fields = chip_smoke.phase_mnist_inmem(sizes, data_dir, 0)
        assert fields['rows_per_epoch'] == 512
    elif phase == 'flash':
        fields = chip_smoke.phase_flash(sizes, 0)
        for name in ('flash', 'segmented'):
            assert max(fields[name + '_rel_l2_vs_dense'].values()) < 1e-4
    else:
        fields = chip_smoke.phase_resnet_dp(sizes, data_dir, 0, jax.devices()[:4])
        assert fields['shards_per_leaf'] == 4
        assert fields['update_rel_l2_vs_one_device'] < 0.1
    assert all(v is None or v == v for v in fields.values()
               if not isinstance(v, (dict, list)))  # no NaN reported


def test_compile_cache_honors_env_and_sets_nothing(monkeypatch, tmp_path):
    monkeypatch.setenv('JAX_COMPILATION_CACHE_DIR', str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.configure_compile_cache('tpu') == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_fixed_repo_path_otherwise(monkeypatch):
    monkeypatch.delenv('JAX_COMPILATION_CACHE_DIR', raising=False)
    assert compile_cache.configure_compile_cache('cpu') is None
    before = jax.config.jax_compilation_cache_dir
    try:
        path = compile_cache.configure_compile_cache('tpu')
        assert path == compile_cache.REPO_CACHE_DIR
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update('jax_compilation_cache_dir', before)
    assert os.path.samefile(os.path.dirname(path), REPO)
    assert os.path.basename(path) == '.jax_cache'
