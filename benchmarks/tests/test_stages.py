"""The stage-span readers on a synthetic record, and the producer's stages over
idle gaps on synthetic per-thread events."""
import os

import pytest

from benchmarks import harness, stages

METRICS = os.path.join(harness.BENCH_DIR, 'metrics')


def _read(name, run):
    return harness.load_module(os.path.join(METRICS, name + '.py'), 'm_' + name).read(run)


def _run(**stage_s):
    return {'work_unit': 'rows', 'window_s': 10.0, 'workers': 4,
            'stage_s': {k: {'s': v, 'count': 1} for k, v in stage_s.items()}}


def test_stage_window_is_the_difference_of_two_snapshots():
    before = {'histograms': {'pool_wait': {'sum': 1.0, 'count': 3}}}
    after = {'histograms': {'pool_wait': {'sum': 4.5, 'count': 10},
                            'consume': {'sum': 0.25, 'count': 7}}}
    assert stages.stage_window(before, after) == {
        'pool_wait': {'s': 3.5, 'count': 7}, 'consume': {'s': 0.25, 'count': 7}}


def test_share_readers_on_a_synthetic_record():
    run = _run(pool_wait=3.0, shm_map=0.5, consume=0.25, rebatch=1.0, collate=0.25,
               h2d=1.0, put_wait=4.0, decode=8.0, rowgroup_read=2.0, serialize=1.0,
               cache_miss=10.0, shm_slot_wait=5.0)
    assert _read('loader_busy_share.rows', run) == pytest.approx(30.0)
    assert _read('pool_wait_share.rows', run) == pytest.approx(30.0)
    # (8 + 2 + 1) s of worker stages over 4 workers x 10 s
    assert _read('worker_busy_share.rows', run) == pytest.approx(27.5)


@pytest.mark.parametrize('run', [
    {'work_unit': 'rows', 'window_s': 10.0},  # a program without stage spans
    dict(_run(pool_wait=1.0), work_unit='tokens'),
    dict(_run(pool_wait=1.0), stage_s={}),
])
def test_share_readers_read_nothing_where_there_is_nothing(run):
    for name in ('loader_busy_share.rows', 'pool_wait_share.rows',
                 'worker_busy_share.rows'):
        assert _read(name, run) is None


def _ms(x):
    return int(x * 1e6)


def test_idle_gap_stages_names_the_producer_stages_over_each_gap():
    # the device runs [0, 10) and [30, 35) ms of a 0-50 ms window: gaps are
    # [10, 30) (20 ms), [35, 50) (15 ms)
    device_ops = {0: [('op', _ms(0), _ms(10)), ('op', _ms(30), _ms(35))]}
    host = [('bench.window', _ms(0), _ms(50)), ('bench.wait_step', _ms(10), _ms(30)),
            ('bench.next_batch', _ms(35), _ms(50))]
    producer = [('petastorm_tpu.pool_wait', _ms(5), _ms(18)),
                ('petastorm_tpu.shm_map', _ms(18), _ms(20)),
                ('petastorm_tpu.loader.rebatch', _ms(20), _ms(26)),
                ('petastorm_tpu.loader.h2d', _ms(26), _ms(40)),
                ('petastorm_tpu.loader.put_wait', _ms(40), _ms(60))]
    consumer = [('petastorm_tpu.loader.wait_input', _ms(35), _ms(50))]
    host += consumer + producer
    out = stages.idle_gap_stages(device_ops, host, [consumer, producer])
    assert [(name, round(s, 6)) for name, s, _ in out] == [('wait_step', 0.02),
                                                           ('wait_input', 0.015)]
    assert out[0][2] == pytest.approx({'pool_wait': 0.008, 'shm_map': 0.002,
                                       'rebatch': 0.006, 'h2d': 0.004})
    assert out[1][2] == pytest.approx({'h2d': 0.005, 'put_wait': 0.01})


def test_idle_gap_stages_is_empty_without_a_producer_line():
    device_ops = {0: [('op', _ms(0), _ms(10))]}
    host = [('bench.window', _ms(0), _ms(50))]
    consumer = [('petastorm_tpu.loader.wait_input', _ms(10), _ms(50))]
    assert stages.idle_gap_stages(device_ops, host, [consumer]) == []
