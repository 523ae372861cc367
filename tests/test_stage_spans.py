"""Stage spans on the producer thread: coverage, the profiler's clock, worker
processes kept free of jax, and pool_wait kept apart from shm_map
(docs/observability.md "Profiler spans")."""

import glob
import os
import time

import numpy as np
import pytest

from petastorm_tpu.parallel.loader import JaxDataLoader
from petastorm_tpu.telemetry.spans import PROFILER_NAMES, STAGES

#: the stages the loader's producer thread runs, which never overlap
PRODUCER_STAGES = ('pool_wait', 'shm_map', 'wire_map', 'shm_release', 'consume',
                   'rebatch', 'collate', 'device_decode', 'h2d', 'd2d_wait',
                   'put_wait')


def _write_store(root, num_rows=160, n_files=8, vec_len=16):
    from petastorm_tpu.codecs import NdarrayCodec, ScalarCodec
    from petastorm_tpu.etl.dataset_metadata import write_rows
    from petastorm_tpu.unischema import Unischema, UnischemaField
    schema = Unischema('SpanProbe', [
        UnischemaField('id', np.int64, (), ScalarCodec(), False),
        UnischemaField('vec', np.float32, (vec_len,), NdarrayCodec(), False),
    ])
    url = 'file://' + str(root)
    write_rows(url, schema,
               [{'id': i, 'vec': np.full(vec_len, i, np.float32)}
                for i in range(num_rows)],
               n_files=n_files, rowgroup_size_mb=1)
    return url


class _TimedLoader(JaxDataLoader):
    """Records the producer thread's lifetime, from entering ``_produce`` to
    leaving it."""

    producer_s = None

    def _produce(self, out_queue, stop_event):
        start = time.perf_counter()
        try:
            super()._produce(out_queue, stop_event)
        finally:
            self.producer_s = time.perf_counter() - start


def _read_all(loader, pause_s=0.005):
    """Consume every batch, pausing like a train step so the prefetch queue
    fills; returns the rows."""
    rows = 0
    for batch in loader:
        rows += len(np.asarray(batch['id']))
        time.sleep(pause_s)
    return rows


def test_stage_catalog_names_the_producer_stages():
    for stage in PRODUCER_STAGES:
        assert stage in STAGES
    assert PROFILER_NAMES['shuffle_wait'] == 'petastorm_tpu.loader.wait_input'
    assert PROFILER_NAMES['h2d'] == 'petastorm_tpu.loader.h2d'
    assert PROFILER_NAMES['device_decode'] == 'petastorm_tpu.loader.device_decode'
    assert PROFILER_NAMES['rebatch'] == 'petastorm_tpu.loader.rebatch'
    assert PROFILER_NAMES['pool_wait'] == 'petastorm_tpu.pool_wait'
    assert PROFILER_NAMES['consume'] == 'petastorm_tpu.consume'


@pytest.mark.parametrize('pool', ['thread', 'process'])
def test_producer_stages_cover_the_producer_thread(tmp_path, pool):
    """Over 40 batches the producer's stage seconds add up to its lifetime:
    at least 90% of it is accounted for, and the stages never overlap."""
    from petastorm_tpu import make_reader

    url = _write_store(tmp_path / 'store')
    reader = make_reader(url, reader_pool_type=pool, workers_count=2,
                         num_epochs=1, shuffle_row_groups=False)
    loader = _TimedLoader(reader, batch_size=4)
    try:
        assert _read_all(loader) == 160
    finally:
        loader.stop()
        loader.join()
    hists = loader.telemetry_snapshot()['histograms']
    covered = sum(hists[s]['sum'] for s in PRODUCER_STAGES if s in hists)
    assert hists['rebatch']['count'] >= 40
    assert hists['put_wait']['count'] >= 40
    assert hists['consume']['count'] == 8
    assert 0.9 * loader.producer_s <= covered <= loader.producer_s
    if pool == 'process':
        assert hists['shm_map']['count'] + hists.get('wire_map', {}).get('count', 0) == 8


def test_stage_spans_land_on_the_producer_threads_profiler_line(tmp_path):
    """Under jax.profiler the producer's stage events sit on one host-plane
    line (its thread) under their profiler names; their summed durations match
    the registry's histogram sums, and the training loop's input wait sits on
    another line."""
    import jax
    from jax.profiler import ProfileData
    from petastorm_tpu import make_reader

    url = _write_store(tmp_path / 'store')
    reader = make_reader(url, reader_pool_type='thread', workers_count=2,
                         num_epochs=1, shuffle_row_groups=False)
    loader = JaxDataLoader(reader, batch_size=4)
    trace_dir = str(tmp_path / 'trace')
    jax.profiler.start_trace(trace_dir)
    try:
        assert _read_all(loader) == 160
    finally:
        loader.stop()
        loader.join()
        jax.profiler.stop_trace()
    path = glob.glob(os.path.join(trace_dir, '**', '*.xplane.pb'), recursive=True)[0]
    names = {PROFILER_NAMES[s]: s for s in PRODUCER_STAGES}
    lines = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith('/host:'):
            for line in plane.lines:
                events = [(e.name, e.duration_ns) for e in line.events
                          if e.name.startswith('petastorm_tpu.')]
                if events:
                    lines.append(events)
    producer = [events for events in lines
                if any(n == PROFILER_NAMES['rebatch'] for n, _ in events)]
    assert len(producer) == 1
    on_line = {}
    for name, duration_ns in producer[0]:
        if name in names:
            count, total = on_line.get(names[name], (0, 0.0))
            on_line[names[name]] = (count + 1, total + duration_ns / 1e9)
    hists = loader.telemetry_snapshot()['histograms']
    for stage in ('pool_wait', 'consume', 'rebatch', 'collate', 'h2d', 'put_wait'):
        assert on_line[stage][0] == hists[stage]['count'], stage
    traced = sum(total for _, total in on_line.values())
    registered = sum(hists[s]['sum'] for s in on_line)
    assert traced == pytest.approx(registered, rel=0.01)
    others = [name for events in lines if events is not producer[0]
              for name, _ in events]
    assert PROFILER_NAMES['shuffle_wait'] in others
    assert not set(others) & set(names)


def test_spans_leave_pool_worker_processes_free_of_jax():
    from petastorm_tpu.workers import EmptyResultError
    from petastorm_tpu.workers.process_pool import ProcessPool
    from petastorm_tpu.workers.ventilator import ConcurrentVentilator
    from _span_probe_worker import SpanProbeWorker

    pool = ProcessPool(2)
    ventilator = ConcurrentVentilator(pool.ventilate, [{'value': i} for i in range(4)])
    pool.start(SpanProbeWorker, {}, ventilator)
    results = []
    try:
        while True:
            try:
                results.append(pool.get_results())
            except EmptyResultError:
                break
    finally:
        pool.stop()
        pool.join()
    assert len(results) == 4
    for result in results:
        assert not result['before'] and not result['after']
        # the worker main's serialize span of the previous item rides along
        assert {'decode', 'rowgroup_read', 'transform'} <= set(result['recorded'])


def test_pool_wait_no_longer_contains_shm_map(tmp_path, monkeypatch):
    """pool_wait ends where mapping the slot begins: with a slow deserialize
    the two stages still add up to no more than the time spent reading."""
    from petastorm_tpu import make_reader

    url = _write_store(tmp_path / 'store', num_rows=64, n_files=8)
    with make_reader(url, reader_pool_type='process', workers_count=2,
                     num_epochs=1, shuffle_row_groups=False,
                     shm_transport=True) as reader:
        serializer = reader._pool._serializer
        deserialize = serializer.deserialize

        def slow_deserialize(frames):
            time.sleep(0.05)
            return deserialize(frames)

        monkeypatch.setattr(serializer, 'deserialize', slow_deserialize)
        start = time.perf_counter()
        rows = sum(batch.num_rows for batch in reader.iter_columnar())
        elapsed = time.perf_counter() - start
        hists = reader.telemetry_snapshot()['histograms']
    assert rows == 64
    assert hists['shm_map']['count'] == 8
    assert hists['shm_map']['sum'] >= 8 * 0.05
    assert hists['pool_wait']['sum'] + hists['shm_map']['sum'] <= elapsed


def test_stage_span_restarts_and_exposes_its_measurement():
    from petastorm_tpu.telemetry import MetricsRegistry
    from petastorm_tpu.telemetry.spans import stage_span

    registry = MetricsRegistry()
    span = stage_span('pool_wait', registry=registry)
    span.end()  # never begun: records nothing
    with span:
        time.sleep(0.002)
    first = span.seconds
    assert first >= 0.002 and span.end_s > span.start_s
    span.end()  # already ended: records nothing more
    span.begin()
    span.end()
    hist = registry.snapshot()['histograms']['pool_wait']
    assert hist['count'] == 2
    assert hist['sum'] == pytest.approx(first + span.seconds)
