"""Zero-copy data plane tests (ISSUE 2): shm ring transport + mmap Arrow-IPC cache.

Covers both pillars and their failure modes:

- ``workers/shm_ring.py`` units: slot write/view/release, too-big and slot-exhaustion
  fallbacks, descriptor wire format;
- ``ArrowIpcDiskCache``: zero-copy mmap hits, pickle-record fallback, concurrency
  (two fillers of one key race-free via atomic rename; eviction under concurrent
  hits), format interop with the shared wire codec;
- process-pool integration under the ``faultinject`` marker: a worker SIGKILL-ed
  mid-epoch while the shm transport is live — the epoch completes through respawn,
  and ``join()`` leaves NO leaked ``/dev/shm`` segment;
- ``wire_bench`` smoke (the acceptance numbers are emitted, cold vs warm cache
  epoch shows hits).
"""

import glob
import os
import threading

import numpy as np
import pytest

from petastorm_tpu.cache import ArrowIpcDiskCache, LocalDiskCache
from petastorm_tpu.workers.shm_ring import (ShmRing, ShmRingWriter,
                                            ShmSlotDescriptor)


def _shm_segments():
    return [name for name in os.listdir('/dev/shm') if name.startswith('ptpu-ring-')]


# ---------------------------------------------------------------------------
# shm ring units
# ---------------------------------------------------------------------------

class TestShmRing(object):
    def test_write_view_roundtrip(self):
        ring = ShmRing(workers_count=2, slots_per_worker=2, slot_bytes=4096)
        try:
            writer = ShmRingWriter(ring.name, worker_slot=1, generation=0,
                                   slots_per_worker=2, slot_bytes=4096,
                                   data_offset=ring.data_offset)
            frames = [b'A', b'x' * 1000, b'sidecar']
            descriptor = writer.try_write(frames)
            assert descriptor is not None
            assert descriptor.worker_slot == 1
            # descriptor survives its wire encoding
            descriptor = ShmSlotDescriptor.from_bytes(descriptor.to_bytes())
            views = ring.view(descriptor)
            assert [bytes(v) for v in views] == frames
            for v in views:
                v.release()
            writer.close()
        finally:
            ring.close_and_unlink()
        assert ring.name not in _shm_segments()

    def test_slot_exhaustion_then_release(self):
        ring = ShmRing(workers_count=1, slots_per_worker=2, slot_bytes=4096)
        try:
            writer = ShmRingWriter(ring.name, 0, 0, 2, 4096,
                                   data_offset=ring.data_offset)
            d1 = writer.try_write([b'one'])
            d2 = writer.try_write([b'two'])
            assert d1 is not None and d2 is not None
            assert writer.try_write([b'three']) is None  # backpressure
            writer.release(d1.ring_slot)
            assert writer.try_write([b'three']) is not None
            writer.close()
        finally:
            ring.close_and_unlink()

    def test_oversized_payload_rejected(self):
        ring = ShmRing(workers_count=1, slots_per_worker=1, slot_bytes=2048)
        try:
            writer = ShmRingWriter(ring.name, 0, 0, 1, 2048,
                                   data_offset=ring.data_offset)
            assert not writer.fits([b'x' * 4096])
            assert writer.try_write([b'x' * 4096]) is None
            writer.close()
        finally:
            ring.close_and_unlink()

    def test_release_outside_partition_ignored(self):
        ring = ShmRing(workers_count=2, slots_per_worker=2, slot_bytes=2048)
        try:
            writer = ShmRingWriter(ring.name, 0, 0, 2, 2048,
                                   data_offset=ring.data_offset)
            writer.release(3)  # worker 1's slot: not ours
            assert writer.free_slots == 2
            writer.close()
        finally:
            ring.close_and_unlink()

    def test_unlink_is_idempotent(self):
        ring = ShmRing(workers_count=1, slots_per_worker=1, slot_bytes=2048)
        ring.close_and_unlink()
        ring.close_and_unlink()
        assert ring.name not in _shm_segments()


# ---------------------------------------------------------------------------
# Arrow-IPC mmap cache
# ---------------------------------------------------------------------------

class TestArrowIpcDiskCache(object):
    def _columns(self):
        return {
            'scalar': np.arange(10, dtype=np.int64),
            'image': np.arange(10 * 4 * 3, dtype=np.uint8).reshape(10, 4, 3),
            'strings': np.array(['s{}'.format(i) for i in range(10)], dtype=object),
            'ragged': [np.arange(i + 1, dtype=np.int32) for i in range(10)],
        }

    def test_columnar_roundtrip_zero_copy_hit(self, tmp_path):
        cache = ArrowIpcDiskCache(str(tmp_path / 'c'), 64 << 20)
        source = self._columns()
        filled = cache.get('k', lambda: source)
        assert filled is source  # miss returns the fill value itself
        hit = cache.get('k', lambda: pytest.fail('must not refill'))
        np.testing.assert_array_equal(hit['scalar'], source['scalar'])
        np.testing.assert_array_equal(hit['image'], source['image'])
        np.testing.assert_array_equal(hit['strings'], source['strings'])
        for got, want in zip(hit['ragged'], source['ragged']):
            np.testing.assert_array_equal(got, want)
        # numeric hits are mmap views: no private copy of the data
        assert not hit['scalar'].flags.owndata
        assert not hit['scalar'].flags.writeable
        assert cache.stats['hits'] == 1
        assert cache.stats['misses'] == 1
        assert cache.stats['arrow_hits'] == 1
        assert cache.stats['bytes_mmapped'] > 0

    def test_non_columnar_value_pickle_record(self, tmp_path):
        cache = ArrowIpcDiskCache(str(tmp_path / 'c'), 1 << 20)
        value = ['not', {'a': 'columns'}, 3]
        assert cache.get('k', lambda: value) == value
        assert cache.get('k', lambda: None) == value
        assert cache.stats['pickle_hits'] == 1

    def test_empty_columns_roundtrip(self, tmp_path):
        cache = ArrowIpcDiskCache(str(tmp_path / 'c'), 1 << 20)
        cache.get('k', lambda: {'a': np.zeros((0, 3), dtype=np.float32)})
        hit = cache.get('k', lambda: pytest.fail('must not refill'))
        assert hit['a'].shape == (0, 3)

    @pytest.mark.parametrize('cache_cls', [LocalDiskCache, ArrowIpcDiskCache])
    def test_concurrent_fillers_race_free(self, tmp_path, cache_cls):
        """Two readers filling the same key concurrently: atomic rename means every
        reader sees either a complete entry or a miss — never a torn file."""
        cache = cache_cls(str(tmp_path / 'c'), 64 << 20)
        barrier = threading.Barrier(2)
        results, errors = [], []

        def fill():
            barrier.wait()
            return {'a': np.arange(1000, dtype=np.int64)}

        def run():
            try:
                results.append(cache.get('shared-key', fill))
            except Exception as exc:  # noqa: BLE001 - the test asserts none happen
                errors.append(exc)

        threads = [threading.Thread(target=run) for _ in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errors
        assert len(results) == 2
        for value in results:
            np.testing.assert_array_equal(value['a'], np.arange(1000))
        # and a later reader hits the (single, complete) stored entry
        hit = cache.get('shared-key', lambda: pytest.fail('must hit'))
        np.testing.assert_array_equal(hit['a'], np.arange(1000))

    @pytest.mark.parametrize('cache_cls', [LocalDiskCache, ArrowIpcDiskCache])
    def test_eviction_under_concurrent_hits(self, tmp_path, cache_cls):
        """Readers hammering hot keys while writers push the cache over its limit:
        no exceptions, size stays bounded, hot reads stay correct (an evicted-
        mid-read entry degrades to a refill, never to an error)."""
        cache = cache_cls(str(tmp_path / 'c'), size_limit_bytes=300_000)
        stop = threading.Event()
        errors = []

        def reader():
            while not stop.is_set():
                try:
                    value = cache.get('hot', lambda: {'v': np.full(2000, 7, np.int64)})
                    assert int(np.asarray(value['v'])[0]) == 7
                except Exception as exc:  # noqa: BLE001
                    errors.append(exc)
                    return

        threads = [threading.Thread(target=reader) for _ in range(2)]
        for t in threads:
            t.start()
        try:
            for i in range(30):
                cache.get('cold-{}'.format(i),
                          lambda i=i: {'v': np.full(4000, i, np.int64)})
        finally:
            stop.set()
            for t in threads:
                t.join()
        assert not errors
        assert cache.size <= 300_000

    def test_shared_dir_eviction_covers_both_formats(self, tmp_path):
        """A pickle cache evicts .arrow entries too (shared cache_location)."""
        path = str(tmp_path / 'c')
        ArrowIpcDiskCache(path, 10 << 20).get('a', lambda: {'v': np.arange(64)})
        pickle_cache = LocalDiskCache(path, 10 << 20)
        assert pickle_cache.size > 0  # .arrow entry visible to the scan


# ---------------------------------------------------------------------------
# reader integration: cache_format knob + diagnostics
# ---------------------------------------------------------------------------

def _write_store(root, num_rows=48, n_files=4, vec_len=8):
    from petastorm_tpu.codecs import NdarrayCodec, ScalarCodec
    from petastorm_tpu.etl.dataset_metadata import write_rows
    from petastorm_tpu.unischema import Unischema, UnischemaField
    schema = Unischema('ZeroCopyProbe', [
        UnischemaField('id', np.int64, (), ScalarCodec(), False),
        UnischemaField('vec', np.float32, (vec_len,), NdarrayCodec(), False),
    ])
    url = 'file://' + str(root)
    write_rows(url, schema,
               [{'id': i, 'vec': np.full(vec_len, i, np.float32)}
                for i in range(num_rows)],
               n_files=n_files, rowgroup_size_mb=1)
    return url


@pytest.mark.parametrize('cache_format', ['arrow-ipc', 'pickle'])
def test_reader_cache_format_warm_epoch_hits(tmp_path, cache_format):
    from petastorm_tpu import make_reader

    url = _write_store(tmp_path / 'store')

    def read_epoch():
        reader = make_reader(url, reader_pool_type='dummy', num_epochs=1,
                             shuffle_row_groups=False, cache_type='local-disk',
                             cache_location=str(tmp_path / 'cache'),
                             cache_size_limit=64 << 20, cache_format=cache_format)
        ids = sorted(int(row.id) for row in reader)
        diag = reader.diagnostics
        reader.stop()
        reader.join()
        return ids, diag

    cold_ids, cold_diag = read_epoch()
    warm_ids, warm_diag = read_epoch()
    assert cold_ids == warm_ids == list(range(48))
    assert cold_diag['cache_misses'] > 0 and cold_diag['cache_hits'] == 0
    assert warm_diag['cache_hits'] == cold_diag['cache_misses']
    assert warm_diag['cache_misses'] == 0
    if cache_format == 'arrow-ipc':
        assert warm_diag['cache']['arrow_hits'] > 0
        assert warm_diag['cache']['bytes_mmapped'] > 0


def test_warm_cache_hit_with_inplace_transform_stays_writable(tmp_path):
    """Regression: arrow-ipc hits are read-only mmap views, but a transform_spec
    may mutate in place — make_reader must decode hits writable in that case, so
    a transform that worked on the cold epoch doesn't crash on the warm one."""
    from petastorm_tpu import make_reader
    from petastorm_tpu.transform import TransformSpec

    url = _write_store(tmp_path / 'store', num_rows=16, n_files=2)

    def double_in_place(row):
        row['vec'] *= 2  # in-place: raises on a read-only array
        return row

    def read_epoch():
        reader = make_reader(url, reader_pool_type='dummy', num_epochs=1,
                             shuffle_row_groups=False, cache_type='local-disk',
                             cache_location=str(tmp_path / 'cache'),
                             cache_size_limit=64 << 20,
                             transform_spec=TransformSpec(double_in_place))
        rows = {int(r.id): np.asarray(r.vec) for r in reader}
        reader.stop()
        reader.join()
        return rows

    cold = read_epoch()
    warm = read_epoch()  # crashed with ValueError('read-only') before the fix
    np.testing.assert_array_equal(cold[3], np.full(8, 6, np.float32))
    np.testing.assert_array_equal(warm[3], np.full(8, 6, np.float32))


def test_reader_rejects_unknown_cache_format(tmp_path):
    from petastorm_tpu import make_reader
    url = _write_store(tmp_path / 'store', num_rows=8, n_files=1)
    with pytest.raises(ValueError, match='cache_format'):
        make_reader(url, cache_type='local-disk',
                    cache_location=str(tmp_path / 'cache'),
                    cache_size_limit=1 << 20, cache_format='msgpack')


# ---------------------------------------------------------------------------
# serializer sidecar-degradation counter (ISSUE 2 satellite)
# ---------------------------------------------------------------------------

def test_sidecar_columns_counted_on_receive():
    from petastorm_tpu.reader_worker import ColumnarBatch
    from petastorm_tpu.workers.serializers import ArrowIpcSerializer
    serializer = ArrowIpcSerializer()
    batch = ColumnarBatch({
        'dense': np.arange(6, dtype=np.float32),
        'names': np.array(['a', 'b', 'c', 'd', 'e', 'f'], dtype=object),
        'ragged': [np.arange(i + 1) for i in range(6)],
    }, 6, item_id=(0, 0, 0))
    for _ in range(3):
        frames = serializer.serialize(batch)
        serializer.deserialize([bytes(memoryview(f)) for f in frames])
    assert serializer.stats['batches'] == 3
    assert serializer.stats['sidecar_columns'] == 6  # 2 columns x 3 batches
    assert sorted(serializer.stats['sidecar_column_names']) == ['names', 'ragged']
    assert serializer.stats['bytes_copied'] > 0


# ---------------------------------------------------------------------------
# process pool + shm transport (faultinject: tier-1)
# ---------------------------------------------------------------------------

@pytest.mark.faultinject
def test_shm_transport_survives_worker_kill_no_segment_leak(tmp_path):
    """Acceptance (ISSUE 2): a worker SIGKILL-ed mid-epoch while the shm transport
    is live — its in-flight slot state is reclaimed through the respawn path
    (generation-stale descriptors dropped, replacement starts all-free), the epoch
    completes with every row exactly once, and after ``join()`` no petastorm_tpu
    segment is left in /dev/shm."""
    from petastorm_tpu import make_reader
    from petastorm_tpu.test_util.fault_injection import (FaultRule, FaultSchedule,
                                                         fault_injecting_filesystem)

    url = _write_store(tmp_path / 'store', num_rows=64, n_files=8)
    target = os.path.basename(sorted(glob.glob(
        os.path.join(str(tmp_path / 'store'), '**', '*.parquet'),
        recursive=True))[3])
    sched = FaultSchedule(tmp_path / 'faults',
                          [FaultRule(target, kind='kill', times=1)])
    with make_reader(url, reader_pool_type='process', workers_count=2, num_epochs=1,
                     shuffle_row_groups=False, shm_transport=True,
                     filesystem=fault_injecting_filesystem(sched)) as reader:
        ids = sorted(int(row.id) for row in reader)
        diag = reader.diagnostics
        ring = reader._pool._ring.name
    assert ids == list(range(64)), 'rows dropped or duplicated across the respawn'
    assert diag['workers_respawned'] == 1
    assert diag['shm_enabled'] and diag['shm_batches'] > 0
    # this pool's segment only: tests in other xdist workers hold rings of their own
    assert ring not in _shm_segments(), 'leaked /dev/shm segment after join()'


@pytest.mark.slow
def test_shm_transport_end_to_end_counters(tmp_path):
    """Fault-free shm epoch: every result batch rides the ring (no fallbacks), the
    bytes-copied counter stays below the mapped payload bytes, and decoded rows
    match the store."""
    from petastorm_tpu import make_reader

    url = _write_store(tmp_path / 'store', num_rows=64, n_files=4)
    with make_reader(url, reader_pool_type='process', workers_count=2,
                     num_epochs=1, shuffle_row_groups=False,
                     shm_transport=True) as reader:
        rows = {int(row.id): np.asarray(row.vec) for row in reader}
        diag = reader.diagnostics
    assert sorted(rows) == list(range(64))
    np.testing.assert_array_equal(rows[5], np.full(8, 5, np.float32))
    assert diag['shm_batches'] > 0
    assert diag['shm_fallback_batches'] == 0
    assert diag['wire_bytes_copied'] < diag['shm_bytes_mapped'] * 2


@pytest.mark.slow
def test_shm_oversized_batch_falls_back_to_zmq(tmp_path):
    """A payload larger than the slot forces the per-batch ZMQ fallback — rows
    still arrive, and the fallback is visible in diagnostics."""
    from petastorm_tpu import make_reader
    from petastorm_tpu.workers.process_pool import ProcessPool

    url = _write_store(tmp_path / 'store', num_rows=32, n_files=2, vec_len=256)
    pool = ProcessPool(2, shm_transport=True, shm_slot_bytes=2048)
    with make_reader(url, reader_pool=pool, shuffle_row_groups=False,
                     num_epochs=1) as reader:
        ids = sorted(int(row.id) for row in reader)
        diag = pool.diagnostics
    assert ids == list(range(32))
    assert diag['shm_fallback_batches'] > 0


# ---------------------------------------------------------------------------
# wire_bench smoke
# ---------------------------------------------------------------------------

def test_wire_bench_fast_sections(tmp_path):
    from petastorm_tpu.benchmark.wire_bench import run_wire_bench
    result = run_wire_bench(rows=64, cols=2, include_transport=False,
                            cache_rows=40)
    assert result['roundtrip_pickle_mb_s'] > 0
    assert result['roundtrip_arrow_mb_s'] > 0
    assert result['cache_cold_hits'] == 0
    assert result['cache_warm_hits'] > 0
    assert result['cache_warm_speedup'] > 0


@pytest.mark.slow
def test_wire_bench_transport_acceptance(tmp_path):
    """The ISSUE-2 acceptance numbers: shm cuts bytes-copied-per-batch >= 2x vs
    the ZMQ/pickle path (measured from pool counters, not claimed)."""
    from petastorm_tpu.benchmark.wire_bench import transport_bench
    result = transport_bench(rows=2048, cols=4, batches=12, workers=2)
    assert result['arrow_shm_shm_batches'] == 12
    assert result['copy_reduction_vs_pickle_zmq'] >= 2.0


# ---------------------------------------------------------------------------
# Frame integrity + heartbeat words (ISSUE 4)
# ---------------------------------------------------------------------------

class TestRingIntegrity(object):
    def test_descriptor_carries_verifiable_crc(self):
        from petastorm_tpu.workers.integrity import payload_checksum
        ring = ShmRing(workers_count=1, slots_per_worker=1, slot_bytes=4096)
        try:
            writer = ShmRingWriter(ring.name, 0, 0, 1, 4096,
                                   data_offset=ring.data_offset)
            descriptor = writer.try_write([b'A', b'payload' * 64, b'sidecar'])
            descriptor = ShmSlotDescriptor.from_bytes(descriptor.to_bytes())
            assert descriptor.crc is not None
            views = ring.view(descriptor)
            assert payload_checksum(views) == descriptor.crc
            # a single flipped byte in the slot must break the match
            views[1][10] = views[1][10] ^ 0xFF
            assert payload_checksum(ring.view(descriptor)) != descriptor.crc
            for v in views:
                v.release()
            writer.close()
        finally:
            ring.close_and_unlink()

    def test_heartbeat_word_roundtrip_per_worker(self):
        ring = ShmRing(workers_count=2, slots_per_worker=1, slot_bytes=4096)
        try:
            writer = ShmRingWriter(ring.name, 1, 0, 1, 4096,
                                   data_offset=ring.data_offset)
            assert ring.heartbeat(0) == 0 and ring.heartbeat(1) == 0
            writer.stamp_heartbeat(41)
            writer.stamp_heartbeat(42)
            assert ring.heartbeat(1) == 42
            assert ring.heartbeat(0) == 0, 'heartbeat words must not alias'
            writer.close()
        finally:
            ring.close_and_unlink()
