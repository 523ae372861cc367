"""JaxDataLoader + mesh tests on the virtual 8-device CPU platform (SURVEY.md §4
'Implication for the TPU build': multi-host logic without hardware)."""

import numpy as np
import pytest

import jax
from jax.sharding import PartitionSpec

from petastorm_tpu import make_batch_reader, make_reader
from petastorm_tpu.parallel import JaxDataLoader, batch_sharding, make_mesh
from petastorm_tpu.parallel.mesh import distributed_shard_info


def test_virtual_devices_present():
    assert len(jax.devices()) == 8  # conftest forces 8 CPU devices


class TestMesh:
    def test_make_mesh_single_axis(self):
        mesh = make_mesh(('data',))
        assert mesh.shape == {'data': 8}

    def test_make_mesh_two_axes(self):
        mesh = make_mesh(('data', 'model'), (4, 2))
        assert mesh.shape == {'data': 4, 'model': 2}

    def test_make_mesh_bad_sizes(self):
        with pytest.raises(ValueError):
            make_mesh(('data',), (3,))

    def test_batch_sharding_default(self):
        mesh = make_mesh(('data',))
        sharding = batch_sharding(mesh)
        assert sharding.spec == PartitionSpec('data')

    def test_distributed_shard_info_explicit(self):
        assert distributed_shard_info(2, 4) == (2, 4)
        with pytest.raises(ValueError):
            distributed_shard_info(2, None)

    def test_distributed_shard_info_single_process(self):
        assert distributed_shard_info() == (None, None)


class TestLoader:
    def test_per_field_partition_spec(self, synthetic_dataset):
        """Dict partition_spec: named fields get their spec (e.g. sequence sharding),
        the rest the batch-axis default — rank-1 labels ride along with rank-2 data."""
        from petastorm_tpu import make_reader
        mesh = make_mesh(('data', 'seq'), (2, 4))
        with make_reader(synthetic_dataset.url, schema_fields=['id', 'matrix'],
                         workers_count=1) as reader:
            loader = JaxDataLoader(
                reader, batch_size=16, mesh=mesh,
                partition_spec={'matrix': PartitionSpec('data', 'seq')})
            batch = next(iter(loader))
            loader.stop()
        assert batch['matrix'].sharding.spec == PartitionSpec('data', 'seq')
        assert batch['id'].sharding.spec == PartitionSpec('data')

    def test_join_waits_for_producer_before_reader_join(self):
        """The producer thread may be inside the reader when the loader stops (a
        process pool polls zmq sockets, which are not thread-safe, and the race
        with the pool's own join crashed the process): join() lets the producer
        leave before the reader tears down."""
        import collections
        import threading
        import time
        row_type = collections.namedtuple('Row', ['x'])

        class BlockingReader:
            num_epochs = None

            def __init__(self):
                self.stopped = threading.Event()
                self.producer_alive_at_join = None

            def __iter__(self):
                for i in range(4):
                    yield row_type(np.float32(i))
                self.stopped.wait(10)  # waiting on workers, like a pool
                time.sleep(0.2)        # which notices stop() on its next poll
                raise RuntimeError('the worker pool was stopped')

            def stop(self):
                self.stopped.set()

            def join(self):
                self.producer_alive_at_join = loader._producer.is_alive()

        reader = BlockingReader()
        loader = JaxDataLoader(reader, batch_size=2)
        batches = iter(loader)
        next(batches)
        batches.close()
        loader.stop()
        loader.join()
        assert reader.producer_alive_at_join is False

    def test_batched_reader_to_device(self, scalar_dataset):
        mesh = make_mesh(('data',))
        with make_batch_reader(scalar_dataset.url, schema_fields=['id', 'float64'],
                               workers_count=2) as reader:
            loader = JaxDataLoader(reader, batch_size=16, mesh=mesh)
            batches = list(loader)
        assert batches, 'no batches emitted'
        for batch in batches:
            assert isinstance(batch['id'], jax.Array)
            assert batch['id'].shape == (16,)
            assert batch['id'].sharding.spec == PartitionSpec('data')
        ids = np.concatenate([np.asarray(b['id']) for b in batches])
        assert len(set(ids.tolist())) == len(ids)

    def test_row_reader_decoded_fields(self, synthetic_dataset):
        mesh = make_mesh(('data',))
        with make_reader(synthetic_dataset.url, schema_fields=['id', 'matrix'],
                         workers_count=2) as reader:
            loader = JaxDataLoader(reader, batch_size=8, mesh=mesh)
            batch = next(iter(loader))
        assert batch['matrix'].shape == (8, 4, 3)
        # values round-trip to device correctly
        host = np.asarray(batch['matrix'])
        ids = np.asarray(batch['id'])
        source = synthetic_dataset.rows_by_id[int(ids[0])]
        np.testing.assert_array_almost_equal(host[0], source['matrix'])

    def test_no_mesh_single_device(self, scalar_dataset):
        with make_batch_reader(scalar_dataset.url, schema_fields=['id'],
                               workers_count=1) as reader:
            loader = JaxDataLoader(reader, batch_size=10)
            batch = next(iter(loader))
        assert isinstance(batch['id'], jax.Array)

    def test_drop_last(self, scalar_dataset):
        # 50 rows, batch 16 -> 3 batches of 16, partial 2 dropped
        with make_batch_reader(scalar_dataset.url, schema_fields=['id'],
                               workers_count=1) as reader:
            loader = JaxDataLoader(reader, batch_size=16)
            batches = list(loader)
        assert len(batches) == 3

    def test_keep_last_partial(self, scalar_dataset):
        with make_batch_reader(scalar_dataset.url, schema_fields=['id'],
                               workers_count=1) as reader:
            loader = JaxDataLoader(reader, batch_size=16, drop_last=False)
            batches = list(loader)
        assert sum(b['id'].shape[0] for b in batches) == 50

    def test_string_field_rejected_with_name(self, scalar_dataset):
        with make_batch_reader(scalar_dataset.url, schema_fields=['id', 'string'],
                               workers_count=1) as reader:
            loader = JaxDataLoader(reader, batch_size=10)
            with pytest.raises(ValueError, match='string'):
                list(loader)

    def test_string_field_ok_host_mode(self, scalar_dataset):
        with make_batch_reader(scalar_dataset.url, schema_fields=['id', 'string'],
                               workers_count=1) as reader:
            loader = JaxDataLoader(reader, batch_size=10, device_put=False)
            batch = next(iter(loader))
        assert batch['string'][0].startswith('value_')

    def test_ragged_requires_pad(self, synthetic_dataset):
        with make_reader(synthetic_dataset.url, schema_fields=['id', 'matrix_var'],
                         workers_count=1) as reader:
            loader = JaxDataLoader(reader, batch_size=8)
            with pytest.raises(ValueError, match='pad_ragged'):
                list(loader)

    def test_pad_ragged_emits_padded_and_lengths(self, synthetic_dataset):
        with make_reader(synthetic_dataset.url, schema_fields=['id', 'matrix_var'],
                         workers_count=1) as reader:
            loader = JaxDataLoader(reader, batch_size=8,
                                   pad_ragged={'matrix_var': (10, 2)})
            batch = next(iter(loader))
        assert batch['matrix_var'].shape == (8, 10, 2)
        assert batch['matrix_var_len'].shape == (8,)
        lengths = np.asarray(batch['matrix_var_len'])
        ids = np.asarray(batch['id'])
        source = synthetic_dataset.rows_by_id[int(ids[0])]['matrix_var']
        assert lengths[0] == source.shape[0]
        np.testing.assert_array_equal(np.asarray(batch['matrix_var'])[0, :lengths[0]],
                                      source)

    def test_shuffling_buffer_changes_order(self, scalar_dataset):
        def read_ids(shuffle_capacity):
            with make_batch_reader(scalar_dataset.url, schema_fields=['id'],
                                   shuffle_row_groups=False, workers_count=1) as reader:
                loader = JaxDataLoader(reader, batch_size=10,
                                       shuffling_queue_capacity=shuffle_capacity,
                                       seed=3, drop_last=False)
                return np.concatenate([np.asarray(b['id']) for b in loader]).tolist()
        ordered = read_ids(0)
        shuffled = read_ids(30)
        assert sorted(ordered) == sorted(shuffled)
        assert ordered != shuffled

    def test_stats_collected(self, scalar_dataset):
        with make_batch_reader(scalar_dataset.url, schema_fields=['id'],
                               workers_count=1) as reader:
            loader = JaxDataLoader(reader, batch_size=10)
            list(loader)
        stats = loader.stats.as_dict()
        assert stats['batches'] == 5
        assert stats['rows'] == 50
        assert 0.0 <= stats['input_stall_fraction'] <= 1.0

    def test_stall_metric_directional_sanity(self, synthetic_dataset):
        """The north-star input-stall metric must move the right way (VERDICT r1 item
        10 — a CI smoke so the metric can't silently rot between TPU runs): a slow
        PRODUCER (sleeping transform) shows high stall; a slow CONSUMER (sleeping
        between batches) shows low stall. Margins are wide to stay robust on 1 CPU."""
        import time as _time
        from petastorm_tpu.transform import TransformSpec

        def slow_producer_stall():
            slow = TransformSpec(lambda row: (_time.sleep(0.05), row)[1])
            with make_reader(synthetic_dataset.url, schema_fields=['id'],
                             transform_spec=slow, workers_count=1,
                             shuffle_row_groups=False) as reader:
                loader = JaxDataLoader(reader, batch_size=25, device_put=False)
                list(loader)
            return loader.stats.input_stall_fraction

        def slow_consumer_stall():
            with make_reader(synthetic_dataset.url, schema_fields=['id'],
                             workers_count=1, shuffle_row_groups=False) as reader:
                loader = JaxDataLoader(reader, batch_size=25, device_put=False,
                                       prefetch=2)
                for _ in loader:
                    _time.sleep(0.08)
            return loader.stats.input_stall_fraction

        producer_bound = slow_producer_stall()
        consumer_bound = slow_consumer_stall()
        assert 0.0 <= consumer_bound <= 1.0 and 0.0 <= producer_bound <= 1.0
        assert producer_bound > consumer_bound + 0.2, \
            'input-bound run must report much higher stall than compute-bound run'

    def test_reader_pool_with_pool_shape_args_warns(self, scalar_dataset, synthetic_dataset):
        import warnings as _warnings
        from petastorm_tpu.workers.thread_pool import ThreadPool
        pool = ThreadPool(2, 10)
        with pytest.warns(UserWarning, match='ignoring pool-shape'):
            reader = make_reader(synthetic_dataset.url, reader_pool=pool,
                                 workers_count=3)
        reader.stop()
        reader.join()
        # no warning when only reader_pool is given
        pool2 = ThreadPool(2, 10)
        with _warnings.catch_warnings():
            _warnings.simplefilter('error')
            reader = make_reader(synthetic_dataset.url, reader_pool=pool2)
        reader.stop()
        reader.join()

    def test_reiteration_after_early_break(self, scalar_dataset):
        """Breaking mid-epoch then re-iterating must not leak the old producer's batches
        into the new iteration."""
        with make_batch_reader(scalar_dataset.url, schema_fields=['id'],
                               workers_count=1, num_epochs=None) as reader:
            loader = JaxDataLoader(reader, batch_size=10, prefetch=2)
            for batch in loader:
                break  # abandon the epoch mid-way (closes the generator)
            seen = []
            for i, batch in enumerate(iter(loader)):
                seen.append(np.asarray(batch['id']))
                if i == 4:
                    break
            assert all(len(b) == 10 for b in seen)
        loader.stop()

    def test_reiteration_resets_reader(self, scalar_dataset):
        with make_batch_reader(scalar_dataset.url, schema_fields=['id'],
                               workers_count=1) as reader:
            loader = JaxDataLoader(reader, batch_size=25)
            first = list(loader)
            second = list(loader)
        assert len(first) == len(second) == 2

    def test_error_propagates_from_producer(self, synthetic_dataset):
        from petastorm_tpu.transform import TransformSpec

        def bad(row):
            raise RuntimeError('producer boom')

        with make_reader(synthetic_dataset.url, schema_fields=['id'],
                         transform_spec=TransformSpec(bad), workers_count=1) as reader:
            loader = JaxDataLoader(reader, batch_size=8)
            with pytest.raises(RuntimeError, match='producer boom'):
                list(loader)

    def test_training_step_consumes_sharded_batch(self, synthetic_dataset):
        """A jitted data-parallel train step over the 8-device mesh consumes loader
        batches without resharding (the end-to-end contract)."""
        import jax.numpy as jnp
        mesh = make_mesh(('data',))
        with make_reader(synthetic_dataset.url, schema_fields=['id', 'matrix'],
                         workers_count=2) as reader:
            loader = JaxDataLoader(reader, batch_size=16, mesh=mesh)

            @jax.jit
            def step(batch):
                x = batch['matrix'].astype(jnp.float32).reshape(16, -1)
                return jnp.mean(x ** 2)

            losses = [float(step(b)) for b in loader]
        assert len(losses) == 6  # 100 rows, batch 16, drop_last
        assert all(np.isfinite(l) for l in losses)


class TestScanStream:
    """scan_stream: streaming with compiled chunk programs — one H2D + one dispatch
    per chunk_batches batches (beyond-reference; the dispatch-bound larger-than-HBM
    configuration)."""

    def _reader(self, synthetic_dataset, **kwargs):
        return make_reader(synthetic_dataset.url, workers_count=1, num_epochs=1,
                           schema_fields=['id'], shuffle_row_groups=False, **kwargs)

    def test_covers_dataset_in_stream_order_chunks(self, synthetic_dataset):
        import jax.numpy as jnp
        loader = JaxDataLoader(self._reader(synthetic_dataset), batch_size=10)
        # int32 carry: x64 is disabled (conftest), int64 would warn-truncate
        carry, aux = loader.scan_stream(
            lambda c, b: (c + jnp.sum(b['id']), b['id']), jnp.int32(0) + 0,
            chunk_batches=4, seed=None)
        ids = np.concatenate([np.asarray(a).ravel() for a in aux])
        assert sorted(ids.tolist()) == sorted(r['id'] for r in synthetic_dataset.rows)
        assert int(carry) == sum(r['id'] for r in synthetic_dataset.rows)
        # 100 rows / 10 per batch = 10 batches -> chunks of 4, 4, 2
        assert [np.asarray(a).shape[0] for a in aux] == [4, 4, 2]

    def test_in_chunk_shuffle_seeded(self, synthetic_dataset):
        def run(seed):
            loader = JaxDataLoader(self._reader(synthetic_dataset), batch_size=10)
            _, aux = loader.scan_stream(lambda c, b: (c, b['id']), None,
                                        chunk_batches=5, seed=seed)
            return np.concatenate([np.asarray(a).ravel() for a in aux]).tolist()

        base = run(None)
        assert base == sorted(base)  # no shuffle, deterministic fill order
        shuffled = run(7)
        assert shuffled != base
        assert sorted(shuffled) == base
        assert run(7) == shuffled

    def test_remainder_rows_dropped(self, synthetic_dataset):
        # 100 rows, batch 30: 3 full batches; 10 remainder rows dropped
        loader = JaxDataLoader(self._reader(synthetic_dataset), batch_size=30)
        _, aux = loader.scan_stream(lambda c, b: (c, b['id']), None, chunk_batches=2)
        total = sum(np.asarray(a).size for a in aux)
        assert total == 90

    def test_trains_a_model(self, synthetic_dataset):
        import jax
        import jax.numpy as jnp
        loader = JaxDataLoader(self._reader(synthetic_dataset), batch_size=10)

        def step(w, batch):
            loss, grad = jax.value_and_grad(
                lambda w: jnp.mean((batch['id'].astype(jnp.float32) * w) ** 2))(w)
            return w - 0.0001 * grad, loss

        w, aux = loader.scan_stream(step, jnp.float32(1.0), chunk_batches=5, seed=1)
        assert np.isfinite(float(w))

    def test_rejects_shuffle_buffer(self, synthetic_dataset):
        loader = JaxDataLoader(self._reader(synthetic_dataset), batch_size=10,
                               shuffling_queue_capacity=32)
        with pytest.raises(ValueError, match='in-chunk shuffle'):
            loader.scan_stream(lambda c, b: (c, None), 0)

    def test_mesh_sharded_chunks_match_single_device(self, synthetic_dataset):
        # VERDICT r3 item 3: scan_stream composes with a mesh — chunks upload as
        # globally-sharded arrays, every batch inside the scan keeps the loader's
        # batch sharding, and the result matches the single-device path exactly.
        import jax.numpy as jnp
        mesh = make_mesh(('data',))

        def run(mesh_arg):
            loader = JaxDataLoader(self._reader(synthetic_dataset), batch_size=16,
                                   mesh=mesh_arg, drop_last=True)
            if mesh_arg is not None:
                with mesh_arg:
                    return loader.scan_stream(
                        lambda c, b: (c + jnp.sum(b['id']), b['id']),
                        jnp.int32(0) + 0, chunk_batches=3)
            return loader.scan_stream(
                lambda c, b: (c + jnp.sum(b['id']), b['id']),
                jnp.int32(0) + 0, chunk_batches=3)

        carry_mesh, aux_mesh = run(mesh)
        carry_one, aux_one = run(None)
        assert int(carry_mesh) == int(carry_one)
        got = np.concatenate([np.asarray(a).ravel() for a in aux_mesh])
        want = np.concatenate([np.asarray(a).ravel() for a in aux_one])
        np.testing.assert_array_equal(got, want)

    def test_mesh_per_field_spec_batches_sharded_inside_scan(self, synthetic_dataset):
        # A dict partition_spec rides into the chunk program: assert from INSIDE
        # the compiled step that the per-batch view still has the global batch
        # size, and that training over the mesh produces a finite carry.
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        mesh = make_mesh(('data',))
        loader = JaxDataLoader(self._reader(synthetic_dataset), batch_size=16,
                               mesh=mesh, partition_spec={'id': P('data')},
                               drop_last=True)

        def step(w, batch):
            ids = batch['id'].astype(jnp.float32)
            assert ids.shape == (16,)  # trace-time: global batch inside the scan
            loss, grad = jax.value_and_grad(
                lambda w: jnp.mean((ids * w - 1.0) ** 2))(w)
            return w - 0.01 * grad, loss

        with mesh:
            w, aux = loader.scan_stream(step, jnp.float32(0.5), chunk_batches=2,
                                        seed=3)
        losses = np.concatenate([np.asarray(a).ravel() for a in aux])
        assert np.isfinite(float(w))
        assert losses.size == 6  # 100 rows / 16 = 6 full batches
        assert np.all(np.isfinite(losses))

    def test_infinite_reader_rejected(self, synthetic_dataset):
        reader = make_reader(synthetic_dataset.url, workers_count=1, num_epochs=None,
                             schema_fields=['id'])
        loader = JaxDataLoader(reader, batch_size=10)
        try:
            with pytest.raises(ValueError, match='infinite'):
                loader.scan_stream(lambda c, b: (c, None), 0)
        finally:
            reader.stop()
            reader.join()

    def test_concurrent_with_iter_rejected(self, synthetic_dataset):
        loader = JaxDataLoader(self._reader(synthetic_dataset), batch_size=10)
        it = iter(loader)
        next(it)
        with pytest.raises(RuntimeError, match='__iter__ is active'):
            loader.scan_stream(lambda c, b: (c, None), 0)
        it.close()

    def test_programs_cached_across_passes_with_auto_reset(self, synthetic_dataset):
        """Repeated scan_stream calls auto-reset the consumed reader (a second call
        must NOT silently return (carry, [])) and reuse the compiled programs — the
        bench's steady-state measurement depends on both."""
        loader = JaxDataLoader(self._reader(synthetic_dataset), batch_size=10)
        step = lambda c, b: (c + 1, None)  # noqa: E731
        carry = 0
        for _ in range(3):
            carry, aux = loader.scan_stream(step, carry, chunk_batches=4)
            assert len(aux) == 3  # each pass re-serves the full dataset
        assert int(carry) == 3 * 10  # 10 batches per pass, 3 passes
        # chunks of 4,4,2 -> exactly two program shapes, compiled once each
        assert len(loader._scan_stream_programs) == 2

    def test_device_put_false_rejected(self, synthetic_dataset):
        loader = JaxDataLoader(self._reader(synthetic_dataset), batch_size=10,
                               device_put=False)
        with pytest.raises(ValueError, match='device_put'):
            loader.scan_stream(lambda c, b: (c, None), 0)

    def test_drop_last_false_rejected(self, synthetic_dataset):
        loader = JaxDataLoader(self._reader(synthetic_dataset), batch_size=30,
                               drop_last=False)
        with pytest.raises(ValueError, match='drop_last'):
            loader.scan_stream(lambda c, b: (c, None), 0)

    def test_state_dict_rejected_after_scan_stream(self, synthetic_dataset):
        loader = JaxDataLoader(self._reader(synthetic_dataset), batch_size=10)
        loader.scan_stream(lambda c, b: (c, None), 0, chunk_batches=2)
        with pytest.raises(ValueError, match='scan_stream'):
            loader.state_dict()


class TestPerFieldUpload:
    """Every batch ships field by field through ``jax.device_put``: each device
    field equals the host batch's field after jax's x32 canonicalization
    (64-bit ints truncate mod 2^32), for every dtype the store holds."""

    _FIELDS = ('id', 'img', 'vec', 'flag', 'small', 'short')

    def _write_mixed_store(self, tmp_path):
        from petastorm_tpu.codecs import NdarrayCodec, ScalarCodec
        from petastorm_tpu.etl.dataset_metadata import write_rows
        from petastorm_tpu.unischema import Unischema, UnischemaField
        url = 'file://' + str(tmp_path / 'mixed')
        schema = Unischema('Mixed', [
            UnischemaField('id', np.int64, (), ScalarCodec(), False),
            UnischemaField('img', np.uint8, (5, 7), NdarrayCodec(), False),
            UnischemaField('vec', np.float32, (3,), NdarrayCodec(), False),
            UnischemaField('flag', np.bool_, (), ScalarCodec(), False),
            UnischemaField('small', np.int8, (), ScalarCodec(), False),
            UnischemaField('short', np.int16, (2,), NdarrayCodec(), False),
        ])
        rows = [{'id': (2 ** 40 + i if i == 3 else i),  # exercises truncation
                 'img': np.arange(35, dtype=np.uint8).reshape(5, 7) + i,
                 'vec': np.full(3, i * 1.5, np.float32),
                 'flag': bool(i % 2), 'small': np.int8(i - 5),
                 'short': np.array([-i, i * 300], np.int16)}
                for i in range(24)]
        write_rows(url, schema, rows, n_files=2)
        return url

    def _collect(self, url, device_put):
        reader = make_reader(url, workers_count=1, num_epochs=1,
                             shuffle_row_groups=False)
        loader = JaxDataLoader(reader, batch_size=8, device_put=device_put)
        try:
            batches = [dict(b) for b in loader]
        finally:
            loader.stop()
            loader.join()
        return batches, loader.stats.as_dict()

    @pytest.mark.parametrize('field', _FIELDS)
    def test_device_field_matches_host_field(self, tmp_path, field):
        url = self._write_mixed_store(tmp_path)
        host, _ = self._collect(url, False)
        device, _ = self._collect(url, True)
        assert len(host) == len(device) == 3
        for host_batch, device_batch in zip(host, device):
            want = jax.device_put(host_batch[field])
            assert device_batch[field].dtype == want.dtype, field
            np.testing.assert_array_equal(np.asarray(device_batch[field]),
                                          np.asarray(want), err_msg=field)

    def test_int64_truncates_under_x32(self, tmp_path):
        assert not jax.config.jax_enable_x64
        device, _ = self._collect(self._write_mixed_store(tmp_path), True)
        ids = np.concatenate([np.asarray(b['id']) for b in device])
        assert ids.dtype == np.int32
        assert sorted(ids.tolist()) == list(range(24))  # 2**40 + 3 -> 3

    def test_every_batch_counts_as_per_field_upload(self, tmp_path):
        _, stats = self._collect(self._write_mixed_store(tmp_path), True)
        assert stats['per_field_uploads'] == stats['batches'] == 3

    def test_scan_stream_matches_iteration(self, tmp_path):
        """scan_stream's chunk upload and scan give what a Python loop over the
        same batches gives."""
        url = self._write_mixed_store(tmp_path)

        def step(carry, batch):
            return carry + batch['vec'].sum() + batch['id'].sum(), batch['id']

        reader = make_reader(url, workers_count=1, num_epochs=1,
                             shuffle_row_groups=False, schema_fields=['id', 'vec'])
        loader = JaxDataLoader(reader, batch_size=4)
        try:
            carry, aux = loader.scan_stream(step, 0.0, chunk_batches=3)
        finally:
            loader.stop()
            loader.join()
        reader = make_reader(url, workers_count=1, num_epochs=1,
                             shuffle_row_groups=False, schema_fields=['id', 'vec'])
        loader = JaxDataLoader(reader, batch_size=4)
        want, ids = 0.0, []
        try:
            for batch in loader:
                want, out = step(want, batch)
                ids.append(np.asarray(out))
        finally:
            loader.stop()
            loader.join()
        assert float(carry) == float(want)
        np.testing.assert_array_equal(
            np.concatenate([np.asarray(a).reshape(-1) for a in aux]),
            np.concatenate(ids))
