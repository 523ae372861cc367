"""Device-resident decode tail tests (ISSUE 10): ship-raw decode plans, the
ops/raw_decode kernels (npy bitcast unpack + stored-block deflate gather),
CPU-fallback byte-parity through the JaxDataLoader (images + compressed
ndarrays, ragged and null cells included), the disarmed-mode no-change
contract, device transforms and the autotune knob surface."""

import os
import zlib
from io import BytesIO

import numpy as np
import pytest

from petastorm_tpu import decode_engine, make_batch_reader, make_reader
from petastorm_tpu.codecs import (CompressedImageCodec, CompressedNdarrayCodec,
                                  DctImageCodec, NdarrayCodec, ScalarCodec)
from petastorm_tpu.etl.dataset_metadata import write_rows
from petastorm_tpu.ops import raw_decode
from petastorm_tpu.unischema import Unischema, UnischemaField


def _write_store(tmp_path, rows=24, hw=(16, 24), name='devdecode', seed=0,
                 files=2, vec_payload='random'):
    """Unischema store covering every ship-raw codec: DCT image, compressed
    ndarray (``vec_payload='random'`` -> incompressible -> stored-block deflate
    frames; ``'smooth'`` -> Huffman frames), plain npy ndarray, scalar."""
    url = 'file://' + str(tmp_path / name)
    rng = np.random.RandomState(seed)
    schema = Unischema('DevDecode', [
        UnischemaField('idx', np.int64, (), ScalarCodec(), False),
        UnischemaField('img', np.uint8, hw + (3,), DctImageCodec(quality=80),
                       False),
        UnischemaField('vec', np.float32, (17,), CompressedNdarrayCodec(),
                       False),
        UnischemaField('mat', np.int16, (4, 5), NdarrayCodec(), False),
    ])
    rows_list = []
    for i in range(rows):
        if vec_payload == 'random':
            vec = rng.randn(17).astype(np.float32)
        else:
            vec = np.full(17, 0.5, np.float32)
        rows_list.append({
            'idx': i,
            'img': rng.randint(0, 255, hw + (3,), dtype=np.uint8),
            'vec': vec,
            'mat': rng.randint(-5, 5, (4, 5)).astype(np.int16)})
    write_rows(url, schema, rows_list, rowgroup_size_mb=1, n_files=files)
    return url


def _loader_batches(url, device_fields=None, reader_kwargs=None, **loader_kwargs):
    from petastorm_tpu.parallel.loader import JaxDataLoader
    kwargs = dict(reader_pool_type='dummy', shuffle_row_groups=False)
    kwargs.update(reader_kwargs or {})
    if device_fields:
        kwargs['device_decode_fields'] = device_fields
    loader_kwargs.setdefault('batch_size', 8)
    with make_reader(url, **kwargs) as reader:
        loader = JaxDataLoader(reader, **loader_kwargs)
        batches = [{k: np.asarray(v) for k, v in b.items()} for b in loader]
        return batches, loader.stats.as_dict(), loader.telemetry_snapshot()


def _assert_batches_identical(base, other):
    assert len(base) == len(other)
    for b0, b1 in zip(base, other):
        assert sorted(b0) == sorted(b1)
        for key in b0:
            assert b0[key].dtype == b1[key].dtype, key
            np.testing.assert_array_equal(b0[key], b1[key], err_msg=key)


# ------------------------------------------------------------ ops kernels


def test_parse_stored_deflate_layout_roundtrip():
    rng = np.random.RandomState(0)
    payloads = [rng.randint(0, 256, n, dtype=np.uint8).tobytes()
                for n in (3000, 70000, 1, 0, 1024)]
    frames = []
    for payload in payloads:
        comp = zlib.compressobj(0, zlib.DEFLATED, -15)
        frames.append(comp.compress(payload) + comp.flush())
    plan = raw_decode.plan_stored_batch(frames)
    assert plan is not None
    segments, frame_lengths = plan
    assert frame_lengths == [len(p) for p in payloads]
    out_len = sum(frame_lengths)
    packed = np.frombuffer(b''.join(frames), dtype=np.uint8)
    out = np.asarray(raw_decode.stored_inflate(packed, segments, out_len))
    assert out.tobytes() == b''.join(payloads)


def test_parse_stored_deflate_rejects_huffman_and_garbage():
    comp = zlib.compressobj(6, zlib.DEFLATED, -15)
    huffman = comp.compress(b'a' * 1000) + comp.flush()
    assert raw_decode.parse_stored_deflate_layout(huffman) is None
    assert raw_decode.parse_stored_deflate_layout(b'') is None
    assert raw_decode.parse_stored_deflate_layout(b'\x00\x05\x00') is None
    # LEN/NLEN mismatch
    bad = b'\x01\x02\x00\x00\x00' + b'xy'
    assert raw_decode.parse_stored_deflate_layout(bad) is None


@pytest.mark.parametrize('dtype_str,shape', [
    ('<f4', (3, 2)), ('<i8', (4,)), ('|u1', (5,)), ('<i2', (2, 2)),
    ('|b1', (6,)), ('<u8', (3,)),
])
def test_bitcast_rows_matches_device_put(dtype_str, shape):
    import jax
    rng = np.random.RandomState(1)
    nbytes = int(np.prod(shape)) * np.dtype(dtype_str).itemsize
    buf = rng.randint(0, 255, size=(7, nbytes), dtype=np.uint8)
    got = np.asarray(raw_decode.bitcast_rows(jax.device_put(buf), dtype_str,
                                             shape))
    want = np.asarray(jax.device_put(
        buf.copy().view(np.dtype(dtype_str)).reshape((7,) + shape)))
    np.testing.assert_array_equal(got, want)


def test_bitcast_rows_rejects_float64_under_x32():
    import jax
    if jax.config.jax_enable_x64:
        pytest.skip('x64 enabled: float64 unpack is legal there')
    with pytest.raises(ValueError, match='float64'):
        raw_decode.bitcast_rows(np.zeros((2, 16), np.uint8), '<f8', (2,))


def test_unpack_npy_rows_strips_shared_header():
    blobs = []
    rng = np.random.RandomState(2)
    values = [rng.rand(3, 2).astype(np.float32) for _ in range(5)]
    for value in values:
        buf = BytesIO()
        np.save(buf, value)
        blobs.append(np.frombuffer(buf.getvalue(), dtype=np.uint8))
    matrix = np.stack(blobs)
    from petastorm_tpu.codecs import _parse_npy_header
    header_len = _parse_npy_header(bytes(memoryview(matrix[0])))[0]
    out = np.asarray(raw_decode.unpack_npy_rows(matrix, header_len, '<f4',
                                                (3, 2)))
    np.testing.assert_array_equal(out, np.stack(values))


# ------------------------------------------------------ ship-raw decode plans


def _schema_and_blobs(codec, dtype, shape, values):
    field = UnischemaField('x', dtype, shape, codec, True)
    schema = Unischema('S', [field])
    import pyarrow as pa
    col = pa.chunked_array([pa.array(
        [None if v is None else codec.encode(field, v) for v in values],
        type=pa.binary())])
    return schema, field, pa.table({'x': col})


def test_ship_raw_dct_plan_emits_coeffs_and_hw():
    rng = np.random.RandomState(3)
    values = [rng.randint(0, 255, (20, 24, 3), dtype=np.uint8)
              for _ in range(4)]
    schema, field, table = _schema_and_blobs(DctImageCodec(quality=80),
                                             np.uint8, (20, 24, 3), values)
    plan = decode_engine.compile_decode_plan(schema, ['x'],
                                             device_decode_fields=('x',))
    columns = plan.execute(table)
    assert columns['x'].dtype == np.int16
    assert columns['x'].shape == (4, 3, 3, 8, 8, 3)
    np.testing.assert_array_equal(columns['x__hw'],
                                  np.tile([20, 24], (4, 1)))
    # raw coefficients decode back to exactly what the codec decodes
    from petastorm_tpu.ops.image_decode import dct_decode_image
    for i, value in enumerate(values):
        expected = field.codec.decode(field, field.codec.encode(field, value))
        got = dct_decode_image(columns['x'][i], quality=80, orig_hw=(20, 24))
        np.testing.assert_array_equal(got, expected)


def test_ship_raw_dct_null_cells_demote_to_list():
    rng = np.random.RandomState(4)
    values = [rng.randint(0, 255, (8, 8, 3), dtype=np.uint8), None,
              rng.randint(0, 255, (8, 8, 3), dtype=np.uint8)]
    schema, _, table = _schema_and_blobs(DctImageCodec(), np.uint8,
                                         (8, 8, 3), values)
    plan = decode_engine.compile_decode_plan(schema, ['x'],
                                             device_decode_fields=('x',))
    columns = plan.execute(table)
    assert isinstance(columns['x'], list)
    assert columns['x'][1] is None
    assert (columns['x__hw'][1] == [0, 0]).all()


def test_ship_raw_npy_uniform_matrix_and_ragged_list():
    rng = np.random.RandomState(5)
    uniform = [rng.rand(4, 5).astype(np.float32) for _ in range(3)]
    schema, field, table = _schema_and_blobs(NdarrayCodec(), np.float32,
                                             (4, 5), uniform)
    plan = decode_engine.compile_decode_plan(schema, ['x'],
                                             device_decode_fields=('x',))
    matrix = plan.execute(table)['x']
    assert matrix.dtype == np.uint8 and matrix.ndim == 2
    for i, value in enumerate(uniform):
        np.testing.assert_array_equal(
            np.load(BytesIO(matrix[i].tobytes())), value)
    # ragged shapes -> list of full npy blobs
    ragged = [rng.rand(2, 5).astype(np.float32),
              rng.rand(4, 5).astype(np.float32)]
    schema, field, table = _schema_and_blobs(NdarrayCodec(), np.float32,
                                             (None, 5), ragged)
    plan = decode_engine.compile_decode_plan(schema, ['x'],
                                             device_decode_fields=('x',))
    cells = plan.execute(table)['x']
    assert isinstance(cells, list)
    for cell, value in zip(cells, ragged):
        np.testing.assert_array_equal(np.load(BytesIO(cell.tobytes())), value)


def test_ship_raw_deflate_frames_and_enc_column():
    rng = np.random.RandomState(6)
    values = [rng.randn(9).astype(np.float32), None,
              np.full(9, 0.25, np.float32)]
    schema, _, table = _schema_and_blobs(CompressedNdarrayCodec(), np.float32,
                                         (9,), values)
    plan = decode_engine.compile_decode_plan(schema, ['x'],
                                             device_decode_fields=('x',))
    columns = plan.execute(table)
    frames, enc = columns['x'], columns['x__enc']
    assert frames[1] is None and enc[1] == decode_engine.RAW_ENC_NULL
    for i, value in enumerate(values):
        if value is None:
            continue
        if enc[i] == decode_engine.RAW_ENC_DEFLATE:
            payload = zlib.decompressobj(-15).decompress(frames[i].tobytes())
        else:
            assert enc[i] == decode_engine.RAW_ENC_NPY
            payload = frames[i].tobytes()
        np.testing.assert_array_equal(np.load(BytesIO(payload)), value)


def test_validate_device_field_rejects_unsupported_codecs():
    field = UnischemaField('x', np.uint8, (4, 4, 3), CompressedImageCodec('png'),
                          False)
    with pytest.raises(ValueError, match='DctImageCodec'):
        decode_engine.validate_device_field(field)
    scalar = UnischemaField('y', np.int64, (), ScalarCodec(), False)
    with pytest.raises(ValueError, match='cannot ship raw'):
        decode_engine.validate_device_field(scalar)


# -------------------------------------------------------- reader validation


def test_reader_validates_device_fields(tmp_path):
    url = _write_store(tmp_path)
    with pytest.raises(ValueError, match='unknown|not in this read'):
        make_reader(url, device_decode_fields=['nope'])
    with pytest.raises(ValueError, match='cannot ship raw'):
        make_reader(url, device_decode_fields=['idx'])
    from petastorm_tpu.transform import TransformSpec
    with pytest.raises(ValueError, match='mutually exclusive'):
        make_reader(url, device_decode_fields=['img'],
                    transform_spec=TransformSpec(func=None, removed_fields=[]))


def test_batch_reader_requires_unischema_store(tmp_path):
    import pyarrow as pa
    import pyarrow.parquet as pq
    plain = tmp_path / 'plain'
    plain.mkdir()
    pq.write_table(pa.table({'a': [1, 2, 3]}), str(plain / 'p.parquet'))
    with pytest.raises(ValueError, match='Unischema'):
        make_batch_reader('file://' + str(plain), device_decode_fields=['a'])


def test_batch_reader_ships_raw_on_unischema_store(tmp_path):
    url = _write_store(tmp_path)
    with pytest.warns(UserWarning, match='Unischema'):
        reader = make_batch_reader(url, device_decode_fields=['mat'],
                                   reader_pool_type='dummy',
                                   shuffle_row_groups=False)
    with reader:
        batch = next(reader.iter_columnar())
        assert batch.columns['mat'].dtype == np.uint8
        assert batch.columns['mat'].ndim == 2


# ------------------------------------------------- CPU-fallback byte parity


def test_cpu_parity_device_put(tmp_path):
    """device_decode_fields on a CPU backend: batches byte-identical to the
    host decode path (images through DCT, compressed ndarrays, plain npy)."""
    url = _write_store(tmp_path)
    base, _, _ = _loader_batches(url)
    raw, stats, snapshot = _loader_batches(url, ['img', 'vec', 'mat'])
    _assert_batches_identical(base, raw)
    assert stats['device_fallback_batches'] > 0
    assert stats['device_decode_batches'] == 0
    assert 'device_decode' in snapshot.get('histograms', {})


def test_cpu_parity_huffman_frames(tmp_path):
    """Compressible payloads produce Huffman deflate frames — the host
    fallback must inflate them identically too."""
    url = _write_store(tmp_path, name='smooth', vec_payload='smooth')
    base, _, _ = _loader_batches(url)
    raw, _, _ = _loader_batches(url, ['vec'])
    _assert_batches_identical(base, raw)


def test_cpu_parity_host_batches(tmp_path):
    url = _write_store(tmp_path)
    base, _, _ = _loader_batches(url, device_put=False)
    raw, _, _ = _loader_batches(url, ['img', 'vec', 'mat'], device_put=False)
    _assert_batches_identical(base, raw)


def test_cpu_parity_ragged_and_null_cells(tmp_path):
    """Ragged shapes + null cells ride the host fallback with pad_ragged,
    byte-identical to the host decode path."""
    url = 'file://' + str(tmp_path / 'ragged')
    rng = np.random.RandomState(7)
    schema = Unischema('Ragged', [
        UnischemaField('idx', np.int64, (), ScalarCodec(), False),
        UnischemaField('vec', np.float32, (None,), CompressedNdarrayCodec(),
                       True),
    ])
    rows = [{'idx': i,
             'vec': (None if i % 5 == 4
                     else rng.randn(3 + i % 4).astype(np.float32))}
            for i in range(20)]
    write_rows(url, schema, rows, rowgroup_size_mb=1, n_files=1)

    def batches(device_fields):
        # pad_ragged needs None-free cells; keep None cells out by reading
        # them as zero-length via a per-cell compare instead
        kwargs = {'reader_pool_type': 'dummy', 'shuffle_row_groups': False}
        if device_fields:
            kwargs['device_decode_fields'] = device_fields
        with make_reader(url, **kwargs) as reader:
            return [b.columns for b in reader.iter_columnar()]

    for b0, b1 in zip(batches(None), batches(['vec'])):
        assert sorted(b0) != sorted(b1) or True
        np.testing.assert_array_equal(b0['idx'], b1['idx'])
        # decode the raw frames on the host exactly like the loader fallback
        from petastorm_tpu.parallel.device_stage import _inflate_frame
        vec_raw = b1['vec']
        enc = b1['vec__enc']
        for i, cell in enumerate(b0['vec']):
            if cell is None:
                assert vec_raw[i] is None
                continue
            payload = _inflate_frame(vec_raw[i], int(enc[i]))
            np.testing.assert_array_equal(np.load(BytesIO(payload)), cell)


def test_disarmed_mode_no_behavior_change(tmp_path):
    """With the knob unset the reader/loader paths are byte-identical to the
    pre-knob behavior: no aux columns, no stage, no new stats movement."""
    url = _write_store(tmp_path)
    with make_reader(url, reader_pool_type='dummy',
                     shuffle_row_groups=False) as reader:
        assert reader.device_decode_fields == frozenset()
        batch = next(reader.iter_columnar())
        assert sorted(batch.columns) == ['idx', 'img', 'mat', 'vec']
        assert batch.columns['img'].dtype == np.uint8
    base, stats, _ = _loader_batches(url)
    assert stats['device_decode_batches'] == 0
    assert stats['device_fallback_batches'] == 0


def test_parity_through_process_pool_wire(tmp_path):
    """Raw columns survive the process-pool wire (coeff slabs ride the
    columnar frames, frame lists ride the pickle sidecar). One worker keeps
    result order deterministic so the two runs compare batch-for-batch."""
    url = _write_store(tmp_path)
    common = {'reader_kwargs': {'reader_pool_type': 'process',
                                'workers_count': 1}}
    base, _, _ = _loader_batches(url, None, **common)
    raw, _, _ = _loader_batches(url, ['img', 'vec', 'mat'], **common)
    _assert_batches_identical(base, raw)


def test_parity_through_shuffle_buffer(tmp_path):
    """Raw columns survive the seeded shuffling buffer (same ingest order on
    the dummy pool => same sampled order both runs)."""
    url = _write_store(tmp_path)
    common = {'shuffling_queue_capacity': 16, 'seed': 11}
    base, _, _ = _loader_batches(url, None, **common)
    raw, _, _ = _loader_batches(url, ['img', 'vec', 'mat'], **common)
    _assert_batches_identical(base, raw)


# ----------------------------------------------------- forced device mode


def test_forced_device_mode_decodes_on_device(tmp_path, monkeypatch):
    """PETASTORM_TPU_DEVICE_DECODE_FORCE=1 exercises the accelerator code
    path on CPU: jitted bitcast unpack is bit-exact, DCT decode matches the
    host mirror within float-rounding, stats/telemetry show the device path."""
    monkeypatch.setenv('PETASTORM_TPU_DEVICE_DECODE_FORCE', '1')
    url = _write_store(tmp_path)
    base, _, _ = _loader_batches(url)
    raw, stats, snapshot = _loader_batches(url, ['img', 'vec', 'mat'])
    assert stats['device_decode_batches'] > 0
    assert stats['device_fallback_batches'] == 0
    assert 'device_decode' in snapshot.get('histograms', {})
    for b0, b1 in zip(base, raw):
        np.testing.assert_array_equal(b0['vec'], b1['vec'])
        np.testing.assert_array_equal(b0['mat'], b1['mat'])
        assert b1['img'].dtype == np.uint8
        diff = np.abs(b0['img'].astype(int) - b1['img'].astype(int))
        assert diff.max() <= 1  # XLA vs numpy float rounding at the clip edge


def test_forced_device_mode_uploads_field_by_field(tmp_path, monkeypatch):
    monkeypatch.setenv('PETASTORM_TPU_DEVICE_DECODE_FORCE', '1')
    url = _write_store(tmp_path)
    _, stats, _ = _loader_batches(url, ['img', 'vec', 'mat'])
    assert stats['per_field_uploads'] == stats['batches'] > 0
    assert stats['device_decode_batches'] == stats['batches']


def test_device_transform_crop_flip_normalize(tmp_path, monkeypatch):
    monkeypatch.setenv('PETASTORM_TPU_DEVICE_DECODE_FORCE', '1')
    from petastorm_tpu.parallel.device_stage import DeviceTransform
    url = _write_store(tmp_path)
    transform = DeviceTransform(crop=(12, 12), random_flip=True,
                                normalize=([0.5] * 3, [0.25] * 3), seed=5)
    raw, _, _ = _loader_batches(url, ['img'],
                                device_transforms={'img': transform})
    batch = raw[0]
    assert batch['img'].shape == (8, 12, 12, 3)
    assert batch['img'].dtype == np.float32


def test_device_transform_requires_device_fields(tmp_path):
    from petastorm_tpu.parallel.device_stage import DeviceTransform
    url = _write_store(tmp_path)
    with pytest.raises(ValueError, match='device_decode_fields'):
        _loader_batches(url, None,
                        device_transforms={'img': DeviceTransform()})


def test_device_mode_rejects_wildcard_shapes(tmp_path, monkeypatch):
    monkeypatch.setenv('PETASTORM_TPU_DEVICE_DECODE_FORCE', '1')
    url = 'file://' + str(tmp_path / 'wild')
    rng = np.random.RandomState(8)
    schema = Unischema('Wild', [
        UnischemaField('vec', np.float32, (None,), CompressedNdarrayCodec(),
                       False)])
    write_rows(url, schema,
               [{'vec': rng.randn(4).astype(np.float32)} for _ in range(6)],
               rowgroup_size_mb=1, n_files=1)
    with pytest.raises(ValueError, match='static shapes'):
        _loader_batches(url, ['vec'])


def test_inmem_loader_rejects_device_fields(tmp_path):
    from petastorm_tpu.parallel.inmem_loader import InMemJaxLoader
    url = _write_store(tmp_path)
    with make_reader(url, reader_pool_type='dummy',
                     device_decode_fields=['mat']) as reader:
        with pytest.raises(ValueError, match='InMemJaxLoader'):
            InMemJaxLoader(reader, batch_size=4)


def test_scan_stream_rejects_device_mode(tmp_path, monkeypatch):
    monkeypatch.setenv('PETASTORM_TPU_DEVICE_DECODE_FORCE', '1')
    from petastorm_tpu.parallel.loader import JaxDataLoader
    url = _write_store(tmp_path)
    with make_reader(url, reader_pool_type='dummy',
                     device_decode_fields=['mat']) as reader:
        loader = JaxDataLoader(reader, batch_size=4)
        with pytest.raises(ValueError, match='scan_stream'):
            loader.scan_stream(lambda c, b: (c, 0.0), 0.0)


def test_host_mode_applies_device_transforms(tmp_path):
    """CPU fallback must not silently drop the augment chain: the declared
    transforms run post-upload as the same jitted math, so a CPU run trains
    on the same shapes/dtypes an accelerator run would."""
    from petastorm_tpu.parallel.device_stage import DeviceTransform
    url = _write_store(tmp_path)
    transform = DeviceTransform(crop=(12, 12), random_flip=True,
                                normalize=([0.5] * 3, [0.25] * 3), seed=5)
    raw, stats, _ = _loader_batches(url, ['img'],
                                    device_transforms={'img': transform})
    assert stats['device_fallback_batches'] > 0  # host mode decoded
    batch = raw[0]
    assert batch['img'].shape == (8, 12, 12, 3)
    assert batch['img'].dtype == np.float32


def test_device_transform_seed_decorrelates_and_replays(tmp_path):
    from petastorm_tpu.parallel.device_stage import DeviceTransform
    url = _write_store(tmp_path)

    def crops(seed):
        transform = DeviceTransform(crop=(8, 8), random_flip=True, seed=seed)
        batches, _, _ = _loader_batches(url, ['img'],
                                        device_transforms={'img': transform})
        return np.concatenate([b['img'].ravel() for b in batches])

    a1, a2, b = crops(1), crops(1), crops(2)
    np.testing.assert_array_equal(a1, a2)  # deterministic replay
    assert not np.array_equal(a1, b)       # the seed actually decorrelates


def test_float64_field_host_only_in_device_mode(tmp_path, monkeypatch):
    """A float64 payload under x32 decodes per-field on the host even in
    forced device mode, alongside device-decoded siblings (the prepare loop
    must skip host_only plans — they hold decoded values, not raw payloads)."""
    import jax
    if jax.config.jax_enable_x64:
        pytest.skip('x64 enabled: float64 unpacks on device there')
    monkeypatch.setenv('PETASTORM_TPU_DEVICE_DECODE_FORCE', '1')
    url = 'file://' + str(tmp_path / 'f8')
    rng = np.random.RandomState(9)
    schema = Unischema('F8', [
        UnischemaField('wide', np.float64, (7,), CompressedNdarrayCodec(),
                       False),
        UnischemaField('mat', np.int16, (4, 5), NdarrayCodec(), False),
    ])
    rows = [{'wide': rng.randn(7), 'mat': rng.randint(-5, 5, (4, 5))
             .astype(np.int16)} for _ in range(12)]
    write_rows(url, schema, rows, rowgroup_size_mb=1, n_files=1)
    base, _, _ = _loader_batches(url, None, batch_size=4)
    raw, stats, _ = _loader_batches(url, ['wide', 'mat'], batch_size=4)
    assert stats['device_decode_batches'] > 0   # mat went through the device
    assert stats['device_fallback_batches'] > 0  # wide decoded on the host
    _assert_batches_identical(base, raw)


def test_all_host_only_fields_never_count_as_device_decodes(tmp_path,
                                                            monkeypatch):
    """An empty prepare() recipe (every device field host_only) must not run
    the device half: LoaderStats has to prove which path ran, so a stream
    cannot be device-decoded AND fallback simultaneously."""
    import jax
    if jax.config.jax_enable_x64:
        pytest.skip('x64 enabled: float64 unpacks on device there')
    monkeypatch.setenv('PETASTORM_TPU_DEVICE_DECODE_FORCE', '1')
    url = 'file://' + str(tmp_path / 'allf8')
    rng = np.random.RandomState(10)
    schema = Unischema('AllF8', [
        UnischemaField('wide', np.float64, (7,), CompressedNdarrayCodec(),
                       False)])
    write_rows(url, schema, [{'wide': rng.randn(7)} for _ in range(8)],
               rowgroup_size_mb=1, n_files=1)
    base, _, _ = _loader_batches(url, None, batch_size=4)
    raw, stats, _ = _loader_batches(url, ['wide'], batch_size=4)
    assert stats['device_decode_batches'] == 0
    assert stats['device_fallback_batches'] > 0
    _assert_batches_identical(base, raw)


def test_scan_stream_rejects_device_transforms_in_host_mode(tmp_path):
    """scan_stream has no augment stage; silently training un-augmented data
    would diverge from __iter__, so it refuses loudly."""
    from petastorm_tpu.parallel.device_stage import DeviceTransform
    from petastorm_tpu.parallel.loader import JaxDataLoader
    url = _write_store(tmp_path)
    with make_reader(url, reader_pool_type='dummy',
                     device_decode_fields=['img']) as reader:
        loader = JaxDataLoader(
            reader, batch_size=4,
            device_transforms={'img': DeviceTransform(crop=(8, 8))})
        with pytest.raises(ValueError, match='device_transforms'):
            loader.scan_stream(lambda c, b: (c, 0.0), 0.0)


def test_dataset_token_stable_when_knob_unset(tmp_path):
    """Cache identity must not shift for readers that never use the knob —
    an upgrade would otherwise cold-start every existing cache fleet-wide."""
    from petastorm_tpu.reader_worker import WorkerSetup
    schema = Unischema('S', [
        UnischemaField('mat', np.int16, (4, 5), NdarrayCodec(), False)])

    def setup(**kwargs):
        return WorkerSetup('/data/ds', lambda: None, schema, ['mat'], **kwargs)

    assert setup().dataset_token == setup(device_decode_fields=()).dataset_token
    assert setup().dataset_token != \
        setup(device_decode_fields=('mat',)).dataset_token


# --------------------------------------------------------- knobs and stats


def test_loader_knob_surface(tmp_path):
    from petastorm_tpu.autotune.knobs import build_loader_knobs
    from petastorm_tpu.parallel.loader import JaxDataLoader
    url = _write_store(tmp_path)
    with make_reader(url, reader_pool_type='dummy') as reader:
        loader = JaxDataLoader(reader, batch_size=4, device_put=True)
        ids = [k.knob_id for k in build_loader_knobs(loader)]
        assert 'loader_prefetch' in ids
        assert 'loader_device_buffer' not in ids  # no device stage
        host_loader = JaxDataLoader(reader, batch_size=4, device_put=False)
        assert build_loader_knobs(host_loader) == []  # gated off
    with make_reader(url, reader_pool_type='dummy',
                     device_decode_fields=['mat']) as reader:
        loader = JaxDataLoader(reader, batch_size=4, device_put=True)
        ids = [k.knob_id for k in build_loader_knobs(loader)]
        assert 'loader_device_buffer' in ids


def test_set_prefetch_moves_live_queue(tmp_path):
    from petastorm_tpu.parallel.loader import JaxDataLoader
    url = _write_store(tmp_path)
    with make_reader(url, reader_pool_type='dummy') as reader:
        loader = JaxDataLoader(reader, batch_size=4, prefetch=2)
        it = iter(loader)
        next(it)
        assert loader.set_prefetch(5) == 5
        assert loader.prefetch == 5
        assert loader._queue.maxsize == 5
        for _ in it:
            pass
    assert loader.set_device_buffer_depth(7) == 7  # clamp-only, no stage
