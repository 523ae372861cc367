"""Codec unit tests (model: petastorm/tests/test_codec_{scalar,ndarray,image}.py)."""

import numpy as np
import pyarrow as pa
import pytest

from petastorm_tpu.codecs import (CompressedImageCodec, CompressedNdarrayCodec, NdarrayCodec,
                                  ScalarCodec, _is_compliant_shape, codec_from_config)
from petastorm_tpu.unischema import UnischemaField


def _roundtrip(codec, field, value):
    return codec.decode(field, codec.encode(field, value))


class TestScalarCodec:
    def test_int_roundtrip(self):
        field = UnischemaField('x', np.int32, (), ScalarCodec(), False)
        out = _roundtrip(field.codec, field, np.int32(42))
        assert out == 42
        assert out.dtype == np.int32

    def test_float_roundtrip(self):
        field = UnischemaField('x', np.float64, (), ScalarCodec(), False)
        out = _roundtrip(field.codec, field, 1.5)
        assert out == 1.5

    def test_string_passthrough(self):
        field = UnischemaField('s', np.str_, (), ScalarCodec(), False)
        assert _roundtrip(field.codec, field, 'hello') == 'hello'

    def test_bytes_roundtrip_stays_bytes(self):
        """np.bytes_ fields must map to Arrow binary, not string — otherwise decode
        hands back str and binary payloads get UTF-8 mangled."""
        field = UnischemaField('b', np.bytes_, (), ScalarCodec(), False)
        assert field.codec.arrow_type(field) == pa.binary()
        out = _roundtrip(field.codec, field, b'\x00\xffraw')
        assert isinstance(out, bytes) and out == b'\x00\xffraw'

    def test_rejects_array(self):
        field = UnischemaField('x', np.int32, (), ScalarCodec(), False)
        with pytest.raises(TypeError):
            field.codec.encode(field, np.zeros(3, dtype=np.int32))

    def test_arrow_type_default(self):
        field = UnischemaField('x', np.int16, (), ScalarCodec(), False)
        assert field.codec.arrow_type(field) == pa.int16()

    def test_arrow_type_override(self):
        codec = ScalarCodec(pa.int64())
        field = UnischemaField('x', np.int16, (), codec, False)
        assert codec.arrow_type(field) == pa.int64()

    def test_config_roundtrip(self):
        codec = ScalarCodec(pa.int64())
        restored = codec_from_config(codec.to_config())
        assert restored == codec


class TestNdarrayCodecs:
    @pytest.mark.parametrize('codec_cls', [NdarrayCodec, CompressedNdarrayCodec])
    def test_roundtrip(self, codec_cls):
        codec = codec_cls()
        field = UnischemaField('m', np.float32, (3, 4), codec, False)
        value = np.random.rand(3, 4).astype(np.float32)
        out = _roundtrip(codec, field, value)
        np.testing.assert_array_equal(out, value)
        assert out.flags['C_CONTIGUOUS']

    @pytest.mark.parametrize('codec_cls', [NdarrayCodec, CompressedNdarrayCodec])
    def test_variable_shape(self, codec_cls):
        codec = codec_cls()
        field = UnischemaField('m', np.int64, (None, 2), codec, False)
        value = np.arange(10).reshape(5, 2)
        np.testing.assert_array_equal(_roundtrip(codec, field, value), value)

    def test_wrong_dtype_raises(self):
        codec = NdarrayCodec()
        field = UnischemaField('m', np.float32, (3,), codec, False)
        with pytest.raises(ValueError, match='dtype'):
            codec.encode(field, np.zeros(3, dtype=np.float64))

    def test_wrong_shape_raises(self):
        codec = NdarrayCodec()
        field = UnischemaField('m', np.float32, (3,), codec, False)
        with pytest.raises(ValueError, match='shape'):
            codec.encode(field, np.zeros((4,), dtype=np.float32))

    @pytest.mark.parametrize('level', [None, 0, 1, 9])
    def test_compresslevel_roundtrip(self, level):
        from petastorm_tpu.codecs import codec_from_config
        codec = CompressedNdarrayCodec(compresslevel=level)
        field = UnischemaField('m', np.float32, (8, 16), codec, False)
        value = np.random.rand(8, 16).astype(np.float32)
        np.testing.assert_array_equal(_roundtrip(codec, field, value), value)
        np.testing.assert_array_equal(codec.decode(field, codec.encode(field, value)), value)
        assert codec_from_config(codec.to_config()) == codec

    def test_default_level_config_unchanged(self):
        # stores written before the option existed keep their schema config
        assert CompressedNdarrayCodec().to_config() == {'codec': 'compressed_ndarray'}
        assert CompressedNdarrayCodec(0) != CompressedNdarrayCodec()

    @pytest.mark.parametrize('level', [-1, 10])
    def test_compresslevel_out_of_range(self, level):
        with pytest.raises(ValueError, match='compresslevel'):
            CompressedNdarrayCodec(compresslevel=level)

    @pytest.mark.parametrize('level,stored', [(0, True), (None, False)])
    def test_level_zero_frames_are_device_inflatable(self, level, stored):
        """Level 0 leaves every deflate block stored, the frames the device
        inflates; zlib's default level opens with a Huffman block."""
        from petastorm_tpu.codecs import _npz_raw_member
        from petastorm_tpu.ops.raw_decode import parse_stored_deflate_layout
        codec = CompressedNdarrayCodec(compresslevel=level)
        field = UnischemaField('m', np.float32, (256,), codec, False)
        blob = codec.encode(field, np.random.rand(256).astype(np.float32))
        method, body = _npz_raw_member(blob)
        assert method == 8
        assert (parse_stored_deflate_layout(bytes(body)) is not None) == stored

    def test_compressed_smaller_on_redundant_data(self):
        field_plain = UnischemaField('m', np.float32, (100, 100), NdarrayCodec(), False)
        value = np.zeros((100, 100), dtype=np.float32)
        plain = NdarrayCodec().encode(field_plain, value)
        compressed = CompressedNdarrayCodec().encode(field_plain, value)
        assert len(compressed) < len(plain)


class TestImageCodec:
    def test_png_roundtrip_grayscale(self):
        codec = CompressedImageCodec('png')
        field = UnischemaField('im', np.uint8, (12, 10), codec, False)
        value = np.random.randint(0, 255, (12, 10), dtype=np.uint8)
        np.testing.assert_array_equal(_roundtrip(codec, field, value), value)

    def test_png_roundtrip_rgb(self):
        codec = CompressedImageCodec('png')
        field = UnischemaField('im', np.uint8, (12, 10, 3), codec, False)
        value = np.random.randint(0, 255, (12, 10, 3), dtype=np.uint8)
        # png is lossless: RGB->BGR->RGB swap must be exact
        np.testing.assert_array_equal(_roundtrip(codec, field, value), value)

    def test_png_uint16(self):
        codec = CompressedImageCodec('png')
        field = UnischemaField('im', np.uint16, (6, 6), codec, False)
        value = np.random.randint(0, 2 ** 16 - 1, (6, 6)).astype(np.uint16)
        np.testing.assert_array_equal(_roundtrip(codec, field, value), value)

    def test_jpeg_lossy_close(self):
        codec = CompressedImageCodec('jpeg', quality=95)
        field = UnischemaField('im', np.uint8, (32, 32, 3), codec, False)
        value = np.full((32, 32, 3), 128, dtype=np.uint8)
        out = _roundtrip(codec, field, value)
        assert out.shape == value.shape
        assert np.abs(out.astype(int) - value.astype(int)).mean() < 5

    def test_jpeg_rejects_uint16(self):
        codec = CompressedImageCodec('jpeg')
        field = UnischemaField('im', np.uint16, (6, 6), codec, False)
        with pytest.raises(ValueError):
            codec.encode(field, np.zeros((6, 6), dtype=np.uint16))

    def test_bad_codec_name(self):
        with pytest.raises(ValueError):
            CompressedImageCodec('gif')

    def test_config_roundtrip(self):
        codec = CompressedImageCodec('jpeg', quality=70)
        restored = codec_from_config(codec.to_config())
        assert restored == codec
        assert restored.quality == 70


def test_compliant_shape():
    assert _is_compliant_shape((3, 4), (3, 4))
    assert _is_compliant_shape((3, 4), (None, 4))
    assert not _is_compliant_shape((3, 4), (3, 5))
    assert not _is_compliant_shape((3, 4), (3, 4, 1))


ALL_DTYPES = [np.int8, np.int16, np.int32, np.int64, np.uint8, np.uint16,
              np.uint32, np.uint64, np.float16, np.float32, np.float64, np.bool_]


class TestDtypeMatrix:
    """Round-trip property across the supported dtype x codec matrix (model:
    reference test_codec_scalar/ndarray/image trio breadth)."""

    @pytest.mark.parametrize('dtype', ALL_DTYPES)
    def test_scalar_codec_every_dtype(self, dtype):
        field = UnischemaField('x', dtype, (), ScalarCodec(), False)
        value = dtype(1) if dtype != np.bool_ else np.bool_(True)
        decoded = _roundtrip(field.codec, field, value)
        assert decoded == value
        assert np.asarray(decoded).dtype == np.dtype(dtype)

    @pytest.mark.parametrize('dtype', ALL_DTYPES)
    @pytest.mark.parametrize('codec_cls', [NdarrayCodec, CompressedNdarrayCodec])
    def test_ndarray_codec_every_dtype(self, dtype, codec_cls):
        rng = np.random.RandomState(0)
        if dtype == np.bool_:
            value = rng.rand(3, 4) > 0.5
        elif np.dtype(dtype).kind == 'f':
            value = rng.randn(3, 4).astype(dtype)
        else:
            value = rng.randint(0, 100, (3, 4)).astype(dtype)
        field = UnischemaField('x', dtype, (3, 4), codec_cls(), False)
        out = _roundtrip(field.codec, field, value)
        np.testing.assert_array_equal(out, value)
        assert out.dtype == np.dtype(dtype)

    @pytest.mark.parametrize('shape', [(0,), (1,), (5, 0, 2), (2, 3, 4, 5)])
    def test_ndarray_codec_edge_shapes(self, shape):
        value = np.zeros(shape, np.float32)
        field = UnischemaField('x', np.float32, shape, NdarrayCodec(), False)
        assert _roundtrip(field.codec, field, value).shape == shape

    def test_fortran_order_array_roundtrips(self):
        value = np.asfortranarray(np.arange(12, dtype=np.float32).reshape(3, 4))
        field = UnischemaField('x', np.float32, (3, 4), NdarrayCodec(), False)
        np.testing.assert_array_equal(_roundtrip(field.codec, field, value), value)
