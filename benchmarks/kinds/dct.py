"""Photographs at a fixed side stored as quantized DCT coefficients
(``DctImageCodec``), the fields the mix names decoded on the device."""
import io
import struct

import numpy as np

from benchmarks import images

COLUMNS = ('label', 'image')
alter = images.alter_pixel

#: JPEG Annex K base tables (luminance, chrominance)
_LUMA = np.array([[16, 11, 10, 16, 24, 40, 51, 61], [12, 12, 14, 19, 26, 58, 60, 55],
                  [14, 13, 16, 24, 40, 57, 69, 56], [14, 17, 22, 29, 51, 87, 80, 62],
                  [18, 22, 37, 56, 68, 109, 103, 77], [24, 35, 55, 64, 81, 104, 113, 92],
                  [49, 64, 78, 87, 103, 121, 120, 101], [72, 92, 95, 98, 112, 100, 103, 99]],
                 np.float64)
_CHROMA = np.full((8, 8), 99.0)
_CHROMA[:4, :4] = [[17, 18, 24, 47], [18, 21, 26, 66], [24, 26, 56, 99], [47, 66, 99, 99]]


def fields(store):
    from petastorm_tpu.codecs import DctImageCodec, ScalarCodec
    from petastorm_tpu.unischema import UnischemaField
    hw = store['hw']
    return [UnischemaField('label', np.int32, (), ScalarCodec(), False),
            UnischemaField('image', np.uint8, (hw, hw, 3),
                           DctImageCodec(quality=store['quality']), False)]


def rows(store):
    return images.photo_rows(store, lambda rng: (store['hw'], store['hw']))


def reader_kwargs(mix, seeds):
    return {'device_decode_fields': list(mix['device_decode_fields'])}


def _dct_decode(blob, quality):
    """The stored DCT record (``DCT1``, height and width, then an .npy of int16
    [H/8, W/8, 8, 8, 3] quantized coefficients) to uint8 RGB, in float64 with
    scipy's orthonormal inverse DCT and the JFIF colour transform."""
    from scipy.fft import idctn
    if blob[:4] != b'DCT1':
        raise ValueError('not a DCT record')
    h, w = struct.unpack('<HH', blob[4:8])
    coeffs = np.load(io.BytesIO(blob[8:]), allow_pickle=False).astype(np.float64)
    scale = 5000.0 / quality if quality < 50 else 200.0 - 2.0 * quality
    tables = np.stack([np.clip(np.floor((base * scale + 50.0) / 100.0), 1, 255)
                       for base in (_LUMA, _CHROMA, _CHROMA)], axis=-1)
    blocks = idctn(coeffs * tables, axes=(2, 3), norm='ortho')
    h8, w8 = blocks.shape[:2]
    ycc = blocks.transpose(0, 2, 1, 3, 4).reshape(h8 * 8, w8 * 8, 3) + 128.0
    y, cb, cr = ycc[..., 0], ycc[..., 1] - 128.0, ycc[..., 2] - 128.0
    rgb = np.stack([y + 1.402 * cr, y - 0.344136 * cb - 0.714136 * cr, y + 1.772 * cb], -1)
    return np.clip(np.round(rgb), 0, 255).astype(np.uint8)[:h, :w]


def plain_rows(mix, table, ids, seeds):
    quality = mix['store']['quality']
    return {'label': np.asarray([table['label'][i] for i in ids], np.int32),
            'image': np.stack([_dct_decode(table['image'][i], quality) for i in ids])}
