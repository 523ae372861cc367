"""Photographs of ILSVRC-2012's sizes stored as JPEG (``CompressedImageCodec('jpeg')``),
read through the MLPerf ResNet-50 training crop in the reader's workers."""
import numpy as np

from benchmarks import images

COLUMNS = ('label', 'image')
alter = images.alter_pixel


def fields(store):
    from petastorm_tpu.codecs import CompressedImageCodec, ScalarCodec
    from petastorm_tpu.unischema import UnischemaField
    return [UnischemaField('label', np.int32, (), ScalarCodec(), False),
            UnischemaField('image', np.uint8, (None, None, 3),
                           CompressedImageCodec('jpeg', quality=store['quality']), False)]


def rows(store):
    def side(rng):
        h, w = store['sides'][int(rng.integers(len(store['sides'])))]
        jitter = store['side_jitter']
        return (int(round(h * rng.uniform(1 - jitter, 1 + jitter))),
                int(round(w * rng.uniform(1 - jitter, 1 + jitter))))

    return images.photo_rows(store, side)


def reader_kwargs(mix, seeds):
    crop = mix['transform']
    return {'transform_spec': images.crop_transform(seeds['crop'], crop['out_hw'],
                                                    crop['scale'], crop['ratio'],
                                                    crop['flip'])}


def plain_rows(mix, table, ids, seeds):
    """cv2's decode of each stored JPEG, then the same crop from (seed, row id)."""
    import cv2
    crop = mix['transform']
    out = []
    for i in ids:
        bgr = cv2.imdecode(np.frombuffer(table['image'][i], np.uint8), cv2.IMREAD_COLOR)
        out.append(images.random_resized_crop(cv2.cvtColor(bgr, cv2.COLOR_BGR2RGB),
                                              seeds['crop'], i, crop['out_hw'],
                                              crop['scale'], crop['ratio'], crop['flip']))
    return {'label': np.asarray([table['label'][i] for i in ids], np.int32),
            'image': np.stack(out)}
