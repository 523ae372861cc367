"""Synthetic photographs and the MLPerf ResNet-50 training crop, in numpy and cv2,
for the image kinds of store.

Jax-free at import: the reader's worker processes import this module to run the
crop, and the reference read runs the same functions on the rows it reads
itself.
"""
import functools

import cv2
import numpy as np

from petastorm_tpu.transform import TransformSpec


def synthetic_photo(rng, h, w, mid_amp, tex_amp, cell=24):
    """Photograph-like uint8 [h, w, 3]: smooth colour fields (coarse noise, cubic
    upsampling), mid-frequency structure and fine texture. Uniform noise would be
    the worst case for JPEG; these land near ImageNet's bytes per image."""
    coarse = rng.integers(0, 256, (max(2, h // cell), max(2, w // cell), 3), dtype=np.uint8)
    img = cv2.resize(coarse, (w, h), interpolation=cv2.INTER_CUBIC).astype(np.int16)
    mid = rng.integers(-mid_amp, mid_amp + 1, (max(2, h // 2), max(2, w // 2), 3),
                       dtype=np.int16)
    img += cv2.resize(mid, (w, h), interpolation=cv2.INTER_LINEAR)
    img += rng.integers(-tex_amp, tex_amp + 1, (h, w, 3), dtype=np.int16)
    return np.clip(img, 0, 255).astype(np.uint8)


def photo_rows(store, side):
    """``store['rows']`` rows of ``label`` and ``image`` (:func:`synthetic_photo`),
    each image ``side(rng)`` = (h, w) in size, from ``store['seed']``."""
    rng = np.random.default_rng(store['seed'])
    for _ in range(store['rows']):
        label = np.int32(rng.integers(store['labels']))
        h, w = side(rng)
        yield {'label': label,
               'image': synthetic_photo(rng, h, w, store['mid_amp'], store['tex_amp'])}


def alter_pixel(batch):
    """One channel of row 0's first pixel moved by 128, as a loader fault would."""
    import jax.numpy as jnp
    x = batch['image']
    index = (0,) * x.ndim
    x = x.at[index].set(((x[index].astype(jnp.int32) + 128) % 256).astype(x.dtype))
    return dict(batch, image=x)


def crop_params(crop_seed, row_id, h, w, scale, ratio, flip):
    """RandomResizedCrop (torchvision's sampling, as MLPerf ResNet-50 trains) and a
    horizontal flip for one row: ``(top, left, height, width, flipped)``, a function
    of ``(crop_seed, row_id)`` alone."""
    rng = np.random.default_rng([int(crop_seed), int(row_id)])
    area = h * w
    log_ratio = np.log(ratio)
    for _ in range(10):
        target = area * rng.uniform(scale[0], scale[1])
        aspect = np.exp(rng.uniform(log_ratio[0], log_ratio[1]))
        cw = int(round(np.sqrt(target * aspect)))
        ch = int(round(np.sqrt(target / aspect)))
        if 0 < cw <= w and 0 < ch <= h:
            top = int(rng.integers(0, h - ch + 1))
            left = int(rng.integers(0, w - cw + 1))
            break
    else:  # the central crop at the nearest allowed aspect
        in_ratio = w / h
        if in_ratio < ratio[0]:
            cw, ch = w, int(round(w / ratio[0]))
        elif in_ratio > ratio[1]:
            ch, cw = h, int(round(h * ratio[1]))
        else:
            cw, ch = w, h
        top, left = (h - ch) // 2, (w - cw) // 2
    return top, left, ch, cw, bool(rng.uniform() < flip)


def random_resized_crop(image, crop_seed, row_id, out_hw, scale, ratio, flip):
    """uint8 [H, W, 3] -> uint8 [out_hw, out_hw, 3], bilinear, as MLPerf trains."""
    top, left, ch, cw, flipped = crop_params(crop_seed, row_id, image.shape[0],
                                             image.shape[1], scale, ratio, flip)
    out = cv2.resize(image[top:top + ch, left:left + cw], (out_hw, out_hw),
                     interpolation=cv2.INTER_LINEAR)
    return np.ascontiguousarray(out[:, ::-1]) if flipped else out


def _crop_row(row, crop_seed, out_hw, scale, ratio, flip):
    row['image'] = random_resized_crop(row['image'], crop_seed, row['id'], out_hw, scale,
                                       ratio, flip)
    return row


def crop_transform(crop_seed, out_hw, scale, ratio, flip):
    """The worker-side TransformSpec of the crop: keeps ``id`` and ``label``."""
    func = functools.partial(_crop_row, crop_seed=crop_seed, out_hw=out_hw,
                             scale=tuple(scale), ratio=tuple(ratio), flip=flip)
    return TransformSpec(func, edit_fields=[('image', np.uint8, (out_hw, out_hw, 3), False)],
                         selected_fields=['id', 'label', 'image'])
