"""Materialize an ImageNet-style petastorm_tpu dataset (parity: reference
examples/imagenet/generate_petastorm_imagenet.py, which scans an on-disk ImageNet tree
with Spark; here either a directory of ``<noun_id>/*.jpg|png`` images or an offline
synthetic mode).

Run: ``python -m examples.imagenet.generate_petastorm_imagenet -o file:///tmp/imagenet
--synthetic``
"""

import argparse
import os

import numpy as np

from examples.imagenet.schema import ImagenetSchema
from petastorm_tpu.etl.dataset_metadata import write_rows

SYNTHETIC_NOUNS = {'n01440764': 'tench', 'n01443537': 'goldfish', 'n01484850': 'shark'}


def synthetic_imagenet_rows(images_per_class=4, seed=0, hw=(96, 128)):
    """Random RGB rows for every synthetic noun; ``hw`` is the inclusive
    ``(min, max)`` range of each image side."""
    rng = np.random.default_rng(seed)
    rows = []
    for noun_id, text in SYNTHETIC_NOUNS.items():
        for _ in range(images_per_class):
            h = int(rng.integers(hw[0], hw[1], endpoint=True))
            w = int(rng.integers(hw[0], hw[1], endpoint=True))
            rows.append({'noun_id': noun_id, 'text': text,
                         'image': rng.integers(0, 255, size=(h, w, 3),
                                               dtype=np.uint8)})
    return rows


def directory_imagenet_rows(imagenet_dir, noun_id_to_text=None):
    """Scan ``<imagenet_dir>/<noun_id>/*`` images into rows."""
    import cv2
    rows = []
    for noun_id in sorted(os.listdir(imagenet_dir)):
        class_dir = os.path.join(imagenet_dir, noun_id)
        if not os.path.isdir(class_dir):
            continue
        text = (noun_id_to_text or {}).get(noun_id, noun_id)
        for name in sorted(os.listdir(class_dir)):
            image_bgr = cv2.imread(os.path.join(class_dir, name))
            if image_bgr is None:
                continue
            rows.append({'noun_id': noun_id, 'text': text,
                         'image': cv2.cvtColor(image_bgr, cv2.COLOR_BGR2RGB)})
    return rows


def _center_resize(image, hw):
    """Center-crop to square + nearest-neighbor resize to (hw, hw) — host numpy."""
    h, w = image.shape[:2]
    side = min(h, w)
    top, left = (h - side) // 2, (w - side) // 2
    square = image[top:top + side, left:left + side]
    idx = np.arange(hw) * side // hw
    return np.ascontiguousarray(square[idx][:, idx])


def generate_petastorm_imagenet(output_url, imagenet_dir=None, synthetic=False,
                                rowgroup_size_mb=8, dct_hw=None, dct_quality=90,
                                images_per_class=4, seed=0, hw=(96, 128)):
    """``dct_hw`` switches to the fixed-size DCT-domain store (schema.py
    dct_imagenet_schema): images are resized at write time and stored as quantized DCT
    coefficient blocks so readers can decode on-chip. ``images_per_class``, ``seed``
    and ``hw`` size the synthetic rows (:func:`synthetic_imagenet_rows`)."""
    rows = (synthetic_imagenet_rows(images_per_class, seed, hw) if synthetic
            else directory_imagenet_rows(imagenet_dir))
    if dct_hw is not None:
        from examples.imagenet.schema import dct_imagenet_schema
        for row in rows:
            row['image'] = _center_resize(row['image'], dct_hw)
        schema = dct_imagenet_schema(dct_hw, quality=dct_quality)
    else:
        schema = ImagenetSchema
    write_rows(output_url, schema, rows, rowgroup_size_mb=rowgroup_size_mb)
    print('wrote {} rows to {}'.format(len(rows), output_url))


def main():
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument('-o', '--output-url', default='file:///tmp/imagenet')
    parser.add_argument('-i', '--imagenet-dir', default=None,
                        help='directory of <noun_id>/*.jpg class folders')
    parser.add_argument('--synthetic', action='store_true',
                        help='generate random images instead of scanning a directory')
    parser.add_argument('--dct-hw', type=int, default=None,
                        help='write the DCT-domain store with images resized to this '
                             'size (multiple of 8) for on-chip decode')
    parser.add_argument('--dct-quality', type=int, default=90)
    args = parser.parse_args()
    generate_petastorm_imagenet(args.output_url, imagenet_dir=args.imagenet_dir,
                                synthetic=args.synthetic or args.imagenet_dir is None,
                                dct_hw=args.dct_hw, dct_quality=args.dct_quality)


if __name__ == '__main__':
    main()
