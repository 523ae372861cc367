"""Host-side row transforms of the ImageNet example: jax-free, so the reader's
worker processes that run them never import jax."""

import numpy as np

from petastorm_tpu.transform import TransformSpec

IMAGE_HW = 64


def make_transform(class_to_label, image_hw=IMAGE_HW):
    from examples.imagenet.generate_petastorm_imagenet import _center_resize

    def _transform(row):
        row['image'] = _center_resize(row['image'], image_hw)
        row['label'] = np.int32(class_to_label[row['noun_id']])
        return row

    return TransformSpec(_transform,
                         edit_fields=[('image', np.uint8, (image_hw, image_hw, 3), False),
                                      ('label', np.int32, (), False)],
                         selected_fields=['image', 'label'])


def make_label_transform(class_to_label, image_field_spec):
    """Label mapping for a fixed-size store (DCT or raw): keeps the image field as-is
    (host decode already yields a static shape — or raw coefficient blocks under a
    field override) and adds the integer label."""
    def _transform(row):
        row['label'] = np.int32(class_to_label[row['noun_id']])
        return row

    return TransformSpec(_transform,
                         edit_fields=[image_field_spec, ('label', np.int32, (), False)],
                         selected_fields=['image', 'label'])
