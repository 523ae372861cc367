"""Train ResNet from an ImageNet-style petastorm_tpu dataset — TPU-native flagship image
pipeline (no direct reference analog: the reference only materializes ImageNet,
examples/imagenet/generate_petastorm_imagenet.py; here we also consume it). Variable-size
stored images are center-cropped/resized on the host worker (TransformSpec) to a static
shape so every device batch is XLA-friendly; normalization + augmentation run on-chip
(petastorm_tpu.ops.image).

Run: ``python -m examples.imagenet.jax_example --dataset-url file:///tmp/imagenet``
"""

import argparse

import jax
import jax.numpy as jnp
import numpy as np
import optax

from examples.imagenet.schema import ImagenetSchema  # noqa: F401  (schema parity anchor)
from examples.imagenet.transforms import IMAGE_HW, make_label_transform, make_transform
from petastorm_tpu import make_reader
from petastorm_tpu.models.resnet import ResNet
from petastorm_tpu.ops.image import normalize_image, random_crop_flip
from petastorm_tpu.parallel.loader import JaxDataLoader

def train(dataset_url, batch_size=8, epochs=1, learning_rate=1e-3,
          stage_sizes=(1, 1, 1, 1), num_filters=16, on_chip_decode=False,
          image_hw=IMAGE_HW, dct_quality=90, reader_pool_type='thread',
          workers_count=4, prefetch=2, scan_chunk=0, verbose=True):
    """``on_chip_decode=True`` reads a DCT-domain store (generate with ``--dct-hw``)
    through a field override so workers ship raw int16 coefficient blocks; dequant +
    IDCT + color conversion then run inside the jitted train step on the device
    (SURVEY.md §7.3 — the decode FLOPs land on the MXU, the host never runs an IDCT)."""
    with make_reader(dataset_url, schema_fields=['noun_id'], num_epochs=1,
                     shuffle_row_groups=False) as scan_reader:
        nouns = sorted({row.noun_id for row in scan_reader})
    class_to_label = {noun: i for i, noun in enumerate(nouns)}

    model = ResNet(stage_sizes=list(stage_sizes), num_classes=len(nouns),
                   num_filters=num_filters)
    variables = model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, image_hw, image_hw, 3)))
    params, batch_stats = variables['params'], variables['batch_stats']
    optimizer = optax.adam(learning_rate)
    opt_state = optimizer.init(params)

    @jax.jit
    def train_step(params, batch_stats, opt_state, rng, images, labels):
        if on_chip_decode:
            from petastorm_tpu.ops.image_decode import dct_decode_images_jax
            images = dct_decode_images_jax(images, quality=dct_quality)
        # On-chip preprocessing: crop/flip augment + bf16 normalize (ops/image.py).
        images = random_crop_flip(rng, images, (image_hw - 8, image_hw - 8))
        images = normalize_image(images, mean=127.5, std=127.5)

        def loss_fn(p):
            logits, updates = model.apply({'params': p, 'batch_stats': batch_stats},
                                          images, train=True, mutable=['batch_stats'])
            loss = optax.softmax_cross_entropy_with_integer_labels(logits, labels).mean()
            return loss, updates['batch_stats']

        (loss, new_stats), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), new_stats, opt_state, loss

    rng = jax.random.PRNGKey(1)
    loss = None
    if on_chip_decode:
        from examples.imagenet.schema import dct_coefficients_field
        override = dct_coefficients_field(image_hw, quality=dct_quality)
        transform = make_label_transform(
            class_to_label, ('image', np.int16,
                             (image_hw // 8, image_hw // 8, 8, 8, 3), False))
        reader_kwargs = dict(field_overrides=[override], transform_spec=transform)
    else:
        reader_kwargs = dict(transform_spec=make_transform(class_to_label,
                                                           image_hw=image_hw))
    with make_reader(dataset_url, num_epochs=epochs, shuffle_rows=True, seed=0,
                     reader_pool_type=reader_pool_type, workers_count=workers_count,
                     **reader_kwargs) as reader:
        loader = JaxDataLoader(reader, batch_size=batch_size, drop_last=True,
                               prefetch=prefetch)
        if scan_chunk:
            # Compiled-chunk streaming: one upload + one dispatch per scan_chunk
            # batches (JaxDataLoader.scan_stream) — the dispatch-bound config for
            # larger-than-HBM stores; the augmentation rng rides the carry.
            def scan_body(carry, batch):
                params, batch_stats, opt_state, rng = carry
                rng, step_rng = jax.random.split(rng)
                params, batch_stats, opt_state, loss = train_step(
                    params, batch_stats, opt_state, step_rng,
                    batch['image'], batch['label'])
                return (params, batch_stats, opt_state, rng), loss

            (params, batch_stats, opt_state, rng), losses = loader.scan_stream(
                scan_body, (params, batch_stats, opt_state, rng),
                chunk_batches=scan_chunk, seed=0)
            loss = losses[-1][-1] if losses else None
            if verbose:
                for chunk in losses:
                    for l in np.asarray(chunk):
                        print('loss {:.4f}'.format(float(l)))
        else:
            for step, batch in enumerate(loader):
                rng, step_rng = jax.random.split(rng)
                params, batch_stats, opt_state, loss = train_step(
                    params, batch_stats, opt_state, step_rng,
                    batch['image'], batch['label'])
                if verbose:
                    print('step {} loss {:.4f}'.format(step, loss))
        stats = loader.stats.as_dict()
        if verbose:
            print('input pipeline stats:', stats)
    return params, batch_stats, (float(loss) if loss is not None else None), stats


def main():
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument('--dataset-url', default='file:///tmp/imagenet')
    parser.add_argument('--batch-size', type=int, default=8)
    parser.add_argument('--epochs', type=int, default=1)
    parser.add_argument('--on-chip-decode', action='store_true',
                        help='read a --dct-hw store and decode on the device')
    parser.add_argument('--image-hw', type=int, default=IMAGE_HW)
    parser.add_argument('--stage-sizes', type=int, nargs='+', default=[1, 1, 1, 1],
                        help='ResNet stage depths, e.g. 3 4 6 3 for ResNet50')
    parser.add_argument('--num-filters', type=int, default=16)
    parser.add_argument('--pool', default='thread',
                        choices=['thread', 'process', 'dummy'],
                        help='reader worker pool (process = spawned workers + '
                             'Arrow IPC wire; the larger-than-HBM streaming config)')
    parser.add_argument('--workers', type=int, default=4)
    parser.add_argument('--prefetch', type=int, default=2)
    parser.add_argument('--scan-chunk', type=int, default=0,
                        help='>0: drive training through scan_stream with this '
                             'many batches per compiled chunk (one H2D + one '
                             'dispatch per chunk)')
    args = parser.parse_args()
    train(args.dataset_url, batch_size=args.batch_size, epochs=args.epochs,
          on_chip_decode=args.on_chip_decode, image_hw=args.image_hw,
          stage_sizes=tuple(args.stage_sizes), num_filters=args.num_filters,
          reader_pool_type=args.pool, workers_count=args.workers,
          prefetch=args.prefetch, scan_chunk=args.scan_chunk)


if __name__ == '__main__':
    main()
