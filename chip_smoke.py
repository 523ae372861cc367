"""Bring-up smoke of the main path on a TPU: Parquet store -> ``make_reader`` ->
``JaxDataLoader`` / ``InMemJaxLoader`` -> jitted train step, at full model width.

    python chip_smoke.py [--seed N]              # one chip: four phases
    python chip_smoke.py --chips 4 [--seed N]    # the 4-chip data-parallel path only

One chip runs, in one process and in order:

- ``resnet50_stream``: ResNet-50 as published (stages 3-4-6-3, 64 filters, 1000
  classes, bf16) trained from a synthetic ImageNet store of 1,536 224x224 rows
  through ``make_reader(reader_pool_type='process')`` -> ``JaxDataLoader``; the
  first step's loss, logits and parameter update are checked against the same
  step in float32 on the CPU.
- ``device_decode``: a DCT ImageNet store plus a stored-deflate ndarray field read
  with ``device_decode_fields``; decoded batches are checked against the host
  decode of the same rows.
- ``mnist_inmem``: ``InMemJaxLoader.scan_epochs`` over 50,000 MNIST-shaped rows.
- ``flash``: ``TransformerLM`` on the Pallas flash-attention kernels at T=8192,
  head_dim 128, B=2; the kernels' output and dq/dk/dv (plain and segmented) are
  checked against dense attention, next to bf16 controls.

``--chips 4`` runs only ``resnet50_dp``: a 4-device ``data`` mesh fed by the
loader with a global batch of 256; the first step's loss and parameter update are
checked against the same step on one device.

Every dataset is generated from ``--seed`` under ``<checkout>/.smoke_data``. Each
phase prints one JSON line; the last line is ``{"ok": true, "device": {...}}``.
Without a TPU (``JAX_PLATFORMS=cpu`` included) it exits non-zero before any phase.
"""
import argparse
import contextlib
import dataclasses
import faulthandler
import json
import os
import shutil
import sys
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from examples.imagenet.generate_petastorm_imagenet import (
    SYNTHETIC_NOUNS, generate_petastorm_imagenet, synthetic_imagenet_rows)
from examples.imagenet.transforms import make_label_transform
from examples.imagenet.schema import dct_imagenet_schema
from petastorm_tpu import make_reader
from petastorm_tpu.benchmark.compile_cache import configure_compile_cache
from petastorm_tpu.codecs import CompressedNdarrayCodec, NdarrayCodec, ScalarCodec
from petastorm_tpu.etl.dataset_metadata import write_rows
from petastorm_tpu.models import MnistCNN, TransformerLM, next_token_loss
from petastorm_tpu.models.resnet import ResNet
from petastorm_tpu.ops.flash_attention import (_use_pallas, flash_attention,
                                               flash_attention_segmented)
from petastorm_tpu.ops.image import normalize_image
from petastorm_tpu.ops.packing import masked_dense_attention, segment_mask
from petastorm_tpu.ops.ring_attention import dense_attention
from petastorm_tpu.parallel import InMemJaxLoader, JaxDataLoader
from petastorm_tpu.unischema import Unischema, UnischemaField

REPO = os.path.dirname(os.path.abspath(__file__))
IMAGENET_MEAN = (0.485, 0.456, 0.406)
IMAGENET_STD = (0.229, 0.224, 0.225)
#: SGD step of both ResNet phases: 0.1 (the published rate for batch 256, without
#: its warm-up) took the loss from 6.72 to 28.31 in 9 steps on the chip
RESNET_LR = 0.01


@dataclasses.dataclass(frozen=True)
class Sizes:
    """Every shape the phases use. The defaults are the chip run; tests shrink them."""

    image_hw: int = 224
    resnet_stages: tuple = (3, 4, 6, 3)
    resnet_filters: int = 64
    resnet_rows_per_class: int = 512     # 3 synthetic classes -> 1,536 rows
    resnet_batch: int = 128
    resnet_steps: int = 8
    workers: int = 8
    dct_rows_per_class: int = 64         # 192 rows
    dct_batch: int = 64
    vector_width: int = 1024             # the stored-deflate float32 field
    mnist_rows: int = 50000
    mnist_batch: int = 2048
    flash_t: int = 8192
    flash_batch: int = 2
    flash_embed: int = 512
    flash_heads: int = 4
    flash_layers: int = 4
    flash_steps: int = 3
    flash_segmented_t: int = 2048
    dp_rows_per_class: int = 342         # 1,026 rows -> 4 global batches of 256
    dp_batch: int = 256
    dp_steps: int = 3


def log(msg):
    print(msg, file=sys.stderr, flush=True)


class Laps:
    """Logs the seconds each step of a phase took, to stderr."""

    def __init__(self, phase):
        self.phase = phase
        self.last = time.perf_counter()

    def __call__(self, step):
        now = time.perf_counter()
        log('{}: {} {:.1f}s'.format(self.phase, step, now - self.last))
        self.last = now


def peak_hbm_bytes(devices):
    """Highest ``peak_bytes_in_use`` over ``devices`` so far in this process."""
    peaks = [(d.memory_stats() or {}).get('peak_bytes_in_use') for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def _url(path):
    return 'file://' + path


def _fresh_dir(data_dir, name):
    path = os.path.join(data_dir, name)
    shutil.rmtree(path, ignore_errors=True)
    return path


def _imagenet_store(data_dir, name, seed, rows_per_class, hw):
    url = _url(_fresh_dir(data_dir, name))
    with contextlib.redirect_stdout(sys.stderr):
        generate_petastorm_imagenet(url, synthetic=True, images_per_class=rows_per_class,
                                    seed=seed, hw=(hw, hw))
    return url


def _imagenet_reader(url, sizes, seed):
    labels = {noun: i for i, noun in enumerate(sorted(SYNTHETIC_NOUNS))}
    transform = make_label_transform(
        labels, ('image', np.uint8, (sizes.image_hw, sizes.image_hw, 3), False))
    return make_reader(url, reader_pool_type='process', workers_count=sizes.workers,
                       num_epochs=1, shuffle_row_groups=True, seed=seed,
                       transform_spec=transform)


def _resnet(sizes, dtype=jnp.bfloat16):
    return ResNet(stage_sizes=list(sizes.resnet_stages), num_filters=sizes.resnet_filters,
                  num_classes=1000, dtype=dtype)


def _resnet_fns(model, optimizer):
    """(loss_fn, train_step) of the image-classification consumer."""
    def loss_fn(params, batch_stats, images, labels):
        x = normalize_image(images, IMAGENET_MEAN, IMAGENET_STD)
        logits, updates = model.apply({'params': params, 'batch_stats': batch_stats}, x,
                                      train=True, mutable=['batch_stats'])
        loss = optax.softmax_cross_entropy_with_integer_labels(logits, labels).mean()
        return loss, (updates['batch_stats'], logits)

    def train_step(state, images, labels):
        params, batch_stats, opt_state = state
        (loss, (batch_stats, logits)), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params, batch_stats, images, labels)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        return (optax.apply_updates(params, updates), batch_stats, opt_state), loss, logits

    return loss_fn, jax.jit(train_step)


def _resnet_state(model, optimizer, sizes, seed):
    hw = sizes.image_hw
    variables = jax.jit(model.init)(jax.random.PRNGKey(seed),
                                    jnp.zeros((1, hw, hw, 3), jnp.float32))
    params = variables['params']
    return params, variables['batch_stats'], optimizer.init(params)


def _first_leaf(tree):
    return np.asarray(jax.tree.leaves(tree)[0], dtype=np.float32)


def _rel_l2(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / max(np.linalg.norm(want), 1e-12))


def _update(before, after):
    """Every parameter's change in one step, flattened to one float64 vector."""
    leaves = zip(jax.tree.leaves(jax.device_get(before)),
                 jax.tree.leaves(jax.device_get(after)))
    return np.concatenate([(np.asarray(b, np.float64) - np.asarray(a, np.float64)).ravel()
                           for a, b in leaves])


def phase_resnet_stream(sizes, data_dir, seed):
    """ResNet-50 trained from the process-pool streaming loader; the first step is
    checked against the same step in float32 on the CPU backend of this process."""
    lap = Laps('resnet50_stream')
    url = _imagenet_store(data_dir, 'imagenet', seed, sizes.resnet_rows_per_class,
                          sizes.image_hw)
    lap('store')
    model = _resnet(sizes)
    optimizer = optax.sgd(RESNET_LR, momentum=0.9)
    _, step = _resnet_fns(model, optimizer)
    state = _resnet_state(model, optimizer, sizes, seed)
    init_state = jax.device_get(state)
    lap('init')
    with JaxDataLoader(_imagenet_reader(url, sizes, seed),
                       batch_size=sizes.resnet_batch) as loader:
        batches = iter(loader)
        first = next(batches)
        lap('first batch')
        start = time.perf_counter()
        compiled = step.lower(state, first['image'], first['label']).compile()
        compile_s = time.perf_counter() - start
        state, loss0, logits0 = compiled(state, first['image'], first['label'])
        loss0, logits0 = float(loss0), np.asarray(logits0)
        update0 = _update(init_state[0], state[0])
        start = time.perf_counter()
        losses = []
        for _ in range(sizes.resnet_steps):
            batch = next(batches)
            state, loss, _ = compiled(state, batch['image'], batch['label'])
            losses.append(loss)
        jax.block_until_ready(state)
        elapsed = time.perf_counter() - start
        # a value fetched after block_until_ready: ~0 s when the wait was real
        start = time.perf_counter()
        losses = [float(v) for v in losses]
        readback_s = time.perf_counter() - start
        lap('compile and steps')
        batches.close()
        stall = loader.stats.as_dict()['input_stall_fraction']
    lap('loader stop')

    # the same first step, float32, on the CPU backend of this process
    _, ref_step = _resnet_fns(_resnet(sizes, jnp.float32), optimizer)
    ref_state, ref_loss, ref_logits = ref_step(*jax.device_put(
        (init_state, np.asarray(first['image']), np.asarray(first['label'])),
        jax.devices('cpu')[0]))
    ref_loss = float(ref_loss)
    logits_err = _rel_l2(logits0, ref_logits)
    ref_update = _update(init_state[0], ref_state[0])
    update_err = _rel_l2(update0, ref_update)
    update_cos = float(update0 @ ref_update / max(
        np.linalg.norm(update0) * np.linalg.norm(ref_update), 1e-30))
    lap('cpu reference')

    assert np.isfinite([loss0] + losses).all(), (loss0, losses)
    assert np.abs(update0).max() > 0, 'parameters did not change'
    assert abs(loss0 - ref_loss) <= 2e-2 * abs(ref_loss), (loss0, ref_loss)
    assert logits_err <= 5e-2, logits_err
    # bf16 against float32 turns the update by a few degrees (cosine 0.966 for a
    # 1-1-1-1 ResNet at 32x32, batch 8, on the CPU); a wrong gradient or optimizer
    # step turns it by tens of degrees or flips it
    assert update_cos >= 0.9, (update_cos, update_err)
    rows = sizes.resnet_steps * sizes.resnet_batch
    return {'rows_per_s': rows / elapsed, 'step_time_s': elapsed / sizes.resnet_steps,
            'compile_s': compile_s, 'input_stall_fraction': stall,
            'first_loss': loss0, 'cpu_reference_loss': ref_loss,
            'logits_rel_l2_vs_cpu': logits_err, 'update_rel_l2_vs_cpu': update_err,
            'update_cosine_vs_cpu': update_cos,
            'losses': [loss0] + losses, 'readback_after_block_s': readback_s}


def _dct_store(data_dir, seed, sizes):
    """DCT ImageNet rows plus ``idx`` and a float32 vector written at deflate
    level 0: its frames hold stored blocks only, so the device inflates it."""
    hw = sizes.image_hw
    schema = Unischema('SmokeDctImagenet', list(dct_imagenet_schema(hw).fields.values()) + [
        UnischemaField('idx', np.int64, (), ScalarCodec(), False),
        UnischemaField('vector', np.float32, (sizes.vector_width,),
                       CompressedNdarrayCodec(compresslevel=0), False)])
    rng = np.random.default_rng(seed)
    rows = synthetic_imagenet_rows(sizes.dct_rows_per_class, seed, (hw, hw))
    for i, row in enumerate(rows):
        row['idx'] = i
        row['vector'] = rng.standard_normal(sizes.vector_width).astype(np.float32)
    url = _url(_fresh_dir(data_dir, 'dct_imagenet'))
    write_rows(url, schema, rows, rowgroup_size_mb=8)
    return url


def phase_device_decode(sizes, data_dir, seed):
    """Device decode tail: DCT images and a stored-deflate vector decoded on the
    device, compared with the host decode of the same rows."""
    lap = Laps('device_decode')
    url = _dct_store(data_dir, seed, sizes)
    lap('store')
    fields = ['idx', 'image', 'vector']
    with JaxDataLoader(make_reader(url, schema_fields=fields, workers_count=sizes.workers,
                                   num_epochs=1, shuffle_row_groups=False,
                                   device_decode_fields=['image', 'vector']),
                       batch_size=sizes.dct_batch) as loader:
        start = time.perf_counter()
        device_rows, first_batch_s = {}, None
        for batch in loader:
            batch = jax.device_get(batch)
            if first_batch_s is None:
                first_batch_s = time.perf_counter() - start
                start = time.perf_counter()
            for i, idx in enumerate(batch['idx']):
                device_rows[int(idx)] = (batch['image'][i], batch['vector'][i])
        elapsed = time.perf_counter() - start
        stats = loader.stats.as_dict()
        recipes = [entry[0] for recipe in loader._device_stage._programs
                   for entry in recipe]
        lap('device read')
    lap('loader stop')
    with make_reader(url, schema_fields=fields, workers_count=sizes.workers, num_epochs=1,
                     shuffle_row_groups=False) as reader:
        host_rows = {int(row.idx): (row.image, row.vector) for row in reader}
    lap('host read')

    assert stats['device_decode_batches'] > 0, stats
    assert stats['device_fallback_batches'] == 0, stats
    assert 'stored' in recipes and 'dct' in recipes, recipes
    assert device_rows and set(device_rows) <= set(host_rows)
    image_diff = max(int(np.abs(img.astype(np.int32) - host_rows[i][0]).max())
                     for i, (img, _) in device_rows.items())
    assert image_diff <= 2, image_diff
    for i, (_, vector) in device_rows.items():
        np.testing.assert_array_equal(vector, host_rows[i][1])
    rows_after_first = len(device_rows) - sizes.dct_batch
    return {'rows_per_s': rows_after_first / elapsed if elapsed > 0 else None,
            'first_batch_s': first_batch_s, 'rows': len(device_rows),
            'device_decode_batches': stats['device_decode_batches'],
            'device_fallback_batches': stats['device_fallback_batches'],
            'image_max_abs_diff_vs_host': image_diff, 'recipes': sorted(set(recipes))}


def _mnist_store(data_dir, seed, rows):
    schema = Unischema('SmokeMnist', [
        UnischemaField('idx', np.int64, (), ScalarCodec(), False),
        UnischemaField('digit', np.int64, (), ScalarCodec(), False),
        UnischemaField('image', np.uint8, (28, 28), NdarrayCodec(), False),
    ])
    rng = np.random.default_rng(seed)
    digits = rng.integers(0, 10, rows)
    images = rng.integers(0, 256, (rows, 28, 28), dtype=np.uint8)
    url = _url(_fresh_dir(data_dir, 'mnist'))
    write_rows(url, schema, ({'idx': i, 'digit': int(digits[i]), 'image': images[i]}
                             for i in range(rows)), rowgroup_size_mb=8, n_files=4)
    return url


def phase_mnist_inmem(sizes, data_dir, seed):
    """bench.py's headline path: one HBM fill, then whole epochs of MnistCNN as one
    compiled ``scan_epochs`` program each."""
    url = _mnist_store(data_dir, seed, sizes.mnist_rows)
    model = MnistCNN()
    optimizer = optax.sgd(0.01)
    params = jax.jit(model.init)(jax.random.PRNGKey(seed),
                                 jnp.zeros((sizes.mnist_batch, 28, 28, 1)))
    before = _first_leaf(params)

    def step(carry, batch):
        p, o = carry
        images = normalize_image(batch['image'][..., None], mean=[0.1307], std=[0.3081])

        def loss_fn(p):
            logits = model.apply(p, images)
            return optax.softmax_cross_entropy_with_integer_labels(
                logits, batch['digit']).mean()

        loss, grads = jax.value_and_grad(loss_fn)(p)
        updates, o = optimizer.update(grads, o, p)
        return (optax.apply_updates(p, updates), o), loss

    start = time.perf_counter()
    loader = InMemJaxLoader(make_reader(url, workers_count=sizes.workers, num_epochs=1,
                                        shuffle_row_groups=True, seed=seed),
                            batch_size=sizes.mnist_batch, num_epochs=None, shuffle=True,
                            seed=seed, drop_last=True)
    fill_s = time.perf_counter() - start
    carry = (params, optimizer.init(params))
    start = time.perf_counter()
    carry, losses = loader.scan_epochs(step, carry, num_epochs=1)
    jax.block_until_ready(carry)
    first_epoch_s = time.perf_counter() - start
    start = time.perf_counter()
    carry, losses = loader.scan_epochs(step, carry, num_epochs=1)
    jax.block_until_ready(carry)
    epoch_s = time.perf_counter() - start
    losses = np.asarray(losses)
    assert np.isfinite(losses).all(), losses
    assert not np.array_equal(before, _first_leaf(carry[0])), 'parameters did not change'
    rows = len(loader) * sizes.mnist_batch
    return {'rows_per_s': rows / epoch_s, 'step_time_s': epoch_s / len(loader),
            'compile_and_first_epoch_s': first_epoch_s, 'fill_s': fill_s,
            'rows_per_epoch': rows, 'last_loss': float(losses.reshape(-1)[-1])}


def _attention_vjp(attend, q, k, v, g):
    """(out, dq, dk, dv) of ``attend`` at float32, for the cotangent ``g``."""
    def run(q, k, v, g):
        out, vjp = jax.vjp(attend, q, k, v)
        return tuple(x.astype(jnp.float32) for x in (out,) + vjp(g.astype(out.dtype)))
    return jax.jit(run)(q, k, v, g)


def _check_attention(name, flash, dense, shape, seed):
    """Relative L2 error of the flash kernels' output and dq/dk/dv against ``dense``
    at float32 ``highest``, next to two controls: ``dense`` at the backend's
    default matmul precision, and ``dense`` on bf16-rounded inputs. A kernel whose
    dots ran in one bf16 pass would land with the controls; it must stay 10x
    under them. (On the CPU the default precision is float32, so only the bf16
    control separates there.)"""
    rng = np.random.default_rng(seed)
    q, k, v, g = (jnp.asarray(rng.standard_normal(shape), jnp.float32) for _ in range(4))
    with jax.default_matmul_precision('highest'):
        want = _attention_vjp(dense, q, k, v, g)
    errs = lambda got: [_rel_l2(a, b) for a, b in zip(got, want)]  # noqa: E731
    flash_errs = errs(_attention_vjp(flash, q, k, v, g))
    controls = {'default_precision': errs(_attention_vjp(dense, q, k, v, g)),
                'bf16_inputs': errs(_attention_vjp(
                    dense, *(x.astype(jnp.bfloat16) for x in (q, k, v, g))))}
    beat = ['bf16_inputs'] + (['default_precision'] if jax.default_backend() == 'tpu'
                              else [])
    for control in beat:
        for part, err, ctl in zip(('out', 'dq', 'dk', 'dv'), flash_errs,
                                  controls[control]):
            assert err <= 0.1 * ctl, (name, part, err, control, ctl)
    fields = {'{}_rel_l2_vs_dense'.format(name): dict(zip(('out', 'dq', 'dk', 'dv'),
                                                           flash_errs))}
    for control, values in controls.items():
        fields['{}_control_{}'.format(name, control)] = dict(
            zip(('out', 'dq', 'dk', 'dv'), values))
    return fields


def _segments(b, t, seed):
    """[B, T] packed-segment ids: a few documents per row, then padding (0)."""
    rng = np.random.default_rng(seed)
    rows = []
    for _ in range(b):
        cuts = np.sort(rng.choice(np.arange(1, t - t // 8), 3, replace=False))
        row = np.zeros(t, np.int32)
        for doc, (lo, hi) in enumerate(zip((0,) + tuple(cuts), tuple(cuts) + (t - t // 8,))):
            row[lo:hi] = doc + 1
        rows.append(row)
    return jnp.asarray(np.stack(rows))


def phase_flash(sizes, seed):
    """TransformerLM on the Pallas flash-attention kernels at the long-context shape;
    the kernels' output and gradients, plain and segmented, are checked against
    dense attention."""
    t, b, heads = sizes.flash_t, sizes.flash_batch, sizes.flash_heads
    head_dim = sizes.flash_embed // heads
    shape = (b, t, heads, head_dim)
    assert _use_pallas(jax.ShapeDtypeStruct(shape, jnp.float32),
                       jax.ShapeDtypeStruct(shape, jnp.float32), 'auto', 'auto'), \
        'shape would fall back to dense attention'
    checks = _check_attention('flash', lambda q, k, v: flash_attention(q, k, v, causal=True),
                              lambda q, k, v: dense_attention(q, k, v, causal=True),
                              shape, seed)
    seg_shape = (b, sizes.flash_segmented_t, heads, head_dim)
    segments = _segments(b, sizes.flash_segmented_t, seed)
    mask = segment_mask(segments, segments, causal=True)
    checks.update(_check_attention(
        'segmented',
        lambda q, k, v: flash_attention_segmented(q, k, v, segments, causal=True),
        lambda q, k, v: masked_dense_attention(q, k, v, mask), seg_shape, seed + 1))
    rng = np.random.default_rng(seed)

    model = TransformerLM(vocab=256, embed=sizes.flash_embed, heads=heads,
                          layers=sizes.flash_layers, max_len=t,
                          attention_fn=lambda q, k, v: flash_attention(q, k, v, causal=True))
    optimizer = optax.adam(3e-4)
    tokens = jnp.asarray(rng.integers(0, 256, (b, t)), jnp.int32)
    params = jax.jit(model.init)(jax.random.PRNGKey(seed), tokens)
    opt_state = optimizer.init(params)

    @jax.jit
    def train_step(params, opt_state, tokens):
        loss, grads = jax.value_and_grad(
            lambda p: next_token_loss(model.apply(p, tokens), tokens))(params)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    start = time.perf_counter()
    compiled = train_step.lower(params, opt_state, tokens).compile()
    compile_s = time.perf_counter() - start
    kernel_in_step = 'tpu_custom_call' in compiled.as_text()
    params, opt_state, loss = compiled(params, opt_state, tokens)
    jax.block_until_ready(loss)
    losses = []
    start = time.perf_counter()
    for _ in range(sizes.flash_steps):
        params, opt_state, loss = compiled(params, opt_state, tokens)
        losses.append(loss)
    jax.block_until_ready(params)
    elapsed = time.perf_counter() - start
    losses = [float(x) for x in losses]
    assert np.isfinite(losses).all(), losses
    return dict(checks, tokens_per_s=sizes.flash_steps * b * t / elapsed,
                step_time_s=elapsed / sizes.flash_steps, compile_s=compile_s,
                kernel_in_step=kernel_in_step, last_loss=losses[-1])


def phase_resnet_dp(sizes, data_dir, seed, devices):
    """Mesh-sharded data parallelism: the loader feeds a ``data`` mesh over
    ``devices``; the first step's loss and parameter update are checked against
    the same step on one device with the same global batch."""
    mesh = Mesh(np.asarray(devices), ('data',))
    url = _imagenet_store(data_dir, 'imagenet_dp', seed, sizes.dp_rows_per_class,
                          sizes.image_hw)
    model = _resnet(sizes)
    optimizer = optax.sgd(RESNET_LR, momentum=0.9)
    _, step = _resnet_fns(model, optimizer)
    host_state = jax.device_get(_resnet_state(model, optimizer, sizes, seed))
    state = jax.device_put(host_state, NamedSharding(mesh, P()))
    with JaxDataLoader(_imagenet_reader(url, sizes, seed), batch_size=sizes.dp_batch,
                       mesh=mesh, partition_spec=P('data')) as loader:
        batches = iter(loader)
        first = next(batches)
        for name, leaf in first.items():
            shards = leaf.addressable_shards
            assert len(shards) == len(devices), (name, len(shards))
            assert len({s.device for s in shards}) == len(devices), name
        start = time.perf_counter()
        compiled = step.lower(state, first['image'], first['label']).compile()
        compile_s = time.perf_counter() - start
        state, loss_dp, _ = compiled(state, first['image'], first['label'])
        loss_dp = float(loss_dp)
        update_dp = _update(host_state[0], state[0])
        start = time.perf_counter()
        losses = []
        for _ in range(sizes.dp_steps):
            batch = next(batches)
            state, loss, _ = compiled(state, batch['image'], batch['label'])
            losses.append(loss)
        jax.block_until_ready(state)
        elapsed = time.perf_counter() - start
        batches.close()
    one = jax.device_put((host_state, np.asarray(first['image']),
                          np.asarray(first['label'])), devices[0])
    state_one, loss_one, _ = step(*one)
    loss_one = float(loss_one)
    # a gradient that missed the cross-device reduction is a quarter batch's
    update_err = _rel_l2(update_dp, _update(host_state[0], state_one[0]))
    losses = [float(x) for x in losses]
    assert np.isfinite([loss_dp] + losses).all(), (loss_dp, losses)
    assert abs(loss_dp - loss_one) <= 5e-3 * abs(loss_one), (loss_dp, loss_one)
    assert update_err <= 0.1, update_err
    rows = sizes.dp_steps * sizes.dp_batch
    return {'rows_per_s': rows / elapsed, 'step_time_s': elapsed / sizes.dp_steps,
            'compile_s': compile_s, 'first_loss': loss_dp,
            'single_device_loss': loss_one, 'update_rel_l2_vs_one_device': update_err,
            'losses': [loss_dp] + losses, 'shards_per_leaf': len(devices)}


def require_tpu(chips):
    """The accelerator this run measures, or exit non-zero: no CPU fallback."""
    if os.environ.get('JAX_PLATFORMS', '').strip().lower() == 'cpu':
        sys.exit('chip_smoke: JAX_PLATFORMS=cpu — this script runs on a TPU only')
    devices = jax.devices()
    if devices[0].platform != 'tpu':
        sys.exit('chip_smoke: no TPU (jax found {!r}); refusing to run on it'
                 .format(devices[0].platform))
    if len(devices) < chips:
        sys.exit('chip_smoke: --chips {} needs {} TPUs, jax found {}'
                 .format(chips, chips, len(devices)))
    return devices


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument('--chips', type=int, choices=(1, 4), default=1)
    parser.add_argument('--seed', type=int, default=0)
    parser.add_argument('--data-dir', default=os.path.join(REPO, '.smoke_data'))
    args = parser.parse_args(argv)
    devices = require_tpu(args.chips)
    # a phase that hangs shows where, in the tail the chip tool returns
    faulthandler.dump_traceback_later(300, repeat=True)
    cache = configure_compile_cache(devices[0].platform)
    log('chip_smoke: {} x {}, compile cache {}'.format(len(devices),
                                                        devices[0].device_kind, cache))
    sizes = Sizes()
    data_dir = os.path.abspath(args.data_dir)
    if args.chips == 4:
        devices = devices[:4]
        phases = [('resnet50_dp', lambda: phase_resnet_dp(sizes, data_dir, args.seed,
                                                          devices))]
    else:
        phases = [
            ('resnet50_stream', lambda: phase_resnet_stream(sizes, data_dir, args.seed)),
            ('device_decode', lambda: phase_device_decode(sizes, data_dir, args.seed)),
            ('mnist_inmem', lambda: phase_mnist_inmem(sizes, data_dir, args.seed)),
            ('flash', lambda: phase_flash(sizes, args.seed)),
        ]
    failed = []
    for name, run in phases:
        start = time.perf_counter()
        try:
            fields = run()
            if name == 'flash' and not fields['kernel_in_step']:
                raise AssertionError('flash train step holds no tpu_custom_call')
        except Exception:  # noqa: BLE001 - report every phase, then fail the run
            log('phase {} FAILED:\n{}'.format(name, traceback.format_exc()))
            failed.append(name)
            continue
        fields.update(phase=name, wall_s=time.perf_counter() - start,
                      peak_hbm_bytes=peak_hbm_bytes(devices))
        print(json.dumps(fields), flush=True)
    faulthandler.cancel_dump_traceback_later()
    shutil.rmtree(data_dir, ignore_errors=True)
    if failed:
        sys.exit('chip_smoke: phases failed: {}'.format(', '.join(failed)))
    print(json.dumps({'ok': True, 'device': {'platform': devices[0].platform,
                                             'kind': devices[0].device_kind,
                                             'count': len(devices)}}), flush=True)


if __name__ == '__main__':
    main()
