"""Throughput benchmark: MNIST-shaped end-to-end training pipeline on the real chip.

Writes a synthetic MNIST dataset (28x28 uint8 NdarrayCodec images + labels — the
reference's examples/mnist/schema.py shape), then measures the framework's
*recommended MNIST configuration* end to end:

- **Headline (in-mem epochs)**: ``make_reader -> InMemJaxLoader`` — fill HBM once from
  the streaming pipeline, then train ``jitted MnistCNN`` epochs entirely on device with
  seeded on-device permutations. This is the configuration the docs prescribe for any
  dataset that fits in HBM (the reference's InMemBatchedDataLoader analog,
  petastorm/pytorch.py:368-496), and the one that meets BASELINE.md's >=90%
  input-efficiency north star: after the fill, the input pipeline touches the host zero
  times, so input stall is structurally ~0 (measured, not assumed).
- **Streaming** (also reported): ``make_reader -> JaxDataLoader -> train step`` per-epoch
  re-read. Its stall fraction is workload-relative: a 28x28 CNN consumes rows far faster
  than any single-core host pipeline can decode them, so this number is the honest
  "tiny-model worst case", reported as ``streaming_*``.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...}.
``vs_baseline`` is the ratio to the reference's published hello_world reader throughput
(709.84 samples/sec — docs/benchmarks_tutorial.rst:20-21; BASELINE.md). The reference
number is a bare reader loop; ours consumes every row through a jitted train step, which
is strictly more work per row.

Runs in ONE process: the process that measures is the only one on the chip. With no
TPU it exits non-zero unless ``JAX_PLATFORMS=cpu`` asks for a CPU run, whose numbers
are counts and correctness only, never device speed. A failed section still prints
the cumulative line, then the process exits non-zero. The on-chip bring-up check is
``chip_smoke.py``.

Estimator note: ``value`` is the MEDIAN of per-epoch rates (robust to shared-host CPU
contention transients); the baseline constant 709.84 is a mean-style published number.
The JSON line carries both ``value`` (median) and ``value_mean`` plus an ``estimator``
tag so historical ``vs_baseline`` ratios stay interpretable (ADVICE.md round 1).

Extra diagnostics go to stderr only.
"""

import json
import os
import sys
import tempfile
import threading
import time

import numpy as np

REFERENCE_BASELINE_ROWS_PER_SEC = 709.84
NUM_ROWS = int(os.environ.get('BENCH_ROWS', 50000))
BATCH_SIZE = int(os.environ.get('BENCH_BATCH', 2048))
WORKERS = int(os.environ.get('BENCH_WORKERS', 4))
EPOCHS = int(os.environ.get('BENCH_EPOCHS', 7))
# Per-section soft deadline for MEASURED-epoch loops: loops keep at least one
# measured epoch, then stop once the section has run this long; the emitted
# estimator reports the actual count.
SECTION_DEADLINE_S = float(os.environ.get('BENCH_SECTION_DEADLINE', 600))
IMG_ROWS = int(os.environ.get('BENCH_IMG_ROWS', 768))
IMG_HW = int(os.environ.get('BENCH_IMG_HW', 128))
IMG_BATCH = int(os.environ.get('BENCH_IMG_BATCH', 64))
IMG_EPOCHS = int(os.environ.get('BENCH_IMG_EPOCHS', 3))
# larger-than-HBM streaming config: process pool + on-chip DCT
# decode feeding a real-depth ResNet
STREAM_EPOCHS = int(os.environ.get('BENCH_STREAM_EPOCHS', 3))
STREAM_POOL = os.environ.get('BENCH_STREAM_POOL', 'process')
STREAM_STAGES = tuple(int(s) for s in
                      os.environ.get('BENCH_STREAM_STAGES', '3,8,36,3').split(','))
# flash-attention long-context section
FLASH_T = int(os.environ.get('BENCH_FLASH_T', 8192))
FLASH_BATCH = int(os.environ.get('BENCH_FLASH_BATCH', 2))
FLASH_EMBED = int(os.environ.get('BENCH_FLASH_EMBED', 512))
FLASH_HEADS = int(os.environ.get('BENCH_FLASH_HEADS', 4))  # head_dim 128 = TPU lane
FLASH_LAYERS = int(os.environ.get('BENCH_FLASH_LAYERS', 4))
FLASH_STEPS = int(os.environ.get('BENCH_FLASH_STEPS', 8))
FLASH_ROWS = int(os.environ.get('BENCH_FLASH_ROWS', 64))
# expert-routed compute section (MoETransformerLM; Switch routing on the MXU)
MOE_T = int(os.environ.get('BENCH_MOE_T', 2048))
MOE_BATCH = int(os.environ.get('BENCH_MOE_BATCH', 4))
MOE_EMBED = int(os.environ.get('BENCH_MOE_EMBED', 512))
MOE_HEADS = int(os.environ.get('BENCH_MOE_HEADS', 4))
MOE_EXPERTS = int(os.environ.get('BENCH_MOE_EXPERTS', 8))
MOE_LAYERS = int(os.environ.get('BENCH_MOE_LAYERS', 2))
MOE_STEPS = int(os.environ.get('BENCH_MOE_STEPS', 8))
MOE_ROWS = int(os.environ.get('BENCH_MOE_ROWS', 32))


def log(msg):
    print(msg, file=sys.stderr, flush=True)


# Headline fallback chain: when the mnist_inmem headline did not run (section
# failure or a deliberate BENCH_SECTIONS subset), the
# emitted line falls back to the best measured rate WITH a metric/unit that matches
# its semantics and a config tag naming the substitution — never a bare value=0.0
# that reads as a performance collapse downstream.
_HEADLINE_FALLBACKS = (
    # scan_stream before per-batch streaming: the compiled-chunk path is the
    # framework's measured streaming headline
    ('streaming_scan_rows_per_sec', 'streaming_scan_vs_baseline',
     'mnist_train_rows_per_sec_per_chip', 'rows/s/chip',
     'scan_stream_fallback_headline'),
    ('streaming_rows_per_sec', 'streaming_vs_baseline',
     'mnist_train_rows_per_sec_per_chip', 'rows/s/chip', 'streaming_fallback_headline'),
    ('imagenet_stream_rows_per_sec', None,
     'imagenet_train_rows_per_sec_per_chip', 'rows/s/chip',
     'imagenet_stream_fallback_headline'),
    ('imagenet_scan_rows_per_sec', None,
     'imagenet_train_rows_per_sec_per_chip', 'rows/s/chip',
     'imagenet_scan_fallback_headline'),
    ('flash_train_tokens_per_sec', None,
     'flash_train_tokens_per_sec', 'tokens/s', 'flash_fallback_headline'),
    ('moe_train_tokens_per_sec', None,
     'moe_train_tokens_per_sec', 'tokens/s', 'moe_fallback_headline'),
    ('bare_reader_rows_per_sec', 'bare_reader_vs_baseline',
     'bare_reader_rows_per_sec', 'rows/s', 'bare_reader_fallback_headline'),
    # decode_delta: without this entry a decode-only run would normalize to
    # value=0.0 + 'no_sections_completed' — a falsely-tagged placeholder
    ('imagenet_onchip_decode_rows_per_sec', None,
     'imagenet_onchip_decode_rows_per_sec', 'rows/s',
     'decode_delta_fallback_headline'),
)


SECTION_NAMES = ('mnist_stream', 'mnist_scan_stream', 'bare_reader',
                 'mnist_inmem', 'imagenet_stream', 'imagenet_scan', 'decode_delta',
                 'flash', 'moe', 'wire_bench', 'decode_bench', 'telemetry',
                 'resilience', 'pipecheck', 'tracing', 'service', 'autotune',
                 'device_decode', 'observability', 'schedule', 'storage',
                 'lineage', 'incidents', 'chaos', 'history', 'topology')

# Execution order for a full run: the headline-carrying mnist_inmem first, so a
# run cut short still has its headline. test_tools_and_benchmark guards the
# headline-first invariant.
SECTION_RUN_ORDER = ('mnist_inmem', 'pipecheck', 'observability', 'incidents',
                     'history', 'topology', 'lineage',
                     'schedule', 'storage', 'autotune', 'device_decode',
                     'decode_bench',
                     'service', 'chaos', 'wire_bench', 'telemetry', 'tracing',
                     'resilience', 'mnist_scan_stream', 'flash', 'moe',
                     'imagenet_scan', 'imagenet_stream', 'decode_delta',
                     'bare_reader', 'mnist_stream')
assert sorted(SECTION_RUN_ORDER) == sorted(SECTION_NAMES)


def validate_bench_sections():
    """Parse BENCH_SECTIONS into an allowlist set (empty = run everything). A typo
    must fail loudly before any measurement — not silently skip every section and
    emit value=0.0."""
    allowlist = {s.strip() for s in
                 os.environ.get('BENCH_SECTIONS', '').split(',') if s.strip()}
    unknown = allowlist - set(SECTION_NAMES)
    if unknown:
        raise SystemExit('BENCH_SECTIONS contains unknown section(s) {}; known: {}'
                         .format(sorted(unknown), ', '.join(SECTION_NAMES)))
    return allowlist


def compose_config(existing, tag):
    """Config tags must never stomp the 'sections:' provenance of a BENCH_SECTIONS
    subset run — append to it instead."""
    existing = existing or ''
    return existing + '+' + tag if existing.startswith('sections:') else tag


def normalize_headline(result):
    """Enforce the one-JSON-line contract ({metric, value, unit, vs_baseline}) on
    the emitted line."""
    def tag_config(tag):
        result['config'] = compose_config(result.get('config'), tag)

    if 'value' not in result:
        for key, vs_key, metric, unit, tag in _HEADLINE_FALLBACKS:
            if key in result:
                result['value'] = result[key]
                result['metric'] = metric
                result['unit'] = unit
                result['vs_baseline'] = result.get(vs_key, 0.0) if vs_key else 0.0
                tag_config(tag)
                break
        else:
            result.update(value=0.0, vs_baseline=0.0)
            tag_config('no_sections_completed')
    result.setdefault('metric', 'mnist_train_rows_per_sec_per_chip')
    result.setdefault('unit', 'rows/s/chip')
    result.setdefault('vs_baseline',
                      round(result['value'] / REFERENCE_BASELINE_ROWS_PER_SEC, 3))
    return result


def dataset_url():
    return os.path.join(tempfile.gettempdir(),
                        'petastorm_tpu_bench_mnist_{}'.format(NUM_ROWS))


def build_dataset(url):
    from petastorm_tpu.codecs import NdarrayCodec, ScalarCodec
    from petastorm_tpu.etl.dataset_metadata import write_rows
    from petastorm_tpu.unischema import Unischema, UnischemaField

    schema = Unischema('MnistBench', [
        UnischemaField('idx', np.int64, (), ScalarCodec(), False),
        UnischemaField('digit', np.int64, (), ScalarCodec(), False),
        UnischemaField('image', np.uint8, (28, 28), NdarrayCodec(), False),
    ])
    rng = np.random.RandomState(0)
    rows = [{'idx': i, 'digit': int(rng.randint(10)),
             'image': rng.randint(0, 255, (28, 28), dtype=np.uint8)}
            for i in range(NUM_ROWS)]
    write_rows(url, schema, rows, rowgroup_size_mb=8, n_files=4)
    return schema


def imagenet_dataset_url():
    # 'dct3': v3 content (photograph-like images, zstd) — must not collide with stores
    # cached in this tempdir under earlier keys
    return os.path.join(tempfile.gettempdir(),
                        'petastorm_tpu_bench_dct3_{}_{}'.format(IMG_ROWS, IMG_HW))


def _synthetic_photo(rng, hw):
    """Photograph-like synthetic image: low-frequency structure + mild texture.
    Uniform noise is the pathological case for a DCT store (quantization keeps every
    high-frequency coefficient, so parquet compression cannot do its job); real
    photographs are low-frequency dominated, which is exactly what the DCT
    representation and the storage compressor exploit. Built as upsampled coarse
    noise (smooth fields) plus low-amplitude texture."""
    coarse = rng.randint(0, 255, (hw // 16, hw // 16, 3)).astype(np.float32)
    img = np.kron(coarse, np.ones((16, 16, 1), dtype=np.float32))
    texture = rng.randn(hw, hw, 3).astype(np.float32) * 4.0
    return np.clip(img + texture, 0, 255).astype(np.uint8)


def build_imagenet_dataset(url):
    """DCT-domain image store (DctImageCodec): the imagenet-shaped half of the
    BASELINE.md metric. The same stored bytes serve both decode modes — host IDCT via
    the codec, or raw coefficients to the chip via a DctCoefficientsCodec override."""
    from petastorm_tpu.codecs import DctImageCodec, ScalarCodec
    from petastorm_tpu.etl.dataset_metadata import write_rows
    from petastorm_tpu.unischema import Unischema, UnischemaField

    schema = Unischema('DctBench', [
        UnischemaField('idx', np.int64, (), ScalarCodec(), False),
        UnischemaField('label', np.int64, (), ScalarCodec(), False),
        UnischemaField('image', np.uint8, (IMG_HW, IMG_HW, 3),
                       DctImageCodec(quality=90), False),
    ])
    rng = np.random.RandomState(0)
    rows = [{'idx': i, 'label': int(rng.randint(1000)),
             'image': _synthetic_photo(rng, IMG_HW)}
            for i in range(IMG_ROWS)]
    # zstd: quantized coefficients of photograph-like images are mostly zeros —
    # smaller shipped bytes is exactly what the on-chip-decode streaming config needs
    write_rows(url, schema, rows, rowgroup_size_mb=16, n_files=4, compression='zstd')


def require_platform(env=None):
    """The backend this run measures: a TPU, or the CPU when the caller asked
    for it with ``JAX_PLATFORMS=cpu``. Anything else exits non-zero — a run
    with no chip never falls back to the CPU by itself."""
    import jax
    env = os.environ if env is None else env
    platform = jax.devices()[0].platform
    if platform == 'tpu':
        return platform
    if platform == 'cpu' and env.get('JAX_PLATFORMS', '').strip().lower() == 'cpu':
        return platform
    raise SystemExit('bench.py: no TPU (jax found {!r}); set JAX_PLATFORMS=cpu to '
                     'run on the CPU on purpose'.format(platform))


def run_bench():
    import jax
    from petastorm_tpu.benchmark.compile_cache import configure_compile_cache
    configure_compile_cache(require_platform())
    import jax.numpy as jnp
    import optax

    from petastorm_tpu import make_reader
    from petastorm_tpu.models import MnistCNN
    from petastorm_tpu.ops.image import normalize_image
    from petastorm_tpu.parallel import JaxDataLoader

    device = jax.devices()[0]
    log('bench device: {}'.format(device))

    url = dataset_url()
    if not os.path.exists(os.path.join(url, '_common_metadata')):
        log('materializing {} rows to {}'.format(NUM_ROWS, url))
        build_dataset(url)

    model = MnistCNN()
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((BATCH_SIZE, 28, 28, 1)))
    optimizer = optax.sgd(0.01)
    opt_state = optimizer.init(params)

    @jax.jit
    def train_step(params, opt_state, images_u8, labels):
        images = normalize_image(images_u8[..., None], mean=[0.1307], std=[0.3081],
                                 dtype=jnp.bfloat16)

        def loss_fn(p):
            logits = model.apply(p, images)
            return optax.softmax_cross_entropy_with_integer_labels(logits, labels).mean()

        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt_state2 = optimizer.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state2, loss

    mnist_row_bytes = None

    def link_floor_fields(prefix, row_bytes, batch_size, measured_rate):
        """Measured link ceiling for a per-batch streaming loader, and the share
        of it the measured rate achieved. The ceiling bounds the serial
        transfer+dispatch path (linkprobe docstring); prefetch overlap can beat
        it, so efficiency > 1 means double-buffering is hiding link time. A probe
        failure only loses these extra fields, never the section's own
        measurement."""
        try:
            from petastorm_tpu.benchmark.linkprobe import (
                probe_link, streaming_ceiling_rows_per_sec)
            link = probe_link(sizes_mb=(1, 4), dispatch_iters=10,
                              transfer_iters=3)
            ceiling = streaming_ceiling_rows_per_sec(link, row_bytes, batch_size)
            return {
                prefix + '_row_bytes': int(row_bytes),
                prefix + '_link_dispatch_rtt_ms': link['dispatch_rtt_ms'],
                prefix + '_link_h2d_mbytes_per_sec': link['h2d_mbytes_per_sec'],
                prefix + '_link_ceiling_rows_per_sec': round(ceiling, 2),
                prefix + '_link_efficiency':
                    round(measured_rate / ceiling, 4) if ceiling > 0 else 0.0,
            }
        except Exception as exc:  # noqa: BLE001 - floor analysis is best-effort
            log('link floor probe failed for {}: {!r}'.format(prefix, exc))
            return {}

    def deadline_exceeded(section_start, done, total, label):
        """True once the section has outlived SECTION_DEADLINE_S, logging the
        uniform stopped-early line. Call only after at least one measured
        epoch so every section keeps a result."""
        if time.monotonic() - section_start <= SECTION_DEADLINE_S:
            return False
        log('{}: epoch loop stopped early at the section deadline '
            '({} of {} epochs)'.format(label, done, total))
        return True

    def run_epoch(measure):
        nonlocal params, opt_state, mnist_row_bytes
        reader = make_reader(url, workers_count=WORKERS, shuffle_row_groups=True,
                             seed=42, num_epochs=1)
        # prefetch 4: more transfers in flight hide more of the serial
        # transfer+dispatch path
        loader = JaxDataLoader(reader, batch_size=BATCH_SIZE,
                               prefetch=int(os.environ.get('BENCH_PREFETCH', 4)))
        rows = 0
        start = time.perf_counter()
        loss = None
        for batch in loader:
            if mnist_row_bytes is None:
                # jax-array nbytes: no device readback
                mnist_row_bytes = sum(
                    v.nbytes for v in batch.values()) / BATCH_SIZE
            params, opt_state, loss = train_step(params, opt_state,
                                                 batch['image'], batch['digit'])
            rows += BATCH_SIZE
        float(np.asarray(loss))  # readback ends the timed epoch
        elapsed = time.perf_counter() - start
        reader.stop()
        reader.join()
        if measure:
            log('epoch: {} rows in {:.2f}s -> {:.1f} rows/s; loader stats {}'
                .format(rows, elapsed, rows / elapsed, loader.stats.as_dict()))
        return rows / elapsed, loader.stats

    def force_done(loss_stack):
        """Read the epoch's last loss back to the host: it depends on every
        preceding step, so its readback ends the timed epoch."""
        return float(np.asarray(loss_stack)[-1])

    def run_inmem():
        """Fill HBM once, then EPOCHS fully-compiled epochs via scan_epochs: per-epoch
        permutation + gather + every train step in ONE XLA program, one dispatch per
        epoch. Per-epoch (rate, stall); stall is measured against a compute floor of
        *sequential-slice* epochs (scan_epochs(shuffle=False)) — the same train steps
        over the same varying data with the minimal possible feed, so the delta is
        exactly what the shuffling input machinery costs. (A captive-batch floor is
        unfair: XLA hoists the per-batch normalization out of a constant-input loop.)"""
        nonlocal params, opt_state
        from petastorm_tpu.parallel import InMemJaxLoader
        reader = make_reader(url, workers_count=WORKERS, shuffle_row_groups=True,
                             seed=42, num_epochs=1)
        fill_start = time.perf_counter()
        loader = InMemJaxLoader(reader, batch_size=BATCH_SIZE, num_epochs=None,
                                shuffle=True, seed=7, drop_last=True)
        batches_per_epoch = len(loader)

        def step(carry, batch):
            p, o = carry
            p, o, loss = train_step(p, o, batch['image'], batch['digit'])
            return (p, o), loss

        # warmup epoch: device upload + scan compile
        (params, opt_state), aux = loader.scan_epochs(step, (params, opt_state),
                                                      num_epochs=1)
        force_done(aux[0])
        fill_epoch_s = time.perf_counter() - fill_start

        # compile the sequential-floor variant before timing anything
        (params, opt_state), aux = loader.scan_epochs(
            step, (params, opt_state), num_epochs=1, shuffle=False)
        force_done(aux[0])

        section_start = time.monotonic()
        compute_times = []
        for i in range(3):
            t0 = time.perf_counter()
            (params, opt_state), aux = loader.scan_epochs(
                step, (params, opt_state), num_epochs=1, shuffle=False)
            force_done(aux[0])
            compute_times.append(time.perf_counter() - t0)
            if i > 0 and time.monotonic() - section_start > SECTION_DEADLINE_S / 2:
                log('inmem: floor loop stopped early at deadline/2')
                break
        compute_floor_s = float(np.median(compute_times))

        results = []
        rows = batches_per_epoch * BATCH_SIZE
        for epoch in range(EPOCHS):
            start = time.perf_counter()
            (params, opt_state), aux = loader.scan_epochs(
                step, (params, opt_state), num_epochs=1)
            force_done(aux[0])
            elapsed = time.perf_counter() - start
            stall = max(0.0, 1.0 - compute_floor_s / elapsed)
            results.append((rows / elapsed, stall))
            log('inmem epoch: {} rows in {:.4f}s -> {:.1f} rows/s; input overhead '
                '{:.1%} (sequential floor {:.4f}s)'.format(
                    rows, elapsed, rows / elapsed, stall, compute_floor_s))
            if deadline_exceeded(section_start, epoch + 1, EPOCHS, 'inmem'):
                break
        return results, fill_epoch_s

    def run_decode_delta():
        """Imagenet-shaped decode comparison over one DCT store (SURVEY.md §7.3):
        host-IDCT via the codec vs raw int16 coefficients to the chip + MXU IDCT
        inside the consuming jitted op. Returns (host_rows_per_sec, onchip_rows_per_sec)."""
        from petastorm_tpu.codecs import DctCoefficientsCodec
        from petastorm_tpu.ops.image_decode import dct_decode_images_jax
        from petastorm_tpu.parallel import JaxDataLoader
        from petastorm_tpu.unischema import UnischemaField
        img_url = imagenet_dataset_url()
        if not os.path.exists(os.path.join(img_url, '_common_metadata')):
            log('materializing {} DCT images to {}'.format(IMG_ROWS, img_url))
            build_imagenet_dataset(img_url)

        @jax.jit
        def consume_host(images_u8, labels):
            x = images_u8.astype(jnp.bfloat16) / 255.0
            return jnp.sum(x) + jnp.sum(labels)

        @jax.jit
        def consume_onchip(coeffs, labels):
            images_u8 = dct_decode_images_jax(coeffs, quality=90)
            x = images_u8.astype(jnp.bfloat16) / 255.0
            return jnp.sum(x) + jnp.sum(labels)

        override = UnischemaField('image', np.int16,
                                  (IMG_HW // 8, IMG_HW // 8, 8, 8, 3),
                                  DctCoefficientsCodec(quality=90), False)

        def measure(consume, reader_kwargs):
            rates = []
            for epoch in range(IMG_EPOCHS + 1):   # epoch 0 = warmup/compile
                reader = make_reader(img_url, workers_count=WORKERS, num_epochs=1,
                                     shuffle_row_groups=False, **reader_kwargs)
                loader = JaxDataLoader(reader, batch_size=IMG_BATCH, prefetch=2,
                                       drop_last=True)
                rows = 0
                start = time.perf_counter()
                total = None
                for batch in loader:
                    total = consume(batch['image'], batch['label'])
                    rows += IMG_BATCH
                float(np.asarray(total))
                elapsed = time.perf_counter() - start
                reader.stop()
                reader.join()
                if epoch > 0:
                    rates.append(rows / elapsed)
            return float(np.median(rates))

        host = measure(consume_host, {})
        onchip = measure(consume_onchip, {'field_overrides': [override]})
        log('decode delta: host {:.0f} rows/s vs on-chip {:.0f} rows/s ({:.2f}x)'
            .format(host, onchip, onchip / max(host, 1e-9)))
        return host, onchip

    def compute_reference_rate(step_fn, carry, chunk, rows_per_run, runs=3):
        """Pure-compute reference shared by the scan-stream sections: run the SAME
        scan body over a device-resident chunk, gating the timed window on a final
        readback, and return rows/s. The gap between a streamed rate and this is
        exactly what the input pipeline + per-chunk upload cost."""
        chunk_program = jax.jit(lambda c, ch: jax.lax.scan(step_fn, c, ch))
        carry_c, aux_c = chunk_program(carry, chunk)  # compile warmup
        float(np.asarray(aux_c)[-1])
        start = time.perf_counter()
        for _ in range(runs):
            carry_c, aux_c = chunk_program(carry_c, chunk)
        float(np.asarray(aux_c)[-1])
        return runs * rows_per_run / (time.perf_counter() - start), chunk_program

    def imagenet_train_setup():
        """ONE definition of the imagenet-bench pieces shared by the __iter__
        (imagenet_stream) and scan_stream (imagenet_scan) sections — store, DCT
        read-time override, ResNet config, optimizer, and the decode+train loss —
        so the two sections measure the SAME model and math and can only differ in
        how batches reach the chip."""
        from petastorm_tpu.codecs import DctCoefficientsCodec
        from petastorm_tpu.models.resnet import ResNet
        from petastorm_tpu.ops.image import normalize_image
        from petastorm_tpu.ops.image_decode import dct_decode_images_jax
        from petastorm_tpu.unischema import UnischemaField
        img_url = imagenet_dataset_url()
        if not os.path.exists(os.path.join(img_url, '_common_metadata')):
            log('materializing {} DCT images to {}'.format(IMG_ROWS, img_url))
            build_imagenet_dataset(img_url)
        model = ResNet(stage_sizes=list(STREAM_STAGES), num_classes=1000,
                       num_filters=64)
        variables = model.init(jax.random.PRNGKey(0),
                               jnp.zeros((IMG_BATCH, IMG_HW, IMG_HW, 3)))

        def decoded_loss(params, batch_stats, coeffs, labels):
            """On-chip DCT decode + normalize + ResNet train-mode loss; returns
            ``(loss, new_batch_stats)`` for ``value_and_grad(has_aux=True)``."""
            images = dct_decode_images_jax(coeffs, quality=90)
            images = normalize_image(images, mean=127.5, std=127.5,
                                     dtype=jnp.bfloat16)
            logits, updates = model.apply(
                {'params': params, 'batch_stats': batch_stats}, images, train=True,
                mutable=['batch_stats'])
            loss = optax.softmax_cross_entropy_with_integer_labels(
                logits, labels).mean()
            return loss, updates['batch_stats']

        return {
            'img_url': img_url,
            'variables': variables,
            'optimizer': optax.sgd(0.1, momentum=0.9),
            'override': UnischemaField('image', np.int16,
                                       (IMG_HW // 8, IMG_HW // 8, 8, 8, 3),
                                       DctCoefficientsCodec(quality=90), False),
            'decoded_loss': decoded_loss,
        }

    def run_imagenet_stream():
        """The larger-than-HBM streaming configuration: DCT store
        read by the BENCH_STREAM_POOL pool (spawn + Arrow IPC wire for 'process'),
        raw int16 coefficient blocks to the chip, dequant+IDCT on the MXU inside the
        jitted real-depth ResNet train step, JaxDataLoader prefetch double-buffering.
        ONE reader serves warmup+measured epochs so per-epoch numbers measure the
        steady state, not worker-spawn cost; per-epoch stall comes from loader.stats
        deltas. This is the config where the streaming machinery itself must carry
        the north star (stall < 0.10) — the dataset is never HBM-resident."""
        setup = imagenet_train_setup()
        optimizer = setup['optimizer']
        params = setup['variables']['params']
        batch_stats = setup['variables']['batch_stats']
        opt_state = optimizer.init(params)

        @jax.jit
        def stream_step(params, batch_stats, opt_state, coeffs, labels):
            (loss, new_stats), grads = jax.value_and_grad(
                lambda p: setup['decoded_loss'](p, batch_stats, coeffs, labels),
                has_aux=True)(params)
            updates, opt_state2 = optimizer.update(grads, opt_state, params)
            return optax.apply_updates(params, updates), new_stats, opt_state2, loss

        img_url = setup['img_url']
        reader = make_reader(img_url, reader_pool_type=STREAM_POOL,
                             workers_count=WORKERS, num_epochs=STREAM_EPOCHS + 1,
                             shuffle_row_groups=True, seed=13,
                             field_overrides=[setup['override']])
        loader = JaxDataLoader(reader, batch_size=IMG_BATCH, prefetch=4,
                               drop_last=True)
        rows_per_epoch = (len(reader) // IMG_BATCH) * IMG_BATCH
        rates, stalls = [], []
        epoch_rows = 0
        loss = None
        step_flops = None
        prev_stats = dict(loader.stats.as_dict())
        img_section_start = time.monotonic()
        epoch_start = time.perf_counter()
        img_row_bytes = None
        for batch in loader:
            if step_flops is None:
                # XLA cost analysis of the compiled step (epoch 0 is warmup, so
                # the extra lowering never lands in a measured epoch). The ResNet
                # step is pure HLO — no custom calls — so executed == model FLOPs.
                from petastorm_tpu.benchmark.mfu import xla_cost_flops
                step_flops = xla_cost_flops(
                    stream_step, params, batch_stats, opt_state,
                    batch['image'], batch['label']) or 0.0
                img_row_bytes = sum(v.nbytes for v in batch.values()) / IMG_BATCH
            params, batch_stats, opt_state, loss = stream_step(
                params, batch_stats, opt_state, batch['image'], batch['label'])
            epoch_rows += IMG_BATCH
            if epoch_rows >= rows_per_epoch:
                float(np.asarray(loss))  # gate timing on a real device readback
                now = time.perf_counter()
                stats = loader.stats.as_dict()
                wait = stats['wait_time_s'] - prev_stats['wait_time_s']
                total = stats['total_time_s'] - prev_stats['total_time_s']
                rate = epoch_rows / (now - epoch_start)
                stall = wait / total if total > 0 else 0.0
                rates.append(rate)
                stalls.append(stall)
                log('imagenet stream epoch: {} rows in {:.2f}s -> {:.1f} rows/s, '
                    'stall {:.3f}'.format(epoch_rows, now - epoch_start, rate, stall))
                prev_stats, epoch_rows, epoch_start = stats, 0, now
                # len > 1: epoch 0 is compile warmup; keep >= 1 measured epoch
                if len(rates) > 1 and deadline_exceeded(
                        img_section_start, len(rates), STREAM_EPOCHS + 1,
                        'imagenet stream (incl. warmup)'):
                    break
        reader.stop()
        reader.join()
        # epoch 0 carries every compile: it is warmup, not steady state
        measured_rates, measured_stalls = rates[1:] or rates, stalls[1:] or stalls
        median_rate = float(np.median(measured_rates))
        results.update({
            'imagenet_stream_rows_per_sec': round(median_rate, 2),
            'imagenet_stream_epochs_measured': len(measured_rates),
            'imagenet_stream_input_stall_fraction':
                round(float(np.median(measured_stalls)), 4),
            'imagenet_stream_config': '{}_pool+dct_onchip_decode+resnet{}x{}@{}px_b{}'
                .format(STREAM_POOL, '-'.join(map(str, STREAM_STAGES)), 64,
                        IMG_HW, IMG_BATCH),
        })
        if step_flops and median_rate > 0:
            from petastorm_tpu.benchmark.mfu import mfu_fields
            results.update(mfu_fields('imagenet_train', step_flops, steps=1,
                                      elapsed_s=IMG_BATCH / median_rate))
        if img_row_bytes:
            results.update(link_floor_fields(
                'imagenet_stream', img_row_bytes, IMG_BATCH, median_rate))

    def run_imagenet_scan():
        """Larger-than-HBM streaming through compiled chunk programs: the same
        DCT store + on-chip decode + real-depth ResNet as imagenet_stream, but
        driven by ``JaxDataLoader.scan_stream`` — one H2D upload and ONE XLA
        dispatch per chunk of batches instead of per batch.
        Reports its own efficiency: measured streaming rate over the rate of the
        SAME compiled chunk program on a device-resident chunk (pure compute).
        efficiency >= 0.90 == the streaming north star (BASELINE.md) with the
        input pipeline in the loop."""
        setup = imagenet_train_setup()
        optimizer = setup['optimizer']
        variables = setup['variables']
        carry0 = (variables['params'], variables['batch_stats'],
                  optimizer.init(variables['params']))

        def scan_step(carry, batch):
            params, batch_stats, opt_state = carry
            (loss, new_stats), grads = jax.value_and_grad(
                lambda p: setup['decoded_loss'](p, batch_stats, batch['image'],
                                                batch['label']),
                has_aux=True)(params)
            updates, opt_state2 = optimizer.update(grads, opt_state, params)
            return (optax.apply_updates(params, updates), new_stats, opt_state2), loss

        chunk_batches = int(os.environ.get('BENCH_IMG_CHUNK', 4))
        reader = make_reader(setup['img_url'], reader_pool_type=STREAM_POOL,
                             workers_count=WORKERS, num_epochs=1,
                             shuffle_row_groups=True, seed=17,
                             field_overrides=[setup['override']])
        loader = JaxDataLoader(reader, batch_size=IMG_BATCH, drop_last=True)
        carry = carry0
        rates = []
        section_start = time.monotonic()
        for epoch in range(IMG_EPOCHS + 1):  # epoch 0 absorbs the compiles
            start = time.perf_counter()
            carry, aux = loader.scan_stream(scan_step, carry,
                                            chunk_batches=chunk_batches, seed=epoch)
            rows = sum(int(np.asarray(a).shape[0]) for a in aux) * IMG_BATCH
            float(np.asarray(aux[-1])[-1])  # gate on device readback
            elapsed = time.perf_counter() - start
            if epoch > 0:
                rates.append(rows / elapsed)
                log('imagenet scan epoch: {} rows in {:.2f}s -> {:.1f} rows/s'
                    .format(rows, elapsed, rows / elapsed))
                if deadline_exceeded(section_start, len(rates), IMG_EPOCHS,
                                     'imagenet scan'):
                    break
        reader.stop()
        reader.join()
        stream_rate = float(np.median(rates))

        # Streamed metrics land in results BEFORE the compute reference runs: a
        # reference failure must not discard the section's headline measurement.
        chunk_rows = chunk_batches * IMG_BATCH
        results.update({
            'imagenet_scan_rows_per_sec': round(stream_rate, 2),
            'imagenet_scan_chunk_batches': chunk_batches,
            'imagenet_scan_epochs_measured': len(rates),
        })
        rng = np.random.RandomState(0)
        chunk = {
            'image': jnp.asarray(rng.randint(
                -512, 512, (chunk_batches, IMG_BATCH, IMG_HW // 8, IMG_HW // 8,
                            8, 8, 3)).astype(np.int16)),
            'label': jnp.asarray(rng.randint(
                0, 1000, (chunk_batches, IMG_BATCH)).astype(np.int64)),
        }
        compute_rate, chunk_program = compute_reference_rate(
            scan_step, carry0, chunk, chunk_rows)
        log('imagenet scan: stream {:.1f} rows/s vs compute-only {:.1f} rows/s '
            '-> efficiency {:.3f}'.format(stream_rate, compute_rate,
                                          stream_rate / compute_rate))
        results.update({
            'imagenet_scan_compute_rows_per_sec': round(compute_rate, 2),
            'imagenet_scan_efficiency': round(stream_rate / compute_rate, 4),
        })
        from petastorm_tpu.benchmark.mfu import mfu_fields, xla_cost_flops
        chunk_flops = xla_cost_flops(chunk_program, carry0, chunk)
        if chunk_flops and stream_rate > 0:
            results.update(mfu_fields('imagenet_scan_train', chunk_flops, steps=1,
                                      elapsed_s=chunk_rows / stream_rate))
        # Row bytes measured from the reference chunk (same shapes/dtypes the
        # loader streams), not hand-derived from the codec layout.
        results.update(link_floor_fields(
            'imagenet_scan',
            sum(v.nbytes for v in chunk.values()) / chunk_rows,
            chunk_rows, stream_rate))

    def ensure_token_store(rows, seq_len):
        """Synthetic rolled-pattern token store (learnable, compressible) shared by
        the flash and moe sections; cached on disk keyed by geometry."""
        from petastorm_tpu.codecs import NdarrayCodec, ScalarCodec
        from petastorm_tpu.etl.dataset_metadata import write_rows
        from petastorm_tpu.unischema import Unischema, UnischemaField

        token_url = os.path.join(tempfile.gettempdir(),
                                 'petastorm_tpu_bench_tokens_{}_{}'
                                 .format(rows, seq_len))
        if not os.path.exists(os.path.join(token_url, '_common_metadata')):
            schema = Unischema('Tokens', [
                UnischemaField('doc_id', np.int64, (), ScalarCodec(), False),
                UnischemaField('tokens', np.int32, (seq_len,), NdarrayCodec(), False),
            ])
            rng = np.random.RandomState(0)
            base = rng.randint(0, 255, size=16, dtype=np.int32)
            rows_data = [{'doc_id': i,
                          'tokens': np.roll(np.tile(base, seq_len // 16 + 1)
                                            [:seq_len], i).astype(np.int32)}
                         for i in range(rows)]
            write_rows(token_url, schema, rows_data, rowgroup_size_mb=32, n_files=2)
        return token_url

    def run_moe():
        """Expert-routed compute section: train MoETransformerLM (Switch routing,
        static-capacity one-hot dispatch on the MXU) from InMemJaxLoader. Single
        chip measures the routed-MLP throughput; the expert all-to-all is covered
        by dryrun_multichip/tests (no multi-chip hardware at bench time)."""
        from petastorm_tpu.models import (MoETransformerLM, moe_aux_total,
                                          next_token_loss)
        from petastorm_tpu.models.moe import moe_drop_fractions
        from petastorm_tpu.parallel import InMemJaxLoader

        model = MoETransformerLM(vocab=256, embed=MOE_EMBED, heads=MOE_HEADS,
                                 layers=MOE_LAYERS, num_experts=MOE_EXPERTS,
                                 moe_every=1, max_len=MOE_T)
        optimizer = optax.adam(3e-4)

        def loss_fn(params, tokens):
            logits, mods = model.apply(params, tokens, mutable='losses')
            loss = (next_token_loss(logits, tokens)
                    + moe_aux_total(mods, weight=0.01))
            # Drop fraction rides the jitted step as an aux output — no extra
            # un-jitted forward pass just to read the sown diagnostics.
            drops = moe_drop_fractions(mods)
            max_drop = jnp.max(jnp.stack(drops)) if drops else jnp.float32(0)
            return loss, max_drop

        @jax.jit
        def moe_step(params, opt_state, tokens):
            (loss, max_drop), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, tokens)
            updates, opt_state2 = optimizer.update(grads, opt_state, params)
            return optax.apply_updates(params, updates), opt_state2, loss, max_drop

        token_url = ensure_token_store(MOE_ROWS, MOE_T)
        reader = make_reader(token_url, workers_count=2, num_epochs=1,
                             shuffle_row_groups=False)
        loader = InMemJaxLoader(reader, batch_size=MOE_BATCH, num_epochs=None,
                                shuffle=True, seed=4, drop_last=True)
        it = iter(loader)
        first = next(it)
        params = {'params': model.init(jax.random.PRNGKey(0),
                                       first['tokens'])['params']}
        opt_state = optimizer.init(params)
        params, opt_state, loss, max_drop = moe_step(params, opt_state,
                                                     first['tokens'])
        float(np.asarray(loss))  # warmup: compile fwd+bwd
        start = time.perf_counter()
        for _ in range(MOE_STEPS):
            batch = next(it)
            params, opt_state, loss, max_drop = moe_step(params, opt_state,
                                                         batch['tokens'])
        final_loss = float(np.asarray(loss))
        elapsed = time.perf_counter() - start
        tokens_per_sec = MOE_STEPS * MOE_BATCH * MOE_T / elapsed
        drop = float(np.asarray(max_drop))
        log('moe: {} steps of [{}x{}] x{} experts in {:.2f}s -> {:.0f} tokens/s '
            '(loss {:.3f}, max drop {:.3f})'.format(
                MOE_STEPS, MOE_BATCH, MOE_T, MOE_EXPERTS, elapsed, tokens_per_sec,
                final_loss, drop))
        from petastorm_tpu.benchmark.mfu import (
            mfu_fields, moe_transformer_train_flops_per_step)
        step_flops = moe_transformer_train_flops_per_step(
            MOE_BATCH, MOE_T, vocab=256, embed=MOE_EMBED, layers=MOE_LAYERS,
            num_experts=MOE_EXPERTS, num_selected=1, moe_every=1)
        results.update({
            'moe_train_tokens_per_sec': round(tokens_per_sec, 1),
            'moe_seq_len': MOE_T,
            'moe_experts': MOE_EXPERTS,
            'moe_max_drop_fraction': round(drop, 4),
            'moe_model': 'MoETransformerLM(embed={},heads={},layers={})'.format(
                MOE_EMBED, MOE_HEADS, MOE_LAYERS),
        })
        results.update(mfu_fields('moe_train', step_flops, MOE_STEPS, elapsed))

    def run_flash():
        """Long-context compute section: train TransformerLM with
        the Pallas flash-attention kernels at T=BENCH_FLASH_T, feeding token windows
        through InMemJaxLoader. no_fallback is asserted from the kernel's own dispatch
        predicate (_use_pallas) — if shapes ever stopped tiling, this flips to False
        rather than silently benchmarking the dense path."""
        from types import SimpleNamespace
        from petastorm_tpu.models import TransformerLM, next_token_loss
        from petastorm_tpu.ops.flash_attention import _use_pallas, flash_attention
        from petastorm_tpu.parallel import InMemJaxLoader

        head_dim = FLASH_EMBED // FLASH_HEADS
        # Kernel tile sizes, sweepable from the env for on-chip tuning runs
        block_q = int(os.environ.get('BENCH_FLASH_BLOCK_Q', 256))
        block_k = int(os.environ.get('BENCH_FLASH_BLOCK_K', 256))
        shape_q = SimpleNamespace(shape=(FLASH_BATCH, FLASH_T, FLASH_HEADS, head_dim))
        no_fallback = bool(_use_pallas(shape_q, shape_q, block_q, block_k))

        # On-hardware numerical evidence before timing: the kernels are
        # interpret-mode-verified on CPU; this asserts fwd+bwd against the dense
        # reference on THIS backend at a small tiling shape (T=512 so the pallas
        # path, not the fallback, is what gets checked).
        from petastorm_tpu.ops.ring_attention import dense_attention
        # The check length scales with the swept tile sizes: at fixed T=512 a
        # block_q/k > 512 would fail tiling and silently turn this into a
        # dense-vs-dense comparison (the hollow check the guard below exists to
        # catch).
        check_t = max(512, 2 * max(block_q, block_k))
        check_shape = SimpleNamespace(shape=(1, check_t, FLASH_HEADS, head_dim))
        check_uses_pallas = bool(_use_pallas(check_shape, check_shape, block_q, block_k))
        rng_q = jax.random.PRNGKey(0)
        qkv = [jax.random.normal(jax.random.fold_in(rng_q, i),
                                 (1, check_t, FLASH_HEADS, head_dim), dtype=jnp.float32)
               for i in range(3)]

        def flash_loss(q, k, v):
            return jnp.sum(flash_attention(q, k, v, causal=True, block_q=block_q,
                                           block_k=block_k) ** 2)

        def dense_loss(q, k, v):
            return jnp.sum(dense_attention(q, k, v, causal=True) ** 2)

        flash_val, flash_grads = jax.value_and_grad(flash_loss, argnums=(0, 1, 2))(*qkv)
        # the reference at fp32: the TPU's default precision contracts f32 in
        # one bf16 pass, which alone moved these gradients past the tolerance
        with jax.default_matmul_precision('highest'):
            dense_val, dense_grads = jax.value_and_grad(dense_loss,
                                                        argnums=(0, 1, 2))(*qkv)
        value_ok = bool(np.allclose(np.asarray(flash_val), np.asarray(dense_val),
                                    rtol=2e-3, atol=2e-3))
        grads_ok = all(np.allclose(np.asarray(fg), np.asarray(dg), rtol=2e-2, atol=2e-2)
                       for fg, dg in zip(flash_grads, dense_grads))
        # Vacuous-check guard: if the check shape itself would fall back to dense,
        # "flash vs dense" compares dense against dense — report False, not a
        # hollow True.
        flash_matches_dense = check_uses_pallas and value_ok and grads_ok
        log('flash vs dense on {}: pallas_path={} fwd {} bwd {}'.format(
            jax.devices()[0].platform, check_uses_pallas, value_ok, grads_ok))

        token_url = ensure_token_store(FLASH_ROWS, FLASH_T)

        model = TransformerLM(vocab=256, embed=FLASH_EMBED, heads=FLASH_HEADS,
                              layers=FLASH_LAYERS, max_len=FLASH_T,
                              attention_fn=lambda q, k, v: flash_attention(
                                  q, k, v, causal=True, block_q=block_q,
                                  block_k=block_k))
        optimizer = optax.adam(3e-4)

        @jax.jit
        def flash_step(params, opt_state, tokens):
            loss, grads = jax.value_and_grad(
                lambda p: next_token_loss(model.apply(p, tokens), tokens))(params)
            updates, opt_state2 = optimizer.update(grads, opt_state, params)
            return optax.apply_updates(params, updates), opt_state2, loss

        reader = make_reader(token_url, workers_count=2, num_epochs=1,
                             shuffle_row_groups=False)
        loader = InMemJaxLoader(reader, batch_size=FLASH_BATCH, num_epochs=None,
                                shuffle=True, seed=3, drop_last=True)
        it = iter(loader)
        first = next(it)
        params = model.init(jax.random.PRNGKey(0), first['tokens'])
        opt_state = optimizer.init(params)
        params, opt_state, loss = flash_step(params, opt_state, first['tokens'])
        float(np.asarray(loss))  # warmup: compile fwd+bwd
        start = time.perf_counter()
        for _ in range(FLASH_STEPS):
            batch = next(it)
            params, opt_state, loss = flash_step(params, opt_state, batch['tokens'])
        final_loss = float(np.asarray(loss))
        elapsed = time.perf_counter() - start
        tokens_per_sec = FLASH_STEPS * FLASH_BATCH * FLASH_T / elapsed
        log('flash: {} steps of [{}x{}] in {:.2f}s -> {:.0f} tokens/s '
            '(no_fallback={}, loss {:.3f})'.format(
                FLASH_STEPS, FLASH_BATCH, FLASH_T, elapsed, tokens_per_sec,
                no_fallback, final_loss))
        from petastorm_tpu.benchmark.mfu import (
            mfu_fields, transformer_train_flops_per_step)
        step_flops = transformer_train_flops_per_step(
            FLASH_BATCH, FLASH_T, vocab=256, embed=FLASH_EMBED,
            layers=FLASH_LAYERS)
        results.update({
            'flash_train_tokens_per_sec': round(tokens_per_sec, 1),
            'flash_seq_len': FLASH_T,
            'flash_no_fallback': no_fallback,
            'flash_matches_dense': flash_matches_dense,
            'flash_model': 'TransformerLM(embed={},heads={},layers={})'.format(
                FLASH_EMBED, FLASH_HEADS, FLASH_LAYERS),
            'flash_block_qk': '{}x{}'.format(block_q, block_k),
        })
        results.update(mfu_fields('flash_train', step_flops, FLASH_STEPS, elapsed))

    # ---------------------------------------------------------------- orchestration
    platform = jax.devices()[0].platform
    results = {'platform': platform}

    section_allowlist = validate_bench_sections()
    if section_allowlist:
        results['config'] = 'sections:' + ','.join(
            s for s in SECTION_NAMES if s in section_allowlist)

    def run_section(name, fn):
        if section_allowlist and name not in section_allowlist:
            log('section {} skipped (BENCH_SECTIONS)'.format(name))
            # the JSON line names what DIDN'T run: a subset round must never
            # read downstream as "those paths measured 0" (it reads as
            # sections_skipped) — no silent caps
            results.setdefault('sections_skipped', []).append(name)
            return
        try:
            fn()
        except Exception as exc:  # noqa: BLE001 - later sections still run; exit is non-zero
            import traceback
            log('section {} FAILED: {!r}\n{}'.format(name, exc, traceback.format_exc()))
            results[name + '_error'] = repr(exc)

    def run_mnist_stream():
        log('warmup epoch (compile + cache)...')
        section_start = time.monotonic()
        run_epoch(measure=False)
        stream_rates, stream_stalls = [], []
        stats = None
        for _ in range(EPOCHS):
            rate, stats = run_epoch(measure=True)
            stream_rates.append(rate)
            stream_stalls.append(stats.input_stall_fraction)
            if deadline_exceeded(section_start, len(stream_rates), EPOCHS,
                                 'streaming'):
                break
        stream_value = float(np.median(stream_rates))
        results.update({
            'streaming_rows_per_sec': round(stream_value, 2),
            'streaming_vs_baseline':
                round(stream_value / REFERENCE_BASELINE_ROWS_PER_SEC, 3),
            'streaming_input_stall_fraction':
                round(float(np.median(stream_stalls)), 4),
            'streaming_epochs_measured': len(stream_rates),
        })
        if stats is not None:  # BENCH_EPOCHS=0 runs zero measured epochs
            results['streaming_per_field_uploads'] = stats.per_field_uploads
        if mnist_row_bytes is not None:
            results.update(link_floor_fields(
                'streaming', mnist_row_bytes, BATCH_SIZE, stream_value))

    def run_scan_stream():
        """Compiled-chunk streaming (JaxDataLoader.scan_stream): the dispatch-bound
        larger-than-HBM configuration — per-epoch re-read like streaming_*, but one
        H2D transfer + one XLA dispatch per chunk of batches instead of per batch.
        The delta against streaming_rows_per_sec is exactly what per-batch dispatch
        costs on this host/device link."""
        nonlocal params, opt_state

        def step(carry, batch):
            p, o = carry
            p, o, loss = train_step(p, o, batch['image'], batch['digit'])
            return (p, o), loss

        # ONE loader across epochs (reader.reset() between passes): the compiled
        # chunk programs live on the loader instance, so epochs 1..N measure the
        # steady state while epoch 0 absorbs the compiles.
        scan_chunk = int(os.environ.get('BENCH_SCAN_CHUNK', 8))
        reader = make_reader(url, workers_count=WORKERS, shuffle_row_groups=True,
                             seed=42, num_epochs=1)
        loader = JaxDataLoader(reader, batch_size=BATCH_SIZE)
        rates = []
        section_start = time.monotonic()
        for epoch in range(EPOCHS + 1):  # epoch 0 = compile warmup; auto-reset after
            start = time.perf_counter()
            (params, opt_state), aux = loader.scan_stream(
                step, (params, opt_state), chunk_batches=scan_chunk, seed=epoch)
            rows = sum(int(np.asarray(a).shape[0]) for a in aux) * BATCH_SIZE
            float(np.asarray(aux[-1])[-1])  # gate on device readback
            elapsed = time.perf_counter() - start
            if epoch > 0:
                rates.append(rows / elapsed)
                log('scan_stream epoch: {} rows in {:.2f}s -> {:.0f} rows/s'
                    .format(rows, elapsed, rows / elapsed))
                if deadline_exceeded(section_start, len(rates), EPOCHS,
                                     'scan_stream'):
                    break
        reader.stop()
        reader.join()
        value = float(np.median(rates))
        # Streamed metrics land in results first — a compute-reference failure
        # must not discard the section's headline measurement.
        results.update({
            'streaming_scan_rows_per_sec': round(value, 2),
            'streaming_scan_vs_baseline':
                round(value / REFERENCE_BASELINE_ROWS_PER_SEC, 3),
            'streaming_scan_chunk_batches': scan_chunk,
            'streaming_scan_epochs_measured': len(rates),
        })
        rng = np.random.RandomState(1)
        chunk = {
            'image': jnp.asarray(rng.randint(
                0, 255, (scan_chunk, BATCH_SIZE, 28, 28)).astype(np.uint8)),
            'digit': jnp.asarray(rng.randint(
                0, 10, (scan_chunk, BATCH_SIZE)).astype(np.int64)),
        }
        compute_rate, _ = compute_reference_rate(
            step, (params, opt_state), chunk, scan_chunk * BATCH_SIZE, runs=4)
        log('scan_stream: streamed {:.0f} rows/s vs compute-only {:.0f} rows/s '
            '-> efficiency {:.3f}'.format(value, compute_rate, value / compute_rate))
        results.update({
            'streaming_scan_compute_rows_per_sec': round(compute_rate, 2),
            'streaming_scan_efficiency': round(value / compute_rate, 4),
        })

    def run_bare_reader():
        """The apples-to-apples ratio: the reference's 709.84 is
        a bare make_reader row loop — measure OUR bare row loop (same row-namedtuple
        API, no train step, no device) on the same store, so bare_reader_vs_baseline
        compares like with like (host-only; hardware still differs from the
        reference's unspecified 2018 doc run, which the docs caveat)."""
        rates = []
        for _ in range(3):
            reader = make_reader(url, workers_count=WORKERS, shuffle_row_groups=True,
                                 seed=42, num_epochs=1)
            start = time.perf_counter()
            rows = sum(1 for _ in reader)
            elapsed = time.perf_counter() - start
            reader.stop()
            reader.join()
            rates.append(rows / elapsed)
            log('bare reader: {} rows in {:.2f}s -> {:.0f} rows/s'.format(
                rows, elapsed, rates[-1]))
        rate = float(np.median(rates))
        results.update({
            'bare_reader_rows_per_sec': round(rate, 2),
            'bare_reader_vs_baseline':
                round(rate / REFERENCE_BASELINE_ROWS_PER_SEC, 3),
        })

    def run_mnist_inmem():
        inmem_results, fill_epoch_s = run_inmem()
        inmem_rates = [r for r, _ in inmem_results]
        # median: per-epoch rates on a shared host are noisy (transient CPU contention
        # can halve a single epoch); the median is the robust steady-state estimate
        value = float(np.median(inmem_rates))
        # Headline MFU: XLA cost analysis of the per-batch train step (MnistCNN is
        # pure HLO) scaled by the measured rows/s. A 28x28 CNN is tiny, so a small
        # MFU here is expected — the number exists so "569x vs the 2018 CPU
        # baseline" is never the only efficiency evidence.
        from petastorm_tpu.benchmark.mfu import mfu_fields, xla_cost_flops
        rng = np.random.RandomState(2)
        step_flops = xla_cost_flops(
            train_step, params, opt_state,
            jnp.asarray(rng.randint(0, 255, (BATCH_SIZE, 28, 28)).astype(np.uint8)),
            jnp.asarray(rng.randint(0, 10, (BATCH_SIZE,)).astype(np.int64)))
        if step_flops and value > 0:
            results.update(mfu_fields('mnist_train', step_flops, steps=1,
                                      elapsed_s=BATCH_SIZE / value))
        results.update({
            'value': round(value, 2),
            'vs_baseline': round(value / REFERENCE_BASELINE_ROWS_PER_SEC, 3),
            'input_stall_fraction':
                round(float(np.median([s for _, s in inmem_results])), 4),
            'config': compose_config(results.get('config'),
                                     'inmem_hbm_resident_epochs'),
            'fill_epoch_s': round(fill_epoch_s, 3),
            'value_mean': round(float(np.mean(inmem_rates)), 2),
            'estimator': 'median_of_{}_epochs'.format(len(inmem_rates)),
        })

    def run_wire_bench():
        """Zero-copy data-plane microbench (host-only, fast): pickle vs arrow-ipc
        vs shm transport MB/s + bytes-copied-per-batch, and the cold-fill vs
        warm-mmap cache epoch ratio — the ISSUE-2 acceptance numbers
        (wire_arrow_shm_bytes_copied_per_batch >= 2x below the pickle path,
        wire_cache_warm_speedup >= 3)."""
        from petastorm_tpu.benchmark.wire_bench import run_wire_bench as wire_bench
        fields = wire_bench(
            rows=int(os.environ.get('BENCH_WIRE_ROWS', 2048)),
            batches=int(os.environ.get('BENCH_WIRE_BATCHES', 24)),
            workers=int(os.environ.get('BENCH_WIRE_WORKERS', 2)),
            cache_rows=int(os.environ.get('BENCH_WIRE_CACHE_ROWS', 1500)))
        results.update({'wire_' + key: value for key, value in fields.items()})

    def run_telemetry():
        """Stage-time-share breakdown (fast, host-only): one instrumented epoch
        over the MNIST store through a spawned process pool (shm transport
        auto), then the bottleneck attribution — so the perf trajectory records
        WHERE the pipeline spends its time, not just how fast it went
        (docs/observability.md)."""
        from petastorm_tpu.telemetry.analyze import attribute_bottleneck
        reader = make_reader(url, reader_pool_type='process',
                             workers_count=min(WORKERS, 2), num_epochs=1,
                             shuffle_row_groups=False)
        rows = 0
        start = time.perf_counter()
        for batch in reader.iter_columnar():
            rows += batch.num_rows
        elapsed = time.perf_counter() - start
        snapshot = reader.telemetry_snapshot()
        diag = reader.diagnostics
        reader.stop()
        reader.join()
        report = attribute_bottleneck(snapshot)
        log('telemetry: {} rows in {:.2f}s; top stage {} ({:.0%}) -> {}'.format(
            rows, elapsed, report['top_stage'], report['top_share'],
            report['recommendation']))
        fields = {
            'telemetry_rows_per_sec': round(rows / elapsed, 1),
            'telemetry_total_stage_seconds': report['total_stage_seconds'],
            'telemetry_top_stage': report['top_stage'],
            'telemetry_top_share': report['top_share'],
            'telemetry_recommendation': report['recommendation'],
            'telemetry_shm_batches': diag.get('shm_batches', 0),
        }
        for entry in report['ranked']:
            fields['telemetry_stage_share_' + entry['stage']] = entry['share']
        results.update(fields)

    def run_tracing():
        """Flight-recorder overhead + capture validity (host-only, fast): the
        same process-pool epoch with the trace ring armed vs disarmed; the
        overhead percentage is the BENCH-history guard for the ISSUE-6
        acceptance (<= 3% with tracing on — docs/observability.md "Flight
        recorder"), and the captured trace's event/drop counts prove the
        default ring size holds a full epoch without silent loss."""
        from petastorm_tpu.telemetry import tracing as flight
        from petastorm_tpu.telemetry.trace_export import summarize_trace

        def epoch_rows_per_sec(traced):
            flight.reset_tracing()
            flight.set_trace_enabled(traced)
            reader = make_reader(url, reader_pool_type='process',
                                 workers_count=min(WORKERS, 2), num_epochs=1,
                                 shuffle_row_groups=False)
            rows = 0
            start = time.perf_counter()
            for batch in reader.iter_columnar():
                rows += batch.num_rows
            elapsed = time.perf_counter() - start
            summary = (summarize_trace(flight.trace_snapshot())
                       if traced else None)
            reader.stop()
            reader.join()
            return rows / elapsed, summary

        try:
            baseline_rate, _ = epoch_rows_per_sec(traced=False)
            traced_rate, summary = epoch_rows_per_sec(traced=True)
        finally:
            flight.set_trace_enabled(False)
            flight.reset_tracing()
        overhead_pct = (baseline_rate - traced_rate) / baseline_rate * 100.0
        log('tracing: traced {:.1f} rows/s vs off {:.1f} rows/s ({:+.2f}% '
            'flight-recorder overhead); {} events over {} rowgroup traces '
            'across {} processes, {} dropped'
            .format(traced_rate, baseline_rate, overhead_pct,
                    summary['events'], summary['rowgroups_traced'],
                    len(summary['processes']), summary['dropped_events']))
        results.update({
            'tracing_traced_rows_per_sec': round(traced_rate, 1),
            'tracing_baseline_rows_per_sec': round(baseline_rate, 1),
            'tracing_overhead_pct': round(overhead_pct, 2),
            'tracing_events': summary['events'],
            'tracing_dropped_events': summary['dropped_events'],
            'tracing_rowgroups_traced': summary['rowgroups_traced'],
            'tracing_process_tracks': len(summary['processes']),
        })

    def run_observability():
        """Goodput observatory (host-only, fast; docs/observability.md):
        (1) scrape-while-reading overhead — the same process-pool epoch with
        a live /metrics endpoint being scraped hard vs no endpoint; the
        overhead percentage is the BENCH-history guard for the ISSUE-11
        acceptance (<= 3%); (2) the input-efficiency SLO fields of the
        scraped epoch; (3) the cost-ledger persist -> reload probe (identical
        what-if ranking across the roundtrip)."""
        import urllib.request

        def epoch(metrics_port):
            reader = make_reader(url, reader_pool_type='process',
                                 workers_count=min(WORKERS, 2), num_epochs=1,
                                 shuffle_row_groups=False,
                                 metrics_port=metrics_port)
            stop = threading.Event()
            scrapes = [0]
            scraper = None
            if metrics_port is not None:
                def scrape_loop():
                    while not stop.is_set():
                        try:
                            urllib.request.urlopen(
                                reader.metrics_url + '/metrics',
                                timeout=5).read()
                            scrapes[0] += 1
                        except Exception:  # noqa: BLE001 - endpoint may be tearing down
                            pass
                        time.sleep(0.02)
                scraper = threading.Thread(target=scrape_loop, daemon=True)
                scraper.start()
            rows = 0
            start = time.perf_counter()
            for batch in reader.iter_columnar():
                rows += batch.num_rows
            elapsed = time.perf_counter() - start
            slo = reader.efficiency_report()
            stop.set()
            if scraper is not None:
                scraper.join(timeout=5)
            reader.stop()
            reader.join()
            return rows / elapsed, slo, scrapes[0]

        baseline_rate, _, _ = epoch(None)
        scraped_rate, slo, scrapes = epoch(0)
        overhead_pct = (baseline_rate - scraped_rate) / baseline_rate * 100.0

        # cost-ledger probe: traced epoch -> ledger -> persist -> reload ->
        # identical what-if ranking
        from petastorm_tpu.telemetry import tracing as flight
        from petastorm_tpu.telemetry.cost_model import CostLedger
        flight.reset_tracing()
        flight.set_trace_enabled(True)
        try:
            reader = make_reader(url, num_epochs=1, shuffle_row_groups=False)
            for batch in reader.iter_columnar():
                pass
            ledger = reader.cost_ledger()
            reader.stop()
            reader.join()
        finally:
            flight.set_trace_enabled(False)
            flight.reset_tracing()
        ledger_path = os.path.join(tempfile.mkdtemp(prefix='bench_costs_'),
                                   'ledger.json')
        ledger.save(ledger_path)
        reloaded = CostLedger.load(ledger_path)
        roundtrip_ok = (reloaded.what_if() == ledger.what_if()
                        and reloaded.ranking(10) == ledger.ranking(10))
        what_if = ledger.what_if()
        skew = next((row['skew_p95_over_median'] for row in what_if
                     if row['scope'] == 'total'), 0.0)

        log('observability: scraped {:.1f} rows/s vs bare {:.1f} rows/s '
            '({:+.2f}% scrape overhead over {} scrape(s)); efficiency '
            '{:.1%} (target {:.0%}); cost ledger {} rowgroup(s), persist '
            'roundtrip {}'.format(
                scraped_rate, baseline_rate, overhead_pct, scrapes,
                slo['efficiency'], slo['target_efficiency'], len(ledger),
                'ok' if roundtrip_ok else 'MISMATCH'))
        results.update({
            'observability_scraped_rows_per_sec': round(scraped_rate, 1),
            'observability_baseline_rows_per_sec': round(baseline_rate, 1),
            'observability_scrape_overhead_pct': round(overhead_pct, 2),
            'observability_scrapes': scrapes,
            'observability_slo_efficiency': slo['efficiency'],
            'observability_slo_target': slo['target_efficiency'],
            'observability_slo_met': bool(slo['met']),
            'observability_cost_rowgroups': len(ledger),
            'observability_cost_skew_p95_over_median': skew,
            'observability_cost_persist_roundtrip_ok': bool(roundtrip_ok),
        })

    def run_lineage():
        """Sample-lineage audit plane (host-only, fast; docs/observability.md
        "Sample lineage & determinism audit"): (1) recording-overhead guard —
        a lineage-armed process-pool epoch (manifest written) vs a bare one,
        min-of-3 interleaved pairs to cancel shared-host drift; the overhead
        percentage is the BENCH-history guard for the ISSUE-13 acceptance
        (<= 3%); (2) pool-parity probe — the dummy-pool digest of the same
        seed must equal the process-pool digest; (3) a manifest verify
        roundtrip (dry replay, zero data re-read)."""
        from petastorm_tpu.telemetry.lineage import (LineagePolicy,
                                                     verify_manifest)
        lineage_dir = tempfile.mkdtemp(prefix='bench_lineage_')
        manifest = os.path.join(lineage_dir, 'manifest.jsonl')

        def epoch(lineage, pool='process'):
            reader = make_reader(url, reader_pool_type=pool,
                                 workers_count=min(WORKERS, 2), num_epochs=1,
                                 seed=13, shuffle_row_groups=True,
                                 lineage=lineage)
            rows = 0
            start = time.perf_counter()
            for batch in reader.iter_columnar():
                rows += batch.num_rows
            elapsed = time.perf_counter() - start
            digest = reader.order_digest()
            report = (reader.diagnostics.get('lineage')
                      if lineage is not None else None)
            reader.stop()
            reader.join()
            return rows / elapsed, digest, report

        bare_rates, armed_rates = [], []
        digest = report = None
        for _ in range(3):  # interleaved pairs: shared-host drift cancels
            bare_rates.append(epoch(None)[0])
            rate, digest, report = epoch(
                LineagePolicy(manifest_path=manifest))
            armed_rates.append(rate)
        bare_rate = max(bare_rates)
        armed_rate = max(armed_rates)
        overhead_pct = (bare_rate - armed_rate) / bare_rate * 100.0
        dummy_digest = epoch(LineagePolicy(manifest=False), pool='dummy')[1]
        verify = verify_manifest(manifest, dataset_url=url)
        log('lineage: armed {:.1f} rows/s vs bare {:.1f} rows/s ({:+.2f}% '
            'recording overhead); digest {}… over {} item(s), pool parity '
            '{}, divergence {}, dry-replay verify {}'.format(
                armed_rate, bare_rate, overhead_pct, (digest or '')[:12],
                report['items_folded'], 'ok' if digest == dummy_digest
                else 'MISMATCH', report['divergence'],
                'ok' if verify['ok'] else 'FAIL({})'.format(verify['reason'])))
        results.update({
            'lineage_armed_rows_per_sec': round(armed_rate, 1),
            'lineage_bare_rows_per_sec': round(bare_rate, 1),
            'lineage_overhead_pct': round(overhead_pct, 2),
            'lineage_items_folded': report['items_folded'],
            'lineage_divergence': report['divergence'],
            'lineage_pool_parity_ok': bool(digest == dummy_digest),
            'lineage_verify_ok': bool(verify['ok']),
        })

    def run_incidents():
        """Incident autopsy plane (host-only, fast; docs/observability.md
        "Incident autopsy plane"): (1) capture-overhead guard — an
        incidents-armed process-pool epoch (recorder wired, no edge fires)
        vs a bare one, min-of-3 interleaved pairs; the overhead percentage
        is the BENCH-history guard for the ISSUE-15 acceptance (<= 3%);
        (2) capture probe — a forced breaker closed->open edge on an armed
        dummy-pool reader retains exactly one bundle (the re-trip inside the
        refill window is rate-limited) whose autopsy ranks storage-path
        first with its exit code; (3) retention probe — max_bundles + 1
        triggers on an injected clock retain exactly max_bundles, oldest
        evicted."""
        from petastorm_tpu.resilience import default_board
        from petastorm_tpu.telemetry.incident import (EXIT_CODES,
                                                      IncidentPolicy,
                                                      IncidentRecorder,
                                                      analyze_bundle,
                                                      scan_bundles)
        incident_root = tempfile.mkdtemp(prefix='bench_incidents_')

        def epoch(incidents):
            reader = make_reader(url, reader_pool_type='process',
                                 workers_count=min(WORKERS, 2), num_epochs=1,
                                 seed=13, shuffle_row_groups=True,
                                 incidents=incidents)
            rows = 0
            start = time.perf_counter()
            for batch in reader.iter_columnar():
                rows += batch.num_rows
            elapsed = time.perf_counter() - start
            reader.stop()
            reader.join()
            return rows / elapsed

        armed_policy = IncidentPolicy(
            home=os.path.join(incident_root, 'armed'))
        bare_rates, armed_rates = [], []
        for _ in range(3):  # interleaved pairs: shared-host drift cancels
            bare_rates.append(epoch(None))
            armed_rates.append(epoch(armed_policy))
        bare_rate = max(bare_rates)
        armed_rate = max(armed_rates)
        overhead_pct = (bare_rate - armed_rate) / bare_rate * 100.0

        # capture probe: the acceptance (b) path — forced breaker trip on an
        # armed reader => exactly one bundle, second edge rate-limited,
        # autopsy ranks the trigger's cause class first
        probe_home = os.path.join(incident_root, 'probe')
        reader = make_reader(url, reader_pool_type='dummy', num_epochs=1,
                             incidents=IncidentPolicy(home=probe_home))
        for _ in reader.iter_columnar():
            break
        breaker = default_board().breaker('bench_incident_probe',
                                          failure_threshold=1)
        breaker.record_failure()  # closed -> open: the captured edge
        breaker.reset()           # open -> closed: no capture (not an open)
        breaker.record_failure()  # second edge inside refill: rate-limited
        probe = reader.incident_report() or {}
        reader.stop()
        reader.join()
        breaker.reset()  # don't leak an open breaker into later sections
        bundles = scan_bundles(probe_home)
        autopsy = analyze_bundle(bundles[0]['path']) if bundles else {}
        capture_ok = (probe.get('captured') == 1
                      and probe.get('rate_limited', 0) >= 1
                      and len(bundles) == 1
                      and autopsy.get('top_cause') == 'storage-path'
                      and autopsy.get('exit_code')
                      == EXIT_CODES['storage-path'])

        # retention probe: provably bounded — max_bundles + 1 captures on an
        # injected clock (every trigger gets a fresh token) keep exactly
        # max_bundles, and the survivor set is the NEWEST ones
        fake = {'now': 0.0}
        retention_policy = IncidentPolicy(
            home=os.path.join(incident_root, 'retention'), max_bundles=3,
            refill_interval_s=1.0)
        recorder = IncidentRecorder(retention_policy.home, retention_policy,
                                    clock=lambda: fake['now'])
        for i in range(retention_policy.max_bundles + 1):
            fake['now'] += retention_policy.refill_interval_s
            recorder.trigger('slo_breach', args={'probe': i})
        retained = scan_bundles(retention_policy.home)
        recorder.close()
        retention_ok = (len(retained) == retention_policy.max_bundles
                        and all(entry['bundle'] > 'incident-00000'
                                for entry in retained))

        log('incidents: armed {:.1f} rows/s vs bare {:.1f} rows/s ({:+.2f}% '
            'capture-plane overhead); probe capture {} (captured={} '
            'rate_limited={} top={} exit={}), retention {} ({} of {} kept '
            'after {} triggers)'.format(
                armed_rate, bare_rate, overhead_pct,
                'ok' if capture_ok else 'FAIL', probe.get('captured'),
                probe.get('rate_limited'), autopsy.get('top_cause'),
                autopsy.get('exit_code'), 'ok' if retention_ok else 'FAIL',
                len(retained), retention_policy.max_bundles,
                retention_policy.max_bundles + 1))
        results.update({
            'incidents_armed_rows_per_sec': round(armed_rate, 1),
            'incidents_bare_rows_per_sec': round(bare_rate, 1),
            'incidents_overhead_pct': round(overhead_pct, 2),
            'incidents_capture_ok': bool(capture_ok),
            'incidents_rate_limited': int(probe.get('rate_limited', 0)),
            'incidents_autopsy_exit_code': autopsy.get('exit_code'),
            'incidents_retention_ok': bool(retention_ok),
        })

    def run_history():
        """Longitudinal observatory (host-only, fast; docs/observability.md
        "Longitudinal observatory"): (1) historian-overhead guard — a
        history+sentinel-armed process-pool epoch vs a bare one, min-of-3
        interleaved pairs; the overhead percentage is the BENCH-history
        guard for the ISSUE-18 acceptance (<= 3%); (2) store round-trip
        probe — both armed epochs land CRC-intact run records whose
        trailing-median compare of the last run verdicts within-noise
        against its sibling (same config, same host)."""
        from petastorm_tpu.telemetry.history import (compare_against_history,
                                                     load_records)
        history_root = tempfile.mkdtemp(prefix='bench_history_')
        store = os.path.join(history_root, 'run_history.bin')

        def epoch(history):
            reader = make_reader(url, reader_pool_type='process',
                                 workers_count=min(WORKERS, 2), num_epochs=1,
                                 seed=13, shuffle_row_groups=True,
                                 history=history)
            rows = 0
            start = time.perf_counter()
            for batch in reader.iter_columnar():
                rows += batch.num_rows
            elapsed = time.perf_counter() - start
            reader.stop()
            reader.join()
            return rows / elapsed

        bare_rates, armed_rates = [], []
        for _ in range(3):  # interleaved pairs: shared-host drift cancels
            bare_rates.append(epoch(None))
            armed_rates.append(epoch(store))
        bare_rate = max(bare_rates)
        armed_rate = max(armed_rates)
        overhead_pct = (bare_rate - armed_rate) / bare_rate * 100.0

        records, dropped = load_records(store)
        report = (compare_against_history(records, records[-1])
                  if records else {})
        # identically-configured same-host runs must not read as a change
        compare_ok = (len(records) == 3 and dropped == 0
                      and report.get('verdict') in ('within-noise',
                                                    'improved',
                                                    'insufficient-history'))

        log('history: armed {:.1f} rows/s vs bare {:.1f} rows/s ({:+.2f}% '
            'historian+sentinel overhead); store round-trip {} ({} records, '
            '{} dropped, self-compare verdict {})'.format(
                armed_rate, bare_rate, overhead_pct,
                'ok' if compare_ok else 'FAIL', len(records), dropped,
                report.get('verdict')))
        results.update({
            'history_armed_rows_per_sec': round(armed_rate, 1),
            'history_bare_rows_per_sec': round(bare_rate, 1),
            'history_overhead_pct': round(overhead_pct, 2),
            'history_records_written': len(records),
            'history_frames_dropped': int(dropped),
            'history_compare_ok': bool(compare_ok),
        })

    def run_topology():
        """Elastic pod-scale sharding (host-only; docs/robustness.md
        "Elastic pod-scale sharding"): (1) negotiation-overhead guard — a
        topology-armed single-host epoch (journal + per-item progress
        appends) vs a static epoch, min-of-3 interleaved pairs, the <=3%
        acceptance guard; (2) host-kill recovery probe — a 2-host pod with
        one host abandoned mid-shard must recover rows-exact with the
        composed digest byte-identical to an undisturbed pod, and the
        survivor's reshard decision (journal replay + remainder re-deal)
        is timed as the recovery-latency headline."""
        from petastorm_tpu.parallel.topology import (TopologyPolicy,
                                                     replay_topology_journal,
                                                     reshard_assignments,
                                                     undelivered_items)
        from petastorm_tpu.test_util.chaos import run_host_chaos
        topo_root = tempfile.mkdtemp(prefix='bench_topology_')

        def epoch(policy):
            reader = make_reader(url, reader_pool_type='process',
                                 workers_count=min(WORKERS, 2), num_epochs=1,
                                 seed=13, shuffle_row_groups=True,
                                 topology=policy)
            rows = 0
            start = time.perf_counter()
            for batch in reader.iter_columnar():
                rows += batch.num_rows
            elapsed = time.perf_counter() - start
            reader.stop()
            reader.join()
            return rows / elapsed

        journal = os.path.join(topo_root, 'membership-journal.bin')
        bare_rates, armed_rates = [], []
        for _ in range(3):  # interleaved pairs: shared-host drift cancels
            bare_rates.append(epoch(None))
            armed_rates.append(epoch(TopologyPolicy(journal_path=journal,
                                                    process_index=0,
                                                    process_count=1)))
        bare_rate = max(bare_rates)
        armed_rate = max(armed_rates)
        overhead_pct = (bare_rate - armed_rate) / bare_rate * 100.0

        verdict = run_host_chaos(url, os.path.join(topo_root, 'kill'),
                                 hosts=2, seed=13, kill_host=True)
        # the survivor-side reshard decision, re-timed on the journal the
        # probe left behind: replay + undelivered remainder + re-deal is
        # everything a survivor computes before its recovery epoch starts
        kill_journal = verdict['journal']['path']
        start = time.perf_counter()
        replay = replay_topology_journal(kill_journal)
        remainder = undelivered_items(verdict['global_rowgroups'], 0,
                                      replay.delivered)
        if remainder:
            reshard_assignments(remainder, ['host-0'])
        reshard_decision_ms = (time.perf_counter() - start) * 1000.0

        log('topology: armed {:.1f} rows/s vs bare {:.1f} rows/s ({:+.2f}% '
            'negotiation overhead; acceptance <=3%); 2-host kill probe: '
            'rows {} ({}/{}), composed digest {}, {} undelivered item(s) '
            're-dealt, reshard decision {:.2f} ms'.format(
                armed_rate, bare_rate, overhead_pct,
                'exact' if verdict['rows_exact'] else 'LOST/DUPED',
                verdict['rows_chaos'], verdict['rows_baseline'],
                'EXACT' if verdict['digest_exact'] else 'DIVERGED',
                verdict['undelivered_resharded'], reshard_decision_ms))
        results.update({
            'topology_armed_rows_per_sec': round(armed_rate, 1),
            'topology_bare_rows_per_sec': round(bare_rate, 1),
            'topology_overhead_pct': round(overhead_pct, 2),
            'topology_kill_rows_exact': bool(verdict['rows_exact']),
            'topology_kill_digest_exact': bool(verdict['digest_exact']),
            'topology_kill_verdict_ok': bool(verdict['ok']),
            'topology_undelivered_resharded':
                int(verdict['undelivered_resharded']),
            'topology_reshard_decision_ms': round(reshard_decision_ms, 2),
        })

    def run_schedule():
        """Cost-aware scheduling (host-only; docs/performance.md "Cost-aware
        scheduling"): on a deliberately skewed store (heavy random-payload
        rowgroups clustered at the END — the worst-case FIFO tail stall),
        (1) FIFO epoch vs cost-scheduled epoch (interleave + split from a
        profiled ledger) => ``schedule_speedup``; (2) cold-start overhead
        guard — scheduler armed with NO ledger vs plain, <=3% (the plan is a
        no-op there, so any cost is bookkeeping); (3) a socket-free
        FairShareScheduler probe showing the measured-cost DRR spreading the
        ledger's heavy items across >=2 workers (the routing half of the
        ISSUE-12 acceptance, deterministic — no fleet to flake)."""
        from petastorm_tpu.codecs import CompressedNdarrayCodec, ScalarCodec
        from petastorm_tpu.etl.dataset_metadata import write_rows
        from petastorm_tpu.telemetry import tracing as flight
        from petastorm_tpu.telemetry.cost_model import default_ledger_path
        from petastorm_tpu.unischema import Unischema, UnischemaField

        heavy_rows = int(os.environ.get('BENCH_SCHEDULE_HEAVY_ROWS', 24))
        light_rows = int(os.environ.get('BENCH_SCHEDULE_LIGHT_ROWS', 72))
        heavy_dim = int(os.environ.get('BENCH_SCHEDULE_HEAVY_DIM', 512))
        sched_dir = tempfile.mkdtemp(prefix='bench_schedule_')
        sched_url = 'file://' + os.path.join(sched_dir, 'skewed')
        # variable-shape compressed payload: light rows are one 4KB vector,
        # heavy rows inflate a ~2MB patterned (compressible, so the deflate
        # decode does real output work) matrix — the image-vs-scalar cost
        # spread in rowgroup form
        schema = Unischema('ScheduleBench', [
            UnischemaField('idx', np.int64, (), ScalarCodec(), False),
            UnischemaField('payload', np.float32, (None, 1024),
                           CompressedNdarrayCodec(), False),
        ])
        rng = np.random.RandomState(7)
        pattern = np.tile(rng.rand(8, 1024).astype(np.float32),
                          (heavy_dim // 8, 1))

        def rows():
            # lights first, heavies last: FIFO pays the full tail stall
            for i in range(light_rows):
                yield {'idx': i,
                       'payload': np.zeros((1, 1024), np.float32)}
            for i in range(light_rows, light_rows + heavy_rows):
                yield {'idx': i, 'payload': pattern}
        # small files: many light rowgroups ahead of the few heavy ones, so
        # under FIFO the heavies only ventilate once the bounded in-flight
        # window has drained most of the lights — the batch-former stall
        write_rows(sched_url, schema, rows(), rowgroup_size_mb=64,
                   rows_per_file=8)

        # paced consumer: a fixed per-row budget models the train step the
        # batch former feeds (the stall in the ISSUE-12 motivation). Pacing
        # is sleep, not CPU, so decode genuinely overlaps it even on this
        # 1-core bench host — what pre-staging is FOR; raw unpaced drain on
        # one core is decode-bound and order-insensitive by construction.
        pace_s = float(os.environ.get('BENCH_SCHEDULE_PACE_S', 0.004))

        def epoch_seconds(cost_schedule=None):
            reader = make_reader(sched_url, reader_pool_type='process',
                                 workers_count=2, num_epochs=1,
                                 shuffle_row_groups=False,
                                 cost_schedule=cost_schedule)
            start = time.perf_counter()
            rows_read = 0
            for batch in reader.iter_columnar():
                rows_read += batch.num_rows
                time.sleep(batch.num_rows * pace_s)
            elapsed = time.perf_counter() - start
            diag_schedule = (reader.diagnostics.get('schedule')
                             if cost_schedule else None)
            reader.stop()
            reader.join()
            assert rows_read == heavy_rows + light_rows
            return elapsed, diag_schedule

        # warmup epoch (fs cache + process spawn cold start)
        epoch_seconds()
        plain_s = min(epoch_seconds()[0], epoch_seconds()[0])

        # profile one traced epoch -> persisted ledger at the default path
        flight.reset_tracing()
        flight.set_trace_enabled(True)
        try:
            reader = make_reader(sched_url, workers_count=2, num_epochs=1,
                                 shuffle_row_groups=False)
            for batch in reader.iter_columnar():
                pass
            ledger = reader.cost_ledger()
            token = reader.dataset_token
            reader.stop()
            reader.join()
        finally:
            flight.set_trace_enabled(False)
            flight.reset_tracing()
        ledger_path = default_ledger_path(sched_url, token)
        ledger.save(ledger_path)

        # (1) FIFO vs cost-scheduled, interleaved A/B/A/B/A/B to cancel host
        # drift (the autotune section's methodology); min-of-runs — per-epoch
        # process-pool spawn makes single pairs noisy
        pairs = int(os.environ.get('BENCH_SCHEDULE_PAIRS', 3))
        fifo_runs, sched_runs = [], []
        sched_report = None
        for _ in range(pairs):
            fifo_s, _ = epoch_seconds()
            sched_s, sched_report = epoch_seconds(cost_schedule=True)
            fifo_runs.append(fifo_s)
            sched_runs.append(sched_s)
        fifo_s = min(fifo_runs)
        sched_s = min(sched_runs)
        speedup = fifo_s / sched_s if sched_s else 0.0

        # (2) cold-start overhead, measured DIRECTLY (the autotune section's
        # methodology: whole-pipeline A/B deltas on sub-second epochs drift
        # +-10% and guard nothing): time exactly what an armed-cold reader
        # adds — the failed sidecar load, the no-op plan, one order pass per
        # epoch, one observe per batch — against the plain epoch wall
        from petastorm_tpu.schedule import (CostAwareScheduler,
                                            SchedulePolicy, load_ledger)
        probe_start = time.perf_counter()
        load_ledger(sched_url, 'no-such-token')
        cold_sched = CostAwareScheduler('no-such-token', SchedulePolicy())
        cold_items = [{'piece_index': i,
                       'shuffle_row_drop_partition': (0, 1)}
                      for i in range(16)]
        cold_locator = {i: ('part', 0, 8) for i in range(16)}
        cold_items, _ = cold_sched.plan_items(cold_items, cold_locator,
                                              max_parts=2)
        cold_sched.order_items(cold_items, None)
        for i in range(16):
            cold_sched.observe(i, {'decode': {'sum': 0.0, 'count': 1}})
        overhead_s = time.perf_counter() - probe_start
        overhead_pct = overhead_s / plain_s * 100.0

        # (3) measured-cost DRR probe: heavy ledger items through a 2-worker
        # socket-free scheduler — distinct workers the heavies landed on
        from petastorm_tpu.service.dispatcher import FairShareScheduler
        from petastorm_tpu.service.wire import WorkerDescriptor
        cost_sched = CostAwareScheduler(token, SchedulePolicy(), ledger=ledger)
        heavy_keys = cost_sched.report()['heavy_rowgroups']
        fake_clock = [0.0]
        drr = FairShareScheduler(clock=lambda: fake_clock[0])
        drr.add_client(b'c', 'bench', 'host', None)
        drr.add_worker(b'w1', WorkerDescriptor(1, 1, 'host'))
        drr.add_worker(b'w2', WorkerDescriptor(2, 2, 'host'))
        drr.add_setup(b'c', b's', b'x')
        for index, key in enumerate(heavy_keys):
            drr.submit(b'c', b'%d' % index, b's', b'x',
                       cost=cost_sched.normalized_cost(key))
        heavy_workers = set()
        while True:
            drr.worker_ready(b'w1')
            drr.worker_ready(b'w2')
            assignment = drr.next_assignment()
            if assignment is None:
                break
            heavy_workers.add(assignment.worker_key)
            drr.retire(assignment.token, assignment.attempt)

        splits = len((sched_report or {}).get('splits', []))
        cpus = os.cpu_count() or 1
        log('schedule: fifo {:.3f}s vs cost-aware {:.3f}s ({:.2f}x on {} '
            'cpu(s) — split parallelism scales with cores), {} split(s), '
            'cold-path overhead {:+.3f}%, heavy items spread across {} '
            'worker(s)'.format(fifo_s, sched_s, speedup, cpus, splits,
                               overhead_pct, len(heavy_workers)))
        results.update({
            'schedule_fifo_epoch_s': round(fifo_s, 4),
            'schedule_cost_aware_epoch_s': round(sched_s, 4),
            'schedule_speedup': round(speedup, 3),
            'schedule_splits': splits,
            'schedule_heavy_rowgroups': len(heavy_keys),
            'schedule_overhead_pct': round(overhead_pct, 3),
            'schedule_heavy_worker_spread': len(heavy_workers),
            'schedule_cpu_count': cpus,
        })

    def run_storage():
        """Object-store ingest engine (host-only; docs/performance.md
        "Object-store ingest engine"): against a latency-injected store
        whose distribution has a deterministic p99 tail (FaultSchedule
        ``tail_every_n``), (1) seed passthrough reads vs
        planned+coalesced+hedged engine reads => ``storage_coalesce_speedup``
        (the ISSUE-17 >=1.3x acceptance), with the hedge counters proving
        duplicates actually fired and won; (2) per-batch arrival-interval
        p99, engine hedge-off vs hedge-on =>
        ``storage_hedge_p99_improvement_pct``; (3) footer-cache hit rate
        across the multi-epoch run; (4) the cold-path guard measured on the
        clean local store — ``storage_policy=None`` (auto-resolve says
        local => seed path plus the resolution/gating bookkeeping) vs
        explicitly-off, <=3%."""
        from petastorm_tpu.codecs import ScalarCodec
        from petastorm_tpu.etl.dataset_metadata import write_rows
        from petastorm_tpu.storage import (StoragePolicy,
                                           reset_storage_metrics,
                                           storage_metrics_snapshot)
        from petastorm_tpu.test_util.fault_injection import (
            FaultRule, FaultSchedule, fault_injecting_filesystem)
        from petastorm_tpu.unischema import Unischema, UnischemaField

        storage_dir = tempfile.mkdtemp(prefix='bench_storage_')
        store_url = 'file://' + os.path.join(storage_dir, 'wide')
        n_rows = int(os.environ.get('BENCH_STORAGE_ROWS', 256))
        n_cols = int(os.environ.get('BENCH_STORAGE_COLS', 6))
        # base per-request RTT + a tail stall on every Nth open/read event:
        # the injected model of an object store's p99 (docs/robustness.md)
        base_s = float(os.environ.get('BENCH_STORAGE_BASE_S', 0.02))
        tail_s = float(os.environ.get('BENCH_STORAGE_TAIL_S', 0.4))
        tail_every = int(os.environ.get('BENCH_STORAGE_TAIL_EVERY', 8))
        epochs = int(os.environ.get('BENCH_STORAGE_EPOCHS', 2))

        # wide scalar store: every rowgroup is n_cols+1 small column chunks —
        # exactly the many-tiny-GETs shape footer-planned coalescing collapses
        schema = Unischema('StorageBench', [
            UnischemaField('idx', np.int64, (), ScalarCodec(), False),
        ] + [UnischemaField('c{}'.format(i), np.float64, (), ScalarCodec(),
                            False) for i in range(n_cols)])

        def store_rows():
            for i in range(n_rows):
                row = {'idx': i}
                row.update({'c{}'.format(j): float(i * (j + 1))
                            for j in range(n_cols)})
                yield row
        write_rows(store_url, schema, store_rows(), rowgroup_size_mb=64,
                   rows_per_file=32)

        # the hedge deadline must sit between the base RTT and the tail:
        # quantile 0.5 keeps the adaptive estimate anchored on the base
        # (with a 1-in-8 tail, a p90 would BE a tail sample and the deadline
        # would chase it out of reach)
        hedged_policy = StoragePolicy(
            hedge_quantile=0.5, hedge_min_s=0.05,
            cache_dir=os.path.join(storage_dir, 'footers'))
        unhedged_policy = StoragePolicy(
            hedge_enabled=False,
            cache_dir=os.path.join(storage_dir, 'footers_unhedged'))

        state_seq = [0]

        def epoch(policy):
            """One injected multi-epoch read; fresh fault state per run so
            every arm faces the identical deterministic distribution.
            Returns (wall seconds, per-batch arrival intervals)."""
            state_seq[0] += 1
            sched = FaultSchedule(
                os.path.join(storage_dir, 'faults_{}'.format(state_seq[0])),
                [FaultRule('part_', kind='latency', latency_s=base_s,
                           tail_latency_s=tail_s, tail_every_n=tail_every)])
            reader = make_reader(store_url, reader_pool_type='dummy',
                                 num_epochs=epochs, shuffle_row_groups=False,
                                 filesystem=fault_injecting_filesystem(sched),
                                 storage_policy=policy)
            rows_read = 0
            intervals = []
            last = None
            start = time.perf_counter()
            for batch in reader.iter_columnar():
                now = time.perf_counter()
                if last is not None:
                    intervals.append(now - last)
                last = now
                rows_read += batch.num_rows
            elapsed = time.perf_counter() - start
            reader.stop()
            reader.join()
            assert rows_read == n_rows * epochs
            return elapsed, intervals

        # (1) passthrough vs planned+coalesced+hedged, interleaved pairs
        # with min-of-runs (the schedule section's methodology)
        pairs = int(os.environ.get('BENCH_STORAGE_PAIRS', 2))
        passthrough_runs, engine_runs = [], []
        engine_intervals = []
        reset_storage_metrics()
        for _ in range(pairs):
            passthrough_runs.append(epoch(False)[0])
            engine_s, intervals = epoch(hedged_policy)
            engine_runs.append(engine_s)
            engine_intervals = intervals
        counters = storage_metrics_snapshot().get('counters', {})
        passthrough_s = min(passthrough_runs)
        engine_s = min(engine_runs)
        speedup = passthrough_s / engine_s if engine_s else 0.0
        hedges_fired = int(counters.get('storage_hedge_fired', 0))
        hedges_won = int(counters.get('storage_hedge_won', 0))
        hits = int(counters.get('storage_footer_cache_hit', 0))
        misses = int(counters.get('storage_footer_cache_miss', 0))
        hit_rate = hits / (hits + misses) if (hits + misses) else 0.0

        # (2) injected-tail p99 per batch interval: same engine, hedge off.
        # Scored on the LAST epoch's intervals only: by then footers are
        # cached in both arms, so every injected event lands on a hedgeable
        # range fetch — epoch-1 footer reads are unhedged by design (one
        # small read, no duplicate worth racing) and would tail both arms
        # equally.
        _, unhedged_intervals = epoch(unhedged_policy)
        last_epoch = (n_rows // 32) - 1  # batches per epoch - 1 intervals
        p99_off = float(np.percentile(unhedged_intervals[-last_epoch:], 99))
        p99_on = float(np.percentile(engine_intervals[-last_epoch:], 99))
        p99_improvement_pct = ((p99_off - p99_on) / p99_off * 100.0
                               if p99_off else 0.0)

        # (4) cold-path overhead, measured DIRECTLY (the schedule section's
        # methodology: whole-pipeline A/B deltas on these ~100ms local
        # epochs drift +-10% on this shared host and guard nothing): on a
        # local URL ``storage_policy=None`` adds exactly one auto-resolve at
        # reader construction (=> None: local scheme) plus one disarmed gate
        # per rowgroup load — time those against a measured plain epoch wall
        from petastorm_tpu.storage import resolve_storage_policy

        def clean_epoch():
            reader = make_reader(store_url, reader_pool_type='dummy',
                                 num_epochs=1, shuffle_row_groups=False,
                                 storage_policy=False)
            start = time.perf_counter()
            rows_read = 0
            for batch in reader.iter_columnar():
                rows_read += batch.num_rows
            elapsed = time.perf_counter() - start
            reader.stop()
            reader.join()
            assert rows_read == n_rows
            return elapsed

        clean_epoch()  # warmup: fs cache
        plain_s = min(clean_epoch() for _ in range(3))

        class _DisarmedSetup(object):
            storage_policy = None
        rowgroups = n_rows // 32
        armed_loads = 0
        probe_start = time.perf_counter()
        resolved = resolve_storage_policy(None, store_url)
        for _ in range(rowgroups):
            if getattr(_DisarmedSetup, 'storage_policy', None) is not None:
                armed_loads += 1
        overhead_s = time.perf_counter() - probe_start
        assert resolved is None and armed_loads == 0
        cold_overhead_pct = overhead_s / plain_s * 100.0

        log('storage: passthrough {:.3f}s vs engine {:.3f}s ({:.2f}x), '
            'hedges {} fired / {} won, footer cache {:.0%} hits, batch p99 '
            '{:.3f}s unhedged -> {:.3f}s hedged ({:+.1f}%), cold-path '
            'overhead {:+.2f}%'.format(
                passthrough_s, engine_s, speedup, hedges_fired, hedges_won,
                hit_rate, p99_off, p99_on, p99_improvement_pct,
                cold_overhead_pct))
        results.update({
            'storage_passthrough_epoch_s': round(passthrough_s, 4),
            'storage_engine_epoch_s': round(engine_s, 4),
            'storage_coalesce_speedup': round(speedup, 3),
            'storage_hedges_fired': hedges_fired,
            'storage_hedges_won': hedges_won,
            'storage_footer_cache_hit_rate': round(hit_rate, 3),
            'storage_hedge_p99_improvement_pct': round(p99_improvement_pct, 1),
            'storage_cold_overhead_pct': round(cold_overhead_pct, 2),
        })

    def run_resilience():
        """Watchdog + CRC clean-path overhead (host-only, fast): the same
        process-pool epoch with every robustness guard off (no heartbeats, no
        hang timeout, no shm checksum) vs the shipping defaults; the overhead
        percentage is the BENCH-history guard for the ISSUE-4 acceptance
        (<= 3% on the clean path — docs/robustness.md)."""
        from petastorm_tpu.workers.process_pool import ProcessPool

        def epoch_rows_per_sec(guarded):
            if guarded:
                pool = ProcessPool(min(WORKERS, 2))
            else:
                pool = ProcessPool(min(WORKERS, 2), heartbeat_interval_s=0,
                                   hang_timeout_s=None, shm_checksum=False)
            reader = make_reader(url, reader_pool=pool, num_epochs=1,
                                 shuffle_row_groups=False)
            rows = 0
            start = time.perf_counter()
            for batch in reader.iter_columnar():
                rows += batch.num_rows
            elapsed = time.perf_counter() - start
            diag = reader.diagnostics
            reader.stop()
            reader.join()
            return rows / elapsed, diag

        baseline_rate, _ = epoch_rows_per_sec(guarded=False)
        guarded_rate, diag = epoch_rows_per_sec(guarded=True)
        overhead_pct = (baseline_rate - guarded_rate) / baseline_rate * 100.0
        log('resilience: guarded {:.1f} rows/s vs bare {:.1f} rows/s '
            '({:+.2f}% watchdog+CRC overhead); {} shm batches CRC-verified'
            .format(guarded_rate, baseline_rate, overhead_pct,
                    diag.get('shm_batches', 0)))
        results.update({
            'resilience_guarded_rows_per_sec': round(guarded_rate, 1),
            'resilience_baseline_rows_per_sec': round(baseline_rate, 1),
            'resilience_overhead_pct': round(overhead_pct, 2),
            'resilience_crc_verified_batches': diag.get('shm_batches', 0),
            'resilience_breaker_state':
                diag.get('breakers', {}).get('shm_transport',
                                             {}).get('state', 'closed'),
        })

    def run_service():
        """Disaggregated input service (host-only; docs/service.md): one
        localhost fleet epoch via make_reader(service_url=...) vs the
        in-process process-pool epoch on the same store, plus a second
        service epoch against the fleet's (now warm) shared cache — the
        ISSUE-8 numbers: the TCP dispatch overhead a co-located deployment
        pays, and the warm-hit speedup every OTHER job reading the same
        dataset inherits."""
        import shutil as _shutil
        from petastorm_tpu.service.fleet import ServiceFleet
        from petastorm_tpu.workers.process_pool import ProcessPool

        service_workers = min(WORKERS, 2)

        def pool_epoch():
            reader = make_reader(url, reader_pool=ProcessPool(service_workers),
                                 num_epochs=1, shuffle_row_groups=False)
            rows = 0
            start = time.perf_counter()
            for batch in reader.iter_columnar():
                rows += batch.num_rows
            elapsed = time.perf_counter() - start
            reader.stop()
            reader.join()
            return rows / elapsed

        def service_epoch(service_url):
            reader = make_reader(url, service_url=service_url, num_epochs=1,
                                 shuffle_row_groups=False)
            rows = 0
            start = time.perf_counter()
            for batch in reader.iter_columnar():
                rows += batch.num_rows
            elapsed = time.perf_counter() - start
            diag = reader.diagnostics
            reader.stop()
            reader.join()
            return rows / elapsed, diag

        cache_dir = tempfile.mkdtemp(prefix='petastorm_tpu_bench_service_')
        try:
            with ServiceFleet(workers=service_workers,
                              cache_dir=cache_dir) as fleet:
                cold_rate, diag = service_epoch(fleet.service_url)
                warm_rate, warm_diag = service_epoch(fleet.service_url)
            pool_rate = pool_epoch()
        finally:
            _shutil.rmtree(cache_dir, ignore_errors=True)
        overhead_pct = (pool_rate - cold_rate) / pool_rate * 100.0
        warm_speedup = warm_rate / max(cold_rate, 1e-9)
        log('service: {:.1f} rows/s over the fleet (cold) vs {:.1f} rows/s '
            'in-process ({:+.1f}% dispatch overhead); warm shared-cache '
            'epoch {:.1f} rows/s ({:.2f}x), {} shm batch(es), {} worker(s)'
            .format(cold_rate, pool_rate, overhead_pct, warm_rate,
                    warm_speedup, diag.get('service_shm_batches', 0),
                    service_workers))
        results.update({
            'service_rows_per_sec': round(cold_rate, 1),
            'service_pool_rows_per_sec': round(pool_rate, 1),
            'service_overhead_pct': round(overhead_pct, 2),
            'service_cache_warm_rows_per_sec': round(warm_rate, 1),
            'service_cache_warm_speedup': round(warm_speedup, 3),
            'service_shm_batches': diag.get('service_shm_batches', 0),
            'service_warm_cache_hits': warm_diag.get('cache_hits', 0),
            # provenance: the fleet shape behind the numbers
            'service_workers': service_workers,
        })

    def run_chaos():
        """Epoch-survivable control plane (host-only; docs/service.md
        "Restarting with a ledger"): the ISSUE-16 numbers. Three localhost
        fleet epochs on the bench store: ledger-off vs ledger-armed (the
        journal's happy-path cost — the <=3% acceptance guard), then a
        ledger-armed epoch with the dispatcher hard-crashed mid-epoch —
        rows must stay exact and the recovery gap (crash to the first
        post-restart batch; optimistic by whatever the client had
        prefetched) is the headline robustness number."""
        import shutil as _shutil
        from petastorm_tpu.service.fleet import ServiceFleet

        os.environ.setdefault('PETASTORM_TPU_SERVICE_RESPONSE_TIMEOUT_S',
                              '2.0')
        service_workers = min(WORKERS, 2)

        def epoch(fleet, crash_at=None):
            reader = make_reader(url, service_url=fleet.service_url,
                                 num_epochs=1, shuffle_row_groups=False)
            rows = 0
            crash_t = None
            recovery_s = None
            start = time.perf_counter()
            for batch in reader.iter_columnar():
                if crash_t is not None and recovery_s is None:
                    recovery_s = time.perf_counter() - crash_t
                rows += batch.num_rows
                if crash_at is not None and rows >= crash_at \
                        and crash_t is None:
                    crash_t = time.perf_counter()
                    fleet.crash_dispatcher()
            elapsed = time.perf_counter() - start
            reader.stop()
            reader.join()
            return rows, rows / elapsed, recovery_s

        def fleet_epoch(ledger_dir=None, crash_at=None):
            cache_dir = tempfile.mkdtemp(prefix='petastorm_tpu_bench_chaos_')
            try:
                with ServiceFleet(workers=service_workers,
                                  cache_dir=cache_dir,
                                  ledger=bool(ledger_dir)) as fleet:
                    rows, rate, recovery_s = epoch(fleet, crash_at=crash_at)
                    epoch_n = fleet.dispatcher.ledger_state().get('epoch', 0)
                return rows, rate, recovery_s, epoch_n
            finally:
                _shutil.rmtree(cache_dir, ignore_errors=True)

        plain_rows, plain_rate, _, _ = fleet_epoch()
        armed_rows, armed_rate, _, _ = fleet_epoch(ledger_dir=True)
        crash_rows, crash_rate, recovery_s, ledger_epoch = fleet_epoch(
            ledger_dir=True, crash_at=max(1, plain_rows // 2))
        overhead_pct = (plain_rate - armed_rate) / plain_rate * 100.0
        rows_exact = (armed_rows == plain_rows and crash_rows == plain_rows)
        log('chaos: ledger-armed epoch {:.1f} rows/s vs {:.1f} rows/s '
            'unarmed ({:+.1f}% journal overhead; acceptance <=3%); '
            'dispatcher SIGKILL mid-epoch: {}/{} rows ({}), {:.2f}s to the '
            'first post-restart batch, ledger epoch {}'
            .format(armed_rate, plain_rate, overhead_pct,
                    crash_rows, plain_rows,
                    'exact' if rows_exact else 'LOST/DUPED',
                    recovery_s or 0.0, ledger_epoch))
        if overhead_pct > 3.0:
            log('chaos: WARNING — ledger-armed overhead {:.1f}% exceeds the '
                '3% acceptance bound'.format(overhead_pct))
        results.update({
            'chaos_plain_rows_per_sec': round(plain_rate, 1),
            'chaos_ledger_rows_per_sec': round(armed_rate, 1),
            'chaos_ledger_overhead_pct': round(overhead_pct, 2),
            'chaos_recovery_s': round(recovery_s or 0.0, 3),
            'chaos_crash_rows_per_sec': round(crash_rate, 1),
            'chaos_rows_exact': rows_exact,
            'chaos_ledger_epoch': ledger_epoch,
            'chaos_workers': service_workers,
        })

    def run_autotune():
        """Closed-loop autotuner (host-only; docs/autotuning.md): the ISSUE-9
        acceptance numbers. Uses a dedicated heavier store (the mnist bench
        store's epochs are ~10ms — shorter than any control window): a reader
        started from deliberately degraded knobs (1 worker, in-flight window
        1) runs time-budgeted epochs with the controller on — the median of
        the last completed epochs shows what the hill climb converged to,
        next to the degraded-off baseline and the fixed-default epoch rate.
        The overhead guard runs the controller in measure-only mode (empty
        knob allowlist: it samples telemetry every window but never actuates)
        on a default-shaped reader — the <=3% controller-cost acceptance."""
        from petastorm_tpu.autotune import AutotunePolicy
        from petastorm_tpu.codecs import NdarrayCodec, ScalarCodec
        from petastorm_tpu.etl.dataset_metadata import write_rows
        from petastorm_tpu.unischema import Unischema, UnischemaField

        at_rows = int(os.environ.get('BENCH_AUTOTUNE_ROWS', 8000))
        at_url = 'file://' + os.path.join(
            tempfile.gettempdir(),
            'petastorm_tpu_bench_autotune_{}'.format(at_rows))
        if not os.path.exists(at_url[len('file://'):]):
            at_schema = Unischema('AutotuneBench', [
                UnischemaField('idx', np.int64, (), ScalarCodec(), False),
                UnischemaField('vec', np.float32, (256,), NdarrayCodec(),
                               False),
            ])
            write_rows(at_url, at_schema,
                       ({'idx': i, 'vec': np.full(256, i % 97, np.float32)}
                        for i in range(at_rows)), rowgroup_size_mb=1)

        # calm pacing: 0.3s windows + a 2% gate keep scheduler noise from
        # validating commits (a noisy gate lets the climb wander off the
        # optimum it already found)
        policy = AutotunePolicy(window_s=0.3, warmup_windows=1,
                                hold_windows=1, min_improvement=0.02,
                                cooldown_windows=3)
        base_budget_s = float(os.environ.get('BENCH_AUTOTUNE_BASE_S', 2.5))
        tuned_budget_s = float(os.environ.get('BENCH_AUTOTUNE_TUNED_S', 15.0))

        def run_reader(workers, autotune=None, budget_s=base_budget_s,
                       vent_in_flight=None):
            """One time-budgeted run over whole epochs (num_epochs=None,
            stopped at the first epoch boundary past the budget, always
            completing >=2 epochs); returns (whole-run rows/s, completed
            per-epoch rows/s list, autotune report). ``vent_in_flight`` pins
            the ventilation window (1 = the deliberate degradation; the
            tuner-found value = the converged-config measurement run)."""
            kwargs = {'num_epochs': None, 'shuffle_row_groups': False,
                      'autotune': autotune}
            if workers is not None:
                kwargs['workers_count'] = workers
            reader = make_reader(at_url, **kwargs)
            if vent_in_flight is not None:
                reader._ventilator.set_max_in_flight(int(vent_in_flight))
            rows = 0
            epoch_rows = {}
            epoch_start = {}
            epoch_end = {}
            cur_epoch = None
            start = time.perf_counter()
            for batch in reader.iter_columnar():
                now = time.perf_counter()
                epoch = batch.item_id[0] if batch.item_id else 0
                if (epoch != cur_epoch and cur_epoch is not None
                        and len(epoch_rows) >= 2
                        and now - start > budget_s):
                    break
                cur_epoch = epoch
                epoch_start.setdefault(epoch, now)
                epoch_end[epoch] = now
                epoch_rows[epoch] = epoch_rows.get(epoch, 0) + batch.num_rows
                rows += batch.num_rows
            elapsed = time.perf_counter() - start
            report = reader.autotune_report()
            reader.stop()
            reader.join()
            # completed epochs only (the one we broke out of is complete —
            # the break fires on the FIRST batch of the next epoch)
            per_epoch = [epoch_rows[e] / max(epoch_end[e] - epoch_start[e],
                                             1e-9)
                         for e in sorted(epoch_rows)
                         if epoch_rows[e] and epoch_end[e] > epoch_start[e]]
            return rows / max(elapsed, 1e-9), per_epoch, report, elapsed

        def tail_median(rates, fallback):
            """Steady-state ('converged') rate of one run: the median of the
            last quarter of its completed epochs — excludes spin-up for EVERY
            run the same way, so tuned-vs-default compares plateau to plateau
            (not the tuned plateau to a default average paying its warmup)."""
            tail = rates[-max(1, len(rates) // 4):]
            return sorted(tail)[len(tail) // 2] if tail else fallback

        # The decode-threads knob actuates through the env contract; restore
        # it so a tuned value cannot leak into later sections' readers.
        saved_decode_threads = os.environ.get('PETASTORM_TPU_DECODE_THREADS')
        try:
            # warm-up run: pages the store into cache so no later config is
            # the one paying the cold reads
            run_reader(None, budget_s=base_budget_s / 2)
            degraded_run_rate, degraded_epochs, _, _ = run_reader(
                1, vent_in_flight=1)
            tuned_rate, tuned_epoch_rates, report, _ = run_reader(
                1, autotune=policy, budget_s=tuned_budget_s,
                vent_in_flight=1)
            # "converged" = the plateau of the CONFIGURATION the climb found,
            # measured without the controller: the tuned run's own tail still
            # pays the exploration tax (propose -> hold -> revert cycles keep
            # perturbing a converged pipeline), which is controller overhead,
            # not the quality of the answer it converged to.
            knobs = report.get('knobs', {})
            found_workers = int((knobs.get('pool_workers') or {})
                                .get('value') or 1)
            found_in_flight = int((knobs.get('ventilator_max_in_flight')
                                   or {}).get('value') or 1)
            found_decode = (knobs.get('decode_threads') or {}).get('value')
            if found_decode is not None:
                os.environ['PETASTORM_TPU_DECODE_THREADS'] = str(
                    int(found_decode))
            # Paired A/B/A/B/A/B alternation: ambient load on this shared
            # host drifts run rates by far more than the effect size, so
            # back-to-back interleaved rounds (ratio of summed plateau rates)
            # cancel the drift to first order — the only comparison at this
            # noise floor that means anything.
            paired = {'default': [], 'converged': []}
            for _ in range(3):
                rate, epochs, _ignored, _t = run_reader(
                    None, budget_s=base_budget_s / 2)
                paired['default'].append(tail_median(epochs, rate))
                rate, epochs, _ignored, _t = run_reader(
                    found_workers, vent_in_flight=found_in_flight,
                    budget_s=base_budget_s / 2)
                paired['converged'].append(tail_median(epochs, rate))
        finally:
            if saved_decode_threads is None:
                os.environ.pop('PETASTORM_TPU_DECODE_THREADS', None)
            else:
                os.environ['PETASTORM_TPU_DECODE_THREADS'] = saved_decode_threads
        default_rate = sum(paired['default']) / len(paired['default'])
        degraded_rate = tail_median(degraded_epochs, degraded_run_rate)
        tuned_final = sum(paired['converged']) / len(paired['converged'])
        # Controller overhead: a measure-only controller (samples telemetry +
        # attributes the bottleneck every window, zero actuations) on a
        # default-shaped reader, measured DIRECTLY — controller step seconds
        # over run wall time. Whole-pipeline A/B deltas on this shared host
        # drift by several percent between runs, far above the controller's
        # true cost; the direct account is what the <=3% guard actually
        # asserts about.
        measure_only = AutotunePolicy(window_s=0.3, knob_ids=())
        _rate, _epochs, guard_report, guard_elapsed = run_reader(
            None, autotune=measure_only, budget_s=base_budget_s)
        overhead_pct = (guard_report.get('controller_step_seconds', 0.0)
                        / max(guard_elapsed, 1e-9) * 100.0)
        decisions = report.get('decisions', [])
        log('autotune: degraded {:.1f} -> converged config {:.1f} rows/s '
            '(default {:.1f}) after {} epoch(s)/{} window(s); {} decision(s), '
            '{} committed, {} reverted; workers {} in-flight {}; controller '
            'overhead {:+.2f}%'.format(
                degraded_rate, tuned_final, default_rate,
                len(tuned_epoch_rates), report.get('windows', 0),
                len(decisions), report.get('committed', 0),
                report.get('reverted', 0), found_workers, found_in_flight,
                overhead_pct))
        results.update({
            'autotune_default_rows_per_sec': round(default_rate, 1),
            'autotune_degraded_rows_per_sec': round(degraded_rate, 1),
            'autotune_tuned_rows_per_sec': round(tuned_rate, 1),
            'autotune_tuned_final_epoch_rows_per_sec': round(tuned_final, 1),
            'autotune_tuned_vs_default':
                round(tuned_final / max(default_rate, 1e-9), 3),
            'autotune_tuned_vs_degraded':
                round(tuned_final / max(degraded_rate, 1e-9), 3),
            'autotune_decisions': len(decisions),
            'autotune_committed': report.get('committed', 0),
            'autotune_reverted': report.get('reverted', 0),
            'autotune_windows': report.get('windows', 0),
            'autotune_frozen_by_breaker': report.get('frozen_by_breaker',
                                                     False),
            'autotune_final_pool_workers':
                (knobs.get('pool_workers') or {}).get('value'),
            'autotune_final_ventilator_max_in_flight':
                (knobs.get('ventilator_max_in_flight') or {}).get('value'),
            'autotune_final_decode_threads':
                (knobs.get('decode_threads') or {}).get('value'),
            'autotune_overhead_pct': round(overhead_pct, 2),
            'autotune_tuned_epochs': len(tuned_epoch_rates),
            # provenance: the store + budgets behind the numbers
            'autotune_store_rows': at_rows,
            'autotune_tuned_budget_s': tuned_budget_s,
        })

    def run_device_decode():
        """Device-resident decode tail (ISSUE 10; docs/performance.md): the
        DCT image store read twice through JaxDataLoader — host decode (the
        codec's numpy IDCT in the reader workers) vs ship-raw
        (``device_decode_fields=['image']``: coefficients upload with the
        batch, dequant+IDCT runs as a jitted device
        kernel double-buffered against the consumer). ``h2d_overlap_fraction``
        is 1 - input_stall_fraction of the ship-raw run: the share of the
        input pipeline's work (upload + device decode included) hidden behind
        the consuming loop. On a CPU backend the tail falls back to
        byte-identical host decode and the line says so honestly
        (``cpu_fallback=true`` + device_decode_batches=0) — treat those
        numbers as a fallback-path regression check, not a decode-tail
        measurement."""
        section_start = time.monotonic()
        img_url = imagenet_dataset_url()
        if not os.path.exists(os.path.join(img_url, '_common_metadata')):
            log('materializing {} DCT images to {}'.format(IMG_ROWS, img_url))
            build_imagenet_dataset(img_url)
        dd_epochs = int(os.environ.get('BENCH_DEVICE_DECODE_EPOCHS', 3))

        def run_epochs(device_fields, label):
            rates = []
            stats = {}
            snapshot = {}
            for _ in range(dd_epochs):
                kwargs = {'num_epochs': 1, 'shuffle_row_groups': False,
                          'workers_count': WORKERS}
                if device_fields:
                    kwargs['device_decode_fields'] = device_fields
                reader = make_reader(img_url, **kwargs)
                loader = JaxDataLoader(reader, batch_size=IMG_BATCH,
                                       drop_last=True)
                start = time.perf_counter()
                rows = 0
                for batch in loader:
                    # synchronize like a train step would: the overlap number
                    # must measure hidden work, not unsynchronized dispatch
                    jax.block_until_ready(
                        jax.tree_util.tree_leaves(batch)[0])
                    rows += IMG_BATCH
                rates.append(rows / max(time.perf_counter() - start, 1e-9))
                stats = loader.stats.as_dict()
                snapshot = loader.telemetry_snapshot()
                reader.stop()
                reader.join()
                if deadline_exceeded(section_start, len(rates), dd_epochs,
                                     'device_decode/' + label):
                    break
            return sorted(rates)[len(rates) // 2], stats, snapshot

        host_rate, host_stats, _ = run_epochs(None, 'host')
        raw_rate, raw_stats, raw_snapshot = run_epochs(['image'], 'ship_raw')
        hist = raw_snapshot.get('histograms', {})
        cpu_fallback = jax.devices()[0].platform == 'cpu'
        overlap = 1.0 - raw_stats.get('input_stall_fraction', 0.0)
        log('device_decode: host {:.1f} rows/s vs ship-raw {:.1f} rows/s '
            '({} device-decoded / {} fallback batches, '
            'overlap {:.2f}){}'.format(
                host_rate, raw_rate, raw_stats.get('device_decode_batches'),
                raw_stats.get('device_fallback_batches'), overlap,
                ' [CPU FALLBACK]' if cpu_fallback else ''))
        results.update({
            'device_decode_rows_per_sec': round(raw_rate, 2),
            'device_decode_host_rows_per_sec': round(host_rate, 2),
            'device_decode_speedup': round(raw_rate / max(host_rate, 1e-9), 3),
            'device_decode_h2d_overlap_fraction': round(overlap, 4),
            'device_decode_batches':
                int(raw_stats.get('device_decode_batches', 0)),
            'device_decode_fallback_batches':
                int(raw_stats.get('device_fallback_batches', 0)),
            'device_decode_stage_present': 'device_decode' in hist,
            'device_decode_epochs': dd_epochs,
            # honest provenance: on CPU the tail host-falls-back and the
            # speedup is a no-op check, not a decode-tail measurement
            'device_decode_cpu_fallback': cpu_fallback,
        })

    def run_pipecheck():
        """Check phase (host-only, sub-second): the pipecheck static
        data-plane invariant analysis + the mypy-strict ratchet over the
        installed package (docs/static-analysis.md). A non-clean result is
        recorded in the BENCH json — perf history that rides on code whose
        producer/consumer protocol has drifted is not trustworthy perf
        history."""
        import time as _time
        from petastorm_tpu.analysis import run_pipecheck as pipecheck
        started = _time.perf_counter()
        report = pipecheck()
        elapsed_s = _time.perf_counter() - started
        by_rule = report.by_rule()
        log('pipecheck: {} — {} file(s), {} finding(s), {} suppressed, '
            '{} call-graph function(s), {:.2f}s{}'
            .format('clean' if report.clean else 'FINDINGS', report.files,
                    len(report.findings), report.suppressed,
                    report.callgraph_functions, elapsed_s,
                    '' if report.clean else '; first: ' +
                    report.findings[0].format()))
        results.update({
            'pipecheck_clean': report.clean,
            'pipecheck_findings': len(report.findings),
            'pipecheck_suppressed': report.suppressed,
            'pipecheck_files': report.files,
            'pipecheck_callgraph_functions': report.callgraph_functions,
            'pipecheck_wall_s': round(elapsed_s, 3),
            # the whole-program pass must stay CI-cheap: the interprocedural
            # engine is summaries + memoized closures, not path exploration
            'pipecheck_under_30s': elapsed_s <= 30.0,
            'pipecheck_mypy_ratchet_findings':
                by_rule.get('mypy-ratchet', 0),
        })
        # per-rule finding counts for the interprocedural families so a
        # regression names its rule straight from the BENCH json
        for rule in ('resource-lifecycle', 'determinism',
                     'journal-discipline', 'lock-discipline',
                     'exception-hygiene'):
            results['pipecheck_' + rule.replace('-', '_') +
                    '_findings'] = by_rule.get(rule, 0)

    def run_decode_bench():
        """Vectorized decode-engine microbench (host-only, fast): per-codec
        decoded rows/s + MB/s through the compiled DecodePlan vs the per-cell
        fallback path, plus the predicate pushdown ratio — the ISSUE-7
        acceptance numbers (compressed_ndarray/image speedups; image kernels
        scale with decode_threads — docs/performance.md "Vectorized decode
        engine")."""
        from petastorm_tpu.benchmark.decode_bench import \
            run_decode_bench as decode_bench
        fields = decode_bench(
            rows=int(os.environ.get('BENCH_DECODE_ROWS', 2000)),
            image_rows=int(os.environ.get('BENCH_DECODE_IMAGE_ROWS', 512)))
        # decode_threads already carries the section prefix — don't double it
        results.update({key if key.startswith('decode_') else 'decode_' + key:
                        value for key, value in fields.items()})

    def run_decode():
        decode_host, decode_onchip = run_decode_delta()
        results.update({
            'imagenet_host_decode_rows_per_sec': round(decode_host, 2),
            'imagenet_onchip_decode_rows_per_sec': round(decode_onchip, 2),
            'onchip_decode_speedup':
                round(decode_onchip / max(decode_host, 1e-9), 3),
        })

    section_fns = {
        'mnist_stream': run_mnist_stream,
        'mnist_scan_stream': run_scan_stream,
        'bare_reader': run_bare_reader,
        'mnist_inmem': run_mnist_inmem,
        'imagenet_stream': run_imagenet_stream,
        'imagenet_scan': run_imagenet_scan,
        'decode_delta': run_decode,
        'flash': run_flash,
        'moe': run_moe,
        'wire_bench': run_wire_bench,
        'decode_bench': run_decode_bench,
        'telemetry': run_telemetry,
        'tracing': run_tracing,
        'resilience': run_resilience,
        'pipecheck': run_pipecheck,
        'service': run_service,
        'autotune': run_autotune,
        'device_decode': run_device_decode,
        'observability': run_observability,
        'schedule': run_schedule,
        'storage': run_storage,
        'lineage': run_lineage,
        'incidents': run_incidents,
        'history': run_history,
        'topology': run_topology,
        'chaos': run_chaos,
    }
    for name in SECTION_RUN_ORDER:
        run_section(name, section_fns[name])

    print(json.dumps(normalize_headline(results)), flush=True)
    failed = sorted(key[:-len('_error')] for key in results if key.endswith('_error'))
    if failed:
        raise SystemExit('bench.py: sections failed: {}'.format(', '.join(failed)))


def main():
    validate_bench_sections()  # fail fast on typos before any measure work
    run_bench()


if __name__ == '__main__':
    main()
