"""Reader factories and the Reader runtime (reference: petastorm/reader.py).

``make_reader`` reads petastorm_tpu (or petastorm) datasets row-at-a-time with codec
decode; ``make_batch_reader`` reads any Parquet store columnar-batch-at-a-time. Both drive
the same columnar worker (petastorm_tpu/reader_worker.py) over a ventilated rowgroup
schedule with bounded in-flight work.
"""

import logging
import threading
import warnings

import numpy as np

from petastorm_tpu.cache import ArrowIpcDiskCache, LocalDiskCache, NullCache
from petastorm_tpu.errors import MetadataError, NoDataAvailableError
from petastorm_tpu.etl import dataset_metadata
from petastorm_tpu.fs_utils import (as_arrow_filesystem, check_hdfs_driver,
                                    make_filesystem_factory,
                                    normalize_dataset_url_or_urls)
from petastorm_tpu.reader_worker import ColumnarBatch, RowGroupWorker, WorkerSetup
from petastorm_tpu.telemetry.tracing import (merge_trace_events,
                                             set_trace_enabled, trace_enabled,
                                             trace_instant)
from petastorm_tpu.unischema import Unischema
from petastorm_tpu.workers import EmptyResultError
from petastorm_tpu.workers.dummy_pool import DummyPool
from petastorm_tpu.workers.thread_pool import ThreadPool
from petastorm_tpu.workers.ventilator import ConcurrentVentilator

logger = logging.getLogger(__name__)

#: extra rowgroups kept in flight beyond the worker count (reference: reader.py:45-47)
_VENTILATE_EXTRA_ROWGROUPS = 2

#: pool-shape defaults shared by the make_reader signature and the reader_pool
#: conflict warning — one source of truth so they cannot drift apart
_DEFAULT_POOL_TYPE = 'thread'
_DEFAULT_WORKERS_COUNT = 10
_DEFAULT_RESULTS_QUEUE_SIZE = 50


def _make_pool(reader_pool_type, workers_count, results_queue_size,
               shm_transport=None, item_deadline_s=None, heartbeat_interval_s=None):
    if reader_pool_type == 'thread':
        return ThreadPool(workers_count, results_queue_size)
    if reader_pool_type == 'process':
        from petastorm_tpu.workers.process_pool import ProcessPool
        kwargs = {}
        if heartbeat_interval_s is not None:
            kwargs['heartbeat_interval_s'] = heartbeat_interval_s
        return ProcessPool(workers_count, results_queue_size,
                           shm_transport=shm_transport,
                           item_deadline_s=item_deadline_s, **kwargs)
    if reader_pool_type == 'dummy':
        return DummyPool()
    raise ValueError('Unknown reader_pool_type {!r} (expected thread/process/dummy)'
                     .format(reader_pool_type))


def _retrying(fn, retry_policy, counter=None):
    """Run a construction-time filesystem operation (dataset open, rowgroup
    enumeration) under the reader's retry policy; ``counter`` (a 1-element list)
    accumulates retries so they surface in ``diagnostics['io_retries']`` like any
    worker-side retry."""
    if retry_policy is None:
        return fn()
    from petastorm_tpu.resilience import run_with_retry

    def on_retry(attempt, exc, delay):
        logger.warning('Transient IO failure opening dataset (attempt %d): %s; '
                       'retrying in %.3fs', attempt, exc, delay)
    result, retries = run_with_retry(fn, retry_policy, on_retry=on_retry)
    if counter is not None:
        counter[0] += retries
    return result


def _make_cache(cache_type, cache_location, cache_size_limit, cache_row_size_estimate,
                cache_extra_settings, cache_format='arrow-ipc', has_transform=False):
    if cache_type in (None, 'null'):
        return NullCache()
    if cache_type == 'local-disk':
        extra = dict(cache_extra_settings or {})
        if cache_format == 'arrow-ipc':
            cache_cls = ArrowIpcDiskCache
            # A transform_spec may mutate columns/rows in place; zero-copy mmap
            # hits are read-only and would crash it on the warm epoch only. Decode
            # hits writable in that case (one memcpy per column — still no Parquet
            # read/decode/unpickle); cache_extra_settings={'writable_hits': ...}
            # overrides either way.
            if has_transform:
                extra.setdefault('writable_hits', True)
        elif cache_format == 'pickle':
            cache_cls = LocalDiskCache
        else:
            raise ValueError('Unknown cache_format {!r} (expected arrow-ipc/pickle)'
                             .format(cache_format))
        cache = cache_cls(cache_location, cache_size_limit, cache_row_size_estimate or 0,
                          **extra)
        # An explicit writable_hits override is a statement about what the
        # consumer needs (e.g. in-place mutation of hit columns with no
        # transform_spec) — pin it so the autotuner never treats the hit mode
        # as a free knob (docs/autotuning.md).
        if 'writable_hits' in (cache_extra_settings or {}):
            cache.writable_hits_pinned = True
        return cache
    raise ValueError('Unknown cache_type {!r} (expected null/local-disk)'.format(cache_type))


def make_reader(dataset_url_or_urls, schema_fields=None,
                reader_pool_type=_DEFAULT_POOL_TYPE,
                workers_count=_DEFAULT_WORKERS_COUNT,
                results_queue_size=_DEFAULT_RESULTS_QUEUE_SIZE, seed=None, shuffle_rows=False,
                shuffle_row_groups=True, shuffle_row_drop_partitions=1, predicate=None,
                rowgroup_selector=None, num_epochs=1, cur_shard=None, shard_count=None,
                shard_seed=None, cache_type='null', cache_location=None,
                cache_size_limit=None, cache_row_size_estimate=None,
                cache_extra_settings=None, cache_format='arrow-ipc',
                transform_spec=None, storage_options=None,
                filesystem=None, resume_state=None, reader_pool=None,
                field_overrides=None, hdfs_driver='libhdfs', on_error='raise',
                retry_policy=None, shm_transport=None, item_deadline_s=None,
                heartbeat_interval_s=None, trace=None, service_url=None,
                autotune=None, device_decode_fields=None, metrics_port=None,
                slo_policy=None, cost_schedule=None, lineage=None,
                incidents=None, storage_policy=None, history=None,
                topology=None):
    """Reader for datasets written with a Unischema (petastorm_tpu or petastorm stores):
    rows decoded through codecs, emitted one namedtuple per ``next()`` (reference:
    petastorm/reader.py:62-204). ``schema_fields`` may be a list of field names / regexes,
    or an :class:`~petastorm_tpu.ngram.NGram` for sequence windows. ``reader_pool``
    overrides ``reader_pool_type`` with a pre-built pool instance (e.g. a ThreadPool with
    profiling_enabled). ``field_overrides`` — list of :class:`UnischemaField`s replacing
    same-named stored fields for THIS read (read-time reinterpretation: e.g. swap a
    ``DctImageCodec`` field to ``DctCoefficientsCodec`` so raw coefficients flow to an
    on-device decode). ``hdfs_driver`` — petastorm API compatibility (reference:
    reader.py:126-127); pyarrow.fs provides libhdfs only, 'libhdfs3' warns.

    Resilience (docs/robustness.md): ``on_error`` is the per-rowgroup failure policy —
    ``'raise'`` (default; any failure aborts the read, today's exact behavior),
    ``'retry'`` (transient IO failures are retried per ``retry_policy``, then raised),
    ``'skip'`` (after retries, the failing rowgroup is excluded and recorded in the
    quarantine ledger, visible via ``Reader.diagnostics['quarantine']``). ``retry_policy``
    is a :class:`~petastorm_tpu.resilience.RetryPolicy` (default: 3 attempts,
    exponential backoff with seeded jitter).

    Zero-copy data plane (docs/performance.md): ``cache_format`` picks the
    ``cache_type='local-disk'`` value format — ``'arrow-ipc'`` (default; decoded
    rowgroups stored as Arrow IPC files, hits are memory-mapped READ-ONLY zero-copy
    views — with a ``transform_spec`` present, hits are decoded writable instead so
    in-place mutation keeps working; ``cache_extra_settings={'writable_hits': ...}``
    overrides) or ``'pickle'`` (the reference's format; every hit pays a full
    unpickle and returns writable arrays).
    ``shm_transport`` controls the process pool's shared-memory result transport —
    None (auto-on when available), True (require), False (ZMQ frames only); ignored
    by thread/dummy pools, which never cross a process boundary.

    Hang watchdog (docs/robustness.md "Hang detection & circuit breakers";
    process pool only): ``item_deadline_s`` — a worker holding one rowgroup
    longer than this without a result is reaped and respawned; under
    ``on_error='skip'`` the offending rowgroup is quarantined with
    ``reason='hang'`` instead of re-dispatched (None, the default, disables the
    per-item deadline). ``heartbeat_interval_s`` — cadence of the workers'
    liveness stamps (default 0.5s; a worker whose stamp stalls while it holds
    work is reaped even without an item deadline; 0 disables stamping).

    Flight recorder (docs/observability.md "Flight recorder"): ``trace``
    arms/disarms the per-process trace ring buffer — True/False call
    :func:`~petastorm_tpu.telemetry.tracing.set_trace_enabled` (process-global,
    like the telemetry switch; workers spawned by this reader's pool inherit
    it), None (default) leaves the ``PETASTORM_TPU_TRACE`` env setting in
    place. Export the capture with ``Reader.dump_trace()``.

    Disaggregated input service (docs/service.md): ``service_url``
    (``'tcp://host:port'``) points this reader at a shared preprocessing
    fleet instead of building an in-process pool — decode runs on the
    service's workers, results arrive over TCP (shm fast path when
    co-located), and ``on_error`` modes, the quarantine ledger, telemetry
    and tracing work unchanged. Pool-shape arguments are ignored (the fleet
    defines its own shape); ``None`` (default) keeps today's in-process
    behavior byte-identical.

    Closed-loop autotuning (docs/autotuning.md): ``autotune=True`` (or an
    :class:`~petastorm_tpu.autotune.AutotunePolicy`) starts a controller
    thread that samples this reader's telemetry mid-epoch, attributes the
    bottleneck stage, and hill-climbs one knob at a time (ventilation depth,
    pool workers, decode threads, cache mode — propose, hold, measure rows/s,
    commit or revert) with the circuit-breaker board as a safety interlock.
    Inspect with :meth:`Reader.autotune_report` / ``diagnostics['autotune']``;
    every decision is also an ``autotune_decision`` JSONL/trace event. Off by
    default — with ``autotune`` unset no controller exists and no knob is
    ever touched.

    Device-resident decode tail (docs/performance.md): ``device_decode_fields``
    names codec fields whose payloads SKIP host decode — workers pass the
    compressed/packed bytes through (DCT coefficient blocks for
    ``DctImageCodec``, raw ``.npy`` bytes for ``NdarrayCodec``, raw deflate
    frames for ``CompressedNdarrayCodec``) and the
    :class:`~petastorm_tpu.parallel.loader.JaxDataLoader` decodes them as
    jitted device kernels after the upload, double-buffered against
    the train step. Raw-form values reach non-loader consumers as-is; the
    small ``__hw``/``__enc`` auxiliary metadata columns ride
    ``iter_columnar`` batches only (the namedtuple row/batch APIs emit schema
    fields and drop them). On a CPU backend the loader falls back to host
    decode byte-identically. Unset (default) keeps every
    path byte-identical to a reader without the knob. Mutually exclusive with
    ``transform_spec`` (host transforms need decoded values — use the loader's
    ``device_transforms`` instead) and NGram readers.

    Live metrics plane (docs/observability.md "Live metrics plane"):
    ``metrics_port`` attaches a scrape endpoint to this reader — ``/metrics``
    (Prometheus text over :meth:`Reader.telemetry_snapshot`, SLO gauges
    refreshed per scrape), ``/healthz``, ``/vars``; ``0`` binds an ephemeral
    port (``Reader.metrics_url`` names it), None (default) serves nothing.
    ``slo_policy`` sets the input-efficiency SLO
    (:class:`~petastorm_tpu.telemetry.slo.SloPolicy`, a float target, or
    None = the default 0.9 target) evaluated by
    :meth:`Reader.efficiency_report` / ``diagnostics['slo']``.

    Cost-aware scheduling (docs/performance.md "Cost-aware scheduling"):
    ``cost_schedule`` consumes the persisted per-rowgroup cost ledger
    (``petastorm-tpu-throughput costs``) to interleave heavy and light
    rowgroups deterministically (same seed + same ledger => same order on
    every pool), split oversized rowgroups into sub-range work items, and
    pre-stage predicted-slow items — ``True`` (default policy), a
    :class:`~petastorm_tpu.schedule.SchedulePolicy`, or a ledger path
    string. With no persisted ledger the read is byte-identical to an
    unscheduled reader (cold start) while live cost observations accumulate
    and persist at ``stop()`` for the next run. Unset (None, the default)
    builds no scheduler and keeps every path byte-identical. Not compatible
    with ``resume_state`` (a re-planned schedule would shift the
    checkpoint's item coordinates).

    Sample-lineage audit (docs/observability.md "Sample lineage &
    determinism audit"): ``lineage`` arms the
    :class:`~petastorm_tpu.telemetry.lineage.LineageRecorder` — a chained
    order digest over every delivered item's ``(epoch, fragment, rowgroup,
    row_range, drop, rows)`` identity (:meth:`Reader.order_digest`;
    identical across dummy/thread/process/service pools for the same seed,
    invariant under worker respawns), optional sampled content fingerprints,
    and a bounded batch-manifest JSONL next to the dataset that
    ``petastorm-tpu-throughput lineage verify`` replays without reading
    data. ``True`` (default policy), a manifest path string, or a
    :class:`~petastorm_tpu.telemetry.lineage.LineagePolicy`; digest state
    rides ``state_dict()`` so save/resume folds to the same digest. Unset
    (None, the default) records nothing.

    Incident autopsy plane (docs/observability.md "Incident autopsy
    plane"): ``incidents`` arms an edge-triggered black-box recorder
    (:class:`~petastorm_tpu.telemetry.incident.IncidentRecorder`) — when a
    failure edge fires (breaker trip, hang-watchdog reap, quarantine, shm
    CRC drop, SLO breach, lineage divergence) the recorder atomically writes
    a bundle directory holding the drained trace ring, the full telemetry
    snapshot, breaker/quarantine/cost/lineage state and config provenance,
    rate-limited per trigger kind and retention-bounded. Inspect with
    ``petastorm-tpu-throughput autopsy <bundle>`` (ranked probable-cause
    report) and :meth:`Reader.incident_report` / ``diagnostics
    ['incidents']``. ``True`` (default policy), or an
    :class:`~petastorm_tpu.telemetry.incident.IncidentPolicy`. Unset (None,
    the default) builds no recorder and keeps every path byte-identical.

    Object-store ingest engine (docs/performance.md "Object-store ingest
    engine"): ``storage_policy`` arms planned byte-range I/O in the workers
    — column-chunk ranges planned from a cached Parquet footer, coalesced
    into merged GETs, fetched by a parallel bounded-window pool with
    tail-latency request hedging. ``None`` (default) auto-engages only for
    non-local URL schemes (s3/gs/abfs/...) and keeps local/HDFS reads
    byte-identical to the seed path; ``False`` never engages; ``True`` or a
    :class:`~petastorm_tpu.storage.StoragePolicy` always does. Counters and
    ``range_fetch``/``range_hedge`` stage timings land in
    :meth:`Reader.telemetry_snapshot`; per-rowgroup fetch costs flow into
    the cost ledger so ``cost_schedule`` prices network I/O too.

    Longitudinal observatory (docs/observability.md "Longitudinal
    observatory"): ``history`` arms the cross-run goodput historian — one
    structured run record (config/knob/storage/schedule fingerprints,
    rows/s, goodput efficiency, per-stage time shares, storage counters,
    incident/quarantine counts) is appended at ``stop()`` to an append-only
    CRC-framed store keyed by :attr:`Reader.dataset_token`, which
    ``petastorm-tpu-throughput history list|show|compare`` diffs against a
    robust trailing baseline with change-point attribution. Arming history
    also arms the live regression sentinel (an EWMA + Page–Hinkley drift
    test over the run's own rows/s and wait-share series) that fires a
    ``perf_regression`` incident on a mid-run goodput collapse. ``True``
    (default policy), a store path string, or a
    :class:`~petastorm_tpu.telemetry.history.HistoryPolicy` (its
    ``sentinel`` field tunes/disables the sentinel). Unset (None, the
    default) records nothing and keeps every path byte-identical.

    Elastic pod-scale sharding (docs/robustness.md "Elastic pod-scale
    sharding"): ``topology`` replaces static ``cur_shard``/``shard_count``
    with a shard map negotiated from the process topology
    (``jax.process_index()``/``process_count()``, env-overridable with
    ``PETASTORM_TPU_PROCESS_INDEX/_COUNT``) and recorded in a durable
    CRC-framed membership journal on shared storage; on a host
    join/leave/lease expiry the survivors re-deal ONLY the undelivered
    rowgroups, and per-host lineage digests compose into a
    topology-invariant global digest
    (:func:`~petastorm_tpu.parallel.topology.compose_global_digest`).
    ``True`` (default policy), a journal path string, or a
    :class:`~petastorm_tpu.parallel.topology.TopologyPolicy`. Mutually
    exclusive with ``cur_shard``/``shard_count``/``shard_seed`` and
    ``cost_schedule``. Unset (None, the default) keeps the static-shard
    path byte-identical."""
    from petastorm_tpu.resilience import resolve_retry_policy
    if trace is not None:
        set_trace_enabled(bool(trace))
    check_hdfs_driver(hdfs_driver)
    retry_policy = resolve_retry_policy(on_error, retry_policy)
    construction_retries = [0]
    dataset_url_or_urls = normalize_dataset_url_or_urls(dataset_url_or_urls)
    handle = _retrying(
        lambda: dataset_metadata.open_dataset(dataset_url_or_urls,
                                              storage_options=storage_options,
                                              filesystem=filesystem),
        retry_policy, construction_retries)
    try:
        schema = dataset_metadata.get_schema(handle)
    except MetadataError:
        raise RuntimeError(
            'Dataset at {!r} has no Unischema metadata. Use make_batch_reader for plain '
            'Parquet stores.'.format(dataset_url_or_urls))
    if field_overrides:
        schema = _apply_field_overrides(schema, field_overrides)
    cache = _make_cache(cache_type, cache_location, cache_size_limit,
                        cache_row_size_estimate, cache_extra_settings, cache_format,
                        has_transform=transform_spec is not None)
    if service_url is not None:
        if reader_pool is not None:
            raise ValueError('service_url and reader_pool are mutually '
                             'exclusive — the service defines the pool')
        from petastorm_tpu.service.service_client import ServicePool
        reader_pool = ServicePool(service_url)
    if reader_pool is not None:
        # Pool-shape kwargs describe a pool this call is NOT building (ADVICE.md r1).
        ignored = [name for name, value, default in [
            ('workers_count', workers_count, _DEFAULT_WORKERS_COUNT),
            ('results_queue_size', results_queue_size, _DEFAULT_RESULTS_QUEUE_SIZE),
            ('reader_pool_type', reader_pool_type, _DEFAULT_POOL_TYPE),
            ('shm_transport', shm_transport, None),
            ('item_deadline_s', item_deadline_s, None),
            ('heartbeat_interval_s', heartbeat_interval_s, None)]
            if value != default]
        if ignored:
            warnings.warn('{} was supplied; ignoring pool-shape arguments {} '
                          '(the {} defines its own shape)'.format(
                              'service_url' if service_url is not None
                              else 'reader_pool', ignored,
                              'service fleet' if service_url is not None
                              else 'pre-built pool'))
    pool = reader_pool if reader_pool is not None else _make_pool(
        reader_pool_type, workers_count, results_queue_size, shm_transport,
        item_deadline_s, heartbeat_interval_s)
    return Reader(dataset_url_or_urls, handle=handle, schema=schema,
                  schema_fields=schema_fields,
                  reader_pool=pool, seed=seed, shuffle_rows=shuffle_rows,
                  shuffle_row_groups=shuffle_row_groups,
                  shuffle_row_drop_partitions=shuffle_row_drop_partitions,
                  predicate=predicate, rowgroup_selector=rowgroup_selector,
                  num_epochs=num_epochs, cur_shard=cur_shard, shard_count=shard_count,
                  shard_seed=shard_seed, cache=cache, transform_spec=transform_spec,
                  is_batched_reader=False, decode=True,
                  storage_options=storage_options, filesystem=filesystem,
                  resume_state=resume_state, on_error=on_error,
                  retry_policy=retry_policy,
                  initial_io_retries=construction_retries[0],
                  autotune=autotune, device_decode_fields=device_decode_fields,
                  metrics_port=metrics_port, slo_policy=slo_policy,
                  cost_schedule=cost_schedule, lineage=lineage,
                  incidents=incidents, storage_policy=storage_policy,
                  history=history, topology=topology)


def make_batch_reader(dataset_url_or_urls, schema_fields=None, reader_pool_type='thread',
                      workers_count=10, results_queue_size=50, seed=None,
                      shuffle_rows=False, shuffle_row_groups=True,
                      shuffle_row_drop_partitions=1, predicate=None, num_epochs=1,
                      cur_shard=None, shard_count=None, shard_seed=None, cache_type='null',
                      cache_location=None, cache_size_limit=None,
                      cache_row_size_estimate=None, cache_extra_settings=None,
                      cache_format='arrow-ipc', transform_spec=None,
                      storage_options=None, filesystem=None,
                      resume_state=None, hdfs_driver='libhdfs', on_error='raise',
                      retry_policy=None, shm_transport=None, item_deadline_s=None,
                      heartbeat_interval_s=None, trace=None, service_url=None,
                      autotune=None, device_decode_fields=None,
                      metrics_port=None, slo_policy=None, cost_schedule=None,
                      lineage=None, incidents=None, storage_policy=None,
                      history=None, topology=None):
    """Reader for arbitrary Parquet stores: native columns only (no codec decode), one
    namedtuple of column arrays per rowgroup batch (reference: petastorm/reader.py:207-346).
    ``on_error`` / ``retry_policy`` / ``cache_format`` / ``shm_transport`` /
    ``item_deadline_s`` / ``heartbeat_interval_s`` / ``trace`` /
    ``service_url`` / ``autotune`` / ``metrics_port`` / ``slo_policy`` /
    ``cost_schedule`` / ``lineage`` / ``incidents`` / ``storage_policy`` /
    ``history`` / ``topology``
    behave exactly as in
    :func:`make_reader`.
    ``device_decode_fields`` (docs/performance.md "Device-resident decode
    tail") requires the store's Unischema codec registry: on a Unischema
    store the named fields ship their raw codec payloads (container stripped)
    instead of the stored blob values; on a plain Parquet store it raises —
    there is no codec to interpret the bytes with (use :func:`make_reader`
    for the full decode tail).
    """
    from petastorm_tpu.resilience import resolve_retry_policy
    if trace is not None:
        set_trace_enabled(bool(trace))
    check_hdfs_driver(hdfs_driver)
    retry_policy = resolve_retry_policy(on_error, retry_policy)
    construction_retries = [0]
    dataset_url_or_urls = normalize_dataset_url_or_urls(dataset_url_or_urls)
    handle = _retrying(
        lambda: dataset_metadata.open_dataset(dataset_url_or_urls,
                                              storage_options=storage_options,
                                              filesystem=filesystem),
        retry_policy, construction_retries)
    stored_schema = None
    try:
        stored_schema = dataset_metadata.get_schema(handle)
        warnings.warn('This store was written with a Unischema; use make_reader to get '
                      'codec-decoded rows. make_batch_reader will emit raw stored values.')
    except MetadataError:
        pass
    if device_decode_fields:
        # the batch reader has no codec registry of its own: ship-raw kernels
        # need the store's Unischema to know each field's payload form
        if stored_schema is None:
            raise ValueError(
                'device_decode_fields requires a Unischema store (the codec '
                'registry tells the ship-raw kernels what the payload bytes '
                'are); this store has none — use make_reader on a Unischema '
                'store instead')
        batch_schema = stored_schema
    else:
        batch_schema = None
    cache = _make_cache(cache_type, cache_location, cache_size_limit,
                        cache_row_size_estimate, cache_extra_settings, cache_format,
                        has_transform=transform_spec is not None)
    if service_url is not None:
        # Pool-shape kwargs describe a pool this call is NOT building — the
        # service fleet defines its own shape (same contract as make_reader's
        # reader_pool warning).
        ignored = [name for name, value, default in [
            ('workers_count', workers_count, _DEFAULT_WORKERS_COUNT),
            ('results_queue_size', results_queue_size, _DEFAULT_RESULTS_QUEUE_SIZE),
            ('reader_pool_type', reader_pool_type, _DEFAULT_POOL_TYPE),
            ('shm_transport', shm_transport, None),
            ('item_deadline_s', item_deadline_s, None),
            ('heartbeat_interval_s', heartbeat_interval_s, None)]
            if value != default]
        if ignored:
            warnings.warn('service_url was supplied; ignoring pool-shape '
                          'arguments {} (the service fleet defines its own '
                          'shape)'.format(ignored))
        from petastorm_tpu.service.service_client import ServicePool
        pool = ServicePool(service_url)
    else:
        pool = _make_pool(reader_pool_type, workers_count, results_queue_size,
                          shm_transport, item_deadline_s, heartbeat_interval_s)
    return Reader(dataset_url_or_urls, handle=handle, schema=batch_schema,
                  schema_fields=schema_fields,
                  reader_pool=pool, seed=seed, shuffle_rows=shuffle_rows,
                  shuffle_row_groups=shuffle_row_groups,
                  shuffle_row_drop_partitions=shuffle_row_drop_partitions,
                  predicate=predicate, rowgroup_selector=None, num_epochs=num_epochs,
                  cur_shard=cur_shard, shard_count=shard_count, shard_seed=shard_seed,
                  cache=cache, transform_spec=transform_spec, is_batched_reader=True,
                  decode=False, storage_options=storage_options, filesystem=filesystem,
                  resume_state=resume_state, on_error=on_error,
                  retry_policy=retry_policy,
                  initial_io_retries=construction_retries[0],
                  autotune=autotune, device_decode_fields=device_decode_fields,
                  metrics_port=metrics_port, slo_policy=slo_policy,
                  cost_schedule=cost_schedule, lineage=lineage,
                  incidents=incidents, storage_policy=storage_policy,
                  history=history, topology=topology)


class Reader(object):
    """The reader runtime: schedules rowgroups through a worker pool and iterates results
    (reference: petastorm/reader.py:349-710)."""

    def __init__(self, dataset_url_or_urls, handle=None, schema=None, schema_fields=None,
                 reader_pool=None, seed=None, shuffle_rows=False, shuffle_row_groups=True,
                 shuffle_row_drop_partitions=1, predicate=None, rowgroup_selector=None,
                 num_epochs=1, cur_shard=None, shard_count=None, shard_seed=None,
                 cache=None, transform_spec=None, is_batched_reader=False, decode=True,
                 storage_options=None, filesystem=None, resume_state=None,
                 on_error='raise', retry_policy=None, initial_io_retries=0,
                 autotune=None, device_decode_fields=None, metrics_port=None,
                 slo_policy=None, cost_schedule=None, lineage=None,
                 incidents=None, storage_policy=None, history=None,
                 topology=None):
        from petastorm_tpu.resilience import QuarantineLedger, resolve_retry_policy
        retry_policy = resolve_retry_policy(on_error, retry_policy)
        construction_retries = [initial_io_retries]
        construction_policy = retry_policy
        self.num_epochs = num_epochs
        self.is_batched_reader = is_batched_reader
        self.last_row_consumed = False
        self._stopped = False
        self.on_error = on_error
        #: skip-with-quarantine ledger — records arrive on the results channel attached
        #: to the empty stand-in batches of skipped rowgroups (docs/robustness.md)
        self.quarantine = QuarantineLedger()
        self._io_retries = 0
        # Circuit-breaker observability: worker-process breaker states arrive on
        # each batch's 'breakers' sidecar (last writer wins per breaker name) and
        # merge with this process's board in diagnostics['breakers'].
        self._breaker_states = {}
        # Cache observability: per-batch cache_hit sidecar flags accumulate here
        # (works across all pools — the flag rides the results channel).
        self._cache = cache
        self._cache_hits = 0
        self._cache_misses = 0
        # Autotune goodput signal (docs/autotuning.md): rows delivered off the
        # results channel — the controller's per-window rows/s numerator.
        self._rows_consumed = 0
        self._transform_spec = transform_spec
        self._autotune = None
        # Pipeline telemetry (docs/observability.md): worker-process stage times
        # arrive on each batch's telemetry sidecar and merge here; pool-level
        # registries merge at snapshot time, so telemetry_snapshot() covers every
        # process that touched this reader's rows.
        from petastorm_tpu.telemetry import MetricsRegistry
        self._telemetry = MetricsRegistry()
        # Input-efficiency SLO (docs/observability.md "Efficiency SLOs"):
        # windows are measured from construction on the span clock; breach
        # events are edge-triggered inside the tracker, so polling
        # diagnostics cannot inflate the count.
        from petastorm_tpu.telemetry.export import logger_from_env
        from petastorm_tpu.telemetry.slo import (SloTracker,
                                                 resolve_slo_policy, slo_clock)
        self._started_at = slo_clock()
        self._slo = SloTracker(resolve_slo_policy(slo_policy),
                               jsonl=logger_from_env())
        self._metrics_server = None
        # Sample-lineage audit plane (docs/observability.md): the policy is
        # resolved up front (its fingerprint sampling knob ships to workers
        # in the WorkerSetup); the recorder itself is built after the work
        # plan is frozen, so its manifest header can record the exact
        # reproduction config.
        from petastorm_tpu.telemetry.lineage import resolve_lineage_policy
        self._lineage = None
        self._lineage_policy = resolve_lineage_policy(lineage)
        # Incident autopsy plane (docs/observability.md "Incident autopsy
        # plane"): policy resolved up front, the recorder itself is built
        # after the pool starts — its evidence sources (cost/lineage/
        # autotune) must exist before the first edge can fire.
        from petastorm_tpu.telemetry.incident import resolve_incident_policy
        self._incidents = None
        self._incident_policy = resolve_incident_policy(incidents)
        # Longitudinal observatory (docs/observability.md "Longitudinal
        # observatory"): policy resolved up front; the historian + sentinel
        # are built after the incident plane so the sentinel can file its
        # perf_regression bundles there. Unset => nothing is built.
        from petastorm_tpu.telemetry.history import resolve_history_policy
        self._history = None
        self._history_policy = resolve_history_policy(history)
        self._history_written = False
        self._history_fingerprints = {}
        self._sentinel = None
        # edge-detection state for the poll-based triggers (all consumed
        # under _accounting_lock in _note_item_consumed)
        self._incident_last_divergence = 0
        self._incident_last_crc_failures = 0
        # Elastic pod-scale sharding (docs/robustness.md "Elastic pod-scale
        # sharding"): policy resolved up front; the HostTopology itself is
        # built once the filtered rowgroup list exists, so the negotiated
        # deal covers exactly what this read will ventilate. Unset => no
        # journal, no negotiation — the static path stays byte-identical.
        from petastorm_tpu.parallel.topology import resolve_topology_policy
        self._topology = None
        self._topology_policy = resolve_topology_policy(topology)
        self._shard_skew = None

        if (cur_shard is None) != (shard_count is None):
            raise ValueError('cur_shard and shard_count must be specified together')
        if cur_shard is not None and not 0 <= cur_shard < shard_count:
            raise ValueError('cur_shard must be in [0, shard_count)')
        if self._topology_policy is not None:
            if cur_shard is not None or shard_seed is not None:
                raise ValueError(
                    'topology= and static cur_shard/shard_count/shard_seed '
                    'are mutually exclusive — the topology plane negotiates '
                    'the shard map (docs/robustness.md "Elastic pod-scale '
                    'sharding")')
            if cost_schedule is not None:
                raise ValueError(
                    'topology= is not compatible with cost_schedule — a '
                    're-planned interleave would shift the global item '
                    'coordinates a reshard re-deals')
        if predicate is not None and schema_fields is not None and _is_ngram(schema_fields):
            raise ValueError('Predicates are not supported together with NGram '
                             '(reference semantics: reader.py:430-434)')

        if handle is None:
            handle = _retrying(
                lambda: dataset_metadata.open_dataset(dataset_url_or_urls,
                                                      storage_options=storage_options,
                                                      filesystem=filesystem),
                construction_policy, construction_retries)
        self._handle = handle
        if schema is None:
            schema = Unischema.from_arrow_schema(handle.arrow_dataset.schema)
        self.schema = schema

        ngram = None
        if schema_fields is not None and _is_ngram(schema_fields):
            ngram = schema_fields
            if is_batched_reader:
                raise ValueError('NGram is not supported by make_batch_reader '
                                 '(reference semantics: arrow_reader_worker.py:107-108)')
            ngram.resolve_regex_field_names(schema)
            if not ngram.timestamp_overlap and shuffle_row_drop_partitions > 1:
                raise NotImplementedError('timestamp_overlap=False is not supported with '
                                          'shuffle_row_drop_partitions > 1 (reference: '
                                          'reader.py:436-438)')
            fields_to_read = list(ngram.get_field_names_at_all_timesteps())
        elif schema_fields is not None:
            view = schema.create_schema_view(schema_fields)
            fields_to_read = list(view.fields)
        else:
            fields_to_read = list(schema.fields)
        self.ngram = ngram

        # Predicate fields must be loaded even if not in the requested view.
        partition_names = set(handle.partition_field_names)
        worker_predicate = predicate
        main_process_predicate = None
        if predicate is not None:
            predicate_fields = set(predicate.get_fields())
            if predicate_fields and predicate_fields <= partition_names:
                # Pure partition-key predicate: prune rowgroups up front, no worker work
                # (reference: reader.py:617-641).
                main_process_predicate = predicate
                worker_predicate = None
            else:
                missing = [f for f in predicate_fields if f not in fields_to_read]
                fields_to_read += [f for f in missing if f in schema.fields
                                   or f in partition_names]

        # ------------------------------------------- device-resident decode tail
        # (docs/performance.md): validate the ship-raw field set up front so a
        # bad knob fails at construction with a precise message, not inside a
        # worker process mid-epoch.
        self.device_decode_fields = frozenset(device_decode_fields or ())
        if self.device_decode_fields:
            from petastorm_tpu import decode_engine
            if ngram is not None:
                raise ValueError('device_decode_fields is not supported with '
                                 'NGram readers (windows need decoded values)')
            if transform_spec is not None:
                raise ValueError(
                    'device_decode_fields and transform_spec are mutually '
                    'exclusive: host transforms need decoded values — declare '
                    'the augment chain as JaxDataLoader device_transforms '
                    'instead (docs/performance.md)')
            missing = sorted(f for f in self.device_decode_fields
                             if f not in fields_to_read)
            if missing:
                raise ValueError('device_decode_fields name fields not in this '
                                 'read: {}'.format(missing))
            in_partition = sorted(self.device_decode_fields & partition_names)
            if in_partition:
                raise ValueError('device_decode_fields cannot name partition '
                                 'keys: {}'.format(in_partition))
            for name in sorted(self.device_decode_fields):
                field = schema.fields.get(name)
                if field is None:
                    raise ValueError('device_decode_fields names field {!r} '
                                     'which has no schema entry'.format(name))
                decode_engine.validate_device_field(field)

        # Object-store ingest engine (docs/performance.md): resolve the
        # storage_policy kwarg ONCE against the dataset URL — None stays None
        # on local/HDFS schemes, so the seed path pays nothing, not even an
        # attribute lookup in the workers' hot loop.
        from petastorm_tpu.storage import resolve_storage_policy
        self._storage_policy = resolve_storage_policy(storage_policy,
                                                      dataset_url_or_urls)

        url_for_factory = dataset_url_or_urls if not isinstance(dataset_url_or_urls, list) \
            else dataset_url_or_urls[0]
        # Workers feed this filesystem into Arrow C++ — unwrap any HA failover proxy
        # (as_arrow_filesystem) when the caller supplied one explicitly. Under a
        # retrying on_error policy the factory itself retries filesystem RESOLUTION
        # (connection setup is as transient-failure-prone as reads).
        filesystem_factory = (make_filesystem_factory(url_for_factory, storage_options,
                                                      retry_policy=retry_policy)
                              if filesystem is None
                              else (lambda: as_arrow_filesystem(filesystem)))
        worker_setup = WorkerSetup(
            dataset_path_or_paths=handle.path_or_paths,
            filesystem_factory=filesystem_factory,
            schema=schema,
            fields_to_read=fields_to_read,
            transform_spec=transform_spec,
            batched_output=is_batched_reader,
            decode=decode,
            ngram=ngram,
            cache=cache,
            shuffle_rows=shuffle_rows,
            seed=seed,
            partition_field_names=partition_names,
            on_error=on_error,
            retry_policy=retry_policy,
            device_decode_fields=self.device_decode_fields,
            lineage_fingerprint_every=(self._lineage_policy.fingerprint_every
                                       if self._lineage_policy is not None
                                       else 0),
            storage_policy=self._storage_policy)
        # Single source of truth for the emitted schema: the workers' own derivation.
        self.result_schema = worker_setup.result_schema
        #: the dataset identity the disk cache and the cost ledger key on
        #: (docs/observability.md "Cost profiler")
        self.dataset_token = worker_setup.dataset_token

        # ------------------------------------------------ rowgroup schedule
        # Under 'skip', permanently unreadable footers (truncated part-files) are
        # excluded from the schedule and quarantined at enumeration time — workers
        # would only re-discover the same corruption per rowgroup. Records are staged
        # per attempt and committed once, so a transient mid-enumeration failure that
        # triggers a construction retry cannot double-record a corrupt fragment.
        # NOT with a rowgroup_selector: its selected indexes refer to the FULL
        # enumeration (see below), and dropping a fragment would silently shift every
        # later piece under the selection — a corrupt footer is loud in that combination.
        def enumerate_row_groups():
            staged = []
            on_fragment_error = None
            if on_error == 'skip' and rowgroup_selector is None:
                from petastorm_tpu.resilience import QuarantineRecord

                def on_fragment_error(exc, fragment_path, fragment_index):
                    staged.append(QuarantineRecord.from_exception(
                        exc, piece_index=fragment_index, fragment_path=fragment_path,
                        row_group_id=None, attempts=1, epoch=0))
            return dataset_metadata.load_row_groups(
                handle, on_fragment_error=on_fragment_error), staged

        row_groups, construction_quarantine = _retrying(
            enumerate_row_groups, construction_policy, construction_retries)
        if construction_quarantine and resume_state is not None:
            # Fragments dropped at enumeration shift the (piece, drop) coordinates the
            # checkpoint's consumed sets refer to; a shifted resume would silently
            # re-serve or lose the wrong rowgroups. items_per_epoch validation below
            # only catches COUNT changes — refuse explicitly.
            raise ValueError(
                'Cannot resume: {} fragment(s) became unreadable since the checkpoint '
                'was taken ({}); resume coordinates would not match the checkpoint'
                .format(len(construction_quarantine),
                        ', '.join(r.fragment_path for r in construction_quarantine)))
        for record in construction_quarantine:
            self.quarantine.add(record)
        self._io_retries = construction_retries[0]
        if rowgroup_selector is not None:
            # Selector piece indexes refer to the FULL load_row_groups enumeration (what
            # build_rowgroup_index scanned) — apply before any other filtering.
            from petastorm_tpu.etl.rowgroup_indexing import get_row_group_indexes
            indexes = get_row_group_indexes(handle)
            selected = rowgroup_selector.select_row_groups(indexes)
            row_groups = [rg for i, rg in enumerate(row_groups) if i in selected]
        if main_process_predicate is not None:
            row_groups = [rg for rg in row_groups
                          if _eval_partition_predicate(main_process_predicate, rg)]
        self._row_groups = row_groups

        if self._topology_policy is not None:
            # Negotiated sharding: the deal is computed over GLOBAL rowgroup
            # indices, journaled for the rest of the pod, and replaces the
            # static modulo split (generation-0 deals match it exactly).
            from petastorm_tpu.parallel.topology import (
                HostTopology, default_topology_journal_path)
            from petastorm_tpu.dataset_state import cache_state_home
            url_for_topology = dataset_url_or_urls if not isinstance(
                dataset_url_or_urls, list) else dataset_url_or_urls[0]
            journal_path = self._topology_policy.journal_path or \
                default_topology_journal_path(url_for_topology,
                                              cache_state_home(cache))
            if journal_path is None:
                raise ValueError(
                    'topology= needs a membership journal on shared storage, '
                    'but this dataset has no local state home (remote store, '
                    'no cache) — pass TopologyPolicy(journal_path=...)')
            self._topology = HostTopology(self._topology_policy, journal_path,
                                          len(row_groups),
                                          registry=self._telemetry)
            bad = [i for i in self._topology.assignment
                   if not 0 <= i < len(row_groups)]
            if bad:
                raise ValueError(
                    'topology assignment names global rowgroup indices {} '
                    'outside this dataset\'s {} filtered rowgroup(s) — the '
                    'policy was dealt against a different dataset or filter '
                    'config'.format(bad, len(row_groups)))
            effective_count = self._topology.process_count
            shard_row_groups = [row_groups[i]
                                for i in self._topology.assignment]
        else:
            effective_count = shard_count
            shard_row_groups = self._partition_row_groups(
                row_groups, cur_shard, shard_count, shard_seed)
        # Degenerate-sharding detector (docs/robustness.md): a shard count
        # above the filtered rowgroup count leaves >= 1 sibling empty — THIS
        # shard may look healthy while the pod's split is silently skewed.
        # Detected here on every shard so pods see it before training starts.
        if effective_count is not None and effective_count > len(row_groups):
            self._shard_skew = {
                'shard_count': effective_count,
                'rowgroups': len(row_groups),
                'empty_shards': effective_count - len(row_groups),
            }
            warnings.warn(
                'shard_skew: {} shard(s) over {} rowgroup(s) leaves {} '
                'shard(s) empty and the split skewed — use fewer shards or '
                'more files (diagnostics["shard_skew"])'.format(
                    effective_count, len(row_groups),
                    effective_count - len(row_groups)))
        if not shard_row_groups:
            raise NoDataAvailableError(
                'No rowgroups available for shard {} of {} (dataset has {} rowgroups '
                'after filtering). Use fewer shards or more files.'
                .format(self._topology.process_index
                        if self._topology is not None else cur_shard,
                        effective_count, len(row_groups)))
        self._shard_row_groups = shard_row_groups
        #: the frozen shard configuration a checkpoint must match on resume
        #: (satellite: silent wrong-stream replay on config drift)
        self._shard_config = {'cur_shard': cur_shard,
                              'shard_count': shard_count,
                              'shard_seed': shard_seed,
                              'topology': self._topology is not None}

        items = []
        for piece_index, rg in enumerate(shard_row_groups):
            for drop_part in range(shuffle_row_drop_partitions):
                items.append({
                    'piece_index': piece_index,
                    'fragment_path': rg.fragment_path,
                    'row_group_id': rg.row_group_id,
                    'partition_keys': rg.partition_keys,
                    'worker_predicate': worker_predicate,
                    'shuffle_row_drop_partition': (drop_part, shuffle_row_drop_partitions),
                })

        # -------------------------------------------- cost-aware scheduling
        # (docs/performance.md "Cost-aware scheduling"): load the persisted
        # per-rowgroup cost ledger, split oversized rowgroups into sub-range
        # work items, and pick the epoch ventilation order — all frozen here
        # (pure function of ledger + seed), so the order never depends on
        # runtime timing. Unset => nothing is built, every path byte-identical.
        #: piece index -> (fragment_path, row_group_id), incl. the virtual
        #: pieces of split rowgroups — what cost_ledger() attributes with
        self._piece_locator = {index: (rg.fragment_path, rg.row_group_id)
                               for index, rg in enumerate(shard_row_groups)}
        self._cost_scheduler = None
        order_fn = None
        from petastorm_tpu.schedule import resolve_schedule_policy
        schedule_policy = resolve_schedule_policy(cost_schedule)
        if schedule_policy is not None:
            if resume_state is not None:
                raise ValueError(
                    'cost_schedule cannot be combined with resume_state: a '
                    're-planned schedule (ledger-driven splits) would shift '
                    'the work-item coordinates the checkpoint refers to — '
                    'resume without cost_schedule')
            from petastorm_tpu.dataset_state import cache_state_home
            from petastorm_tpu.schedule import CostAwareScheduler, load_ledger
            url_for_ledger = dataset_url_or_urls if not isinstance(
                dataset_url_or_urls, list) else dataset_url_or_urls[0]
            ledger, ledger_path = load_ledger(
                url_for_ledger, self.dataset_token,
                cache_location=cache_state_home(cache),
                ledger_path=schedule_policy.ledger_path)
            self._cost_scheduler = CostAwareScheduler(
                self.dataset_token, schedule_policy, ledger=ledger,
                ledger_path=ledger_path)
            locator = {index: (rg.fragment_path, rg.row_group_id,
                               rg.row_group_num_rows)
                       for index, rg in enumerate(shard_row_groups)}
            # NGram windows span rows — interleave applies, splitting never.
            # Split parts cap at the pool's worker count (sub-ranges re-pay
            # the rowgroup read, so parts beyond the parallelism are
            # overhead), floored at 2: even a 1-worker pool benefits from a
            # p99 rowgroup publishing incrementally, and the floor keeps the
            # plan identical across equally-shaped pool/service topologies.
            items, _virtual = self._cost_scheduler.plan_items(
                items, locator, allow_split=ngram is None,
                max_parts=max(2, int(getattr(reader_pool, 'workers_count',
                                             1) or 1)))
            # ONE source of truth for piece->rowgroup attribution (virtual
            # split pieces included): the scheduler's own plan map
            self._piece_locator = self._cost_scheduler.piece_locator()
            if shuffle_row_groups:
                order_fn = self._cost_scheduler.order_items
                self._cost_scheduler.live_reorder = True
            else:
                # no per-epoch shuffle: one static cost-balanced order,
                # identical every epoch (the FIFO analog of the seeded path)
                items = self._cost_scheduler.order_items(items, None)

        # ---------------------------------------------- checkpoint / resume
        # Consumption is tracked at work-item (rowgroup x drop-partition) granularity:
        # every item yields exactly one ColumnarBatch, tagged with its absolute epoch and
        # counted when popped off the results queue. Deterministic epoch order (sorted
        # fragments + seeded shuffles) makes the position replayable — the extension
        # SURVEY.md §5.4 prescribes over the reference's epoch-only restart granularity.
        self._items_per_epoch = len(items)
        self._accounting_lock = threading.Lock()
        self._next_lock = threading.Lock()  # concurrent next() support (see __next__)
        self._epochs_consumed = 0
        self._consumed_by_epoch = {}  # absolute epoch -> set of (piece, drop)
        iterations = num_epochs
        skip_by_iteration = None
        pre_shuffles = 0
        self._resume_fast_forward = {}
        self._resume_lineage = None
        if resume_state is not None:
            self._load_resume_state(resume_state)
            pre_shuffles = self._epochs_consumed
            skip_by_iteration = {epoch - self._epochs_consumed: set(ids)
                                 for epoch, ids in self._consumed_by_epoch.items()}
            if num_epochs is not None:
                iterations = num_epochs - self._epochs_consumed
                if iterations <= 0:
                    raise ValueError(
                        'resume_state shows all {} epochs already consumed'.format(num_epochs))

        # ------------------------------------------------- lineage recorder
        # (docs/observability.md "Sample lineage & determinism audit"): built
        # once the work plan is frozen — the manifest header written here is
        # the exact reproduction record the dry replay verifier consumes.
        if self._lineage_policy is not None:
            from petastorm_tpu.dataset_state import cache_state_home
            from petastorm_tpu.telemetry.lineage import (LineageRecorder,
                                                         build_manifest_logger,
                                                         canonical_identity)
            url_for_state = dataset_url_or_urls if not isinstance(
                dataset_url_or_urls, list) else dataset_url_or_urls[0]
            manifest_jsonl, manifest_path = build_manifest_logger(
                self._lineage_policy, url_for_state, self.dataset_token,
                cache_state_home(cache))
            self._lineage = LineageRecorder(
                self.dataset_token, self._lineage_policy,
                jsonl=manifest_jsonl, manifest_path=manifest_path,
                registry=self._telemetry,
                resume_state=self._resume_lineage)
            header = {
                'dataset_url': str(url_for_state),
                'seed': seed,
                'shuffle_row_groups': bool(shuffle_row_groups),
                'num_epochs': num_epochs,
                'pre_shuffles': pre_shuffles,
                'resumed': resume_state is not None,
                'cur_shard': cur_shard, 'shard_count': shard_count,
                'shard_seed': shard_seed,
                'drop_partitions': shuffle_row_drop_partitions,
                'items_per_epoch': len(items),
                # construction-order item list: what each epoch's reorder
                # permutes — [piece, fragment, rowgroup, row_range, drop],
                # coerced through the same canonicalization deliveries fold
                # with so replay and recording can never disagree on types
                'items': [[int(item['piece_index'])] + canonical_identity(
                    0, item['fragment_path'], item['row_group_id'],
                    item.get('row_range'),
                    item['shuffle_row_drop_partition'][0])[1:]
                    for item in items],
                # the sharded enumeration for the zero-read dataset
                # cross-check (footer metadata only)
                'shard_rowgroups': [
                    [str(rg.fragment_path),
                     int(rg.row_group_id)
                     if rg.row_group_id is not None else None,
                     int(rg.row_group_num_rows)]
                    for rg in shard_row_groups],
                'quarantined_fragments': sorted(
                    record.fragment_path
                    for record in construction_quarantine),
                'schedule': (self._cost_scheduler.plan_fingerprint()
                             if self._cost_scheduler is not None else None),
            }
            if self._topology is not None:
                # negotiated-topology provenance (parallel/topology.py):
                # written ONLY when armed so a static-shard recording stays
                # byte-identical to the seed manifest format
                header['topology'] = self._topology.header()
            if skip_by_iteration:
                header['skip_by_iteration'] = {
                    str(k): sorted(list(item) for item in v)
                    for k, v in skip_by_iteration.items()}
            self._lineage.write_header(header)

        max_in_flight = getattr(reader_pool, 'workers_count', 1) + _VENTILATE_EXTRA_ROWGROUPS
        self._ventilator = ConcurrentVentilator(
            ventilate_fn=_traced_ventilate(reader_pool.ventilate,
                                           self._lineage),
            items_to_ventilate=items,
            iterations=iterations,
            max_ventilation_queue_size=max_in_flight,
            randomize_item_order=shuffle_row_groups,
            random_seed=seed,
            pre_shuffle_count=pre_shuffles,
            skip_ids_by_iteration=skip_by_iteration,
            item_id_fn=_item_id,
            reset_iterations=num_epochs,
            tag_epoch=True,
            order_fn=order_fn)
        self._pool = reader_pool
        if (self._cost_scheduler is not None
                and hasattr(reader_pool, 'set_cost_hint_fn')):
            # service path: ship the measured cost with every submit so the
            # dispatcher's DRR charges real cost and routes heavy items to
            # the least-loaded workers (docs/performance.md)
            reader_pool.set_cost_hint_fn(self._cost_scheduler.cost_hint_for)
        if on_error == 'skip' and hasattr(reader_pool, 'set_hang_result_factory'):
            # Per-item-deadline watchdog hook (docs/robustness.md): when the pool
            # reaps a hung worker, the overdue rowgroup is quarantined — an empty
            # stand-in batch carrying a QuarantineRecord(reason='hang') rides the
            # normal delivery path, so consumption accounting stays exact.
            reader_pool.set_hang_result_factory(
                _make_hang_stand_in_factory(ngram))
        self._pool.start(RowGroupWorker, worker_setup, self._ventilator)

        if ngram is not None:
            self._results_reader = _NGramResultsReader(
                self.result_schema, ngram, on_batch=self._note_item_consumed,
                fast_forward=self._resume_fast_forward)
        elif is_batched_reader:
            self._results_reader = _BatchResultsReader(self.result_schema,
                                                       on_batch=self._note_item_consumed,
                                                       fast_forward=self._resume_fast_forward)
        else:
            self._results_reader = _RowResultsReader(self.result_schema,
                                                     on_batch=self._note_item_consumed,
                                                     fast_forward=self._resume_fast_forward)

        # Closed-loop autotuner (docs/autotuning.md): built only when asked —
        # the disabled path constructs nothing and mutates nothing.
        from petastorm_tpu.autotune.policy import resolve_policy
        autotune_policy = resolve_policy(autotune)
        if autotune_policy is not None:
            from petastorm_tpu.autotune.controller import setup_reader_autotune
            self._autotune = setup_reader_autotune(self, autotune_policy)
            self._autotune.start()

        # Incident autopsy plane (docs/observability.md "Incident autopsy
        # plane"): the black-box recorder subscribes to the failure edges the
        # pipeline already raises — breaker trips (both this process's board
        # and the worker-side sidecar states), hang reaps, quarantines, shm
        # CRC drops, SLO breach edges and lineage divergence — and captures
        # one rate-limited evidence bundle per edge.
        if self._incident_policy is not None:
            from petastorm_tpu.dataset_state import cache_state_home
            from petastorm_tpu.resilience import default_board
            from petastorm_tpu.telemetry.incident import (IncidentRecorder,
                                                          default_incident_home)
            url_for_incidents = dataset_url_or_urls if not isinstance(
                dataset_url_or_urls, list) else dataset_url_or_urls[0]
            self._incidents = IncidentRecorder(
                default_incident_home(cache_state_home(cache)),
                self._incident_policy, registry=self._telemetry)
            self._incidents.add_source('metrics', self.telemetry_snapshot)
            self._incidents.add_source(
                'slo', lambda: self._evaluate_slo(self.telemetry_snapshot()))
            self._incidents.add_source('breakers', self._breaker_evidence)
            self._incidents.add_source('quarantine', self.quarantine.as_dicts)
            if self._cost_scheduler is not None:
                self._incidents.add_source('costs',
                                           self._cost_scheduler.report)
            if self._lineage is not None:
                self._incidents.add_source('lineage', self._lineage.report)
            if self._autotune is not None:
                self._incidents.add_source('autotune', self._autotune.report)
            if self._topology is not None:
                self._incidents.add_source('topology', self._topology.report)
                # construction-time edges: a corrupt membership journal and
                # a reshard-survivor join are both capture-worthy evidence
                if self._topology.frames_dropped:
                    self._incidents.trigger(
                        'ledger_corrupt',
                        args={'journal': self._topology.journal.path,
                              'frames_dropped': self._topology.frames_dropped,
                              'plane': 'topology'})
                if self._topology.generation > 0:
                    self._incidents.trigger(
                        'host_reshard',
                        args={'generation': self._topology.generation,
                              'host_id': self._topology.host_id,
                              'assignment': list(self._topology.assignment)})
            provenance = {
                'dataset_url': str(url_for_incidents),
                'dataset_token': self.dataset_token,
                'seed': seed, 'num_epochs': num_epochs,
                'shuffle_row_groups': bool(shuffle_row_groups),
                'cur_shard': cur_shard, 'shard_count': shard_count,
                'topology': (self._topology.header()
                             if self._topology is not None else None),
                'on_error': on_error,
                'pool': type(reader_pool).__name__,
                'items_per_epoch': self._items_per_epoch,
            }
            self._incidents.add_source('config', lambda: provenance)
            default_board().observe_transitions(
                self._incidents.on_breaker_transition)
            self._slo.observe_breaches(self._on_slo_breach)

        # Longitudinal observatory (docs/observability.md "Longitudinal
        # observatory"): the historian appends one run record at stop();
        # the sentinel watches this run's own rows/s + wait-share series and
        # fires the edge-triggered perf_regression anomaly into the
        # incident plane on a mid-run collapse.
        if self._history_policy is not None:
            from petastorm_tpu.dataset_state import cache_state_home
            from petastorm_tpu.telemetry.history import (RunHistorian,
                                                         default_history_path,
                                                         fingerprint)
            from petastorm_tpu.telemetry.sentinel import (
                RegressionSentinel, resolve_sentinel_policy)
            url_for_history = dataset_url_or_urls if not isinstance(
                dataset_url_or_urls, list) else dataset_url_or_urls[0]
            history_path = (self._history_policy.path
                            or default_history_path(url_for_history,
                                                    cache_state_home(cache)))
            if history_path is not None:
                self._history = RunHistorian(history_path,
                                             self._history_policy,
                                             registry=self._telemetry)
            # the run's configuration identity, frozen now so the record
            # written at stop() attributes with construction-time truth
            self._history_fingerprints = {
                'config': fingerprint({
                    'seed': seed, 'num_epochs': num_epochs,
                    'shuffle_row_groups': bool(shuffle_row_groups),
                    'shuffle_rows': bool(shuffle_rows),
                    'cur_shard': cur_shard, 'shard_count': shard_count,
                    'on_error': on_error,
                    'pool': type(reader_pool).__name__,
                    'batched': bool(is_batched_reader),
                    'transform': transform_spec is not None,
                    'device_decode_fields': sorted(self.device_decode_fields),
                    'items_per_epoch': self._items_per_epoch}),
                'storage': (fingerprint(repr(self._storage_policy))
                            if self._storage_policy is not None else None),
                'schedule': (self._cost_scheduler.plan_fingerprint()
                             if self._cost_scheduler is not None else None),
            }
            sentinel_policy = resolve_sentinel_policy(
                self._history_policy.sentinel)
            if sentinel_policy is not None:
                self._sentinel = RegressionSentinel(
                    sentinel_policy, owner='reader',
                    registry=self._telemetry, incidents=self._incidents,
                    dataset_token=self.dataset_token)
                if self._incidents is not None:
                    self._incidents.add_source('sentinel',
                                               self._sentinel.report)
            if (self._autotune is not None and self._history is not None
                    and getattr(autotune_policy, 'warm_start', False)):
                self._warm_start_autotune()

        # Live metrics plane (docs/observability.md): one scrape endpoint
        # over this reader's cross-process snapshot; SLO gauges refresh per
        # scrape. Started last so a scrape can never observe a half-built
        # reader; stop() tears it down.
        if metrics_port is not None:
            from petastorm_tpu.telemetry.http_exporter import MetricsHttpServer
            self._metrics_server = MetricsHttpServer(
                snapshot_fn=self._scrape_snapshot,
                health_fn=self._scrape_health,
                port=int(metrics_port))
            self._metrics_server.start()

    # --------------------------------------------------------------- sharding

    @staticmethod
    def _partition_row_groups(row_groups, cur_shard, shard_count, shard_seed):
        """Deterministic modulo sharding, with optional seeded pre-shuffle so shards draw
        from the whole dataset (reference: petastorm/reader.py:570-594)."""
        if cur_shard is None:
            return list(row_groups)
        indexed = list(enumerate(row_groups))
        if shard_seed is not None:
            np.random.RandomState(shard_seed).shuffle(indexed)
        return [rg for index, (orig, rg) in enumerate(indexed)
                if index % shard_count == cur_shard]

    # --------------------------------------------------------------- iterator

    def __iter__(self):
        return self

    def __next__(self):
        if self._stopped:
            raise RuntimeError('Trying to read a sample from a stopped reader')
        try:
            # Serialized: the results reader buffers a batch across calls, and the
            # reference supports concurrent next() from many threads
            # (reference test_end_to_end.py:832-842) — per-row lock cost is noise
            # next to namedtuple assembly.
            with self._next_lock:
                result = self._results_reader.read_next(self._pool)
            return result
        except EmptyResultError:
            self.last_row_consumed = True
            raise StopIteration

    next = __next__

    def __len__(self):
        """Total rows in this shard per epoch (reference: reader.py:492-494)."""
        return sum(rg.row_group_num_rows for rg in self._shard_row_groups)

    def iter_columnar(self, include_empty=False):
        """Iterate raw :class:`ColumnarBatch` results straight off the worker pool —
        the zero-copy fast path for columnar consumers (JaxDataLoader), skipping the
        per-row namedtuple conversion of ``__next__``. Do not interleave with ``next()``.
        ``include_empty`` also yields zero-row batches (published so every work item is
        observable — delivery-exact checkpointing needs them).

        NGram readers yield WINDOW-major batches: each column is
        ``(num_windows, ngram.length, *field_shape)`` (``NGram.windows_as_arrays``) and
        ``num_rows`` counts windows. Window batches carry the piece's ``item_id``
        (zero-window pieces publish an empty batch to carry it), so checkpoint/resume
        and the device loaders' delivery accounting work for NGram exactly as for
        rows, with the window as the row unit (VERDICT r3 item 4)."""
        while True:
            if self._stopped:
                raise RuntimeError('Trying to read from a stopped reader')
            try:
                batch = self._pool.get_results()
            except EmptyResultError:
                self.last_row_consumed = True
                return
            if self.ngram is not None:
                # NGramWindows payload (shared columns + gather starts) -> dense
                # window-major arrays, one vectorized gather per column. item_id
                # rides along so delivery accounting / resume see the piece —
                # and so do the resilience/cache/telemetry sidecars, which
                # _note_item_consumed below accounts from this rebuilt batch.
                batch = ColumnarBatch(
                    self.ngram.windows_as_arrays(batch.columns, batch.starts),
                    len(batch.starts), item_id=batch.item_id,
                    retries=getattr(batch, 'retries', 0),
                    quarantine=getattr(batch, 'quarantine', None),
                    cache_hit=getattr(batch, 'cache_hit', None),
                    telemetry=getattr(batch, 'telemetry', None),
                    breakers=getattr(batch, 'breakers', None),
                    trace=getattr(batch, 'trace', None),
                    lineage=getattr(batch, 'lineage', None))
            self._note_item_consumed(batch)
            if self._resume_fast_forward and batch.item_id is not None:
                # Honor a row_cursor from a row-path checkpoint: skip the rows that
                # were already emitted before the checkpoint (exact-once everywhere).
                start = self._resume_fast_forward.pop(batch.item_id, 0)
                if start:
                    batch = _slice_batch(batch, start)
            if batch.num_rows or include_empty:
                yield batch

    def reset(self):
        """Re-ventilate for another ``num_epochs`` pass; only valid after full consumption
        (reference: reader.py:496-520)."""
        if not self.last_row_consumed:
            raise NotImplementedError('Currently reset() can only be called after the '
                                      'reader was fully consumed')
        self._results_reader.reset()
        self._ventilator.reset()
        self.last_row_consumed = False

    # ----------------------------------------------------------- checkpoint / resume

    def _note_item_consumed(self, batch):
        # Resilience sidecar first: retry/quarantine accounting applies to every result
        # (on_batch fires exactly once per published batch on every pool).
        record = getattr(batch, 'quarantine', None)
        if record is not None:
            self.quarantine.add(record)
            if self._incidents is not None:
                # black-box capture at the edge: a reaped hang and a skipped
                # rowgroup are distinct trigger kinds (distinct autopsy
                # causes), both carrying the (epoch, rowgroup, attempt)
                # coordinates of the failing item
                kind = ('watchdog_reap' if record.reason == 'hang'
                        else 'quarantine')
                self._incidents.trigger(
                    kind,
                    ctx=(record.epoch, record.piece_index, record.attempts),
                    args=record.as_dict())
        retries = getattr(batch, 'retries', 0)
        if retries:
            with self._accounting_lock:
                self._io_retries += retries
        cache_hit = getattr(batch, 'cache_hit', None)
        if cache_hit is not None:
            with self._accounting_lock:
                if cache_hit:
                    self._cache_hits += 1
                else:
                    self._cache_misses += 1
        stage_times = getattr(batch, 'telemetry', None)
        if stage_times:
            # cross-process span merge: the sidecar is a {stage: hist_snapshot}
            # dict (additive, so respawned workers merge like any other)
            self._telemetry.merge_stage_times(stage_times)
            if self._cost_scheduler is not None:
                # live cost feed (docs/performance.md "Cost-aware scheduling"):
                # a batch's sidecar holds the stage time of (mostly) its own
                # rowgroup — fold it into the live ledger persisted at stop()
                scheduled_id = getattr(batch, 'item_id', None)
                if scheduled_id is not None:
                    self._cost_scheduler.observe(scheduled_id[1], stage_times)
        breakers = getattr(batch, 'breakers', None)
        if breakers:
            opened = []
            with self._accounting_lock:
                if self._incidents is not None:
                    # worker-process breakers arrive as sidecar states, not
                    # callbacks: detect the closed→open edge against the
                    # last-seen state before folding the update in
                    opened = [
                        (name, state) for name, state in breakers.items()
                        if state.get('state') == 'open'
                        and (self._breaker_states.get(name) or {}).get(
                            'state') != 'open']
                self._breaker_states.update(breakers)
            for name, state in opened:
                self._incidents.trigger(
                    'breaker_open',
                    args={'breaker': name, 'snapshot': state})
        trace_sidecar = getattr(batch, 'trace', None)
        if trace_sidecar:
            # flight-recorder merge: the producing process's drained timeline
            # events land in this process's recorder, preserving their pid —
            # one dump_trace() then spans every process
            merge_trace_events(trace_sidecar)
        if self._incidents is not None:
            # poll-based edges, O(1) per batch: the process pool's CRC-drop
            # count and the lineage recorder's divergence count only ever
            # grow — a delta since the last batch IS the edge
            crc_failures = getattr(self._pool, '_shm_crc_failures', 0)
            if crc_failures > self._incident_last_crc_failures:
                self._incident_last_crc_failures = crc_failures
                self._incidents.trigger(
                    'shm_crc_drop',
                    args={'shm_crc_failures': crc_failures})
        if self._sentinel is not None:
            # live drift watch (docs/observability.md "Longitudinal
            # observatory"): one float compare per batch between windows;
            # the snapshot + evaluation only run when a window is due
            from petastorm_tpu.telemetry.slo import slo_clock
            if self._sentinel.due(slo_clock() - self._started_at):
                self._evaluate_slo(self.telemetry_snapshot())
        item_id = getattr(batch, 'item_id', None)
        if item_id is None:
            return
        if self._lineage is not None:
            # lineage delivery accounting (docs/observability.md "Sample
            # lineage"): exactly one deliver per work item on every pool —
            # the recorder folds it at its ventilation-order slot
            self._lineage.deliver(
                item_id, getattr(batch, 'num_rows', 0) or 0,
                fingerprint=getattr(batch, 'lineage', None),
                quarantined=record is not None)
            if self._incidents is not None:
                divergences = self._lineage.divergence_count
                if divergences > self._incident_last_divergence:
                    self._incident_last_divergence = divergences
                    self._incidents.trigger(
                        'lineage_divergence', ctx=item_id,
                        args={'divergence_count': divergences})
        epoch, piece, drop = item_id
        if trace_enabled():
            # consumer-side anchor of the rowgroup's trace: present on every
            # pool/transport, so a trace always ends on the consumer track
            trace_instant('rowgroup_consumed', ctx=(epoch, piece, 0),
                          args={'rows': getattr(batch, 'num_rows', 0)})
        if self._topology is not None:
            # journal the delivery under its GLOBAL rowgroup index — the set
            # a reshard subtracts to re-deal only the undelivered remainder
            # (docs/robustness.md "Elastic pod-scale sharding")
            self._topology.note_progress(epoch, piece, drop)
        with self._accounting_lock:
            self._rows_consumed += getattr(batch, 'num_rows', 0) or 0
            self._consumed_by_epoch.setdefault(epoch, set()).add((piece, drop))
            # Epochs complete strictly in order; results of later epochs accumulate in
            # their own sets until the earlier epoch's straggler items are popped.
            while (len(self._consumed_by_epoch.get(self._epochs_consumed, ()))
                   >= self._items_per_epoch):
                del self._consumed_by_epoch[self._epochs_consumed]
                self._epochs_consumed += 1

    def _load_resume_state(self, state):
        if not isinstance(state, dict) or state.get('version') != 1:
            raise ValueError('Unrecognized resume_state {!r}'.format(state))
        saved_shard = state.get('shard_config')
        if saved_shard is not None and saved_shard != self._shard_config:
            # Silent wrong-stream guard: a checkpoint replayed under a
            # different shard split skips/duplicates rows without any error
            # — refuse loudly, naming both configs (the split-plan refusal
            # discipline). Cross-topology restore goes through the
            # negotiated path only (topology.merge_topology_states).
            raise ValueError(
                'resume_state was captured under shard config {!r}, but '
                'this reader is configured with {!r} — resuming would '
                'silently replay the wrong row stream. Rebuild with the '
                'original sharding, or restore across topologies via '
                'petastorm_tpu.parallel.topology.merge_topology_states'
                .format(saved_shard, self._shard_config))
        saved_topology = state.get('topology')
        if saved_topology is not None:
            if self._topology is None:
                raise ValueError(
                    'resume_state was captured by a topology-armed reader '
                    '(identity {}/{}), but this reader is static-sharded — '
                    'restore through make_reader(topology=...) (see '
                    'topology.policy_from_state)'.format(
                        saved_topology.get('process_index'),
                        saved_topology.get('process_count')))
            if list(saved_topology.get('assignment') or []) != \
                    list(self._topology.assignment):
                raise ValueError(
                    'resume_state was dealt assignment {!r}, but this '
                    'reader negotiated {!r} — re-deal the checkpoint with '
                    'topology.merge_topology_states before resuming on a '
                    'changed topology'.format(
                        saved_topology.get('assignment'),
                        list(self._topology.assignment)))
        if state['items_per_epoch'] != self._items_per_epoch:
            raise ValueError(
                'resume_state was captured from a reader with {} work items per epoch, '
                'but this reader has {} — dataset contents, sharding, predicate, selector '
                'or shuffle_row_drop_partitions differ'
                .format(state['items_per_epoch'], self._items_per_epoch))
        self._epochs_consumed = int(state['epochs_consumed'])
        self._consumed_by_epoch = {
            self._epochs_consumed + int(offset): {tuple(item) for item in ids}
            for offset, ids in state['consumed_by_epoch'].items()}
        # lineage digest continuity (docs/observability.md): the chain value
        # + pending suffix saved by state_dict(), handed to the recorder so
        # the resumed run folds to the same digest as an uninterrupted one
        self._resume_lineage = state.get('lineage')
        cursor = state.get('row_cursor')
        if cursor is not None:
            # Replay the mid-batch position: the item is NOT in the consumed sets (its
            # batch was only partially emitted), so it re-ventilates in its epoch; the
            # row reader fast-forwards past the rows already emitted before checkpoint.
            key = (self._epochs_consumed + int(cursor['epoch_offset']),
                   int(cursor['piece']), int(cursor['drop']))
            self._resume_fast_forward[key] = int(cursor['next_row'])

    def state_dict(self):
        """Snapshot of the read position, resumable via ``make_reader(...,
        resume_state=state)`` with identical construction arguments.

        Granularity is the work item (rowgroup x drop-partition): an item counts as
        consumed once ALL of its rows have been emitted (``consumed_by_epoch`` maps
        epoch offsets to consumed items — several epochs can be partially consumed at
        once since completions interleave across epoch boundaries). A checkpoint taken
        mid-batch on the row path additionally records a ``row_cursor`` (item + next
        row index), and resume fast-forwards that item to the exact row — no rows are
        lost or duplicated (row-exact, provided in-batch row order is reproducible:
        either ``shuffle_rows=False`` or a fixed ``seed``; with ``shuffle_rows=True``
        and ``seed=None`` the partial batch is replayed in a new random order and
        resume is only item-exact). Results published by workers but not yet popped
        are re-read (at-least-once). Call from the consuming thread, between ``next()``
        calls. The reference has no analog (restart granularity is the epoch,
        SURVEY.md §5.4).

        NGram readers checkpoint identically with the WINDOW as the row unit: the
        cursor records the next window of the partially-emitted piece, and resume
        replays from that window (window-exact under a seeded shuffle, since the
        per-piece window order is then reproducible).
        """
        if (self._cost_scheduler is not None
                and self._cost_scheduler.split_count):
            # A split plan's work items carry row_range coordinates a resumed
            # reader cannot reconstruct (resume rejects cost_schedule, and an
            # unscheduled resume would match a parent piece id against the
            # unsplit item — silently skipping the rowgroup's other
            # sub-ranges). Refuse loudly rather than emit a checkpoint that
            # loses rows. Interleave-only plans (no splits) checkpoint fine:
            # their item coordinates are identical to an unscheduled reader's.
            raise ValueError(
                'state_dict() is not supported on a cost-scheduled reader '
                'whose plan split rowgroups ({} split(s)): the sub-range '
                'work-item coordinates cannot be resumed. Checkpoint with '
                'cost_schedule disabled, or a SchedulePolicy(split=False).'
                .format(self._cost_scheduler.split_count))
        lineage_state = None
        if self._lineage is not None:
            # taken OUTSIDE the accounting lock (the recorder has its own);
            # state_dict runs on the consuming thread between next() calls,
            # so no deliver can interleave with this snapshot
            lineage_state = self._lineage.state_dict()
        cursor = None
        if isinstance(self._results_reader, (_RowResultsReader, _NGramResultsReader)):
            # NGram: the work-item unit is identical; the cursor's row index counts
            # WINDOWS (the NGram path's row unit) instead of rows. Under _next_lock:
            # with concurrent next() threads, an unlocked read could catch the
            # last-row/acknowledge window mid-flight and snapshot a torn position.
            with self._next_lock:
                cursor = self._results_reader.cursor()
        with self._accounting_lock:
            state = {
                'version': 1,
                'items_per_epoch': self._items_per_epoch,
                'epochs_consumed': self._epochs_consumed,
                'consumed_by_epoch': {
                    epoch - self._epochs_consumed: sorted(ids)
                    for epoch, ids in self._consumed_by_epoch.items()},
                # the shard configuration this position is only valid under
                # — resume validates it and refuses a drifted config loudly
                'shard_config': dict(self._shard_config),
            }
            if self._topology is not None:
                # the negotiated identity + explicit global assignment that
                # cross-topology restore (topology.merge_topology_states)
                # re-deals onto a different host count
                state['topology'] = self._topology.state_block()
            if cursor is not None:
                (epoch, piece, drop), next_row = cursor
                # Deferred acknowledgment guarantees epoch >= _epochs_consumed: the
                # partially-emitted item is unconsumed, so its epoch cannot be closed.
                state['row_cursor'] = {'epoch_offset': epoch - self._epochs_consumed,
                                       'piece': piece, 'drop': drop,
                                       'next_row': next_row}
            if lineage_state is not None:
                # the chained-digest state (docs/observability.md "Sample
                # lineage"): a resumed reader seeded with it folds to the
                # exact digest of an uninterrupted run
                state['lineage'] = lineage_state
            return state

    @property
    def items_per_epoch(self):
        return self._items_per_epoch

    @property
    def io_retries(self):
        """Cumulative transient-IO retries spent by workers on this reader's behalf."""
        with self._accounting_lock:
            return self._io_retries

    @property
    def rows_consumed(self):
        """Cumulative rows delivered off the results channel (NGram: windows) —
        the autotuner's goodput numerator (docs/autotuning.md)."""
        with self._accounting_lock:
            return self._rows_consumed

    def autotune_report(self):
        """The closed-loop autotuner's state (docs/autotuning.md): windows,
        decision log, frozen-by-breaker flag, and current knob values/bounds —
        ``{'enabled': False}`` when the reader was built without
        ``autotune``."""
        if self._autotune is None:
            return {'enabled': False}
        return self._autotune.report()

    @property
    def telemetry(self):
        """The reader's consumer-side :class:`~petastorm_tpu.telemetry.MetricsRegistry`
        (worker sidecar merges land here); prefer :meth:`telemetry_snapshot` for
        the pool-inclusive view."""
        return self._telemetry

    def telemetry_snapshot(self):
        """One JSON-safe telemetry snapshot covering every process: the reader's
        registry (which absorbed the worker-sidecar stage times) merged with the
        pool's consumer-side registry (shm_map/shm_release/pool_wait,
        wire_bytes_copied). Feed it to
        :func:`petastorm_tpu.telemetry.analyze.attribute_bottleneck` or
        :func:`petastorm_tpu.telemetry.export.to_prometheus_text`."""
        from petastorm_tpu.telemetry import merge_snapshots
        pool_registry = getattr(self._pool, 'telemetry', None)
        storage_snapshot = None
        if self._storage_policy is not None:
            # the ingest engine's process-local counters (footer cache /
            # coalescing / hedging); armed-only so unarmed readers stay
            # byte-identical, and populated in-process for thread/dummy
            # pools (process-pool workers keep them worker-side, like the
            # other worker counters)
            from petastorm_tpu.storage import storage_metrics_snapshot
            storage_snapshot = storage_metrics_snapshot()
        if pool_registry is None and storage_snapshot is None:
            return self._telemetry.snapshot()
        return merge_snapshots(self._telemetry.snapshot(),
                               pool_registry.snapshot()
                               if pool_registry is not None else None,
                               storage_snapshot)

    # ------------------------------------------------------- efficiency SLO

    def _evaluate_slo(self, snapshot):
        from petastorm_tpu.telemetry.slo import slo_clock
        report = self._slo.evaluate(snapshot, slo_clock() - self._started_at,
                                    rows=self.rows_consumed,
                                    registry=self._telemetry)
        if self._sentinel is not None:
            # the regression sentinel windows the same cumulative series the
            # SLO report carries; it enforces its own min_window_s, so extra
            # evaluations (scrapes, diagnostics) cannot shrink a window
            self._sentinel.observe(report)
            self._sentinel.export_gauges()
        return report

    def efficiency_report(self):
        """One input-efficiency SLO evaluation over this reader's lifetime
        (docs/observability.md "Efficiency SLOs"): efficiency in [0, 1]
        derived from the recorded consumer wait spans (``pool_wait``, plus
        ``shuffle_wait``/``d2d_wait`` when a loader consumes this reader),
        the starvation fraction, goodput vs ideal rows/s, and the breach
        accounting (edge-triggered ``slo_breach`` counter / JSONL event /
        trace instant on each ok→breach transition). Also under
        ``diagnostics['slo']``; the ``slo_efficiency`` gauge refreshes in
        the telemetry registry on every call."""
        return self._evaluate_slo(self.telemetry_snapshot())

    # --------------------------------------------------------- cost profiler

    def cost_ledger(self, ledger=None):
        """Fold the flight recorder's per-rowgroup span history for this
        reader into a :class:`~petastorm_tpu.telemetry.cost_model.CostLedger`
        (docs/observability.md "Cost profiler"). Requires tracing to have
        been armed for the read (``trace=True`` / ``PETASTORM_TPU_TRACE=1``)
        — an unarmed read yields an empty ledger. ``ledger`` continues an
        existing ledger (same dataset token); the default starts a fresh one
        keyed by :attr:`dataset_token`. The one-command form is
        ``petastorm-tpu-throughput costs <dataset_url>``."""
        from petastorm_tpu.telemetry.cost_model import CostLedger
        from petastorm_tpu.telemetry.tracing import trace_snapshot
        if ledger is None:
            ledger = CostLedger(self.dataset_token)
        # the piece locator covers the virtual pieces of split rowgroups too,
        # so a scheduled read attributes sub-range costs to the parent rowgroup
        ledger.ingest_trace(trace_snapshot(), dict(self._piece_locator))
        return ledger

    # ------------------------------------------------------- lineage audit

    def order_digest(self):
        """The chained sample-lineage order digest over every item delivered
        so far (docs/observability.md "Sample lineage & determinism audit"):
        a hex string identical across dummy/thread/process/service pools for
        the same seed + shard config + schedule plan, and invariant under
        worker respawns/redeliveries. None when the reader was built without
        ``lineage``."""
        if self._lineage is None:
            return None
        return self._lineage.order_digest()

    # ----------------------------------------------- incident autopsy plane

    def _breaker_evidence(self):
        """The bundle's ``breakers`` source: worker-sidecar states merged
        with this process's board (same merge ``diagnostics`` performs)."""
        from petastorm_tpu.resilience import default_board
        with self._accounting_lock:
            breakers = dict(self._breaker_states)
        breakers.update(default_board().snapshot())
        return breakers

    def _on_slo_breach(self, report):
        """SLO ok→breach edge observer → one ``slo_breach`` incident."""
        if self._incidents is not None:
            self._incidents.trigger(
                'slo_breach',
                args={'efficiency': report.get('efficiency'),
                      'target': report.get('target_efficiency'),
                      'wait_seconds': report.get('wait_seconds')})

    def incident_report(self):
        """The incident recorder's summary — capture/rate-limit counters and
        the retained bundle names (docs/observability.md "Incident autopsy
        plane"); None when the reader was built without ``incidents``."""
        if self._incidents is None:
            return None
        return self._incidents.report()

    # ------------------------------------------- longitudinal observatory

    def build_history_record(self):
        """The structured run record this reader would append at ``stop()``
        (docs/observability.md "Longitudinal observatory"): fingerprints,
        headline rows/s + efficiency, per-stage time shares, storage
        counters, incident/quarantine counts. None when built without
        ``history``. Knob values are read live, so call before ``stop()``
        restores the autotuner's knobs to see what the run actually ran
        with."""
        if self._history_policy is None:
            return None
        from petastorm_tpu.telemetry.history import build_run_record, fingerprint
        from petastorm_tpu.telemetry.slo import (efficiency_from_snapshot,
                                                 slo_clock)
        elapsed = slo_clock() - self._started_at
        snapshot = self.telemetry_snapshot()
        rows = self.rows_consumed
        slo_report = efficiency_from_snapshot(snapshot, elapsed, rows=rows)
        knobs = {}
        try:
            from petastorm_tpu.autotune.knobs import build_reader_knobs
            knobs = {knob.knob_id: float(knob.get())
                     for knob in build_reader_knobs(self)}
        except Exception:  # noqa: BLE001 - the record is advisory; a dead knob target must not fail stop()
            logger.debug('history: knob capture failed', exc_info=True)
        fingerprints = dict(self._history_fingerprints)
        fingerprints['knobs'] = fingerprint(knobs) if knobs else None
        cost_skew = None
        if self._cost_scheduler is not None:
            cost_skew = self._cost_scheduler.cost_skew()
        return build_run_record(
            'reader', self.dataset_token, elapsed, rows,
            snapshot=snapshot, slo_report=slo_report,
            fingerprints=fingerprints, knobs=knobs,
            incidents=self.incident_report(),
            quarantined=len(self.quarantine), cost_skew=cost_skew)

    def _warm_start_autotune(self):
        """``AutotunePolicy(warm_start=True)``: seed the live knobs from the
        newest same-token, same-platform run record before the controller's
        first window, so this run starts from last run's converged values
        instead of re-climbing from the defaults. Gated off — with a debug
        line, never an error — when the store holds no comparable record
        (first run, or the platform changed)."""
        from petastorm_tpu.telemetry.history import (last_good_record,
                                                     load_records,
                                                     run_platform)
        try:
            records, _dropped = load_records(self._history.path)
            record = last_good_record(records, self.dataset_token,
                                      run_platform())
            if record is None:
                logger.debug('autotune warm start: no comparable run record '
                             'in %s; starting from defaults',
                             self._history.path)
                return
            applied = self._autotune.warm_start(record.get('knobs') or {})
            if applied:
                logger.info('autotune warm start: seeded %s from the run '
                            'recorded at %s',
                            {k: v['to'] for k, v in applied.items()},
                            record.get('recorded_unix_s'))
        except Exception:  # noqa: BLE001 - warm start is an optimization; failure means defaults, not a dead reader
            logger.warning('autotune warm start failed; starting from '
                           'defaults', exc_info=True)

    def _write_history_record(self):
        """Append this run's record to the longitudinal store — called from
        ``stop()`` BEFORE the autotuner restores its knobs (the record must
        capture the values the run actually ran with). Idempotent."""
        if self._history is None or self._history_written:
            return
        self._history_written = True
        try:
            record = self.build_history_record()
            if record is not None:
                self._history.append(record)
        except Exception:  # noqa: BLE001 - the historian is advisory; a read that succeeded must not fail over its memory
            logger.warning('could not record this run in the history store',
                           exc_info=True)

    def history_report(self):
        """The historian's store status (path, appended count, dropped
        frames); None when the reader was built without ``history``."""
        if self._history is None:
            return None
        return self._history.state()

    # ------------------------------------------------------- metrics plane

    def _snapshot_with_slo(self):
        """One telemetry snapshot (built ONCE — the cross-process merge is
        the expensive half) evaluated against the SLO, with the fresh
        ``slo_*`` gauges spliced in; returns ``(snapshot, slo_report)``."""
        snapshot = self.telemetry_snapshot()
        report = self._evaluate_slo(snapshot)
        gauges = snapshot.setdefault('gauges', {})
        if report['efficiency'] is not None:
            gauges['slo_efficiency'] = report['efficiency']
        gauges['slo_target_efficiency'] = report['target_efficiency']
        if self._lineage is not None:
            # the /metrics view of the audit plane: fold progress + reorder-
            # buffer depth (the lineage_divergence counter rides the
            # registry's counters like any other)
            lineage = self._lineage.report()
            gauges['lineage_items_folded'] = lineage['items_folded']
            gauges['lineage_pending_items'] = lineage['pending_items']
        if self._sentinel is not None:
            # the smoothed drift series (sentinel_rate_ewma /
            # sentinel_wait_share_ewma) ride the same scrape
            gauges.update(self._sentinel.gauges())
        # the SLO tracker's trailing ring buffer rides the /vars document
        # (a list, not a gauge — the text scrape ignores it)
        snapshot['slo_history'] = report.get('history', [])
        return snapshot, report

    def _scrape_snapshot(self):
        """The /metrics endpoint's per-scrape snapshot (SLO gauges fresh)."""
        snapshot, _report = self._snapshot_with_slo()
        return snapshot

    def _scrape_health(self):
        """The ``/healthz`` fields for this reader's endpoint."""
        return {'rows_consumed': self.rows_consumed,
                'stopped': self._stopped,
                'rowgroups_quarantined': len(self.quarantine)}

    @property
    def metrics_url(self):
        """The live scrape endpoint base URL, or None when the reader was
        built without ``metrics_port`` (docs/observability.md)."""
        if self._metrics_server is None:
            return None
        return self._metrics_server.url

    # --------------------------------------------------------- flight recorder

    def dump_trace(self, path=None):
        """Export the flight recorder as Chrome-trace/Perfetto JSON
        (docs/observability.md "Flight recorder"): every event this process
        recorded plus the worker events merged off the ``trace`` batch
        sidecars — per-process tracks, stage slices, anomaly instants, and
        worker→consumer flow arrows per rowgroup. Writes to ``path`` when
        given; returns the trace dict either way (load it at
        https://ui.perfetto.dev). Requires tracing to have been armed for the
        read (``trace=True`` / ``PETASTORM_TPU_TRACE=1``) — otherwise the
        trace is empty."""
        from petastorm_tpu.telemetry.trace_export import (to_chrome_trace,
                                                          write_chrome_trace)
        from petastorm_tpu.telemetry.tracing import trace_snapshot
        snapshot = trace_snapshot()
        if path is not None:
            return write_chrome_trace(path, snapshot)
        return to_chrome_trace(snapshot)

    def trace_summary(self):
        """The non-visual flight-recorder view (doctor/bench embed it): event
        counts, dropped-event count, anomaly instants, and the top-5 longest
        rowgroup traces — see
        :func:`petastorm_tpu.telemetry.trace_export.summarize_trace`."""
        from petastorm_tpu.telemetry.trace_export import summarize_trace
        from petastorm_tpu.telemetry.tracing import trace_snapshot
        return summarize_trace(trace_snapshot())

    # ------------------------------------------------------------- lifecycle

    def stop(self):
        self._stopped = True
        if self._metrics_server is not None:
            # the scrape plane goes first: a scrape against a tearing-down
            # pool would race the very state it reports
            self._metrics_server.stop()
        # the longitudinal run record is written BEFORE the autotuner stops:
        # autotune.stop() restores the pre-tuning knob values, and the
        # record must capture what the run actually ran with
        self._write_history_record()
        if self._autotune is not None:
            # the controller must stop turning knobs before the pool they
            # actuate starts tearing down
            self._autotune.stop()
        if self._cost_scheduler is not None:
            # hand this run's live cost observations to the next one
            # (best-effort: a read must never fail over its bookkeeping)
            try:
                self._cost_scheduler.persist()
            except Exception:  # noqa: BLE001 - ledger persistence is advisory; the read itself already succeeded
                logger.warning('could not persist the cost ledger',
                               exc_info=True)
        if self._lineage is not None:
            # flush the final manifest record (idempotent; the JSONL logger
            # swallows its own write failures)
            self._lineage.close()
        if self._incidents is not None:
            # the recorder only detaches its sources — retained bundles are
            # the whole point and stay on disk for the autopsy CLI
            self._incidents.close()
        if self._topology is not None:
            # journal a clean leave so survivors re-deal immediately rather
            # than waiting out the lease (idempotent)
            self._topology.close()
        self._pool.stop()

    def join(self):
        self._pool.join()

    def cleanup(self):
        pass

    @property
    def diagnostics(self):
        """Pool counters plus the resilience view: cumulative transient-IO retries and
        the quarantine ledger (always present, so dashboards can alert on non-zero
        values without key-existence checks)."""
        diag = dict(self._pool.diagnostics)
        with self._accounting_lock:
            diag['io_retries'] = self._io_retries
            diag['cache_hits'] = self._cache_hits
            diag['cache_misses'] = self._cache_misses
        # In-process cache counters (exact for thread/dummy pools; for the process
        # pool each worker keeps its own copy, so the per-batch cache_hits/misses
        # above are the cross-process aggregate).
        cache_stats = getattr(self._cache, 'stats', None)
        if cache_stats is not None:
            diag['cache'] = dict(cache_stats)
        diag['rowgroups_quarantined'] = len(self.quarantine)
        diag['quarantine'] = self.quarantine.as_dicts()
        # Circuit-breaker states (docs/robustness.md): worker-process breakers
        # (cache/filesystem, via the results-channel sidecar) + this process's
        # board (exact for thread/dummy pools) + the process pool's shm breaker.
        # Healthy (never-tripped, closed) breakers are omitted — an empty dict
        # means everything is closed.
        from petastorm_tpu.resilience import default_board
        with self._accounting_lock:
            breakers = dict(self._breaker_states)
        breakers.update(default_board().snapshot(only_tripped=True))
        shm_breaker = diag.get('shm_breaker')
        if shm_breaker is not None and (
                shm_breaker.get('failures') or shm_breaker.get('opened_count')
                or shm_breaker.get('state') != 'closed'):
            breakers['shm_transport'] = shm_breaker
        diag['breakers'] = breakers
        # One cross-process telemetry snapshot (docs/observability.md): per-stage
        # latency histograms merged from every worker sidecar + the pool
        # registry — built once and shared with the SLO evaluation (which
        # splices its fresh gauges back in).
        snapshot, slo_report = self._snapshot_with_slo()
        diag['slo'] = slo_report
        diag['telemetry'] = snapshot
        # Flight-recorder summary, only while tracing is armed (the summary of
        # an empty recorder would just be noise in every dashboard).
        if trace_enabled():
            diag['trace'] = self.trace_summary()
        # Autotune block only when a controller exists: the disabled path's
        # diagnostics stay byte-identical to the seed.
        if self._autotune is not None:
            diag['autotune'] = self._autotune.report()
        # Cost-aware schedule block only when armed, same contract.
        if self._cost_scheduler is not None:
            diag['schedule'] = self._cost_scheduler.report()
        # Lineage audit block only when armed, same contract.
        if self._lineage is not None:
            diag['lineage'] = self._lineage.report()
        # Incident autopsy block only when armed, same contract.
        if self._incidents is not None:
            diag['incidents'] = self._incidents.report()
        # Longitudinal observatory blocks only when armed, same contract.
        if self._history is not None:
            diag['history'] = self._history.state()
        if self._sentinel is not None:
            diag['sentinel'] = self._sentinel.report()
        # Storage ingest-engine block only when armed, same contract: the
        # counter roll-up doctor and dashboards read (footer-cache hits,
        # ranges coalesced, hedges fired/won — docs/performance.md
        # "Object-store ingest engine").
        if self._storage_policy is not None:
            counters = snapshot.get('counters') or {}
            diag['storage'] = {
                'policy': {
                    'coalesce_gap_bytes':
                        self._storage_policy.coalesce_gap_bytes,
                    'max_in_flight': self._storage_policy.max_in_flight,
                    'hedge_enabled': self._storage_policy.hedge_enabled,
                },
                'footer_cache_hits':
                    int(counters.get('storage_footer_cache_hit', 0)),
                'footer_cache_misses':
                    int(counters.get('storage_footer_cache_miss', 0)),
                'ranges_coalesced':
                    int(counters.get('storage_ranges_coalesced', 0)),
                'hedges_fired':
                    int(counters.get('storage_hedge_fired', 0)),
                'hedges_won':
                    int(counters.get('storage_hedge_won', 0)),
            }
        # Degenerate-sharding detector, only when one fired at construction
        # (docs/robustness.md): shard_count/rowgroups/empty_shards.
        if self._shard_skew is not None:
            diag['shard_skew'] = dict(self._shard_skew)
        # Elastic-topology block only when armed, same contract: negotiated
        # identity, assignment, membership-journal state, stale leases.
        if self._topology is not None:
            diag['topology'] = self._topology.report()
        return diag

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc_val, exc_tb):
        self.stop()
        self.join()


def _item_id(item):
    """Stable identity of a ventilated work item for consumption accounting."""
    return (item['piece_index'], item['shuffle_row_drop_partition'][0])


def _traced_ventilate(pool_ventilate, lineage=None):
    """Wrap a pool's ``ventilate`` so each work item's birth lands on the
    flight-recorder timeline (docs/observability.md "Flight recorder"): the
    ``ventilate`` instant is the causal origin of a rowgroup's trace — the
    ``(epoch, rowgroup)`` context every later span inherits starts here. One
    enabled-check per item when tracing is off.

    ``lineage`` (a :class:`~petastorm_tpu.telemetry.lineage.LineageRecorder`)
    additionally records each item's EXPECTED position: ventilation order is
    the fold order of the chained order digest, which is why the digest is
    identical across pools whose completion order is not."""
    def ventilate(**kwargs):
        piece = kwargs.get('piece_index')
        if trace_enabled() and piece is not None:
            trace_instant('ventilate',
                          ctx=(int(kwargs.get('epoch_index', 0)),
                               int(piece), 0))
        if lineage is not None and piece is not None:
            lineage.expect(int(kwargs.get('epoch_index', 0)), int(piece),
                           int(kwargs['shuffle_row_drop_partition'][0]),
                           str(kwargs.get('fragment_path', '')),
                           kwargs.get('row_group_id'),
                           kwargs.get('row_range'))
        pool_ventilate(**kwargs)
    return ventilate


def _make_hang_stand_in_factory(ngram):
    """Build the pool's hang-quarantine hook (docs/robustness.md): maps a
    reaped item's ventilated kwargs to the empty stand-in batch (row or NGram
    shape) carrying its ``QuarantineRecord(reason='hang')``."""
    def factory(item_kwargs, elapsed_s):
        from petastorm_tpu.resilience import QuarantineRecord
        epoch = int(item_kwargs.get('epoch_index', 0))
        piece_index = int(item_kwargs['piece_index'])
        item_id = (epoch, piece_index,
                   item_kwargs['shuffle_row_drop_partition'][0])
        record = QuarantineRecord(
            piece_index=piece_index,
            fragment_path=item_kwargs.get('fragment_path', ''),
            row_group_id=item_kwargs.get('row_group_id'),
            error_type='WorkerHangError',
            error='no result after {:.3g}s; the worker holding this rowgroup '
                  'was reaped by the watchdog'.format(elapsed_s),
            attempts=1, epoch=epoch, reason='hang')
        # anomaly marker (consumer side — the hung worker can't publish one)
        trace_instant('quarantine', ctx=(epoch, piece_index, 0),
                      args={'reason': 'hang',
                            'elapsed_s': round(elapsed_s, 3)})
        if ngram is not None:
            from petastorm_tpu.ngram_worker import NGramWindows
            return NGramWindows({}, np.empty(0, np.int64), item_id=item_id,
                                quarantine=record)
        return ColumnarBatch({}, 0, item_id=item_id, quarantine=record)
    return factory


def _slice_batch(batch, start):
    """Drop the first ``start`` rows of a ColumnarBatch (row-cursor fast-forward)."""
    from petastorm_tpu.reader_worker import ColumnarBatch
    n = max(batch.num_rows - start, 0)
    return ColumnarBatch({name: col[start:] for name, col in batch.columns.items()},
                         n, item_id=batch.item_id)


def _apply_field_overrides(schema, field_overrides):
    by_name = {f.name: f for f in field_overrides}
    unknown = sorted(set(by_name) - set(schema.fields))
    if unknown:
        raise ValueError('field_overrides name fields not in the schema: {}'
                         .format(unknown))
    return Unischema(schema.name,
                     [by_name.get(name, field) for name, field in schema.fields.items()])


def _is_ngram(schema_fields):
    from petastorm_tpu.ngram import NGram
    return isinstance(schema_fields, NGram)


def _eval_partition_predicate(predicate, row_group):
    values = {name: value for name, value in row_group.partition_keys.items()}
    return bool(predicate.do_include(values))


# ---------------------------------------------------------------------------
# Results-queue readers (reference: py_dict_reader_worker.py:66-99,
# arrow_reader_worker.py:31-88)
# ---------------------------------------------------------------------------

class _RowResultsReader(object):
    """Buffers a ColumnarBatch and pops one namedtuple per read (row-at-a-time API).

    Hot loop: rows are emitted positionally (``namedtuple._make`` over columns
    pre-ordered once per batch) — profiling shows dict-based per-row assembly costs
    ~4x the actual decode at small row sizes.

    Consumption accounting is row-exact: ``on_batch`` is invoked only once the LAST row
    of a batch has been emitted (not when the batch is popped off the queue), so a
    checkpoint taken mid-batch leaves the item unconsumed and :meth:`cursor` pinpoints
    the resume row. ``fast_forward`` maps ``item_id -> start_row`` for replaying such a
    cursor: the matching batch starts emitting at ``start_row`` instead of 0."""

    def __init__(self, result_schema, on_batch=None, fast_forward=None):
        self._namedtuple = result_schema.namedtuple
        self._field_names = list(result_schema.fields)
        self._on_batch = on_batch
        self._fast_forward = dict(fast_forward or {})
        self._columns = None
        self._num_rows = 0
        self._next_row = 0
        self._current_batch = None

    def read_next(self, pool):
        while self._columns is None or self._next_row >= self._num_rows:
            batch = pool.get_results()
            item_id = getattr(batch, 'item_id', None)
            start_row = self._fast_forward.pop(item_id, 0) if item_id is not None else 0
            if batch.num_rows == 0 or start_row >= batch.num_rows:
                # Nothing (left) to emit: consumed the moment it is popped.
                if self._on_batch is not None:
                    self._on_batch(batch)
                self._columns = None
                continue
            self._columns = [batch.columns[name] for name in self._field_names]
            self._num_rows = batch.num_rows
            self._next_row = start_row
            self._current_batch = batch
        i = self._next_row
        self._next_row = i + 1
        if self._next_row >= self._num_rows and self._on_batch is not None:
            # Acknowledge consumption only now that every row has been emitted
            # (at-least-once semantics; ADVICE.md round 1).
            self._on_batch(self._current_batch)
        return self._namedtuple._make([col[i] for col in self._columns])

    def cursor(self):
        """``(item_id, next_row)`` of the partially-emitted buffered batch, or None."""
        if self._columns is not None and self._next_row < self._num_rows:
            item_id = getattr(self._current_batch, 'item_id', None)
            if item_id is not None:
                return item_id, self._next_row
        return None

    def reset(self):
        self._columns = None
        self._num_rows = 0
        self._next_row = 0
        self._current_batch = None


class _BatchResultsReader(object):
    """Emits one namedtuple-of-arrays per rowgroup batch. A ``fast_forward`` map (from a
    row-path checkpoint's ``row_cursor``) slices the matching batch so already-emitted
    rows are not re-delivered."""

    def __init__(self, result_schema, on_batch=None, fast_forward=None):
        self._schema = result_schema
        self._on_batch = on_batch
        self._fast_forward = fast_forward if fast_forward is not None else {}

    def read_next(self, pool):
        while True:
            batch = pool.get_results()
            if self._on_batch is not None:
                self._on_batch(batch)
            if self._fast_forward and batch.item_id is not None:
                start = self._fast_forward.pop(batch.item_id, 0)
                if start:
                    batch = _slice_batch(batch, start)
            if batch.num_rows:
                # restrict to schema fields: ship-raw batches carry auxiliary
                # __hw/__enc columns the namedtuple has no slots for
                return self._schema.make_namedtuple(
                    **{name: batch.columns[name] for name in self._schema.fields})

    def reset(self):
        pass


class _NGramResultsReader(object):
    """Buffers a columnar NGramWindows payload and emits one {offset: namedtuple} per
    read, gathering rows lazily from the shared columns (no per-row dict
    materialization on the hot path).

    Checkpoint contract mirrors :class:`_RowResultsReader` with the window as the
    row unit: ``on_batch`` acknowledges a payload only once its LAST window has been
    emitted (zero-window payloads acknowledge on pop), ``cursor()`` pinpoints a
    partially-emitted payload's next window, and ``fast_forward`` replays a resumed
    payload from that window (window-exact when the per-piece shuffle is seeded)."""

    def __init__(self, result_schema, ngram, on_batch=None, fast_forward=None):
        self._ngram = ngram
        self._on_batch = on_batch
        self._fast_forward = dict(fast_forward or {})
        self._payload = None
        self._plan = None
        self._plan_columns = None
        self._next = 0

    def read_next(self, pool):
        while self._payload is None or self._next >= len(self._payload.starts):
            payload = pool.get_results()
            item_id = getattr(payload, 'item_id', None)
            start = self._fast_forward.pop(item_id, 0) if item_id is not None else 0
            if not len(payload.starts) or start >= len(payload.starts):
                # Nothing (left) to emit: consumed the moment it is popped.
                if self._on_batch is not None:
                    self._on_batch(payload)
                self._payload = None
                continue
            self._payload = payload
            self._next = start
            columns_key = frozenset(self._payload.columns)
            if columns_key != self._plan_columns:
                # one plan per column set (constant per reader) — not per window
                self._plan = self._ngram.window_plan(columns_key)
                self._plan_columns = columns_key
        start = self._payload.starts[self._next]
        self._next += 1
        if self._next >= len(self._payload.starts) and self._on_batch is not None:
            # Acknowledge only now that every window has been emitted
            # (at-least-once semantics, same as the row path).
            self._on_batch(self._payload)
        return self._ngram.window_from_plan(self._payload.columns, start, self._plan)

    def cursor(self):
        """``(item_id, next_window)`` of the partially-emitted payload, or None."""
        if self._payload is not None and self._next < len(self._payload.starts):
            item_id = getattr(self._payload, 'item_id', None)
            if item_id is not None:
                return item_id, self._next
        return None

    def reset(self):
        self._payload = None
        self._next = 0
