"""JaxDataLoader: reader -> mesh-sharded ``jax.Array`` batches with double-buffered
host->device transfer and input-stall instrumentation.

This is the TPU-native flagship adapter (the role petastorm/pytorch.py:126-496 plays for
torch), designed per SURVEY.md §7.1 item 5:

- batches are assembled columnar on the host (numpy), optionally through a seeded
  shuffling buffer (the reference's shuffling-queue semantics, pytorch.py:178-186);
- each batch becomes a pytree of globally-sharded ``jax.Array`` via
  ``jax.make_array_from_process_local_data`` over an arbitrary ``PartitionSpec`` — batch
  axis DP by default, but any TP/SP layout is accepted (SURVEY.md §2.8);
- a background producer thread keeps ``prefetch`` batches in flight so host IO/decode and
  H2D transfer overlap device compute (double buffering);
- ``stats.input_stall_fraction`` measures the time the consumer blocked waiting on the
  input pipeline — the BASELINE.md north-star metric — from inside the loader, where
  async dispatch can't hide it.
"""

import collections
import queue
import sys
import threading
import time
import warnings

import numpy as np
from jax.profiler import TraceAnnotation

from petastorm_tpu.telemetry import tracing as _flight
from petastorm_tpu.parallel.shuffling_buffer import (NoopShufflingBuffer,
                                                     RandomShufflingBuffer)

_END = object()
#: scan_stream keeps this many compiled (step_fn, chunk-shape) programs per loader
_SCAN_STREAM_CACHE_MAX = 8


def _trace_span(name):
    """jax.profiler annotation so loader stages show up in device traces next to the
    XLA ops they feed (SURVEY.md §5.1: the TPU-native replacement for the reference's
    per-thread cProfile)."""
    return TraceAnnotation(name)


class LoaderStats(object):
    """Thread-safe loader counters (batches/rows, wait vs total time); the input
    stall fraction ``wait_time_s / total_time_s`` is the bench's efficiency
    metric. Mutation happens through :meth:`add` (deltas) and :meth:`mirror`
    (absolute values) under one internal lock — the loader writes from BOTH its
    consumer thread (per-batch accounting) and its producer thread (reader-stat
    mirroring), so bare ``stats.field += 1`` would lose updates under the race.
    ``as_dict`` snapshots every field under the same lock (one consistent view).
    ``per_field_uploads`` counts batches uploaded field by field (every
    ``device_put`` batch, mesh path included).

    ``io_retries`` / ``rowgroups_quarantined`` mirror the reader's resilience
    counters (docs/robustness.md) into the loader's own stats surface: a training
    job that only watches ``LoaderStats`` still sees degradation — a non-zero
    quarantine count means the epoch silently served fewer rowgroups.

    The zero-copy data-plane counters mirror the same way (docs/performance.md):
    ``cache_hits``/``cache_misses`` (decoded-rowgroup cache; a warm epoch should be
    all hits), ``shm_batches``/``shm_fallback_batches`` (which transport the process
    pool's results actually took) and ``wire_bytes_copied_per_batch`` (bytes
    materialized into new host memory per result batch — the number the shm ring
    exists to shrink; a true running mean from the pool's ``wire_bytes_copied``
    histogram, so multi-pool and mixed-transport runs report the stream-wide
    mean, not the last pool's last value).

    Device-resident decode tail (docs/performance.md): ``device_decode_batches``
    counts batches whose raw-shipped fields decoded as device kernels;
    ``device_fallback_batches`` counts chunks whose device fields decoded on
    the host instead (CPU backend, ``device_put=False``, or a per-field
    fallback) — a capture can PROVE which path ran."""

    _FIELDS = ('batches', 'rows', 'wait_time_s', 'total_time_s',
               'per_field_uploads', 'io_retries',
               'rowgroups_quarantined', 'cache_hits', 'cache_misses',
               'shm_batches', 'shm_fallback_batches',
               'wire_bytes_copied_per_batch', 'device_decode_batches',
               'device_fallback_batches')

    def __init__(self):
        self._lock = threading.Lock()
        self.batches = 0
        self.rows = 0
        self.wait_time_s = 0.0
        self.total_time_s = 0.0
        self.per_field_uploads = 0
        self.io_retries = 0
        self.rowgroups_quarantined = 0
        self.cache_hits = 0
        self.cache_misses = 0
        self.shm_batches = 0
        self.shm_fallback_batches = 0
        self.wire_bytes_copied_per_batch = 0.0
        self.device_decode_batches = 0
        self.device_fallback_batches = 0

    def add(self, **deltas):
        """Add keyword deltas to counter fields atomically (one lock hold)."""
        with self._lock:
            for name, delta in deltas.items():
                if name not in self._FIELDS:
                    raise AttributeError('unknown LoaderStats field {!r}'
                                         .format(name))
                setattr(self, name, getattr(self, name) + delta)

    def mirror(self, **values):
        """Set absolute values for mirrored counters atomically (reader/pool
        counters copied into the loader surface)."""
        with self._lock:
            for name, value in values.items():
                if name not in self._FIELDS:
                    raise AttributeError('unknown LoaderStats field {!r}'
                                         .format(name))
                setattr(self, name, value)

    @property
    def input_stall_fraction(self):
        with self._lock:
            if self.total_time_s <= 0:
                return 0.0
            return min(1.0, self.wait_time_s / self.total_time_s)

    def as_dict(self):
        with self._lock:
            snapshot = {name: getattr(self, name) for name in self._FIELDS}
        stall = (min(1.0, snapshot['wait_time_s'] / snapshot['total_time_s'])
                 if snapshot['total_time_s'] > 0 else 0.0)
        snapshot['wait_time_s'] = round(snapshot['wait_time_s'], 4)
        snapshot['total_time_s'] = round(snapshot['total_time_s'], 4)
        snapshot['input_stall_fraction'] = round(stall, 4)
        return snapshot


class JaxDataLoader(object):
    """Iterates pytrees (dicts) of device-sharded arrays assembled from a Reader.

    :param reader: a petastorm_tpu Reader (row, batched, or NGram). An NGram reader
        yields sequence batches: each window field arrives as
        ``(batch, ngram.length, *field_shape)`` (windows are the batch axis — shuffle
        buffer, padding and sharding all operate on windows), ready for
        ``partition_spec={'field': PartitionSpec('data', 'seq')}`` sequence sharding.
        Delivery accounting counts windows, so ``state_dict`` checkpoints NGram
        streams exactly like row streams (VERDICT r3 item 4).
    :param batch_size: rows per emitted batch **on this host**. With a multi-host mesh the
        global batch is ``batch_size * jax.process_count()``.
    :param mesh: optional ``jax.sharding.Mesh``; None = single default device.
    :param partition_spec: ``PartitionSpec`` for every batch array (default: batch axis
        over the mesh's first axis), or a dict ``{field: PartitionSpec}`` — named fields
        get their spec, the rest the batch-axis default. Accepts any layout for TP/SP
        consumers (e.g. ``{'tokens': P('data', 'seq')}`` for sequence-sharded batches).
    :param shuffling_queue_capacity: >0 enables a RandomShufflingBuffer of that capacity.
    :param min_after_retrieve: decorrelation floor (default capacity//2).
    :param pad_ragged: {field: padded_shape_tuple} — ragged fields are zero-padded to the
        given per-row shape and an ``<field>_len`` int32 column is emitted. Required for
        any variable-shape field reaching the device (XLA static shapes;
        SURVEY.md §7.3 pad-and-mask).
    :param prefetch: device batches kept in flight (2 = double buffering).
    :param drop_last: drop the final partial batch (keeps shapes static under jit).
    :param device_put: False returns host numpy batches (debugging / CPU consumers).
    :param device_transforms: ``{field: DeviceTransform}`` on-device augment
        chains (crop/flip/normalize) for raw-shipped image fields — requires a
        reader built with ``device_decode_fields`` (docs/performance.md
        "Device-resident decode tail").
    :param device_buffer_depth: device batches the decode tail may dispatch
        ahead of the train step (the prefetch-to-device ring; only meaningful
        with ``device_decode_fields``).
    :param metrics_port: attach a live scrape endpoint over
        :meth:`telemetry_snapshot` (``/metrics`` Prometheus text with the SLO
        gauges refreshed per scrape, ``/healthz``, ``/vars``); ``0`` binds an
        ephemeral port (``metrics_url`` names it), None (default) serves
        nothing — docs/observability.md "Live metrics plane".
    :param slo_policy: the input-efficiency SLO evaluated by
        :meth:`efficiency_report` (an
        :class:`~petastorm_tpu.telemetry.slo.SloPolicy`, a float target, or
        None = the default 0.9 target).
    :param incidents: arm the incident autopsy plane at the loader layer
        (``True`` or an
        :class:`~petastorm_tpu.telemetry.incident.IncidentPolicy`) — an SLO
        breach of the WHOLE pipeline (training loop starved) or a breaker
        trip captures a black-box bundle over the merged loader+reader
        telemetry; when the reader already carries its own recorder
        (``make_reader(incidents=...)``) the loader reuses it instead of
        building a second one — docs/observability.md "Incident autopsy
        plane".
    :param history: arm the longitudinal observatory at the loader layer
        (docs/observability.md "Longitudinal observatory"): one ``owner:
        'loader'`` run record (whole-pipeline rows/s, efficiency, stage
        shares) is appended at :meth:`stop`, and a live regression sentinel
        watches the training loop's own rows/s + wait-share series, firing a
        ``perf_regression`` incident on a mid-run collapse. ``True``
        (default policy), a store path string, or a
        :class:`~petastorm_tpu.telemetry.history.HistoryPolicy`. With no
        explicit path the loader records into the reader's store
        (``make_reader(history=...)``); ``True`` with an unarmed reader
        warns and disables (the loader has no dataset home of its own).
    """

    def __init__(self, reader, batch_size, mesh=None, partition_spec=None,
                 shuffling_queue_capacity=0, min_after_retrieve=None, seed=None,
                 pad_ragged=None, prefetch=2, drop_last=True, device_put=True,
                 device_transforms=None,
                 device_buffer_depth=2, metrics_port=None, slo_policy=None,
                 incidents=None, history=None):
        if batch_size < 1:
            raise ValueError('batch_size must be >= 1')
        self.reader = reader
        self.batch_size = batch_size
        self.stats = LoaderStats()
        # Loader-side stage telemetry (docs/observability.md): shuffle_wait /
        # collate / h2d histograms; telemetry_snapshot() merges in the reader's
        # cross-process view. PETASTORM_TPU_TELEMETRY_JSONL streams periodic
        # snapshots from the consumer loop.
        from petastorm_tpu.telemetry import MetricsRegistry
        from petastorm_tpu.telemetry.export import logger_from_env
        self.telemetry = MetricsRegistry()
        self._telemetry_jsonl = logger_from_env()
        # Input-efficiency SLO over the whole pipeline (docs/observability.md
        # "Efficiency SLOs"): shuffle_wait is the loader's primary starvation
        # stage; breach events are edge-triggered inside the tracker and ride
        # the loader's JSONL log when one is armed.
        from petastorm_tpu.telemetry.slo import (SloTracker,
                                                 resolve_slo_policy, slo_clock)
        self._started_at = slo_clock()
        self._slo = SloTracker(resolve_slo_policy(slo_policy),
                               jsonl=self._telemetry_jsonl)
        self._metrics_server = None
        self._mesh = mesh
        self._partition_spec = partition_spec
        self._pad_ragged = dict(pad_ragged or {})
        self._prefetch = max(1, prefetch)
        self._drop_last = drop_last
        self._device_put = device_put
        self._seed = seed
        self._shuffling_queue_capacity = shuffling_queue_capacity
        self._min_after_retrieve = min_after_retrieve
        self._sharding = None
        self._in_iter = False
        self._error = None
        self._queue = None
        self._producer = None
        self._stop_event = threading.Event()
        # Delivery-exact checkpoint accounting: the producer appends
        # [item_id, rows_pending] per reader chunk (FIFO order == emission order for the
        # no-shuffle path); the consumer decrements as batches are yielded and marks an
        # item delivered only when every one of its rows reached the training loop.
        self._delivery_fifo = collections.deque()
        self._fifo_lock = threading.Lock()
        self._delivery_supported = None
        self._epochs_delivered = 0
        self._delivered_by_epoch = {}
        self._spec_keys_checked = False
        self._scan_stream_used = False
        # Sample-lineage step stamping (docs/observability.md "Sample
        # lineage"): the reader's recorder (when armed) learns which
        # training step each manifest record lands under — cumulative across
        # re-iterations, like stats.batches.
        self._lineage_steps = 0
        self._scan_stream_programs = {}
        self._scan_stream_cache_warned = False
        # Device-resident decode tail (docs/performance.md): when the reader
        # ships raw codec payloads, this stage finishes decode (and augment)
        # as jitted device kernels after the upload; on CPU backends it
        # decodes on the host byte-identically.
        self._device_buffer_depth = max(1, int(device_buffer_depth))
        device_fields = frozenset(getattr(reader, 'device_decode_fields', None)
                                  or ())
        if device_fields:
            from petastorm_tpu.parallel.device_stage import DeviceDecodeStage
            self._device_stage = DeviceDecodeStage(reader, device_transforms,
                                                   device_buffer_depth,
                                                   device_put)
        else:
            if device_transforms:
                raise ValueError('device_transforms requires a reader built '
                                 'with device_decode_fields')
            self._device_stage = None
        # Closed-loop autotuning (docs/autotuning.md): when the reader carries
        # a controller (make_reader(autotune=...)), contribute the loader's
        # own knob — the shuffle-buffer fill threshold — to its catalog so the
        # one controller tunes the whole pipeline. _active_buffer hands the
        # live buffer to the knob's apply.
        self._active_buffer = None
        controller = getattr(reader, '_autotune', None)
        if controller is not None:
            from petastorm_tpu.autotune.knobs import build_loader_knobs
            for knob in build_loader_knobs(self):
                controller.catalog.add(knob)
        # Incident autopsy plane (docs/observability.md "Incident autopsy
        # plane"): a reader-owned recorder is reused (the loader's SLO edge
        # joins its triggers); otherwise incidents= builds a loader-owned one
        # over the merged whole-pipeline evidence.
        from petastorm_tpu.telemetry.incident import resolve_incident_policy
        self._incidents = getattr(reader, '_incidents', None)
        self._owns_incidents = False
        incident_policy = resolve_incident_policy(incidents)
        if incident_policy is not None and self._incidents is None:
            from petastorm_tpu.resilience import default_board
            from petastorm_tpu.telemetry.incident import (
                IncidentRecorder, default_incident_home)
            self._incidents = IncidentRecorder(
                default_incident_home(None), incident_policy,
                registry=self.telemetry)
            self._owns_incidents = True
            self._incidents.add_source('metrics', self.telemetry_snapshot)
            self._incidents.add_source(
                'slo', lambda: self._evaluate_slo(self.telemetry_snapshot()))
            self._incidents.add_source(
                'config', lambda: {'batch_size': self.batch_size,
                                   'prefetch': self._prefetch,
                                   'drop_last': self._drop_last,
                                   'reader': type(reader).__name__})
            default_board().observe_transitions(
                self._incidents.on_breaker_transition)
        if self._incidents is not None:
            self._slo.observe_breaches(self._on_slo_breach)
        # Longitudinal observatory at the loader layer (docs/observability.md
        # "Longitudinal observatory"): an owner='loader' run record of the
        # WHOLE pipeline at stop(), plus a loader-side regression sentinel
        # over the training loop's own goodput series. With no explicit path
        # the record lands in the reader's store (same journal, two owners).
        from petastorm_tpu.telemetry.history import resolve_history_policy
        self._history = None
        self._history_policy = resolve_history_policy(history)
        self._history_written = False
        self._sentinel = None
        if self._history_policy is not None:
            from petastorm_tpu.telemetry.history import RunHistorian
            from petastorm_tpu.telemetry.sentinel import (
                RegressionSentinel, resolve_sentinel_policy)
            history_path = self._history_policy.path
            if history_path is None:
                reader_history = getattr(reader, '_history', None)
                history_path = getattr(reader_history, 'path', None)
            if history_path is not None:
                self._history = RunHistorian(history_path,
                                             self._history_policy,
                                             registry=self.telemetry)
            else:
                warnings.warn(
                    'JaxDataLoader(history=...) has no store path: pass a '
                    'path/HistoryPolicy(path=...), or arm the reader with '
                    'make_reader(history=...) so the loader can record into '
                    'its store — recording disabled for this run')
            sentinel_policy = resolve_sentinel_policy(
                self._history_policy.sentinel)
            if sentinel_policy is not None:
                self._sentinel = RegressionSentinel(
                    sentinel_policy, owner='loader',
                    registry=self.telemetry, incidents=self._incidents,
                    dataset_token=getattr(reader, 'dataset_token', None))
                if (self._incidents is not None
                        and getattr(reader, '_sentinel', None) is None):
                    # the bundle's 'sentinel' evidence slot belongs to the
                    # reader's sentinel when one is armed there
                    self._incidents.add_source('sentinel',
                                               self._sentinel.report)
        # Live metrics plane (docs/observability.md): one scrape endpoint
        # over the whole-pipeline snapshot; closed by stop(). Started LAST —
        # a constructor raise after binding would leak the port and serve a
        # half-built loader (same ordering contract as Reader.__init__).
        if metrics_port is not None:
            from petastorm_tpu.telemetry.http_exporter import MetricsHttpServer
            self._metrics_server = MetricsHttpServer(
                snapshot_fn=self._scrape_snapshot,
                health_fn=lambda: {'batches': self.stats.batches,
                                   'rows': self.stats.rows},
                port=int(metrics_port))
            self._metrics_server.start()

    # ------------------------------------------------------------------ sharding

    def _resolve_sharding(self):
        return resolve_sharding(self._mesh, self._partition_spec, self._device_put)

    # ------------------------------------------------------------------ iteration

    def __iter__(self):
        if self._in_iter:
            raise RuntimeError('Concurrent iteration of a JaxDataLoader is not allowed '
                               '(reference semantics: pytorch.py:98-123)')
        if self._producer is not None and self._producer.is_alive():
            # Previous iteration broken off early: stop and JOIN the old producer before
            # touching queue/stop state, or it would write stale batches into the new
            # iteration's queue.
            self._stop_event.set()
            self._drain_queue()
            self._producer.join(timeout=30)
            if self._producer.is_alive():
                raise RuntimeError('Previous producer thread did not stop')
        if self.stats.batches and getattr(self.reader, 'last_row_consumed', False):
            # Re-iteration after full consumption: reset the reader like the reference's
            # LoaderBase (pytorch.py:104-123).
            self.reader.reset()
        self._in_iter = True
        self._error = None
        # Fresh Event per iteration: a (joined or straggling) old producer keeps its own
        # already-set event and can never interfere with the new run.
        self._stop_event = threading.Event()
        self._queue = queue.Queue(self._prefetch)
        self._sharding = self._resolve_sharding()
        # Stale pending entries from an abandoned previous iteration reference a dead
        # stream; dropping them leaves their items undelivered, so a resume re-serves
        # those rows instead of losing them.
        self._delivery_fifo.clear()
        self._producer = threading.Thread(target=self._produce,
                                          args=(self._queue, self._stop_event),
                                          daemon=True,
                                          name='petastorm-tpu-loader-producer')
        self._producer.start()
        try:
            last_emit = time.monotonic()
            while True:
                wait_start = time.monotonic()
                with _trace_span('petastorm_tpu.loader.wait_input'):
                    item = self._queue.get()
                now = time.monotonic()
                if item is _END:
                    if self._error is not None:
                        raise self._error
                    self._mark_delivered(None)  # drop_last / buffer-drain leftovers
                    return
                batch, local_rows = item
                self.stats.add(wait_time_s=now - wait_start,
                               total_time_s=now - last_emit,
                               batches=1, rows=local_rows)
                # shuffle_wait: time the training loop sat blocked on the input
                # pipeline for this batch — the stage the stall fraction sums
                # (clocked on monotonic, so the timeline leg back-dates)
                self.observe_traced('shuffle_wait', now - wait_start)
                if self._telemetry_jsonl is not None and self._telemetry_jsonl.due():
                    # one snapshot serves both legs: the periodic interval
                    # line AND the SLO evaluation (whose ok->breach
                    # transition appends its own slo_breach line; the
                    # regression sentinel rides the same evaluation)
                    snapshot = self.telemetry_snapshot()
                    self._evaluate_slo(snapshot)
                    self._telemetry_jsonl.emit(snapshot,
                                               event='loader_interval')
                elif self._sentinel is not None:
                    # no JSONL armed: the sentinel still needs its windows —
                    # one float compare per batch between them
                    from petastorm_tpu.telemetry.slo import slo_clock
                    if self._sentinel.due(slo_clock() - self._started_at):
                        self._evaluate_slo(self.telemetry_snapshot())
                last_emit = now
                self._mark_delivered(local_rows)
                self._lineage_steps += 1
                lineage = getattr(self.reader, '_lineage', None)
                if lineage is not None:
                    # step-stamp the audit plane: manifest records written
                    # from here on carry this training step
                    lineage.stamp_step(self._lineage_steps)
                yield batch
        finally:
            self._stop_event.set()
            self._in_iter = False
            self._drain_queue()

    def _drain_queue(self, _empty=queue.Empty, _is_finalizing=sys.is_finalizing):
        # Bound at definition time and guarded: this runs from generator finalizers,
        # which can fire during interpreter shutdown after module globals (ours AND
        # the stdlib queue module's Empty) are cleared — `raise Empty` inside
        # queue.get then raises TypeError. Draining is pointless at shutdown anyway.
        if self._queue is None or _is_finalizing():
            return
        try:
            while True:
                self._queue.get_nowait()
        except _empty:
            pass

    # ------------------------------------------------------------------ producer

    def _make_buffer(self):
        if self._shuffling_queue_capacity and self._shuffling_queue_capacity > 0:
            min_after = self._min_after_retrieve
            if min_after is None:
                min_after = self._shuffling_queue_capacity // 2
            return RandomShufflingBuffer(self._shuffling_queue_capacity, min_after,
                                         seed=self._seed)
        return NoopShufflingBuffer()

    def _produce(self, out_queue, stop_event):
        try:
            buffer = self._make_buffer()
            self._active_buffer = buffer
            for columns in self._reader_chunks():
                # Feed the buffer in batch_size slices so a whole-rowgroup chunk (the
                # iter_columnar fast path) cannot blow past the shuffling buffer's
                # configured capacity; slices of ndarrays are views, so this is cheap.
                for part in _iter_column_slices(columns, self.batch_size):
                    buffer.add_many(part)
                    while buffer.can_retrieve(self.batch_size):
                        if stop_event.is_set():
                            return
                        self._emit(buffer.retrieve(self.batch_size), out_queue, stop_event)
                if stop_event.is_set():
                    return
            buffer.finish()
            while buffer.can_retrieve(self.batch_size) and not stop_event.is_set():
                batch = buffer.retrieve(self.batch_size)
                if self._batch_cols_rows(batch) < self.batch_size and self._drop_last:
                    break
                self._emit(batch, out_queue, stop_event)
        except Exception as exc:  # noqa: BLE001 - surface in consumer
            if not stop_event.is_set():
                self._error = exc
        finally:
            self._put(_END, out_queue, stop_event)

    @staticmethod
    def _batch_cols_rows(columns):
        from petastorm_tpu.workers.serializers import _columns_num_rows
        return _columns_num_rows(columns)

    def _reader_chunks(self):
        """Yield sanitized columnar chunks from the reader, tracking delivery when the
        columnar fast path provides item identity."""
        try:
            for columns, num_rows, item_id in iter_reader_chunks(
                    self.reader, accum_rows=self.batch_size, include_empty=True):
                if item_id is None:
                    self._delivery_supported = False
                else:
                    self._delivery_supported = self._delivery_supported is not False
                    with self._fifo_lock:
                        self._delivery_fifo.append([item_id, num_rows])
                if num_rows:
                    yield self._sanitize(columns)
        finally:
            self._sync_resilience_stats()

    def _sync_resilience_stats(self):
        """Mirror the reader's retry/quarantine counters — and the zero-copy
        data-plane counters (cache hits, shm transport, wire bytes copied) — into
        LoaderStats so training jobs watching only the loader still see input
        degradation (docs/robustness.md, docs/performance.md)."""
        mirrored = {}
        retries = getattr(self.reader, 'io_retries', None)
        if retries is not None:
            mirrored['io_retries'] = retries
        ledger = getattr(self.reader, 'quarantine', None)
        if ledger is not None:
            mirrored['rowgroups_quarantined'] = len(ledger)
        try:
            diag = getattr(self.reader, 'diagnostics', None)
        except Exception:  # noqa: BLE001 - wrapper readers may not expose it
            diag = None
        if isinstance(diag, dict):
            for key in ('cache_hits', 'cache_misses', 'shm_batches',
                        'shm_fallback_batches'):
                if key in diag:
                    mirrored[key] = diag[key]
            # wire_bytes_copied_per_batch: a TRUE running mean over the whole
            # stream, from the pool's wire_bytes_copied histogram (sum/count) —
            # the diagnostics scalar is a last-writer value that misreports
            # multi-pool / mixed-transport runs.
            hist = (diag.get('telemetry', {}).get('histograms', {})
                    .get('wire_bytes_copied'))
            if hist and hist.get('count'):
                mirrored['wire_bytes_copied_per_batch'] = round(
                    float(hist['sum']) / int(hist['count']), 1)
            elif 'wire_bytes_copied_per_batch' in diag:
                mirrored['wire_bytes_copied_per_batch'] = \
                    diag['wire_bytes_copied_per_batch']
        if mirrored:
            self.stats.mirror(**mirrored)

    def _sanitize(self, columns):
        # collate stage: host batch assembly — dtype sanitization + ragged padding
        collate_start = time.perf_counter()
        passthrough = frozenset()
        stage = self._device_stage
        if stage is not None:
            # host-mode device fields decode HERE (before sanitize, so
            # pad_ragged still applies to them); device-mode fields pass
            # through sanitize raw and decode on chip in _emit
            dd_start = time.perf_counter()
            columns, decoded_any = stage.sanitize_decode(columns)
            if decoded_any:
                self.stats.add(device_fallback_batches=1)
                self.observe_traced('device_decode',
                                    time.perf_counter() - dd_start,
                                    start_pc=dd_start)
            passthrough = stage.passthrough_names
        out = sanitize_columns(columns, self._pad_ragged, self._device_put,
                               passthrough=passthrough)
        self.observe_traced('collate', time.perf_counter() - collate_start,
                            start_pc=collate_start)
        return out

    def _emit(self, columns, out_queue, stop_event):
        local_rows = self._batch_cols_rows(columns)
        if self._device_put:
            import jax
            stage = self._device_stage
            recipe = None
            prepare_s = 0.0
            if stage is not None and not stage.host_mode:
                # device decode tail, host half: pack/inflate raw payloads
                # into upload-ready arrays + the static program recipe
                prep_start = time.perf_counter()
                columns, recipe = stage.prepare(columns, self._mesh)
                prepare_s = time.perf_counter() - prep_start
            sharding = self._sharding
            if isinstance(sharding, FieldShardings) and not self._spec_keys_checked:
                self._spec_keys_checked = True
                sharding.check_unused(columns.keys())
            h2d_start = time.perf_counter()
            with _trace_span('petastorm_tpu.loader.h2d'):
                if self._mesh is not None:
                    batch = {name: jax.make_array_from_process_local_data(
                                 sharding_for_field(sharding, name), col)
                             for name, col in columns.items()}
                    self.stats.add(per_field_uploads=1)
                else:
                    batch = jax.device_put(columns, sharding)
                    self.stats.add(per_field_uploads=1)
            self.observe_traced('h2d', time.perf_counter() - h2d_start,
                                start_pc=h2d_start)
            if recipe:
                # device half: the jitted decode+augment program (async
                # dispatch — the train step synchronizes), then the
                # prefetch-to-device ring bound. An EMPTY recipe (every
                # device field host_only, already decoded in _sanitize) must
                # not count as a device decode — the stats contract is that a
                # capture can prove which path ran.
                finish_start = time.perf_counter()
                with _trace_span('petastorm_tpu.loader.device_decode'):
                    batch = stage.finish(batch, recipe)
                self.stats.add(device_decode_batches=1)
                self.observe_traced(
                    'device_decode',
                    prepare_s + time.perf_counter() - finish_start)
                waited = stage.throttle(batch)
                if waited:
                    self.observe_traced('d2d_wait', waited)
            elif (stage is not None and stage.host_mode
                  and stage.has_transforms):
                # host-mode backends still apply the declared augment chains
                # (same jitted math, post-upload) — a CPU fallback run must
                # train on the same data an accelerator run would
                t_start = time.perf_counter()
                batch = stage.apply_transforms(batch)
                self.observe_traced('device_decode',
                                    time.perf_counter() - t_start)
        else:
            batch = columns
        # Host-local row count travels alongside: with a multi-process mesh the device
        # array's leading dim is the GLOBAL batch, but stats and delivery accounting are
        # per-host.
        self._put((batch, local_rows), out_queue, stop_event)

    def _put(self, item, out_queue, stop_event):
        while not stop_event.is_set():
            try:
                out_queue.put(item, timeout=0.1)
                return
            except queue.Full:
                continue
        if item is _END:
            try:
                out_queue.put_nowait(_END)
            except queue.Full:
                pass

    # ------------------------------------------------------------ compiled streaming

    def scan_stream(self, step_fn, carry, chunk_batches=32, seed=None):
        """Stream the reader through compiled chunk programs: accumulate
        ``chunk_batches`` batches of host rows, upload them as ONE transfer, and run
        every train step of the chunk inside ONE ``lax.scan`` dispatch.

        The dispatch-bound streaming configuration for larger-than-HBM datasets: the
        per-batch Python dispatch + small-transfer overhead of ``__iter__`` (which
        dominates small-model streaming, docs/performance.md) collapses to one
        host->device transfer and one XLA program launch per ``chunk_batches``
        batches, while memory stays bounded at one chunk (vs
        ``InMemJaxLoader.scan_epochs``, which needs the whole dataset resident).
        No reference analog (petastorm crosses into Python per batch everywhere).

        Rows are shuffled within each chunk (seeded numpy permutation on the host;
        combine with ``shuffle_row_groups``/``shuffle_rows`` on the reader for
        cross-chunk decorrelation). The trailing partial chunk runs through a
        smaller program of the same structure (one extra compile); the final
        sub-batch-size remainder is dropped (static shapes).

        With a ``mesh`` the chunk uploads as a globally-sharded array — each batch
        inside the scan keeps the loader's ``partition_spec`` sharding (the scan
        axis is replicated), so the compiled chunk program trains dp/sp-sharded
        exactly like the ``__iter__`` path, minus the per-batch dispatch. Run it
        under ``with mesh:`` (or pre-shard the carry) so the carry's shardings
        resolve. ``batch_size`` stays the HOST-local row count with a
        multi-process mesh, matching ``__iter__``.

        :param step_fn: ``step_fn(carry, batch) -> (carry, aux)`` — standard
            ``lax.scan`` body over dicts of ``(batch_size, ...)`` arrays.
        :param carry: initial carry pytree.
        :param chunk_batches: batches per compiled chunk (chunk rows =
            ``chunk_batches * batch_size``).
        :param seed: within-chunk shuffle seed; None disables the in-chunk shuffle.
        :return: ``(carry, aux_chunks)`` — aux stacked per chunk, in stream order.
        """
        import jax
        if self._shuffling_queue_capacity:
            raise ValueError('scan_stream has its own in-chunk shuffle; construct '
                             'the loader with shuffling_queue_capacity=0')
        if chunk_batches < 1:
            raise ValueError('chunk_batches must be >= 1')
        if not self._device_put:
            raise ValueError('scan_stream compiles device programs; it does not '
                             'support device_put=False (use __iter__ for host batches)')
        if not self._drop_last:
            raise ValueError('scan_stream always drops the sub-batch-size remainder '
                             '(static shapes); construct the loader with '
                             'drop_last=True to make that explicit, or use __iter__ '
                             'to see every row')
        if reader_may_be_infinite(self.reader):
            raise ValueError('scan_stream runs to stream end and cannot consume an '
                             'infinite reader (num_epochs=None); give the reader a '
                             'finite num_epochs and call scan_stream per pass')
        if self._device_stage is not None and (
                not self._device_stage.host_mode
                or self._device_stage.has_transforms):
            raise ValueError('scan_stream does not support on-accelerator '
                             'device_decode_fields (raw payloads cannot pack '
                             'into chunk programs) or device_transforms (the '
                             'chunk path has no augment stage — silently '
                             'training un-augmented would be worse than '
                             'refusing); use __iter__, or on a CPU backend '
                             'drop the transforms')
        if self._in_iter:
            raise RuntimeError('scan_stream cannot run while __iter__ is active: '
                               'both would consume the same reader')
        if self._producer is not None and self._producer.is_alive():
            # An abandoned __iter__ left its producer prefetching from the reader:
            # stop and join it, exactly like a fresh __iter__ would, so the stream
            # has one consumer.
            self._stop_event.set()
            self._drain_queue()
            self._producer.join(timeout=30)
            if self._producer.is_alive():
                raise RuntimeError('Previous producer thread did not stop')
        if getattr(self.reader, 'last_row_consumed', False):
            # Mirror __iter__'s re-iteration contract: a fully consumed reader resets
            # for the next pass — without this, a second scan_stream call would
            # silently return (carry, []) with zero training done.
            self.reader.reset()
        # Chunk arrays carry a leading scan axis: replicate it (PartitionSpec
        # (None, *batch_spec)) so each scan step's batch keeps the loader's batch
        # sharding while every device sees every step of its shard.
        sharding = _chunk_sharding(self._resolve_sharding())
        self._scan_stream_used = True  # bypasses delivery accounting: see state_dict
        batch_size = self.batch_size
        # Program cache on the instance: a fresh per-call dict would re-trace and
        # re-compile the chunk program every call (one call per epoch is the intended
        # pattern), silently billing full XLA compiles to every epoch. Keyed on
        # step_fn IDENTITY — pass a stable function object; fresh closures per call
        # recompile, and past the cap the oldest program is evicted (warned once).
        programs = self._scan_stream_programs

        def run_chunk(carry, columns, n_batches, chunk_index):
            usable = n_batches * batch_size
            if seed is not None:
                perm = np.random.RandomState(
                    (seed + chunk_index) % (2 ** 31)).permutation(usable)
                columns = {name: col[:usable][perm] for name, col in columns.items()}
            else:
                columns = {name: col[:usable] for name, col in columns.items()}
            chunk = {name: np.ascontiguousarray(
                         col.reshape((n_batches, batch_size) + col.shape[1:]))
                     for name, col in columns.items()}
            h2d_start = time.perf_counter()
            with _trace_span('petastorm_tpu.loader.scan_stream.h2d'):
                if self._mesh is not None:
                    # Same upload contract as __iter__'s mesh path: host-local
                    # chunk rows assemble into the global sharded chunk array
                    # (single- and multi-process meshes alike).
                    chunk = {name: jax.make_array_from_process_local_data(
                                 sharding_for_field(sharding, name), col)
                             for name, col in chunk.items()}
                else:
                    chunk = jax.device_put(chunk, sharding)
            self.observe_traced('h2d', time.perf_counter() - h2d_start,
                                start_pc=h2d_start)
            key = (step_fn, n_batches)
            if key not in programs:
                @jax.jit
                def chunk_program(carry, chunk):
                    return jax.lax.scan(step_fn, carry, chunk)
                if len(programs) >= _SCAN_STREAM_CACHE_MAX:
                    # Unbounded growth would pin every evicted closure's captured
                    # scope + compiled executable for the loader's lifetime.
                    if not self._scan_stream_cache_warned:
                        self._scan_stream_cache_warned = True
                        import warnings
                        warnings.warn(
                            'scan_stream compiled more than {} distinct (step_fn, '
                            'chunk-shape) programs; pass a stable step_fn object to '
                            'reuse compilations'.format(_SCAN_STREAM_CACHE_MAX))
                    programs.pop(next(iter(programs)))
                programs[key] = chunk_program
            return programs[key](carry, chunk)

        pending = []
        pending_rows = 0
        chunk_rows = chunk_batches * batch_size
        chunk_index = 0
        aux_chunks = []
        for columns in map(self._sanitize,
                           (c for c, n, _ in iter_reader_chunks(
                                self.reader, accum_rows=batch_size,
                                include_empty=False) if n)):
            pending.append(columns)
            pending_rows += self._batch_cols_rows(columns)
            while pending_rows >= chunk_rows:
                merged = _concat_column_chunks(pending)
                head = {name: col[:chunk_rows] for name, col in merged.items()}
                tail = {name: col[chunk_rows:] for name, col in merged.items()}
                carry, aux = run_chunk(carry, head, chunk_batches, chunk_index)
                aux_chunks.append(aux)
                chunk_index += 1
                pending = [tail]
                pending_rows -= chunk_rows
        if pending_rows >= batch_size:
            merged = _concat_column_chunks(pending)
            carry, aux = run_chunk(carry, merged, pending_rows // batch_size,
                                   chunk_index)
            aux_chunks.append(aux)
        self._sync_resilience_stats()
        return carry, aux_chunks

    # ------------------------------------------------------------------ checkpoint

    def _mark_delivered(self, n_rows):
        """Consumer-thread half of delivery accounting: retire ``n_rows`` from the FIFO
        (``None`` = end of stream: everything still pending was dropped by ``drop_last``
        or drained out of the buffer and will never be served in this run)."""
        fifo = self._delivery_fifo
        remaining = n_rows
        while True:
            with self._fifo_lock:
                if not fifo:
                    break
                head = fifo[0]
                if n_rows is None:
                    take = head[1]
                else:
                    if head[1] > 0 and remaining <= 0:
                        break
                    take = min(head[1], remaining)
                head[1] -= take
                if n_rows is not None:
                    remaining -= take
                if head[1] > 0:
                    break
                fifo.popleft()
            self._note_delivered(head[0])

    def _note_delivered(self, item_id):
        epoch, piece, drop = item_id
        self._delivered_by_epoch.setdefault(epoch, set()).add((piece, drop))
        items_per_epoch = getattr(self.reader, 'items_per_epoch', None)
        if not items_per_epoch:
            return
        while (len(self._delivered_by_epoch.get(self._epochs_delivered, ()))
               >= items_per_epoch):
            del self._delivered_by_epoch[self._epochs_delivered]
            self._epochs_delivered += 1

    def state_dict(self):
        """Delivery-exact resumable read position: an item (rowgroup x drop-partition)
        counts as consumed only once every one of its rows was handed to the training
        loop — rows still inside the prefetch queue, the producer, or a drained buffer
        are NOT counted and will be re-served on resume (at-least-once; a partially
        delivered item is re-read whole). Rebuild the reader with the same arguments
        plus ``resume_state=state`` and wrap it in a fresh loader to continue.

        With a shuffling buffer, emission order differs from ingest order, so per-item
        attribution is only trustworthy when nothing is pending — checkpoint at a stream
        boundary (after the iterator is exhausted) in that case."""
        if self._delivery_supported is False:
            raise ValueError('state_dict requires a Reader with the columnar fast path '
                             '(iter_columnar)')
        if self._scan_stream_used:
            raise ValueError('state_dict is not supported after scan_stream (it '
                             'consumes the reader outside the delivery accounting); '
                             'checkpoint with the __iter__ path instead')
        with self._fifo_lock:
            pending = any(entry[1] > 0 for entry in self._delivery_fifo)
        if pending and self._shuffling_queue_capacity:
            raise ValueError('With a shuffling buffer the loader cannot attribute '
                             'in-flight rows to work items; checkpoint after the '
                             'iterator is exhausted (epoch boundary) instead')
        items_per_epoch = getattr(self.reader, 'items_per_epoch', None)
        if items_per_epoch is None:
            raise ValueError('Reader does not support checkpointing')
        return {
            'version': 1,
            'items_per_epoch': items_per_epoch,
            'epochs_consumed': self._epochs_delivered,
            'consumed_by_epoch': {
                epoch - self._epochs_delivered: sorted(ids)
                for epoch, ids in self._delivered_by_epoch.items()},
        }

    # -------------------------------------------------------------- runtime knobs

    def set_prefetch(self, depth):
        """Runtime-adjust the prefetch queue depth (the autotune knob surface,
        docs/autotuning.md): applied to the LIVE queue — ``maxsize`` moves
        under the queue's own mutex and parked producers are woken, so a raise
        takes effect immediately and a shrink drains as the consumer pops.
        Returns the applied value."""
        depth = max(1, int(depth))
        self._prefetch = depth
        out_queue = self._queue
        if out_queue is not None:
            with out_queue.mutex:
                out_queue.maxsize = depth
                out_queue.not_full.notify_all()
        return depth

    @property
    def prefetch(self):
        """The current prefetch queue depth."""
        return self._prefetch

    def set_device_buffer_depth(self, depth):
        """Runtime-adjust the device decode tail's prefetch-to-device ring
        depth (autotune knob; no-op clamp when the loader has no device
        stage). Returns the applied value."""
        stage = self._device_stage
        if stage is None:
            return max(1, int(depth))
        return stage.set_depth(depth)

    @property
    def device_buffer_depth(self):
        """The device decode tail's ring depth (construction value when no
        stage exists)."""
        stage = self._device_stage
        if stage is None:
            return self._device_buffer_depth
        return stage.depth

    # ------------------------------------------------------------------ telemetry

    def observe_traced(self, stage, dur_s, start_pc=None):
        """One loader-stage measurement, both legs: the loader's registry
        histogram and (when the flight recorder is armed) a timeline span.
        ``start_pc`` is the ``perf_counter`` start; None back-dates by the
        measured duration (for stages clocked on a different timebase, e.g.
        the monotonic-clocked ``shuffle_wait``). The stage name is validated
        against the spans.py catalog by pipecheck's telemetry-names rule."""
        self.telemetry.observe(stage, dur_s)
        if _flight.trace_enabled():
            if start_pc is None:
                start_pc = time.perf_counter() - dur_s
            _flight.trace_complete(stage, start_pc, dur_s)

    def telemetry_snapshot(self):
        """One JSON-safe telemetry snapshot covering the WHOLE pipeline: the
        loader's own stages (shuffle_wait/collate/h2d) merged with the reader's
        cross-process snapshot (worker stages + pool registry). Feed it to
        ``petastorm_tpu.telemetry.analyze.attribute_bottleneck`` (or the
        ``petastorm-tpu-throughput analyze`` CLI) for the bottleneck report."""
        from petastorm_tpu.telemetry import merge_snapshots
        reader_snapshot_fn = getattr(self.reader, 'telemetry_snapshot', None)
        if reader_snapshot_fn is None:
            return self.telemetry.snapshot()
        return merge_snapshots(self.telemetry.snapshot(), reader_snapshot_fn())

    def _evaluate_slo(self, snapshot):
        from petastorm_tpu.telemetry.slo import slo_clock
        report = self._slo.evaluate(snapshot, slo_clock() - self._started_at,
                                    rows=self.stats.rows,
                                    registry=self.telemetry)
        if self._sentinel is not None:
            # loader-side drift watch over the same cumulative series the
            # SLO report carries (min_window_s enforced by the sentinel)
            self._sentinel.observe(report)
            self._sentinel.export_gauges()
        return report

    def efficiency_report(self):
        """One input-efficiency SLO evaluation over this loader's lifetime
        (docs/observability.md "Efficiency SLOs"): efficiency in [0, 1]
        derived from ``shuffle_wait`` (+ ``d2d_wait``) — the seconds the
        training loop actually sat starved — with goodput-vs-ideal rows/s
        and edge-triggered breach accounting. Evaluated automatically at
        every JSONL interval when ``PETASTORM_TPU_TELEMETRY_JSONL`` is armed,
        and on every ``/metrics`` scrape when ``metrics_port`` is set."""
        return self._evaluate_slo(self.telemetry_snapshot())

    def _scrape_snapshot(self):
        """Per-scrape snapshot: built ONCE, SLO-evaluated, fresh ``slo_*``
        gauges spliced in (same one-snapshot contract as the reader's)."""
        snapshot = self.telemetry_snapshot()
        report = self._evaluate_slo(snapshot)
        gauges = snapshot.setdefault('gauges', {})
        if report['efficiency'] is not None:
            gauges['slo_efficiency'] = report['efficiency']
        gauges['slo_target_efficiency'] = report['target_efficiency']
        if self._sentinel is not None:
            gauges.update(self._sentinel.gauges())
        # the SLO tracker's trailing ring buffer rides the /vars document
        # (a list, not a gauge — the text scrape ignores it)
        snapshot['slo_history'] = report.get('history', [])
        return snapshot

    def _on_slo_breach(self, report):
        """Loader SLO ok→breach edge → one ``slo_breach`` incident (the
        training loop itself sat starved past the target)."""
        if self._incidents is not None:
            self._incidents.trigger(
                'slo_breach',
                args={'efficiency': report.get('efficiency'),
                      'target': report.get('target_efficiency'),
                      'wait_seconds': report.get('wait_seconds'),
                      'layer': 'loader'})

    def incident_report(self):
        """The attached incident recorder's summary (loader-owned or the
        reader's — docs/observability.md "Incident autopsy plane"); None
        when neither layer armed ``incidents``."""
        if self._incidents is None:
            return None
        return self._incidents.report()

    @property
    def metrics_url(self):
        """The live scrape endpoint base URL, or None without
        ``metrics_port`` (docs/observability.md)."""
        if self._metrics_server is None:
            return None
        return self._metrics_server.url

    # ------------------------------------------------------------------ lifecycle

    def _write_history_record(self):
        """Append the loader-layer run record (owner='loader': whole-pipeline
        rows/s + shuffle_wait shares) to the longitudinal store. Idempotent;
        advisory — a run that delivered its batches must not fail over its
        memory."""
        if self._history is None or self._history_written:
            return
        self._history_written = True
        from petastorm_tpu.telemetry.history import (
            build_run_record, fingerprint as _history_fingerprint)
        from petastorm_tpu.telemetry.slo import (efficiency_from_snapshot,
                                                 slo_clock)
        try:
            elapsed = slo_clock() - self._started_at
            snapshot = self.telemetry_snapshot()
            rows = self.stats.rows
            slo_report = efficiency_from_snapshot(snapshot, elapsed,
                                                  rows=rows)
            reader_record = None
            build_reader_record = getattr(self.reader, 'build_history_record',
                                          None)
            if build_reader_record is not None:
                reader_record = build_reader_record()
            fingerprints = dict((reader_record or {}).get('fingerprints', {}))
            fingerprints['loader'] = _history_fingerprint({
                'batch_size': self.batch_size,
                'prefetch': self._prefetch,
                'drop_last': self._drop_last,
                'shuffling_queue_capacity': self._shuffling_queue_capacity,
                'device_stage': self._device_stage is not None})
            record = build_run_record(
                'loader',
                str(getattr(self.reader, 'dataset_token', 'unknown')),
                elapsed, rows, snapshot=snapshot, slo_report=slo_report,
                fingerprints=fingerprints,
                knobs=dict((reader_record or {}).get('knobs', {})),
                incidents=self.incident_report(),
                quarantined=(reader_record or {}).get('quarantined', 0))
            self._history.append(record)
        except Exception:  # noqa: BLE001 - the historian is advisory
            import logging
            logging.getLogger(__name__).warning(
                'could not record this run in the history store',
                exc_info=True)

    def history_report(self):
        """The loader-layer historian's store status; None when built
        without ``history`` (docs/observability.md "Longitudinal
        observatory")."""
        if self._history is None:
            return None
        return self._history.state()

    def stop(self):
        if self._metrics_server is not None:
            self._metrics_server.stop()
        # the loader's run record first: the reader's own stop() below
        # appends its reader-layer record to the same store
        self._write_history_record()
        if self._owns_incidents and self._incidents is not None:
            # reader-owned recorders are the reader's to close
            self._incidents.close()
        self._stop_event.set()
        self.reader.stop()

    def join(self):
        # The producer thread may still be polling the reader's worker pool;
        # zmq sockets are not thread-safe, so it must be gone before the
        # pool's join() drains and closes them.
        producer = self._producer
        if producer is not None and producer is not threading.current_thread():
            self._drain_queue()
            producer.join(timeout=30)
        self.reader.join()

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc_val, exc_tb):
        self.stop()
        self.join()


def iter_reader_chunks(reader, accum_rows=4096, include_empty=False):
    """Yield ``(columns_dict, num_rows, item_id_or_None)`` from any reader: the columnar
    fast path when available (item identity preserved for delivery accounting), else
    batched-namedtuple or per-row accumulation (``accum_rows`` per chunk). The single
    reader-dispatch used by both JaxDataLoader and InMemJaxLoader."""
    iter_columnar = getattr(reader, 'iter_columnar', None)
    if iter_columnar is not None:
        # NGram readers ride the same path: iter_columnar yields window-major batches
        # ({field: (num_windows, length, ...)}) carrying the piece's item_id, so
        # delivery accounting counts windows exactly like rows.
        for batch in iter_columnar(include_empty=include_empty):
            yield dict(batch.columns), batch.num_rows, batch.item_id
    elif getattr(reader, 'is_batched_reader', False):
        for batch in reader:
            columns = batch._asdict()
            num_rows = len(next(iter(columns.values()))) if columns else 0
            yield columns, num_rows, None
    else:
        pending = []
        for row in reader:
            pending.append(row._asdict())
            if len(pending) >= accum_rows:
                yield _rows_to_columns(pending), len(pending), None
                pending = []
        if pending:
            yield _rows_to_columns(pending), len(pending), None


def reader_may_be_infinite(reader):
    """Conservative infinite-stream detection: ``num_epochs is None`` on the reader or,
    for wrapper readers exposing ``_readers``/``readers``, on any wrapped reader;
    unknown shapes count as infinite (callers should then demand an explicit cap)."""
    if hasattr(reader, 'num_epochs'):
        return reader.num_epochs is None
    inner = getattr(reader, 'readers', None) or getattr(reader, '_readers', None)
    if inner:
        return any(reader_may_be_infinite(r) for r in inner)
    return True


class FieldShardings(object):
    """Per-field sharding table: fields named in the ``partition_spec`` dict get their
    spec, everything else the batch-axis default (rank-1 label columns can ride along
    with a rank-2 sequence-sharded tokens column)."""

    def __init__(self, per_field, default):
        self._per_field = per_field
        self._default = default

    def for_field(self, name):
        return self._per_field.get(name, self._default)

    def check_unused(self, field_names):
        """Warn once about spec keys matching no batch field (a typoed key would
        otherwise silently leave its field on the batch-axis default)."""
        unused = set(self._per_field) - set(field_names)
        if unused:
            import warnings
            warnings.warn('partition_spec keys {} match no batch field (fields: {}); '
                          'those fields fall back to the default batch-axis sharding'
                          .format(sorted(unused), sorted(field_names)))


def resolve_sharding(mesh, partition_spec, device_put):
    """Sharding for emitted batch arrays: single default device without a mesh, else a
    ``NamedSharding`` over ``partition_spec`` (default: batch axis over the mesh's first
    axis). A dict ``partition_spec`` ({field: PartitionSpec}) returns a
    :class:`FieldShardings` table."""
    if isinstance(partition_spec, dict):
        per_field = {name: resolve_sharding(mesh, spec, device_put)
                     for name, spec in partition_spec.items()}
        default = resolve_sharding(mesh, None, device_put)
        return FieldShardings(per_field, default)
    if not device_put:
        if partition_spec is not None and mesh is None:
            raise ValueError('partition_spec requires a mesh')
        return None
    import jax
    from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding
    if mesh is None:
        if partition_spec is not None:
            raise ValueError('partition_spec requires a mesh')
        return SingleDeviceSharding(jax.devices()[0])
    spec = partition_spec
    if spec is None:
        spec = PartitionSpec(mesh.axis_names[0])
    return NamedSharding(mesh, spec)


def sharding_for_field(sharding, name):
    """Per-field sharding lookup: FieldShardings tables dispatch by name, plain
    shardings apply to every field."""
    return sharding.for_field(name) if isinstance(sharding, FieldShardings) else sharding


def _chunk_sharding(sharding):
    """Batch sharding -> chunk sharding: prepend an unsharded (replicated-over-mesh)
    scan axis to every NamedSharding's PartitionSpec, so a ``(batch, ...)`` spec
    applies to the trailing dims of a ``(n_batches, batch, ...)`` chunk array.
    SingleDeviceSharding (mesh=None) already covers any rank and passes through."""
    from jax.sharding import NamedSharding, PartitionSpec
    if isinstance(sharding, FieldShardings):
        return FieldShardings(
            {name: _chunk_sharding(s) for name, s in sharding._per_field.items()},
            _chunk_sharding(sharding._default))
    if isinstance(sharding, NamedSharding):
        return NamedSharding(sharding.mesh, PartitionSpec(None, *sharding.spec))
    return sharding


def sanitize_columns(columns, pad_ragged, device_put, passthrough=frozenset()):
    """Dtype sanitization for the device (the analog of the torch/tf sanitizers,
    pytorch.py:40-65 / tf_utils.py:57-96): datetimes -> int64 ns, ragged fields padded
    per ``pad_ragged`` (emitting a ``<field>_len`` mask column), strings/objects rejected
    with the field named when a device representation is required. Columns named
    in ``passthrough`` skip sanitization entirely — raw-shipped payloads (and
    their auxiliary columns) keep whatever form the ship-raw kernel produced
    until the device decode tail finishes them (docs/performance.md)."""
    out = {}
    for name, col in columns.items():
        if name in passthrough:
            out[name] = col
            continue
        if name in pad_ragged:
            padded, lengths = _pad_column(col, pad_ragged[name], name)
            out[name] = padded
            out[name + '_len'] = lengths
            continue
        if isinstance(col, list):
            raise ValueError(
                'Field {!r} is ragged (variable shape); pass pad_ragged={{{!r}: '
                '(max_shape...)}} to pad it, or drop it via schema_fields'
                .format(name, name))
        if col.dtype.kind == 'M':
            out[name] = col.astype('datetime64[ns]').astype(np.int64)
        elif col.dtype.kind in ('U', 'S', 'O'):
            if device_put:
                raise ValueError(
                    'Field {!r} has dtype {} which has no device representation; '
                    'drop it via schema_fields or use device_put=False'
                    .format(name, col.dtype))
            out[name] = col
        else:
            out[name] = np.ascontiguousarray(col)
    return out


def _iter_column_slices(columns, slice_rows):
    n = 0
    for col in columns.values():
        n = len(col)
        break
    if n <= slice_rows:
        yield columns
        return
    for start in range(0, n, slice_rows):
        yield {name: col[start:start + slice_rows] for name, col in columns.items()}


def _concat_column_chunks(chunks):
    """Concatenate a list of sanitized column dicts along the row axis (single-chunk
    lists pass through without a copy)."""
    if len(chunks) == 1:
        return chunks[0]
    return {name: np.concatenate([c[name] for c in chunks])
            for name in chunks[0]}


def _rows_to_columns(rows):
    columns = {}
    for name in rows[0]:
        values = [row[name] for row in rows]
        first = values[0]
        if isinstance(first, np.ndarray) and first.ndim >= 1:
            shapes = {v.shape for v in values}
            if len(shapes) == 1:
                columns[name] = np.stack(values)
            else:
                columns[name] = values  # ragged: stays a list until pad_ragged
        elif isinstance(first, (str, bytes)) or first is None:
            columns[name] = np.array(values, dtype=object)
        else:
            columns[name] = np.asarray(values)
    return columns


def _pad_column(col, target_shape, name):
    """Zero-pad each row of a ragged column to ``target_shape``; return (padded array,
    int32 first-dim lengths)."""
    values = list(col)
    target_shape = tuple(target_shape)
    first = np.asarray(values[0])
    padded = np.zeros((len(values),) + target_shape, dtype=first.dtype)
    lengths = np.zeros(len(values), dtype=np.int32)
    for i, value in enumerate(values):
        value = np.asarray(value)
        if value.ndim != len(target_shape):
            raise ValueError('pad_ragged[{!r}]={} rank mismatch with value shape {}'
                             .format(name, target_shape, value.shape))
        if any(v > t for v, t in zip(value.shape, target_shape)):
            raise ValueError('Value of field {!r} with shape {} exceeds pad_ragged '
                             'target {}'.format(name, value.shape, target_shape))
        region = tuple(slice(0, s) for s in value.shape)
        padded[(i,) + region] = value
        lengths[i] = value.shape[0]
    return padded, lengths


def make_jax_loader(dataset_url_or_urls, batch_size, mesh=None, partition_spec=None,
                    batched=True, loader_kwargs=None, **reader_kwargs):
    """Convenience factory: reader + JaxDataLoader in one call. ``batched=True`` uses
    make_batch_reader (native Parquet, fastest); ``batched=False`` uses make_reader
    (codec decode)."""
    from petastorm_tpu.parallel.mesh import distributed_shard_info
    from petastorm_tpu.reader import make_batch_reader, make_reader
    cur_shard, shard_count = distributed_shard_info(
        reader_kwargs.pop('cur_shard', None), reader_kwargs.pop('shard_count', None))
    if shard_count is not None:
        reader_kwargs['cur_shard'] = cur_shard
        reader_kwargs['shard_count'] = shard_count
    factory = make_batch_reader if batched else make_reader
    reader = factory(dataset_url_or_urls, **reader_kwargs)
    return JaxDataLoader(reader, batch_size, mesh=mesh, partition_spec=partition_spec,
                         **(loader_kwargs or {}))
