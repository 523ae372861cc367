"""Thread worker pool (reference: petastorm/workers_pool/thread_pool.py:37-221).

The TPU-idiomatic default pool: Arrow's Parquet C++ reader releases the GIL, so thread
workers overlap IO + decompression with the consumer; no serialization cost crosses the
worker->consumer boundary (unlike the process pool's IPC).
"""

import logging
import queue
import threading
import time

from petastorm_tpu.telemetry.registry import MetricsRegistry
from petastorm_tpu.telemetry.spans import stage_span
from petastorm_tpu.workers import (EmptyResultError, TimeoutWaitingForResultError,
                                   VentilatedItemProcessedMessage)

logger = logging.getLogger(__name__)

DEFAULT_RESULTS_QUEUE_SIZE = 50
_STOP_SENTINEL = object()


class _WorkerError(object):
    def __init__(self, exc, tb):
        self.exc = exc
        self.tb = tb


class WorkerThread(threading.Thread):
    def __init__(self, pool, worker):
        super().__init__(daemon=True, name='petastorm-tpu-worker-{}'.format(worker.worker_id))
        self._pool = pool
        self._worker = worker

    def run(self):
        profiler = None
        if self._pool._profiling_enabled:
            import cProfile
            profiler = cProfile.Profile()
        while True:
            # Elastic park point (docs/autotuning.md): a worker whose id is
            # beyond the pool's current active count waits here instead of
            # pulling work, so set_workers_count can shrink the pool without
            # killing threads (and grow it again by just notifying).
            self._pool._await_active(self._worker.worker_id)
            item = self._pool._ventilator_queue.get()
            if item is _STOP_SENTINEL:
                break
            # CPython 3.12's cProfile registers a process-global sys.monitoring tool, so
            # only one profiler may be active at a time: workers contend for the lock
            # per item and whoever holds it profiles that item (a sample of all
            # workers' work rather than the reference's true per-thread profiles,
            # thread_pool.py:41-49 — py3.12 removed that option).
            profiling_this = profiler is not None and \
                self._pool._profiler_slot.acquire(blocking=False)
            if profiling_this:
                try:
                    profiler.enable()
                except ValueError:
                    # another tool (e.g. coverage) owns the global monitoring slot
                    self._pool._profiler_slot.release()
                    profiling_this = False
            try:
                try:
                    self._worker.process(**item)
                finally:
                    if profiling_this:
                        profiler.disable()
                        self._pool._profiler_slot.release()
                self._pool._put_result(VentilatedItemProcessedMessage())
            except Exception as exc:  # noqa: BLE001 - propagate to consumer
                import traceback
                self._pool._put_result(_WorkerError(exc, traceback.format_exc()))
        if profiler is not None:
            self._pool._collect_profile(profiler)
        self._worker.shutdown()


class ThreadPool(object):
    """N worker threads, each owning a worker instance; bounded results queue provides
    backpressure (reference: thread_pool.py)."""

    def __init__(self, workers_count, results_queue_size=DEFAULT_RESULTS_QUEUE_SIZE,
                 profiling_enabled=False, max_workers_count=None):
        """``max_workers_count`` bounds runtime growth via
        :meth:`set_workers_count` (default ``4 * workers_count``) — the elastic
        worker knob the autotuner turns (docs/autotuning.md)."""
        self._workers_count = workers_count
        self._max_workers_count = max(int(max_workers_count or 4 * workers_count),
                                      workers_count)
        self._results_queue = queue.Queue(results_queue_size)
        self._ventilator_queue = queue.Queue()
        self._threads = []
        self._ventilator = None
        self._stopped = threading.Event()
        self.workers_count = workers_count
        # ------------------------------------------------ elastic grow/park
        # _active_workers is the number of worker ids allowed to pull work;
        # threads with a higher id park on _resize_cond (see WorkerThread.run).
        # Worker construction args are kept so growth past the spawned set can
        # start fresh threads mid-epoch.
        self._resize_cond = threading.Condition()
        self._active_workers = workers_count
        self._worker_class = None
        self._worker_args = None
        #: per-worker cProfile, aggregated and logged on join() (reference:
        #: thread_pool.py:41-49,190-198)
        self._profiling_enabled = profiling_enabled
        self._profiles = []
        self._profiles_lock = threading.Lock()
        self._profiler_slot = threading.Lock()
        #: consumer-side telemetry: ``pool_wait`` (time the consumer spent inside
        #: get_results per result) — worker-side stages ride each batch's
        #: telemetry sidecar instead (docs/observability.md)
        self.telemetry = MetricsRegistry()

    def start(self, worker_class, worker_args=None, ventilator=None):
        if self._threads:
            raise RuntimeError('ThreadPool already started')
        self._worker_class = worker_class
        self._worker_args = worker_args
        for worker_id in range(self._workers_count):
            self._spawn_worker_thread(worker_id)
        if ventilator is not None:
            self._ventilator = ventilator
            self._ventilator.start()

    def _spawn_worker_thread(self, worker_id):
        worker = self._worker_class(worker_id, self._put_result, self._worker_args)
        thread = WorkerThread(self, worker)
        self._threads.append(thread)
        thread.start()

    # ------------------------------------------------------- elastic grow/park

    def _await_active(self, worker_id):
        """Park the calling worker thread while its id is beyond the active
        count (and the pool is not stopped) — the shrink half of
        :meth:`set_workers_count`."""
        with self._resize_cond:
            while (worker_id >= self._active_workers
                   and not self._stopped.is_set()):
                self._resize_cond.wait(timeout=0.5)

    def set_workers_count(self, value):
        """Bounded, thread-safe runtime resize of the worker set
        (docs/autotuning.md): growing beyond the threads already spawned starts
        fresh worker threads; shrinking parks the excess threads at their next
        item boundary (an in-progress item always completes — nothing is
        killed). Clamped to ``[1, max_workers_count]``; returns the applied
        value. No-op (returning the current count) after ``stop()``."""
        value = max(1, min(int(value), self._max_workers_count))
        with self._resize_cond:
            if self._stopped.is_set() or self._worker_class is None:
                return self._active_workers
            spawned = len(self._threads)
            for worker_id in range(spawned, value):
                self._spawn_worker_thread(worker_id)
            self._active_workers = value
            self.workers_count = value
            self._resize_cond.notify_all()
        return value

    def ventilate(self, *args, **kwargs):
        """Enqueue one work item (kwargs form is the worker.process signature)."""
        if args:
            raise TypeError('ventilate accepts keyword arguments only')
        self._ventilator_queue.put(kwargs)

    def _put_result(self, result):
        """Stop-aware bounded put: never deadlocks a worker against a stopped consumer
        (reference: thread_pool.py:200-214)."""
        while not self._stopped.is_set():
            try:
                self._results_queue.put(result, timeout=0.1)
                return
            except queue.Full:
                continue

    def get_results(self, timeout=None):
        """Next result payload; raises EmptyResultError when all ventilated work finished
        and the queue drained; re-raises worker exceptions (reference:
        thread_pool.py:139-172)."""
        deadline = None if timeout is None else time.monotonic() + timeout
        with stage_span('pool_wait', registry=self.telemetry):
            while True:
                try:
                    result = self._results_queue.get_nowait()
                except queue.Empty:
                    if self._ventilator is not None and getattr(self._ventilator, 'error',
                                                                None):
                        self.stop()
                        raise self._ventilator.error
                    if self._ventilator is not None and self._ventilator.completed():
                        raise EmptyResultError()
                    if deadline is not None and time.monotonic() > deadline:
                        raise TimeoutWaitingForResultError()
                    try:
                        result = self._results_queue.get(timeout=0.1)
                    except queue.Empty:
                        continue
                if isinstance(result, VentilatedItemProcessedMessage):
                    if self._ventilator is not None:
                        self._ventilator.processed_item()
                    continue
                if isinstance(result, _WorkerError):
                    self.stop()
                    logger.error('Worker failure re-raised in consumer:\n%s', result.tb)
                    raise result.exc
                return result

    def stop(self):
        self._stopped.set()
        with self._resize_cond:
            # wake parked (shrunk-away) workers so they can take their sentinel
            self._resize_cond.notify_all()
        if self._ventilator is not None:
            self._ventilator.stop()
        for _ in self._threads:
            self._ventilator_queue.put(_STOP_SENTINEL)

    def _collect_profile(self, profiler):
        with self._profiles_lock:
            self._profiles.append(profiler)

    def join(self):
        if not self._stopped.is_set():
            raise RuntimeError('join() must be preceded by stop()')
        stragglers = []
        for thread in self._threads:
            thread.join(timeout=30)
            if thread.is_alive():
                stragglers.append(thread.name)
        self._threads = []
        if stragglers and self._profiling_enabled:
            logger.warning('Worker thread(s) %s still alive after join timeout; their '
                           'profile data is not included in the aggregate', stragglers)
        if self._profiling_enabled and self._profiles:
            import io
            import pstats
            stream = io.StringIO()
            stats = None
            with self._profiles_lock:
                for profiler in self._profiles:
                    try:
                        profiler.create_stats()
                    except Exception:  # noqa: BLE001 - never profiled anything
                        continue
                    if not getattr(profiler, 'stats', None):
                        continue  # worker never won the (py3.12-global) profiler slot
                    if stats is None:
                        stats = pstats.Stats(profiler, stream=stream)
                    else:
                        stats.add(profiler)
                self._profiles = []
            if stats is not None:
                stats.sort_stats('cumulative').print_stats(30)
                logger.info('Aggregated worker-thread profile:\n%s', stream.getvalue())

    @property
    def diagnostics(self):
        return {'output_queue_size': self._results_queue.qsize()}
