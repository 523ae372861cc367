"""Process worker pool over ZeroMQ (reference: petastorm/workers_pool/process_pool.py:114-424).

Socket topology (evolved from the reference's PUSH ventilation, process_pool.py:52-74):

    main ROUTER (dispatch)  <─> worker DEALER    ('ready' requests up, work items down)
    main PUB    (control)   ──> worker SUB       ('stop' broadcast)
    main PULL   (results)   <── worker PUSH      (handshake / result / done / error)

Dispatch is **pull-based**: a worker asks for work ('ready') and the pool assigns the
next pending item to that specific worker. Unlike PUSH round-robin, nothing ever sits in
a dead worker's socket buffer, and the pool knows exactly which items each worker holds —
that attribution is what makes worker **respawn** sound: when a worker dies mid-epoch
(OOM-kill, segfault in a native decoder), the pool respawns it (bounded by
``max_worker_respawns``) and re-ventilates its un-acked in-flight items instead of
aborting the epoch (docs/robustness.md; the tf.data-service recovery model,
arXiv 2210.14826). Items are acked per-token ('done'), and a duplicate result from an
item that was re-ventilated after its first result already reached the consumer is
dropped (``results_dropped`` in diagnostics) — re-ventilation assumes the petastorm_tpu
worker contract of exactly one published result per item.

Workers are spawned (never forked — fork breaks JVM/libhdfs state, reference
exec_in_new_process.py:15-17) as fresh interpreters running
``petastorm_tpu.workers.process_worker_main`` with a dill-serialized bootstrap file.
Each worker runs a parent-watchdog thread and exits if the main process dies
(reference: process_pool.py:320-327).

**Hang watchdog** (docs/robustness.md "Hang detection & circuit breakers"): respawn
alone only fires on process *death* — a worker wedged in a native deadlock or an
NFS stall would stall the epoch forever. Two complementary consumer-side detectors
reap hung-but-alive workers through the same bounded-respawn path:

- **heartbeat staleness**: each worker's heartbeat thread stamps a monotone counter
  (shm heartbeat word when the ring is up, ``heartbeat`` results-channel messages
  otherwise); a worker holding assigned items whose stamp has not changed for
  ``hang_timeout_s`` is process-wide wedged (a GIL-releasing stall keeps stamping)
  and is SIGKILLed — the existing death path then respawns it and re-ventilates its
  items.
- **per-item deadline** (``item_deadline_s``, off by default): an assigned item with
  no result for that long marks its worker hung even though it keeps heartbeating
  (GIL-released native stall). The worker is reaped; when a hang-result factory is
  installed (``on_error='skip'``), the overdue items are *quarantined* — an empty
  stand-in batch carrying a ``QuarantineRecord(reason='hang')`` is delivered instead
  of re-dispatching a rowgroup that already demonstrated it hangs a worker.

Both checks run only while ``get_results`` is idle-polling (results drained, consumer
actually starved) — a consumer away in a long training step can neither observe
staleness nor accrue false deadlines against queued-but-unread results. Reaps count
into ``workers_hung_reaped`` and the ``watchdog_reap`` telemetry counter, and consume
the same ``max_worker_respawns`` budget as deaths: a worker that hangs repeatedly
fails loudly, exactly like one that crashes repeatedly.

**Frame integrity + the shm circuit breaker**: every shm descriptor carries a CRC-32
of its payload (``workers/shm_ring.py``) verified before deserialization. A mismatch
(torn write / bit flip that the generation stamp cannot see) drops the frame unread,
counts ``shm_crc_failures`` (+ ``shm_crc_fail`` telemetry), SIGKILLs the producing
worker — its slot memory is no longer trusted, and the proven death path re-ventilates
its in-flight items — and records a failure on the pool's shm
:class:`~petastorm_tpu.resilience.CircuitBreaker`. While that breaker is open, work
dispatches carry a ``b'0'`` transport flag telling workers to publish over plain ZMQ
frames (the temporary wire fallback); after ``recovery_timeout_s`` a half-open probe
item rides the ring again and a verified result re-closes the breaker.

**Shared-memory transport** (``shm_transport``, default auto-on): result payloads are
written into a ``workers/shm_ring.py`` slot ring owned by this pool and only a tiny
slot descriptor crosses ZMQ as a ``result_shm`` message; the consumer maps the slot
zero-copy, deserializes, then acks the slot back to the producing worker with a
``release`` on the dispatch ROUTER. Payloads that exceed the slot size (or arrive
while no slot is free past the backpressure window, or when shm is unavailable) fall
back transparently to the original ZMQ ``result`` frames — counted in
``diagnostics['shm_fallback_batches']``. Descriptors carry the producing worker's
generation, so results written by a worker that died and was respawned are dropped
(``shm_stale_drops``) instead of read while the replacement overwrites the slot; the
ring is closed AND unlinked in ``join()`` regardless of worker deaths, so no
``/dev/shm`` segment outlives the pool."""

import collections
import logging
import os
import pickle
import subprocess
import sys
import tempfile
import threading
import time

from petastorm_tpu.telemetry import tracing as _tracing
from petastorm_tpu.telemetry.registry import (BYTES_UNIT, MetricsRegistry,
                                              telemetry_enabled)
from petastorm_tpu.workers import EmptyResultError, TimeoutWaitingForResultError

logger = logging.getLogger(__name__)

_WORKER_STARTUP_TIMEOUT_S = 30
#: message kinds on the results channel; ``result_shm`` carries a shm-slot
#: descriptor instead of the payload frames, ``heartbeat`` a liveness stamp
MSG_STARTED, MSG_RESULT, MSG_DONE, MSG_ERROR = b'started', b'result', b'done', b'error'
MSG_RESULT_SHM = b'result_shm'
MSG_HEARTBEAT = b'heartbeat'
#: default total respawn budget — one bad rowgroup killing the same worker repeatedly
#: must exhaust the budget and fail loudly, not respawn forever
DEFAULT_MAX_WORKER_RESPAWNS = 3
#: watchdog defaults: stamp cadence, and how long a stamp may go unchanged (while
#: the worker holds assigned items) before the worker counts as hung. The timeout
#: is deliberately >> the interval: a worker briefly starved of the GIL by a big
#: in-Python decode must not be reaped for being slow.
DEFAULT_HEARTBEAT_INTERVAL_S = 0.5
DEFAULT_HANG_TIMEOUT_S = 30.0
#: shm breaker defaults: consecutive CRC failures before the wire fallback, and
#: the cooldown before a half-open probe rides the ring again
DEFAULT_SHM_BREAKER_THRESHOLD = 3
DEFAULT_SHM_BREAKER_RECOVERY_S = 30.0


class WorkerTerminationError(Exception):
    pass


class ProcessPool(object):
    """Spawned-process worker pool over a ZMQ dispatcher/sink pair (reference:
    workers_pool/process_pool.py): dill-bootstrapped spawn (never fork), Arrow-IPC
    or pickle wire, orphan watchdog, exception propagation, bounded worker respawn."""

    def __init__(self, workers_count, results_queue_size=50, zmq_copy_buffers=False,
                 payload_serializer=None, max_worker_respawns=DEFAULT_MAX_WORKER_RESPAWNS,
                 shm_transport=None, shm_slot_bytes=None, shm_slots_per_worker=None,
                 heartbeat_interval_s=DEFAULT_HEARTBEAT_INTERVAL_S,
                 hang_timeout_s=DEFAULT_HANG_TIMEOUT_S, item_deadline_s=None,
                 shm_checksum=True, shm_breaker=None):
        """``payload_serializer`` picks the wire format for worker results (reference:
        process_pool.py:251-270 pluggable serializers): default
        :class:`~petastorm_tpu.workers.serializers.ArrowIpcSerializer` (columnar
        zero-copy receive); pass :class:`PickleSerializer` to force plain pickle.
        ``zmq_copy_buffers=False`` (default) receives result frames without copying —
        deserialized arrays then alias ZMQ frame memory. ``max_worker_respawns`` is the
        pool-wide budget of worker restarts after unexpected deaths; 0 restores the
        seed's die-loudly-on-first-death behavior.

        ``shm_transport``: None (auto — enable when ``multiprocessing.shared_memory``
        works and the serializer receives writable copies), True (require; raises if
        unavailable), False (ZMQ frames only, the seed behavior). ``shm_slot_bytes`` /
        ``shm_slots_per_worker`` size the ring (defaults in ``workers/shm_ring.py``);
        slot count bounds the transport's in-flight payloads per worker
        (backpressure).

        Watchdog knobs (module docstring; docs/robustness.md): workers stamp
        liveness every ``heartbeat_interval_s`` (0/None disables stamping); a worker
        holding assigned items whose stamp stalls for ``hang_timeout_s`` (None
        disables the staleness reap) or whose item exceeds ``item_deadline_s``
        (None disables the per-item deadline) is SIGKILLed and respawned within
        ``max_worker_respawns``. ``shm_checksum=False`` skips CRC verification of
        shm frames (benchmark baseline; keep it on in production). ``shm_breaker``
        overrides the shm transport's :class:`~petastorm_tpu.resilience.
        CircuitBreaker` (tests inject one with a fake clock)."""
        from petastorm_tpu.resilience import CircuitBreaker
        from petastorm_tpu.workers import shm_ring
        from petastorm_tpu.workers.serializers import ArrowIpcSerializer
        self._workers_count = workers_count
        self.workers_count = workers_count
        self._results_queue_size = results_queue_size
        self._zmq_copy = zmq_copy_buffers
        self._serializer = (payload_serializer if payload_serializer is not None
                            else ArrowIpcSerializer())
        self._max_worker_respawns = max_worker_respawns
        self._shm_transport = shm_transport
        self._shm_slot_bytes = shm_slot_bytes or shm_ring.DEFAULT_SLOT_BYTES
        self._shm_slots_per_worker = (shm_slots_per_worker
                                      or shm_ring.DEFAULT_SLOTS_PER_WORKER)
        self._ring = None
        if shm_transport is not False \
                and getattr(self._serializer, 'writable', True) is False:
            # Slot memory is handed back to the worker the moment deserialize
            # returns; zero-copy receives would alias reclaimed slots.
            if shm_transport:
                raise ValueError('shm_transport requires a writable-receive '
                                 'serializer (slot memory is reclaimed after '
                                 'deserialize); use ArrowIpcSerializer(writable=True)')
            self._shm_transport = False
        self._context = None
        self._ventilator = None
        self._processes = []
        self._stopped = False
        #: consumer-side telemetry (docs/observability.md): shm_map/shm_release/
        #: pool_wait latency stages plus the per-batch wire_bytes_copied size
        #: histogram (the running-mean source for wire_bytes_copied_per_batch);
        #: merged into Reader.telemetry_snapshot()
        self.telemetry = MetricsRegistry()
        # Instance state, not a get_results local: a typical call returns after one
        # result, so a per-call throttle would still run the liveness probe (ventilator
        # lock + per-worker poll) once per result.
        self._next_liveness_check = 0.0

        # ------------------------------------------------------- hang watchdog
        self._heartbeat_interval_s = heartbeat_interval_s or 0
        self._hang_timeout_s = hang_timeout_s
        if (self._hang_timeout_s is not None and self._heartbeat_interval_s
                and self._hang_timeout_s < 4 * self._heartbeat_interval_s):
            raise ValueError('hang_timeout_s ({}) must be >= 4x '
                             'heartbeat_interval_s ({}) or staleness cannot be '
                             'told from stamp jitter'
                             .format(hang_timeout_s, heartbeat_interval_s))
        self._item_deadline_s = item_deadline_s
        #: worker slot -> [last_stamp_value, monotonic_time_of_last_change]
        self._hb_state = {}
        self._dispatch_time = {}              # token -> monotonic dispatch time
        self._hang_results = collections.deque()  # synthesized quarantine batches
        self._hang_result_factory = None
        self._workers_hung_reaped = 0
        self._next_hang_check = 0.0

        # -------------------------------------------------------- shm integrity
        self._shm_checksum = shm_checksum
        self._shm_crc_failures = 0
        # token -> current attempt number, bumped on every re-ventilation. The
        # 'done' ack echoes the attempt it was dispatched with, so an ack from a
        # SUPERSEDED attempt (e.g. the done a corrupt result's producer may or
        # may not have flushed before its SIGKILL — ZMQ gives no guarantee
        # either way) can never retire an item the redelivery attempt still
        # owes, nor double-retire one the redelivery already acked.
        self._attempt = {}

        def _count_breaker_open(name, old_state, new_state):
            if new_state == 'open' and telemetry_enabled():
                self.telemetry.inc('breaker_open')
        self._shm_breaker = shm_breaker if shm_breaker is not None else \
            CircuitBreaker('shm_transport',
                           failure_threshold=DEFAULT_SHM_BREAKER_THRESHOLD,
                           recovery_timeout_s=DEFAULT_SHM_BREAKER_RECOVERY_S)
        # injected breakers feed the breaker_open telemetry counter too;
        # observe_transitions chains after (never clobbers) any caller wiring
        self._shm_breaker.observe_transitions(_count_breaker_open)

        # ---------------------------------------------------- dispatch bookkeeping
        # All mutated under _state_lock: ventilate() runs on the ventilator thread,
        # dispatch/ack/requeue on the consumer thread.
        self._state_lock = threading.Lock()
        self._next_token = 0
        self._items = {}                      # token -> dilled kwargs (until done-acked)
        self._pending = collections.deque()   # tokens awaiting assignment
        self._assigned = {}                   # token -> worker identity holding it
        self._ready = collections.deque()     # worker identities awaiting work
        self._identity_slot = {}              # identity -> (slot, generation)
        self._slot_identity = {}              # slot -> current identity (for releases)
        self._slot_generation = []            # slot -> current generation
        # Tokens whose result reached the consumer but whose 'done' has not (cleared on
        # done). Any further result for such a token is a duplicate from a
        # re-ventilated attempt — the worker contract is one result per item — and is
        # dropped, regardless of whether the first result arrived before or after the
        # producing worker died.
        self._delivered = set()
        self._workers_respawned = 0
        self._results_dropped = 0
        # ------------------------------------------------------ wire counters
        # All consumer-thread-only except where noted; read under _state_lock in
        # diagnostics for a consistent snapshot.
        self._wire_batches = 0          # result payloads delivered or dropped
        self._shm_batches = 0           # payloads that arrived via the shm ring
        self._shm_fallback_batches = 0  # ZMQ-frame results while shm was enabled
        self._shm_stale_drops = 0       # descriptors from a pre-respawn generation
        self._shm_bytes_mapped = 0      # payload bytes served zero-copy from slots
        self._zmq_result_bytes = 0      # payload bytes copied off the ZMQ wire

    # ------------------------------------------------------------------ lifecycle

    def start(self, worker_class, worker_args=None, ventilator=None):
        import zmq
        self._context = zmq.Context()
        self._dispatch_socket = self._context.socket(zmq.ROUTER)
        dispatch_port = self._dispatch_socket.bind_to_random_port('tcp://127.0.0.1')
        self._control_socket = self._context.socket(zmq.PUB)
        control_port = self._control_socket.bind_to_random_port('tcp://127.0.0.1')
        self._results_socket = self._context.socket(zmq.PULL)
        self._results_socket.set_hwm(self._results_queue_size)
        results_port = self._results_socket.bind_to_random_port('tcp://127.0.0.1')

        if self._shm_transport is not False and self._ring is None:
            from petastorm_tpu.workers.shm_ring import ShmRing
            try:
                self._ring = ShmRing(self._workers_count,
                                     slots_per_worker=self._shm_slots_per_worker,
                                     slot_bytes=self._shm_slot_bytes)
            except Exception as exc:  # noqa: BLE001 - auto mode degrades to ZMQ
                if self._shm_transport:
                    raise
                logger.warning('shared-memory transport unavailable (%r); falling '
                               'back to ZMQ result frames', exc)
                self._ring = None

        import dill
        # Spawned interpreters must resolve petastorm_tpu itself (python -m resolves it at
        # interpreter startup) AND user modules (transform fns, predicates) exactly like
        # the parent: propagate the parent's sys.path via PYTHONPATH.
        self._child_env = dict(os.environ)
        parent_paths = [p for p in sys.path if p]
        existing = self._child_env.get('PYTHONPATH')
        self._child_env['PYTHONPATH'] = os.pathsep.join(
            parent_paths + ([existing] if existing else []))
        # The trainer process holds the chip; a worker that imported jax (a
        # user transform may) must never try to take it too.
        self._child_env['JAX_PLATFORMS'] = 'cpu'
        # Propagate the telemetry kill switch: set_telemetry_enabled(False) in
        # the parent must also silence SPAWNED workers (captured at pool start;
        # an explicit PETASTORM_TPU_TELEMETRY in the env wins).
        self._child_env.setdefault('PETASTORM_TPU_TELEMETRY',
                                   '1' if telemetry_enabled() else '0')
        # Same capture for the flight recorder: workers spawned while tracing
        # is armed record their own timeline events (trace sidecar).
        self._child_env.setdefault('PETASTORM_TPU_TRACE',
                                   '1' if _tracing.trace_enabled() else '0')
        # Kept for the lifetime of the pool: respawns re-materialize the bootstrap file
        # (workers unlink it at startup).
        self._bootstrap_template = {
            'worker_class': dill.dumps(worker_class),
            'worker_args': dill.dumps(worker_args),
            'serializer': dill.dumps(self._serializer),
            'dispatch_addr': 'tcp://127.0.0.1:{}'.format(dispatch_port),
            'control_addr': 'tcp://127.0.0.1:{}'.format(control_port),
            'results_addr': 'tcp://127.0.0.1:{}'.format(results_port),
            'parent_pid': os.getpid(),
            'shm': (dict(self._ring.worker_spec(), name=self._ring.name,
                         checksum=self._shm_checksum)
                    if self._ring is not None else None),
            'heartbeat_interval_s': self._heartbeat_interval_s,
        }
        self._slot_generation = [0] * self._workers_count
        for worker_id in range(self._workers_count):
            self._processes.append(self._spawn_worker(worker_id, generation=0))
            self._hb_state[worker_id] = [0, time.monotonic()]

        # Startup handshake (reference: process_pool.py:200-213).
        deadline = time.time() + _WORKER_STARTUP_TIMEOUT_S
        started = 0
        poller = zmq.Poller()
        poller.register(self._results_socket, zmq.POLLIN)
        while started < self._workers_count:
            if time.time() > deadline:
                self.stop()
                self._release_ring()
                raise WorkerTerminationError(
                    'Only {} of {} workers started within {}s'
                    .format(started, self._workers_count, _WORKER_STARTUP_TIMEOUT_S))
            if poller.poll(200):
                kind, _ = self._recv()
                if kind == MSG_STARTED:
                    started += 1

        if ventilator is not None:
            self._ventilator = ventilator
            self._ventilator.start()

    def _spawn_worker(self, slot, generation):
        bootstrap = dict(self._bootstrap_template)
        bootstrap['worker_id'] = slot
        bootstrap['generation'] = generation
        fd, path = tempfile.mkstemp(suffix='.petastorm-tpu-worker')
        with os.fdopen(fd, 'wb') as f:
            pickle.dump(bootstrap, f)
        return subprocess.Popen(
            [sys.executable, '-m', 'petastorm_tpu.workers.process_worker_main', path],
            env=self._child_env)

    # ------------------------------------------------------------------ messaging

    def _recv(self):
        parts = self._results_socket.recv_multipart(copy=self._zmq_copy)
        if not self._zmq_copy:
            parts = [p.buffer for p in parts]  # memoryviews over frame memory, no copy
        kind = bytes(memoryview(parts[0]))
        payload = parts[1:] if len(parts) > 1 else None
        return kind, payload

    def ventilate(self, **kwargs):
        if self._stopped:
            raise WorkerTerminationError('Pool is stopped')
        # dill, not pickle: ventilated items carry user callables (lambda predicates,
        # per-item transform state) that plain pickle rejects — the same reason the
        # worker bootstrap ships via dill. Items are only enqueued here; the consumer
        # thread assigns them to workers in response to 'ready' requests (pull-based
        # dispatch — see module docstring).
        import dill
        blob = dill.dumps(kwargs)
        with self._state_lock:
            token = self._next_token
            self._next_token += 1
            self._items[token] = blob
            self._pending.append(token)

    def _handle_ready(self, frames):
        """A worker announced itself idle on the dispatch ROUTER: remember its route and
        slot so pending work (and shm slot releases) can be routed to it
        specifically."""
        identity, slot, generation = frames[0], int(frames[2]), int(frames[3])
        with self._state_lock:
            self._identity_slot[identity] = (slot, generation)
            if self._slot_generation[slot] == generation:
                self._slot_identity[slot] = identity
            self._ready.append(identity)

    def _dispatch_pending(self):
        """Assign pending items to ready workers (consumer thread only — ROUTER sends
        must stay single-threaded). The trailing transport flag tells the worker
        whether its result may ride the shm ring — ``b'0'`` while the shm circuit
        breaker is open (the temporary ZMQ-wire fallback after repeated CRC
        failures)."""
        while True:
            with self._state_lock:
                while self._pending and self._pending[0] not in self._items:
                    # Superseded token: its original attempt completed after the token
                    # was re-ventilated (crash-after-done race) — nothing left to do.
                    self._pending.popleft()
                if not self._pending or not self._ready:
                    return
                identity = self._ready.popleft()
                slot, generation = self._identity_slot.get(identity, (None, None))
                if slot is None or self._slot_generation[slot] != generation:
                    continue  # stale 'ready' from a dead/replaced worker
                token = self._pending.popleft()
                blob = self._items[token]
                self._assigned[token] = identity
                self._dispatch_time[token] = time.monotonic()
                attempt = self._attempt.setdefault(token, 0)
            shm_flag = b'1' if (self._ring is not None
                                and self._shm_breaker.allow()) else b'0'
            self._dispatch_socket.send_multipart(
                [identity, b'work', b'%d' % token, blob, shm_flag,
                 b'%d' % attempt])

    def _release_slot(self, descriptor):
        """Ack a consumed (or duplicate-dropped) shm slot back to the worker that
        owns it, so the slot re-enters the worker's free set. Consumer thread only
        (ROUTER sends are single-threaded). A vanished identity (worker died after
        publishing) is fine: ROUTER drops unroutable messages and the replacement
        worker starts with every slot free."""
        with self._state_lock:
            identity = self._slot_identity.get(descriptor.worker_slot)
            current = self._slot_generation[descriptor.worker_slot]
        if identity is None or current != descriptor.generation:
            return
        release_start = time.perf_counter()
        self._dispatch_socket.send_multipart(
            [identity, b'release', b'%d' % descriptor.ring_slot])
        if telemetry_enabled():
            self.telemetry.observe('shm_release',
                                   time.perf_counter() - release_start)

    def _handle_done(self, token, attempt=None):
        with self._state_lock:
            if token not in self._items:
                return  # duplicate 'done' from a superseded attempt
            if attempt is not None and attempt != self._attempt.get(token, 0):
                # Ack from a superseded dispatch (e.g. the producer of a
                # CRC-failed frame flushed its done before the reaping SIGKILL
                # landed): the item was re-ventilated, and only the CURRENT
                # attempt's ack may retire it — otherwise the redelivered
                # result would be lost (retire-before-delivery).
                return
            del self._items[token]
            self._assigned.pop(token, None)
            self._dispatch_time.pop(token, None)
            self._attempt.pop(token, None)
            self._delivered.discard(token)
        if self._ventilator is not None:
            self._ventilator.processed_item()

    def _check_liveness(self):
        """Consumer-thread probe: respawn dead workers while work remains (bounded
        budget), or raise once the budget is exhausted. A death after all work finished
        must not turn a successful read into an error."""
        all_work_done = self._ventilator is not None and self._ventilator.completed()
        for slot, process in enumerate(self._processes):
            if process.poll() is None:
                continue
            if all_work_done:
                continue
            if self._workers_respawned >= self._max_worker_respawns:
                self.stop()
                raise WorkerTerminationError(
                    'Worker {} (pid {}) exited with code {} while results were still '
                    'expected, and the respawn budget ({}) is exhausted'
                    .format(slot, process.pid, process.returncode,
                            self._max_worker_respawns))
            self._respawn(slot, process)

    def _respawn(self, slot, dead_process):
        """Replace the dead worker at ``slot`` and re-ventilate every item it held:
        requeued items go to the FRONT of the pending queue (they are the oldest
        work — consumers may be blocked on exactly these rowgroups)."""
        requeued = []
        requeued_ctx = []
        with self._state_lock:
            for token, identity in list(self._assigned.items()):
                slot_gen = self._identity_slot.get(identity)
                if slot_gen is None or slot_gen[0] != slot:
                    continue
                del self._assigned[token]
                self._dispatch_time.pop(token, None)
                # New attempt number: any done the dead worker managed to flush
                # for this token is now a stale ack and cannot retire the item.
                reaped_attempt = self._attempt.get(token, 0)
                self._attempt[token] = reaped_attempt + 1
                requeued_ctx.append((token, self._items.get(token),
                                     reaped_attempt))
                # _delivered intentionally untouched: whether the dead worker's result
                # already reached the consumer or is still in the PULL buffer, the
                # FIRST result to be delivered marks the token and every later one is
                # dropped as a duplicate.
                self._pending.appendleft(token)
                requeued.append(token)
            self._slot_generation[slot] += 1
            generation = self._slot_generation[slot]
            self._workers_respawned += 1
            # fresh liveness clock for the replacement (it has not stamped yet)
            self._hb_state[slot] = [0, time.monotonic()]
        logger.warning(
            'Worker %d (pid %d) died with exit code %s mid-epoch; respawning '
            '(%d/%d respawns used) and re-ventilating %d in-flight item(s)',
            slot, dead_process.pid, dead_process.returncode, self._workers_respawned,
            self._max_worker_respawns, len(requeued))
        if _tracing.trace_enabled():
            # Timeline markers for the dead attempt: the worker took its
            # unpublished events with it, so this instant (old attempt) plus
            # the replacement's spans (attempt+1) are how one rowgroup's two
            # lives appear as distinct attempts on the merged trace.
            import dill
            for token, blob, reaped_attempt in requeued_ctx:
                ctx = None
                if blob is not None:
                    try:
                        ctx = self._kwargs_trace_ctx(dill.loads(blob),
                                                     reaped_attempt)
                    except Exception:  # noqa: BLE001 - an undecodable blob only costs the marker its context tag, never the respawn
                        ctx = None
                _tracing.trace_instant(
                    'worker_respawn', ctx=ctx,
                    args={'worker_slot': slot, 'exit_code':
                          dead_process.returncode,
                          'new_attempt': reaped_attempt + 1})
        self._processes[slot] = self._spawn_worker(slot, generation)

    def set_shm_slot_config(self, slots_per_worker=None, slot_bytes=None):
        """Bounded runtime update of the shm ring shape — a **deferred** knob
        (docs/autotuning.md): the live ring is never resized under its workers;
        the new shape applies to the NEXT ring generation (the next
        ``start()``, e.g. the next reader built from this configuration).
        Returns the ``(slots_per_worker, slot_bytes)`` now configured."""
        if slots_per_worker is not None:
            slots_per_worker = int(slots_per_worker)
            if slots_per_worker < 1:
                raise ValueError('slots_per_worker must be >= 1, got {}'
                                 .format(slots_per_worker))
            self._shm_slots_per_worker = slots_per_worker
        if slot_bytes is not None:
            slot_bytes = int(slot_bytes)
            if slot_bytes < 4096:
                raise ValueError('slot_bytes must be >= 4096, got {}'
                                 .format(slot_bytes))
            self._shm_slot_bytes = slot_bytes
        return self._shm_slots_per_worker, self._shm_slot_bytes

    # ----------------------------------------------------------- hang watchdog

    def set_hang_result_factory(self, factory):
        """Install the per-item-deadline quarantine hook: ``factory(item_kwargs,
        elapsed_s)`` must return a result object (an empty stand-in batch carrying
        a ``QuarantineRecord(reason='hang')``) delivered in place of the overdue
        item's real result. Installed by the reader under ``on_error='skip'``;
        without it, overdue items are re-ventilated on the replacement worker (and
        a rowgroup that hangs every worker exhausts the respawn budget loudly)."""
        self._hang_result_factory = factory

    def _note_heartbeat(self, payload):
        """A ``heartbeat`` message arrived on the results channel (ring-less
        transport): record the stamp for the producing worker slot."""
        slot = int(bytes(memoryview(payload[0])))
        generation = int(bytes(memoryview(payload[1])))
        seq = int(bytes(memoryview(payload[2])))
        with self._state_lock:
            if self._slot_generation[slot] != generation:
                return  # stale stamp from a reaped worker's dying breath
            state = self._hb_state.get(slot)
            if state is None or state[0] != seq:
                self._hb_state[slot] = [seq, time.monotonic()]

    def _heartbeat_stale_s(self, slot, now):
        """Seconds since worker ``slot``'s heartbeat stamp last CHANGED (0.0 right
        after a change), or None when stamping is disabled. Change detection is
        consumer-side, so worker and pool clocks are never compared."""
        if not self._heartbeat_interval_s:
            return None
        state = self._hb_state.get(slot)
        if state is None:
            state = [0, now]
            self._hb_state[slot] = state
        if self._ring is not None:
            value = self._ring.heartbeat(slot)
            if value != state[0]:
                self._hb_state[slot] = [value, now]
                return 0.0
        return now - state[1]

    def _check_hangs(self):
        """Reap hung-but-alive workers (module docstring). Runs only from the
        idle branch of ``get_results`` — every queued result/heartbeat has been
        drained, so observed staleness is real, not a consumer that was away."""
        if self._hang_timeout_s is None and self._item_deadline_s is None:
            return
        now = time.monotonic()
        if now < self._next_hang_check:
            return
        self._next_hang_check = now + 0.5
        with self._state_lock:
            assigned_by_slot = {}
            for token, identity in self._assigned.items():
                slot_gen = self._identity_slot.get(identity)
                if slot_gen is not None:
                    assigned_by_slot.setdefault(slot_gen[0], []).append(token)
            dispatch_time = dict(self._dispatch_time)
        for slot, process in enumerate(self._processes):
            if process.poll() is not None:
                continue  # already dead: _check_liveness owns that path
            tokens = assigned_by_slot.get(slot)
            if not tokens:
                # keep the change tracker fresh so idle stretches between items
                # never accrue staleness
                self._heartbeat_stale_s(slot, now)
                continue
            stale_s = self._heartbeat_stale_s(slot, now)
            heartbeat_hung = (self._hang_timeout_s is not None
                              and stale_s is not None
                              and stale_s > self._hang_timeout_s)
            overdue = []
            if self._item_deadline_s is not None:
                overdue = [token for token in tokens
                           if now - dispatch_time.get(token, now)
                           > self._item_deadline_s]
            if heartbeat_hung or overdue:
                self._reap_hung_worker(slot, process, overdue, stale_s, now,
                                       dispatch_time)

    def _reap_hung_worker(self, slot, process, overdue, stale_s, now,
                          dispatch_time):
        """SIGKILL a hung worker so the existing death path respawns it and
        re-ventilates its items. Overdue items are quarantined first (when a
        hang-result factory is installed): re-dispatching a rowgroup that just
        demonstrated it hangs a worker would burn the whole respawn budget on
        the same poison item."""
        with self._state_lock:
            self._workers_hung_reaped += 1
            reap_count = self._workers_hung_reaped
        if telemetry_enabled():
            self.telemetry.inc('watchdog_reap')
        if _tracing.trace_enabled():
            # Anomaly markers for the flight recorder, tagged with the reaped
            # attempt's context while the items are still registered — the hung
            # worker published nothing, so these instants ARE the reaped
            # attempt's footprint on the merged timeline.
            reap_args = {'worker_slot': slot, 'pid': process.pid,
                         'stale_s': round(stale_s, 3) if stale_s is not None
                         else None}
            if overdue:
                # one lock acquisition for all overdue tokens; decode and
                # emit lock-free (mirrors the _respawn requeued_ctx pattern)
                with self._state_lock:
                    pairs = [(self._attempt.get(token, 0),
                              self._items.get(token)) for token in overdue]
                import dill
                for attempt, blob in pairs:
                    ctx = None
                    if blob is not None:
                        try:
                            ctx = self._kwargs_trace_ctx(dill.loads(blob),
                                                         attempt)
                        except Exception:  # noqa: BLE001 - an undecodable blob only costs the marker its context tag, never the reap
                            ctx = None
                    _tracing.trace_instant('watchdog_reap', ctx=ctx,
                                           args=reap_args)
            else:
                _tracing.trace_instant('watchdog_reap', args=reap_args)
        logger.error(
            'Worker %d (pid %d) is hung (heartbeat stale %.1fs, %d item(s) past '
            'the %s item deadline); reaping it (hung-reap #%d — consumes the '
            'respawn budget)',
            slot, process.pid, stale_s if stale_s is not None else -1.0,
            len(overdue), self._item_deadline_s, reap_count)
        if self._hang_result_factory is not None and overdue:
            import dill
            for token in overdue:
                with self._state_lock:
                    blob = self._items.pop(token, None)
                    self._assigned.pop(token, None)
                    self._dispatch_time.pop(token, None)
                    self._attempt.pop(token, None)
                if blob is None:
                    continue  # superseded meanwhile
                elapsed = now - dispatch_time.get(token, now)
                try:
                    stand_in = self._hang_result_factory(dill.loads(blob), elapsed)
                except Exception:  # noqa: BLE001 - never lose the reap to the hook
                    logger.exception('hang-result factory failed for token %d; '
                                     're-ventilating the item instead', token)
                    with self._state_lock:
                        self._items[token] = blob
                        self._attempt[token] = self._attempt.get(token, 0) + 1
                        self._pending.appendleft(token)
                    continue
                self._hang_results.append(stand_in)
                # the item is retired exactly as a 'done' would retire it
                if self._ventilator is not None:
                    self._ventilator.processed_item()
        process.kill()
        # The next liveness pass observes the death and respawns through the
        # bounded budget; any still-assigned tokens re-ventilate there.

    def get_results(self, timeout=None):
        import zmq
        poller = zmq.Poller()
        poller.register(self._results_socket, zmq.POLLIN)
        poller.register(self._dispatch_socket, zmq.POLLIN)
        deadline = None if timeout is None else time.time() + timeout
        wait_start = time.perf_counter()
        while True:
            if self._hang_results:
                # Stand-in batch synthesized for a hang-quarantined item: deliver
                # it like any other result (the quarantine record rides it).
                return self._hang_results.popleft()
            # Liveness on the hot path too — not only when results stop: with several
            # workers, survivors keep producing after one dies, but the dead worker's
            # in-flight items would otherwise silently vanish. Throttled to ~10Hz
            # (detection latency is bounded by the 100ms poller timeout anyway);
            # ventilator.completed() acquires the ventilator lock (shared with the
            # backpressure condition), so it is only evaluated inside this throttled
            # window and on poll timeout — never per-result on the hot path.
            now = time.time()
            if not self._stopped and now >= self._next_liveness_check:
                self._next_liveness_check = now + 0.1
                self._check_liveness()
            self._dispatch_pending()
            events = dict(poller.poll(100))
            if not events:
                # Hang detection belongs exactly here: the queues are drained and
                # the consumer is genuinely starved, so heartbeat staleness and
                # item deadlines measure the workers, not a busy consumer.
                if self._stopped:
                    # stop() came from another thread: return to the caller
                    # before that thread's join() polls these same sockets
                    raise RuntimeError('the worker pool was stopped')
                self._check_hangs()
                if self._hang_results:
                    # a reap just quarantined item(s) — deliver the stand-in
                    # BEFORE the completed() check can end the epoch
                    continue
                if self._ventilator is not None and getattr(self._ventilator, 'error', None):
                    self.stop()
                    raise self._ventilator.error
                if self._ventilator is not None and self._ventilator.completed():
                    raise EmptyResultError()
                if deadline is not None and time.time() > deadline:
                    raise TimeoutWaitingForResultError()
                continue
            if self._dispatch_socket in events:
                frames = self._dispatch_socket.recv_multipart()
                if len(frames) >= 4 and bytes(frames[1]) == b'ready':
                    self._handle_ready(frames)
                self._dispatch_pending()
            if self._results_socket not in events:
                continue
            kind, payload = self._recv()
            if kind == MSG_HEARTBEAT:
                self._note_heartbeat(payload)
                continue
            if kind == MSG_DONE:
                self._handle_done(
                    int(bytes(memoryview(payload[0]))),
                    attempt=(int(bytes(memoryview(payload[1])))
                             if len(payload) > 1 else None))
                continue
            if kind == MSG_ERROR:
                exc, tb = pickle.loads(bytes(memoryview(payload[1])))
                logger.error('Worker failure re-raised in consumer:\n%s', tb)
                self.stop()
                raise exc
            if kind == MSG_RESULT:
                token = int(bytes(memoryview(payload[0])))
                payload_bytes = sum(memoryview(frame).nbytes for frame in payload[1:])
                with self._state_lock:
                    self._wire_batches += 1
                    self._zmq_result_bytes += payload_bytes
                    shm_fallback = self._ring is not None
                    if shm_fallback:
                        self._shm_fallback_batches += 1
                    if token not in self._items or token in self._delivered:
                        # Duplicate from a re-ventilated item whose first result was
                        # already delivered (retired token, or delivered-but-not-yet-
                        # acked) — count it, never deliver it twice.
                        self._results_dropped += 1
                        continue
                    self._delivered.add(token)
                if shm_fallback and _tracing.trace_enabled():
                    # anomaly marker: this result rode the ZMQ wire although the
                    # shm ring was enabled (oversized / slot-starved / breaker)
                    _tracing.trace_instant('shm_fallback', args={'token': token})
                copy_before = self._serializer_bytes_copied()
                result = self._serializer.deserialize(payload[1:])
                if telemetry_enabled():
                    # true per-batch copied bytes: ZMQ frame bytes + the
                    # serializer's receive-side copies for THIS batch
                    self.telemetry.observe(
                        'wire_bytes_copied',
                        payload_bytes + self._serializer_bytes_copied()
                        - copy_before, unit=BYTES_UNIT)
                    self.telemetry.observe('pool_wait',
                                           time.perf_counter() - wait_start)
                return result
            if kind == MSG_RESULT_SHM:
                result = self._handle_shm_result(payload)
                if result is not None:
                    if telemetry_enabled():
                        self.telemetry.observe('pool_wait',
                                               time.perf_counter() - wait_start)
                    return result[0]
                continue
            if kind == MSG_STARTED:  # respawned worker joining — expected
                continue

    def _handle_shm_result(self, payload):
        """One ``result_shm`` message: validate the descriptor's generation, dedup the
        token, verify the payload CRC, deserialize zero-copy from the slot, ack the
        slot. Returns ``(payload_obj,)`` to deliver or None to keep polling."""
        from petastorm_tpu.workers.shm_ring import ShmSlotDescriptor
        token = int(bytes(memoryview(payload[0])))
        descriptor = ShmSlotDescriptor.from_bytes(bytes(memoryview(payload[1])))
        with self._state_lock:
            self._wire_batches += 1
            self._zmq_result_bytes += memoryview(payload[1]).nbytes
            if self._slot_generation[descriptor.worker_slot] != descriptor.generation:
                # Written by a worker that has since died and been respawned: the
                # replacement owns (and may be overwriting) the slot — never read
                # it. The item was re-ventilated, so a fresh result is coming.
                self._shm_stale_drops += 1
                return None
            duplicate = token not in self._items or token in self._delivered
        if duplicate:
            with self._state_lock:
                self._results_dropped += 1
            self._release_slot(descriptor)  # still owed: the slot holds real bytes
            return None
        if self._ring is None:  # defensive: descriptor without a ring
            self._release_slot(descriptor)
            return None
        map_start = time.perf_counter()
        copy_before = self._serializer_bytes_copied()
        views = self._ring.view(descriptor)
        if self._shm_checksum and descriptor.crc is not None:
            from petastorm_tpu.workers.integrity import payload_checksum
            if payload_checksum(views) != descriptor.crc:
                for view in views:
                    view.release()
                self._on_shm_corruption(descriptor, token)
                return None
        with self._state_lock:
            self._delivered.add(token)
            self._shm_batches += 1
            self._shm_bytes_mapped += descriptor.total_bytes
        try:
            result = self._serializer.deserialize(views)
            self._shm_breaker.record_success()
            if _tracing.trace_enabled():
                # consumer-side leg of the rowgroup's trace: the shm_map span
                # tagged with the delivered batch's (epoch, rowgroup, attempt),
                # so the exported timeline stitches worker and consumer tracks
                item_id = getattr(result, 'item_id', None)
                ctx = None
                if item_id is not None:
                    with self._state_lock:
                        attempt = self._attempt.get(token, 0)
                    ctx = (int(item_id[0]), int(item_id[1]), attempt)
                _tracing.trace_complete(
                    'shm_map', map_start, time.perf_counter() - map_start,
                    ctx=ctx)
            if telemetry_enabled():
                # shm_map: slot view + CRC verify + deserialize; copied bytes =
                # descriptor frame + the serializer's receive-side copies
                self.telemetry.observe('shm_map',
                                       time.perf_counter() - map_start)
                self.telemetry.observe(
                    'wire_bytes_copied',
                    memoryview(payload[1]).nbytes
                    + self._serializer_bytes_copied() - copy_before,
                    unit=BYTES_UNIT)
            return (result,)
        finally:
            # Frames never outlive this call (writable-receive contract enforced in
            # __init__): drop the slot views so join()'s unlink can't hit exported
            # buffers, then hand the slot back.
            for view in views:
                try:
                    view.release()
                except BufferError:  # pragma: no cover - a consumer kept a ref
                    pass
            self._release_slot(descriptor)

    def _on_shm_corruption(self, descriptor, token):
        """A shm frame failed its CRC — a torn write or bit flip the generation
        stamp cannot see. The frame is dropped unread; the producing worker is
        SIGKILLed (its slot memory is no longer trusted, and the proven death
        path re-ventilates everything it held, this token included, with the
        duplicate-drop guard intact); the shm breaker records the failure, so
        repeated corruption opens it and routes results over the ZMQ wire until
        the cooldown's half-open probe passes (docs/robustness.md)."""
        with self._state_lock:
            self._shm_crc_failures += 1
            failures = self._shm_crc_failures
            # Invalidate the producer's ack for this token RIGHT NOW: if its
            # done(attempt) was flushed before the SIGKILL below lands, it is
            # already queued behind this frame and would otherwise retire the
            # item before the respawn path can redeliver it.
            reaped_attempt = self._attempt.get(token, 0)
            self._attempt[token] = reaped_attempt + 1
        if telemetry_enabled():
            self.telemetry.inc('shm_crc_fail')
        if _tracing.trace_enabled():
            _tracing.trace_instant(
                'shm_crc_drop', ctx=self._token_trace_ctx(token, reaped_attempt),
                args={'worker_slot': descriptor.worker_slot,
                      'ring_slot': descriptor.ring_slot, 'token': token})
        self._shm_breaker.record_failure()
        logger.error(
            'shm frame from worker %d (ring slot %d, token %d) failed CRC '
            'verification (corruption #%d); dropping it unread, reaping the '
            'producing worker, and recording a shm-breaker failure (state now %r)',
            descriptor.worker_slot, descriptor.ring_slot, token, failures,
            self._shm_breaker.state)
        process = self._processes[descriptor.worker_slot]
        if process.poll() is None:
            process.kill()
        # No slot release: the replacement worker starts with its range free,
        # and the death path re-ventilates everything the worker held.

    def _token_trace_ctx(self, token, attempt):
        """Causal trace context ``(epoch, rowgroup, attempt)`` for a dispatched
        token, decoded from its ventilated kwargs blob — anomaly-path only
        (reaps, respawns, CRC drops are rare; the hot path never loads blobs)."""
        with self._state_lock:
            blob = self._items.get(token)
        if blob is None:
            return None
        import dill
        try:
            kwargs = dill.loads(blob)
        except Exception:  # noqa: BLE001 - an undecodable blob only costs the anomaly marker its context tag, never the reap/redelivery itself
            return None
        return self._kwargs_trace_ctx(kwargs, attempt)

    @staticmethod
    def _kwargs_trace_ctx(kwargs, attempt):
        piece = kwargs.get('piece_index')
        if piece is None:
            return None
        return (int(kwargs.get('epoch_index', 0)), int(piece), int(attempt))

    def _serializer_bytes_copied(self):
        """Cumulative receive-side copied bytes from the serializer's stats (0 when
        the serializer keeps none) — deltas around one deserialize give the
        per-batch copy cost for the wire_bytes_copied histogram."""
        stats = getattr(self._serializer, 'stats', None)
        return stats.get('bytes_copied', 0) if stats else 0

    def stop(self):
        if self._stopped:
            return
        self._stopped = True
        if self._ventilator is not None:
            self._ventilator.stop()
        try:
            self._control_socket.send(b'stop')
        except Exception:  # noqa: BLE001 - stop() is best-effort: a dead socket/context must not mask shutdown
            logger.warning('Failed to broadcast stop to workers; relying on the '
                           'parent-watchdog exit path', exc_info=True)

    def join(self):
        deadline = time.time() + 10
        self._drain_until_exit(deadline)
        for slot, process in enumerate(self._processes):
            if process.poll() is None:
                # Loud fallback + reap: a silent kill() left both an unexplained
                # SIGKILL in the logs' absence AND a zombie (kill without wait).
                logger.warning('Worker %d (pid %d) did not exit within 10s of '
                               'stop(); sending SIGKILL', slot, process.pid)
                process.kill()
                try:
                    process.wait(timeout=5)
                except subprocess.TimeoutExpired:
                    logger.error('Worker %d (pid %d) is unreaped after SIGKILL; '
                                 'abandoning it as a zombie', slot, process.pid)
        if self._context is not None:
            for sock in (self._dispatch_socket, self._control_socket,
                         self._results_socket):
                sock.close(linger=0)
            self._context.term()
            self._context = None
        # After every worker is reaped: close AND unlink the ring so no /dev/shm
        # segment survives the pool, however the workers died.
        self._release_ring()

    def _drain_until_exit(self, deadline):
        """Wait (to ``deadline``) for workers to exit, DRAINING both channels in
        200ms polls. Discarding queued results/heartbeats and acking un-released
        shm descriptors is what lets a worker blocked in its slot-wait
        backpressure loop (e.g. publishing the items it held when a sibling was
        hang-reaped) finish its publish, see the stop broadcast, and exit —
        instead of riding the full slot-wait timeout into the SIGKILL fallback."""
        if self._context is None:
            while (time.time() < deadline
                    and any(p.poll() is None for p in self._processes)):
                time.sleep(0.2)
            return
        import zmq
        from petastorm_tpu.workers.shm_ring import ShmSlotDescriptor
        poller = zmq.Poller()
        poller.register(self._results_socket, zmq.POLLIN)
        poller.register(self._dispatch_socket, zmq.POLLIN)
        next_stop_broadcast = 0.0
        while any(p.poll() is None for p in self._processes):
            now = time.time()
            if now >= deadline:
                return
            if now >= next_stop_broadcast:
                # Re-broadcast stop: a worker respawned moments before stop() may
                # still have been starting up — its SUB socket missed the original
                # broadcast (PUB drops messages for unjoined subscribers).
                next_stop_broadcast = now + 1.0
                try:
                    self._control_socket.send(b'stop')
                except Exception:  # noqa: BLE001 - socket may already be closed
                    pass
            events = dict(poller.poll(200))
            if self._dispatch_socket in events:
                frames = self._dispatch_socket.recv_multipart()
                if len(frames) >= 4 and bytes(frames[1]) == b'ready':
                    self._handle_ready(frames)  # keep release routing current
            if self._results_socket in events:
                kind, payload = self._recv()
                if kind == MSG_RESULT_SHM:
                    try:
                        descriptor = ShmSlotDescriptor.from_bytes(
                            bytes(memoryview(payload[1])))
                    except Exception:  # noqa: BLE001 - shutdown drain is best-effort
                        continue
                    self._release_slot(descriptor)
                # every other kind (result/done/heartbeat/started/error) is
                # drained and dropped — the epoch is over

    def _release_ring(self):
        if self._ring is not None:
            try:
                self._ring.close_and_unlink()
            except Exception:  # noqa: BLE001 - cleanup must not mask the exit path
                logger.warning('failed to unlink the shm ring', exc_info=True)
            self._ring = None

    @property
    def diagnostics(self):
        serializer_stats = dict(getattr(self._serializer, 'stats', None) or {})
        with self._state_lock:
            wire_batches = self._wire_batches
            bytes_copied = (self._zmq_result_bytes
                            + serializer_stats.get('bytes_copied', 0))
            diag = {
                'workers_alive': sum(1 for p in self._processes if p.poll() is None),
                'workers_respawned': self._workers_respawned,
                'results_dropped': self._results_dropped,
                'in_flight_items': len(self._items),
                # --------------------------------- hang watchdog + integrity
                'workers_hung_reaped': self._workers_hung_reaped,
                'shm_crc_failures': self._shm_crc_failures,
                'shm_breaker': self._shm_breaker.as_dict(),
                # ------------------------- zero-copy data plane observability
                'shm_enabled': self._ring is not None,
                'shm_batches': self._shm_batches,
                'shm_fallback_batches': self._shm_fallback_batches,
                'shm_stale_drops': self._shm_stale_drops,
                'shm_bytes_mapped': self._shm_bytes_mapped,
                'zmq_result_bytes': self._zmq_result_bytes,
                'wire_batches': wire_batches,
                # bytes materialized into new host memory per delivered batch:
                # ZMQ-frame bytes copied off the wire + the serializer's receive-
                # side copies (unpickle payloads, writable column copies)
                'wire_bytes_copied': bytes_copied,
                'wire_bytes_copied_per_batch':
                    round(bytes_copied / wire_batches, 1) if wire_batches else 0.0,
                'sidecar_columns': serializer_stats.get('sidecar_columns', 0),
                'sidecar_column_names':
                    list(serializer_stats.get('sidecar_column_names', [])),
            }
        return diag
