"""Stage spans: named timing scopes over the data-plane pipeline stages.

A *stage* is one step a row batch passes through on its way to the device —
``fs_open``, ``rowgroup_read``, ``decode``, ``transform``, ``shuffle``,
``cache_hit`` / ``cache_miss`` / ``cache_store``, ``serialize``,
``shm_slot_wait`` / ``shm_map`` / ``shm_release``, ``shuffle_wait``, ``collate``,
``h2d``, ``device_decode`` / ``d2d_wait`` (the catalog with semantics:
docs/observability.md). Worker-side stages
execute in whatever process the pool runs them in, so their timings cannot be
written into the consumer's registry directly; instead each worker thread
accumulates them in a process-local :class:`StageRecorder` and the rowgroup
worker **drains** the accumulation into the published batch's ``telemetry``
sidecar — the same results-channel ride ``cache_hit`` takes — where
``Reader._note_item_consumed`` merges it into the consumer-side registry. One
snapshot therefore covers every process, and a respawned worker's fresh recorder
merges additively like any other (no double counting, no loss beyond the
unpublished in-flight item).

The recorder is sharded per THREAD (``threading.local``): a drain returns only
the calling thread's accumulation, so thread-pool workers never race each other,
and the serialize/slot-wait stages recorded by the process-pool worker main land
on the same thread that publishes the next batch (they ride one item late —
still the same process total).

Consumer-side stages (the pools' ``pool_wait`` / ``shm_map`` / ``wire_map``,
the reader's ``consume``, the loader's stages) pass the registry they belong to
(``stage_span(stage, registry=...)``). One :class:`stage_span` then writes the
same ``time.perf_counter`` pair to three views: the registry histogram, the
flight-recorder event when that recorder is armed, and — in a process that has
already imported ``jax`` — a ``jax.profiler`` TraceMe while a profiler session
collects, so the span lands on the device trace's clock under
:data:`PROFILER_NAMES` (docs/observability.md "Profiler spans").
"""

from __future__ import annotations

import sys
import threading
import time
from types import TracebackType
from typing import Any, Dict, List, Optional, Tuple, Type

from petastorm_tpu.telemetry import registry as _registry
from petastorm_tpu.telemetry import tracing as _tracing
from petastorm_tpu.telemetry.registry import (DEFAULT_NUM_BUCKETS, SECONDS_UNIT,
                                              bucket_index)

#: canonical stage names, pipeline order (docs/observability.md metric catalog)
STAGES = (
    'fs_open',        # filesystem construction / reconnect (worker)
    'rowgroup_read',  # Parquet rowgroup -> Arrow table (worker)
    'decode',         # codec decode, Arrow -> numpy columns (worker)
    'shuffle',        # in-rowgroup seeded permutation (worker)
    'transform',      # TransformSpec application (worker)
    'cache_hit',      # serving a decoded rowgroup from the cache (worker)
    'cache_miss',     # the full fill of a missed key — ENVELOPES read+decode
    'cache_store',    # writing a filled value to the cache (worker)
    'cache_corrupt',  # detecting+deleting a corrupt entry (worker; count = entries)
    'serialize',      # result -> wire frames (process-pool worker main)
    'shm_slot_wait',  # backpressure wait for a free ring slot (worker main)
    'shm_map',        # slot view + CRC verify + deserialize on the consumer (pool)
    'wire_map',       # deserialize of a result that rode the ZMQ wire (pool)
    'shm_release',    # slot ack back to the producing worker (pool)
    'pool_wait',      # consumer waiting in pool.get_results for a result
                      # message — ends where shm_map / wire_map begins (pool)
    'consume',        # the reader's per-item work after get_results returns:
                      # NGram rebuild, sidecar accounting, resume fast-forward
                      # (reader)
    'rebatch',        # slicing reader items into batches through the
                      # shuffling buffer (loader producer)
    'shuffle_wait',   # consumer blocked on the loader's prefetch queue (loader)
    'collate',        # host batch assembly / sanitize (loader)
    'h2d',            # host->device upload (loader)
    'device_decode',  # decode-tail work on raw-shipped fields: pack/inflate +
                      # jitted device decode dispatch, or the host fallback
                      # decode (loader; docs/performance.md)
    'd2d_wait',       # blocked on the prefetch-to-device ring: the oldest
                      # dispatched device batch had not finished (loader)
    'put_wait',       # the producer blocked on the full prefetch queue: the
                      # training loop is behind the input pipeline (loader)
    'decode_field',   # ONE field's kernel inside 'decode' — emitted to the
                      # flight-recorder timeline only (never a histogram),
                      # and only while tracing is armed: the per-field leg of
                      # the cost profiler (telemetry/cost_model.py)
    'range_fetch',    # one planned multi-range fetch of a rowgroup's column
                      # chunks (storage/fetcher.py) — disjoint from
                      # 'rowgroup_read', which covers only the Parquet
                      # decode of the already-fetched bytes when the storage
                      # engine is armed (docs/performance.md "Object-store
                      # ingest engine")
    'range_hedge',    # lifetime of one hedged duplicate GET, win or lose
                      # (storage/fetcher.py)
)

#: stages the loader times; their profiler events are named
#: ``petastorm_tpu.loader.<stage>``, every other stage's ``petastorm_tpu.<stage>``
LOADER_STAGES = frozenset({'rebatch', 'shuffle_wait', 'collate', 'h2d',
                           'device_decode', 'd2d_wait', 'put_wait'})

#: each stage's event name on the jax profiler's host plane. ``shuffle_wait``
#: is the training loop's input wait, which benchmarks/trace.py names idle
#: device gaps by as ``petastorm_tpu.loader.wait_input``
PROFILER_NAMES = {stage: ('petastorm_tpu.loader.' if stage in LOADER_STAGES
                          else 'petastorm_tpu.') + stage for stage in STAGES}
PROFILER_NAMES['shuffle_wait'] = 'petastorm_tpu.loader.wait_input'

#: stages whose span ENVELOPES other recorded stages (cache_miss wraps
#: rowgroup_read+decode) — excluded from time-share attribution so shares of the
#: leaf stages sum sensibly (telemetry/analyze.py)
ENVELOPE_STAGES = frozenset({'cache_miss'})

#: declared event counters (``registry.inc(name)`` call sites). Part of the
#: telemetry name catalog alongside STAGES: pipecheck's telemetry-names rule
#: (docs/static-analysis.md) rejects any ``inc`` of a name not listed here,
#: so a typo'd counter fails the tier-1 self-check instead of silently
#: minting an orphan metric.
COUNTERS = (
    'breaker_open',    # a circuit breaker tripped open (pool consumer side)
    'watchdog_reap',   # a hung worker was SIGKILLed by the watchdog (pool)
    'shm_crc_fail',    # a shm frame failed CRC verification (pool)
    'service_busy',    # the input service rejected a submit (admission control)
    'service_resubmit',  # a service item was re-requested (lost shm segment)
    'slo_breach',      # input-efficiency fell below the SLO target (edge-
                       # triggered: one count per ok->breach transition —
                       # telemetry/slo.py, docs/observability.md)
    'lineage_divergence',  # a delivered item broke the expected lineage
                           # stream (unknown/duplicate delivery, resume
                           # mismatch) — telemetry/lineage.py,
                           # docs/observability.md "Sample lineage"
    'incidents_captured',      # an incident bundle was written (edge-
                               # triggered black-box capture —
                               # telemetry/incident.py, docs/observability.md
                               # "Incident autopsy plane")
    'incidents_rate_limited',  # an incident trigger was dropped by the
                               # per-kind token bucket (telemetry/incident.py)
    'ledger_frames_dropped',   # dispatcher-ledger journal frames that failed
                               # CRC replay (service/ledger.py — the loud
                               # half of degrade-to-replay-from-clients)
    'storage_footer_cache_hit',   # a Parquet footer was served from the
                                  # metadata cache (storage/metadata_cache.py)
    'storage_footer_cache_miss',  # a footer had to be read from storage
    'storage_ranges_coalesced',   # raw column-chunk ranges merged away by
                                  # gap-threshold coalescing (storage/
                                  # range_planner.py; count = raw - merged)
    'storage_hedge_fired',        # a hedged duplicate GET was launched
                                  # (storage/fetcher.py)
    'storage_hedge_won',          # the hedge returned before the primary
                                  # (its bytes were committed; the primary's
                                  # were dropped)
    'perf_regression',            # the live regression sentinel's drift test
                                  # fired on a goodput collapse / wait-share
                                  # growth (edge-triggered: one count per
                                  # alarm — telemetry/sentinel.py,
                                  # docs/observability.md "Longitudinal
                                  # observatory")
    'history_record_written',     # one run record was appended to the
                                  # longitudinal run-history store
                                  # (telemetry/history.py)
    'history_frames_dropped',     # run-history journal frames that failed
                                  # CRC replay (torn tail / flipped byte —
                                  # telemetry/history.py)
    'host_reshard',               # a reader joined as a reshard survivor —
                                  # undelivered rowgroups were re-dealt
                                  # after a host join/leave/lease expiry
                                  # (parallel/topology.py,
                                  # docs/robustness.md "Elastic pod-scale
                                  # sharding")
    'topology_frames_dropped',    # membership-journal frames that failed
                                  # CRC replay (torn tail / flipped byte —
                                  # parallel/topology.py)
)

#: declared size histograms (``registry.observe(name, n, unit=BYTES_UNIT)``
#: call sites) — same catalog contract as COUNTERS
SIZE_HISTOGRAMS = (
    'wire_bytes_copied',  # bytes materialized into new host memory per batch
)

#: declared flight-recorder instant events (``tracing.trace_instant(name)``
#: call sites — docs/observability.md "Flight recorder"). Same catalog
#: contract as COUNTERS: pipecheck's telemetry-names rule rejects any
#: ``trace_instant`` of a name not listed here, so anomaly markers cannot
#: silently drift from the timeline legend.
TRACE_INSTANTS = (
    'ventilate',           # a work item entered the pool (consumer, ventilator thread)
    'rowgroup_consumed',   # the item's result was popped and accounted (consumer)
    'quarantine',          # a rowgroup was quarantined (worker, or consumer hang path)
    'watchdog_reap',       # a hung worker was SIGKILLed by the watchdog (consumer)
    'worker_respawn',      # a dead worker's in-flight item was re-ventilated (consumer)
    'breaker_transition',  # a circuit breaker changed state (any process)
    'shm_crc_drop',        # a shm frame failed CRC and was dropped unread (consumer)
    'shm_fallback',        # a result rode the ZMQ wire while the shm ring was enabled
    'autotune_decision',   # the closed-loop autotuner proposed/committed/reverted/froze a knob change (controller)
    'slo_breach',          # input-efficiency fell below the SLO target (consumer; telemetry/slo.py)
    'schedule_plan',       # the cost-aware scheduler planned one epoch's ventilation order (ventilator thread; schedule/cost_schedule.py)
    'lineage_divergence',  # a delivered item broke the expected lineage stream (consumer; telemetry/lineage.py)
    'incident_captured',   # an incident bundle was written at this point on the timeline (telemetry/incident.py)
    'reshard',             # undelivered service work was re-split across a changed worker set (dispatcher; service/dispatcher.py)
    'ledger_replay',       # a restarting dispatcher replayed its durable token ledger (service/ledger.py)
    'perf_regression',     # the live regression sentinel fired mid-run (consumer/dispatcher; telemetry/sentinel.py)
    'host_reshard',        # a reader joined as a host-reshard survivor after a topology change (consumer; parallel/topology.py)
)

#: declared gauge ids (``registry.gauge(name)`` call sites with literal
#: names, plus the service scheduler's snapshot gauges) — same catalog
#: contract as COUNTERS: pipecheck's telemetry-names rule rejects a
#: ``gauge('x')`` of a name not listed here
GAUGES = (
    'slo_efficiency',          # latest evaluated input efficiency [0,1] (slo.py)
    'slo_target_efficiency',   # the SLO target the efficiency is held against
    'service_queue_depth',       # accepted items queued fleet-wide (dispatcher)
    'service_ready_workers',     # idle decode workers (dispatcher)
    'service_workers',           # registered decode workers (dispatcher)
    'service_admission_window',  # per-client in-flight cap (dispatcher)
    'service_client_window',     # smallest live client window (dispatcher)
    'lineage_items_folded',      # items folded into the order digest so far
                                 # (reader scrape; telemetry/lineage.py)
    'lineage_pending_items',     # delivered-out-of-order items awaiting
                                 # their fold slot (reader scrape)
    'sentinel_rate_ewma',        # the regression sentinel's smoothed windowed
                                 # rows/s (telemetry/sentinel.py)
    'sentinel_wait_share_ewma',  # the sentinel's smoothed primary-wait share
                                 # of each window (telemetry/sentinel.py)
)


class StageRecorder(object):
    """Per-thread accumulation of stage timings, drained into batch sidecars.

    Each thread owns a private ``{stage: [count, sum, max, {bucket: n}]}`` dict;
    ``record`` appends to it without locks and ``drain`` atomically (per thread)
    hands it off as a JSON-safe ``{stage: histogram_snapshot}`` mapping that
    :meth:`MetricsRegistry.merge_stage_times` understands."""

    __slots__ = ('_local',)

    def __init__(self) -> None:
        self._local = threading.local()

    def _cells(self) -> Dict[str, List[Any]]:
        cells = getattr(self._local, 'cells', None)
        if cells is None:
            cells = {}
            self._local.cells = cells
        return cells

    def record(self, stage: str, seconds: float) -> None:
        """Accumulate one observation of ``stage`` for the calling thread."""
        if not _registry.telemetry_enabled():
            return
        cells = self._cells()
        cell = cells.get(stage)
        if cell is None:
            cell = [0, 0.0, 0.0, {}]
            cells[stage] = cell
        cell[0] += 1
        cell[1] += seconds
        if seconds > cell[2]:
            cell[2] = seconds
        idx = bucket_index(seconds, SECONDS_UNIT, DEFAULT_NUM_BUCKETS)
        cell[3][idx] = cell[3].get(idx, 0) + 1

    def drain(self) -> Optional[Dict[str, Dict[str, Any]]]:
        """Hand off and clear the calling thread's accumulation (None if empty)."""
        cells = getattr(self._local, 'cells', None)
        if not cells:
            return None
        self._local.cells = {}
        return {stage: {'unit': SECONDS_UNIT, 'count': cell[0], 'sum': cell[1],
                        'max': cell[2],
                        'buckets': {str(i): n for i, n in cell[3].items()}}
                for stage, cell in cells.items()}


#: the process-wide recorder every data-plane stage writes to (worker side)
_process_recorder = StageRecorder()


def record_stage(stage: str, seconds: float,
                 trace_args: Optional[Dict[str, Any]] = None) -> None:
    """Record one observation, measured before the call, into the
    process-wide stage recorder (and, when the flight recorder is armed, a
    matching trace event back-dated by the measured duration —
    docs/observability.md "Flight recorder"). The profiler cannot back-date
    an event, so a stage that should appear on its clock is timed with
    :class:`stage_span` instead. ``trace_args`` rides only the trace event
    (never the histogram) — the storage engine uses it to ship per-fetch
    byte/range/hedge totals to the cost ledger (telemetry/cost_model.py)."""
    _process_recorder.record(stage, seconds)
    if _tracing.trace_enabled():
        _tracing.trace_complete(stage, time.perf_counter() - seconds, seconds,
                                args=trace_args)


def drain_stage_times() -> Optional[Dict[str, Dict[str, Any]]]:
    """Drain the calling thread's accumulated stage times (for batch sidecars)."""
    return _process_recorder.drain()


#: jax's TraceMe class, looked up once this process has imported jax
_trace_me_class: Any = None


def _profiler_trace_me() -> Any:
    """The ``jax.profiler`` TraceMe class, or None in a process that has not
    imported jax: a span never imports jax itself, so pool worker processes
    stay free of it."""
    global _trace_me_class
    if _trace_me_class is None and 'jax' in sys.modules:
        from jax.profiler import TraceAnnotation
        _trace_me_class = TraceAnnotation
    return _trace_me_class


def _ctx_metadata(ctx: Optional[Tuple[int, int, int]]) -> Dict[str, int]:
    if ctx is None:
        return {}
    return {'epoch': ctx[0], 'rowgroup': ctx[1], 'attempt': ctx[2]}


class stage_span(object):
    """One timed stage: ``with stage_span('decode'): ...``, or
    ``span = stage_span('pool_wait', registry=r).begin()`` ... ``span.end()``
    where the scope is not a block.

    One ``time.perf_counter`` pair feeds every view: the histogram
    (``registry``, else the process recorder that worker sidecars drain), the
    flight-recorder event when that recorder is armed, and a ``jax.profiler``
    TraceMe named :data:`PROFILER_NAMES` ``[stage]`` while a profiler session
    collects in a process that has imported jax (one inactive-TraceMe check
    otherwise). ``ctx`` is the causal ``(epoch, rowgroup, attempt)`` context
    of both events; the TraceMe carries it as metadata, encoded only while a
    profile is collected. ``start_s`` / ``end_s`` / ``seconds`` expose the
    measurement to callers that keep their own counters. Exceptions
    propagate; the partial duration is still recorded (a stage that died slow
    is exactly the signal the bottleneck report wants)."""

    __slots__ = ('_stage', '_registry', '_ctx', '_trace_me', 'start_s',
                 'end_s')

    def __init__(self, stage: str,
                 registry: Optional[_registry.MetricsRegistry] = None,
                 ctx: Optional[Tuple[int, int, int]] = None) -> None:
        self._stage = stage
        self._registry = registry
        self._ctx = ctx
        self._trace_me: Any = None
        self.start_s = 0.0
        self.end_s = 0.0

    @property
    def seconds(self) -> float:
        """The span's duration (0 until it ended)."""
        return self.end_s - self.start_s if self.end_s else 0.0

    def begin(self) -> 'stage_span':
        """Start the span (again, after :meth:`end`: a wait that resumes is
        one more observation); returns it."""
        self.end_s = 0.0
        trace_me = _profiler_trace_me()
        if trace_me is not None and trace_me.is_enabled():
            self._trace_me = trace_me(
                PROFILER_NAMES.get(self._stage, 'petastorm_tpu.' + self._stage),
                **_ctx_metadata(self._ctx))
            self._trace_me.__enter__()
        self.start_s = time.perf_counter()
        return self

    def set_context(self, ctx: Tuple[int, int, int]) -> None:
        """Tag the span with a causal context learned while it runs (the
        ``shm_map`` span learns its batch's rowgroup from the payload)."""
        self._ctx = ctx
        if self._trace_me is not None:
            self._trace_me.set_metadata(**_ctx_metadata(ctx))

    def end(self, record: bool = True) -> None:
        """End the span and record it; only the first call records.
        ``record=False`` ends it without a histogram or flight-recorder
        entry (a wait that ended without what it waited for); a profiler
        TraceMe, once begun, cannot be withdrawn and still shows the time."""
        if self.end_s or not self.start_s:
            return
        self.end_s = time.perf_counter()
        if self._trace_me is not None:
            self._trace_me.__exit__(None, None, None)
            self._trace_me = None
        if not record:
            return
        duration = self.end_s - self.start_s
        if self._registry is None:
            _process_recorder.record(self._stage, duration)
        else:
            self._registry.observe(self._stage, duration)
        if _tracing.trace_enabled():
            _tracing.trace_complete(self._stage, self.start_s, duration,
                                    ctx=self._ctx)

    def __enter__(self) -> 'stage_span':
        return self.begin()

    def __exit__(self, exc_type: Optional[Type[BaseException]],
                 exc: Optional[BaseException],
                 tb: Optional[TracebackType]) -> None:
        self.end()
