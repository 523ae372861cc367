"""Environment doctor: one command that answers "is this install healthy and
what will it be fast at?".

``petastorm-tpu-doctor`` (or ``python -m petastorm_tpu.tools.doctor``) checks,
in order:

1. **Versions** — python / jax / pyarrow / numpy (flax, optax, orbax if present).
2. **Accelerator backend** — probed in a SUBPROCESS with a hard timeout:
   backend init can *hang* rather than fail, and a doctor that wedges on the
   exact condition it exists to diagnose is useless. The doctor's own process
   never initializes a backend, so the child is the only process on the chip.
3. **Link characterization** — dispatch RTT + H2D/D2H bandwidth
   (:mod:`petastorm_tpu.benchmark.linkprobe`) when a device is up, plus the
   implied per-batch streaming ceiling for a reference 1 KiB row — this is the
   number that says whether streaming or HBM-resident (``scan_epochs``)
   configurations fit today's link.
4. **Store roundtrip** — write a small dataset to a temp dir through the real
   codec/metadata path, read it back with ``make_reader`` across the thread
   pool, verify row integrity, report rows/s.
5. **Pipecheck** — the static data-plane invariant analysis
   (:mod:`petastorm_tpu.analysis`, docs/static-analysis.md) over the
   installed package; findings print as a WARNING (``report['pipecheck']``).
6. **Input service** — when ``--service-url`` (or the
   ``PETASTORM_TPU_SERVICE_URL`` env var) names a disaggregated input
   service (docs/service.md), probe its dispatcher: reachable? workers
   registered? queue depth? An unreachable configured service prints a
   WARNING (``report['service']``) — readers pointed at it will fail.
7. **Topology** — when ``--topology-journal`` (or the
   ``PETASTORM_TPU_TOPOLOGY_JOURNAL`` env var) names an elastic-sharding
   membership journal (docs/robustness.md "Elastic pod-scale sharding"),
   replay it: generation, members, stale leases (WARNING — a host crashed
   without a leave record), torn frames dropped by CRC (WARNING).

Prints a human-readable report; with ``--json``, one machine-readable JSON
line (the same dict :func:`collect_report` returns). Exit code 0 iff the
store roundtrip passed — that is the install-health criterion. Backend DOWN
and link-probe failures are reported as warnings, not failures: they describe
the attached environment (CPU development installs are healthy installs; a
flaky link is the environment's fault, and diagnosing it is this tool's
job, not a reason for it to fail).

The reference ships per-task CLIs (generate-metadata, copy-dataset,
throughput); the doctor composes this repo's equivalents into the first
command to run on a new box.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import tempfile
import time

PROBE_CODE = (
    "import jax\n"
    "ds = jax.devices()\n"
    "print(ds[0].platform, len(ds))\n")

# Link probe child: a device that wedges AFTER the backend probe subprocess
# succeeded would hang the doctor in-process on exactly the condition it exists
# to diagnose. Same subprocess+timeout pattern as check_backend; the tagged last
# line survives banner noise on stdout.
LINK_PROBE_CODE = (
    "import json\n"
    "from petastorm_tpu.benchmark.linkprobe import (\n"
    "    probe_link, streaming_ceiling_rows_per_sec)\n"
    "link = probe_link(sizes_mb=(1, 4), dispatch_iters=10, transfer_iters=3)\n"
    "link['streaming_ceiling_rows_per_sec_at_1kib'] = round(\n"
    "    streaming_ceiling_rows_per_sec(link, {row_bytes}, {batch}), 1)\n"
    "print('LINKPROBE_JSON ' + json.dumps(link))\n")


def check_versions():
    """Importable-library report; missing optional libraries are reported, not
    fatal."""
    import numpy
    import pyarrow
    report = {'python': sys.version.split()[0],
              'numpy': numpy.__version__,
              'pyarrow': pyarrow.__version__}
    import importlib
    for name in ('jax', 'flax', 'optax', 'orbax.checkpoint', 'torch',
                 'tensorflow'):
        try:
            # import_module resolves the dotted submodule (orbax.checkpoint's
            # version lives there; the bare orbax namespace package has none)
            mod = importlib.import_module(name)
            report[name.split('.')[0]] = getattr(mod, '__version__', 'present')
        except Exception:  # noqa: BLE001 - absence is information, not error
            report[name.split('.')[0]] = None
    from petastorm_tpu import __version__ as pt_version
    report['petastorm_tpu'] = pt_version
    return report


def _probe_subprocess(code, timeout_s, timeout_detail, env=None):
    """Run probe ``code`` in a subprocess with a hard timeout.

    Returns ``(completed_process, None)`` on a clean exit, else
    ``(None, error_dict)`` with ``status`` 'timeout'/'down' and a ``detail``
    drawn from the child's stderr tail — the shared scaffolding for every
    doctor check that must survive a wedged device."""
    try:
        out = subprocess.run([sys.executable, '-c', code], env=env,
                             capture_output=True, text=True, timeout=timeout_s)
    except subprocess.TimeoutExpired:
        return None, {'status': 'timeout',
                      'detail': timeout_detail.format(timeout_s)}
    if out.returncode != 0:
        return None, {'status': 'down',
                      'detail': out.stderr.strip().splitlines()[-1][:200]
                      if out.stderr.strip() else 'unknown'}
    return out, None


def check_backend(timeout_s=60):
    """Probe ``jax.devices()`` in a subprocess with a hard timeout.

    Returns ``{'status': 'up'|'down'|'timeout', 'platform': ..., 'devices': N}``.
    """
    out, error = _probe_subprocess(
        PROBE_CODE, timeout_s,
        'backend init exceeded {}s — device unreachable?')
    if error is not None:
        error.update(platform=None, devices=0)
        return error
    # parse the LAST line only: accelerator plugins/libtpu may write banner
    # text to the child's stdout before the probe's own print
    try:
        platform, n = out.stdout.strip().splitlines()[-1].split()
        return {'status': 'up', 'platform': platform, 'devices': int(n)}
    except (IndexError, ValueError):
        return {'status': 'down', 'platform': None, 'devices': 0,
                'detail': 'unparseable probe output: {!r}'.format(
                    out.stdout.strip()[-200:])}


def check_link(reference_row_bytes=1024, reference_batch=1024, timeout_s=180):
    """Link probe + the per-batch streaming ceiling it implies, run in a
    subprocess with a hard timeout (only call when the backend is up).

    A hang — a wedged device or link, which can start *between*
    the backend probe and this measurement — is reported as
    ``{'status': 'timeout', ...}``, a link failure, instead of wedging the
    doctor."""
    code = LINK_PROBE_CODE.format(row_bytes=int(reference_row_bytes),
                                  batch=int(reference_batch))
    env = dict(os.environ)
    # the child must find petastorm_tpu even when the doctor runs from a
    # source checkout that was put on sys.path by hand
    pkg_root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    existing = env.get('PYTHONPATH', '')
    # no trailing separator when PYTHONPATH was unset: an empty entry means
    # cwd, where a stray jax.py/json.py would shadow the real module
    env['PYTHONPATH'] = (pkg_root + os.pathsep + existing if existing
                         else pkg_root)
    out, error = _probe_subprocess(
        code, timeout_s,
        'link probe exceeded {}s — device wedged after backend probe?',
        env=env)
    if error is not None:
        if error['status'] == 'down':
            error['status'] = 'fail'  # backend was up; this is a link failure
        return error
    for line in reversed(out.stdout.strip().splitlines()):
        if line.startswith('LINKPROBE_JSON '):
            try:
                return json.loads(line[len('LINKPROBE_JSON '):])
            except ValueError:
                break
    return {'status': 'fail',
            'detail': 'unparseable link probe output: {!r}'.format(
                out.stdout.strip()[-200:])}


def check_store_roundtrip(rows=200, workers=2):
    """Write a real store (scalar + ndarray codecs) to a temp dir, read it back
    through ``make_reader``, verify integrity, report rows/s."""
    import numpy as np
    import pyarrow as pa

    from petastorm_tpu import make_reader
    from petastorm_tpu.codecs import NdarrayCodec, ScalarCodec
    from petastorm_tpu.etl.dataset_metadata import write_rows
    from petastorm_tpu.unischema import Unischema, UnischemaField

    schema = Unischema('DoctorSchema', [
        UnischemaField('idx', np.int64, (), ScalarCodec(pa.int64()), False),
        UnischemaField('vec', np.float32, (8,), NdarrayCodec(), False),
    ])
    # Flight recorder armed for the roundtrip (docs/observability.md "Flight
    # recorder"): the doctor's trace summary is the per-rowgroup view of the
    # same read the telemetry block aggregates — restored (and the ring
    # cleared) afterwards so the doctor leaves no armed recorder behind.
    from petastorm_tpu.telemetry import tracing
    trace_was_enabled = tracing.trace_enabled()
    try:
        # armed INSIDE the restoring try: a tempdir/write failure must not
        # leave the recorder running process-wide. When the doctor itself arms
        # the recorder, it also clears it first so the summary covers ONLY
        # this roundtrip (a user-armed capture — PETASTORM_TPU_TRACE=1 — is
        # left intact and the summary then spans their whole recording).
        if not trace_was_enabled:
            tracing.reset_tracing()
        tracing.set_trace_enabled(True)
        with tempfile.TemporaryDirectory(prefix='petastorm_tpu_doctor_') as tmp:
            url = 'file://' + tmp
            write_rows(url, schema,
                       ({'idx': i, 'vec': np.full(8, i, np.float32)}
                        for i in range(rows)),
                       rowgroup_size_mb=1)
            start = time.perf_counter()
            seen = []
            # on_error='retry': the roundtrip doubles as a probe of the resilience
            # path — a flaky local disk shows up as a non-zero retry count in the
            # report rather than an opaque failure (docs/robustness.md).
            # autotune armed with a long window (docs/autotuning.md): the
            # roundtrip is far shorter than one control window, so no knob is
            # ever turned — the block proves the controller wires up (knob
            # catalog, breaker interlock state) without perturbing the probe.
            from petastorm_tpu.autotune import AutotunePolicy
            # lineage armed manifest-less (docs/observability.md "Sample
            # lineage"): the block proves the audit plane folds a clean
            # digest with zero divergence on this install, without leaving
            # a manifest file in the temp store.
            from petastorm_tpu.telemetry.lineage import LineagePolicy
            # history armed into a temp store (docs/observability.md
            # "Longitudinal observatory"): the block proves the run
            # historian's append + CRC replay on this install without
            # leaving a store behind.
            hist_path = os.path.join(tmp, 'run_history.bin')
            with make_reader(url, workers_count=workers, num_epochs=1,
                             on_error='retry',
                             lineage=LineagePolicy(manifest=False),
                             history=hist_path,
                             autotune=AutotunePolicy(window_s=3600.0)) as reader:
                for row in reader:
                    seen.append(int(row.idx))
                    if row.vec[0] != row.idx:
                        return {'status': 'fail',
                                'detail': 'row {} decoded wrong vec'.format(row.idx)}
                diag = reader.diagnostics
                telemetry = reader.telemetry_snapshot()
                trace = reader.trace_summary()
                autotune = reader.autotune_report()
                slo = reader.efficiency_report()
                lineage = diag.get('lineage')
                sentinel = diag.get('sentinel')
            elapsed = time.perf_counter() - start
            history = check_history(hist_path, sentinel)
    finally:
        tracing.set_trace_enabled(trace_was_enabled)
        if not trace_was_enabled:
            tracing.reset_tracing()
    if sorted(seen) != list(range(rows)):
        return {'status': 'fail',
                'detail': 'expected {} distinct rows, got {}'.format(
                    rows, len(set(seen)))}
    return {'status': 'ok', 'rows': rows,
            'rows_per_sec': round(rows / elapsed, 1),
            'io_retries': diag.get('io_retries', 0),
            'rowgroups_quarantined': diag.get('rowgroups_quarantined', 0),
            'quarantine': diag.get('quarantine', []),
            'telemetry': telemetry,
            # lifted to report['trace'] by collect_report — the flight-recorder
            # summary of docs/observability.md "Flight recorder"
            'trace': trace,
            # lifted to report['autotune'] by collect_report — the closed-loop
            # controller's state (docs/autotuning.md)
            'autotune': autotune,
            # lifted to report['slo'] by collect_report — the input-efficiency
            # SLO evaluation of docs/observability.md "Efficiency SLOs"
            'slo': slo,
            # lifted to report['lineage'] by collect_report — the sample-
            # lineage audit of docs/observability.md "Sample lineage"
            'lineage': lineage,
            # lifted to report['history'] by collect_report — the run
            # historian + regression sentinel of docs/observability.md
            # "Longitudinal observatory"
            'history': history,
            # lifted to report['resilience'] by collect_report — the hang/
            # integrity/breaker view of docs/robustness.md
            'resilience': {
                'breakers': diag.get('breakers', {}),
                'workers_hung_reaped': diag.get('workers_hung_reaped', 0),
                'shm_crc_failures': diag.get('shm_crc_failures', 0),
                'cache_corrupt_entries':
                    diag.get('cache', {}).get('corrupt_entries', 0),
                'rowgroups_quarantined': diag.get('rowgroups_quarantined', 0),
            }}


def check_storage(rows=64, workers=1):
    """Force-arm the object-store ingest engine (docs/performance.md
    "Object-store ingest engine") over a tiny local store and report its
    counters: footer-cache hits/misses, ranges coalesced away, hedges
    fired/won. On a healthy local disk hedges should essentially never
    fire — the human report WARNS when the hedge-win rate exceeds 50%,
    because storage that tail-heavy means every other fetch is racing a
    straggler and the hedge deadline is doing the store's job."""
    import numpy as np
    import pyarrow as pa

    from petastorm_tpu import make_reader
    from petastorm_tpu.codecs import ScalarCodec
    from petastorm_tpu.etl.dataset_metadata import write_rows
    from petastorm_tpu.storage import (reset_storage_metrics,
                                       storage_metrics_snapshot)
    from petastorm_tpu.telemetry.registry import (set_telemetry_enabled,
                                                  telemetry_enabled)
    from petastorm_tpu.unischema import Unischema, UnischemaField

    schema = Unischema('DoctorStorageSchema', [
        UnischemaField('idx', np.int64, (), ScalarCodec(pa.int64()), False),
        UnischemaField('val', np.float64, (), ScalarCodec(pa.float64()), False),
    ])
    was_enabled = telemetry_enabled()
    set_telemetry_enabled(True)   # counters are gated on the kill switch
    reset_storage_metrics()       # this probe's reads only
    try:
        with tempfile.TemporaryDirectory(prefix='petastorm_tpu_doctor_') as tmp:
            url = 'file://' + tmp
            write_rows(url, schema,
                       ({'idx': i, 'val': float(i)} for i in range(rows)),
                       rowgroup_size_mb=1)
            seen = []
            # storage_policy=True force-arms the engine on the local store
            # (auto-engage is non-local-schemes only); two epochs so the
            # second one exercises the footer cache's hit path.
            with make_reader(url, workers_count=workers, num_epochs=2,
                             storage_policy=True) as reader:
                for row in reader:
                    seen.append(int(row.idx))
        counters = storage_metrics_snapshot().get('counters', {})
    finally:
        set_telemetry_enabled(was_enabled)
        reset_storage_metrics()   # don't leak probe counts into real reads
    if sorted(set(seen)) != list(range(rows)):
        return {'status': 'fail',
                'detail': 'engine-armed read returned {} distinct rows, '
                          'expected {}'.format(len(set(seen)), rows)}
    fired = int(counters.get('storage_hedge_fired', 0))
    won = int(counters.get('storage_hedge_won', 0))
    return {'status': 'ok',
            'footer_cache_hits': int(counters.get('storage_footer_cache_hit', 0)),
            'footer_cache_misses': int(counters.get('storage_footer_cache_miss', 0)),
            'ranges_coalesced': int(counters.get('storage_ranges_coalesced', 0)),
            'hedges_fired': fired,
            'hedges_won': won,
            'hedge_win_rate': round(won / fired, 3) if fired else 0.0}


def check_service(service_url=None, timeout_s=2.0):
    """Probe the disaggregated input service (docs/service.md) when one is
    configured — ``service_url`` argument or the ``PETASTORM_TPU_SERVICE_URL``
    env var. Returns ``{'status': 'unconfigured'}`` when no URL is set,
    ``{'status': 'ok', 'workers': N, 'clients': N, 'queue_depth': N, ...}``
    when the dispatcher answers a state request, or ``{'status':
    'unreachable', 'detail': ...}`` — which the human report prints as a
    WARNING: a reader pointed at that URL will fail its hello."""
    url = service_url or os.environ.get('PETASTORM_TPU_SERVICE_URL')
    if not url:
        return {'status': 'unconfigured'}
    # tripped client-transport breakers registered by any ServicePool this
    # process created (they live on the default board so they surface here
    # and in Reader.diagnostics through one mechanism)
    from petastorm_tpu.resilience import default_board
    breakers = {name: state for name, state
                in default_board().snapshot(only_tripped=True).items()
                if name.startswith('service:')}
    try:
        from petastorm_tpu.service.service_client import fetch_service_state
        state = fetch_service_state(url, timeout_s=timeout_s)
    except Exception as exc:  # noqa: BLE001 - unreachability is the finding, not a doctor failure
        return {'status': 'unreachable', 'service_url': url,
                'detail': repr(exc), 'breakers': breakers}
    workers = state.get('workers') or []
    return {'status': 'ok', 'service_url': url,
            'workers': len(workers),
            'clients': len(state.get('clients') or []),
            'queue_depth': state.get('queue_depth', 0),
            'in_flight': state.get('in_flight', 0),
            'busy_rejections': state.get('busy_rejections', 0),
            'items_requeued': state.get('items_requeued', 0),
            'workers_departed': state.get('workers_departed', 0),
            'breakers': breakers,
            'state': state}


def check_pipecheck():
    """Run the pipecheck static analysis over the installed package
    (docs/static-analysis.md) and summarize: ``{'status': 'ok'|'findings',
    'findings': N, 'suppressed': M, 'files': F, 'by_rule': {...}}``.

    Static findings mean the *installed code* has drifted from its own
    data-plane invariants (protocol kinds, telemetry names, the mypy
    ratchet) — a WARNING in the human report, not an install-health failure:
    reads still work, but the next refactor is flying blind."""
    from petastorm_tpu.analysis import run_pipecheck
    report = run_pipecheck()
    return {'status': 'ok' if report.clean else 'findings',
            'findings': len(report.findings),
            'suppressed': report.suppressed,
            'files': report.files,
            'callgraph_functions': report.callgraph_functions,
            'by_rule': report.by_rule(),
            'first': report.findings[0].format() if report.findings else None}


def collect_report(probe_timeout_s=60, link=True, link_timeout_s=180,
                   service_url=None, topology_journal=None):
    """Run every check; returns the full report dict (no printing)."""
    report = {'versions': check_versions()}
    report['backend'] = check_backend(timeout_s=probe_timeout_s)
    if link and report['backend']['status'] == 'up':
        try:
            report['link'] = check_link(timeout_s=link_timeout_s)
        except Exception as exc:  # noqa: BLE001 - link probe is best-effort
            report['link'] = {'status': 'fail', 'detail': repr(exc)}
    try:
        report['store_roundtrip'] = check_store_roundtrip()
    except Exception as exc:  # noqa: BLE001 - the report must always complete
        report['store_roundtrip'] = {'status': 'fail', 'detail': repr(exc)}
    # Pipeline telemetry (docs/observability.md): the roundtrip reader's
    # cross-process stage snapshot + the bottleneck attribution it implies —
    # the doctor's answer to "what will this install's input pipeline be slow
    # at". Lifted to report level so --json consumers find one stable key.
    snapshot = report['store_roundtrip'].pop('telemetry', None)
    if snapshot is not None:
        from petastorm_tpu.telemetry.analyze import attribute_bottleneck
        report['telemetry'] = {'snapshot': snapshot,
                               'bottleneck': attribute_bottleneck(snapshot)}
    # Flight-recorder block (docs/observability.md "Flight recorder"): event
    # counts, dropped-event count, anomaly instants and the top-5 longest
    # rowgroup traces of the roundtrip read. Always present so --json
    # consumers find one stable key.
    trace = report['store_roundtrip'].pop('trace', None)
    if trace is None:
        # one stable schema either way: the empty summary IS the summarizer's
        # own empty-snapshot output, so the two paths cannot drift apart
        from petastorm_tpu.telemetry.trace_export import summarize_trace
        trace = summarize_trace({})
    report['trace'] = trace
    # Resilience block (docs/robustness.md): breaker states + hung-reap/corrupt
    # counts, lifted to report level so --json consumers find one stable key.
    # Always present — dashboards alert on it without key-existence checks.
    resilience = report['store_roundtrip'].pop('resilience', None)
    report['resilience'] = resilience if resilience is not None else {
        'breakers': {}, 'workers_hung_reaped': 0, 'shm_crc_failures': 0,
        'cache_corrupt_entries': 0, 'rowgroups_quarantined': 0}
    # Autotune block (docs/autotuning.md): the roundtrip controller's state —
    # knob catalog, decision log, frozen-by-breaker flag. Always present so
    # --json consumers find one stable key.
    autotune = report['store_roundtrip'].pop('autotune', None)
    report['autotune'] = autotune if autotune is not None else {
        'enabled': False}
    # Input-efficiency SLO block (docs/observability.md "Efficiency SLOs"):
    # the roundtrip reader's efficiency-vs-target evaluation. Always present
    # so --json consumers find one stable key.
    slo = report['store_roundtrip'].pop('slo', None)
    report['slo'] = slo if slo is not None else {'evaluated': False}
    # Sample-lineage block (docs/observability.md "Sample lineage &
    # determinism audit"): the roundtrip reader's order digest + divergence
    # count. Always present so --json consumers find one stable key.
    lineage = report['store_roundtrip'].pop('lineage', None)
    report['lineage'] = lineage if lineage is not None else {
        'enabled': False}
    # Longitudinal-observatory block (docs/observability.md "Longitudinal
    # observatory"): the roundtrip's run-history store replayed — record
    # landed, zero CRC drops, sentinel armed. Always present so --json
    # consumers find one stable key.
    history = report['store_roundtrip'].pop('history', None)
    report['history'] = history if history is not None else {
        'status': 'unprobed', 'records': 0, 'frames_dropped': 0,
        'sentinel_armed': False}
    # Static-analysis block (docs/static-analysis.md): does the installed
    # package still satisfy its own data-plane invariants? Always present so
    # --json consumers find one stable key; failures of the analyzer itself
    # are reported, never fatal to the doctor.
    try:
        report['pipecheck'] = check_pipecheck()
    except Exception as exc:  # noqa: BLE001 - the report must always complete
        report['pipecheck'] = {'status': 'fail', 'detail': repr(exc)}
    # Input-service block (docs/service.md): when PETASTORM_TPU_SERVICE_URL
    # (or --service-url) names a dispatcher, is it reachable and how does its
    # fleet look? Always present so --json consumers find one stable key;
    # an unconfigured service is a healthy install.
    try:
        report['service'] = check_service(service_url)
    except Exception as exc:  # noqa: BLE001 - the report must always complete
        report['service'] = {'status': 'fail', 'detail': repr(exc)}
    # Durable-ledger block (docs/service.md "Failure modes"): when the
    # probed dispatcher journals its token lifecycle, how did its last
    # restart go — journal present, last replay result, frames dropped by
    # CRC? Always present so --json consumers find one stable key.
    try:
        report['ledger'] = check_ledger(report.get('service'))
    except Exception as exc:  # noqa: BLE001 - the report must always complete
        report['ledger'] = {'status': 'fail', 'detail': repr(exc)}
    # Topology block (docs/robustness.md "Elastic pod-scale sharding"): when
    # PETASTORM_TPU_TOPOLOGY_JOURNAL (or --topology-journal) names a
    # membership journal, the replayed pod view — generation, members, stale
    # leases, CRC drops. Always present so --json consumers find one stable
    # key; an unarmed topology is a healthy install.
    try:
        report['topology'] = check_topology(topology_journal)
    except Exception as exc:  # noqa: BLE001 - the report must always complete
        report['topology'] = {'status': 'fail', 'detail': repr(exc)}
    # Incident-bundle block (docs/observability.md "Incident autopsy
    # plane"): retained black-box bundles in the default incident home (or
    # PETASTORM_TPU_INCIDENT_HOME) — each one is a captured failure edge
    # awaiting `petastorm-tpu-throughput autopsy`. Always present so --json
    # consumers find one stable key.
    try:
        report['incidents'] = check_incidents()
    except Exception as exc:  # noqa: BLE001 - the report must always complete
        report['incidents'] = {'status': 'fail', 'detail': repr(exc)}
    # Object-store ingest block (docs/performance.md "Object-store ingest
    # engine"): a force-armed engine read over a local store — footer-cache
    # hit/miss, ranges coalesced, hedges fired/won. Always present so --json
    # consumers find one stable key.
    try:
        report['storage'] = check_storage()
    except Exception as exc:  # noqa: BLE001 - the report must always complete
        report['storage'] = {'status': 'fail', 'detail': repr(exc)}
    report['healthy'] = report['store_roundtrip'].get('status') == 'ok'
    return report


def check_ledger(service_report=None):
    """The probed dispatcher's durable-ledger health (docs/service.md
    "Failure modes"), derived from the ``check_service`` state snapshot:
    ``{'status': 'unarmed'}`` when no service is configured or the
    dispatcher runs without a ledger, else journal path, ledger epoch,
    the last replay result (``ok`` / ``corrupt`` / ``absent`` /
    ``discarded``) and the CRC-dropped frame count — a nonzero drop count
    means a past restart degraded to replay-from-clients."""
    state = ((service_report or {}).get('state') or {}).get('ledger') or {}
    if not state.get('armed'):
        return {'status': 'unarmed'}
    return {'status': 'ok',
            'path': state.get('path'),
            'epoch': state.get('epoch'),
            'last_replay': state.get('last_replay'),
            'frames_dropped': state.get('frames_dropped', 0),
            'records_replayed': state.get('records_replayed', 0)}


def check_history(path, sentinel=None):
    """Replay the roundtrip's run-history store (docs/observability.md
    "Longitudinal observatory"): record count, CRC-dropped frames, the
    newest record's headline rows/s, and the sentinel's armed state — a
    nonzero drop count means a past append was torn and the store healed
    around it."""
    from petastorm_tpu.telemetry.history import load_records
    records, dropped = load_records(path)
    block = {'status': 'ok' if records and not dropped else 'degraded',
             'records': len(records), 'frames_dropped': dropped,
             'sentinel_armed': bool(sentinel)}
    if records:
        newest = records[-1]
        block['rows_per_sec'] = newest.get('rows_per_sec')
        block['platform'] = newest.get('platform')
    return block


def check_topology(journal_path=None):
    """Replay the elastic-sharding membership journal (docs/robustness.md
    "Elastic pod-scale sharding") when one is named — ``journal_path``
    argument or the ``PETASTORM_TPU_TOPOLOGY_JOURNAL`` env var. Returns
    ``{'status': 'unarmed'}`` when no journal is configured,
    ``{'status': 'absent', ...}`` when the path does not exist yet, else
    the replayed membership view: generation, live members, stale leases
    (hosts whose lease expired without a leave — reshard candidates) and
    the CRC-dropped frame count."""
    path = journal_path or os.environ.get('PETASTORM_TPU_TOPOLOGY_JOURNAL')
    if not path:
        return {'status': 'unarmed'}
    from petastorm_tpu.parallel.topology import replay_topology_journal
    replay = replay_topology_journal(path)
    if replay.result == 'absent':
        return {'status': 'absent', 'path': path}
    stale = replay.stale_leases(time.time())
    return {'status': replay.result, 'path': path,
            'generation': replay.generation,
            'members': sorted(replay.members),
            'stale_leases': stale,
            'delivered': len(replay.delivered),
            'resharded': replay.resharded,
            'frames_dropped': replay.frames_dropped,
            'records': replay.records}


def check_incidents(home=None):
    """Scan the incident home for retained bundles (newest first): the
    doctor's view of the incident autopsy plane — bundle names, trigger
    kinds and ranked causes, without opening the heavyweight evidence."""
    from petastorm_tpu.telemetry.incident import (default_incident_home,
                                                  scan_bundles)
    home = home or default_incident_home(None)
    bundles = scan_bundles(home)
    return {'status': 'ok', 'home': home, 'retained': len(bundles),
            'bundles': bundles[:8]}


def _print_human(report):
    v = report['versions']
    print('petastorm-tpu doctor')
    print('  versions: petastorm_tpu {} / python {} / jax {} / pyarrow {}'
          .format(v['petastorm_tpu'], v['python'], v['jax'], v['pyarrow']))
    optional = ', '.join('{} {}'.format(k, v[k]) for k in
                         ('flax', 'optax', 'orbax', 'torch', 'tensorflow')
                         if v.get(k))
    if optional:
        print('  optional: ' + optional)
    b = report['backend']
    if b['status'] == 'up':
        print('  backend: UP — {} x{}'.format(b['platform'], b['devices']))
    else:
        print('  backend: {} ({}) — CPU development still works; streaming '
              'benchmarks need the device'.format(
                  b['status'].upper(), b.get('detail', '')))
    link = report.get('link')
    if link and 'dispatch_rtt_ms' in link:
        print('  link: RTT {} ms, H2D {} MB/s, D2H {} MB/s -> streaming '
              'ceiling ~{} rows/s at 1 KiB rows'.format(
                  link['dispatch_rtt_ms'], link['h2d_mbytes_per_sec'],
                  link['d2h_mbytes_per_sec'],
                  link['streaming_ceiling_rows_per_sec_at_1kib']))
    elif link:
        print('  link: FAIL ({}) — device up but unmeasurable; expect '
              'streaming anomalies'.format(link.get('detail', 'unknown')))
    s = report['store_roundtrip']
    if s.get('status') == 'ok':
        print('  store roundtrip: OK — {} rows at {} rows/s'.format(
            s['rows'], s['rows_per_sec']))
        if s.get('io_retries') or s.get('rowgroups_quarantined'):
            print('  resilience: {} transient-IO retries, {} rowgroups quarantined '
                  '— local reads should never need these; check the disk'.format(
                      s.get('io_retries', 0), s.get('rowgroups_quarantined', 0)))
    else:
        print('  store roundtrip: FAIL — {}'.format(s.get('detail')))
    telemetry = report.get('telemetry')
    if telemetry and telemetry['bottleneck'].get('top_stage'):
        b = telemetry['bottleneck']
        print('  telemetry: top stage {} ({:.0%} of {:.3f}s stage time) -> {}'
              .format(b['top_stage'], b['top_share'],
                      b.get('total_stage_seconds', 0.0), b['recommendation']))
    slo = report.get('slo') or {}
    if slo.get('evaluated'):
        print('  input efficiency: {:.1%} (target {:.0%}; consumer waited '
              '{:.3f}s of {:.3f}s)'.format(
                  slo.get('efficiency', 0.0),
                  slo.get('target_efficiency', 0.0),
                  slo.get('wait_seconds', 0.0), slo.get('elapsed_s', 0.0)))
        if slo.get('breached'):
            print('  WARNING: input efficiency is BELOW the SLO target — '
                  'the consumer sat starved {:.0%} of the time; see the '
                  'telemetry bottleneck line for the knob to turn '
                  '(docs/observability.md "Efficiency SLOs")'.format(
                      slo.get('starvation_fraction', 0.0)))
    lineage = report.get('lineage') or {}
    if lineage.get('enabled'):
        print('  lineage: digest {}… over {} item(s), {} pending, '
              '{} divergence event(s)'.format(
                  (lineage.get('order_digest') or '')[:12],
                  lineage.get('items_folded', 0),
                  lineage.get('pending_items', 0),
                  lineage.get('divergence', 0)))
        if lineage.get('divergence'):
            last = lineage.get('last_divergence') or {}
            print('  WARNING: sample-lineage verification FAILED {} time(s) '
                  '(last: {} — {}) — the delivered stream broke its expected '
                  'order; reproducibility is not provable for this run '
                  '(docs/observability.md "Sample lineage")'.format(
                      lineage.get('divergence'), last.get('reason'),
                      last.get('detail')))
    history = report.get('history') or {}
    if history.get('status') != 'unprobed':
        print('  history: {} run record(s) replayed ({} CRC-dropped '
              'frame(s)), sentinel {}'.format(
                  history.get('records', 0),
                  history.get('frames_dropped', 0),
                  'armed' if history.get('sentinel_armed') else 'unarmed'))
        if history.get('frames_dropped'):
            print('  WARNING: the run-history store dropped torn frame(s) '
                  'on replay — a past append was interrupted; the store '
                  'heals on the next append (docs/observability.md '
                  '"Longitudinal observatory")')
    trace = report.get('trace') or {}
    if trace.get('events'):
        anomalies = trace.get('anomaly_instants') or []
        slowest = (trace.get('top_rowgroup_traces') or [{}])[0]
        print('  trace: {} event(s) across {} process(es), {} rowgroup '
              'trace(s), {} dropped; {} anomaly instant(s){}'.format(
                  trace.get('events', 0), len(trace.get('processes', [])),
                  trace.get('rowgroups_traced', 0),
                  trace.get('dropped_events', 0), len(anomalies),
                  '; slowest rowgroup {} at {} ms'.format(
                      slowest.get('rowgroup'), slowest.get('duration_ms'))
                  if slowest else ''))
    resilience = report.get('resilience') or {}
    open_breakers = sorted(
        name for name, state in (resilience.get('breakers') or {}).items()
        if state.get('state') != 'closed')
    if open_breakers:
        print('  WARNING: circuit breaker(s) not closed: {} — a dependency is '
              'being routed around; reads are degraded, not broken '
              '(docs/robustness.md)'.format(', '.join(open_breakers)))
    degraded = {key: resilience.get(key, 0)
                for key in ('workers_hung_reaped', 'shm_crc_failures',
                            'cache_corrupt_entries')
                if resilience.get(key, 0)}
    if degraded:
        print('  resilience: {} — the roundtrip needed hang/corruption '
              'recovery on a local disk; check the hardware'.format(
                  ', '.join('{}={}'.format(k, v) for k, v in sorted(degraded.items()))))
    autotune = report.get('autotune') or {}
    if autotune.get('enabled'):
        decisions = autotune.get('decisions') or []
        line = '  autotune: {} knob(s) catalogued, {} window(s), {} decision(s)' \
            .format(len(autotune.get('knobs') or {}),
                    autotune.get('windows', 0), len(decisions))
        if decisions:
            last = decisions[-1]
            line += '; last: {} {}'.format(last.get('action'),
                                           last.get('knob') or '')
        print(line)
        if autotune.get('frozen_by_breaker'):
            print('  WARNING: autotune is FROZEN by an open circuit breaker — '
                  'the controller reverted its last change and will not retune '
                  'until the board is healthy (docs/autotuning.md)')
    service = report.get('service') or {}
    if service.get('status') == 'ok':
        print('  service: {} — {} worker(s), {} client(s), queue depth {} '
              '(docs/service.md)'.format(
                  service.get('service_url'), service.get('workers', 0),
                  service.get('clients', 0), service.get('queue_depth', 0)))
        if service.get('workers', 0) == 0:
            print('  WARNING: input service at {} has NO registered decode '
                  'workers — readers pointed at it will stall until workers '
                  'join'.format(service.get('service_url')))
    elif service.get('status') == 'unreachable':
        print('  WARNING: input service at {} is UNREACHABLE ({}) — readers '
              'with this service_url will fail their hello; is the '
              'dispatcher running? (docs/service.md)'.format(
                  service.get('service_url'), service.get('detail', '')))
    ledger = report.get('ledger') or {}
    if ledger.get('status') == 'ok':
        print('  ledger: armed at {} — epoch {}, last replay {} ({} '
              'record(s), {} frame(s) CRC-dropped) (docs/service.md '
              '"Failure modes")'.format(
                  ledger.get('path'), ledger.get('epoch'),
                  ledger.get('last_replay'),
                  ledger.get('records_replayed', 0),
                  ledger.get('frames_dropped', 0)))
        if ledger.get('frames_dropped'):
            print('  WARNING: the dispatcher ledger dropped {} journal '
                  'frame(s) on its last replay — a restart degraded to '
                  'replay-from-clients; inspect the journal and any '
                  'ledger_corrupt incident bundle'.format(
                      ledger.get('frames_dropped')))
    topology = report.get('topology') or {}
    if topology.get('status') in ('ok', 'corrupt'):
        print('  topology: journal {} — generation {}, {} member(s), {} '
              'item(s) journaled delivered, {} reshard(s) '
              '(docs/robustness.md "Elastic pod-scale sharding")'.format(
                  topology.get('path'), topology.get('generation'),
                  len(topology.get('members') or []),
                  topology.get('delivered', 0),
                  topology.get('resharded', 0)))
        if topology.get('stale_leases'):
            print('  WARNING: topology member(s) with EXPIRED leases and no '
                  'leave record: {} — they look crashed or partitioned; '
                  'survivors should reshard their undelivered remainder '
                  '(`petastorm-tpu-throughput chaos --hosts N --kill-host` '
                  'rehearses exactly this)'.format(
                      ', '.join(sorted(topology.get('stale_leases')))))
        if topology.get('frames_dropped'):
            print('  WARNING: the membership journal dropped {} torn '
                  'frame(s) on replay — a past append was interrupted; '
                  'membership resumed from the intact prefix '
                  '(docs/robustness.md)'.format(
                      topology.get('frames_dropped')))
    elif topology.get('status') == 'absent':
        print('  topology: journal {} configured but not created yet — no '
              'topology-armed reader has opened it'.format(
                  topology.get('path')))
    incidents = report.get('incidents') or {}
    if incidents.get('retained'):
        newest = (incidents.get('bundles') or [{}])[0]
        print('  WARNING: {} incident bundle(s) retained in {} (newest: {} — '
              'cause {}) — a failure edge black-boxed its evidence; run '
              '`petastorm-tpu-throughput autopsy {}` for the ranked '
              'probable-cause report (docs/observability.md "Incident '
              'autopsy plane")'.format(
                  incidents.get('retained'), incidents.get('home'),
                  newest.get('bundle'), newest.get('cause'),
                  newest.get('path', '<bundle>')))
    storage = report.get('storage') or {}
    if storage.get('status') == 'ok':
        print('  storage engine: footer cache {} hit(s) / {} miss(es), {} '
              'range(s) coalesced, hedges {} fired / {} won '
              '(docs/performance.md "Object-store ingest engine")'.format(
                  storage.get('footer_cache_hits', 0),
                  storage.get('footer_cache_misses', 0),
                  storage.get('ranges_coalesced', 0),
                  storage.get('hedges_fired', 0),
                  storage.get('hedges_won', 0)))
        if storage.get('hedges_fired', 0) and \
                storage.get('hedge_win_rate', 0.0) > 0.5:
            print('  WARNING: hedge-win rate is {:.0%} — storage is '
                  'tail-heavy; more than half the hedged duplicates beat '
                  'the primary GET, so the hedge deadline is doing the '
                  "store's job. Investigate the backing filesystem before "
                  'trusting throughput numbers'.format(
                      storage.get('hedge_win_rate', 0.0)))
    elif storage:
        print('  storage engine: FAIL ({}) — the force-armed probe read '
              'errored'.format(storage.get('detail', 'unknown')))
    pipecheck = report.get('pipecheck') or {}
    if pipecheck.get('status') == 'ok':
        print('  pipecheck: clean — {} files, {} call-graph function(s), '
              '{} suppression(s) honored (docs/static-analysis.md)'.format(
                  pipecheck.get('files', 0),
                  pipecheck.get('callgraph_functions', 0),
                  pipecheck.get('suppressed', 0)))
    elif pipecheck.get('status') == 'findings':
        print('  WARNING: pipecheck found {} data-plane invariant '
              'violation(s) ({}); first: {} — run '
              '`petastorm-tpu-pipecheck` for the full list'.format(
                  pipecheck.get('findings', 0),
                  ', '.join('{}={}'.format(rule, count) for rule, count
                            in sorted(pipecheck.get('by_rule', {}).items())),
                  pipecheck.get('first')))
    elif pipecheck:
        print('  pipecheck: FAIL ({}) — the analyzer itself errored'.format(
            pipecheck.get('detail', 'unknown')))
    print('  verdict: {}'.format('healthy' if report['healthy'] else 'BROKEN'))


def main(argv=None):
    """CLI: run all checks, print the report, exit 0 iff healthy."""
    parser = argparse.ArgumentParser(
        description='petastorm-tpu environment doctor')
    parser.add_argument('--json', action='store_true',
                        help='print one machine-readable JSON line instead')
    parser.add_argument('--probe-timeout', type=int, default=60,
                        help='backend probe subprocess timeout (seconds)')
    parser.add_argument('--link-timeout', type=int, default=180,
                        help='link probe subprocess timeout (seconds)')
    parser.add_argument('--no-link', action='store_true',
                        help='skip the link bandwidth probe')
    parser.add_argument('--service-url', default=None,
                        help='probe this input-service dispatcher (default: '
                             'the PETASTORM_TPU_SERVICE_URL env var; unset = '
                             'skip)')
    parser.add_argument('--topology-journal', default=None,
                        help='replay this elastic-sharding membership '
                             'journal (default: the '
                             'PETASTORM_TPU_TOPOLOGY_JOURNAL env var; '
                             'unset = skip)')
    args = parser.parse_args(argv)
    report = collect_report(probe_timeout_s=args.probe_timeout,
                            link=not args.no_link,
                            link_timeout_s=args.link_timeout,
                            service_url=args.service_url,
                            topology_journal=args.topology_journal)
    if args.json:
        print(json.dumps(report))
    else:
        _print_human(report)
    return 0 if report['healthy'] else 1


if __name__ == '__main__':
    sys.exit(main())
