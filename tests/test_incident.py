"""Incident autopsy plane tests (ISSUE 15, docs/observability.md "Incident
autopsy plane"): edge-triggered black-box capture into rate-limited, bounded
bundle retention; fleet-wide ``w_incident`` collection with straggler/seq
guards and same-cause correlation; the root-cause-ranked ``autopsy`` CLI with
per-cause exit codes — plus the satellite fixes (ephemeral metrics port +
SO_REUSEADDR restart, the SLO not-enough-data shape, scrape-under-churn
straggler guards, the bench baseline-comparison diff).

The two end-to-end acceptance paths:
- (a) a fault-injected hang reaped mid-epoch produces exactly ONE
  ``watchdog_reap`` bundle whose autopsy ranks hang first (exit 10), with the
  failing item's (epoch, rowgroup, attempt) context in the bundled trace;
- (b) a forced breaker closed→open edge produces exactly ONE rate-limited
  ``breaker_open`` bundle whose autopsy ranks storage-path first (exit 12).
"""
import json
import os
import time

import numpy as np
import pytest

from petastorm_tpu import make_reader
from petastorm_tpu.codecs import NdarrayCodec, ScalarCodec
from petastorm_tpu.etl.dataset_metadata import write_rows
from petastorm_tpu.resilience import default_board
from petastorm_tpu.telemetry import tracing
from petastorm_tpu.telemetry.incident import (EXIT_BAD_BUNDLE, EXIT_CODES,
                                              EXIT_UNKNOWN, TRIGGER_KINDS,
                                              IncidentPolicy, IncidentRecorder,
                                              bundle_reference,
                                              default_incident_home,
                                              resolve_incident_policy,
                                              scan_bundles)
from petastorm_tpu.telemetry.incident import analyze_bundle
from petastorm_tpu.telemetry.incident import main as autopsy_main
from petastorm_tpu.test_util.fault_injection import (FaultRule, FaultSchedule,
                                                     fault_injecting_filesystem)
from petastorm_tpu.unischema import Unischema, UnischemaField


class FakeClock(object):
    """Injectable monotonic clock: rate-limit tests never sleep."""

    def __init__(self):
        self.now = 0.0

    def __call__(self):
        return self.now


def _recorder(tmp_path, **policy_kwargs):
    policy = IncidentPolicy(home=str(tmp_path / 'incidents'), **policy_kwargs)
    clock = FakeClock()
    return IncidentRecorder(policy.home, policy, clock=clock), clock


def _write_store(root, num_rows=48, n_files=4):
    schema = Unischema('IncidentProbe', [
        UnischemaField('id', np.int64, (), ScalarCodec(), False),
        UnischemaField('vec', np.float32, (8,), NdarrayCodec(), False),
    ])
    url = 'file://' + str(root)
    write_rows(url, schema,
               [{'id': i, 'vec': np.full(8, i, np.float32)}
                for i in range(num_rows)],
               n_files=n_files, rowgroup_size_mb=1)
    return url


# ---------------------------------------------------------------------------
# policy + recorder units (injectable clock — no sleeps anywhere)
# ---------------------------------------------------------------------------

def test_policy_resolution_and_validation():
    assert resolve_incident_policy(None) is None
    assert resolve_incident_policy(False) is None
    default = resolve_incident_policy(True)
    assert default.max_bundles == 8 and default.bucket_capacity == 1
    assert tuple(default.triggers) == TRIGGER_KINDS
    policy = IncidentPolicy(max_bundles=2)
    assert resolve_incident_policy(policy) is policy
    with pytest.raises(ValueError):
        resolve_incident_policy('yes')
    with pytest.raises(ValueError):
        IncidentPolicy(max_bundles=0)
    with pytest.raises(ValueError):
        IncidentPolicy(bucket_capacity=0)
    with pytest.raises(ValueError):
        IncidentPolicy(refill_interval_s=0.0)
    with pytest.raises(ValueError):
        IncidentPolicy(triggers=('nope',))


def test_rate_limit_per_kind_token_bucket(tmp_path):
    recorder, clock = _recorder(tmp_path, bucket_capacity=1,
                                refill_interval_s=60.0)
    assert recorder.trigger('slo_breach') is not None
    assert recorder.trigger('slo_breach') is None  # same kind: bucket empty
    # a DIFFERENT kind has its own bucket — edges of distinct failure modes
    # never starve each other
    assert recorder.trigger('breaker_open') is not None
    assert recorder.captured == 2 and recorder.rate_limited == 1
    clock.now += 59.0
    assert recorder.trigger('slo_breach') is None  # still inside the window
    clock.now += 1.0
    assert recorder.trigger('slo_breach') is not None  # token refilled
    report = recorder.report()
    assert report['captured'] == 3 and report['rate_limited'] == 2
    assert report['retained'] == 3 and len(report['bundles']) == 3


def test_retention_provably_bounded_newest_survive(tmp_path):
    recorder, clock = _recorder(tmp_path, max_bundles=3,
                                refill_interval_s=1.0)
    paths = []
    for _ in range(5):  # N+1 (and then some): every capture gets a token
        clock.now += 1.0
        paths.append(recorder.trigger('slo_breach'))
    assert all(paths)
    retained = scan_bundles(recorder.home)
    assert len(retained) == 3
    # newest-first scan == the LAST three captures; the oldest were evicted
    assert [entry['path'] for entry in retained] == paths[:1:-1]
    assert not os.path.isdir(paths[0]) and not os.path.isdir(paths[1])


def test_trigger_filtering_and_close(tmp_path):
    recorder, clock = _recorder(tmp_path, triggers=('slo_breach',),
                                refill_interval_s=1.0)
    assert recorder.trigger('breaker_open') is None  # not subscribed
    assert recorder.trigger('slo_breach') is not None
    assert recorder.rate_limited == 0  # filtered != rate-limited
    recorder.close()
    clock.now += 10.0
    assert recorder.trigger('slo_breach') is None  # closed: no-op
    # retained bundles survive close — they ARE the artifact
    assert len(scan_bundles(recorder.home)) == 1


def test_bundle_contents_sources_and_trace_window(tmp_path):
    recorder, clock = _recorder(tmp_path, pre_trigger_window_s=30.0)
    recorder.add_source('metrics', lambda: {'counters': {'rows': 7}})

    def boom():
        raise RuntimeError('evidence source died')
    recorder.add_source('costs', boom)
    tracing.reset_tracing()
    tracing.set_trace_enabled(True)
    try:
        tracing.trace_complete('rowgroup_read', time.perf_counter() - 0.5,
                               0.5, ctx=(0, 3, 1))
        # a span OLDER than the pre-trigger window must be cut from the
        # bundle: the black box is the approach, not the whole flight
        tracing.trace_complete('fs_open', time.perf_counter() - 3600.0,
                               0.1, ctx=(0, 1, 0))
        tracing.trace_instant('quarantine', ctx=(0, 3, 1),
                              args={'reason': 'error'})
        path = recorder.trigger('quarantine', ctx=(0, 3, 1),
                                args={'reason': 'error',
                                      'error_type': 'ValueError'})
    finally:
        tracing.set_trace_enabled(False)
        tracing.reset_tracing()
    assert path is not None and os.path.isdir(path)
    assert not [entry for entry in os.listdir(recorder.home)
                if entry.startswith('.tmp-')], 'staging dir leaked'
    with open(os.path.join(path, 'manifest.json')) as f:
        manifest = json.load(f)
    assert manifest['kind'] == 'quarantine'
    assert manifest['cause'] == 'corruption'  # ValueError: not transient
    assert manifest['ctx'] == [0, 3, 1]
    with open(os.path.join(path, 'trace.json')) as f:
        trace = json.load(f)
    names = {e.get('name') for e in trace['traceEvents']}
    assert {'rowgroup_read', 'quarantine'} <= names
    assert 'fs_open' not in names  # outside the pre-trigger window
    instant = [e for e in trace['traceEvents']
               if e.get('name') == 'quarantine'][0]
    assert instant['args']['epoch'] == 0 and instant['args']['rowgroup'] == 3
    with open(os.path.join(path, 'metrics.json')) as f:
        assert json.load(f) == {'counters': {'rows': 7}}
    with open(os.path.join(path, 'costs.json')) as f:
        assert 'evidence source died' in json.load(f)['error']
    with open(os.path.join(path, 'environment.json')) as f:
        env = json.load(f)
    assert env['pid'] == os.getpid() and 'python' in env


def test_breaker_transition_observer_captures_open_edges_only(tmp_path):
    recorder, _clock = _recorder(tmp_path)
    recorder.on_breaker_transition('b', 'closed', 'half-open')
    assert recorder.captured == 0
    recorder.on_breaker_transition('b', 'closed', 'open')
    assert recorder.captured == 1
    (entry,) = scan_bundles(recorder.home)
    assert entry['kind'] == 'breaker_open'
    assert entry['cause'] == 'storage-path'


def test_quarantine_cause_resolved_from_record(tmp_path):
    recorder, clock = _recorder(tmp_path, refill_interval_s=1.0)
    cases = [({'reason': 'hang'}, 'hang'),
             ({'reason': 'error', 'error_type': 'TransientIOError'},
              'storage-path'),
             ({'reason': 'error', 'error_type': 'ValueError'}, 'corruption')]
    for args, expected in cases:
        clock.now += 1.0
        path = recorder.trigger('quarantine', args=args)
        with open(os.path.join(path, 'manifest.json')) as f:
            assert json.load(f)['cause'] == expected


def test_seq_resumes_past_retained_bundles(tmp_path):
    recorder, _clock = _recorder(tmp_path)
    first = recorder.trigger('slo_breach')
    recorder.close()
    # a restarted owner must never clobber a retained bundle name
    reborn = IncidentRecorder(recorder.home, recorder.policy,
                              clock=FakeClock())
    second = reborn.trigger('slo_breach')
    assert os.path.basename(first) == 'incident-00000-slo_breach'
    assert os.path.basename(second) == 'incident-00001-slo_breach'


def test_default_incident_home_rules(tmp_path, monkeypatch):
    monkeypatch.delenv('PETASTORM_TPU_INCIDENT_HOME', raising=False)
    assert default_incident_home('/state/home') == '/state/home/incidents'
    assert 'petastorm-tpu-incidents' in default_incident_home(None)
    monkeypatch.setenv('PETASTORM_TPU_INCIDENT_HOME', str(tmp_path / 'ih'))
    assert default_incident_home('/state/home') == str(tmp_path / 'ih')


# ---------------------------------------------------------------------------
# fleet shipping: references, adoption, wire frame, dispatcher guards
# ---------------------------------------------------------------------------

def test_bundle_reference_inline_cap_and_adopt(tmp_path):
    recorder, _clock = _recorder(tmp_path)
    path = recorder.trigger('breaker_open', args={'breaker': 'store'})
    small = bundle_reference(path, ship_bytes_cap=1 << 20)
    assert small['kind'] == 'breaker_open'
    assert small['cause'] == 'storage-path'
    assert small['size_bytes'] > 0
    assert 'manifest.json' in small['inline']
    # over-cap bundles ship as reference-only: no inline payload
    big = bundle_reference(path, ship_bytes_cap=1)
    assert 'inline' not in big

    adopter, _ = _recorder(tmp_path / 'dispatcher')
    adopted = adopter.adopt(small)
    assert adopted is not None and os.path.isdir(adopted)
    report = analyze_bundle(adopted)  # a first-class, analyzable copy
    assert report['trigger'] == 'breaker_open'
    assert adopter.adopt(big) is None  # nothing to materialize
    assert adopter.captured == 1


def test_drain_references_hand_off(tmp_path):
    recorder, _clock = _recorder(tmp_path)
    recorder.trigger('slo_breach')
    refs = recorder.drain_references()
    assert len(refs) == 1 and refs[0]['kind'] == 'slo_breach'
    assert recorder.drain_references() == []  # drained exactly once


def test_worker_incident_update_wire_roundtrip():
    from petastorm_tpu.service.wire import WorkerIncidentUpdate
    reference = {'bundle': '/tmp/x/incident-00000-slo_breach',
                 'kind': 'slo_breach', 'cause': 'scheduling-skew',
                 'ctx': [1, 2, 3], 'size_bytes': 512,
                 'inline': {'manifest.json': '{}'}}
    update = WorkerIncidentUpdate(worker_id=4, seq=9, reference=reference)
    decoded = WorkerIncidentUpdate.from_bytes(update.to_bytes())
    assert decoded.worker_id == 4 and decoded.seq == 9
    assert decoded.reference == reference


def test_dispatcher_incident_guards_and_correlation(tmp_path, monkeypatch):
    from petastorm_tpu.service.dispatcher import Dispatcher
    from petastorm_tpu.service.wire import WorkerDescriptor
    monkeypatch.setenv('PETASTORM_TPU_INCIDENT_HOME',
                       str(tmp_path / 'dispatcher'))
    worker_home = tmp_path / 'worker'
    shipper = IncidentRecorder(str(worker_home),
                               IncidentPolicy(home=str(worker_home),
                                              refill_interval_s=0.001))
    ref = bundle_reference(shipper.trigger('watchdog_reap',
                                           args={'worker_id': 3}),
                           ship_bytes_cap=1 << 20)
    dispatcher = Dispatcher(incidents=True)
    try:
        # an unregistered worker's frame is dropped (departed straggler)
        dispatcher.record_worker_incident(3, 1, ref)
        assert dispatcher.incidents_state()['fleet'] == []
        dispatcher.scheduler.add_worker(
            b'w3', WorkerDescriptor(worker_id=3, pid=1, host='h'))
        dispatcher.scheduler.add_worker(
            b'w4', WorkerDescriptor(worker_id=4, pid=2, host='h'))
        dispatcher.record_worker_incident(3, 1, ref)
        dispatcher.record_worker_incident(3, 1, ref)  # stale seq: dropped
        # same cause from another worker inside the window: ONE fleet
        # incident spanning both workers
        dispatcher.record_worker_incident(4, 1, ref)
        state = dispatcher.incidents_state()
        (entry,) = state['fleet']
        assert entry['cause'] == 'hang' and entry['count'] == 2
        assert sorted(entry['workers']) == [3, 4]
        assert len(entry['bundles']) == 2
        assert entry['first_age_s'] >= 0 and entry['last_age_s'] >= 0
        # inline ships were materialized into the dispatcher's own home
        assert state['captured'] == 2 and state['retained'] == 2
        # a DISTINCT cause opens its own fleet incident
        poison = dict(ref, cause='corruption', kind='shm_crc_drop')
        poison.pop('inline', None)
        dispatcher.record_worker_incident(4, 2, poison)
        assert len(dispatcher.incidents_state()['fleet']) == 2
        # dispatcher-side incident counters ride the fleet aggregate
        merged = dispatcher.fleet_metrics_snapshot()
        assert merged['counters'].get('incidents_captured', 0) >= 0
        # departure pops the seq entry; the straggler cannot resurrect it
        dispatcher._depart_worker(b'w4', reason='left')
        before = dispatcher.incidents_state()
        dispatcher.record_worker_incident(4, 5, ref)
        assert dispatcher.incidents_state()['captured'] \
            == before['captured']
    finally:
        dispatcher.stop()


# ---------------------------------------------------------------------------
# autopsy CLI
# ---------------------------------------------------------------------------

def test_autopsy_exit_codes_per_trigger(tmp_path, capsys):
    recorder, clock = _recorder(tmp_path, refill_interval_s=1.0)
    expected = {'breaker_open': EXIT_CODES['storage-path'],
                'watchdog_reap': EXIT_CODES['hang'],
                'shm_crc_drop': EXIT_CODES['corruption'],
                'slo_breach': EXIT_CODES['scheduling-skew'],
                'lineage_divergence': EXIT_CODES['divergence'],
                'service_poison_item': EXIT_CODES['hang']}
    assert set(EXIT_CODES.values()) == {10, 11, 12, 13, 14}
    for kind, code in sorted(expected.items()):
        clock.now += 1.0
        path = recorder.trigger(kind)
        assert autopsy_main([path]) == code
        out = capsys.readouterr().out
        assert 'probable causes' in out or 'verdict' in out
    # --json emits the machine report
    clock.now += 1.0
    path = recorder.trigger('slo_breach', ctx=(2, 7, 1))
    assert autopsy_main(['--json', path]) == EXIT_CODES['scheduling-skew']
    report = json.loads(capsys.readouterr().out)
    assert report['top_cause'] == 'scheduling-skew'
    assert report['ctx'] == [2, 7, 1]
    # a HOME directory resolves to its newest bundle
    assert autopsy_main([recorder.home]) == EXIT_CODES['scheduling-skew']
    capsys.readouterr()


def test_autopsy_bad_bundle_and_unknown(tmp_path, capsys):
    assert autopsy_main([str(tmp_path / 'nope')]) == EXIT_BAD_BUNDLE
    bundle = tmp_path / 'incident-00000-garbage'
    bundle.mkdir()
    (bundle / 'manifest.json').write_text('{not json')
    assert autopsy_main([str(bundle)]) == EXIT_BAD_BUNDLE
    # a manifest naming no known cause ranks nothing: EXIT_UNKNOWN
    (bundle / 'manifest.json').write_text(json.dumps(
        {'schema': 1, 'kind': 'mystery', 'cause': 'not-a-cause'}))
    assert autopsy_main([str(bundle)]) == EXIT_UNKNOWN
    capsys.readouterr()


def test_benchmark_cli_dispatches_autopsy(tmp_path, capsys):
    from petastorm_tpu.benchmark.cli import main as cli_main
    recorder, _clock = _recorder(tmp_path)
    path = recorder.trigger('watchdog_reap')
    assert cli_main(['autopsy', path]) == EXIT_CODES['hang']
    capsys.readouterr()


def test_doctor_reports_retained_incidents(tmp_path, monkeypatch):
    from petastorm_tpu.tools import doctor
    monkeypatch.setenv('PETASTORM_TPU_INCIDENT_HOME', str(tmp_path / 'ih'))
    report = doctor.check_incidents()
    assert report['status'] == 'ok' and report['retained'] == 0
    recorder = IncidentRecorder(default_incident_home(None),
                                IncidentPolicy())
    recorder.trigger('breaker_open', args={'breaker': 'store'})
    report = doctor.check_incidents()
    assert report['retained'] == 1
    assert report['bundles'][0]['kind'] == 'breaker_open'


# ---------------------------------------------------------------------------
# end-to-end acceptance (a): hang reaped mid-epoch -> hang bundle, exit 10
# ---------------------------------------------------------------------------

@pytest.fixture
def disarm_tracing():
    """``make_reader(trace=True)`` arms the process-global recorder: disarm it
    after the test so later tests in this worker start with it off."""
    yield
    tracing.set_trace_enabled(False)
    tracing.reset_tracing()


@pytest.mark.faultinject
def test_e2e_hang_reap_one_bundle_ctx_in_trace_autopsy_hang(tmp_path, disarm_tracing):
    url = _write_store(tmp_path / 'store', num_rows=64, n_files=8)
    import glob as globmod
    parts = sorted(globmod.glob(os.path.join(str(tmp_path / 'store'), '**',
                                             '*.parquet'), recursive=True))
    target = os.path.basename(parts[3])
    sched = FaultSchedule(tmp_path / 'faults',
                          [FaultRule(target, kind='hang', times=1)])
    home = str(tmp_path / 'incidents')
    with make_reader(url, reader_pool_type='process', workers_count=2,
                     num_epochs=1, shuffle_row_groups=False, on_error='skip',
                     item_deadline_s=2.0, trace=True,
                     incidents=IncidentPolicy(home=home),
                     filesystem=fault_injecting_filesystem(sched)) as reader:
        ids = sorted(int(row.id) for row in reader)
        diag = reader.diagnostics
        probe = reader.incident_report()
    assert len(ids) == 56
    (record,) = diag['quarantine']
    assert record['reason'] == 'hang'
    # exactly ONE bundle for the one injected hang
    assert probe['captured'] == 1
    (entry,) = scan_bundles(home)
    assert entry['kind'] == 'watchdog_reap'
    assert entry['ctx'] == [record['epoch'], record['piece_index'],
                            record['attempts']]
    # the failing item's coordinates are in the bundled trace, not just the
    # manifest: the pre-trigger window caught its quarantine instant
    with open(os.path.join(entry['path'], 'trace.json')) as f:
        events = json.load(f)['traceEvents']
    marked = [e for e in events if e.get('name') == 'quarantine'
              and (e.get('args') or {}).get('rowgroup')
              == record['piece_index']]
    assert marked, 'quarantine instant with rowgroup ctx missing from trace'
    assert (marked[0]['args']['epoch'], marked[0]['args']['rowgroup']) \
        == (record['epoch'], record['piece_index'])
    report = analyze_bundle(entry['path'])
    assert report['top_cause'] == 'hang'
    assert report['causes'][0]['cause'] == 'hang'
    assert autopsy_main([entry['path']]) == EXIT_CODES['hang'] == 10


# ---------------------------------------------------------------------------
# end-to-end acceptance (b): forced breaker open -> storage-path, exit 12
# ---------------------------------------------------------------------------

def test_e2e_breaker_trip_one_rate_limited_bundle_autopsy_storage(tmp_path):
    url = _write_store(tmp_path / 'store')
    home = str(tmp_path / 'incidents')
    with make_reader(url, reader_pool_type='dummy', num_epochs=1,
                     incidents=IncidentPolicy(home=home)) as reader:
        for _ in reader:
            break
        breaker = default_board().breaker('probe_store',
                                          failure_threshold=1)
        breaker.record_failure()  # closed -> open: captured
        breaker.reset()
        breaker.record_failure()  # second edge inside refill: rate-limited
        probe = reader.incident_report()
        assert reader.diagnostics['incidents']['captured'] == 1
    assert probe['captured'] == 1 and probe['rate_limited'] >= 1
    (entry,) = scan_bundles(home)
    assert entry['kind'] == 'breaker_open'
    report = analyze_bundle(entry['path'])
    assert report['top_cause'] == 'storage-path'
    # the bundled breaker evidence corroborates: the open breaker is cited
    assert any('probe_store' in clue for clue in
               report['causes'][0]['evidence'])
    assert autopsy_main([entry['path']]) \
        == EXIT_CODES['storage-path'] == 12


# ---------------------------------------------------------------------------
# end-to-end acceptance (a, fleet): SIGKILL'd service worker -> hang bundle
# ---------------------------------------------------------------------------

def test_e2e_fleet_sigkill_worker_incident_and_scrape_churn(tmp_path,
                                                            monkeypatch):
    """One fleet run covers the SIGKILL acceptance AND the scrape-churn
    satellite: the killed worker's incident lands at the dispatcher (hang,
    exit 10), its labeled series leave /metrics, and neither a ``w_metrics``
    nor a ``w_incident`` straggler resurrects the departed entry."""
    import urllib.request
    from petastorm_tpu.service.fleet import ServiceFleet
    monkeypatch.setenv('PETASTORM_TPU_INCIDENT_HOME',
                       str(tmp_path / 'incidents'))
    url = _write_store(tmp_path / 'store', num_rows=64, n_files=8)
    with ServiceFleet(workers=2, metrics_port=0, incidents=True,
                      heartbeat_interval_s=0.2,
                      stale_timeout_s=1.0) as fleet:
        metrics_url = fleet.dispatcher.metrics_url
        with make_reader(url, service_url=fleet.service_url,
                         num_epochs=1) as reader:
            assert sum(1 for _ in reader) == 64
        # both workers' labeled series are on the scrape surface
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            body = urllib.request.urlopen(metrics_url + '/metrics',
                                          timeout=10).read().decode()
            if body.count('worker="') and 'worker="0"' in body \
                    and 'worker="1"' in body:
                break
            time.sleep(0.25)
        fleet.kill_worker(0)  # SIGKILL mid-scrape: heartbeats stop cold
        deadline = time.monotonic() + 30
        state = {}
        while time.monotonic() < deadline:
            state = fleet.dispatcher.incidents_state()
            if state.get('captured', 0) >= 1:
                break
            time.sleep(0.25)
        assert state.get('captured', 0) >= 1, \
            'stale-worker reap never produced an incident'
        (entry,) = state['fleet']
        assert entry['cause'] == 'hang' and 'watchdog_reap' in entry['kinds']
        assert 0 in entry['workers']
        # dispatcher state() carries the same block
        assert fleet.dispatcher.state()['incidents']['captured'] >= 1
        # the departed worker's series left the scrape surface...
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            body = urllib.request.urlopen(metrics_url + '/metrics',
                                          timeout=10).read().decode()
            if 'worker="0"' not in body:
                break
            time.sleep(0.25)
        assert 'worker="0"' not in body
        # ...and stragglers (late w_metrics / w_incident frames from the
        # dead worker) cannot resurrect it
        fleet.dispatcher.record_worker_metrics(
            0, 10 ** 6, {'counters': {'zombie': 1}})
        fleet.dispatcher.record_worker_incident(
            0, 10 ** 6, {'kind': 'watchdog_reap', 'cause': 'hang'})
        assert '0' not in fleet.dispatcher.worker_metrics_snapshots()
        captured_before = fleet.dispatcher.incidents_state()['captured']
        body = urllib.request.urlopen(metrics_url + '/metrics',
                                      timeout=10).read().decode()
        assert 'worker="0"' not in body and 'zombie' not in body
        assert fleet.dispatcher.incidents_state()['captured'] \
            == captured_before
        # the autopsy over the dispatcher's home ranks the injected hang
        bundles = scan_bundles(state['home'])
        assert bundles and bundles[0]['kind'] == 'watchdog_reap'
        assert autopsy_main([bundles[0]['path']]) == EXIT_CODES['hang']


# ---------------------------------------------------------------------------
# satellite: ephemeral metrics port + SO_REUSEADDR restart
# ---------------------------------------------------------------------------

def test_metrics_server_port_zero_ephemeral_and_fast_restart():
    import urllib.request
    from petastorm_tpu.telemetry.http_exporter import (
        MetricsHttpServer, _ReusableThreadingHTTPServer)
    assert _ReusableThreadingHTTPServer.allow_reuse_address is True
    snapshot_fn = lambda: {'counters': {'up': 1}}  # noqa: E731
    first = MetricsHttpServer(snapshot_fn, port=0)
    second = MetricsHttpServer(snapshot_fn, port=0)
    try:
        port = first.start()
        assert port > 0 and first.port == port
        # two ephemeral binds never collide
        assert second.start() not in (0, port)
    finally:
        first.stop()
        second.stop()
    # rapid restart onto the SAME fixed port: SO_REUSEADDR means the new
    # listener binds inside the old socket's TIME_WAIT instead of crashing
    for _ in range(3):
        server = MetricsHttpServer(snapshot_fn, port=port)
        try:
            assert server.start() == port
            body = urllib.request.urlopen(
                server.url + '/metrics', timeout=10).read().decode()
            assert 'petastorm_tpu_up 1' in body
        finally:
            server.stop()


# ---------------------------------------------------------------------------
# satellite: SLO warmup window is not-enough-data, never a spurious breach
# ---------------------------------------------------------------------------

def test_slo_warmup_not_enough_data_shape_and_no_breach_edge():
    from petastorm_tpu.telemetry.registry import MetricsRegistry
    from petastorm_tpu.telemetry.slo import SloPolicy, SloTracker
    fired = []
    tracker = SloTracker(SloPolicy(target_efficiency=0.9, min_elapsed_s=5.0),
                         on_breach=fired.append)
    registry = MetricsRegistry()
    starved = {'histograms': {'shuffle_wait': {
        'unit': 1e-6, 'count': 1, 'sum': 4.0, 'max': 4.0,
        'buckets': {'31': 1}}}, 'counters': {}, 'gauges': {}}
    report = tracker.evaluate(starved, 1.0, registry=registry)
    # the explicit not-enough-data shape: no number, no breach, no gauge
    assert report['evaluated'] is False
    assert report['efficiency'] is None
    assert report['starvation_fraction'] is None
    assert report['reason'] == 'not_enough_data'
    assert report['breached'] is False and report['met'] is True
    assert tracker.breaches == 0 and fired == []
    gauges = registry.snapshot()['gauges']
    assert 'slo_efficiency' not in gauges
    # past min_elapsed_s the same starvation IS a breach edge
    report = tracker.evaluate(starved, 8.0, registry=registry)
    assert report['evaluated'] and report['breached']
    assert report['efficiency'] == pytest.approx(0.5)
    assert tracker.breaches == 1 and len(fired) == 1


def test_reader_scrape_never_renders_warmup_efficiency_zero(tmp_path):
    """A scrape during the warmup window must omit slo_efficiency rather
    than expose a spurious 0.0 (the satellite's regression shape)."""
    import urllib.request
    from petastorm_tpu.telemetry.slo import SloPolicy
    url = _write_store(tmp_path / 'store', num_rows=16, n_files=2)
    with make_reader(url, reader_pool_type='dummy', num_epochs=1,
                     metrics_port=0,
                     slo_policy=SloPolicy(target_efficiency=0.9,
                                          min_elapsed_s=3600.0)) as reader:
        for _ in reader:
            break
        body = urllib.request.urlopen(
            reader.metrics_url + '/metrics', timeout=10).read().decode()
        assert 'slo_efficiency' not in body
        assert 'slo_breach' not in body.replace('slo_breach_total', '')


# ---------------------------------------------------------------------------
# satellite: bench section registration
# ---------------------------------------------------------------------------

class TestBenchIncidentsSection:
    def _load_bench(self):
        import importlib.util
        spec = importlib.util.spec_from_file_location(
            'bench_module_incident',
            os.path.join(os.path.dirname(__file__), '..', 'bench.py'))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def test_incidents_section_registered(self):
        bench = self._load_bench()
        assert 'incidents' in bench.SECTION_NAMES
        assert 'incidents' in bench.SECTION_RUN_ORDER
        assert sorted(bench.SECTION_RUN_ORDER) == sorted(bench.SECTION_NAMES)
