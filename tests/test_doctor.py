"""Doctor CLI tests — every check runs for real on the CPU backend; the
subprocess backend probe inherits the conftest's ``JAX_PLATFORMS=cpu``."""
import json

from petastorm_tpu.tools import doctor


def test_versions_report_core_libs():
    v = doctor.check_versions()
    assert v['petastorm_tpu']
    assert v['jax'] is not None
    assert v['pyarrow'] is not None


def test_backend_probe_up_on_cpu():
    b = doctor.check_backend(timeout_s=120)
    assert b == {'status': 'up', 'platform': 'cpu', 'devices': b['devices']}
    assert b['devices'] >= 1


def test_store_roundtrip_ok():
    s = doctor.check_store_roundtrip(rows=60, workers=2)
    assert s['status'] == 'ok'
    assert s['rows'] == 60
    assert s['rows_per_sec'] > 0
    # flight-recorder summary of the same read (ISSUE 6): events recorded,
    # none silently dropped, and the roundtrip left tracing disarmed
    from petastorm_tpu.telemetry.tracing import trace_enabled
    assert s['trace']['events'] > 0
    assert s['trace']['dropped_events'] == 0
    assert s['trace']['rowgroups_traced'] > 0
    assert not trace_enabled()


def test_collect_report_healthy_and_json_clean(capsys, monkeypatch):
    monkeypatch.delenv('PETASTORM_TPU_SERVICE_URL', raising=False)
    rc = doctor.main(['--json', '--no-link', '--probe-timeout', '120'])
    out = capsys.readouterr().out.strip()
    report = json.loads(out)
    assert rc == 0
    assert report['healthy'] is True
    assert report['backend']['status'] == 'up'
    assert 'link' not in report  # --no-link honored
    assert report['store_roundtrip']['status'] == 'ok'
    # input-service block (ISSUE 8): one stable key; no configured service
    # is a healthy install
    assert report['service'] == {'status': 'unconfigured'}
    # resilience block (docs/robustness.md): always present, healthy on a
    # clean local roundtrip — no open breakers, no hung reaps, no corruption
    resilience = report['resilience']
    assert resilience['workers_hung_reaped'] == 0
    assert resilience['shm_crc_failures'] == 0
    assert resilience['cache_corrupt_entries'] == 0
    assert all(state['state'] == 'closed'
               for state in resilience['breakers'].values())
    # flight-recorder block (ISSUE 6): one stable key, anomaly-free and
    # drop-free on a clean local roundtrip
    trace = report['trace']
    assert trace['events'] > 0
    assert trace['dropped_events'] == 0
    assert trace['anomaly_instants'] == []
    assert trace['top_rowgroup_traces']
    # autotune block (ISSUE 9): one stable key; the roundtrip arms a
    # long-window controller, so the catalog is live but no knob was turned
    autotune = report['autotune']
    assert autotune['enabled'] is True
    assert autotune['controller'] == 'reader'
    assert autotune['frozen_by_breaker'] is False
    assert 'pool_workers' in autotune['knobs']
    assert autotune['decisions'] == []
    # storage ingest-engine block (ISSUE 17): always present; the probe
    # forces the engine over a local store, so the footer cache sees a
    # miss (epoch 1) and a hit (epoch 2) while local disk fires no hedges
    storage = report['storage']
    assert storage['status'] == 'ok'
    assert storage['footer_cache_misses'] >= 1
    assert storage['footer_cache_hits'] >= 1
    assert storage['hedges_fired'] == 0


def test_check_storage_probe_counters():
    s = doctor.check_storage(rows=64, workers=1)
    assert s['status'] == 'ok'
    assert s['footer_cache_hits'] >= 1 and s['footer_cache_misses'] >= 1
    assert s['hedge_win_rate'] == 0.0
    from petastorm_tpu.storage import storage_metrics_snapshot
    # the probe cleans up after itself: global registry left reset
    assert not (storage_metrics_snapshot().get('counters') or {})


def test_service_unconfigured_by_default(monkeypatch):
    monkeypatch.delenv('PETASTORM_TPU_SERVICE_URL', raising=False)
    assert doctor.check_service() == {'status': 'unconfigured'}


def test_service_unreachable_reported(monkeypatch):
    # nothing listens on port 1; the probe must come back structured, fast
    s = doctor.check_service('tcp://127.0.0.1:1', timeout_s=0.5)
    assert s['status'] == 'unreachable'
    assert s['service_url'] == 'tcp://127.0.0.1:1'
    assert 'detail' in s and 'breakers' in s
    # the env var is the other configuration path (ISSUE 8)
    monkeypatch.setenv('PETASTORM_TPU_SERVICE_URL', 'tcp://127.0.0.1:1')
    assert doctor.check_service(timeout_s=0.5)['status'] == 'unreachable'


def test_service_reachable_reports_fleet_shape():
    from petastorm_tpu.service.dispatcher import Dispatcher
    dispatcher = Dispatcher()
    url = dispatcher.start()
    try:
        s = doctor.check_service(url, timeout_s=5.0)
    finally:
        dispatcher.stop()
        dispatcher.join()
    assert s['status'] == 'ok'
    assert s['service_url'] == url
    assert s['workers'] == 0 and s['clients'] == 0
    assert s['queue_depth'] == 0


def test_human_report_warns_on_unreachable_service(capsys):
    report = {
        'versions': {'petastorm_tpu': 'x', 'python': 'x', 'jax': 'x',
                     'pyarrow': 'x'},
        'backend': {'status': 'down', 'detail': ''},
        'store_roundtrip': {'status': 'ok', 'rows': 1, 'rows_per_sec': 1.0},
        'service': {'status': 'unreachable',
                    'service_url': 'tcp://fleet:8780', 'detail': 'timeout'},
        'healthy': True,
    }
    doctor._print_human(report)
    out = capsys.readouterr().out
    assert 'WARNING: input service at tcp://fleet:8780 is UNREACHABLE' in out


def test_human_report_warns_on_workerless_service(capsys):
    report = {
        'versions': {'petastorm_tpu': 'x', 'python': 'x', 'jax': 'x',
                     'pyarrow': 'x'},
        'backend': {'status': 'down', 'detail': ''},
        'store_roundtrip': {'status': 'ok', 'rows': 1, 'rows_per_sec': 1.0},
        'service': {'status': 'ok', 'service_url': 'tcp://fleet:8780',
                    'workers': 0, 'clients': 0, 'queue_depth': 0},
        'healthy': True,
    }
    doctor._print_human(report)
    out = capsys.readouterr().out
    assert 'service: tcp://fleet:8780' in out
    assert 'NO registered decode workers' in out


def test_human_report_warns_on_open_breaker(capsys):
    report = {
        'versions': {'petastorm_tpu': 'x', 'python': 'x', 'jax': 'x',
                     'pyarrow': 'x'},
        'backend': {'status': 'down', 'detail': ''},
        'store_roundtrip': {'status': 'ok', 'rows': 1, 'rows_per_sec': 1.0},
        'resilience': {'breakers': {'cache:/tmp/c': {'state': 'open'}},
                       'workers_hung_reaped': 2, 'shm_crc_failures': 1,
                       'cache_corrupt_entries': 0},
        'healthy': True,
    }
    doctor._print_human(report)
    out = capsys.readouterr().out
    assert 'WARNING: circuit breaker(s) not closed: cache:/tmp/c' in out
    assert 'workers_hung_reaped=2' in out and 'shm_crc_failures=1' in out


def test_human_report_autotune_line_and_frozen_warning(capsys):
    report = {
        'versions': {'petastorm_tpu': 'x', 'python': 'x', 'jax': 'x',
                     'pyarrow': 'x'},
        'backend': {'status': 'down', 'detail': ''},
        'store_roundtrip': {'status': 'ok', 'rows': 1, 'rows_per_sec': 1.0},
        'autotune': {'enabled': True, 'windows': 7, 'frozen_by_breaker': True,
                     'knobs': {'pool_workers': {'value': 2.0}},
                     'decisions': [{'action': 'freeze', 'knob': None}]},
        'healthy': True,
    }
    doctor._print_human(report)
    out = capsys.readouterr().out
    assert 'autotune: 1 knob(s) catalogued, 7 window(s), 1 decision(s)' in out
    assert 'last: freeze' in out
    assert 'WARNING: autotune is FROZEN by an open circuit breaker' in out


def test_human_report_autotune_disabled_prints_nothing(capsys):
    report = {
        'versions': {'petastorm_tpu': 'x', 'python': 'x', 'jax': 'x',
                     'pyarrow': 'x'},
        'backend': {'status': 'down', 'detail': ''},
        'store_roundtrip': {'status': 'failed', 'error': 'x'},
        'autotune': {'enabled': False},
        'healthy': False,
    }
    doctor._print_human(report)
    assert 'autotune' not in capsys.readouterr().out


def test_json_report_with_unreachable_service_url(capsys):
    # --service-url names a dead dispatcher: the block reports it, but an
    # unreachable EXTERNAL service does not make the install unhealthy
    rc = doctor.main(['--json', '--no-link', '--probe-timeout', '120',
                      '--service-url', 'tcp://127.0.0.1:1'])
    report = json.loads(capsys.readouterr().out.strip())
    assert rc == 0
    assert report['healthy'] is True
    assert report['service']['status'] == 'unreachable'
    assert report['service']['service_url'] == 'tcp://127.0.0.1:1'


def test_human_report_prints_verdict(capsys):
    rc = doctor.main(['--no-link', '--probe-timeout', '120'])
    out = capsys.readouterr().out
    assert rc == 0
    assert 'verdict: healthy' in out
    assert 'store roundtrip: OK' in out


def test_backend_probe_timeout_reported(monkeypatch):
    # A hanging backend init (a wedged device) must come back
    # as a structured 'timeout', not a wedged doctor.
    monkeypatch.setattr(doctor, 'PROBE_CODE', 'import time; time.sleep(30)')
    b = doctor.check_backend(timeout_s=2)
    assert b['status'] == 'timeout'
    assert b['devices'] == 0


def test_backend_probe_down_reported(monkeypatch):
    monkeypatch.setattr(doctor, 'PROBE_CODE',
                        'import sys; sys.stderr.write("boom\\n"); sys.exit(3)')
    b = doctor.check_backend(timeout_s=30)
    assert b['status'] == 'down'
    assert 'boom' in b['detail']


def test_backend_probe_skips_plugin_banners(monkeypatch):
    # Accelerator plugins write banner text to stdout before the probe's own
    # print; the parser must take the LAST line.
    monkeypatch.setattr(
        doctor, 'PROBE_CODE',
        'print("some plugin banner text"); print("tpu 4")')
    b = doctor.check_backend(timeout_s=30)
    assert b == {'status': 'up', 'platform': 'tpu', 'devices': 4}


def test_backend_probe_unparseable_output(monkeypatch):
    monkeypatch.setattr(doctor, 'PROBE_CODE', 'print("just noise here")')
    b = doctor.check_backend(timeout_s=30)
    assert b['status'] == 'down'
    assert 'unparseable' in b['detail']


def test_link_probe_timeout_reported(monkeypatch):
    # A device that wedges AFTER the backend
    # probe succeeded used to hang the doctor in-process. Now it's a
    # subprocess with a hard timeout reporting a structured link failure.
    monkeypatch.setattr(doctor, 'LINK_PROBE_CODE', 'import time; time.sleep(30)')
    link = doctor.check_link(timeout_s=2)
    assert link['status'] == 'timeout'
    assert 'wedged' in link['detail']


def test_link_probe_crash_reported(monkeypatch):
    monkeypatch.setattr(
        doctor, 'LINK_PROBE_CODE',
        'import sys; sys.stderr.write("link broke\\n"); sys.exit(2)')
    link = doctor.check_link(timeout_s=30)
    assert link['status'] == 'fail'
    assert 'link broke' in link['detail']


def test_link_probe_parses_past_banner_noise(monkeypatch):
    monkeypatch.setattr(
        doctor, 'LINK_PROBE_CODE',
        'print("plugin banner"); '
        'print(\'LINKPROBE_JSON {{"dispatch_rtt_ms": 1.5, '
        '"streaming_ceiling_rows_per_sec_at_1kib": {row_bytes}.0}}\')')
    link = doctor.check_link(reference_row_bytes=2048, timeout_s=30)
    assert link['dispatch_rtt_ms'] == 1.5
    # the format() substitution reached the child code
    assert link['streaming_ceiling_rows_per_sec_at_1kib'] == 2048.0


def test_link_probe_real_on_cpu():
    # Real in-subprocess probe against the CPU backend: exercises the
    # PYTHONPATH plumbing and the linkprobe import inside the child.
    link = doctor.check_link(timeout_s=120)
    assert 'dispatch_rtt_ms' in link, link
    assert link['streaming_ceiling_rows_per_sec_at_1kib'] > 0
