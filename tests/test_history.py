"""Longitudinal performance observatory tests (ISSUE 18,
docs/observability.md "Longitudinal observatory"): the CRC-framed run-record
historian (restart survival, torn-tail tolerance, atomic rotation), the
trailing median/MAD compare + change-point attribution engine with its
exit-coded CLI, the live regression sentinel (Page-Hinkley drift matrix:
step drop fires exactly once, slow drift fires, noisy stationary never
false-positives) wired into the incident plane, and the satellites
(SloTracker ring-buffer history, autotune warm start, bench section
registration)."""
import importlib.util
import json
import os
import struct
import zlib

import pytest

from petastorm_tpu import make_reader
from petastorm_tpu.telemetry.history import (COMPARE_EXIT_CODES,
                                             EXIT_BAD_STORE,
                                             HISTORY_BASENAME, HistoryPolicy,
                                             RunHistorian, build_run_record,
                                             compare_against_history,
                                             compare_records, fingerprint,
                                             last_good_record, load_records,
                                             read_history,
                                             resolve_history_policy,
                                             robust_baseline, run_platform,
                                             select_records,
                                             stage_time_shares,
                                             trailing_baseline)
from petastorm_tpu.telemetry.history import main as history_main
from petastorm_tpu.telemetry.registry import SECONDS_UNIT, MetricsRegistry
from petastorm_tpu.telemetry.sentinel import (DriftDetector,
                                              RegressionSentinel,
                                              SentinelPolicy,
                                              resolve_sentinel_policy)
from petastorm_tpu.telemetry.slo import SloTracker


def _record(rate=100.0, token='tok', platform='test-plat', owner='reader',
            shares=None, knobs=None, fingerprints=None, stamp=1000.0,
            efficiency=0.9):
    snapshot = {'histograms': {}}
    for stage, share in (shares or {}).items():
        snapshot['histograms'][stage] = {
            'unit': SECONDS_UNIT, 'count': 1, 'sum': share * 10.0,
            'max': 1.0, 'mean': 1.0, 'buckets': {}}
    return build_run_record(
        owner, token, elapsed_s=10.0, rows=int(rate * 10), snapshot=snapshot,
        slo_report={'efficiency': efficiency, 'wait_seconds': 1.0,
                    'primary_wait_stage': 'pool_wait'},
        fingerprints=fingerprints or {'config': 'abc'},
        knobs=knobs or {'decode_threads': 4.0},
        platform=platform, recorded_unix_s=stamp)


# ---------------------------------------------------------------------------
# journal discipline
# ---------------------------------------------------------------------------

class TestRunHistorianJournal:
    def test_round_trip_and_restart(self, tmp_path):
        path = str(tmp_path / 'hist.bin')
        historian = RunHistorian(path)
        for i in range(3):
            assert historian.append(_record(rate=100.0 + i, stamp=float(i)))
        # a NEW historian instance (process restart) replays the same store
        records, dropped = read_history(path)
        assert dropped == 0
        assert [r['recorded_unix_s'] for r in records] == [0.0, 1.0, 2.0]
        historian2 = RunHistorian(path)
        historian2.append(_record(stamp=3.0))
        records, dropped = read_history(path)
        assert len(records) == 4 and dropped == 0

    def test_torn_tail_is_dropped_not_fatal(self, tmp_path):
        path = str(tmp_path / 'hist.bin')
        historian = RunHistorian(path)
        for i in range(3):
            historian.append(_record(stamp=float(i)))
        with open(path, 'ab') as f:
            f.write(b'\x00\x00\x01\x00GARB')  # torn frame: header + short payload
        records, dropped = read_history(path)
        assert len(records) == 3 and dropped == 1
        # the next append heals the store: the torn frame triggers a
        # compaction that keeps the survivors AND the new record
        historian.append(_record(stamp=9.0))
        records, dropped = read_history(path)
        assert dropped == 0
        assert [r['recorded_unix_s'] for r in records] == [0.0, 1.0, 2.0, 9.0]

    def test_corrupt_crc_abandons_suffix(self, tmp_path):
        path = str(tmp_path / 'hist.bin')
        historian = RunHistorian(path)
        for i in range(3):
            historian.append(_record(stamp=float(i)))
        data = bytearray(open(path, 'rb').read())
        data[12] ^= 0xFF  # flip a byte inside the first frame's payload
        open(path, 'wb').write(bytes(data))
        records, dropped = read_history(path)
        assert records == [] and dropped == 1

    def test_rotation_keeps_newest(self, tmp_path):
        path = str(tmp_path / 'hist.bin')
        historian = RunHistorian(path, policy=HistoryPolicy(max_records=5),
                                 rotate_bytes=1)  # force rotation every append
        for i in range(8):
            historian.append(_record(stamp=float(i)))
        records, dropped = read_history(path)
        assert dropped == 0
        assert [r['recorded_unix_s'] for r in records] == [3.0, 4.0, 5.0,
                                                           6.0, 7.0]

    def test_missing_and_unreadable_store(self, tmp_path):
        assert load_records(str(tmp_path / 'absent.bin')) == ([], 0)
        assert load_records(None) == ([], 0)

    def test_newer_schema_records_are_skipped(self, tmp_path):
        path = str(tmp_path / 'hist.bin')
        historian = RunHistorian(path)
        historian.append(_record(stamp=1.0))
        payload = json.dumps({'schema': 999, 'kind': 'run'}).encode()
        with open(path, 'ab') as f:
            f.write(struct.Struct('>II').pack(len(payload),
                                              zlib.crc32(payload)) + payload)
        historian.append(_record(stamp=2.0))
        records, dropped = read_history(path)
        assert dropped == 0
        assert [r['recorded_unix_s'] for r in records] == [1.0, 2.0]

    def test_append_counter_and_state(self, tmp_path):
        registry = MetricsRegistry()
        historian = RunHistorian(str(tmp_path / 'hist.bin'),
                                 registry=registry)
        historian.append(_record())
        assert registry.snapshot()['counters']['history_record_written'] == 1
        state = historian.state()
        assert state['appended'] == 1 and state['frames_dropped'] == 0


class TestHistoryPolicy:
    def test_resolution_convention(self, tmp_path):
        assert resolve_history_policy(None) is None
        assert resolve_history_policy(False) is None
        assert resolve_history_policy(True) == HistoryPolicy()
        path = str(tmp_path / 's.bin')
        assert resolve_history_policy(path).path == path
        policy = HistoryPolicy(baseline_window=4)
        assert resolve_history_policy(policy) is policy
        with pytest.raises(ValueError):
            resolve_history_policy(42)

    def test_validation(self):
        with pytest.raises(ValueError):
            HistoryPolicy(max_records=0)
        with pytest.raises(ValueError):
            HistoryPolicy(baseline_window=0)
        with pytest.raises(ValueError):
            HistoryPolicy(noise_mads=-1.0)


# ---------------------------------------------------------------------------
# run records
# ---------------------------------------------------------------------------

class TestRunRecord:
    def test_stage_shares_are_unit_gated(self):
        snapshot = {'histograms': {
            'decode': {'unit': SECONDS_UNIT, 'count': 1, 'sum': 4.0,
                       'max': 1, 'mean': 1, 'buckets': {}},
            'row_bytes': {'unit': 1.0, 'count': 1, 'sum': 1e9,
                          'max': 1, 'mean': 1, 'buckets': {}},
            'cache_miss': {'unit': SECONDS_UNIT, 'count': 1, 'sum': 2.0,
                           'max': 1, 'mean': 1, 'buckets': {}},
        }}
        shares = stage_time_shares(snapshot, elapsed_s=10.0)
        # seconds-unit leaf stages only: the byte histogram and the
        # envelope-overlapped cache_miss stage never pollute the shares
        assert shares == {'decode': 0.4}

    def test_record_shape(self):
        record = _record(rate=100.0, shares={'decode': 0.3})
        assert record['schema'] == 1 and record['kind'] == 'run'
        assert record['rows_per_sec'] == 100.0
        assert record['stage_shares'] == {'decode': 0.3}
        assert record['storage'] == {'footer_cache_hit_rate': None,
                                     'hedge_win_rate': None}
        assert record['incidents'] == {'captured': 0, 'rate_limited': 0}
        json.dumps(record)  # JSON-safe end to end

    def test_fingerprint_is_stable_and_order_free(self):
        assert fingerprint({'a': 1, 'b': 2}) == fingerprint({'b': 2, 'a': 1})
        assert fingerprint({'a': 1}) != fingerprint({'a': 2})
        assert len(fingerprint({'a': 1})) == 12


# ---------------------------------------------------------------------------
# compare / attribution engine
# ---------------------------------------------------------------------------

class TestCompareEngine:
    def test_robust_baseline_median_mad(self):
        base = robust_baseline([100.0, 104.0, 96.0, 102.0, 1000.0])
        assert base['median'] == 102.0  # the outlier cannot drag the median
        assert base['mad'] == 2.0

    def test_select_and_trailing_baseline(self):
        records = ([_record(rate=100.0 + i, stamp=float(i)) for i in range(10)]
                   + [_record(token='other'), _record(platform='other')])
        assert len(select_records(records, 'tok', 'test-plat')) == 10
        baseline = trailing_baseline(records, 'tok', 'test-plat', window=4)
        assert baseline['count'] == 4
        assert baseline['rows_per_sec']['median'] == 107.5

    def test_insufficient_history(self):
        records = [_record(stamp=1.0)]
        report = compare_against_history(records, _record(stamp=2.0))
        assert report['verdict'] == 'insufficient-history'
        assert report['exit_code'] == COMPARE_EXIT_CODES[
            'insufficient-history']

    def test_same_config_within_noise(self):
        records = [_record(rate=100.0 + (i % 3), stamp=float(i))
                   for i in range(6)]
        candidate = _record(rate=101.0, stamp=99.0)
        report = compare_against_history(records, candidate)
        assert report['verdict'] == 'within-noise'
        assert report['exit_code'] == 0

    def test_deliberate_knob_change_attributes_and_regresses(self):
        records = [_record(rate=100.0 + (i % 3), stamp=float(i),
                           shares={'decode': 0.2}) for i in range(6)]
        candidate = _record(rate=50.0, stamp=99.0, shares={'decode': 0.5},
                            knobs={'decode_threads': 2.0},
                            fingerprints={'config': 'xyz'})
        report = compare_against_history(records, candidate)
        assert report['verdict'] == 'regressed'
        assert report['exit_code'] == COMPARE_EXIT_CODES['regressed']
        attribution = report['attribution']
        assert attribution['grown_stages'][0]['stage'] == 'decode'
        assert 'knob decode_threads 4 -> 2' in attribution['changed_knobs']
        assert any('config' in entry
                   for entry in attribution['changed_fingerprints'])
        # the one-line reason names the knob diff — the "decode share +18%,
        # knob decode_threads 4->2" surface the issue asks for
        assert 'decode_threads' in report['reason']
        assert 'decode share' in report['reason']

    def test_improvement_is_exit_coded_distinctly(self):
        records = [_record(rate=100.0, stamp=float(i)) for i in range(6)]
        report = compare_against_history(records, _record(rate=200.0,
                                                          stamp=99.0))
        assert report['verdict'] == 'improved'
        assert report['exit_code'] == COMPARE_EXIT_CODES['improved']

    def test_noise_band_capped_by_max_rel_delta(self):
        # one cold-start outlier blows the MAD past the median; the band
        # cap must still read a halved throughput as a regression
        records = [_record(rate=r, stamp=float(i)) for i, r in
                   enumerate([800.0, 11000.0, 15000.0, 10500.0])]
        candidate = _record(rate=4300.0, stamp=99.0)
        report = compare_against_history(records, candidate)
        assert report['noise_band_rows_per_sec'] <= \
            0.5 * report['baseline']['median_rows_per_sec']
        assert report['verdict'] == 'regressed'
        with pytest.raises(ValueError):
            HistoryPolicy(min_rel_delta=0.3, max_rel_delta=0.1)

    def test_candidate_excluded_from_its_own_baseline(self):
        records = [_record(rate=100.0, stamp=float(i)) for i in range(5)]
        candidate = _record(rate=50.0, stamp=99.0)
        records.append(candidate)
        report = compare_against_history(records, candidate)
        assert report['baseline']['count'] == 5
        assert report['verdict'] == 'regressed'

    def test_last_good_record_gates_warm_start(self):
        records = [_record(stamp=1.0), _record(stamp=2.0, rate=111.0)]
        newest = last_good_record(records, 'tok', 'test-plat')
        assert newest['rows_per_sec'] == 111.0
        assert last_good_record(records, 'absent-token') is None


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

class TestHistoryCli:
    def _store(self, tmp_path, candidate_rate=101.0):
        path = str(tmp_path / HISTORY_BASENAME)
        historian = RunHistorian(path)
        for i in range(6):
            historian.append(_record(rate=100.0 + (i % 3), stamp=float(i)))
        historian.append(_record(rate=candidate_rate, stamp=99.0))
        return path

    def test_list_and_show(self, tmp_path, capsys):
        path = self._store(tmp_path)
        assert history_main(['list', path]) == 0
        out = capsys.readouterr().out
        assert '7 record(s)' in out and 'token=tok' in out
        assert history_main(['show', path, '--index', '0']) == 0
        shown = json.loads(capsys.readouterr().out)
        assert shown['recorded_unix_s'] == 0.0

    def test_compare_exit_codes(self, tmp_path, capsys):
        within = self._store(tmp_path / 'a', candidate_rate=101.0)
        assert history_main(['compare', within]) == 0
        regressed = self._store(tmp_path / 'b', candidate_rate=50.0)
        assert history_main(['compare', regressed]) == COMPARE_EXIT_CODES[
            'regressed']
        improved = self._store(tmp_path / 'c', candidate_rate=200.0)
        assert history_main(['compare', improved]) == COMPARE_EXIT_CODES[
            'improved']
        capsys.readouterr()

    def test_compare_json_and_against(self, tmp_path, capsys):
        path = self._store(tmp_path, candidate_rate=50.0)
        code = history_main(['compare', path, '--json'])
        report = json.loads(capsys.readouterr().out)
        assert code == COMPARE_EXIT_CODES['regressed']
        assert report['verdict'] == 'regressed'
        # pairwise compare against one explicit record
        assert history_main(['compare', path, '--against', '0']
                            ) == COMPARE_EXIT_CODES['regressed']
        capsys.readouterr()

    def test_insufficient_history_exit(self, tmp_path, capsys):
        path = str(tmp_path / 'thin.bin')
        RunHistorian(path).append(_record(stamp=1.0))
        assert history_main(['compare', path]) == COMPARE_EXIT_CODES[
            'insufficient-history']
        capsys.readouterr()

    def test_missing_store_exit(self, tmp_path, capsys):
        assert history_main(['list', str(tmp_path / 'none.bin')]
                            ) == EXIT_BAD_STORE
        capsys.readouterr()

    def test_throughput_cli_dispatch(self, tmp_path, capsys):
        from petastorm_tpu.benchmark.cli import main as throughput_main
        path = self._store(tmp_path)
        assert throughput_main(['history', 'list', path]) == 0
        assert '7 record(s)' in capsys.readouterr().out


# ---------------------------------------------------------------------------
# drift detector matrix
# ---------------------------------------------------------------------------

class TestDriftDetectorMatrix:
    def test_step_drop_fires_exactly_once(self):
        sentinel = RegressionSentinel(SentinelPolicy(min_window_s=1.0),
                                      owner='reader')
        alarms = []
        sentinel._on_alarm = alarms.append
        rows, rate = 0, 1000
        for window in range(60):
            if window == 30:
                rate = 400  # one sustained collapse
            rows += rate
            sentinel.observe_sample(float(window + 1), rows)
        # edge-triggered: the NEW level becomes the baseline after the alarm,
        # so one collapse is one alarm, not one per subsequent window
        assert len(alarms) == 1
        evidence = alarms[0]
        assert evidence['series'] == 'rate'
        assert evidence['pre_rate_rows_per_sec'] > \
            evidence['post_rate_rows_per_sec']

    def test_slow_drift_fires(self):
        sentinel = RegressionSentinel(SentinelPolicy(min_window_s=1.0),
                                      owner='reader')
        rows, rate = 0, 1000.0
        for window in range(120):
            if window >= 20:
                rate *= 0.97  # -3%/window slow leak
            rows += rate
            sentinel.observe_sample(float(window + 1), int(rows))
        assert sentinel.alarms >= 1

    def test_noisy_stationary_never_false_positives(self):
        import random
        rng = random.Random(1234)
        sentinel = RegressionSentinel(SentinelPolicy(min_window_s=1.0),
                                      owner='reader')
        rows = 0.0
        for window in range(1000):
            rows += rng.uniform(900, 1100)  # +/-10% noise, level flat
            sentinel.observe_sample(float(window + 1), int(rows))
        assert sentinel.alarms == 0

    def test_wait_share_growth_fires_wait_series(self):
        sentinel = RegressionSentinel(SentinelPolicy(min_window_s=1.0),
                                      owner='loader')
        alarms = []
        sentinel._on_alarm = alarms.append
        rows, wait = 0, 0.0
        for window in range(60):
            rows += 1000  # rate stays flat: only the wait share grows
            wait += 0.02 if window < 30 else 0.6
            sentinel.observe_sample(float(window + 1), rows,
                                    wait_seconds=wait,
                                    primary_wait_stage='shuffle_wait')
        assert [a['series'] for a in alarms] == ['wait_share']
        assert alarms[0]['grown_stage'] == 'shuffle_wait'

    def test_detector_warmup_and_reset(self):
        detector = DriftDetector(delta=0.05, threshold=0.6, warmup=3,
                                 relative=True, direction='drop')
        for _ in range(3):
            assert not detector.update(1000.0)  # warmup builds the mean only
        assert not detector.update(1000.0)
        fired = any(detector.update(100.0) for _ in range(20))
        assert fired
        # full reset on alarm: the new level is the new baseline
        assert not any(detector.update(100.0) for _ in range(20))

    def test_due_gating_and_max_alarms(self):
        policy = SentinelPolicy(min_window_s=2.0, max_alarms=1)
        sentinel = RegressionSentinel(policy, owner='reader')
        assert sentinel.due(0.0)  # first sample always anchors
        sentinel.observe_sample(0.0, 0)
        assert not sentinel.due(1.0)
        assert sentinel.due(2.5)
        rows, rate = 0, 1000
        for window in range(200):
            if window and window % 40 == 0:
                rate = max(rate // 3, 1)  # repeated collapses
            rows += rate * 3
            sentinel.observe_sample(float(window + 1) * 3.0, rows)
        assert sentinel.alarms == 1  # capped
        assert not sentinel.due(1e9)

    def test_policy_resolution_and_validation(self):
        assert resolve_sentinel_policy(None) is None
        assert resolve_sentinel_policy(False) is None
        assert resolve_sentinel_policy(True) == SentinelPolicy()
        policy = SentinelPolicy(min_window_s=5.0)
        assert resolve_sentinel_policy(policy) is policy
        with pytest.raises(ValueError):
            resolve_sentinel_policy('nope')
        with pytest.raises(ValueError):
            SentinelPolicy(min_window_s=0.0)
        with pytest.raises(ValueError):
            SentinelPolicy(ewma_alpha=2.0)

    def test_report_and_gauges(self):
        registry = MetricsRegistry()
        sentinel = RegressionSentinel(SentinelPolicy(min_window_s=1.0),
                                      owner='reader', registry=registry,
                                      dataset_token='tok')
        rows = 0
        for window in range(5):
            rows += 1000
            sentinel.observe_sample(float(window + 1), rows)
        sentinel.export_gauges()
        report = sentinel.report()
        assert report['armed'] and report['owner'] == 'reader'
        assert report['windows'] == 4 and report['alarms'] == 0
        gauges = registry.snapshot()['gauges']
        assert gauges['sentinel_rate_ewma'] == pytest.approx(1000.0)
        # no wait series was fed: the wait gauge must not export a fake 0.0
        assert 'sentinel_wait_share_ewma' not in gauges


# ---------------------------------------------------------------------------
# sentinel -> incident plane
# ---------------------------------------------------------------------------

class TestSentinelIncidentPlane:
    def _collapse(self, sentinel):
        rows, rate = 0, 1000
        for window in range(60):
            if window == 30:
                rate = 300
            rows += rate
            sentinel.observe_sample(float(window + 1), rows)

    def test_collapse_captures_exactly_one_bundle(self, tmp_path):
        from petastorm_tpu.telemetry.incident import (IncidentPolicy,
                                                      IncidentRecorder,
                                                      scan_bundles)
        registry = MetricsRegistry()
        recorder = IncidentRecorder(str(tmp_path), IncidentPolicy(),
                                    registry=registry)
        sentinel = RegressionSentinel(SentinelPolicy(min_window_s=1.0),
                                      owner='reader', registry=registry,
                                      incidents=recorder,
                                      dataset_token='tok')
        recorder.add_source('sentinel', sentinel.report)
        self._collapse(sentinel)
        bundles = scan_bundles(str(tmp_path))
        kinds = [entry['kind'] for entry in bundles]
        assert kinds.count('perf_regression') == 1
        assert registry.snapshot()['counters']['perf_regression'] == 1

    def test_bundle_autopsy_sees_the_sentinel_evidence(self, tmp_path):
        from petastorm_tpu.telemetry.incident import (IncidentPolicy,
                                                      IncidentRecorder,
                                                      analyze_bundle,
                                                      scan_bundles)
        recorder = IncidentRecorder(str(tmp_path), IncidentPolicy())
        sentinel = RegressionSentinel(SentinelPolicy(min_window_s=1.0),
                                      owner='reader', incidents=recorder,
                                      dataset_token='tok')
        recorder.add_source('sentinel', sentinel.report)
        self._collapse(sentinel)
        bundle = scan_bundles(str(tmp_path))[0]['path']
        report = analyze_bundle(bundle)
        assert report['trigger'] == 'perf_regression'
        assert any('regression sentinel fired' in clue
                   for cause in report['causes']
                   for clue in cause.get('evidence', []))

    def test_undisturbed_run_captures_nothing(self, tmp_path):
        from petastorm_tpu.telemetry.incident import (IncidentPolicy,
                                                      IncidentRecorder,
                                                      scan_bundles)
        recorder = IncidentRecorder(str(tmp_path), IncidentPolicy())
        sentinel = RegressionSentinel(SentinelPolicy(min_window_s=1.0),
                                      owner='reader', incidents=recorder)
        rows = 0
        for window in range(100):
            rows += 1000
            sentinel.observe_sample(float(window + 1), rows)
        assert sentinel.alarms == 0
        assert scan_bundles(str(tmp_path)) == []

    def test_dead_recorder_never_breaks_the_run(self):
        class Exploding:
            def trigger(self, *a, **k):
                raise RuntimeError('recorder died')
        sentinel = RegressionSentinel(SentinelPolicy(min_window_s=1.0),
                                      owner='reader', incidents=Exploding())
        self._collapse(sentinel)
        assert sentinel.alarms == 1  # alarm recorded, exception swallowed


# ---------------------------------------------------------------------------
# reader / loader / dispatcher wiring
# ---------------------------------------------------------------------------

class TestReaderHistoryWiring:
    def test_off_path_builds_nothing(self, synthetic_dataset):
        with make_reader(synthetic_dataset.url, schema_fields=['id'],
                         workers_count=1, num_epochs=1) as reader:
            assert reader._history is None
            assert reader._sentinel is None
            assert reader.history_report() is None
            for _ in reader:
                pass
        dataset_path = synthetic_dataset.url[len('file://'):]
        assert not os.path.exists(os.path.join(dataset_path,
                                               HISTORY_BASENAME))

    def test_two_runs_record_two_comparable_records(self, tmp_path,
                                                    synthetic_dataset):
        store = str(tmp_path / 'hist.bin')
        for _ in range(2):
            with make_reader(synthetic_dataset.url, schema_fields=['id'],
                             workers_count=1, num_epochs=1,
                             history=store) as reader:
                for _ in reader:
                    pass
                token = reader.dataset_token
        records, dropped = load_records(store)
        assert dropped == 0 and len(records) == 2
        for record in records:
            assert record['owner'] == 'reader'
            assert record['dataset_token'] == token
            assert record['platform'] == run_platform()
            assert record['rows'] > 0 and record['rows_per_sec'] > 0
            assert record['fingerprints']['config']
            assert 'decode_threads' in record['knobs']
        # identical construction: identical config fingerprint
        assert (records[0]['fingerprints']['config']
                == records[1]['fingerprints']['config'])

    def test_stop_is_idempotent_one_record(self, tmp_path, synthetic_dataset):
        store = str(tmp_path / 'hist.bin')
        reader = make_reader(synthetic_dataset.url, schema_fields=['id'],
                             workers_count=1, num_epochs=1, history=store)
        for _ in reader:
            pass
        reader.stop()
        reader.stop()
        reader.join()
        records, _ = load_records(store)
        assert len(records) == 1

    def test_diagnostics_and_sentinel_armed(self, tmp_path,
                                            synthetic_dataset):
        store = str(tmp_path / 'hist.bin')
        with make_reader(synthetic_dataset.url, schema_fields=['id'],
                         workers_count=1, num_epochs=1,
                         history=store) as reader:
            assert reader._sentinel is not None
            for _ in reader:
                pass
            diag = reader.diagnostics
            assert diag['history']['path'] == store
            assert diag['sentinel']['owner'] == 'reader'

    def test_warm_start_seeds_from_last_good_record(self, tmp_path,
                                                    synthetic_dataset):
        from petastorm_tpu.autotune import AutotunePolicy
        store = str(tmp_path / 'hist.bin')
        with make_reader(synthetic_dataset.url, schema_fields=['id'],
                         workers_count=1, num_epochs=1,
                         history=store) as reader:
            for _ in reader:
                pass
        records, _ = load_records(store)
        forged = dict(records[-1])
        forged['knobs'] = dict(forged['knobs'],
                               ventilator_max_in_flight=5.0)
        RunHistorian(store).append(forged)
        policy = AutotunePolicy(warm_start=True, warmup_windows=1000)
        with make_reader(synthetic_dataset.url, schema_fields=['id'],
                         workers_count=1, num_epochs=1, history=store,
                         autotune=policy) as reader:
            decisions = reader.autotune_report()['decisions']
            seeded = [d for d in decisions if d['action'] == 'warm_start']
            assert any(d['knob'] == 'ventilator_max_in_flight'
                       and d['to'] == 5.0 for d in seeded)
            for _ in reader:
                pass

    def test_warm_start_gated_off_without_comparable_record(
            self, tmp_path, synthetic_dataset):
        from petastorm_tpu.autotune import AutotunePolicy
        policy = AutotunePolicy(warm_start=True, warmup_windows=1000)
        with make_reader(synthetic_dataset.url, schema_fields=['id'],
                         workers_count=1, num_epochs=1,
                         history=str(tmp_path / 'empty.bin'),
                         autotune=policy) as reader:
            decisions = reader.autotune_report()['decisions']
            assert [d for d in decisions
                    if d['action'] == 'warm_start'] == []
            for _ in reader:
                pass


class TestLoaderHistoryWiring:
    def test_loader_and_reader_both_record(self, tmp_path,
                                           synthetic_dataset):
        from petastorm_tpu.parallel import JaxDataLoader
        store = str(tmp_path / 'hist.bin')
        reader = make_reader(synthetic_dataset.url, schema_fields=['id'],
                             workers_count=1, num_epochs=1, history=store)
        with JaxDataLoader(reader, batch_size=8, history=True) as loader:
            for _ in loader:
                pass
        records, dropped = load_records(store)
        assert dropped == 0
        owners = sorted(record['owner'] for record in records)
        assert owners == ['loader', 'reader']
        loader_record = next(r for r in records if r['owner'] == 'loader')
        assert 'loader' in loader_record['fingerprints']

    def test_loader_without_store_warns_and_disables(self, tmp_path,
                                                     synthetic_dataset):
        import warnings as warnings_module
        from petastorm_tpu.parallel import JaxDataLoader
        reader = make_reader(synthetic_dataset.url, schema_fields=['id'],
                             workers_count=1, num_epochs=1)
        with warnings_module.catch_warnings(record=True) as caught:
            warnings_module.simplefilter('always')
            with JaxDataLoader(reader, batch_size=8,
                               history=True) as loader:
                assert loader._history is None
                for _ in loader:
                    pass
        assert any('no store path' in str(w.message) for w in caught)


class TestDispatcherHistoryWiring:
    def test_dispatcher_records_one_service_record(self, tmp_path):
        pytest.importorskip('zmq')
        from petastorm_tpu.service.dispatcher import (SERVICE_DATASET_TOKEN,
                                                      Dispatcher)
        store = str(tmp_path / 'service-hist.bin')
        dispatcher = Dispatcher(history=store)
        dispatcher.start()
        state = dispatcher.state()
        assert state['history']['path'] == store
        assert state['sentinel']['owner'] == 'dispatcher'
        dispatcher.stop()
        dispatcher.join()
        records, dropped = load_records(store)
        assert dropped == 0 and len(records) == 1
        assert records[0]['owner'] == 'dispatcher'
        assert records[0]['dataset_token'] == SERVICE_DATASET_TOKEN
        assert records[0]['fingerprints']['config']

    def test_history_true_arms_sentinel_only(self):
        pytest.importorskip('zmq')
        from petastorm_tpu.service.dispatcher import Dispatcher
        dispatcher = Dispatcher(history=True)
        assert dispatcher._history is None  # no dataset home to default into
        assert dispatcher._sentinel is not None
        assert dispatcher.history_report() is None

    def test_fleet_resolves_a_store_under_its_cache_dir(self, tmp_path):
        pytest.importorskip('zmq')
        from petastorm_tpu.service.fleet import ServiceFleet
        fleet = ServiceFleet(workers=0, cache_dir=str(tmp_path),
                             history=True)
        assert fleet.history_path == str(tmp_path / HISTORY_BASENAME)
        assert fleet.dispatcher._history is not None


# ---------------------------------------------------------------------------
# satellites: SLO ring buffer, bench section registration
# ---------------------------------------------------------------------------

class TestSloHistoryRingBuffer:
    def _snapshot(self):
        return {'histograms': {'pool_wait': {
            'unit': SECONDS_UNIT, 'count': 1, 'sum': 0.5, 'max': 0.5,
            'mean': 0.5, 'buckets': {}}}}

    def test_ring_buffer_bounds_and_shape(self):
        tracker = SloTracker(history_size=4)
        for i in range(6):
            report = tracker.evaluate(self._snapshot(), elapsed_s=2.0 + i,
                                      rows=100)
        assert len(report['history']) == 4
        point = report['history'][-1]
        assert sorted(point) == ['breached', 'efficiency', 'elapsed_s',
                                 'goodput_rows_per_sec', 'wait_seconds']
        assert len(tracker.history()) == 4

    def test_warmup_windows_never_enter_history(self):
        tracker = SloTracker()
        report = tracker.evaluate(self._snapshot(), elapsed_s=0.1)
        assert report['history'] == []

    def test_reader_vars_carry_the_history(self, tmp_path,
                                           synthetic_dataset):
        with make_reader(synthetic_dataset.url, schema_fields=['id'],
                         workers_count=1, num_epochs=1,
                         history=str(tmp_path / 'h.bin')) as reader:
            for _ in reader:
                pass
            snapshot, report = reader._snapshot_with_slo()
            assert snapshot['slo_history'] == report['history']


class TestBenchHistorySection:
    def _load_bench(self):
        spec = importlib.util.spec_from_file_location(
            'bench_module_history',
            os.path.join(os.path.dirname(__file__), '..', 'bench.py'))
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        return module

    def test_history_section_registered(self):
        bench = self._load_bench()
        assert 'history' in bench.SECTION_NAMES
        assert 'history' in bench.SECTION_RUN_ORDER
        assert sorted(bench.SECTION_RUN_ORDER) == sorted(bench.SECTION_NAMES)
