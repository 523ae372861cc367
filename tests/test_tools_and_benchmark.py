"""Tools / CLI / benchmark-harness / generator / mock / hdfs-resolver tests (model:
petastorm tests for copy_dataset, generate_metadata, metadata_util, throughput,
reader_mock, hdfs namenode)."""

import os

import numpy as np
import pytest

from petastorm_tpu import make_reader
from petastorm_tpu.etl.dataset_metadata import get_schema, open_dataset


class TestCopyDataset:
    def test_full_copy(self, synthetic_dataset, tmp_path):
        from petastorm_tpu.tools.copy_dataset import copy_dataset
        target = str(tmp_path / 'copy')
        count = copy_dataset(synthetic_dataset.url, target)
        assert count == 100
        with make_reader(target, workers_count=1) as reader:
            assert len({row.id for row in reader}) == 100

    def test_field_subset(self, synthetic_dataset, tmp_path):
        from petastorm_tpu.tools.copy_dataset import copy_dataset
        target = str(tmp_path / 'subset')
        copy_dataset(synthetic_dataset.url, target, field_regex=['id.*'])
        schema = get_schema(open_dataset(target))
        assert set(schema.fields) == {'id', 'id2'}

    def test_not_null_filter(self, synthetic_dataset, tmp_path):
        from petastorm_tpu.tools.copy_dataset import copy_dataset
        target = str(tmp_path / 'notnull')
        count = copy_dataset(synthetic_dataset.url, target,
                             field_regex=['id', 'nullable_int'],
                             not_null_fields=['nullable_int'])
        expected = sum(1 for r in synthetic_dataset.rows if r['nullable_int'] is not None)
        assert count == expected

    def test_cli(self, synthetic_dataset, tmp_path):
        from petastorm_tpu.tools.copy_dataset import main
        target = str(tmp_path / 'cli_copy')
        assert main([synthetic_dataset.url, target, '--field-regex', 'id']) == 0


class TestGenerateMetadata:
    def test_regenerate_after_metadata_loss(self, tmp_path):
        from test_common import create_test_dataset
        from petastorm_tpu.etl.generate_metadata import generate_metadata
        url = str(tmp_path / 'ds')
        create_test_dataset(url, num_rows=10)
        schema_before = get_schema(open_dataset(url))
        os.remove(os.path.join(url, '_common_metadata'))
        generate_metadata(url)  # infers (no codecs) but restores readability
        handle = open_dataset(url)
        assert get_schema(handle) is not None

    def test_upgrades_legacy_pickle(self, tmp_path):
        """A reference-written store gets its pickled schema upgraded to the JSON key."""
        reference_dir = '/root/reference/petastorm/tests/data/legacy/0.7.6'
        if not os.path.isdir(reference_dir):
            pytest.skip('reference datasets not mounted')
        import shutil
        from petastorm_tpu.etl.dataset_metadata import (UNISCHEMA_JSON_KEY,
                                                        read_metadata_dict)
        from petastorm_tpu.etl.generate_metadata import generate_metadata
        url = str(tmp_path / 'legacy_copy')
        shutil.copytree(reference_dir, url)
        generate_metadata(url)
        md = read_metadata_dict(open_dataset(url))
        assert UNISCHEMA_JSON_KEY in md
        schema = get_schema(open_dataset(url))
        assert schema.fields['matrix'].codec is not None  # codecs preserved

    def test_metadata_util_cli(self, synthetic_dataset, capsys):
        from petastorm_tpu.etl.metadata_util import main
        assert main([synthetic_dataset.url]) == 0
        out = capsys.readouterr().out
        assert 'TestSchema' in out and 'rowgroups' in out


class TestThroughput:
    def test_reader_throughput(self, synthetic_dataset):
        from petastorm_tpu.benchmark.throughput import reader_throughput
        result = reader_throughput(synthetic_dataset.url, field_regex=['id'],
                                   warmup_cycles_count=10, measure_cycles_count=30,
                                   loaders_count=1, spawn_new_process=False)
        assert result.samples_per_second > 0
        assert result.memory_info.rss > 0

    def test_profile_threads(self, synthetic_dataset, caplog):
        import logging
        from petastorm_tpu.benchmark.throughput import reader_throughput
        with caplog.at_level(logging.INFO, logger='petastorm_tpu.workers.thread_pool'):
            result = reader_throughput(synthetic_dataset.url, field_regex=['id'],
                                       warmup_cycles_count=5, measure_cycles_count=10,
                                       loaders_count=2, profile_threads=True,
                                       spawn_new_process=False)
        assert result.samples_per_second > 0
        profile_logs = [r for r in caplog.records if 'profile' in r.message.lower()]
        assert profile_logs, 'aggregated worker profile must be logged on join'
        assert 'cumulative' in profile_logs[0].getMessage()

    def test_profile_threads_requires_thread_pool(self, synthetic_dataset):
        from petastorm_tpu.benchmark.throughput import reader_throughput
        with pytest.raises(ValueError, match='thread pool'):
            reader_throughput(synthetic_dataset.url, pool_type='dummy',
                              profile_threads=True)

    def test_ngram_windows_throughput(self, synthetic_dataset):
        """NGram benchmarking mode: cycle = one window over every field (VERDICT round 1
        item 8 — benchmarks the columnar gather hot path)."""
        from petastorm_tpu.benchmark.throughput import reader_throughput
        result = reader_throughput(synthetic_dataset.url, field_regex=['id', 'id2'],
                                   warmup_cycles_count=5, measure_cycles_count=20,
                                   loaders_count=1, ngram_length=3, ngram_ts_field='id',
                                   spawn_new_process=False)
        assert result.samples_per_second > 0

    def test_ngram_throughput_requires_ts_field(self, synthetic_dataset):
        from petastorm_tpu.benchmark.throughput import reader_throughput
        with pytest.raises(ValueError, match='ngram_ts_field'):
            reader_throughput(synthetic_dataset.url, ngram_length=3)

    def test_packing_throughput(self, tmp_path):
        """Packed-bin formation mode: cycle = one worker batch of packed bins over a
        native list column; rate is bins/sec."""
        import pyarrow as pa
        import pyarrow.parquet as pq
        from petastorm_tpu.benchmark.throughput import reader_throughput

        rng = np.random.RandomState(0)
        root = tmp_path / 'ragged'
        root.mkdir()
        docs = [rng.randint(0, 99, size=rng.randint(4, 13)).astype(np.int32)
                for _ in range(200)]
        table = pa.table({'doc_id': np.arange(200, dtype=np.int64),
                          'tokens': pa.array([d.tolist() for d in docs],
                                             type=pa.list_(pa.int32()))})
        pq.write_table(table, str(root / 'part_0.parquet'), row_group_size=50)

        result = reader_throughput('file://' + str(root), warmup_cycles_count=2,
                                   measure_cycles_count=10, loaders_count=1,
                                   pack_field='tokens', pack_seq_len=32,
                                   spawn_new_process=False)
        assert result.samples_per_second > 0

    def test_packing_throughput_guards(self, synthetic_dataset):
        from petastorm_tpu.benchmark.throughput import reader_throughput
        with pytest.raises(ValueError, match='together'):
            reader_throughput(synthetic_dataset.url, pack_field='tokens')
        with pytest.raises(ValueError, match='mutually exclusive'):
            reader_throughput(synthetic_dataset.url, pack_field='tokens',
                              pack_seq_len=8, ngram_length=3, ngram_ts_field='id')

    def test_spawn_new_process_isolated_rss(self, synthetic_dataset):
        """Default path (reference parity, throughput.py:144-149): the measurement
        respawns in a fresh interpreter so RSS excludes the caller's footprint."""
        from petastorm_tpu.benchmark.throughput import reader_throughput
        result = reader_throughput(synthetic_dataset.url, field_regex=['id'],
                                   warmup_cycles_count=2, measure_cycles_count=10,
                                   loaders_count=1)  # spawn_new_process defaults True
        assert result.samples_per_second > 0
        assert result.memory_info.rss > 0

    def test_jax_read_method(self, synthetic_dataset):
        from petastorm_tpu.benchmark.throughput import READ_JAX, reader_throughput
        result = reader_throughput(synthetic_dataset.url, field_regex=['id', 'matrix'],
                                   warmup_cycles_count=2, measure_cycles_count=5,
                                   loaders_count=1, read_method=READ_JAX,
                                   jax_batch_size=8, spawn_new_process=False)
        assert result.samples_per_second > 0
        assert 0 <= result.input_stall_fraction <= 1

    def test_cli(self, synthetic_dataset, capsys):
        from petastorm_tpu.benchmark.cli import main
        assert main([synthetic_dataset.url, '-f', 'id', '-m', '5', '-n', '20',
                     '-w', '1', '--in-process']) == 0
        assert 'Throughput' in capsys.readouterr().out


class TestGeneratorAndMock:
    def test_generate_random_datapoint(self):
        from test_common import TestSchema
        from petastorm_tpu.generator import generate_random_datapoint
        row = generate_random_datapoint(TestSchema, np.random.RandomState(0))
        assert set(row) == set(TestSchema.fields)
        assert row['matrix'].shape == (4, 3)
        assert row['matrix_var'].shape[1] == 2

    def test_reader_mock_feeds_adapters(self):
        from test_common import TestSchema
        from petastorm_tpu.test_util.reader_mock import ReaderMock
        view = TestSchema.create_schema_view(['id', 'matrix'])
        mock = ReaderMock(view, num_rows=20)
        from petastorm_tpu.pytorch import DataLoader
        batches = list(DataLoader(mock, batch_size=5))
        assert len(batches) == 4
        assert batches[0]['matrix'].shape == (5, 4, 3)


class TestBatchingTableQueue:
    def test_rechunk(self):
        import pyarrow as pa
        from petastorm_tpu.arrow_helpers import BatchingTableQueue
        queue = BatchingTableQueue(7)
        queue.put(pa.table({'a': list(range(10))}))
        assert not queue.empty()
        first = queue.get()
        assert first.num_rows == 7
        assert queue.empty()
        queue.put(pa.table({'a': list(range(10, 20))}))
        second = queue.get()
        assert second.num_rows == 7
        assert second.column('a').to_pylist() == [7, 8, 9, 10, 11, 12, 13]


class TestHdfsResolver:
    CONFIG = {
        'fs.defaultFS': 'hdfs://nameservice1',
        'dfs.nameservices': 'nameservice1',
        'dfs.ha.namenodes.nameservice1': 'nn1,nn2',
        'dfs.namenode.rpc-address.nameservice1.nn1': 'host1:8020',
        'dfs.namenode.rpc-address.nameservice1.nn2': 'host2:8020',
    }

    def test_resolve_ha_nameservice(self):
        from petastorm_tpu.hdfs.namenode import HdfsNamenodeResolver
        resolver = HdfsNamenodeResolver(self.CONFIG)
        service, namenodes = resolver.resolve_default_hdfs_service()
        assert service == 'nameservice1'
        assert namenodes == ['host1:8020', 'host2:8020']

    def test_direct_host_passthrough(self):
        from petastorm_tpu.hdfs.namenode import HdfsNamenodeResolver
        resolver = HdfsNamenodeResolver(self.CONFIG)
        assert resolver.resolve_hdfs_name_service('other:9000') == ['other:9000']

    def test_missing_rpc_address_raises(self):
        from petastorm_tpu.hdfs.namenode import HdfsConfigError, HdfsNamenodeResolver
        config = dict(self.CONFIG)
        del config['dfs.namenode.rpc-address.nameservice1.nn2']
        with pytest.raises(HdfsConfigError):
            HdfsNamenodeResolver(config).resolve_hdfs_name_service('nameservice1')

    def test_failover_connects_second_namenode(self):
        from petastorm_tpu.hdfs.namenode import HdfsConnector

        class MockConnector(HdfsConnector):
            attempts = []

            @classmethod
            def hdfs_connect_namenode(cls, address, user=None):
                cls.attempts.append(address)
                if address.startswith('host1'):
                    raise IOError('nn1 down')
                return 'fs-{}'.format(address)

        fs = MockConnector.connect_to_either_namenode(['host1:8020', 'host2:8020'])
        assert fs == 'fs-host2:8020'
        assert MockConnector.attempts.count('host1:8020') == 2  # retried then failed over

    def test_all_down_raises(self):
        from petastorm_tpu.hdfs.namenode import HdfsConnectError, HdfsConnector

        class DeadConnector(HdfsConnector):
            @classmethod
            def hdfs_connect_namenode(cls, address, user=None):
                raise IOError('down')

        with pytest.raises(HdfsConnectError):
            DeadConnector.connect_to_either_namenode(['host1:8020', 'host2:8020'])


def test_run_in_subprocess():
    from petastorm_tpu.utils import run_in_subprocess
    assert run_in_subprocess(sum, [1, 2, 3]) == 6


def test_spark_session_cli_arguments_parse():
    import argparse
    from petastorm_tpu.tools import spark_session_cli

    parser = argparse.ArgumentParser()
    spark_session_cli.add_configure_spark_arguments(parser)
    args = parser.parse_args(['--master', 'local[2]',
                              '--spark-session-config', 'a.b=1', 'c.d=x'])
    assert args.master == 'local[2]'
    assert spark_session_cli._parse_config_pairs(args.spark_session_config) == \
        {'a.b': '1', 'c.d': 'x'}


def test_spark_session_cli_bad_pair_rejected():
    import argparse
    import pytest
    from petastorm_tpu.tools import spark_session_cli

    with pytest.raises(argparse.ArgumentTypeError):
        spark_session_cli._parse_config_pairs(['no_equals_sign'])


class TestBenchOneProcess:
    """bench.py runs in one process on the chip it measures: no probe child, no
    CPU fallback. With no TPU it exits non-zero unless ``JAX_PLATFORMS=cpu``
    asks for a CPU run, and a failed section still prints the cumulative line
    but fails the process."""

    BENCH = os.path.join(os.path.dirname(__file__), '..', 'bench.py')

    def _load_bench(self):
        import importlib.util
        spec = importlib.util.spec_from_file_location('bench_one_process', self.BENCH)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def _run(self, tmp_path, env_extra):
        import subprocess
        import sys
        env = dict(os.environ, TMPDIR=str(tmp_path), BENCH_SECTIONS='bare_reader',
                   BENCH_ROWS='64')
        env.update(env_extra)
        return subprocess.run([sys.executable, self.BENCH], capture_output=True,
                              text=True, env=env, timeout=240)

    @staticmethod
    def _last_json(stdout):
        import json
        lines = [ln for ln in stdout.strip().splitlines() if ln.startswith('{')]
        assert lines, stdout
        rec = json.loads(lines[-1])
        for key in ('metric', 'value', 'unit', 'vs_baseline', 'platform'):
            assert key in rec, (key, rec)
        return rec

    @pytest.mark.parametrize('jax_platforms', [None, '', 'tpu,cpu'])
    def test_no_tpu_without_explicit_cpu_request_exits_nonzero(self, monkeypatch,
                                                               jax_platforms):
        # the suite's backend is the CPU: without JAX_PLATFORMS=cpu asking for
        # it, that is "no TPU", and bench.py must refuse rather than measure
        bench = self._load_bench()
        env = {} if jax_platforms is None else {'JAX_PLATFORMS': jax_platforms}
        with pytest.raises(SystemExit) as exc:
            bench.require_platform(env)
        assert 'no TPU' in str(exc.value)
        assert bench.require_platform({'JAX_PLATFORMS': 'cpu'}) == 'cpu'

    def test_explicit_cpu_run_exits_zero_with_line(self, tmp_path):
        out = self._run(tmp_path, {'JAX_PLATFORMS': 'cpu', 'BENCH_WORKERS': '1'})
        assert out.returncode == 0, out.stderr[-2000:]
        rec = self._last_json(out.stdout)
        assert rec['platform'] == 'cpu'
        assert rec['bare_reader_rows_per_sec'] > 0

    def test_failed_section_prints_line_and_exits_nonzero(self, tmp_path):
        # fewer rows than one batch: InMemJaxLoader raises inside the section
        out = self._run(tmp_path, {'JAX_PLATFORMS': 'cpu', 'BENCH_WORKERS': '1',
                                   'BENCH_SECTIONS': 'mnist_inmem',
                                   'BENCH_BATCH': '128'})
        assert out.returncode != 0
        rec = self._last_json(out.stdout)
        assert 'mnist_inmem_error' in rec
        assert 'sections failed: mnist_inmem' in out.stderr


class TestBenchHelpers:
    """bench.py robustness pieces (VERDICT r2 item 1): the DCT-compressible
    synthetic images."""

    def test_synthetic_photo_compresses_in_dct_domain(self):
        """The imagenet stream story depends on it: quantized DCT coefficients of the
        synthetic photos must be mostly zero (parquet compression does the shipping),
        unlike uniform noise."""
        import bench
        from petastorm_tpu.codecs import DctImageCodec
        from petastorm_tpu.unischema import UnischemaField
        rng = np.random.RandomState(0)
        field = UnischemaField('image', np.uint8, (64, 64, 3), DctImageCodec(90), False)
        photo = bench._synthetic_photo(rng, 64)
        noise = rng.randint(0, 255, (64, 64, 3), dtype=np.uint8)
        codec = DctImageCodec(quality=90)
        import zlib
        photo_bytes = codec.encode(field, photo)
        noise_bytes = codec.encode(field, noise)
        photo_ratio = len(zlib.compress(photo_bytes)) / len(photo_bytes)
        noise_ratio = len(zlib.compress(noise_bytes)) / len(noise_bytes)
        assert photo_ratio < 0.5 * noise_ratio, (photo_ratio, noise_ratio)


class TestCopyDatasetOverwrite:
    def test_nonempty_target_refused_without_overwrite(self, synthetic_dataset,
                                                       tmp_path):
        from petastorm_tpu.tools.copy_dataset import copy_dataset
        target = 'file://' + str(tmp_path / 'copy')
        copy_dataset(synthetic_dataset.url, target, field_regex=['id'])
        with pytest.raises(ValueError, match='overwrite'):
            copy_dataset(synthetic_dataset.url, target, field_regex=['id'])

    def test_overwrite_replaces_stale_files(self, synthetic_dataset, tmp_path):
        # The second copy selects FEWER rows; without the delete, part files of
        # the first copy would survive and double-serve.
        from petastorm_tpu import make_reader
        from petastorm_tpu.tools.copy_dataset import copy_dataset
        target = 'file://' + str(tmp_path / 'copy2')
        copy_dataset(synthetic_dataset.url, target, rows_per_file=10)
        copy_dataset(synthetic_dataset.url, target, rows_per_file=100,
                     overwrite=True)
        with make_reader(target, workers_count=1, num_epochs=1) as reader:
            n = sum(1 for _ in reader)
        assert n == len(synthetic_dataset.rows)

    def test_bad_regex_raises(self, synthetic_dataset, tmp_path):
        from petastorm_tpu.tools.copy_dataset import copy_dataset
        with pytest.raises(ValueError, match='matched no fields'):
            copy_dataset(synthetic_dataset.url,
                         'file://' + str(tmp_path / 'never'),
                         field_regex=['bogus_name_xyz'])


class TestBenchHarness:
    """Contracts on the repo-root bench.py the driver runs on hardware."""

    def _load_bench(self):
        import importlib.util
        spec = importlib.util.spec_from_file_location(
            'bench_module', os.path.join(os.path.dirname(__file__), '..', 'bench.py'))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def test_headline_section_runs_first(self):
        # A run cut short keeps its completed prefix, so the
        # headline-carrying section must lead the run order.
        bench = self._load_bench()
        assert bench.SECTION_RUN_ORDER[0] == 'mnist_inmem'
        assert sorted(bench.SECTION_RUN_ORDER) == sorted(bench.SECTION_NAMES)

    def test_headline_fallback_prefers_any_measured_rate(self):
        bench = self._load_bench()
        rec = bench.normalize_headline(
            {'streaming_rows_per_sec': 123.0, 'streaming_vs_baseline': 0.17})
        assert rec['value'] == 123.0
        assert rec['metric'] == 'mnist_train_rows_per_sec_per_chip'
        assert rec['config'] == 'streaming_fallback_headline'
        empty = bench.normalize_headline({})
        assert empty['value'] == 0.0
        assert empty['config'] == 'no_sections_completed'

    def test_headline_fallback_scan_stream_outranks_per_batch_streaming(self):
        # r5: the compiled-chunk path is the streaming headline
        bench = self._load_bench()
        rec = bench.normalize_headline(
            {'streaming_rows_per_sec': 10.0, 'streaming_vs_baseline': 0.01,
             'streaming_scan_rows_per_sec': 50.0,
             'streaming_scan_vs_baseline': 0.07})
        assert rec['value'] == 50.0
        assert rec['config'] == 'scan_stream_fallback_headline'

    def test_headline_fallback_covers_decode_delta(self):
        # r5 code-review catch: a decode-only partial must not normalize to a
        # value=0.0 'no_sections_completed' placeholder
        bench = self._load_bench()
        rec = bench.normalize_headline(
            {'imagenet_onchip_decode_rows_per_sec': 321.0})
        assert rec['value'] == 321.0
        assert rec['config'] == 'decode_delta_fallback_headline'
        assert rec['unit'] == 'rows/s'
