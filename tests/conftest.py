"""Test harness configuration.

Tests run on the CPU backend (``JAX_PLATFORMS=cpu``) with 8 virtual host devices, set
BEFORE jax initializes, so multi-chip sharding logic (mesh construction,
make_array_from_process_local_data, collectives) is exercised without TPU hardware —
the strategy SURVEY.md §4 prescribes. The chip path is covered by ``chip_smoke.py``,
run on a TPU through the chip tool.
"""

import os

os.environ['JAX_PLATFORMS'] = 'cpu'
existing = os.environ.get('XLA_FLAGS', '')
if '--xla_force_host_platform_device_count' not in existing:
    os.environ['XLA_FLAGS'] = (existing + ' --xla_force_host_platform_device_count=8').strip()

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(scope='session')
def rng():
    return np.random.RandomState(42)


@pytest.fixture(autouse=True)
def _reset_breaker_board():
    """Circuit-breaker isolation: the default BreakerBoard is process-global
    (docs/robustness.md), so one test's tripped fs/cache breaker must not leak
    failure streaks into the next test's reads."""
    yield
    from petastorm_tpu.resilience import default_board
    default_board().reset()


class SyntheticDataset(object):
    def __init__(self, url, rows):
        self.url = url
        self.rows = rows
        self.rows_by_id = {row['id']: row for row in rows if 'id' in row}


@pytest.fixture(scope='session')
def synthetic_dataset(tmp_path_factory):
    """Session-scoped synthetic petastorm_tpu dataset (model:
    petastorm/tests/conftest.py:90-125)."""
    from test_common import create_test_dataset
    url = str(tmp_path_factory.mktemp('synthetic') / 'dataset')
    rows = create_test_dataset(url, num_rows=100)
    return SyntheticDataset(url, rows)


@pytest.fixture(scope='session')
def scalar_dataset(tmp_path_factory):
    """Plain (non-unischema) Parquet store for make_batch_reader tests (model:
    petastorm/tests/conftest.py scalar_dataset)."""
    import pyarrow as pa
    import pyarrow.parquet as pq
    url = str(tmp_path_factory.mktemp('scalar') / 'dataset')
    os.makedirs(url)
    data = {
        'id': list(range(50)),
        'float64': [i / 2.0 for i in range(50)],
        'string': ['value_{}'.format(i) for i in range(50)],
        'int_list': [[i, i + 1, i + 2] for i in range(50)],
    }
    table = pa.table(data)
    pq.write_table(table.slice(0, 30), os.path.join(url, 'part_0.parquet'), row_group_size=10)
    pq.write_table(table.slice(30), os.path.join(url, 'part_1.parquet'), row_group_size=10)
    return SyntheticDataset(url, [dict(zip(data, vals)) for vals in zip(*data.values())])


@pytest.fixture(scope='session')
def many_columns_dataset(tmp_path_factory):
    """1000-column plain Parquet store (model: petastorm/tests/conftest.py
    many_columns_non_petastorm_dataset, :248-294) — exercises wide-schema namedtuple
    rendering and columnar reads."""
    import pyarrow as pa
    import pyarrow.parquet as pq
    url = str(tmp_path_factory.mktemp('wide') / 'dataset')
    os.makedirs(url)
    # column-distinct values so column-mixup/reorder bugs are caught
    data = {'col_{}'.format(i): [r + i * 10 for r in range(10)] for i in range(1000)}
    pq.write_table(pa.table(data), os.path.join(url, 'part_0.parquet'), row_group_size=5)
    return SyntheticDataset(url, [dict(zip(data, vals)) for vals in zip(*data.values())])
