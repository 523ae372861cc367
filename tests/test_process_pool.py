"""Process-pool e2e (kept to a few tests: spawned-interpreter startup is slow on this
1-core box; model: the reference's pytest-forked process-pool pass, unittest.yml:104-108)."""

import numpy as np
import pytest

from petastorm_tpu import make_reader
from petastorm_tpu.transform import TransformSpec


@pytest.mark.slow
def test_process_pool_reads_and_decodes(synthetic_dataset):
    with make_reader(synthetic_dataset.url, reader_pool_type='process',
                     workers_count=2) as reader:
        rows = {row.id: row for row in reader}
    assert len(rows) == len(synthetic_dataset.rows)
    source = synthetic_dataset.rows_by_id[0]
    np.testing.assert_array_equal(rows[0].matrix, source['matrix'])
    np.testing.assert_array_equal(rows[0].image_png, source['image_png'])


@pytest.mark.slow
def test_process_pool_worker_exception_propagates(synthetic_dataset):
    def bad(row):
        raise RuntimeError('cross-process boom')

    with pytest.raises(RuntimeError, match='cross-process boom'):
        with make_reader(synthetic_dataset.url, reader_pool_type='process',
                         workers_count=2, transform_spec=TransformSpec(bad)) as reader:
            list(reader)


@pytest.mark.slow
def test_worker_hard_kill_raises_when_respawn_disabled(synthetic_dataset):
    """With ``max_worker_respawns=0`` a SIGKILL-ed worker mid-read must surface
    WorkerTerminationError promptly (reference failure-detection contract,
    SURVEY.md §5.3) — never hang the consumer, never keep silently serving from the
    survivors. (The default pool instead respawns: see the respawn tests here and in
    test_resilience.py.)"""
    import os
    import signal
    import time

    from petastorm_tpu.workers.process_pool import (ProcessPool,
                                                    WorkerTerminationError)

    pool = ProcessPool(2, max_worker_respawns=0)
    with pytest.raises(WorkerTerminationError):
        with make_reader(synthetic_dataset.url, reader_pool=pool,
                         schema_fields=['id'], num_epochs=None,
                         shuffle_row_groups=False) as reader:
            next(reader)  # pool is up and serving
            for process in pool._processes[:1]:
                os.kill(process.pid, signal.SIGKILL)
            deadline = time.time() + 30
            while time.time() < deadline:
                next(reader)
            pytest.fail('reader kept serving for 30s with a killed worker')


def test_get_results_returns_when_stopped_from_another_thread(synthetic_dataset):
    """A consumer thread blocked in get_results() leaves it once stop() is called
    elsewhere, so that thread can be joined before the pool's join() polls and
    closes the same (not thread-safe) zmq sockets."""
    import threading

    from petastorm_tpu.workers.process_pool import ProcessPool

    pool = ProcessPool(2)
    reader = make_reader(synthetic_dataset.url, reader_pool=pool, schema_fields=['id'],
                         num_epochs=None)
    next(reader)
    errors = []

    def consume():
        try:
            while True:
                pool.get_results()
        except RuntimeError as exc:
            errors.append(exc)

    consumer = threading.Thread(target=consume, daemon=True)
    consumer.start()
    reader.stop()
    consumer.join(timeout=15)
    alive = consumer.is_alive()
    if not alive:
        reader.join()
    assert not alive, 'get_results kept polling a stopped pool'
    assert 'stopped' in str(errors[0])


@pytest.mark.slow
def test_worker_hard_kill_respawns_and_completes(synthetic_dataset):
    """Default pool: a killed worker is respawned within the budget, its in-flight
    items are re-ventilated, and the epoch completes with every row served exactly
    once (docs/robustness.md)."""
    import os
    import signal

    from petastorm_tpu.workers.process_pool import ProcessPool

    pool = ProcessPool(2)
    with make_reader(synthetic_dataset.url, reader_pool=pool,
                     schema_fields=['id'], num_epochs=1,
                     shuffle_row_groups=False) as reader:
        ids = [next(reader).id]  # pool is up and serving
        os.kill(pool._processes[0].pid, signal.SIGKILL)
        ids.extend(row.id for row in reader)
        diag = pool.diagnostics
    assert sorted(ids) == sorted(r['id'] for r in synthetic_dataset.rows)
    assert diag['workers_respawned'] == 1
    assert diag['workers_alive'] == 2


@pytest.mark.slow
def test_respawn_budget_exhaustion_raises(synthetic_dataset):
    """Repeated deaths beyond the budget must fail loudly, not respawn forever."""
    import os
    import signal
    import time

    from petastorm_tpu.workers.process_pool import (ProcessPool,
                                                    WorkerTerminationError)

    pool = ProcessPool(2, max_worker_respawns=1)
    with pytest.raises(WorkerTerminationError, match='respawn budget'):
        with make_reader(synthetic_dataset.url, reader_pool=pool,
                         schema_fields=['id'], num_epochs=None,
                         shuffle_row_groups=False) as reader:
            next(reader)
            deadline = time.time() + 60
            while time.time() < deadline:
                for process in pool._processes:
                    if process.poll() is None:
                        os.kill(process.pid, signal.SIGKILL)
                        break
                next(reader)
            pytest.fail('reader kept serving past the respawn budget')
