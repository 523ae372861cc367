"""Link-probe (host<->device characterization) tests — CPU backend.

The probe must produce finite, positive link numbers on any backend (on CPU
the "link" is memcpy; the point here is field contract + math — chip numbers
come only from a run on the chip).
"""
import numpy as np

from petastorm_tpu.benchmark.linkprobe import (
    _fit_bandwidth, probe_link, streaming_ceiling_rows_per_sec)


def test_probe_link_fields_and_sanity():
    link = probe_link(sizes_mb=(0.25, 1), dispatch_iters=5, transfer_iters=3)
    for key in ('dispatch_rtt_ms', 'h2d_mbytes_per_sec', 'd2h_mbytes_per_sec',
                'h2d_per_transfer_overhead_ms', 'd2h_per_transfer_overhead_ms'):
        assert key in link, key
        assert np.isfinite(link[key]) and link[key] >= 0, (key, link[key])
    assert link['h2d_mbytes_per_sec'] > 0
    assert link['d2h_mbytes_per_sec'] > 0
    assert link['platform'] == 'cpu'
    assert link['probe_sizes_mb'] == [0.25, 1]


def test_fit_bandwidth_recovers_slope_and_overhead():
    bw = 100e6  # 100 MB/s
    t0 = 0.004
    sizes = [1 << 20, 4 << 20, 16 << 20]
    times = [t0 + s / bw for s in sizes]
    got_bw, got_t0 = _fit_bandwidth(sizes, times)
    assert abs(got_bw - bw) / bw < 1e-6
    assert abs(got_t0 - t0) < 1e-9


def test_fit_bandwidth_single_size_falls_back():
    got_bw, got_t0 = _fit_bandwidth([1 << 20], [0.01])
    assert got_bw == (1 << 20) / 0.01
    assert got_t0 == 0.0


def test_fit_bandwidth_noise_floor_nonnegative():
    # times DECREASING with size (pure noise): slope<=0 must not produce a
    # negative bandwidth, and overhead must clamp at 0
    got_bw, got_t0 = _fit_bandwidth([1 << 20, 2 << 20], [0.01, 0.005])
    assert got_bw > 0
    assert got_t0 == 0.0


def test_streaming_ceiling_math():
    link = {'dispatch_rtt_ms': 10.0, 'h2d_per_transfer_overhead_ms': 5.0,
            'h2d_mbytes_per_sec': 8.0}
    # batch of 2048 rows x 1 KiB = 2 MiB -> transfer 0.25 s + 0.015 s fixed
    rows_per_sec = streaming_ceiling_rows_per_sec(link, row_bytes=1024,
                                                  batch_size=2048)
    expected = 2048 / (0.010 + 0.005 + 2.0 / 8.0)
    assert abs(rows_per_sec - expected) < 1e-6
    # a faster link raises the ceiling
    faster = dict(link, h2d_mbytes_per_sec=80.0)
    assert streaming_ceiling_rows_per_sec(faster, 1024, 2048) > rows_per_sec


def test_upload_log_waits_for_sharded_trees(caplog):
    import logging

    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from petastorm_tpu.parallel.inmem_loader import _put_with_log

    mesh = Mesh(np.asarray(jax.devices()[:4]), ('data',))
    tree = {'a': jnp.ones((3, 2)), 'd': jnp.zeros((0,))}

    def put():
        return dict(tree, b=jax.device_put(jnp.arange(16.0).reshape(4, 4),
                                           NamedSharding(mesh, P('data'))))

    with caplog.at_level(logging.INFO, logger='petastorm_tpu.parallel.inmem_loader'):
        out = _put_with_log(put, 64, '4 rows')
    assert sorted(out) == ['a', 'b', 'd']
    assert any('uploaded 4 rows' in r.getMessage() for r in caplog.records)
