"""Host<->device link characterization for streaming-floor analysis.

Every streaming ``__iter__`` batch pays one host->device transfer plus one
dispatch round trip (``parallel/loader.py``), so the loader's serial ceiling is

    streaming_ceiling_rows_per_sec ~= 1 / (rtt_s + row_bytes / h2d_bytes_per_sec)
                                      (per batch, divided by batch size)

This module measures the three link primitives directly — dispatch round-trip
time, host->device bandwidth, device->host bandwidth — so a measured streaming
rate can be read against the link ceiling of the machine it ran on.

Bandwidth estimation uses a least-squares line over several transfer sizes:
``t(bytes) = t0 + bytes / bandwidth`` — the slope isolates bandwidth from the
per-op overhead ``t0``, which a single-size measurement would conflate (the
per-op overhead is itself reported as the intercept).
"""
from __future__ import annotations

import json
import time

import numpy as np

__all__ = ['probe_link', 'streaming_ceiling_rows_per_sec']


def _median_time(fn, iters):
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        fn()
        times.append(time.perf_counter() - t0)
    return float(np.median(times))


def _fit_bandwidth(sizes_bytes, times_s):
    """Least-squares ``t = t0 + bytes/bw`` -> (bytes_per_sec, t0_s).

    With only one size, falls back to attributing the whole time to bandwidth
    (overhead indistinguishable; t0 reported as 0).
    """
    if len(sizes_bytes) < 2:
        return sizes_bytes[0] / times_s[0], 0.0
    slope, intercept = np.polyfit(np.asarray(sizes_bytes, dtype=np.float64),
                                  np.asarray(times_s, dtype=np.float64), 1)
    if slope <= 0:  # noise floor: transfers too small to resolve the slope
        return max(sizes_bytes) / min(times_s), 0.0
    return 1.0 / slope, max(float(intercept), 0.0)


def probe_link(sizes_mb=(1, 4, 16), dispatch_iters=30, transfer_iters=5):
    """Measure dispatch RTT and H2D/D2H bandwidth on the default jax device.

    Returns a dict with ``dispatch_rtt_ms``, ``h2d_mbytes_per_sec``,
    ``d2h_mbytes_per_sec``, the per-transfer overheads from the linear fit,
    and the probed ``platform``.
    """
    import jax
    import jax.numpy as jnp

    device = jax.devices()[0]

    @jax.jit
    def bump(x):
        return x + 1

    # warm: compile bump, touch the allocator at every probed size
    seed = jax.device_put(jnp.zeros((8, 128), jnp.float32), device)
    jax.block_until_ready(bump(seed))

    rtt_s = _median_time(lambda: jax.block_until_ready(bump(seed)), dispatch_iters)

    h2d_sizes, h2d_times = [], []
    d2h_sizes, d2h_times = [], []
    for size_mb in sizes_mb:
        n_bytes = int(size_mb * (1 << 20))
        host = np.random.RandomState(7).randint(
            0, 255, size=(n_bytes,), dtype=np.uint8)

        def h2d():
            jax.block_until_ready(jax.device_put(host, device))

        h2d_sizes.append(n_bytes)
        h2d_times.append(_median_time(h2d, transfer_iters))

        # jax.Array caches its host copy after the first conversion, so each
        # timed conversion needs its own resident array or iterations 2..N
        # measure a cache hit instead of a transfer. `bump` makes each array a
        # distinct device buffer even if device_put dedupes the host source.
        residents = []
        for _ in range(transfer_iters):
            r = bump(jax.device_put(host, device))
            jax.block_until_ready(r)
            residents.append(r)
        d2h_times_i = []
        for r in residents:
            t0 = time.perf_counter()
            np.asarray(r)
            d2h_times_i.append(time.perf_counter() - t0)
        del residents
        d2h_sizes.append(n_bytes)
        d2h_times.append(float(np.median(d2h_times_i)))

    h2d_bw, h2d_t0 = _fit_bandwidth(h2d_sizes, h2d_times)
    d2h_bw, d2h_t0 = _fit_bandwidth(d2h_sizes, d2h_times)
    return {
        'platform': device.platform,
        'dispatch_rtt_ms': round(rtt_s * 1e3, 3),
        'h2d_mbytes_per_sec': round(h2d_bw / (1 << 20), 2),
        'h2d_per_transfer_overhead_ms': round(h2d_t0 * 1e3, 3),
        'd2h_mbytes_per_sec': round(d2h_bw / (1 << 20), 2),
        'd2h_per_transfer_overhead_ms': round(d2h_t0 * 1e3, 3),
        'probe_sizes_mb': list(sizes_mb),
    }


def streaming_ceiling_rows_per_sec(link, row_bytes, batch_size):
    """Upper bound for a per-batch streaming loader on the measured link.

    Each batch pays one H2D transfer of ``batch_size * row_bytes`` (plus the
    fitted per-transfer overhead) and one dispatch round trip; compute overlap
    can hide compute but not the serial transfer+dispatch path this bounds.
    """
    batch_bytes = row_bytes * batch_size
    per_batch_s = (link['dispatch_rtt_ms'] / 1e3
                   + link['h2d_per_transfer_overhead_ms'] / 1e3
                   + batch_bytes / (link['h2d_mbytes_per_sec'] * (1 << 20)))
    return batch_size / per_batch_s


def main():
    """CLI: print one JSON line of link measurements on the default device."""
    print(json.dumps(dict(probe_link(), metric='link_probe', value=0.0,
                          unit='link', vs_baseline=0.0)))


if __name__ == '__main__':
    main()
