"""Reduce a profiler trace (``.xplane.pb``) to what the per-layer metrics read.

Device planes are ``/device:TPU:<n>``; their ``XLA Ops`` line holds one event
per operation run. Host spans are the events of the host planes' threads,
``TraceAnnotation`` scopes among them. The window is the host span named
``bench.window``, which the harness opens around the traced part of the run;
device time outside it is clipped away.
"""
import re

DEVICE_PLANE = re.compile(r'^/device:TPU:(\d+)$')
OPS_LINE = 'XLA Ops'
WINDOW_SPAN = 'bench.window'
#: host spans that can name an idle gap, most specific first
GAP_SPANS = ('petastorm_tpu.loader.device_decode', 'petastorm_tpu.loader.h2d',
             'petastorm_tpu.loader.wait_input', 'bench.next_batch', 'bench.dispatch',
             'bench.wait_step')


def _merge(intervals):
    out = []
    for start, end in sorted(intervals):
        if out and start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], end)
        else:
            out.append([start, end])
    return out


def read_events(path):
    """``(device_ops, host_spans)``: ``{device: [(name, start_ns, end_ns)]}`` and
    ``[(name, start_ns, end_ns)]``."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    device_ops, host = {}, []
    for plane in data.planes:
        match = DEVICE_PLANE.match(plane.name)
        if match:
            ops = device_ops.setdefault(int(match.group(1)), [])
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                            for e in line.events]
        elif plane.name.startswith('/host:'):
            for line in plane.lines:
                host += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                         for e in line.events if e.name.startswith(('bench.', 'petastorm_tpu.'))]
    return device_ops, host


def reduce_events(device_ops, host, top=10):
    """The numbers the metrics read, from :func:`read_events`' output."""
    windows = [(s, e) for name, s, e in host if name == WINDOW_SPAN]
    if not windows or not device_ops:
        return None
    w0, w1 = windows[0]
    window_ns = w1 - w0
    busy, op_time, clipped = {}, {}, {}
    for dev, ops in device_ops.items():
        inside = [(n, max(s, w0), min(e, w1)) for n, s, e in ops if e > w0 and s < w1]
        clipped[dev] = inside
        merged = _merge([[s, e] for _, s, e in inside])
        busy[dev] = sum(e - s for s, e in merged)
        for n, s, e in inside:
            op_time[n] = op_time.get(n, 0) + (e - s)
    if not any(busy.values()):
        return None
    busiest = max(busy, key=busy.get)
    merged = _merge([[s, e] for _, s, e in clipped[busiest]])
    edges = [w0] + [x for s, e in merged for x in (s, e)] + [w1]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    named = []
    for g0, g1 in sorted(gaps, key=lambda g: g[0] - g[1])[:top]:
        named.append([_gap_name(host, g0, g1), (g1 - g0) / 1e9])
    ops_sorted = sorted(op_time.items(), key=lambda kv: -kv[1])
    return {
        'window_s': window_ns / 1e9,
        'busy_s': sum(busy.values()) / len(busy) / 1e9,
        'busiest_busy_s': busy[busiest] / 1e9,
        'devices': len(busy),
        'op_seconds': {n: t / 1e9 for n, t in op_time.items()},
        'op_counts': _counts(clipped),
        'device_ops': [[n, t / 1e9] for n, t in ops_sorted[:top]],
        'idle_gaps': named,
    }


def _counts(clipped):
    counts = {}
    for ops in clipped.values():
        for n, _, _ in ops:
            counts[n] = counts.get(n, 0) + 1
    return counts


def _gap_name(host, g0, g1):
    """The most specific host span that covers over half of the gap, else the one
    that covers most of it, else 'host other'."""
    cover = {}
    for name, s, e in host:
        if name in GAP_SPANS:
            cover[name] = max(cover.get(name, 0), min(e, g1) - max(s, g0))
    for name in GAP_SPANS:
        if cover.get(name, 0) > 0.5 * (g1 - g0):
            return name.rsplit('.', 1)[-1]
    best = max(cover, key=cover.get, default=None)
    return best.rsplit('.', 1)[-1] if best and cover[best] > 0 else 'host other'


def reduce(path, top=10):
    return reduce_events(*read_events(path), top=top)
