"""The program's stage spans, as the per-layer readers and a trace's idle gaps
use them.

The program times each pipeline stage with one span
(``petastorm_tpu.telemetry.spans``): a histogram per stage in the loader's
``telemetry_snapshot()``, and, while a profile is taken, one event per span on
its thread's line of the profiler's host plane, named
``petastorm_tpu.loader.<stage>`` for the loader's stages and
``petastorm_tpu.<stage>`` for the others. A program that lacks a stage simply
has no histogram or event for it: what reads it here finds nothing and returns
nothing.

- :func:`stage_window` turns two snapshots, taken at the window's two ends,
  into ``{stage: {'s': seconds, 'count': spans}}``, the run record's
  ``stage_s``;
- :func:`window_share` is the arithmetic of the ``*_share`` readers over it;
- :func:`read_host_lines` and :func:`idle_gap_stages` name, for each of a
  trace's longest idle device gaps, the producer thread's stages over it.
"""
from benchmarks import trace as trace_mod

#: stages of the loader's producer thread, which does all of the main
#: process's input work; they never overlap, so their seconds add up to the
#: thread's time
PRODUCER_STAGES = ('pool_wait', 'shm_map', 'wire_map', 'shm_release', 'consume',
                   'rebatch', 'collate', 'device_decode', 'h2d', 'd2d_wait', 'put_wait')
#: the producer's own work: its stages less the three waits
PRODUCER_BUSY_STAGES = ('shm_map', 'wire_map', 'shm_release', 'consume', 'rebatch',
                        'collate', 'device_decode', 'h2d')
#: work done in the pool's workers (``cache_miss`` envelopes ``rowgroup_read``
#: and ``decode``, and ``shm_slot_wait`` is a wait)
WORKER_STAGES = ('fs_open', 'rowgroup_read', 'decode', 'shuffle', 'transform',
                 'cache_hit', 'cache_store', 'cache_corrupt', 'serialize')
#: the loader's stages, whose events carry the ``petastorm_tpu.loader.`` prefix
_LOADER_STAGES = ('rebatch', 'collate', 'device_decode', 'h2d', 'd2d_wait', 'put_wait')
#: one stage that only the producer thread runs, which finds its line
_PRODUCER_MARK = 'petastorm_tpu.loader.rebatch'


def _event_name(stage):
    return ('petastorm_tpu.loader.' if stage in _LOADER_STAGES else 'petastorm_tpu.') + stage


_PRODUCER_EVENTS = {_event_name(stage): stage for stage in PRODUCER_STAGES}


def stage_window(before, after):
    """``{stage: {'s': seconds, 'count': spans}}`` between two telemetry
    snapshots, for every histogram of the second."""
    out = {}
    old = (before or {}).get('histograms', {})
    for stage, hist in (after or {}).get('histograms', {}).items():
        prev = old.get(stage, {})
        out[stage] = {'s': hist.get('sum', 0.0) - prev.get('sum', 0.0),
                      'count': hist.get('count', 0) - prev.get('count', 0)}
    return out


def window_share(run, stages, unit, per_worker=False):
    """Percent of the window (of each worker's window, with ``per_worker``)
    that ``stages`` took, or None where the run has no stage seconds."""
    stage_s = run.get('stage_s')
    if run['work_unit'] != unit or not stage_s or not run['window_s']:
        return None
    span = run['window_s'] * ((run.get('workers') or 0) if per_worker else 1)
    if not span:
        return None
    return 100.0 * sum(stage_s[s]['s'] for s in stages if s in stage_s) / span


def read_host_lines(path):
    """``(device_ops, host, lines)`` of a ``.xplane.pb``: the first two as
    :func:`benchmarks.trace.read_events` gives them, and ``lines``, the
    program's events of each host thread, ``[[(name, start_ns, end_ns)]]``."""
    from jax.profiler import ProfileData
    device_ops, host = trace_mod.read_events(path)
    lines = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith('/host:'):
            for line in plane.lines:
                events = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                          for e in line.events if e.name.startswith('petastorm_tpu.')]
                if events:
                    lines.append(events)
    return device_ops, host, lines


def _idle_gaps(device_ops, host, top):
    """The ``top`` longest idle gaps of the busiest device inside the traced
    window, longest first, as :func:`benchmarks.trace.reduce_events` finds
    them."""
    windows = [(s, e) for name, s, e in host if name == trace_mod.WINDOW_SPAN]
    if not windows or not device_ops:
        return []
    w0, w1 = windows[0]
    busy, merged = {}, {}
    for dev, ops in device_ops.items():
        merged[dev] = trace_mod._merge([[max(s, w0), min(e, w1)] for _, s, e in ops
                                        if e > w0 and s < w1])
        busy[dev] = sum(e - s for s, e in merged[dev])
    if not any(busy.values()):
        return []
    busiest = max(busy, key=busy.get)
    edges = [w0] + [x for s, e in merged[busiest] for x in (s, e)] + [w1]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    return sorted(gaps, key=lambda g: g[0] - g[1])[:top]


def idle_gap_stages(device_ops, host, lines, top=10):
    """For each of the ``top`` longest idle gaps: ``[name, seconds, {stage:
    seconds}]``, its name as ``idle_gaps`` gives it and the producer thread's
    stages that overlap it, each with the seconds it covers. Empty without
    a producer line (a program whose loader writes no stage events)."""
    producer = [events for events in lines
                if any(name == _PRODUCER_MARK for name, _, _ in events)]
    if not producer:
        return []
    out = []
    for g0, g1 in _idle_gaps(device_ops, host, top):
        covered = {}
        for name, s, e in producer[0]:
            stage = _PRODUCER_EVENTS.get(name)
            if stage is not None and e > g0 and s < g1:
                covered[stage] = covered.get(stage, 0.0) + (min(e, g1) - max(s, g0)) / 1e9
        out.append([trace_mod._gap_name(host, g0, g1), (g1 - g0) / 1e9, covered])
    return out
