"""One run of one cell: set-up, the measured window, the metrics and the check.

Everything here is general. What belongs to one configuration, one traffic mix
or one metric sits in a file of its own, found by the name ``BENCHMARK.json``
gives it:

- ``configs/<config>.json`` holds the sizes; the ``module`` it names (beside it)
  builds the program's train step (``Program``) and holds the plain reference
  (``reference_run``);
- ``traffic/<mix>.json`` holds the store's sizes and the reader's settings,
  which :mod:`benchmarks.stores` turns into a store; the ``kind`` of store it
  names is a module, ``kinds/<kind>.py``, that writes its rows, reads them back
  plainly for the check and sets the reader's transform;
- ``metrics/<metric>.py`` has ``read(run)``, which returns the metric's number
  from the run's record, or None where there is nothing to read.

The window drives ``make_reader`` -> ``JaxDataLoader`` (over a ``data`` mesh
on several chips) -> the compiled train step: it dispatches step i, then waits
for step i-1's loss and stamps the clock, so one step stays queued.
"""
import contextlib
import gc
import importlib.util
import json
import os
import shutil
import sys
import time

import numpy as np

from benchmarks import stores

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(BENCH_DIR)
CACHE = os.path.join(BENCH_DIR, '.cache')
#: steps that set-up drives through the window's own call and feed, and that the
#: reference follows
CHECKED_STEPS = 3
WARM_STEPS = 2
#: batches of the window whose rows are compared, drawn from the seed among its
#: first SAMPLE_RANGE
SAMPLED_BATCHES = 3
SAMPLE_RANGE = 24
#: a first gradient leaf under this share of the median leaf's is round-off in
#: the reference: its parameter change is left out of change_gap
NOUGHT_GRADIENT = 1e-3
#: seconds of steps traced after the window in a ``--trace 1`` run
TRACE_SECONDS = 2.0
FAULTS = ('unchanged', 'half_batch', 'no_exchange', 'altered')


def log(msg):
    print(msg, file=sys.stderr, flush=True)


def load_module(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_spec(root=REPO):
    with open(os.path.join(root, 'BENCHMARK.json')) as f:
        return json.load(f)


def _by_name(entries, name, what):
    for entry in entries:
        if entry['name'] == name:
            return entry
    raise KeyError('no {} named {!r} in BENCHMARK.json'.format(what, name))


class Cell(object):
    """A workload of ``BENCHMARK.json`` with its configuration, mix and metrics."""

    def __init__(self, spec, name, root=REPO, traffic_dir=os.path.join(BENCH_DIR, 'traffic'),
                 metrics_dir=os.path.join(BENCH_DIR, 'metrics'),
                 kinds_dir=os.path.join(BENCH_DIR, 'kinds')):
        self.name = name
        entry = _by_name(spec['workloads'], name, 'workload')
        self.chips = int(entry['chips'])
        config = _by_name(spec['configs'], entry['config'], 'config')
        config_file = os.path.join(root, config['file'])
        with open(config_file) as f:
            self.cfg = json.load(f)
        self.module = load_module(os.path.join(os.path.dirname(config_file),
                                               self.cfg['module']),
                                  'bench_config_' + config['name'])
        self.mix_name = entry['traffic']
        self.mix = stores.load_mix(traffic_dir, self.mix_name)
        kind = self.mix['store']['kind']
        self.kind = load_module(os.path.join(kinds_dir, kind + '.py'), 'bench_kind_' + kind)
        self.end_to_end = [m for m in spec['end_to_end']
                           if name in m.get('workloads', [name])]
        reported = {m['name'] for m in self.end_to_end}
        self.per_layer = [m for m in spec['per_layer']
                          if (name in m['workloads'] if 'workloads' in m
                              else m['moves'] in reported)]
        self.metrics_dir = metrics_dir

    @property
    def global_batch(self):
        return self.cfg['batch_per_chip'] * self.chips

    def reader(self, metric_name):
        return load_module(os.path.join(self.metrics_dir, metric_name + '.py'),
                           'bench_metric_' + metric_name.replace('.', '_'))


def require_chip(chips):
    """The cell's TPU devices, or exit non-zero before any set-up."""
    if os.environ.get('JAX_PLATFORMS', '').strip().lower() == 'cpu':
        sys.exit('benchmark: JAX_PLATFORMS=cpu; this benchmark runs on a TPU only')
    import jax
    devices = jax.devices()
    if devices[0].platform != 'tpu':
        sys.exit('benchmark: no TPU (jax found {!r})'.format(devices[0].platform))
    if len(devices) < chips:
        sys.exit('benchmark: the cell needs {} chips, jax found {}'.format(chips,
                                                                             len(devices)))
    return devices[:chips]


def peak_of(device_kind, bench_dir=BENCH_DIR):
    with open(os.path.join(bench_dir, 'peaks.json')) as f:
        peaks = json.load(f)
    if device_kind not in peaks:
        raise KeyError('peaks.json has no entry for device kind {!r}'.format(device_kind))
    return peaks[device_kind]


def configure_compile_cache():
    """JAX's persistent cache: where ``JAX_COMPILATION_CACHE_DIR`` says, else at one
    fixed path inside the checkout (the path is part of the key)."""
    import jax
    if not os.environ.get('JAX_COMPILATION_CACHE_DIR'):
        jax.config.update('jax_compilation_cache_dir', os.path.join(CACHE, 'jax'))
    jax.config.update('jax_persistent_cache_min_compile_time_secs', 0)
    jax.config.update('jax_persistent_cache_min_entry_size_bytes', 0)


def build_loader(cell, path, seeds, mesh):
    from jax.sharding import PartitionSpec as P
    from petastorm_tpu import make_reader
    from petastorm_tpu.parallel import JaxDataLoader
    mix = cell.mix
    rcfg = mix['reader']
    kwargs = dict(reader_pool_type=rcfg['pool'], workers_count=int(rcfg['workers']),
                  num_epochs=None, shuffle_row_groups=rcfg['shuffle_row_groups'],
                  seed=seeds['reader'])
    kwargs.update(cell.kind.reader_kwargs(mix, seeds))
    reader = make_reader('file://' + path, **kwargs)
    return JaxDataLoader(reader, batch_size=cell.global_batch, mesh=mesh,
                         partition_spec=P('data') if mesh is not None else None,
                         prefetch=mix['loader']['prefetch'])


def _decode_seconds(loader):
    """Worker ``decode`` stage seconds so far, from the program's stage registry."""
    hist = loader.telemetry_snapshot()['histograms'].get('decode')
    return hist['sum'] if hist else None


def _plant(fault, step, cell):
    """The train step with one fault planted in it, for the fault tests."""
    import jax

    def rows(batch, n):
        return jax.tree.map(lambda x: x[:n], batch)

    if fault is None or fault == 'altered':
        return step
    if fault == 'unchanged':
        return lambda state, batch: (state, step(state, batch)[1])
    if fault == 'half_batch':
        return lambda state, batch: step(state, rows(batch, cell.global_batch // 2))
    if fault == 'no_exchange':  # each chip's gradient left at its own quarter
        return lambda state, batch: step(state, rows(batch, cell.global_batch // cell.chips))
    raise ValueError('unknown fault {!r}'.format(fault))


class _Laps(object):
    """Logs each set-up phase's end, in seconds from the process start."""

    def __init__(self, name, t0):
        self.name, self.t0 = name, t0

    def __call__(self, phase):
        log('{}: {} at {:.1f}s'.format(self.name, phase, time.perf_counter() - self.t0))


class _Window(object):
    """Clock stamps of the measured window."""

    def __init__(self):
        self.stamps, self.wait_s, self.fetched, self.start = [], 0.0, 0, None


def run(cell, seed, seconds, trace=False, devices=None, fault=None, control=False,
        t0=None, cache_root=CACHE):
    """Set up, measure ``seconds`` and check one run of ``cell``. Returns the
    result dict (the printed line's keys, plus ``_run`` with the record the metric
    readers read and ``_readings`` with what was compared). ``devices`` defaults to
    the first ``cell.chips`` of JAX's. ``fault`` plants one of :data:`FAULTS` in
    the timed path; ``control`` also reads the control (the reference in fp8)."""
    import jax
    from jax.profiler import TraceAnnotation
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from benchmarks import common, trace as trace_mod

    t0 = time.perf_counter() if t0 is None else t0
    devices = devices if devices is not None else jax.devices()[:cell.chips]
    mesh = Mesh(np.asarray(devices), ('data',)) if cell.chips > 1 else None
    seeds = common.sub_seeds(seed)
    store_t0 = time.perf_counter()
    path, written = stores.ensure_store(cache_root, cell.mix_name, cell.mix['store'], cell.kind)
    # a training job reads its dataset and does not write it: a checkout's first
    # run writes the store outside set-up
    store_s = time.perf_counter() - store_t0 if written else 0.0
    log('{}: store ready at {:.1f}s ({:.1f}s writing)'.format(
        cell.name, time.perf_counter() - t0, store_s))
    program = cell.module.Program(cell.cfg, mesh)
    replicated = NamedSharding(mesh, P()) if mesh is not None else None
    key = common.weight_key(seeds['weights'])
    init = jax.jit(program.init, out_shardings=replicated)
    # the first gradient and the start are worked out inside the programs that take
    # their norms, so that no second copy of the parameters is held beside the state
    first_grad_norms = jax.jit(lambda state: common.leaf_norms(program.first_grads(state)))
    change_since = jax.jit(lambda params, k: common.change_norms(params, program.init_params(k)))
    alter = jax.jit(cell.kind.alter) if fault == 'altered' else None
    lap = _Laps(cell.name, t0)

    window = _Window()
    losses, checked, sampled = [], [], []
    trace_dir = os.path.join(cache_root, 'trace', cell.name)
    with contextlib.ExitStack() as stack:
        # the pool starts reading while the weights are made and the step compiles
        loader = stack.enter_context(build_loader(cell, path, seeds, mesh))
        batches = iter(loader)
        stack.callback(batches.close)
        lap('loader started')
        state = init(key)
        jax.block_until_ready(state)
        lap('weights made')
        out_shardings = None
        if mesh is not None:
            out_shardings = (jax.tree.map(lambda x: x.sharding, state), replicated)
        jitted = jax.jit(_plant(fault, program.step, cell), donate_argnums=0,
                         out_shardings=out_shardings)

        def next_batch():
            batch = next(batches)
            return alter(batch) if alter is not None else batch

        first = next_batch()
        lap('first batch')
        compiled = jitted.lower(state, first).compile()
        flops_per_chip_step = program.flops_per_chip_step(compiled, cell.chips)
        lap('step compiled')
        batch, check_losses = first, []
        for k in range(CHECKED_STEPS):
            batch = first if k == 0 else next_batch()
            checked.append(batch)
            state, loss = compiled(state, batch)
            check_losses.append(loss)
            if k == 0:
                first_grads = common.host_norms(first_grad_norms(state))
        change = common.host_norms(change_since(program.params(state), key))
        for _ in range(WARM_STEPS):
            state, loss = compiled(state, next_batch())
        jax.block_until_ready(loss)
        check_losses = [float(x) for x in check_losses]
        # the checked batches wait on the host, not in the device memory measured
        checked, first, batch = jax.device_get(checked), None, None
        lap('checked and warm steps')

        rng = np.random.default_rng(seeds['sample'])
        sample_at = set(rng.choice(SAMPLE_RANGE, SAMPLED_BATCHES, replace=False).tolist())

        def drive(seconds, window, keep=True):
            """Steps until ``seconds`` of completed steps: dispatch step i, then wait
            for step i-1 and stamp the clock."""
            nonlocal state
            prev = None
            window.start = time.perf_counter()
            while not window.stamps or window.stamps[-1] - window.start < seconds:
                w0 = time.perf_counter()
                with TraceAnnotation('bench.next_batch'):
                    batch = next_batch()
                window.wait_s += time.perf_counter() - w0
                if keep and window.fetched in sample_at:
                    sampled.append(batch)
                window.fetched += 1
                with TraceAnnotation('bench.dispatch'):
                    state, loss = compiled(state, batch)
                if prev is not None:
                    with TraceAnnotation('bench.wait_step'):
                        prev.block_until_ready()
                    window.stamps.append(time.perf_counter())
                    if keep:
                        losses.append(prev)
                prev = loss
            jax.block_until_ready(prev)

        decode0 = _decode_seconds(loader)
        setup_s = time.perf_counter() - t0 - store_s
        drive(seconds, window)
        decode1 = _decode_seconds(loader)
        memory = _memory(devices, compiled)
        reduced = None
        if trace:
            # traced apart, after the window: the profiler slows some cells' steps
            # several times over, and a short segment keeps the trace small
            shutil.rmtree(trace_dir, ignore_errors=True)
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = 1  # the annotations, not every runtime event
            jax.profiler.start_trace(trace_dir, profiler_options=options)
            with TraceAnnotation(trace_mod.WINDOW_SPAN):
                drive(min(seconds, TRACE_SECONDS), _Window(), keep=False)
            jax.profiler.stop_trace()
            files = [os.path.join(d, f) for d, _, fs in os.walk(trace_dir) for f in fs
                     if f.endswith('.xplane.pb')]
            reduced = trace_mod.reduce(files[0]) if files else None
            shutil.rmtree(trace_dir, ignore_errors=True)
        window_losses = np.asarray(jax.device_get(losses), np.float64)
        inputs = jax.device_get([{k: v for k, v in b.items()} for b in checked + sampled])
        del state, compiled, batch, first, checked, sampled, loss, losses
    gc.collect()

    steps = len(window.stamps)
    window_s = window.stamps[-1] - window.start
    intervals = np.diff([window.start] + window.stamps).tolist()
    record = {
        'work_unit': cell.module.WORK_UNIT, 'chips': cell.chips, 'steps': steps,
        'window_s': window_s, 'work_per_step': program.work(cell.global_batch),
        'intervals_s': intervals, 'wait_s': window.wait_s, 'setup_s': setup_s,
        'peak_bytes': memory['peak_bytes'], 'flops_per_chip_step': flops_per_chip_step,
        'peak': peak_of(devices[0].device_kind) if devices[0].platform == 'tpu' else None,
        'decode_s': (decode1 - decode0) if decode0 is not None and decode1 is not None
        else None,
        'rows_in_window': window.fetched * cell.global_batch,
        'trace': reduced, 'flash': program.attention()}

    log('{}: window {:.1f}s, {} steps; memory {}; reference'.format(
        cell.name, window_s, steps, json.dumps(memory)))
    readings, checks = compare(cell, path, seeds, inputs, check_losses, first_grads, change,
                               control, mesh)
    log('{}: checked at {:.1f}s'.format(cell.name, time.perf_counter() - t0))
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = cell.reader(m['name']).read(record)
        if value is not None:
            metrics[m['name']] = {'value': value, 'unit': m['unit']}
    device = {'platform': devices[0].platform, 'kind': devices[0].device_kind,
              'count': len(devices), 'memory_peak_bytes': memory['peak_bytes']}
    result = {'correct': all(v['value'] <= v['limit'] for v in checks.values()),
              'attempted': steps, 'failed': int(np.sum(~np.isfinite(window_losses))),
              'metrics': metrics, 'device': device}
    if reduced is not None:
        device['busy_s'] = reduced['busy_s']
        device['window_s'] = reduced['window_s']
        result['breakdown'] = {'device_ops': reduced['device_ops'],
                               'idle_gaps': reduced['idle_gaps']}
    result['checks'] = checks
    result['_run'] = record
    result['_readings'] = readings
    if control:
        result['_control_checks'] = readings.pop('control_checks')
        result['_control_correct'] = all(v['value'] <= v['limit']
                                         for v in result['_control_checks'].values())
    return result


def _memory(devices, compiled):
    """The fullest chip's peak: the runtime's peak of live arrays, which on a TPU
    leaves out a program's temporary buffers, plus the temporaries of the compiled
    step (XLA's ``memory_analysis``, per chip), which run beside the arrays in
    every step of the window. Read right after the window, before the check."""
    stats = [d.memory_stats() or {} for d in devices]
    fullest = max(stats, key=lambda s: s.get('peak_bytes_in_use', 0))
    arrays = fullest.get('peak_bytes_in_use', 0)
    analysis = compiled.memory_analysis()
    temp = int(analysis.temp_size_in_bytes) if analysis is not None else 0
    return {'peak_bytes': arrays + temp if arrays else 0, 'arrays_peak_bytes': arrays,
            'step_temp_bytes': temp, 'runtime_stats': fullest,
            'step_argument_bytes': int(analysis.argument_size_in_bytes) if analysis else 0,
            'step_alias_bytes': int(analysis.alias_size_in_bytes) if analysis else 0}


def compare(cell, path, seeds, inputs, losses, grads, change, control=False, mesh=None):
    """The check: the delivered rows against the reference's own read of the store,
    and the three checked steps against the reference's. Returns ``(readings,
    checks)``; each check is ``{'value', 'limit'}``."""
    columns = list(cell.kind.COLUMNS)
    table = stores.read_columns(path, columns)
    rows_diff = 0
    plain = []
    for batch in inputs:
        ids = [int(i) for i in np.asarray(batch[stores.ID]).reshape(-1)]
        want = cell.kind.plain_rows(cell.mix, table, ids, seeds)
        plain.append(want)
        for field in columns:
            got = np.asarray(batch[field]).astype(np.int64)
            rows_diff = max(rows_diff, int(np.max(np.abs(got - want[field].astype(np.int64)))))
    shardings = None
    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec as P
        shardings = (NamedSharding(mesh, P()), NamedSharding(mesh, P('data')))
    ref = cell.module.reference_run(cell.cfg, seeds['weights'], plain[:CHECKED_STEPS],
                                    shardings=shardings)
    readings = {'program': _gaps(losses, grads, change, ref), 'rows_max_diff': rows_diff,
                'losses': losses, 'reference_losses': ref['loss']}
    limits = cell.cfg['checks']
    checks = {'rows_max_diff': {'value': rows_diff,
                                'limit': cell.mix['check']['rows_max_diff']}}
    for name, limit in limits.items():
        checks[name] = {'value': readings['program'][name], 'limit': limit}
    if control:  # the reference in fp8 in the program's place, on the plain rows
        low = cell.module.reference_run(cell.cfg, seeds['weights'], plain[:CHECKED_STEPS],
                                        low=True, shardings=shardings)
        readings['control'] = _gaps(low['loss'], low['grad'], low['change'], ref)
        readings['control_checks'] = {
            name: {'value': readings['control'][name], 'limit': limit}
            for name, limit in limits.items()}
    return readings, checks


def _gaps(losses, grads, change, ref):
    from benchmarks import common
    loss_gaps = [abs(a - b) / abs(b) for a, b in zip(losses, ref['loss'])]
    grad = common.leaf_gaps(grads, ref['grad'])
    change = common.leaf_gaps(change, ref['change'], skip_below=(NOUGHT_GRADIENT, ref['grad']))
    top = lambda gaps: sorted(gaps.items(), key=lambda kv: -kv[1])[:5]  # noqa: E731
    return {'loss_gap': max(loss_gaps), 'grad_gap': max(grad.values()),
            'change_gap': max(change.values()), 'loss_gaps': loss_gaps,
            'grad_median_gap': float(np.median(list(grad.values()))),
            'change_median_gap': float(np.median(list(change.values()))),
            'grad_worst': top(grad), 'change_worst': top(change)}


def result_line(result):
    """The printed result line, ``checks`` last."""
    return json.dumps({k: v for k, v in result.items() if not k.startswith('_')})


def check_lines(result):
    return ['check {} {!r} limit {!r}'.format(name, c['value'], c['limit'])
            for name, c in result['checks'].items()]
