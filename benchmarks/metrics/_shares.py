"""Arithmetic that several per-layer readers share."""


def input_wait_share(run, unit):
    if run['work_unit'] != unit:
        return None
    return 100.0 * run['wait_s'] / run['window_s']


def mfu(run, unit):
    if run['work_unit'] != unit or not run['flops_per_chip_step'] or not run['peak']:
        return None
    achieved = run['flops_per_chip_step'] * run['steps'] / run['window_s']
    return 100.0 * achieved / run['peak']['bf16_flops_per_s']


def device_idle_share(run, unit):
    trace = run['trace']
    if run['work_unit'] != unit or trace is None:
        return None
    return 100.0 * (1.0 - trace['busiest_busy_s'] / trace['window_s'])
