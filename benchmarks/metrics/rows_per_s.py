"""Rows consumed by the train steps that completed in the window, per second of
the window and per chip."""


def read(run):
    if run['work_unit'] != 'rows':
        return None
    return run['steps'] * run['work_per_step'] / run['window_s'] / run['chips']
