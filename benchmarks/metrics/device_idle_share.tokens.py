"""1 - the union of device-op intervals over the traced window, on the busiest
chip (cells that count tokens)."""
from benchmarks.metrics import _shares


def read(run):
    return _shares.device_idle_share(run, 'tokens')
