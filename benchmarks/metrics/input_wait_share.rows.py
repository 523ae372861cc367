"""Share of the window the train loop spent blocked in ``next(loader)``, by the
benchmark's own clock (cells that count rows)."""
from benchmarks.metrics import _shares


def read(run):
    return _shares.input_wait_share(run, 'rows')
