"""The step's model operations per second on each chip over the window, as a
share of the chip's bf16 peak (cells that count rows)."""
from benchmarks.metrics import _shares


def read(run):
    return _shares.mfu(run, 'rows')
