"""Share of the window the loader's producer thread spent on its own work
(mapping results, the reader's per-item accounting, re-batching, collate,
device decode and upload), from the program's stage spans over the window.
Near 100 the producer thread sets the pace (cells that count rows)."""
from benchmarks import stages


def read(run):
    return stages.window_share(run, stages.PRODUCER_BUSY_STAGES, 'rows')
