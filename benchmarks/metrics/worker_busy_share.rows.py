"""How full the worker pool is: the workers' stage seconds over the window
(open, read, decode, shuffle, transform, cache, serialize) over the workers
times the window, from the program's stage spans (cells that count rows)."""
from benchmarks import stages


def read(run):
    return stages.window_share(run, stages.WORKER_STAGES, 'rows', per_worker=True)
