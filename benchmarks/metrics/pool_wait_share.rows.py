"""Share of the window the loader's producer thread waited on the worker pool
for a result (the program's ``pool_wait`` spans over the window), cells that
count rows."""
from benchmarks import stages


def read(run):
    return stages.window_share(run, ('pool_wait',), 'rows')
