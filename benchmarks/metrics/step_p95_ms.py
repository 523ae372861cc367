"""95th percentile of the intervals between consecutive step completions over
the whole window, in milliseconds."""
import statistics


def read(run):
    intervals = run['intervals_s']
    if len(intervals) < 2:
        return None
    return statistics.quantiles(intervals, n=20, method='inclusive')[18] * 1e3
