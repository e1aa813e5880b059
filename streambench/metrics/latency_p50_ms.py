"""Median over every chunk of the window of the time from when the chunk
was due (its last event created) until the sink's fold of it returned."""
from streambench.stats import percentile


def read(run):
    n = min(run.folded, run.offered)
    return percentile((run.fold[:n] - run.due[:n]) * 1e3, 50)
