"""Median of a chunk's time in the engine: from when the ingress took it
until the sink's fold of it returned (window fill, the ingress hold, both
hops, egress and the fold)."""
from streambench.stats import percentile


def read(run):
    n = min(run.folded, run.offered)
    return percentile((run.fold[:n] - run.take[:n]) * 1e3, 50)
