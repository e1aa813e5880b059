"""95th percentile of how long a chunk waited at the source: from when it
was due until the engine took it (the ingress pulled it)."""
from streambench.stats import percentile


def read(run):
    return percentile((run.take - run.due) * 1e3, 95)
