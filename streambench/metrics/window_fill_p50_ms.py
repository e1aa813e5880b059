"""Median time to fill an engine window: the program's ``ingress.fill``
spans, one per window, each from the ingress's first request to the
source until the window's last chunk was received.  None where the
program records no such span."""
from streambench.stats import percentile


def read(run):
    if run.spans is None:
        return None
    return percentile([(b - a) * 1e3 for n, a, b in run.spans
                       if n == "ingress.fill"], 50)
