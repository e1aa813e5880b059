"""Per cent of the traced interval spent in the program's ``egress.open``
and ``reduce.fold`` spans (opening each egress window and folding its
chunks into the sink's result)."""
from streambench.stats import share

SPANS = ("egress.open", "reduce.fold")


def read(run):
    if run.spans is None:
        return None
    lo, hi = run.interval
    return share([(a, b) for n, a, b in run.spans if n in SPANS], lo, hi)
