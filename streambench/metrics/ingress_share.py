"""Per cent of the traced interval spent in the program's ``ingress.seal``
spans (framing and sealing each ingress window, as the host sees it)."""
from streambench.stats import share


def read(run):
    if run.spans is None:
        return None
    lo, hi = run.interval
    return share([(a, b) for n, a, b in run.spans if n == "ingress.seal"],
                 lo, hi)
