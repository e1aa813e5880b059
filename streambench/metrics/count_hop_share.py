"""Per cent of the traced interval spent in the program's
``enclave.join`` and ``enclave.count`` spans: the host work of the YSB
join and count hops (cipher coordinates, launches), as the host sees it.
None where the program records no such span."""
from streambench.stats import share

SPANS = ("enclave.join", "enclave.count")


def read(run):
    if run.spans is None:
        return None
    lo, hi = run.interval
    spans = [(a, b) for n, a, b in run.spans if n in SPANS]
    return share(spans, lo, hi) if spans else None
