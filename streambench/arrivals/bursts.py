"""``bursts``: an open loop in bursts.  Each period is ``on_s`` seconds of
arrivals, then ``off_s`` seconds with none.  Every burst carries the
mean rate's work for a period, ``chunks_per_s * (on_s + off_s)`` chunks,
as a Poisson stream at ``chunks_per_s * (on_s + off_s) / on_s`` that
starts with the period.  The run offers the whole windows that the mean
rate fills in ``seconds``.  Inside each burst the gaps are ``poisson``'s
(the quantiles of an exponential) in an order of the seed's, so seeds
change the order of arrivals and not how much work a burst carries."""
import numpy as np

from streambench import layout


def schedule(mix, seconds, window_chunks, seed):
    on, off = float(mix.get("on_s", 0)), float(mix.get("off_s", 0))
    rate = float(mix.get("chunks_per_s", 0))
    if not (on > 0 and off >= 0 and rate > 0):
        raise ValueError("a bursts mix needs on_s > 0, off_s >= 0 and "
                         "chunks_per_s > 0")
    period = on + off
    n = max(1, int(round(rate * seconds / window_chunks))) * window_chunks
    per_burst = max(1, int(round(rate * period)))
    poisson = layout.load_module("arrivals", "poisson")
    burst = dict(mix, chunks_per_s=per_burst / on)
    seeds = np.random.default_rng(seed).integers(2 ** 63,
                                                 size=-(-n // per_burst))
    # one burst's arrivals: per_burst quantile gaps in the seed's order,
    # the first at the period's start; they sum to less than on_s
    t = [p * period + poisson.schedule(burst, on, 1, int(s))
         for p, s in enumerate(seeds)]
    return np.concatenate(t)[:n]
