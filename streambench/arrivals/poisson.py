"""``poisson``: an open loop.  Chunks arrive at ``chunks_per_s`` on
average.  The run offers a fixed number of chunks, the whole windows that
the rate fills in ``seconds``.  Every seed draws the same set of gaps (the
quantiles of an exponential) in its own order, so seeds change the order of
arrivals and not the amount of work."""
import numpy as np


def schedule(mix, seconds, window_chunks, seed):
    rate = float(mix.get("chunks_per_s", 0))
    if not rate > 0:
        raise ValueError("a poisson mix needs chunks_per_s > 0")
    n = max(1, int(round(rate * seconds / window_chunks))) * window_chunks
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n) / rate
    gaps = np.random.default_rng(seed).permutation(gaps)
    # the first chunk is due at 0
    return np.concatenate([[0.0], np.cumsum(gaps[:-1])])
