"""``carrier_delay_stats``: per carrier (word 0 of the DelayedFlights
record), the number of records whose delay (word 1) is above zero and the
sum of those delays, as exact integers."""
import numpy as np

CARRIER_WORD = 0
DELAY_WORD = 1


def per_chunk(recs, config):
    """(chunks, records, words) -> {"count", "sum"}: (chunks, carriers)
    int64, each chunk on its own."""
    carriers = int(config["carriers"])
    n_chunks = recs.shape[0]
    carrier = recs[..., CARRIER_WORD].astype(np.int64)
    delay = recs[..., DELAY_WORD].astype(np.int64)
    valid = delay > 0
    key = (np.arange(n_chunks)[:, None] * carriers + carrier)[valid]
    size = n_chunks * carriers
    count = np.bincount(key, minlength=size).reshape(n_chunks, carriers)
    total = np.bincount(key, weights=delay[valid], minlength=size)
    return {"count": count.astype(np.int64),
            "sum": np.rint(total).astype(np.int64).reshape(n_chunks,
                                                           carriers)}
