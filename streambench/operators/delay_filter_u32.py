"""``delay_filter_u32``: a record whose arrival delay (word 1 of the
DelayedFlights record, minutes as u32) is above ``const`` passes whole; any
other record becomes all zeros."""
import numpy as np

DELAY_WORD = 1


def apply(recs, const):
    keep = recs[..., DELAY_WORD].astype(np.int64) > int(const)
    return np.where(keep[..., None], recs, np.uint32(0))
