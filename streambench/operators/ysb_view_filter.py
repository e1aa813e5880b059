"""``ysb_view_filter``: YSB keeps the views.  A record whose event type
(word 13) is a view (1) passes whole; any other record becomes all
zeros."""
import numpy as np

EVENT_TYPE_WORD = 13
VIEW = 1


def apply(recs, const):
    keep = recs[..., EVENT_TYPE_WORD] == VIEW
    return np.where(keep[..., None], recs, np.uint32(0))
