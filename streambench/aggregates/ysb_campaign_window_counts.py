"""``ysb_campaign_window_counts``: YSB's result, the number of views per
campaign per 10 s tumbling event-time window, every view counted, as
exact integers.  A joined view (word 13 non-zero) counts in window
``event_time // window_ms`` (words 14-15, ms) at slot ``window %
windows``, under its campaign (word 12); a view joined to no campaign
counts nowhere.  ``windows`` and ``campaigns`` come from the job's
``reduce_args``."""
import numpy as np


def per_chunk(recs, config):
    """(chunks, records, words) -> {"count": (chunks, windows, campaigns)
    int64}, each chunk on its own."""
    args = config["job"]["reduce_args"]
    windows, campaigns = int(args["windows"]), int(args["campaigns"])
    n_chunks = recs.shape[0]
    t = recs[..., 14].astype(np.int64) | (recs[..., 15].astype(np.int64)
                                          << 32)
    slot = (t // int(config["window_ms"])) % windows
    camp = recs[..., 12].astype(np.int64)
    live = (recs[..., 13] != 0) & (camp < campaigns)
    chunk = np.broadcast_to(np.arange(n_chunks)[:, None], live.shape)
    key = ((chunk * windows + slot) * campaigns + camp)[live]
    count = np.bincount(key, minlength=n_chunks * windows * campaigns)
    return {"count": count.reshape(n_chunks, windows, campaigns)
            .astype(np.int64)}
