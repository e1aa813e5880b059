"""``ysb_window_count``: YSB's count per campaign per event-time window
is the aggregate (``aggregates/ysb_campaign_window_counts.py``), so this
stage passes the joined records on unchanged."""


def apply(recs, const):
    return recs
