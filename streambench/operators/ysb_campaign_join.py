"""``ysb_campaign_join``: YSB joins each view's ``ad_id`` (words 8-11) to
its campaign in the table that ``const`` seeds (the generator's rule):
100 campaigns of 10 ads, ad ``k`` in campaign ``k // 10``.
A record that holds a view comes out with its campaign in word 12, or
``MISS`` when its ad is in no campaign, and its event type and time kept
(words 13-15); every other word is zero.  An empty record stays empty."""
import numpy as np

from streambench import layout

MISS = 2 ** 32 - 1
CAMPAIGNS, ADS_PER_CAMPAIGN = 100, 10


def apply(recs, const):
    ads = layout.load_module("generators", "ysb_events").ad_ids(
        int(const), CAMPAIGNS * ADS_PER_CAMPAIGN)
    # the ids' first words are distinct, so the first word finds the ad
    order = np.argsort(ads[:, 0])
    first = ads[order, 0]
    ad = recs[..., 8:12]
    pos = np.clip(np.searchsorted(first, ad[..., 0]), 0, len(first) - 1)
    k = order[pos]
    hit = np.all(ads[k] == ad, axis=-1)
    out = np.zeros_like(recs)
    out[..., 12] = np.where(hit, k // ADS_PER_CAMPAIGN, MISS)
    out[..., 13:] = recs[..., 13:]
    present = recs[..., 13] != 0
    return np.where(present[..., None], out, np.uint32(0))
