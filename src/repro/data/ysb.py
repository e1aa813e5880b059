"""The Yahoo Streaming Benchmark's ad events and campaign table.

YSB (github.com/yahoo/streaming-benchmarks; Chintapalli et al., IPDPSW
2016) streams ad events, keeps the views, joins each view's ``ad_id`` to
its campaign and counts views per campaign per 10 s event-time window.
Here an event is one 16-word record (64 B, one ChaCha20 block):

* words 0-3 ``user_id``, 4-7 ``page_id``, 8-11 ``ad_id`` (128-bit ids);
* word 12 ``ad_type`` (0-4), word 13 ``event_type`` (:data:`VIEW`,
  :data:`CLICK`, :data:`PURCHASE`; 0 marks an empty row, such as one a
  filter zeroed);
* words 14-15 ``event_time`` in ms, low then high word.

The campaign table holds 100 campaigns of 10 ads.  Ad ``k``'s id is a
function of the table seed (:func:`campaign_ads`) and its campaign is
``k // 10``.  The join and the count keep their own row layouts
(``repro.kernels.enclave_map``): a joined view keeps words 13-15 and puts
its campaign in word 12; a partial count is ``(window lo, window hi,
campaign, count)`` in words 0-3.
"""
from __future__ import annotations

import numpy as np

RECORD_WORDS = 16
AD_WORDS = slice(8, 12)
CAMPAIGN_WORD = 12          # ad_type in an event, campaign once joined
EVENT_TYPE_WORD = 13
TIME_LO_WORD, TIME_HI_WORD = 14, 15
VIEW, CLICK, PURCHASE = 1, 2, 3

CAMPAIGNS = 100
ADS_PER_CAMPAIGN = 10
TABLE_LANES = 1024          # table slots; slots past the ads match nothing
# a view whose ad is in no campaign is joined to this campaign id
MISS = 0x7FFFFFFF


def _fmix32(x: np.ndarray) -> np.ndarray:
    """MurmurHash3's 32-bit finaliser: a bijection on uint32."""
    x = x.astype(np.uint32)
    x ^= x >> np.uint32(16)
    x *= np.uint32(0x85EBCA6B)
    x ^= x >> np.uint32(13)
    x *= np.uint32(0xC2B2AE35)
    x ^= x >> np.uint32(16)
    return x


def campaign_ads(seed: int, campaigns: int = CAMPAIGNS,
                 ads_per_campaign: int = ADS_PER_CAMPAIGN) -> np.ndarray:
    """(ads, 4) uint32 ad ids: word j of ad k is
    ``fmix32(seed * 0x9E3779B9 + 4k + j + 1)`` (mod 2^32).  fmix32 is a
    bijection, so all 4 * ads words, and so all ids, are distinct."""
    n = campaigns * ads_per_campaign
    base = (int(seed) * 0x9E3779B9 + 1) & 0xFFFFFFFF
    x = (np.uint64(base) + np.arange(4 * n, dtype=np.uint64)) \
        & np.uint64(0xFFFFFFFF)
    return _fmix32(x).reshape(n, 4)


def campaign_table(seed: int) -> np.ndarray:
    """The join's table operand, (64, 128) uint32 in lanes: row ``8r + m``,
    lane ``l`` is word ``r`` of slot ``k = 128m + l``: words 0-3 the ad id,
    word 4 its campaign (``k // 10``), words 5-7 zero.  Slots past the
    1000 ads hold a zero id and campaign :data:`MISS`."""
    ads = campaign_ads(seed)
    n = len(ads)
    if n > TABLE_LANES:
        raise ValueError(f"{n} ads do not fit {TABLE_LANES} table slots")
    t = np.zeros((8, TABLE_LANES), np.uint32)
    t[0:4, :n] = ads.T
    t[4] = MISS
    t[4, :n] = np.arange(n) // ADS_PER_CAMPAIGN
    return t.reshape(64, 128)
