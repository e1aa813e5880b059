"""Yahoo Streaming Benchmark ad events, made from a seed.

YSB (github.com/yahoo/streaming-benchmarks; Chintapalli et al., IPDPSW
2016) streams JSON ad events.  Here an event is one 16-word record (64 B,
one cipher block): words 0-3 ``user_id``, 4-7 ``page_id``, 8-11 ``ad_id``
(128-bit ids), word 12 ``ad_type`` (0-4), word 13 ``event_type`` (1 view,
2 click, 3 purchase; 0 never occurs in an event) and words 14-15
``event_time`` in ms, low then high word.  YSB's ``ip_address`` is a
constant in its generator and is dropped.

Ads are uniform over the campaign table's 100 x 10 ads and event types
uniform over the three.  Event ``i`` of the pool is stamped
``time_origin_ms + floor(i * 1000 / events_per_s)``, as a generator at
``events_per_s`` would stamp it; the origin lies on a window boundary.

The table (:func:`campaign_ads`): word ``j`` of ad ``k``'s id is
``fmix32(seed * 0x9E3779B9 + 4k + j + 1)`` (mod 2^32, MurmurHash3's
finaliser), ``seed`` being the join stage's ``const``; ad ``k`` belongs to
campaign ``k // ads_per_campaign``.
"""
import numpy as np

RECORD_WORDS = 16
JOIN_OP = "ysb_campaign_join"


def _fmix32(x):
    x = x.astype(np.uint64) & np.uint64(0xFFFFFFFF)
    for shift, mul in ((16, 0x85EBCA6B), (13, 0xC2B2AE35)):
        x = ((x ^ (x >> np.uint64(shift))) * np.uint64(mul)) \
            & np.uint64(0xFFFFFFFF)
    return (x ^ (x >> np.uint64(16))).astype(np.uint32)


def table_seed(config) -> int:
    """The join stage's table seed."""
    return int(next(st["const"] for st in config["job"]["stages"]
                    if st["op"] == JOIN_OP))


def ad_ids(seed: int, n_ads: int) -> np.ndarray:
    """(n_ads, 4) uint32: ad k's id, by the rule above."""
    base = (int(seed) * 0x9E3779B9 + 1) & 0xFFFFFFFF
    return _fmix32(base + np.arange(4 * n_ads, dtype=np.uint64)) \
        .reshape(n_ads, 4)


def campaign_ads(config) -> np.ndarray:
    """The configuration's table: (ads, 4) uint32 ids."""
    return ad_ids(table_seed(config), int(config["campaigns"])
                  * int(config["ads_per_campaign"]))


def records(n_records, config, seed):
    """(n_records, 16) uint32 events."""
    if int(config["record_words"]) != RECORD_WORDS:
        raise ValueError(f"YSB events are {RECORD_WORDS} words")
    rng = np.random.default_rng(seed)
    ads = campaign_ads(config)
    rec = np.empty((n_records, RECORD_WORDS), np.uint32)
    rec[:, 0:8] = rng.integers(0, 2 ** 32, (n_records, 8), dtype=np.uint32)
    rec[:, 8:12] = ads[rng.integers(0, len(ads), n_records)]
    rec[:, 12] = rng.integers(0, int(config["ad_types"]), n_records)
    rec[:, 13] = rng.integers(1, int(config["event_types"]) + 1, n_records)
    t = int(config["time_origin_ms"]) \
        + np.arange(n_records, dtype=np.int64) * 1000 \
        // int(config["events_per_s"])
    rec[:, 14] = (t & 0xFFFFFFFF).astype(np.uint32)
    rec[:, 15] = (t >> 32).astype(np.uint32)
    return rec
