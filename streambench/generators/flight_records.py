"""DelayedFlights records, made from a seed.

The paper's DelayedFlights job (§5.2) reads the Data Expo 2009 airline
on-time records.  A record is 16 uint32 words, one cipher block: word 0 is
the carrier, word 1 the arrival delay in minutes, word 2 the distance,
word 3 an opaque payload, and the rest zero.  Carriers are uniform; about
35% of flights are delayed with a gamma(2, 30) delay, the rest arrive within
15 minutes.  The same seed always gives the same records.
"""
import numpy as np

RECORD_WORDS = 16


def records(n_records, config, seed):
    """(n_records, 16) uint32 records."""
    if int(config["record_words"]) != RECORD_WORDS:
        raise ValueError(f"DelayedFlights records are {RECORD_WORDS} words")
    rng = np.random.default_rng(seed)
    rec = np.zeros((n_records, RECORD_WORDS), dtype=np.uint32)
    rec[:, 0] = rng.integers(0, int(config["carriers"]), n_records)
    delayed = rng.random(n_records) < 0.35
    rec[:, 1] = np.where(delayed, rng.gamma(2.0, 30.0, n_records),
                         rng.uniform(0, 15, n_records)).astype(np.uint32)
    rec[:, 2] = rng.integers(100, 5000, n_records)
    rec[:, 3] = rng.integers(0, 2 ** 31, n_records)
    return rec
