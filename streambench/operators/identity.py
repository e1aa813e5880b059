"""``identity``: every record passes unchanged."""


def apply(recs, const):
    return recs
