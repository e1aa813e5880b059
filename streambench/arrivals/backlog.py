"""``backlog``: every chunk is due when the window opens, and the source
offers whole engine windows for as long as the run lasts (closed loop: the
engine pulls as fast as it can)."""


def schedule(mix, seconds, window_chunks, seed):
    return None
