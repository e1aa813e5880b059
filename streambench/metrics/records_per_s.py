"""Records folded into the sink per second, over the whole measured time:
from the first due chunk until ``run()`` returned after the drain."""


def read(run):
    t = run.t_end - run.t_open
    if run.folded == 0 or t <= 0:
        return None
    return run.folded * int(run.config["chunk_records"]) / t
