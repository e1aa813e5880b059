"""Blocking device-to-host rendezvous per ingress window: the program's
``pipeline.host_syncs`` counter over the window, divided by the windows
offered."""


def read(run):
    if run.windows == 0:
        return None
    return run.counters["pipeline.host_syncs"] / run.windows
