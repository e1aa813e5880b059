"""Compiled-program launches per ingress window: the program's
``device.dispatches`` counter over the window, divided by the windows
offered."""


def read(run):
    if run.windows == 0:
        return None
    return run.counters["device.dispatches"] / run.windows
