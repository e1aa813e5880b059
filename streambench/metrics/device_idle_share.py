"""Per cent of the traced interval in which no operation ran on the
device: 1 - (union of the device operations' intervals / interval),
averaged over the chips."""


def read(run):
    if run.device is None or not run.device.devices:
        return None
    lo, hi = run.interval
    if hi <= lo:
        return None
    return 100.0 * (1.0 - run.device.busy_s(lo, hi) / (hi - lo))
