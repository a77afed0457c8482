"""1 - (union of the device's busy intervals) / traced window, the mean over
the chips used."""


def read(r):
    if r.trace is None or r.trace.busy_s <= 0:
        return None
    return 100.0 * (1.0 - r.trace.busy_s / r.trace.window_s)
