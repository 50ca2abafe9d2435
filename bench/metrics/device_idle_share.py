"""Share of the profiled slice in which no operation ran on the device."""


def read(run):
    r = run.reduction
    if r is None or r.window_s <= 0.0:
        return None
    return 100.0 * (1.0 - r.busy_s / r.window_s)
