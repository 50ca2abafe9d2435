"""How long a batch of arrivals waits for its decisions: the 95th percentile
(linear interpolation) of every round's time in the window, host clock."""


def read(run):
    return run.window.round_p95_ms()
