"""Process start to the window's first round: building the scheduler (per-
server profiling), warm-up rounds with their compilation, the round pool."""


def read(run):
    return run.setup_s
