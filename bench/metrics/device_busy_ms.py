"""Whole-device busy time per round: the union of every device operation's
interval over the profiled slice (event loop, segment scan and D re-blend
alike), divided by the rounds in it."""


def read(run):
    r = run.reduction
    if r is None or r.rounds == 0 or r.busy_s <= 0.0:
        return None
    return 1e3 * r.busy_s / r.rounds
