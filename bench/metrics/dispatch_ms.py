"""Mean ``closed_loop.dispatch`` span per round over the traced window (the
program's own span, host clock)."""

SPAN = "closed_loop.dispatch"


def read(run):
    if run.spans is None:
        return None
    n = sum(1 for s in run.spans.spans if s.name == SPAN)
    if n == 0:
        return None
    return 1e3 * run.spans.durations()[SPAN] / n
