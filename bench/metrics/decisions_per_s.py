"""Placement and queue decisions completed per second: the decisions of every
round in the window over window start to the end of its last round."""


def read(run):
    return run.window.decisions_per_s()
