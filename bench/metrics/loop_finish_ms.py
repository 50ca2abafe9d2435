"""Device time per round whose innermost scope is ``obs.finish``: the
event loop's FINISH arm.
(``bench/scopes.py``.)"""
from bench import scopes


def read(run):
    return scopes.of(run).get("loop_finish_ms")
