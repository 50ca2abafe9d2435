"""Device time per round whose innermost scope is ``obs.arrive``: the
event loop's ARRIVE arm, less the scorer it calls.
(``bench/scopes.py``.)"""
from bench import scopes


def read(run):
    return scopes.of(run).get("loop_arrive_ms")
