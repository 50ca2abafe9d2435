"""Device time per round whose innermost scope is ``obs.drain``: the event
loop's DRAIN arm, less the scorer it calls.
(``bench/scopes.py``.)"""
from bench import scopes


def read(run):
    return scopes.of(run).get("loop_drain_ms")
