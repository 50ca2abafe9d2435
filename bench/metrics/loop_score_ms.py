"""Device time per round whose innermost scope is ``obs.score``: the
scorer and the Fig. 8 argmin, called from the ARRIVE and DRAIN arms.
(``bench/scopes.py``.)"""
from bench import scopes


def read(run):
    return scopes.of(run).get("loop_score_ms")
