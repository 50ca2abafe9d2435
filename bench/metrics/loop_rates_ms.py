"""Device time per round whose innermost scope is ``obs.rates``: slot rates,
observed degradation and finish times before each micro-event.
(``bench/scopes.py``.)"""
from bench import scopes


def read(run):
    return scopes.of(run).get("loop_rates_ms")
