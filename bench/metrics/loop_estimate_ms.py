"""Device time per round under ``obs.estimate``: the telemetry block and
the estimator bank's update.
(``bench/scopes.py``.)"""
from bench import scopes


def read(run):
    return scopes.of(run).get("loop_estimate_ms")
