"""Device time per round under ``obs.detect``: the CUSUM update, burn-in
masking and the controller's split/evict step.
(``bench/scopes.py``.)"""
from bench import scopes


def read(run):
    return scopes.of(run).get("loop_detect_ms")
