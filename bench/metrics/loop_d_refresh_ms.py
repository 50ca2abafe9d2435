"""Device time per round under ``obs.d_refresh``: the D re-blend after each
segment (full or by touched columns).
(``bench/scopes.py``.)"""
from bench import scopes


def read(run):
    return scopes.of(run).get("loop_d_refresh_ms")
