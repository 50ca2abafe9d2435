"""Device time per round under ``obs.segment_event_loop``: the segments'
event loops, every scope inside them included.
(``bench/scopes.py``.)"""
from bench import scopes


def read(run):
    return scopes.of(run).get("loop_events_ms")
