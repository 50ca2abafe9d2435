"""Device time per round under none of ``obs.segment_event_loop``,
``obs.estimate``, ``obs.detect`` and ``obs.d_refresh``: scan plumbing,
requeue, the ring write, the initial D and pack's own device operations.
(``bench/scopes.py``.)"""
from bench import scopes


def read(run):
    return scopes.of(run).get("loop_other_ms")
