"""Mean ``closed_loop.dispatch.fetch`` span per round: the device-to-host
copies of the per-segment outputs.
(``bench/scopes.py``.)"""
from bench import scopes


def read(run):
    return scopes.of(run).get("dispatch_fetch_ms")
