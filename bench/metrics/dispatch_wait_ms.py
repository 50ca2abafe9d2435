"""Mean ``closed_loop.dispatch.wait`` span per round: ``block_until_ready`` on
the device loop's outputs, the device program as the host sees it.
(``bench/scopes.py``.)"""
from bench import scopes


def read(run):
    return scopes.of(run).get("dispatch_wait_ms")
