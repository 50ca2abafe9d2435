"""Mean ``closed_loop.dispatch.call`` span per round: the ``run_closed_loop``
call until it returns (argument flattening, the executable's cache lookup,
argument copies to the device, the enqueue).
(``bench/scopes.py``.)"""
from bench import scopes


def read(run):
    return scopes.of(run).get("dispatch_call_ms")
