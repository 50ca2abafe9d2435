"""Mean ``closed_loop.pack.state`` span per round: scorer and hyper-
parameters, the scan's initial carry and its per-segment inputs.
(``bench/scopes.py``.)"""
from bench import scopes


def read(run):
    return scopes.of(run).get("pack_state_ms")
