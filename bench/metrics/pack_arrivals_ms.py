"""Mean ``closed_loop.pack.arrivals`` span per round: sorting the arrivals
and packing their time, type and byte arrays per segment.
(``bench/scopes.py``.)"""
from bench import scopes


def read(run):
    return scopes.of(run).get("pack_arrivals_ms")
