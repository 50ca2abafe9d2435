"""Mean ``closed_loop.pack.tables`` span per round: the per-server tables
(the dynamics bank and its stack, ``PackedCluster.build``, the prior
stacks) and their uploads.
(``bench/scopes.py``.)"""
from bench import scopes


def read(run):
    return scopes.of(run).get("pack_tables_ms")
