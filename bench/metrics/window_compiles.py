"""Compile, lowering, tracing and persistent-cache-load events inside the
window (``jax.monitoring``); anything but 0 is a finding."""


def read(run):
    return run.compiles
