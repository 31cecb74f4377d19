"""Milliseconds of device time a window step under the train step's
``mixer`` scope (attention or the Mamba scan, forward and backward),
scopes nested in it included: from the traced window's operations."""
from bench.spans import scope_ms


def read(run):
    return scope_ms(run, "mixer")
