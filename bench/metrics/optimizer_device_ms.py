"""Milliseconds of device time a window step under the train step's
``optimizer`` scope (clipping and the AdamW update), scopes nested in it
included: from the traced window's operations."""
from bench.spans import scope_ms


def read(run):
    return scope_ms(run, "optimizer")
