"""Seconds from the process's start to the window's start: JAX start-up, the
train state built on the device, the step compiled or loaded from the cache,
the feed built, and set-up's steps with the state readings that the
comparison needs."""


def read(run):
    return run.setup_s
