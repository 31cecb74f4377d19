"""Milliseconds of LOG.io log commits per window step: the ``log.commit``
spans of every thread (the feed's operators, and the acknowledgements the
loop makes after a save) that start in the window, over its steps."""
from bench.spans import recorded, window_start


def read(run):
    t0 = window_start(run)
    if t0 is None:
        return None
    busy = sum(e - s for s, e in recorded("log.commit") if s >= t0)
    return 1000.0 * busy / len(run.step_s)
