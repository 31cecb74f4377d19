"""Mean milliseconds of a window step's ``feed.get`` span: the train loop
asking the LOG.io feed for its next batch and waiting on the hand-off
queue."""
from bench.spans import last


def read(run):
    got = last("feed.get", len(run.step_s))
    return 1000.0 * sum(got) / len(got) if got else None
