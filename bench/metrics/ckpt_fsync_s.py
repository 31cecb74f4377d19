"""Mean seconds of a window save's ``ckpt.fsync`` span: the fsync of the
checkpoint file and its rename into place."""
from bench.spans import last


def read(run):
    got = last("ckpt.fsync", len(run.save_s))
    return sum(got) / len(got) if got else None
