"""Mean seconds of a window save's ``ckpt.pull`` span: the copy of the train
state from the device to the host."""
from bench.spans import last


def read(run):
    got = last("ckpt.pull", len(run.save_s))
    return sum(got) / len(got) if got else None
