"""Mean seconds of a window save's ``ckpt.write`` span: the pickle of the host
copy into the checkpoint file and its flush."""
from bench.spans import last


def read(run):
    got = last("ckpt.write", len(run.save_s))
    return sum(got) / len(got) if got else None
