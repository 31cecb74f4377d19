"""Mean seconds of a checkpoint save in the window (``CheckpointStore.save``
timed by ``run_training``: state pulled to the host, pickled, fsynced)."""


def read(run):
    return sum(run.save_s) / len(run.save_s) if run.save_s else None
