"""Milliseconds per step of the window spent outside the device step and the
save: waiting for the LOG.io feed and the loop's own host work."""


def read(run):
    if not run.step_s:
        return None
    rest = run.window_s - sum(run.step_s) - sum(run.save_s)
    return 1000.0 * rest / len(run.step_s)
