"""Share of the window in which no operation ran on the device, from the
profiler trace of the window, in %."""


def read(run):
    return None if run.trace is None else 100.0 * run.trace["idle_share"]
