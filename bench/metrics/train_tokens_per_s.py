"""Tokens trained in the window over the window's wall time: the steps, the
waits for the feed and the final checkpoint save."""


def read(run):
    return len(run.step_s) * run.tokens_per_step / run.window_s
