"""Model FLOPs of the window's steps over their device-step time (host clock
around ``block_until_ready``) times the chip's bf16 peak, in %."""


def read(run):
    if not run.step_s or not run.peak_flops_per_s > 0:
        return None
    flops = run.flops_per_step * len(run.step_s)
    return 100.0 * flops / (sum(run.step_s) * run.peak_flops_per_s)
