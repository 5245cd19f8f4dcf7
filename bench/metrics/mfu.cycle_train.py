"""Useful FLOPs of the cross-domain steps completed in the traced part of
the window (counted from layer shapes per pair of images,
bench/work_cycle.py) over that part's length in the profiler trace (the
``bench.window`` span), as a share of the chip's peak at the
configuration's compute precision."""

from bench import work, work_cycle


def read(run):
    if run.summary is None or not run.traced:
        return None
    per_pair = work.flops(run.cfg, work_cycle.step_passes(run.cfg))
    rate = per_pair * run.traced["samples"] / run.summary.window_s
    return 100.0 * rate / run.peak_flops
