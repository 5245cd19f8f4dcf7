"""Useful FLOPs of the G+D steps completed in the traced part of the
window (counted from layer shapes, bench/work.py) over that part's length
in the profiler trace (the ``bench.window`` span, which ends once the
last traced step is done), as a share of the chip's peak at the
configuration's compute precision."""

from bench import work


def read(run):
    if run.summary is None or not run.traced:
        return None
    per_sample = work.flops(run.cfg, work.step_passes(run.cfg))
    rate = per_sample * run.traced["samples"] / run.summary.window_s
    return 100.0 * rate / run.peak_flops
