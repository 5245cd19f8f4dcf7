"""The least time the chip could take for the useful conv and projection
work of the traced steps (max of FLOPs at peak and least bytes at HBM
bandwidth, per layer pass, bench/work.py) over the device time of every op
that holds a convolution (a dot included) or a Pallas kernel."""

from bench import work


def read(run):
    if run.summary is None or not run.traced:
        return None
    conv = run.summary.class_seconds("conv")
    if conv <= 0:
        return None
    batch = run.cfg["batch"]
    per_step = work.least_seconds(run.cfg, work.step_passes(run.cfg), batch,
                                  run.peak_flops,
                                  run.peaks["hbm_bytes_per_s"])
    return 100.0 * per_step * run.traced["samples"] / batch / conv
