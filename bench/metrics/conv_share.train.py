"""Device time of the conv-class ops over device busy time; the rest is
glue (pads, interleaves, transposes, elementwise, the update)."""


def read(run):
    if run.summary is None or run.summary.busy_s <= 0:
        return None
    return 100.0 * run.summary.class_seconds("conv") / run.summary.busy_s
