"""Share of the traced window in which no op ran on the device: 1 - (union
of device op intervals) / window, from the profiler trace."""


def read(run):
    if run.summary is None:
        return None
    return 100.0 * run.summary.idle_share
