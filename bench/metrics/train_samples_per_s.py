"""Samples of the G+D steps completed in the window, over the window."""


def read(run):
    return run.samples / run.window_s
