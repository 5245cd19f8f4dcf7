"""Device time of the ops whose path holds ``gan.reconstruct`` (the
reconstructions x_ABA and x_BAB, their MSE, and their backward passes)
over device busy time (bench/drivers/cycle_train_steps.py)."""

from bench.drivers import cycle_train_steps


def read(run):
    split = cycle_train_steps.split(run)
    if split is None or split["reconstruct"] <= 0:
        return None
    return 100.0 * split["reconstruct"] / run.summary.busy_s
