"""Device time of the generators' encoder (e1-e5), all passes and all ops
under their ``layer.<name>`` scopes, in both generators, over device busy
time (bench/drivers/cycle_train_steps.py, bench/layers.py)."""

from bench import layers, work_cycle
from bench.drivers import cycle_train_steps


def read(run):
    split = cycle_train_steps.split(run)
    if split is None:
        return None
    seconds = layers.seconds_of(split["layers"], work_cycle.encoder(run.cfg))
    if seconds <= 0:
        return None
    return 100.0 * seconds / run.summary.busy_s
