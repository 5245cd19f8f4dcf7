"""The least time of the image layers' passes in the traced steps (max of
FLOPs at peak and least bytes at HBM bandwidth, per layer pass, counted
as conv_roofline.train counts them) over the device time of every op
under those layers' scopes, glue (pads, phase interleaves) included:
what a change to those layers has to move (bench/layers.py)."""

from bench import layers


def read(run):
    split = layers.of(run)
    if split is None:
        return None
    names = layers.image_layers(run.cfg)
    seconds = layers.seconds_of(split, names)
    if seconds <= 0:
        return None
    steps = run.traced["samples"] / run.cfg["batch"]
    return 100.0 * layers.least_seconds(run, names) * steps / seconds
