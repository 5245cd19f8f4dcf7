"""Device time of the image layers, G's output layer and D's input layer
(1 or 3 channels at the image's size), all passes and all ops under their
``layer.<name>`` scopes, over device busy time (bench/layers.py)."""

from bench import layers


def read(run):
    split = layers.of(run)
    if split is None:
        return None
    seconds = layers.seconds_of(split, layers.image_layers(run.cfg))
    if seconds <= 0:
        return None
    return 100.0 * seconds / run.summary.busy_s
