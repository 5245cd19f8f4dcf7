"""Useful work of a configuration, counted from its layer shapes alone.

A transposed convolution counts only its useful multiply-accumulates:
each output phase of a stride-``s`` layer is a dense correlation of the
compact input with the kernel taps of that phase, so the inserted zeros
of the zero-insert form never enter the count.  A convolution counts
its output pixels times its taps.  The count therefore reads the same
whichever backend runs the layer (zero-insert, polyphase or a kernel):
nothing here knows of backends.

Minimal bytes of a layer pass are its input, weights and output at the
storage dtype, read or written once.  A roofline's least time is
``max(flops / peak, bytes / bandwidth)``, summed over layer passes.
"""

from __future__ import annotations

import math

__all__ = ["out_size", "layer_macs", "layer_bytes", "step_passes",
           "flops", "least_seconds", "layer_count_check"]

ITEMSIZE = {"float32": 4, "bfloat16": 2, "float16": 2}


def out_size(layer: dict) -> int:
    """Output size along one spatial dimension (1 for a dense layer)."""
    if layer["kind"] == "dense":
        return 1
    n, k, s, p = layer["in"], layer["k"], layer["s"], layer["p"]
    if layer["kind"] == "tconv":
        return s * (n - 1) + k - 2 * p
    return (n + 2 * p - k) // s + 1


def _tconv_positions(layer: dict) -> int:
    """Sum over the output phases of one dimension of (phase outputs x
    phase taps): the useful products a stride-``s`` transposed conv
    makes along that dimension."""
    k, s, p = layer["k"], layer["s"], layer["p"]
    total_out = out_size(layer)
    count = 0
    for phase in range(s):
        taps = len(range((phase + p) % s, k, s))
        outputs = max(0, -(-(total_out - phase) // s))
        count += outputs * taps
    return count


def layer_macs(layer: dict, dims: int) -> int:
    """Useful multiply-accumulates of one forward pass, per sample."""
    cin, cout = layer["cin"], layer["cout"]
    if layer["kind"] == "dense":
        return cin * cout
    if layer["kind"] == "tconv":
        return _tconv_positions(layer) ** dims * cin * cout
    return out_size(layer) ** dims * layer["k"] ** dims * cin * cout


def layer_bytes(layer: dict, dims: int, batch: int, itemsize: int) -> int:
    """Least bytes one pass of the layer moves: input, weights (and bias)
    and output, once each.  The same for the forward pass, the weight
    gradient (input and output cotangent in, weight gradient out) and
    the input gradient (output cotangent and weights in, input cotangent
    out)."""
    if layer["kind"] == "dense":
        x, y = layer["cin"], layer["cout"]
        w = layer["cin"] * layer["cout"] + layer["cout"]
    else:
        x = layer["in"] ** dims * layer["cin"]
        y = out_size(layer) ** dims * layer["cout"]
        w = layer["k"] ** dims * layer["cin"] * layer["cout"] + layer["cout"]
    return (batch * (x + y) + w) * itemsize


def step_passes(cfg: dict) -> list[tuple[dict, str, int]]:
    """``(layer, pass, count)`` of one adversarial G+D step, per sample.

    D update: G forward; D forward on real and on fake; D's weight
    gradients on both, and its input gradients on both except at its
    first layer, whose input nothing differentiates.  G update: D
    forward on fake with the new D and D's input gradients down to the
    image; G's weight and input gradients, except the input gradient of
    the projection (the latent is not trained).  G's second forward is a
    recomputation and does not count.  Together about 3 G + 8 D forward
    equivalents."""
    passes = []
    for i, layer in enumerate(cfg["generator"]):
        passes += [(layer, "fwd", 1), (layer, "wgrad", 1)]
        if i > 0:
            passes.append((layer, "igrad", 1))
    for i, layer in enumerate(cfg["discriminator"]):
        passes += [(layer, "fwd", 3), (layer, "wgrad", 2),
                   (layer, "igrad", 1 if i == 0 else 3)]
    return passes


def flops(cfg: dict, passes) -> int:
    """Useful FLOPs (two per multiply-accumulate) per sample."""
    return sum(2 * layer_macs(layer, cfg["dims"]) * count
               for layer, _, count in passes)


def least_seconds(cfg: dict, passes, batch: int, peak_flops: float,
                  peak_bytes_per_s: float) -> float:
    """The least time the chip could take for ``passes`` at ``batch``:
    each layer pass bounded by its FLOPs at the peak rate or its bytes
    at the peak bandwidth, whichever is longer."""
    itemsize = ITEMSIZE[cfg["dtype"]]
    total = 0.0
    for layer, _, count in passes:
        f = 2 * layer_macs(layer, cfg["dims"]) * batch
        b = layer_bytes(layer, cfg["dims"], batch, itemsize)
        total += count * max(f / peak_flops, b / peak_bytes_per_s)
    return total


def layer_count_check(cfg: dict) -> None:
    """Refuse a layer list whose shapes do not chain."""
    for role in ("generator", "discriminator"):
        layers = cfg[role]
        for a, b in zip(layers, layers[1:]):
            if a["kind"] == "dense":
                size = a["reshape"][0]
                ch = a["reshape"][-1]
                if a["cout"] != math.prod(a["reshape"]):
                    raise ValueError(f"{a['name']}: reshape does not hold "
                                     f"{a['cout']} outputs")
            else:
                size, ch = out_size(a), a["cout"]
            if (size, ch) != (b["in"], b["cin"]):
                raise ValueError(f"{a['name']} -> {b['name']}: "
                                 f"{size}/{ch} feeds {b['in']}/{b['cin']}")
