"""Useful work of the cross-domain (DiscoGAN) step, per pair of images
(one from domain A, one from B), counted from layer shapes with
``bench/work.py``'s functions and conventions.

One step: D's update translates each domain once (x_AB, x_BA); D_A and
D_B each judge a real and a fake batch, with weight gradients on all
four and input gradients down to d2 (nothing differentiates d1's input,
the data or a fixed fake).  G's update translates again (a
recomputation, which does not count, as G's second forward in
``work.step_passes``), reconstructs (x_ABA, x_BAB: two more G
forwards), and judges the translations with the updated D_B and D_A,
whose input gradients run down to the image.  Each of G's four calls
has weight gradients and input gradients, except the first layer's in
the two translations, whose input is data.
"""

from __future__ import annotations

__all__ = ["step_passes", "encoder"]


def step_passes(cfg: dict) -> list[tuple[dict, str, int]]:
    """``(layer, pass, count)`` of one cross-domain step, per pair.
    G: fwd 4, wgrad 4, igrad 4 (2 at the first layer); D: fwd 6,
    wgrad 4, igrad 6 (2 at d1)."""
    passes = []
    for role, fwd, wgrad, igrad, first_igrad in (
            ("generator", 4, 4, 4, 2), ("discriminator", 6, 4, 6, 2)):
        for i, layer in enumerate(cfg[role]):
            passes += [(layer, "fwd", fwd), (layer, "wgrad", wgrad),
                       (layer, "igrad", first_igrad if i == 0 else igrad)]
    return passes


def encoder(cfg: dict) -> tuple[str, ...]:
    """The generator's encoder: its conv layers (e1-e5)."""
    return tuple(layer["name"] for layer in cfg["generator"]
                 if layer["kind"] == "conv")
