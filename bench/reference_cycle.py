"""The plain reference of the cross-domain (DiscoGAN) step, in
straightforward ``jax.numpy``.

As ``bench/reference.py`` (whose convolutions, loss and activations it
uses), it shares no code with the program under test and reads only the
configuration file and the benchmark's own weights and images; every
contraction runs at ``Precision.HIGHEST`` unless ``operand_dtype``
rounds its operands first (the lower-precision control).

The step (Kim et al. 2017, Eq. 1-6), with x_AB = G_AB(x_A), x_BA =
G_BA(x_B), x_ABA = G_BA(x_AB) and x_BAB = G_AB(x_BA): D's update on
L_D = BCE(D_A(x_A), 1) + BCE(D_A(x_BA), 0) + BCE(D_B(x_B), 1) +
BCE(D_B(x_AB), 0); then G's update, through the updated D, on L_G =
BCE(D_B(x_AB), 1) + MSE(x_ABA, x_A) + BCE(D_A(x_BA), 1) + MSE(x_BAB,
x_B).  Each loss is a mean over the batch.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.inputs import param_names
from bench.reference import _act, bce, conv, tconv

__all__ = ["generator", "discriminator", "sgd_steps"]


def generator(cfg: dict, g: dict, x, operand_dtype=None):
    """Images ``(B, *image)`` to images: the encoder's convs, then the
    decoder's transposed convs."""
    dims = cfg["dims"]
    for layer, (wn, bn) in zip(cfg["generator"],
                               param_names(cfg["generator"], "generator")):
        op = tconv if layer["kind"] == "tconv" else conv
        x = op(x, g[wn], layer, dims, operand_dtype) + g[bn]
        x = _act(x, layer["act"], cfg["leaky_slope"])
    return x


def discriminator(cfg: dict, d: dict, x, operand_dtype=None):
    """Images to logits ``(B,)``: the last layer's map, averaged."""
    dims = cfg["dims"]
    for layer, (wn, bn) in zip(cfg["discriminator"],
                               param_names(cfg["discriminator"],
                                           "discriminator")):
        x = conv(x, d[wn], layer, dims, operand_dtype) + d[bn]
        x = _act(x, layer["act"], cfg["leaky_slope"])
    return x.reshape(x.shape[0], -1).mean(axis=-1)


def _squares(x, y):
    """The squared error summed over the rows, each row's averaged over
    its pixels (an MSE over the batch times the rows)."""
    e = (x - y).reshape(x.shape[0], -1)
    return jnp.sum(jnp.mean(e * e, axis=-1))


def sgd_steps(cfg: dict, params, batches, *, rows: int | None = None,
              operand_dtype=None):
    """Follow the program's cross-domain SGD step over ``batches``
    (``{"a", "b"}``) from ``params = ((g_ab, g_ba), (d_a, d_b))``.

    Returns the parameters after each step, the first step's gradients
    (same structure) and each step's losses ``{"g_loss", "d_loss"}``.
    ``rows`` takes the losses' mean over the first ``rows`` of each
    batch only (a fault, for the checks)."""
    opt = cfg["optimizer"]
    gs, ds = params

    def gen(g, x):
        return generator(cfg, g, x, operand_dtype)

    def disc(d, x):
        return discriminator(cfg, d, x, operand_dtype)

    def d_part(ds, gs, a, b):
        (d_a, d_b), (g_ab, g_ba) = ds, gs
        x_ab, x_ba = gen(g_ab, a), gen(g_ba, b)
        return (bce(disc(d_a, a), 1.0) + bce(disc(d_a, x_ba), 0.0)
                + bce(disc(d_b, b), 1.0) + bce(disc(d_b, x_ab), 0.0))

    def g_part(gs, ds, a, b):
        (g_ab, g_ba), (d_a, d_b) = gs, ds
        x_ab, x_ba = gen(g_ab, a), gen(g_ba, b)
        return (bce(disc(d_b, x_ab), 1.0) + _squares(gen(g_ba, x_ab), a)
                + bce(disc(d_a, x_ba), 1.0) + _squares(gen(g_ab, x_ba), b))

    d_grad = jax.jit(jax.value_and_grad(d_part))
    g_grad = jax.jit(jax.value_and_grad(g_part))

    def mean_over_rows(fn, params, other, a, b):
        # the losses are sums over the rows: their mean is the sum over n
        n = rows or a.shape[0]
        value, grads = fn(params, other, a[:n], b[:n])
        return float(value) / n, jax.tree.map(lambda x: x / n, grads)

    states, losses, first = [], [], None
    for batch in batches:
        dl, dg = mean_over_rows(d_grad, ds, gs, batch["a"], batch["b"])
        ds = jax.tree.map(lambda p, q: p - opt["d_lr"] * q, ds, dg)
        gl, gg = mean_over_rows(g_grad, gs, ds, batch["a"], batch["b"])
        gs = jax.tree.map(lambda p, q: p - opt["g_lr"] * q, gs, gg)
        if first is None:
            first = (gg, dg)
        states.append((gs, ds))
        losses.append({"g_loss": gl, "d_loss": dl})
    return states, first, losses
