"""The plain reference: the configured GAN in straightforward ``jax.numpy``.

It shares no code with the program under test (``models/``, ``core/``,
``kernels/``, ``program/``, ``train/``, ``serve/``) and reads only the
configuration file, the benchmark's own weights and inputs, and what the
program served.  Every contraction runs at ``Precision.HIGHEST`` (f32),
unless ``operand_dtype`` rounds both operands to a lower type first,
which is how the lower-precision control is computed.

A stride-``s`` convolution is computed by space-to-depth: the padded
input is folded into ``s**d`` phases along the channels and correlated
at stride 1 with the kernel folded the same way.  A transposed
convolution is computed per output phase: each phase is a stride-1
correlation of the compact input with that phase's taps, reversed, and
the phases are interleaved.  Neither form inserts zeros, so the
differentiated reference holds only stride-1 convolutions (the chip's
compiler spends minutes on the zero-insert form at highest precision).
"""

from __future__ import annotations

import functools
import itertools

import jax
import jax.numpy as jnp

from bench.inputs import param_names

__all__ = ["conv", "tconv", "generator", "discriminator", "bce",
           "sgd_steps"]

HIGHEST = jax.lax.Precision.HIGHEST


def _contract(operand_dtype):
    """Keyword arguments of one contraction and the rounding of its
    operands: f32 at HIGHEST, with both operands (and, going back, their
    cotangents) first rounded to ``operand_dtype`` when it is given."""
    if operand_dtype is None:
        return {"precision": HIGHEST}, lambda a: a
    return {"precision": HIGHEST}, _rounder(operand_dtype)


@functools.lru_cache(maxsize=None)
def _rounder(dtype):
    @jax.custom_vjp
    def rnd(x):
        return x.astype(dtype).astype(x.dtype)

    rnd.defvjp(lambda x: (rnd(x), None),
               lambda _, g: (g.astype(dtype).astype(g.dtype),))
    return rnd


def _dn(dims: int):
    sp = "DHW"[-dims:] if dims <= 3 else None
    return ("N" + sp + "C", sp + "IO", "N" + sp + "C")


def _corr(x, w, dims, operand_dtype):
    """Stride-1 VALID correlation, channels last."""
    kw, cast = _contract(operand_dtype)
    return jax.lax.conv_general_dilated(
        cast(x), cast(w), (1,) * dims, "VALID",
        dimension_numbers=_dn(dims), **kw)


def conv(x, w, layer: dict, dims: int, operand_dtype=None):
    """Strided convolution by space-to-depth (no bias)."""
    k, s, p = layer["k"], layer["s"], layer["p"]
    if k % s or (layer["in"] + 2 * p) % s:
        raise ValueError(f"{layer['name']}: space-to-depth needs the "
                         f"stride to divide kernel and padded input")
    b, cin = x.shape[0], x.shape[-1]
    x = jnp.pad(x, [(0, 0)] + [(p, p)] * dims + [(0, 0)])
    n = (layer["in"] + 2 * p) // s
    kk = k // s
    # (B, n, s, n, s, ..., C) -> (B, n..., s..., C) -> (B, n..., s^d C)
    x = x.reshape((b,) + sum(((n, s) for _ in range(dims)), ()) + (cin,))
    order = [0] + [1 + 2 * i for i in range(dims)] + \
        [2 + 2 * i for i in range(dims)] + [1 + 2 * dims]
    x = x.transpose(order).reshape((b,) + (n,) * dims + (s ** dims * cin,))
    cout = w.shape[-1]
    w = w.reshape(sum(((kk, s) for _ in range(dims)), ()) + (cin, cout))
    order = [2 * i for i in range(dims)] + [1 + 2 * i for i in range(dims)] \
        + [2 * dims, 2 * dims + 1]
    w = w.transpose(order).reshape((kk,) * dims + (s ** dims * cin, cout))
    return _corr(x, w, dims, operand_dtype)


def tconv(x, w, layer: dict, dims: int, operand_dtype=None):
    """Transposed convolution (``out[s*i + t - p] += x[i] @ w[t]``) by
    output phases (no bias)."""
    k, s, p, size = layer["k"], layer["s"], layer["p"], layer["in"]
    total = s * (size - 1) + k - 2 * p
    if total % s:
        raise ValueError(f"{layer['name']}: phases of unequal size")
    n = total // s
    phases = []
    for r in itertools.product(range(s), repeat=dims):
        sub, pads = w, []
        for d, rd in enumerate(r):
            c, a = (rd + p) % s, (rd + p) // s
            taps = len(range(c, k, s))
            idx = [slice(None)] * sub.ndim
            idx[d] = slice(c, None, s)
            sub = jnp.flip(sub[tuple(idx)], axis=d)
            lo = taps - 1 - a
            pads.append((lo, n + taps - 1 - lo - size, 0))
        xp = jax.lax.pad(x, jnp.zeros((), x.dtype),
                         [(0, 0, 0)] + pads + [(0, 0, 0)])
        phases.append(_corr(xp, sub, dims, operand_dtype))
    b, cout = x.shape[0], w.shape[-1]
    y = jnp.stack(phases).reshape((s,) * dims + (b,) + (n,) * dims + (cout,))
    order = [dims] + sum(([dims + 1 + i, i] for i in range(dims)), []) + \
        [2 * dims + 1]
    return y.transpose(order).reshape((b,) + (total,) * dims + (cout,))


def _act(x, name: str, slope: float):
    if name == "relu":
        return jnp.maximum(x, 0.0)
    if name == "leaky_relu":
        return jnp.where(x >= 0, x, slope * x)
    if name == "tanh":
        return jnp.tanh(x)
    return x


def generator(cfg: dict, g: dict, z, operand_dtype=None):
    """Latents ``(B, z_dim)`` to samples ``(B, *image)``."""
    dims, x = cfg["dims"], z
    for layer, (wn, bn) in zip(cfg["generator"],
                               param_names(cfg["generator"], "generator")):
        if layer["kind"] == "dense":
            kw, cast = _contract(operand_dtype)
            x = jnp.dot(cast(x), cast(g[wn]), **kw) + g[bn]
            x = x.reshape((x.shape[0],) + tuple(layer["reshape"]))
        else:
            x = tconv(x, g[wn], layer, dims, operand_dtype) + g[bn]
        x = _act(x, layer["act"], cfg["leaky_slope"])
    return x


def discriminator(cfg: dict, d: dict, x, operand_dtype=None):
    """Samples ``(B, *image)`` to logits ``(B,)``: the last layer's map,
    averaged over its positions."""
    dims = cfg["dims"]
    for layer, (wn, bn) in zip(cfg["discriminator"],
                               param_names(cfg["discriminator"],
                                           "discriminator")):
        x = conv(x, d[wn], layer, dims, operand_dtype) + d[bn]
        x = _act(x, layer["act"], cfg["leaky_slope"])
    return x.reshape(x.shape[0], -1).mean(axis=-1)


def bce(logits, target):
    """Binary cross-entropy on logits, summed over the rows."""
    return jnp.sum(jnp.maximum(logits, 0.0) - logits * target
                   + jnp.log1p(jnp.exp(-jnp.abs(logits))))


def sgd_steps(cfg: dict, params, batches, *, rows: int | None = None,
              operand_dtype=None):
    """Follow the program's adversarial SGD step over ``batches``.

    Each step updates D on real and fake, then G through the updated D,
    both by plain SGD with the configured rates.  Returns the parameters
    after each step, the first step's gradients and each step's losses
    ``{"g_loss", "d_loss"}``.  ``rows`` takes the losses' mean over the
    first ``rows`` of each batch only (a fault, for the checks)."""
    opt = cfg["optimizer"]
    g, d = params

    def d_part(d, g, z, real):
        fake = generator(cfg, g, z, operand_dtype)
        return (bce(discriminator(cfg, d, real, operand_dtype), 1.0)
                + bce(discriminator(cfg, d, fake, operand_dtype), 0.0))

    def g_part(g, d, z):
        fake = generator(cfg, g, z, operand_dtype)
        return bce(discriminator(cfg, d, fake, operand_dtype), 1.0)

    d_grad = jax.jit(jax.value_and_grad(d_part))
    g_grad = jax.jit(jax.value_and_grad(g_part))

    def mean_over_rows(fn, params, *arrays):
        # the losses are sums over the rows: their mean is the sum over n
        n = rows or arrays[0].shape[0]
        value, grads = fn(params, *[a[:n] for a in arrays])
        return float(value) / n, jax.tree.map(lambda a: a / n, grads)

    states, losses, first = [], [], None
    for batch in batches:
        z, real = batch["z"], batch["real"]
        dl, dg = mean_over_rows(
            lambda d_, z_, r_: d_grad(d_, g, z_, r_), d, z, real)
        d = jax.tree.map(lambda p, q: p - opt["d_lr"] * q, d, dg)
        gl, gg = mean_over_rows(lambda g_, z_: g_grad(g_, d, z_), g, z)
        g = jax.tree.map(lambda p, q: p - opt["g_lr"] * q, g, gg)
        if first is None:
            first = (gg, dg)
        states.append((g, d))
        losses.append({"g_loss": gl, "d_loss": dl})
    return states, first, losses

