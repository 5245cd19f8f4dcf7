"""Everything a run feeds the system, made from ``--seed`` alone.

Seeds of any size (the driver's exceed 32 bits) are spread into
independent 32-bit streams by NumPy's ``SeedSequence``, one per use, so
the same seed always gives the same weights, latents and images.
Weights (float32, as the optimizer holds them) and images
are made on the device, each set in one jitted call.
"""

from __future__ import annotations

import numpy as np

__all__ = ["streams", "param_names", "param_shapes", "make_params",
           "make_batches"]

# one stream per use of the seed, by position
USES = ("params", "batches")


def streams(seed: int) -> dict[str, int]:
    """Independent 31-bit seeds for each use of ``seed``."""
    words = np.random.SeedSequence(int(seed)).generate_state(len(USES))
    return {use: int(w) & 0x7FFFFFFF for use, w in zip(USES, words)}


def param_names(role_layers: list[dict], role: str) -> list[tuple[str, str]]:
    """``(weight, bias)`` parameter names of each layer, in the order the
    program reads them: ``proj`` for the generator's dense projection,
    then ``t<i>`` (generator) or ``c<i>`` (discriminator) per conv."""
    prefix = "t" if role == "generator" else "c"
    names, i = [], 0
    for layer in role_layers:
        if layer["kind"] == "dense":
            names.append(("proj_w", "proj_b"))
        else:
            names.append((f"{prefix}{i}_w", f"{prefix}{i}_b"))
            i += 1
    return names


def param_shapes(cfg: dict, role: str) -> dict[str, tuple[tuple, float]]:
    """``name -> (shape, init scale)``; biases have scale 0."""
    dims, out = cfg["dims"], {}
    for layer, (w, b) in zip(cfg[role], param_names(cfg[role], role)):
        if layer["kind"] == "dense":
            out[w] = ((layer["cin"], layer["cout"]),
                      cfg["init"]["proj_scale"])
        else:
            fan_in = layer["k"] ** dims * layer["cin"]
            out[w] = ((layer["k"],) * dims + (layer["cin"], layer["cout"]),
                      fan_in ** -0.5)
        out[b] = ((layer["cout"],), 0.0)
    return out


def make_params(cfg: dict, seed: int):
    """``(g_params, d_params)`` on the device, from one jitted call."""
    import jax
    import jax.numpy as jnp

    shapes = {role: param_shapes(cfg, role)
              for role in ("generator", "discriminator")}

    @jax.jit
    def init(key):
        out = []
        for role in ("generator", "discriminator"):
            key, sub = jax.random.split(key)
            keys = jax.random.split(sub, len(shapes[role]))
            out.append({
                name: (scale * jax.random.normal(k, shape, jnp.float32)
                       if scale else jnp.zeros(shape, jnp.float32))
                for k, (name, (shape, scale))
                in zip(keys, sorted(shapes[role].items()))})
        return tuple(out)

    return init(jax.random.PRNGKey(streams(seed)["params"]))


def make_batches(cfg: dict, seed: int, count: int, batch: int) -> list:
    """``count`` training batches ``{"z", "real"}`` on the device, every
    row different: latents standard normal, "real" images uniform in
    [-1, 1] (there is no dataset in the repository)."""
    import jax
    import jax.numpy as jnp

    image = tuple(cfg["image"])

    @jax.jit
    def make(key):
        out = []
        for key in jax.random.split(key, count):
            kz, kr = jax.random.split(key)
            out.append({
                "z": jax.random.normal(kz, (batch, cfg["z_dim"]),
                                       jnp.float32),
                "real": jax.random.uniform(kr, (batch,) + image,
                                           jnp.float32, -1.0, 1.0)})
        return out

    return make(jax.random.PRNGKey(streams(seed)["batches"]))
