"""Executable GAN models (the paper's Table I workloads) on GANAX ops.

Every (transposed) convolution goes through the unified dispatch layer
(`core.dataflow`): generators run the paper's MIMD-SIMD dataflow for
their transposed convs, discriminators run plain convolutions through the
same unified op (the SIMD mode).  The execution path — Pallas kernel on
TPU, interpret-mode kernel, pure-JAX polyphase, or the zero-insertion
baseline — is selected by a single :class:`~repro.core.dataflow
.DataflowPolicy`: set ``GanConfig.backend`` explicitly, or leave it
``None`` and the legacy ``dataflow``/``use_pallas`` fields are interpreted
by ``DataflowPolicy.from_legacy`` (their meaning lives in
``core/dataflow.py``, not here; they are deprecated — ``backend=`` is
the supported knob).  All paths are differentiable — the dispatch
layer's custom VJP re-enters the unified kernel for the backward pass —
so Pallas-backed configs train end-to-end.

Bias and activation are **fused epilogues**: every conv layer passes an
:class:`~repro.core.dataflow.Epilogue` (and its bias vector) into the
unified op instead of applying ``+ b`` / relu / tanh / leaky-relu as
separate post-ops, so the kernel backends never round-trip the raw
accumulator through HBM between a layer and its activation.
:func:`generator_epilogues` / :func:`discriminator_epilogues` are the
single source of truth for the per-layer specs — the autotuner's plan
keys (``repro.tune.zoo``) are built from the same helpers, so
``backend="auto"`` tunes exactly the fused op the model dispatches.

Execution is **ahead-of-time compiled**: ``generator_apply`` /
``discriminator_apply`` are thin legacy-compatible wrappers over cached
:class:`repro.program.Program` objects — the config → policy →
epilogue → plan walk runs once per (config, policy) at program build,
and the per-call path just replays the frozen
:class:`~repro.program.LayerExec` records.  New code should build a
``Program`` directly (``Program.build(cfg, batch, role)``); these
wrappers keep the historic signatures working.

These power the GAN training examples, the serving engine
(`serve.gan`), and the wall-clock microbenchmarks (GANAX dataflow vs
zero-insertion baseline on identical topologies).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Sequence

import jax
import jax.numpy as jnp

from repro.configs.gans import GAN_MODELS
from repro.core.analytical import ConvLayer
from repro.core.dataflow import DataflowPolicy, Epilogue
from repro.models.common import PSpec, init_params

__all__ = ["GanConfig", "generator_specs", "discriminator_specs",
           "init_gan", "generator_apply", "discriminator_apply",
           "generator_epilogues", "discriminator_epilogues",
           "bce_with_logits", "gan_losses"]

# The discriminator's LeakyReLU slope (DCGAN convention, used by every
# Table-I discriminator).
LEAKY_SLOPE = 0.2


@dataclasses.dataclass(frozen=True)
class GanConfig:
    name: str
    z_dim: int = 100
    dataflow: str = "ganax"     # legacy: "ganax" | "zero_insert"
    use_pallas: bool = False    # legacy: Pallas kernel vs pure-JAX
    channel_scale: float = 1.0  # shrink channels for CPU-sized runs
    # Explicit DataflowPolicy backend override: a registered backend
    # name, the "pallas" preference, or "auto" (measured per-layer plans
    # from the repro.tune planner, heuristic fallback on a plan miss).
    backend: str | None = None
    # (data, model) device mesh programs built from this config freeze
    # by default (see ProgramSpec.build); None = single-device.  A
    # tuple so the config stays hashable for the program cache.
    mesh: tuple[int, int] | None = None
    # Storage precision of programs built from this config: "float32",
    # "bfloat16", or "float16" (aliases f32/bf16/f16 accepted and
    # canonicalized, keeping the config hashable).  Accumulation is
    # always float32 — see repro.quant.  Parameters themselves stay in
    # whatever dtype the optimizer holds (f32 from init_gan): programs
    # cast at use, so mixed-precision training needs no config beyond
    # this field.
    dtype: str = "float32"

    def __post_init__(self):
        from repro.quant.precision import canonical_dtype
        object.__setattr__(self, "dtype", canonical_dtype(self.dtype))

    @property
    def policy(self) -> DataflowPolicy:
        if self.backend is not None:
            return DataflowPolicy(backend=self.backend)
        return DataflowPolicy.from_legacy(dataflow=self.dataflow,
                                          use_pallas=self.use_pallas)

    @property
    def input_kind(self) -> str:
        """What the generator takes, fixed by the model: ``"latent"`` (z
        of ``z_dim``, projected onto the first tconv's input) or
        ``"image"`` (an encoder-decoder, whose first layer is a conv)."""
        return "latent" if GAN_MODELS[self.name][0][0].transposed \
            else "image"

    @property
    def input_shape(self) -> tuple[int, ...]:
        """The generator's input per sample: ``(z_dim,)`` or the first
        layer's ``(*spatial, channels)``."""
        if self.input_kind == "latent":
            return (self.z_dim,)
        first = self.layers[0][0]
        return tuple(first.in_spatial) + (first.cin,)

    @property
    def layers(self) -> tuple[list[ConvLayer], list[ConvLayer]]:
        g, d = GAN_MODELS[self.name]
        if self.channel_scale != 1.0:
            def shrink(l: ConvLayer) -> ConvLayer:
                c_in = max(1, int(l.cin * self.channel_scale)) \
                    if l.cin > 3 else l.cin
                c_out = max(1, int(l.cout * self.channel_scale)) \
                    if l.cout > 3 else l.cout
                return dataclasses.replace(l, cin=c_in, cout=c_out)
            g = [shrink(l) for l in g]
            d = [shrink(l) for l in d]
        return g, d


def _conv_specs(layers: Sequence[ConvLayer], prefix: str) -> dict:
    specs = {}
    for i, l in enumerate(layers):
        fan_in = int(jnp.prod(jnp.asarray(l.kernel))) * l.cin
        specs[f"{prefix}{i}_w"] = PSpec(
            tuple(l.kernel) + (l.cin, l.cout),
            (None,) * len(l.kernel) + ("conv_in", "conv_out"),
            scale=fan_in ** -0.5)   # no batch-norm → fan-in init
        specs[f"{prefix}{i}_b"] = PSpec((l.cout,), ("conv_out",),
                                        init="zeros")
    return specs


def generator_specs(cfg: GanConfig) -> dict:
    """The z-projection (latent input only) and ``t<i>`` per layer."""
    g_layers, _ = cfg.layers
    specs = {}
    if cfg.input_kind == "latent":
        first = g_layers[0]
        proj_dim = int(jnp.prod(jnp.asarray(first.in_spatial))) * first.cin
        specs = {"proj_w": PSpec((cfg.z_dim, proj_dim), (None, "mlp"),
                                 scale=0.02),
                 "proj_b": PSpec((proj_dim,), ("mlp",), init="zeros")}
    specs.update(_conv_specs(g_layers, "t"))
    return specs


def discriminator_specs(cfg: GanConfig) -> dict:
    _, d_layers = cfg.layers
    return _conv_specs(d_layers, "c")


def init_gan(cfg: GanConfig, key: jax.Array):
    kg, kd = jax.random.split(key)
    return (init_params(kg, generator_specs(cfg)),
            init_params(kd, discriminator_specs(cfg)))


def generator_epilogues(g_layers: Sequence[ConvLayer]) -> list[Epilogue]:
    """Per-layer fused epilogues of a Table-I generator: bias + tanh on
    the image-producing last layer; bias + LeakyReLU on an encoder's
    convs (DiscoGAN's, as its paper's encoder); bias + ReLU on every
    other (tconv) layer."""
    last = len(g_layers) - 1
    return [Epilogue(bias=True, activation="tanh")
            if i == last else
            Epilogue(bias=True, activation="relu") if l.transposed else
            Epilogue(bias=True, activation="leaky_relu",
                     leaky_slope=LEAKY_SLOPE)
            for i, l in enumerate(g_layers)]


def discriminator_epilogues(d_layers: Sequence[ConvLayer]
                            ) -> list[Epilogue]:
    """Per-layer fused epilogues of a Table-I discriminator: bias +
    LeakyReLU on every hidden layer, bias only on the logits layer."""
    last = len(d_layers) - 1
    return [Epilogue(bias=True,
                     activation="none" if i == last else "leaky_relu",
                     leaky_slope=LEAKY_SLOPE)
            for i in range(len(d_layers))]


@functools.lru_cache(maxsize=64)
def _cached_program(cfg: GanConfig, policy: DataflowPolicy, role: str,
                    batch: int):
    """One frozen Program per (config, policy, role) — the legacy apply
    functions are thin wrappers over these.  ``batch`` only matters for
    ``backend="auto"`` plan keys; concrete policies resolve
    batch-independently, so they cache under batch=0."""
    from repro.program import Program
    return Program.build(cfg, max(batch, 1), role, policy=policy,
                         differentiable=policy.differentiable)


def _program_for(cfg: GanConfig, policy: DataflowPolicy | None,
                 role: str, batch: int):
    policy = policy or cfg.policy
    if policy.backend == "auto":
        # auto resolution is a planner snapshot: rebuild per call (cheap
        # — lookups only, never measures) so fresh plans take effect,
        # exactly like the per-dispatch consult this API replaces
        from repro.program import Program
        return Program.build(cfg, batch, role, policy=policy,
                             differentiable=policy.differentiable)
    return _cached_program(cfg, policy, role, 0)


def generator_apply(params, z, cfg: GanConfig,
                    policy: DataflowPolicy | None = None):
    """z (B, z_dim), or an image for an image-input generator
    (``cfg.input_kind``), → image (B, *spatial, C).

    Legacy-compatible wrapper over a cached ahead-of-time
    :class:`repro.program.Program`: the layer walk (config → policy →
    epilogues → plans) runs once at program build, not per call.  Every
    conv layer's bias+activation runs as a fused epilogue inside the
    unified op (only a latent generator's z-projection MLP keeps its own
    bias/ReLU)."""
    prog = _program_for(cfg, policy, "generator", int(z.shape[0]))
    return prog.forward(params, z)


def discriminator_apply(params, img, cfg: GanConfig,
                        policy: DataflowPolicy | None = None):
    """img (B, *spatial, C) → logits (B,).  Same program-backed wrapper
    as :func:`generator_apply`; bias + LeakyReLU run as fused epilogues
    inside the unified conv op."""
    prog = _program_for(cfg, policy, "discriminator", int(img.shape[0]))
    return prog.forward(params, img)


def bce_with_logits(logits, target):
    """Numerically stable binary cross-entropy on logits."""
    return jnp.mean(
        jnp.maximum(logits, 0) - logits * target +
        jnp.log1p(jnp.exp(-jnp.abs(logits))))


def gan_losses(g_params, d_params, z, real, cfg: GanConfig,
               programs=None):
    """Non-saturating GAN losses (generator, discriminator).

    ``programs`` — an optional ``(generator Program, discriminator
    Program)`` pair — skips even the cached-program lookup: the train
    loop builds both once and threads them here."""
    if programs is not None:
        g_prog, d_prog = programs
        fake = g_prog.forward(g_params, z)
        d_fake = d_prog.forward(d_params, fake)
        d_real = d_prog.forward(d_params, real)
    else:
        fake = generator_apply(g_params, z, cfg)
        d_fake = discriminator_apply(d_params, fake, cfg)
        d_real = discriminator_apply(d_params, real, cfg)
    d_loss = bce_with_logits(d_real, 1.0) + bce_with_logits(d_fake, 0.0)
    g_loss = bce_with_logits(d_fake, 1.0)
    return g_loss, d_loss, fake
