"""Fused bias+activation epilogues in the unified dataflow dispatch.

Pins the PR-4 contract: (1) fused and unfused formulations agree —
forward and ``jax.grad`` — on every backend × activation × stride, for
2-D and volumetric ops; (2) the Table-I models issue **zero**
out-of-kernel ``+ b`` / activation ops on the fused kernel path (the
epilogue lives inside the custom-VJP-wrapped kernel call); (3) the
legacy ``GanConfig`` flags warn, ``backend=`` does not.
"""

import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.compat import CUSTOM_VJP_CALL
from repro.configs.gans import GAN_MODELS
from repro.core.dataflow import (ACTIVATIONS, DataflowPolicy, Epilogue,
                                 conv, tconv)
from repro.models.gan import (GanConfig, discriminator_apply,
                              discriminator_epilogues, generator_apply,
                              generator_epilogues, init_gan)

BACKENDS = ["zero-insert", "polyphase", "pallas-interpret", "pallas"]

# (x_spatial, kernel, cin, cout) per stride — tiny shapes: the sweep
# below multiplies out to backends × activations × strides × {tconv,
# conv} × {2-D, 3-D}, each with a gradient check.
SPATIAL_2D = {1: ((5, 5), (3, 3)), 2: ((4, 4), (4, 4)),
              3: ((3, 3), (3, 3))}
SPATIAL_3D = {1: ((3, 3, 3), (2, 2, 2)), 2: ((2, 3, 2), (3, 3, 3)),
              3: ((2, 2, 2), (2, 2, 2))}   # kernel < stride: empty phases


def _case(nd, stride, cin=2, cout=3, seed=0):
    sp, k = (SPATIAL_2D if nd == 2 else SPATIAL_3D)[stride]
    rng = np.random.default_rng(seed + 31 * stride + 7 * nd)
    x = jnp.asarray(rng.normal(size=(1, *sp, cin)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(*k, cin, cout)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(cout,)), jnp.float32)
    s = (stride,) * nd
    p = tuple(min(1, kk - 1) for kk in k)
    return x, w, b, s, p


def _unfused(op, x, w, b, s, p, policy, ep):
    """The reference formulation: bare op, then the epilogue as
    out-of-op XLA post-ops."""
    return ep.apply(op(x, w, s, p, policy=policy), b if ep.bias else None)


def _assert_fwd_and_grad_parity(op, x, w, b, s, p, policy, ep, tol=1e-4):
    fused = op(x, w, s, p, policy=policy, bias=b if ep.bias else None,
               epilogue=ep)
    ref = _unfused(op, x, w, b, s, p,
                   DataflowPolicy(backend="zero-insert"), ep)
    np.testing.assert_allclose(np.asarray(fused), np.asarray(ref),
                               atol=tol, rtol=tol)
    cot = jnp.asarray(np.random.default_rng(3).normal(size=ref.shape),
                      jnp.float32)
    argnums = (0, 1, 2) if ep.bias else (0, 1)

    def fused_loss(x, w, b):
        return jnp.sum(op(x, w, s, p, policy=policy,
                          bias=b if ep.bias else None, epilogue=ep) * cot)

    def ref_loss(x, w, b):
        return jnp.sum(_unfused(
            op, x, w, b, s, p,
            DataflowPolicy(backend="zero-insert"), ep) * cot)

    got = jax.grad(fused_loss, argnums)(x, w, b)
    want = jax.grad(ref_loss, argnums)(x, w, b)
    for g_, r_, name in zip(got, want, ("dx", "dw", "db")):
        np.testing.assert_allclose(np.asarray(g_), np.asarray(r_),
                                   atol=tol, rtol=tol, err_msg=name)


@pytest.mark.parametrize("activation", ACTIVATIONS)
@pytest.mark.parametrize("backend", BACKENDS)
def test_fused_parity_2d(backend, activation):
    """Fused == unfused (forward and grad) for 2-D tconv and conv on
    every backend, strides {1, 2, 3}."""
    policy = DataflowPolicy(backend=backend)
    ep = Epilogue(bias=True, activation=activation)
    for stride in (1, 2, 3):
        x, w, b, s, p = _case(2, stride)
        _assert_fwd_and_grad_parity(tconv, x, w, b, s, p, policy, ep)
        _assert_fwd_and_grad_parity(conv, x, w, b, s, p, policy, ep)


@pytest.mark.parametrize("activation", ACTIVATIONS)
@pytest.mark.parametrize("backend", BACKENDS)
def test_fused_parity_3d(backend, activation):
    """Volumetric twin of the 2-D sweep (the 3D-GAN path), including the
    kernel<stride geometry whose empty phases are pure-epilogue
    outputs ``act(0 + b)``."""
    policy = DataflowPolicy(backend=backend)
    ep = Epilogue(bias=True, activation=activation)
    for stride in (1, 2, 3):
        x, w, b, s, p = _case(3, stride)
        _assert_fwd_and_grad_parity(tconv, x, w, b, s, p, policy, ep)
        _assert_fwd_and_grad_parity(conv, x, w, b, s, p, policy, ep)


def test_activation_only_epilogue_no_bias():
    """bias=False epilogues thread a None bias through the fused custom
    VJP (the cotangent structure must match)."""
    policy = DataflowPolicy(backend="pallas-interpret")
    ep = Epilogue(activation="leaky_relu", leaky_slope=0.1)
    x, w, b, s, p = _case(2, 2)
    _assert_fwd_and_grad_parity(tconv, x, w, b, s, p, policy, ep)


def test_epilogue_validation():
    with pytest.raises(ValueError, match="activation"):
        Epilogue(activation="gelu")
    # grad_from_output recovers the leaky derivative from the output's
    # sign, which needs a sign-preserving (non-negative) slope
    with pytest.raises(ValueError, match="leaky_slope"):
        Epilogue(activation="leaky_relu", leaky_slope=-0.1)
    x, w, b, s, p = _case(2, 2)
    with pytest.raises(ValueError, match="bias"):
        tconv(x, w, s, p, epilogue=Epilogue(bias=True))   # missing array
    with pytest.raises(ValueError, match="bias"):
        tconv(x, w, s, p, bias=b, epilogue=Epilogue(bias=False))
    with pytest.raises(ValueError, match="cout"):
        tconv(x, w, s, p, bias=jnp.zeros((7,)),
              epilogue=Epilogue(bias=True))
    # a bare bias= array means a fused bias add — at the dispatch layer
    # and at the ops-layer kernel entry points alike
    out = tconv(x, w, s, p, bias=b)
    ref = tconv(x, w, s, p) + b
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)
    from repro.kernels.ops import ganax_conv_transpose
    out = ganax_conv_transpose(x, w, s, p, interpret=True, bias=b)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-4, rtol=1e-4)


def test_leaky_slope_canonicalized():
    """Specs computing the same function hash equal (plan-key dedup):
    the slope only survives for leaky_relu."""
    assert Epilogue(activation="relu", leaky_slope=0.7) == \
        Epilogue(activation="relu")
    assert Epilogue(activation="leaky_relu", leaky_slope=0.3) != \
        Epilogue(activation="leaky_relu")
    assert Epilogue().is_identity
    assert not Epilogue(bias=True).is_identity


# ---------------------------------------------------------------------------
# Table-I acceptance: zero out-of-kernel epilogue ops + model-level parity.
# ---------------------------------------------------------------------------

def _top_level_prims(fn, *args) -> list[str]:
    return [e.primitive.name
            for e in jax.make_jaxpr(fn)(*args).jaxpr.eqns]


@pytest.mark.parametrize("name", sorted(GAN_MODELS))
def test_no_out_of_kernel_epilogue_ops(name):
    """On the fused kernel path every conv layer traces to a single
    custom-VJP call: no top-level ``add`` (bias) and no top-level
    tanh/max/select_n (activations) besides a latent generator's
    z-projection MLP, for every Table-I model (DiscoGAN's image-input
    generator has no projection)."""
    cfg = GanConfig(name=name, channel_scale=0.03125,
                    backend="pallas-interpret")
    g, d = init_gan(cfg, jax.random.PRNGKey(0))
    g_layers, d_layers = cfg.layers
    z = jnp.zeros((1, *cfg.input_shape))
    proj = int(cfg.input_kind == "latent")

    prims = _top_level_prims(lambda g, z: generator_apply(g, z, cfg), g, z)
    activationish = {"tanh", "max", "select_n", "logistic"}
    assert not activationish & set(prims), prims
    assert prims.count("add") == proj, prims         # the projection bias
    assert prims.count("custom_jvp_call") == proj    # the projection relu
    assert prims.count(CUSTOM_VJP_CALL) == len(g_layers)

    img_sp = tuple(d_layers[0].in_spatial)
    img = jnp.zeros((1, *img_sp, d_layers[0].cin))
    prims = _top_level_prims(
        lambda d, img: discriminator_apply(d, img, cfg), d, img)
    assert "add" not in prims, prims
    assert not activationish & set(prims), prims
    assert prims.count(CUSTOM_VJP_CALL) == len(d_layers)


@pytest.mark.parametrize("name", sorted(GAN_MODELS))
def test_model_fused_matches_unfused(name):
    """Model-level parity for every Table-I model: the fused generator
    and discriminator match a manually unfused reference (bare ops +
    post-ops) to fp32 tolerance."""
    cfg = GanConfig(name=name, channel_scale=0.0625, backend="polyphase")
    g, d = init_gan(cfg, jax.random.PRNGKey(0))
    g_layers, d_layers = cfg.layers
    z = jax.random.normal(jax.random.PRNGKey(1), (2, *cfg.input_shape))

    from repro.core.dataflow import conv as df_conv
    from repro.core.dataflow import tconv as df_tconv

    def unfused_generator(params):
        policy = cfg.policy
        first = g_layers[0]
        x = z
        if cfg.input_kind == "latent":
            x = z @ params["proj_w"] + params["proj_b"]
            x = x.reshape((z.shape[0],) + tuple(first.in_spatial)
                          + (first.cin,))
            x = jax.nn.relu(x)
        for i, (l, ep) in enumerate(zip(g_layers,
                                        generator_epilogues(g_layers))):
            op = df_tconv if l.transposed else df_conv
            x = ep.apply(op(x, params[f"t{i}_w"], l.strides, l.paddings,
                            policy=policy), params[f"t{i}_b"])
        return x

    fused = generator_apply(g, z, cfg)
    np.testing.assert_allclose(np.asarray(fused),
                               np.asarray(unfused_generator(g)),
                               atol=2e-4, rtol=2e-4)

    def unfused_discriminator(params, img):
        policy = cfg.policy
        x = img
        for i, (l, ep) in enumerate(zip(
                d_layers, discriminator_epilogues(d_layers))):
            x = ep.apply(df_conv(x, params[f"c{i}_w"], l.strides,
                                 l.paddings, policy=policy),
                         params[f"c{i}_b"])
        return x.reshape(img.shape[0], -1).mean(axis=-1)

    img = fused
    np.testing.assert_allclose(
        np.asarray(discriminator_apply(d, img, cfg)),
        np.asarray(unfused_discriminator(d, img)), atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize("backend", BACKENDS)
def test_generator_fused_grad_parity_across_backends(backend):
    """Fused end-to-end generator gradients agree across every backend
    (the kernel backends differentiate through the fused custom VJP)."""
    cfg = GanConfig(name="dcgan", channel_scale=0.03125, backend=backend)
    cfg_ref = GanConfig(name="dcgan", channel_scale=0.03125,
                        backend="zero-insert")
    g, _ = init_gan(cfg, jax.random.PRNGKey(0))
    z = jax.random.normal(jax.random.PRNGKey(1), (1, cfg.z_dim))

    def loss(g, cfg):
        return jnp.sum(generator_apply(g, z, cfg) ** 2)

    got = jax.grad(loss)(g, cfg)
    want = jax.grad(loss)(g, cfg_ref)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-3, rtol=2e-3)


# ---------------------------------------------------------------------------
# Legacy-flag deprecation path.
# ---------------------------------------------------------------------------

def test_legacy_gan_config_flags_warn():
    with pytest.warns(DeprecationWarning, match="backend"):
        GanConfig(name="dcgan", use_pallas=True).policy
    with pytest.warns(DeprecationWarning, match="deprecated"):
        DataflowPolicy.from_legacy(dataflow="zero_insert")


def test_supported_knobs_do_not_warn():
    with warnings.catch_warnings():
        warnings.simplefilter("error", DeprecationWarning)
        assert GanConfig(name="dcgan").policy.backend == "polyphase"
        assert GanConfig(name="dcgan", backend="auto").policy.backend \
            == "auto"
