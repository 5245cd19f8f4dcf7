"""Executable GAN models on the GANAX ops: shapes, dataflow equivalence,
trainability."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.gans import GAN_MODELS
from repro.models.gan import (GanConfig, gan_losses, generator_apply,
                              init_gan)


@pytest.mark.parametrize("name", sorted(GAN_MODELS))
def test_generator_shapes_and_losses(name):
    cfg = GanConfig(name=name, channel_scale=0.0625)
    g, d = init_gan(cfg, jax.random.PRNGKey(0))
    z = jax.random.normal(jax.random.PRNGKey(1), (2, *cfg.input_shape))
    img = generator_apply(g, z, cfg)
    nd = len(cfg.layers[0][-1].kernel)
    assert img.ndim == nd + 2 and img.shape[0] == 2
    g_loss, d_loss, fake = gan_losses(g, d, z, jnp.zeros_like(img), cfg)
    assert np.isfinite(float(g_loss)) and np.isfinite(float(d_loss))


def test_dataflow_equivalence():
    """GANAX and zero-insertion dataflows are numerically identical for
    the same weights (the optimization is exact)."""
    for name in ("dcgan", "magan"):
        cfg_g = GanConfig(name=name, channel_scale=0.0625,
                          dataflow="ganax")
        cfg_z = GanConfig(name=name, channel_scale=0.0625,
                          dataflow="zero_insert")
        g, _ = init_gan(cfg_g, jax.random.PRNGKey(0))
        z = jax.random.normal(jax.random.PRNGKey(1), (1, cfg_g.z_dim))
        a = generator_apply(g, z, cfg_g)
        b = generator_apply(g, z, cfg_z)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-4, rtol=2e-4)


def test_pallas_backed_generator_matches():
    cfg = GanConfig(name="dcgan", channel_scale=0.03125, use_pallas=True)
    cfg_ref = GanConfig(name="dcgan", channel_scale=0.03125,
                        use_pallas=False)
    g, _ = init_gan(cfg, jax.random.PRNGKey(0))
    z = jax.random.normal(jax.random.PRNGKey(1), (1, cfg.z_dim))
    a = generator_apply(g, z, cfg)
    b = generator_apply(g, z, cfg_ref)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-3,
                               rtol=2e-3)


def test_gan_one_train_step_improves_discriminator():
    cfg = GanConfig(name="dcgan", channel_scale=0.0625)
    g, d = init_gan(cfg, jax.random.PRNGKey(0))
    z = jax.random.normal(jax.random.PRNGKey(1), (4, cfg.z_dim))
    real = jax.random.normal(jax.random.PRNGKey(2), (4, 64, 64, 3))

    def d_loss_fn(d):
        _, dl, _ = gan_losses(g, d, z, real, cfg)
        return dl

    l0 = float(d_loss_fn(d))
    grads = jax.grad(d_loss_fn)(d)
    d2 = jax.tree.map(lambda p, gr: p - 0.05 * gr, d, grads)
    l1 = float(d_loss_fn(d2))
    assert l1 < l0
