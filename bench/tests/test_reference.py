"""The plain reference: its convolutions against independent oracles, and
the whole GAN against the program at a size the CPU holds."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import inputs, reference
from bench.tests.conftest import tiny_config

def numpy_tconv(x, w, strides, paddings):
    """Transposed convolution in float64 NumPy: every input pixel
    scatters ``x @ w[tap]`` to ``stride * i + tap``, then the padding is
    cropped (copied from ``chip_smoke.py``)."""
    nd = x.ndim - 2
    in_sp = x.shape[1:1 + nd]
    full = [s * (i - 1) + k for s, i, k in zip(strides, in_sp, w.shape)]
    out = np.zeros((x.shape[0], *full, w.shape[-1]))
    for tap in np.ndindex(*w.shape[:nd]):
        at = tuple(slice(t, t + s * (i - 1) + 1, s)
                   for t, s, i in zip(tap, strides, in_sp))
        out[(slice(None),) + at] += x @ w[tap]
    crop = tuple(slice(p, f - p) for p, f in zip(paddings, full))
    return out[(slice(None),) + crop]


GEOMETRIES = [(4, 2, 1, 4), (4, 2, 1, 3), (4, 1, 0, 4), (5, 1, 2, 5),
              (3, 1, 1, 4)]


@pytest.mark.parametrize("dims", [1, 2, 3])
@pytest.mark.parametrize("k, s, p, n", GEOMETRIES)
def test_tconv_matches_the_float64_scatter_oracle(dims, k, s, p, n):
    rng = np.random.default_rng(dims * 100 + k * 10 + s)
    x = rng.standard_normal((2,) + (n,) * dims + (3,))
    w = rng.standard_normal((k,) * dims + (3, 5))
    got = reference.tconv(jnp.asarray(x, jnp.float32),
                          jnp.asarray(w, jnp.float32),
                          {"name": "t", "in": n, "k": k, "s": s, "p": p},
                          dims)
    want = numpy_tconv(x, w, (s,) * dims, (p,) * dims)
    np.testing.assert_allclose(np.asarray(got), want, atol=5e-5)


@pytest.mark.parametrize("dims", [1, 2, 3])
@pytest.mark.parametrize("k, s, p, n", [(4, 2, 1, 8), (4, 1, 0, 4),
                                        (4, 2, 1, 6)])
def test_conv_matches_lax_strided_conv(dims, k, s, p, n):
    rng = np.random.default_rng(dims + k + n)
    x = jnp.asarray(rng.standard_normal((2,) + (n,) * dims + (3,)),
                    jnp.float32)
    w = jnp.asarray(rng.standard_normal((k,) * dims + (3, 5)), jnp.float32)
    got = reference.conv(x, w, {"name": "c", "in": n, "k": k, "s": s,
                                "p": p}, dims)
    sp = "DHW"[-dims:]
    want = jax.lax.conv_general_dilated(
        x, w, (s,) * dims, [(p, p)] * dims,
        dimension_numbers=("N" + sp + "C", sp + "IO", "N" + sp + "C"),
        precision="highest")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=5e-5)


@pytest.fixture(scope="module")
def tiny():
    cfg = tiny_config()
    return cfg, inputs.make_params(cfg, 7), inputs.make_batches(cfg, 7, 3, 4)


def test_reference_gan_matches_the_program(tiny):
    from bench.drivers.common import program_config
    from repro.models.gan import discriminator_apply, generator_apply
    cfg, (g, d), feed = tiny
    gcfg = program_config(cfg)
    z, real = feed[0]["z"], feed[0]["real"]
    np.testing.assert_allclose(
        np.asarray(reference.generator(cfg, g, z)),
        np.asarray(generator_apply(g, z, gcfg)), atol=2e-5)
    np.testing.assert_allclose(
        np.asarray(reference.discriminator(cfg, d, real)),
        np.asarray(discriminator_apply(d, real, gcfg)), atol=2e-5)


def test_the_half_batch_fault_is_the_step_of_the_first_rows(tiny):
    cfg, params, feed = tiny
    half = reference.sgd_steps(cfg, params, feed[:2], rows=2)
    first = reference.sgd_steps(
        cfg, params, [{k: v[:2] for k, v in b.items()} for b in feed[:2]])
    whole = reference.sgd_steps(cfg, params, feed[:2])
    for a, b in zip(jax.tree.leaves(half[0]), jax.tree.leaves(first[0])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)
    for a, b in zip(half[2], first[2]):
        assert a == pytest.approx(b, rel=1e-5)
    assert half[2][0] != pytest.approx(whole[2][0], rel=1e-5)
