"""GANAX tconv dataflows (phase convs and the packed form) vs the
zero-insertion definition and XLA."""

import string

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st  # noqa: E402
from jax import lax  # noqa: E402

from repro.core.scheduler import make_schedule  # noqa: E402
from repro.core.dataflow import DataflowPolicy, Epilogue, tconv  # noqa: E402
from repro.core.tconv import (  # noqa: E402
    tconv_ganax, tconv_output_shape, tconv_taps, tconv_zero_insert,
    zero_insert)
from repro.kernels.ref import tconv_ref  # noqa: E402


def xla_ref(x, w, s, p):
    nd = x.ndim - 2
    pads = tuple((w.shape[i] - 1 - p[i],) * 2 for i in range(nd))
    letters = "".join(c for c in string.ascii_uppercase if c not in "NCIO")
    sp = letters[:nd]
    dn = lax.conv_dimension_numbers(
        x.shape, w.shape, ("N" + sp + "C", sp + "IO", "N" + sp + "C"))
    return lax.conv_general_dilated(
        x, jnp.flip(w, tuple(range(nd))), (1,) * nd, pads,
        lhs_dilation=s, dimension_numbers=dn)


CASES_2D = [
    ((2, 4, 4, 3), (5, 5, 3, 7), (2, 2), (2, 2)),
    ((1, 4, 4, 2), (4, 4, 2, 5), (2, 2), (1, 1)),
    ((1, 5, 3, 2), (3, 5, 2, 4), (3, 2), (1, 2)),
    ((2, 6, 6, 3), (3, 3, 3, 4), (1, 1), (1, 1)),
    ((1, 7, 1, 2), (5, 1, 2, 3), (2, 1), (2, 0)),
    ((1, 8, 8, 1), (2, 2, 1, 1), (2, 2), (0, 0)),
    ((3, 4, 4, 8), (4, 4, 8, 16), (4, 4), (0, 0)),
]

# Lane-sparse outputs (Cout 1 and 3, the image layers the packed form
# is for): odd input sizes, strides 1-3, k < s, k % s != 0, p in {0, 1, k-1}.
LANE_SPARSE_2D = [
    ((2, 5, 7, 4), (4, 4, 4, 1), (2, 2), (1, 1)),
    ((1, 5, 3, 3), (5, 3, 3, 3), (3, 2), (0, 2)),
    ((1, 3, 4, 2), (2, 2, 2, 3), (3, 3), (1, 0)),
    ((2, 5, 5, 3), (5, 5, 3, 3), (1, 1), (4, 4)),
    ((1, 7, 5, 2), (3, 3, 2, 1), (2, 3), (2, 0)),
]

CASES_3D = [
    ((1, 4, 4, 4, 2), (4, 4, 4, 2, 3), (2, 2, 2), (1, 1, 1)),
    ((2, 3, 3, 3, 1), (3, 3, 3, 1, 2), (3, 3, 3), (0, 0, 0)),
]

LANE_SPARSE_3D = [
    ((1, 3, 5, 4, 2), (4, 4, 4, 2, 1), (2, 2, 2), (1, 1, 1)),
    ((1, 3, 2, 3, 3), (3, 2, 5, 3, 3), (2, 3, 1), (2, 1, 0)),
]


@pytest.mark.parametrize("xs,ws,s,p", CASES_2D + LANE_SPARSE_2D + CASES_3D
                         + LANE_SPARSE_3D)
def test_against_xla(xs, ws, s, p):
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=xs), jnp.float32)
    w = jnp.asarray(rng.normal(size=ws), jnp.float32)
    ref = xla_ref(x, w, s, p)
    for fn in (tconv_ganax, tconv_taps, tconv_zero_insert):
        got = fn(x, w, s, p)
        assert got.shape == ref.shape == tconv_output_shape(xs, ws, s, p)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=2e-4, rtol=2e-4)
    # the packed form against the oracle and the phase form directly
    taps = tconv_taps(x, w, s, p)
    for other in (tconv_ref(x, w, s, p), tconv_ganax(x, w, s, p)):
        np.testing.assert_allclose(np.asarray(taps), np.asarray(other),
                                   atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize("xs,ws,s,p", LANE_SPARSE_2D + LANE_SPARSE_3D)
def test_taps_tanh_bias_epilogue(xs, ws, s, p):
    """Through the polyphase backend (packed form or phase convs, as its
    shape rule routes them), these lane-sparse layers with the generator
    output's tanh + bias epilogue compute tanh(oracle + b)."""
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=xs), jnp.float32)
    w = jnp.asarray(rng.normal(size=ws), jnp.float32)
    b = jnp.asarray(rng.normal(size=ws[-1:]), jnp.float32)
    got = tconv(x, w, s, p, policy=DataflowPolicy(backend="polyphase"),
                bias=b, epilogue=Epilogue(bias=True, activation="tanh"))
    ref = jnp.tanh(tconv_ref(x, w, s, p) + b)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize("fn", [tconv_ganax, tconv_taps])
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-4),
                                       (jnp.bfloat16, 1e-1)])
def test_dtypes(dtype, tol, fn):
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(1, 4, 4, 8)), dtype)
    w = jnp.asarray(rng.normal(size=(4, 4, 8, 8)), dtype)
    got = fn(x, w, (2, 2), (1, 1))
    ref = tconv_zero_insert(x, w, (2, 2), (1, 1))
    assert got.dtype == dtype
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(ref, np.float32),
                               atol=tol, rtol=tol)


@settings(max_examples=40, deadline=None)
@given(st.integers(2, 6), st.integers(1, 5), st.integers(1, 3),
       st.integers(0, 2), st.integers(1, 4), st.integers(1, 4))
def test_property_2d(n, k, s, p, cin, cout):
    p = min(p, k - 1)
    rng = np.random.default_rng(n * 1000 + k * 100 + s * 10 + p)
    x = jnp.asarray(rng.normal(size=(1, n, n, cin)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(k, k, cin, cout)), jnp.float32)
    ref = xla_ref(x, w, (s, s), (p, p))
    for fn in (tconv_ganax, tconv_taps):
        got = fn(x, w, (s, s), (p, p))
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=3e-4, rtol=3e-4)


def test_zero_insert_structure():
    """The expanded input is zero exactly off the stride grid."""
    x = jnp.ones((1, 3, 3, 1))
    e = zero_insert(x, (2, 3))
    assert e.shape == (1, 5, 7, 1)
    dense = np.asarray(e[0, :, :, 0])
    mask = np.zeros_like(dense, bool)
    mask[::2, ::3] = True
    assert (dense[mask] == 1).all() and (dense[~mask] == 0).all()
    # inserted-zero fraction matches the schedule's accounting
    sched = make_schedule((3, 3), (2, 3), (2, 3), (0, 0))
    assert sched.inconsequential_fraction() > 0.5


def test_gradients_match():
    """Every dataflow is differentiable and they agree on gradients."""
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(size=(1, 4, 4, 2)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(4, 4, 2, 3)), jnp.float32)

    def loss(fn, x, w):
        return jnp.sum(jnp.square(fn(x, w, (2, 2), (1, 1))))

    g2 = jax.grad(lambda w: loss(tconv_zero_insert, x, w))(w)
    for fn in (tconv_ganax, tconv_taps):
        g1 = jax.grad(lambda w: loss(fn, x, w))(w)
        np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                                   atol=1e-3, rtol=1e-3)
