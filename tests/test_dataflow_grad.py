"""Differentiability of the unified dataflow dispatch (`core.dataflow`).

The custom VJP must match XLA's own autodiff through the lax-built
reference (``tconv_zero_insert`` / ``conv_ref``) on every backend,
including the Pallas kernel — which has no autodiff rule of its own, so
these tests are what certifies ``GanConfig(use_pallas=True)`` as
trainable.  Also locks the μop compilation cache contract: repeated
identical layer geometry runs the scheduler once.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs as _obs
from repro.core.dataflow import (DataflowPolicy, Epilogue,
                                 SecondOrderNotImplemented, compile_uops,
                                 conv, tconv,
                                 uop_cache_clear, uop_cache_info)
from repro.core.tconv import tconv_taps, tconv_zero_insert
from repro.kernels.ref import conv_ref

BACKENDS = ["zero-insert", "polyphase", "pallas-interpret", "pallas"]

# (x_shape, w_shape, strides, pads) — strides {1,2,3} and kernel<stride.
TCONV_CASES = [
    ((2, 5, 5, 2), (3, 3, 2, 4), (1, 1), (1, 1)),
    ((1, 4, 4, 2), (4, 4, 2, 3), (2, 2), (1, 1)),
    ((1, 5, 3, 2), (3, 5, 2, 4), (3, 2), (1, 2)),
    ((1, 3, 3, 2), (2, 2, 2, 3), (3, 3), (0, 0)),   # kernel < stride
]

CONV_CASES = [
    ((2, 6, 6, 3), (3, 3, 3, 4), (1, 1), (1, 1)),
    ((1, 8, 8, 2), (3, 3, 2, 4), (2, 2), (1, 1)),   # stride tail unread
    ((1, 7, 7, 3), (3, 3, 3, 5), (3, 3), (0, 0)),
    ((1, 9, 9, 2), (5, 5, 2, 3), (2, 2), (2, 2)),
]


def _grads(fn, x, w, cot):
    def loss(x, w):
        return jnp.sum(fn(x, w) * cot)
    return jax.grad(loss, argnums=(0, 1))(x, w)


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("xs,ws,s,p", TCONV_CASES)
def test_tconv_grad_parity(backend, xs, ws, s, p):
    rng = np.random.default_rng(hash((xs, ws, s, p)) % 2**31)
    x = jnp.asarray(rng.normal(size=xs), jnp.float32)
    w = jnp.asarray(rng.normal(size=ws), jnp.float32)
    ref = tconv_zero_insert(x, w, s, p)
    cot = jnp.asarray(rng.normal(size=ref.shape), jnp.float32)
    gx_ref, gw_ref = _grads(lambda x, w: tconv_zero_insert(x, w, s, p),
                            x, w, cot)
    policy = DataflowPolicy(backend=backend)
    out = tconv(x, w, s, p, policy=policy)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-4, rtol=1e-4)
    gx, gw = _grads(lambda x, w: tconv(x, w, s, p, policy=policy),
                    x, w, cot)
    np.testing.assert_allclose(np.asarray(gx), np.asarray(gx_ref),
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(gw), np.asarray(gw_ref),
                               atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("xs,ws,s,p", CONV_CASES)
def test_conv_grad_parity(backend, xs, ws, s, p):
    rng = np.random.default_rng(hash((xs, ws, s, p)) % 2**31)
    x = jnp.asarray(rng.normal(size=xs), jnp.float32)
    w = jnp.asarray(rng.normal(size=ws), jnp.float32)
    ref = conv_ref(x, w, s, p)
    cot = jnp.asarray(rng.normal(size=ref.shape), jnp.float32)
    gx_ref, gw_ref = _grads(lambda x, w: conv_ref(x, w, s, p), x, w, cot)
    policy = DataflowPolicy(backend=backend)
    out = conv(x, w, s, p, policy=policy)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-4, rtol=1e-4)
    gx, gw = _grads(lambda x, w: conv(x, w, s, p, policy=policy),
                    x, w, cot)
    np.testing.assert_allclose(np.asarray(gx), np.asarray(gx_ref),
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(gw), np.asarray(gw_ref),
                               atol=1e-4, rtol=1e-4)


# 3-D (volumetric) cases — strides {1,2,3}, mixed strides, kernel<stride.
TCONV3D_CASES = [
    ((1, 3, 3, 3, 2), (3, 3, 3, 2, 3), (1, 1, 1), (1, 1, 1)),
    ((1, 3, 3, 3, 2), (4, 4, 4, 2, 3), (2, 2, 2), (1, 1, 1)),
    ((1, 3, 2, 3, 2), (3, 4, 3, 2, 2), (3, 2, 1), (1, 1, 0)),
    ((1, 2, 2, 2, 2), (2, 2, 2, 2, 3), (3, 3, 3), (0, 0, 0)),
]

CONV3D_CASES = [
    ((1, 5, 5, 5, 2), (3, 3, 3, 2, 3), (1, 1, 1), (1, 1, 1)),
    ((1, 6, 6, 6, 2), (4, 4, 4, 2, 3), (2, 2, 2), (1, 1, 1)),
    ((1, 7, 5, 7, 2), (3, 3, 3, 2, 2), (3, 2, 3), (0, 1, 0)),
]


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("xs,ws,s,p", TCONV3D_CASES)
def test_tconv_grad_parity_3d(backend, xs, ws, s, p):
    """Volumetric grad parity: the 3-D Pallas kernel's custom VJP (and
    the pure-JAX backends) must match XLA's autodiff through the
    zero-insertion reference — the 3D-GAN training path."""
    rng = np.random.default_rng(hash((xs, ws, s, p)) % 2**31)
    x = jnp.asarray(rng.normal(size=xs), jnp.float32)
    w = jnp.asarray(rng.normal(size=ws), jnp.float32)
    ref = tconv_zero_insert(x, w, s, p)
    cot = jnp.asarray(rng.normal(size=ref.shape), jnp.float32)
    gx_ref, gw_ref = _grads(lambda x, w: tconv_zero_insert(x, w, s, p),
                            x, w, cot)
    policy = DataflowPolicy(backend=backend)
    out = tconv(x, w, s, p, policy=policy)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-4, rtol=1e-4)
    gx, gw = _grads(lambda x, w: tconv(x, w, s, p, policy=policy),
                    x, w, cot)
    np.testing.assert_allclose(np.asarray(gx), np.asarray(gx_ref),
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(gw), np.asarray(gw_ref),
                               atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("xs,ws,s,p", CONV3D_CASES)
def test_conv_grad_parity_3d(backend, xs, ws, s, p):
    rng = np.random.default_rng(hash((xs, ws, s, p)) % 2**31)
    x = jnp.asarray(rng.normal(size=xs), jnp.float32)
    w = jnp.asarray(rng.normal(size=ws), jnp.float32)
    ref = conv_ref(x, w, s, p)
    cot = jnp.asarray(rng.normal(size=ref.shape), jnp.float32)
    gx_ref, gw_ref = _grads(lambda x, w: conv_ref(x, w, s, p), x, w, cot)
    policy = DataflowPolicy(backend=backend)
    out = conv(x, w, s, p, policy=policy)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-4, rtol=1e-4)
    gx, gw = _grads(lambda x, w: conv(x, w, s, p, policy=policy),
                    x, w, cot)
    np.testing.assert_allclose(np.asarray(gx), np.asarray(gx_ref),
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(gw), np.asarray(gw_ref),
                               atol=1e-4, rtol=1e-4)


# Lane-sparse outputs (Cout 1 and 3): the packed form, every phase in one
# stride-1 conv with the phases in its output channels.
TAPS_CASES = [
    ((2, 5, 7, 4), (4, 4, 4, 1), (2, 2), (1, 1)),
    ((1, 5, 3, 3), (5, 3, 3, 3), (3, 2), (0, 2)),
    ((1, 3, 4, 2), (2, 2, 2, 3), (3, 3), (1, 0)),      # kernel < stride
    ((2, 5, 5, 3), (5, 5, 3, 3), (1, 1), (4, 4)),
    ((1, 3, 5, 4, 2), (4, 4, 4, 2, 1), (2, 2, 2), (1, 1, 1)),
    ((1, 3, 2, 3, 3), (3, 2, 5, 3, 3), (2, 3, 1), (2, 1, 0)),
]


@pytest.mark.parametrize("xs,ws,s,p", TAPS_CASES)
def test_tconv_taps_grad_parity(xs, ws, s, p):
    """dx, dw and db of the packed form (native autodiff through the
    conv and the interleave, tanh + bias epilogue) match autodiff of the
    zero-insertion oracle."""
    rng = np.random.default_rng(hash((xs, ws, s, p)) % 2**31)
    x = jnp.asarray(rng.normal(size=xs), jnp.float32)
    w = jnp.asarray(rng.normal(size=ws), jnp.float32)
    b = jnp.asarray(rng.normal(size=ws[-1:]), jnp.float32)
    cot = jnp.asarray(rng.normal(size=tconv_zero_insert(x, w, s, p).shape),
                      jnp.float32)

    def grads(fn):
        return jax.grad(lambda x, w, b: jnp.sum(
            jnp.tanh(fn(x, w, s, p) + b) * cot), argnums=(0, 1, 2))(x, w, b)

    got = grads(tconv_taps)
    ref = grads(tconv_zero_insert)
    for g, r, name in zip(got, ref, ("dx", "dw", "db")):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                   atol=1e-4, rtol=1e-4, err_msg=name)


# A strided conv with a lane-sparse input, whose input gradient is the
# packed tconv: Cin 1 and 2 at stride 2^3, paddings 1 and 0, and odd
# sizes that leave a stride tail the conv never reads.
DX_TAPS_CASES = [
    ((2, 6, 6, 6, 1), (4, 4, 4, 1, 3), (2, 2, 2), (1, 1, 1)),
    ((1, 6, 4, 6, 2), (4, 4, 4, 2, 5), (2, 2, 2), (0, 0, 0)),
    ((1, 7, 9, 5, 1), (4, 4, 4, 1, 4), (2, 2, 2), (1, 1, 1)),
    ((1, 9, 7, 8, 2), (3, 3, 3, 2, 3), (2, 2, 2), (0, 0, 0)),
]


def _count(name: str) -> int:
    return _obs.snapshot()["counters"].get(name, 0)


@pytest.mark.parametrize("xs,ws,s,p", DX_TAPS_CASES)
def test_conv_dx_taps_grad_parity(xs, ws, s, p):
    """dx, dw and db of a routed conv (LeakyReLU 0.2 + bias epilogue) on
    the polyphase backend match the zero-insert backend's native
    autodiff, and the conv takes the packed input gradient."""
    rng = np.random.default_rng(hash((xs, ws, s, p)) % 2**31)
    x = jnp.asarray(rng.normal(size=xs), jnp.float32)
    w = jnp.asarray(rng.normal(size=ws), jnp.float32)
    b = jnp.asarray(rng.normal(size=ws[-1:]), jnp.float32)
    ep = Epilogue(bias=True, activation="leaky_relu", leaky_slope=0.2)
    cot = jnp.asarray(rng.normal(size=conv_ref(x, w, s, p).shape),
                      jnp.float32)

    def grads(backend):
        policy = DataflowPolicy(backend=backend)
        return jax.grad(lambda x, w, b: jnp.sum(conv(
            x, w, s, p, policy=policy, bias=b, epilogue=ep) * cot),
            argnums=(0, 1, 2))(x, w, b)

    before = _count("dataflow.conv.dx_taps")
    got = grads("polyphase")
    assert _count("dataflow.conv.dx_taps") == before + 1
    ref = grads("zero-insert")
    for g, r, name in zip(got, ref, ("dx", "dw", "db")):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                   atol=1e-4, rtol=1e-4, err_msg=name)


def test_conv_dx_taps_ops_named_for_the_layer():
    """The packed input gradient's ops keep the layer's scope and a
    ``transpose(`` in their path, which is how the benchmark's per-layer
    split (``bench/layers.py``) credits an op to the layer's backward."""
    import re

    x = jnp.ones((2, 6, 6, 6, 1))
    w = jnp.ones((4, 4, 4, 1, 3))

    def loss(x):
        with jax.named_scope("layer.d1"):
            return jnp.sum(conv(x, w, (2, 2, 2), (1, 1, 1)) ** 2)

    hlo = jax.jit(jax.grad(loss)).lower(x).compile().as_text()
    taps = [p for p in re.findall(r'op_name="([^"]*)"', hlo)
            if "tconv.taps" in p or "tconv.interleave" in p]
    assert taps
    for path in taps:
        assert "layer.d1" in path and "transpose(" in path, path


# The zoo's layers that take a packed form on the polyphase backend: a
# tconv with a lane-sparse output, and a conv with a lane-sparse input
# (its input gradient is that tconv), both with 8 phases or more.  Every
# other layer keeps the phase convs or autodiff of the plain conv: the
# strided ones with 64 channels or more there, the 2-D image layers (4
# phases) and the stride-1 ones (one phase, so no x read per phase to
# save).
@pytest.mark.parametrize("transposed,counter,pinned", [
    (True, "dataflow.tconv.taps", {("3dgan", "g4")}),
    (False, "dataflow.conv.dx_taps", {("3dgan", "d1")}),
], ids=["tconv", "conv"])
def test_tconv_taps_routing_pins_zoo(transposed, counter, pinned):
    """The rule is geometric: across every zoo model's layers of one
    kind at published widths, the counter counts exactly the layers
    pinned above, and the default resolution routes them so."""
    from repro.configs.gans import GAN_MODELS

    op = tconv if transposed else conv
    taken = set()
    for model, (g_layers, d_layers) in GAN_MODELS.items():
        for layer in g_layers + d_layers:
            if layer.transposed != transposed:
                continue
            x = jax.ShapeDtypeStruct((1, *layer.in_spatial, layer.cin),
                                     jnp.float32)
            w = jax.ShapeDtypeStruct((*layer.kernel, layer.cin, layer.cout),
                                     jnp.float32)
            before = _count(counter)
            jax.eval_shape(lambda x, w: op(x, w, layer.strides,
                                           layer.paddings), x, w)
            count = _count(counter) - before
            assert count in (0, 1), (model, layer.name, count)
            if count:
                taken.add((model, layer.name))
    assert taken == pinned


def test_3dgan_layers_pallas_forward_and_vjp_parity():
    """Acceptance: every 3D-GAN generator/discriminator layer geometry
    (channel-scaled for CPU) runs the volumetric Pallas kernel in
    interpret mode with forward and VJP matching the polyphase and
    zero-insert references."""
    from repro.configs.gans import gan_layers

    g_layers, d_layers = gan_layers("3dgan")
    scale = 1 / 16
    interp = DataflowPolicy(backend="pallas-interpret")
    poly = DataflowPolicy(backend="polyphase")
    for layer in g_layers + d_layers:
        cin = max(1, int(layer.cin * scale))
        cout = max(1, int(layer.cout * scale))
        rng = np.random.default_rng(layer.cin * 31 + layer.cout)
        x = jnp.asarray(rng.normal(size=(1, *layer.in_spatial, cin)),
                        jnp.float32)
        w = jnp.asarray(rng.normal(size=(*layer.kernel, cin, cout)),
                        jnp.float32)
        s, p = layer.strides, layer.paddings
        if layer.transposed:
            op, ref_fn = tconv, tconv_zero_insert
        else:
            op, ref_fn = conv, conv_ref
        ref = ref_fn(x, w, s, p)
        got = op(x, w, s, p, policy=interp)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=1e-3, rtol=1e-3,
                                   err_msg=f"forward {layer.name}")
        np.testing.assert_allclose(
            np.asarray(op(x, w, s, p, policy=poly)), np.asarray(ref),
            atol=1e-3, rtol=1e-3, err_msg=f"polyphase {layer.name}")
        cot = jnp.asarray(rng.normal(size=ref.shape), jnp.float32)
        gx_ref, gw_ref = _grads(lambda x, w: ref_fn(x, w, s, p), x, w, cot)
        gx, gw = _grads(lambda x, w: op(x, w, s, p, policy=interp),
                        x, w, cot)
        np.testing.assert_allclose(np.asarray(gx), np.asarray(gx_ref),
                                   atol=1e-3, rtol=1e-3,
                                   err_msg=f"dx {layer.name}")
        np.testing.assert_allclose(np.asarray(gw), np.asarray(gw_ref),
                                   atol=1e-3, rtol=1e-3,
                                   err_msg=f"dw {layer.name}")


@pytest.mark.parametrize("op", [tconv, conv])
def test_second_order_autodiff_raises_clearly(op):
    """The kernel backends' custom VJP defines one backward pass;
    grad-of-grad used to be silently wrong — it must raise with
    guidance instead (ROADMAP open item)."""
    policy = DataflowPolicy(backend="pallas-interpret")
    x = jnp.ones((1, 4, 4, 2))
    w = jnp.ones((3, 3, 2, 2))

    def loss(x):
        return jnp.sum(op(x, w, (2, 2), (1, 1), policy=policy) ** 2)

    jax.grad(loss)(x)  # first order stays supported
    with pytest.raises(SecondOrderNotImplemented,
                       match="pure-JAX backend"):
        jax.grad(lambda x: jnp.sum(jax.grad(loss)(x)))(x)


@pytest.mark.parametrize("op,xs,ws,s,p", [
    (tconv, (1, 3, 3, 2), (2, 2, 2, 2), (2, 2), (0, 0)),
    # a routed conv: its input gradient is the packed tconv
    (conv, (1, 6, 5, 6, 1), (4, 4, 4, 1, 2), (2, 2, 2), (1, 1, 1)),
], ids=["tconv", "conv-dx-taps"])
def test_second_order_supported_on_pure_jax_backends(op, xs, ws, s, p):
    """Higher-order autodiff, and forward mode, keep working where XLA
    natively provides them, and match across the two zero-free
    formulations."""
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=xs), jnp.float32) * 0.5
    w = jnp.asarray(rng.normal(size=ws), jnp.float32) * 0.25

    def f(backend):
        policy = DataflowPolicy(backend=backend)
        return lambda x, w: op(x, w, s, p, policy=policy)

    def loss(backend):
        return lambda x: jnp.sum(f(backend)(x, w) ** 3)

    g2 = {b: jax.grad(lambda x: jnp.sum(jax.grad(loss(b))(x)))(x)
          for b in ("polyphase", "zero-insert")}
    np.testing.assert_allclose(np.asarray(g2["polyphase"]),
                               np.asarray(g2["zero-insert"]),
                               atol=1e-4, rtol=1e-4)
    tangents = (x * 0.3, w * 0.7)
    jvps = {b: jax.jvp(f(b), (x, w), tangents)
            for b in ("polyphase", "zero-insert")}
    for a, r in zip(jvps["polyphase"], jvps["zero-insert"]):
        np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                   atol=1e-4, rtol=1e-4)


def test_uop_cache_hit_on_repeated_geometry():
    """make_schedule runs once for repeated identical layer geometry."""
    uop_cache_clear()
    x = jnp.ones((1, 4, 4, 2))
    w = jnp.ones((4, 4, 2, 3))
    policy = DataflowPolicy(backend="polyphase")
    tconv(x, w, (2, 2), (1, 1), policy=policy)
    first = uop_cache_info()
    assert first["misses"] == 1
    for _ in range(3):
        tconv(x, w, (2, 2), (1, 1), policy=policy)
    again = uop_cache_info()
    assert again["misses"] == 1, "scheduler re-ran for a cached geometry"
    assert again["hits"] >= 3
    # distinct geometry is a distinct cache entry, not a collision
    tconv(jnp.ones((1, 5, 5, 2)), w, (2, 2), (1, 1), policy=policy)
    assert uop_cache_info()["misses"] == 2


def test_policy_resolution():
    """Resolution contract on a CPU host: auto → polyphase, "pallas" →
    interpret for both kernel ranks (2-D and now 3-D) with a polyphase
    fallback for ranks the kernel doesn't implement (1-D), interpret
    override implies the kernel, strict names raise on unsupported
    ranks."""
    assert DataflowPolicy().resolve(2) == "polyphase"
    assert DataflowPolicy(backend="pallas").resolve(2) == "pallas-interpret"
    assert DataflowPolicy(backend="pallas").resolve(3) == "pallas-interpret"
    assert DataflowPolicy(backend="pallas").resolve(1) == "polyphase"
    assert DataflowPolicy(interpret=True).resolve(2) == "pallas-interpret"
    assert DataflowPolicy(interpret=True).resolve(3) == "pallas-interpret"
    assert DataflowPolicy(interpret=True).resolve(1) == "polyphase"
    assert DataflowPolicy(backend="pallas",
                          interpret=True).resolve(1) == "polyphase"
    assert DataflowPolicy(backend="pallas-interpret",
                          interpret=True).resolve(3) == "pallas-interpret"
    with pytest.raises(ValueError, match="available"):
        DataflowPolicy(backend="pallus").resolve(2)
    with pytest.raises(ValueError, match="support"):
        DataflowPolicy(backend="pallas-interpret").resolve(1)
    with pytest.raises(ValueError, match="contradicts"):
        DataflowPolicy(backend="polyphase", interpret=True).resolve(2)
    with pytest.raises(ValueError, match="contradicts"):
        DataflowPolicy(backend="pallas-tpu", interpret=True).resolve(2)
    with pytest.raises(ValueError, match="contradicts"):
        DataflowPolicy(backend="pallas-interpret",
                       interpret=False).resolve(2)


def test_heuristic_resolves_polyphase_on_tpu(monkeypatch):
    """On a TPU host the heuristic, and so an ``auto`` plan miss, picks
    polyphase, which the chip's compiler accepts for every Table-I
    layer; the kernel stays reachable pinned or through ``"pallas"``."""
    from repro.core import dataflow
    from repro.tune import Planner
    monkeypatch.setattr(dataflow, "_on_tpu", lambda: True)
    for nd in (2, 3):
        assert DataflowPolicy().resolve(nd) == "polyphase"
        assert DataflowPolicy(backend="pallas").resolve(nd) == "pallas-tpu"
        assert DataflowPolicy(backend="pallas-tpu").resolve(nd) == \
            "pallas-tpu"
    res = dataflow.resolve_execution(
        DataflowPolicy(backend="auto"), "conv", (32, 32), (4, 4), (2, 2),
        (1, 1), 128, 256, batch=64, planner=Planner())
    assert (res.backend, res.source) == ("polyphase", "heuristic")


def test_compile_uops_artifacts_frozen():
    u = compile_uops((4, 4), (4, 4), (2, 2), (1, 1))
    assert not u.n_taps.flags.writeable
    assert not u.k_idx.flags.writeable
    assert u.schedule.n_phases == 4


def test_gan_pallas_trains_end_to_end():
    """Acceptance: GanConfig(use_pallas=True) runs one gan_losses grad
    step through the Pallas-interpret backend with gradients matching the
    zero-insert baseline to 1e-4."""
    from repro.models.gan import GanConfig, gan_losses, init_gan

    cfg_p = GanConfig(name="dcgan", channel_scale=0.03125, use_pallas=True)
    cfg_z = GanConfig(name="dcgan", channel_scale=0.03125,
                      dataflow="zero_insert")
    assert cfg_p.policy.resolve(2) == "pallas-interpret"  # CPU test host
    g, d = init_gan(cfg_p, jax.random.PRNGKey(0))
    z = jax.random.normal(jax.random.PRNGKey(1), (2, cfg_p.z_dim))
    real = jax.random.normal(jax.random.PRNGKey(2), (2, 64, 64, 3))

    def losses(g, d, cfg):
        gl, dl, _ = gan_losses(g, d, z, real, cfg)
        return gl + dl

    (gp, dp) = jax.grad(losses, argnums=(0, 1))(g, d, cfg_p)
    (gz, dz) = jax.grad(losses, argnums=(0, 1))(g, d, cfg_z)
    for a, b in zip(jax.tree.leaves((gp, dp)), jax.tree.leaves((gz, dz))):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-4, rtol=1e-4)
