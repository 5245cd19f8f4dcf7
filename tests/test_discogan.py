"""DiscoGAN on the normal path: an image-input generator (encoder-decoder,
no projection), its input signature in ``ProgramSpec``, the encoder's
LeakyReLU epilogue, the cross-domain train step against a plain ``jnp``
computation, and serving's refusal of a generator it would feed latents.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.models.gan import (LEAKY_SLOPE, GanConfig, generator_epilogues,
                              init_gan)
from repro.program import Program, ProgramSpec
from repro.train.loop import make_gan_train_step

SCALE = 1 / 16
HIGHEST = jax.lax.Precision.HIGHEST
DN = ("NHWC", "HWIO", "NHWC")


def _cfg(**kw):
    return GanConfig("discogan", channel_scale=SCALE, **kw)


def test_the_model_fixes_the_input_kind():
    cfg = _cfg()
    assert cfg.input_kind == "image" and cfg.input_shape == (64, 64, 3)
    dcgan = GanConfig("dcgan", channel_scale=SCALE)
    assert dcgan.input_kind == "latent" and dcgan.input_shape == (100,)
    g, _ = init_gan(cfg, jax.random.PRNGKey(0))
    assert "proj_w" not in g and "t0_w" in g and "t9_w" in g


def test_image_input_spec_round_trips_through_json():
    spec = ProgramSpec.build(_cfg(), 2, "generator")
    assert spec.input_kind == "image" and spec.z_dim is None
    assert spec.input_shape == (64, 64, 3)
    doc = spec.to_json()
    assert doc["input_kind"] == "image"
    again = ProgramSpec.from_json(doc)
    assert again == spec
    assert again.geometry_signature() == spec.geometry_signature()
    assert "input=image" in spec.describe()
    disc = ProgramSpec.build(_cfg(), 2, "discriminator")
    assert disc.input_kind == "image"
    assert ProgramSpec.from_json(disc.to_json()) == disc


def test_a_latent_file_without_the_field_still_loads():
    spec = ProgramSpec.build(GanConfig("dcgan", channel_scale=SCALE), 2,
                             "generator")
    doc = spec.to_json()
    del doc["input_kind"]
    old = ProgramSpec.from_json(doc)
    assert old.input_kind == "latent" and old.input_shape == (100,)
    assert old == spec
    doc = ProgramSpec.build(_cfg(), 2, "discriminator").to_json()
    del doc["input_kind"]
    assert ProgramSpec.from_json(doc).input_kind == "image"


def test_a_latent_input_needs_a_latent_generator():
    doc = ProgramSpec.build(_cfg(), 2, "discriminator").to_json()
    doc["input_kind"] = "latent"
    with pytest.raises(ValueError, match="latent input"):
        ProgramSpec.from_json(doc)
    doc["input_kind"] = "voxels"
    with pytest.raises(ValueError, match="unknown program input"):
        ProgramSpec.from_json(doc)


def test_the_build_span_names_the_input():
    sink = obs.enable()
    try:
        ProgramSpec.build(_cfg(), 2, "generator")
        ProgramSpec.build(GanConfig("dcgan", channel_scale=SCALE), 2,
                          "generator")
    finally:
        obs.disable()
    assert [s["attrs"]["input"] for s in sink.spans("program.build")] \
        == ["image", "latent"]


def test_the_encoder_takes_leaky_relu():
    g_layers, _ = _cfg().layers
    eps = generator_epilogues(g_layers)
    names = [lay.name for lay in g_layers]
    assert names[:5] == ["e1", "e2", "e3", "e4", "e5"]
    for ep in eps[:5]:
        assert ep.bias and ep.activation == "leaky_relu"
        assert ep.leaky_slope == LEAKY_SLOPE
    assert [ep.activation for ep in eps[5:]] == ["relu"] * 4 + ["tanh"]
    dc_layers, _ = GanConfig("dcgan").layers
    assert [ep.activation for ep in generator_epilogues(dc_layers)] == \
        ["relu"] * 3 + ["tanh"]
    # as the program runs e1: negative values kept, scaled by 0.2
    from repro.core.dataflow import conv as df_conv
    e1 = ProgramSpec.build(_cfg(), 1, "generator").layers[0]
    assert (e1.activation, e1.leaky_slope) == ("leaky_relu", LEAKY_SLOPE)
    g, _ = init_gan(_cfg(), jax.random.PRNGKey(3))
    x = jax.random.uniform(jax.random.PRNGKey(4), (1, 64, 64, 3),
                           minval=-1, maxval=1)
    w, b = g["t0_w"], g["t0_b"]
    pre = _conv(x, w, 2, 1) + b
    out = df_conv(x, w, e1.strides, e1.paddings, bias=b,
                  epilogue=e1.epilogue)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(jnp.where(pre >= 0, pre,
                                                    0.2 * pre)),
                               rtol=1e-5, atol=1e-5)
    assert float(jnp.min(out)) < 0


# -- a plain jnp cross-domain step -------------------------------------------

def _conv(x, w, s, p):
    return jax.lax.conv_general_dilated(x, w, (s, s), [(p, p)] * 2,
                                        dimension_numbers=DN,
                                        precision=HIGHEST)


def _tconv(x, w, s, p):
    # out[s*i + t - p] += x[i] @ w[t]: the input dilated by s, correlated
    # with the flipped kernel over a k-1-p border
    k = w.shape[0]
    return jax.lax.conv_general_dilated(
        x, jnp.flip(w, (0, 1)), (1, 1), [(k - 1 - p, k - 1 - p)] * 2,
        lhs_dilation=(s, s), dimension_numbers=DN, precision=HIGHEST)


def _act(x, name):
    if name == "relu":
        return jnp.maximum(x, 0.0)
    if name == "leaky_relu":
        return jnp.where(x >= 0, x, 0.2 * x)
    return jnp.tanh(x) if name == "tanh" else x


def _plain_g(cfg, g, x):
    g_layers, _ = cfg.layers
    for i, lay in enumerate(g_layers):
        op = _tconv if lay.transposed else _conv
        act = ("tanh" if i == len(g_layers) - 1 else
               "relu" if lay.transposed else "leaky_relu")
        x = _act(op(x, g[f"t{i}_w"], lay.strides[0], lay.paddings[0])
                 + g[f"t{i}_b"], act)
    return x


def _plain_d(cfg, d, x):
    _, d_layers = cfg.layers
    for i, lay in enumerate(d_layers):
        x = _conv(x, d[f"c{i}_w"], lay.strides[0], lay.paddings[0]) \
            + d[f"c{i}_b"]
        x = x if i == len(d_layers) - 1 else _act(x, "leaky_relu")
    return x.reshape(x.shape[0], -1).mean(axis=-1)


def _bce(logits, target):
    return jnp.mean(jnp.maximum(logits, 0) - logits * target
                    + jnp.log1p(jnp.exp(-jnp.abs(logits))))


def _plain_step(cfg, state, a, b, lr):
    (g_ab, g_ba), (d_a, d_b) = state

    def d_loss(ds):
        x_ab, x_ba = _plain_g(cfg, g_ab, a), _plain_g(cfg, g_ba, b)
        return (_bce(_plain_d(cfg, ds[0], a), 1.0)
                + _bce(_plain_d(cfg, ds[0], x_ba), 0.0)
                + _bce(_plain_d(cfg, ds[1], b), 1.0)
                + _bce(_plain_d(cfg, ds[1], x_ab), 0.0))

    dl, dg = jax.value_and_grad(d_loss)((d_a, d_b))
    ds = jax.tree.map(lambda p, q: p - lr * q, (d_a, d_b), dg)

    def g_loss(gs):
        x_ab, x_ba = _plain_g(cfg, gs[0], a), _plain_g(cfg, gs[1], b)
        x_aba, x_bab = _plain_g(cfg, gs[1], x_ab), _plain_g(cfg, gs[0], x_ba)
        return (_bce(_plain_d(cfg, ds[1], x_ab), 1.0)
                + jnp.mean((x_aba - a) ** 2)
                + _bce(_plain_d(cfg, ds[0], x_ba), 1.0)
                + jnp.mean((x_bab - b) ** 2))

    gl, gg = jax.value_and_grad(g_loss)((g_ab, g_ba))
    gs = jax.tree.map(lambda p, q: p - lr * q, (g_ab, g_ba), gg)
    return (gs, ds), float(gl), float(dl)


def _state(cfg):
    keys = jax.random.split(jax.random.PRNGKey(7), 2)
    (g_ab, d_a), (g_ba, d_b) = (init_gan(cfg, k) for k in keys)
    return (g_ab, g_ba), (d_a, d_b)


def test_cross_domain_step_matches_a_plain_computation():
    cfg, lr, batch = _cfg(), 0.05, 2
    step, (g_prog, d_prog) = make_gan_train_step(cfg, batch, g_lr=lr)
    assert g_prog.spec.input_kind == "image"
    state = _state(cfg)
    ka, kb = jax.random.split(jax.random.PRNGKey(8))
    a = jax.random.uniform(ka, (batch, 64, 64, 3), minval=-1, maxval=1)
    b = jax.random.uniform(kb, (batch, 64, 64, 3), minval=-1, maxval=1)
    new, metrics = step(state, {"a": a, "b": b})
    assert set(metrics) == {"g_loss", "d_loss", "loss"}
    want, gl, dl = _plain_step(cfg, state, a, b, lr)
    assert float(metrics["g_loss"]) == pytest.approx(gl, rel=1e-5)
    assert float(metrics["d_loss"]) == pytest.approx(dl, rel=1e-5)
    assert float(metrics["loss"]) == pytest.approx(gl + dl, rel=1e-5)
    moved = jax.tree.map(lambda p, q: p - q, new, state)
    expect = jax.tree.map(lambda p, q: p - q, want, state)
    # each leaf's move is good to 5e-3 of its size (most agree to 1e-5;
    # a ReLU whose input f32 rounding puts on the other side of 0 moves a
    # bias gradient, a sum over every pixel, by that pixel's cotangent:
    # 1.4e-3 in G_AB's g4 here), or to the rounding of p - lr * grad in
    # f32 where the move is below p's last bits
    eps = float(jnp.finfo(jnp.float32).eps)
    for got, ref, p in zip(jax.tree.leaves(moved), jax.tree.leaves(expect),
                           jax.tree.leaves(state)):
        tol = 5e-3 * float(jnp.max(jnp.abs(ref))) \
            + 4 * eps * float(jnp.max(jnp.abs(p)))
        assert float(jnp.max(jnp.abs(got - ref))) <= tol
    # every one of the four parameter sets moved
    for params in jax.tree.leaves(moved, is_leaf=lambda t: isinstance(
            t, dict)):
        assert any(float(jnp.max(jnp.abs(v))) > 0 for v in params.values())


def test_cross_domain_step_names_its_parts_in_the_compiled_hlo():
    cfg = _cfg()
    step, (g_prog, d_prog) = make_gan_train_step(cfg, 2)
    image = jax.ShapeDtypeStruct((2, 64, 64, 3), jnp.float32)
    text = step.lower(_state(cfg), {"a": image, "b": image}) \
        .compile().as_text()
    paths = re.findall(r'op_name="([^"]*)"', text)
    assert not any("layer.proj" in p for p in paths)
    for le in g_prog.spec.layers + d_prog.spec.layers:
        backward = re.compile(rf"transpose\(.*layer\.{le.name}\b")
        assert any(backward.search(p) for p in paths), le.name
    for scope in ("gan.translate", "gan.reconstruct"):
        assert any(p.startswith("jit(train_step)/gan.g_update/")
                   and scope in p for p in paths), scope
    assert any("gan.reconstruct" in p and "layer.e1" in p
               and "transpose(" in p for p in paths)
    assert any(p.startswith("jit(train_step)/gan.sgd/") for p in paths)


def test_serving_refuses_an_image_input_generator():
    from repro.serve.gan import GanServer
    from repro.serve.gan_engine import GanEngine
    cfg = _cfg()
    g, _ = init_gan(cfg, jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="ROADMAP 2.3"):
        GanEngine(cfg, g, buckets=(1,), warmup=False)
    with pytest.raises(ValueError, match="ROADMAP 2.3"):
        GanServer(cfg, g, batch_size=1)
    # a latent program file handed in for an image config is refused too
    with pytest.raises(ValueError, match="ROADMAP 2.3"):
        GanEngine(cfg, g, buckets=(1,), warmup=False,
                  program=Program.build(GanConfig("dcgan",
                                                  channel_scale=SCALE), 1))
