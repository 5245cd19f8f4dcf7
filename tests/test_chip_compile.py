"""Compile the main path's layers for a described TPU v5e chip.

Nothing runs: the TPU compiler, installed beside JAX, compiles for a
chip that is described and not attached, and raises what the chip's
compiler would raise.  That catches what interpret mode cannot: slices
Mosaic refuses, kernels that overflow VMEM.  The topology and every
sharding built from it come from the module-scoped fixtures below, so
the TPU library is loaded only by the worker that runs this file, and
only once one of its tests has started.

* The GANAX kernel, pinned (``pallas-tpu``), at published widths and
  batch 64: the four dcgan generator tconvs and dcgan d5 (stride 1)
  compile; the three known refusals are strict xfails, so the kernel
  fix that lifts one flips its case.
* The default resolution (``DataflowPolicy()``, polyphase) compiles
  every layer of dcgan, 3dgan g4 in the packed form and 3dgan d1 with
  its input gradient in the packed form, forward and backward.
"""

import re

import pytest
import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.configs.gans import GAN_MODELS
from repro.core.dataflow import DataflowPolicy, conv, tconv

BATCH = 64


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described v5e:2x2, with libtpu's logs and the
    persistent compile cache off while this file's tests run."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — any failure means no TPU compiler
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        cache_on = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", cache_on)
            compilation_cache.reset_cache()


def _layer(model: str, name: str):
    g, d = GAN_MODELS[model]
    return next(l for l in g + d if l.name == name)


def _operands(layer, dtype, sharding):
    x = jax.ShapeDtypeStruct((BATCH, *layer.in_spatial, layer.cin), dtype,
                             sharding=sharding)
    w = jax.ShapeDtypeStruct((*layer.kernel, layer.cin, layer.cout), dtype,
                             sharding=sharding)
    return x, w


def _forward(layer, policy):
    op = tconv if layer.transposed else conv

    def f(x, w):
        return op(x, w, layer.strides, layer.paddings, policy=policy)
    return f


KERNEL = DataflowPolicy(backend="pallas-tpu", differentiable=False)
_refused = pytest.mark.xfail(strict=True, reason="refused by the chip's "
                             "compiler (ROADMAP item 2.1)")


@pytest.mark.parametrize("model,name,dtype", [
    ("dcgan", "g1", "float32"),
    ("dcgan", "g2", "float32"),
    ("dcgan", "g3", "float32"),
    ("dcgan", "g4", "float32"),
    ("dcgan", "d5", "float32"),
    # strided windows: "Only 2D gather is supported"
    pytest.param("dcgan", "d2", "float32", marks=_refused),
    # bf16 tap offsets: "cannot statically prove ... multiple of 8"
    pytest.param("dcgan", "g2", "bfloat16", marks=_refused),
    # cout=1 volumetric layer: out of VMEM at the default blocks
    pytest.param("3dgan", "g4", "float32", marks=_refused),
])
def test_pinned_kernel_compiles_for_v5e(one_chip, model, name, dtype):
    layer = _layer(model, name)
    compiled = jax.jit(_forward(layer, KERNEL)).lower(
        *_operands(layer, jnp.dtype(dtype), one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _conv_output_features(hlo: str) -> list[int]:
    """The output feature size of every convolution in an optimized HLO
    module (the dimension ``f`` of its ``dim_labels`` output)."""
    feats = []
    for shape, labels in re.findall(
            r"= \w+\[([\d,]*)\][^ ]* convolution\(.*?"
            r"dim_labels=[^_]+_[^-]+->(\w+)", hlo):
        feats.append(int(shape.split(",")[labels.index("f")]))
    return feats


_DEFAULT_LAYERS = [("dcgan", l.name) for role in GAN_MODELS["dcgan"]
                   for l in role] + [("3dgan", "g4"), ("3dgan", "d1")]


@pytest.mark.parametrize(
    "model,name", _DEFAULT_LAYERS,
    ids=[n if m == "dcgan" else f"{m}-{n}" for m, n in _DEFAULT_LAYERS])
def test_default_resolution_compiles_for_v5e(one_chip, model, name):
    """``DataflowPolicy()`` resolves to a backend the chip's compiler
    accepts, for the forward pass and both cotangents.  3dgan g4 (Cout
    1) runs in the packed form, and so does d1's (Cin 1) input gradient:
    no convolution of their modules, forward or backward, leaves the MXU
    a single output column or convolves a zero-inserted input."""
    layer = _layer(model, name)
    f = _forward(layer, DataflowPolicy())

    def loss(x, w):
        return jnp.sum(f(x, w) ** 2)

    compiled = jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).lower(
        *_operands(layer, jnp.float32, one_chip)).compile()
    hlo = compiled.as_text()
    assert "tpu_custom_call" not in hlo
    if model == "3dgan":
        feats = _conv_output_features(hlo)
        assert feats and 1 not in feats, feats
        assert "lhs_dilate" not in hlo
