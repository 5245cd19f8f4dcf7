"""`repro.quant`: mixed-precision storage and int8-weight programs.

Enforces the checked-in tolerance gates of ``repro.quant.tolerance``:
single-op forward+grad parity across every runnable backend × op kind ×
spatial rank × stride, full-generator forward+grad gates for every
Table-I model at bf16/f16, and the int8-weight export → JSON → serve
round-trip (bit-stable, planner-less, version-gated).
"""

import dataclasses
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.gans import GAN_MODELS
from repro.core.dataflow import DataflowPolicy
from repro.core.dataflow import conv as df_conv
from repro.core.dataflow import tconv as df_tconv
from repro.models.gan import GanConfig, init_gan
from repro.program import Program, ProgramSpec, load_or_build
from repro.quant import (Precision, canonical_dtype, dequantize_weight,
                         model_tolerance, op_tolerance, quantize_program,
                         quantize_weight, storage_dtype, storage_itemsize)
from repro.quant.weights import validate_quantized

# The concrete backends runnable on the CPU CI host (compiled
# pallas-tpu needs TPU hardware; its resolution path is pinned below).
RUNNABLE = ("polyphase", "zero-insert", "pallas-interpret")
DTYPES = ("bfloat16", "float16")


# ---------------------------------------------------------------------------
# Precision spec.
# ---------------------------------------------------------------------------

def test_canonical_dtype_aliases():
    for alias in ("bf16", "bfloat16"):
        assert canonical_dtype(alias) == "bfloat16"
    for alias in ("f16", "fp16", "half", "float16"):
        assert canonical_dtype(alias) == "float16"
    for alias in ("f32", "fp32", "float32"):
        assert canonical_dtype(alias) == "float32"
    assert canonical_dtype(jnp.bfloat16) == "bfloat16"


@pytest.mark.parametrize("bad", ["float64", "int8", "complex64", "nope"])
def test_unsupported_storage_dtype_raises(bad):
    with pytest.raises(ValueError, match="storage dtype"):
        canonical_dtype(bad)


def test_precision_spec():
    p = Precision("bf16")
    assert p.storage == "bfloat16"
    assert p.storage_dtype == jnp.dtype(jnp.bfloat16)
    assert p.accum_dtype == jnp.dtype(jnp.float32)
    assert p.itemsize == 2
    assert not p.is_f32
    assert Precision().is_f32
    assert "float32 accumulate" in p.describe()
    assert storage_itemsize("float32") == 4
    assert storage_dtype("float16") == jnp.dtype(jnp.float16)


def test_gan_config_canonicalizes_and_validates_dtype():
    assert GanConfig("dcgan", dtype="bf16").dtype == "bfloat16"
    assert GanConfig("dcgan").dtype == "float32"
    with pytest.raises(ValueError, match="storage dtype"):
        GanConfig("dcgan", dtype="float64")


# ---------------------------------------------------------------------------
# Single-op parity sweep: backend × kind × rank × stride × dtype.
# ---------------------------------------------------------------------------

# (kind, nd) → stride-parametrized small geometry
_GEOMS = {
    ("tconv", 2): lambda s: ((1, 4, 4, 4), (3, 3, 4, 4), (s, s), (1, 1)),
    ("tconv", 3): lambda s: ((1, 2, 3, 2, 2), (3, 3, 3, 2, 3),
                             (s, s, s), (1, 1, 1)),
    ("conv", 2): lambda s: ((1, 7, 7, 4), (3, 3, 4, 4), (s, s), (1, 1)),
    ("conv", 3): lambda s: ((1, 5, 5, 5, 2), (3, 3, 3, 2, 2),
                            (s, s, s), (1, 1, 1)),
}


def _rel_l2(a, b):
    return float(jnp.linalg.norm((a - b).ravel()) /
                 (jnp.linalg.norm(b.ravel()) + 1e-30))


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("stride", [1, 2, 3])
@pytest.mark.parametrize("kind,nd", sorted(_GEOMS))
@pytest.mark.parametrize("backend", RUNNABLE)
def test_op_parity_low_precision(backend, kind, nd, stride, dtype):
    """Forward within the checked-in (rtol, atol) of the f32 run and
    both cotangents within the relative-L2 gate, for every runnable
    backend, op kind, spatial rank, and stride."""
    xs, ws, strides, pads = _GEOMS[(kind, nd)](stride)
    policy = DataflowPolicy(backend=backend)
    op = df_tconv if kind == "tconv" else df_conv
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=xs), jnp.float32)
    w = jnp.asarray(rng.normal(size=ws), jnp.float32)
    d = jnp.dtype(storage_dtype(dtype))

    y32 = op(x, w, strides, pads, policy=policy)
    y = op(x.astype(d), w.astype(d), strides, pads, policy=policy)
    assert y.dtype == d
    rtol, atol = op_tolerance(dtype, "fwd")
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(y32), rtol=rtol, atol=atol)

    gx32, gw32 = jax.grad(
        lambda x, w: jnp.sum(op(x, w, strides, pads,
                                policy=policy) ** 2),
        argnums=(0, 1))(x, w)
    gx, gw = jax.grad(
        lambda x, w: jnp.sum(op(x.astype(d), w.astype(d), strides,
                                pads, policy=policy)
                             .astype(jnp.float32) ** 2),
        argnums=(0, 1))(x, w)
    # grads land back in the params' dtype (f32): trainable as-is
    assert gx.dtype == gw.dtype == jnp.float32
    gate = op_tolerance(dtype, "grad_rel")
    assert _rel_l2(gx, gx32) < gate, "input cotangent drift"
    assert _rel_l2(gw, gw32) < gate, "weight cotangent drift"


# ---------------------------------------------------------------------------
# Model-level gates: every Table-I generator, forward + grad.
# ---------------------------------------------------------------------------

_SCALE = 0.0625   # the calibration configuration of repro.quant.tolerance


def _grad_tree_rel(a: dict, b: dict) -> float:
    num = sum(float(jnp.sum((a[k] - b[k]) ** 2)) for k in a)
    den = sum(float(jnp.sum(b[k] ** 2)) for k in b)
    return (num / max(den, 1e-30)) ** 0.5


def _f32_reference(name, backend="polyphase"):
    cfg = GanConfig(name, channel_scale=_SCALE, backend=backend)
    g, _ = init_gan(cfg, jax.random.PRNGKey(0))
    z = jax.random.normal(jax.random.PRNGKey(1), (2, *cfg.input_shape),
                          jnp.float32)
    prog = Program.build(cfg, 2, "generator")
    y = prog.forward(g, z)
    grads = jax.grad(lambda p: jnp.sum(prog.forward(p, z) ** 2))(g)
    return cfg, g, z, y, grads


@pytest.mark.parametrize("backend", ["polyphase", "zero-insert"])
@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("name", sorted(GAN_MODELS))
def test_model_low_precision_gates(name, dtype, backend):
    """Acceptance: every Table-I generator runs forward+grad at low
    storage precision within its checked-in tolerance."""
    cfg32, g, z, y32, g32 = _f32_reference(name, backend)
    cfg = dataclasses.replace(cfg32, dtype=dtype)
    prog = Program.build(cfg, 2, "generator")
    y = prog.forward(g, z)
    assert y.dtype == storage_dtype(dtype)
    gate = model_tolerance(name, dtype)
    drift = float(jnp.max(jnp.abs(y.astype(jnp.float32) - y32)))
    assert drift < gate["output_atol"], (drift, gate)
    grads = jax.grad(lambda p: jnp.sum(
        prog.forward(p, z).astype(jnp.float32) ** 2))(g)
    assert all(v.dtype == jnp.float32 for v in grads.values())
    rel = _grad_tree_rel(grads, g32)
    assert rel < gate["grad_rel"], (rel, gate)


def test_model_bf16_pallas_interpret_kernel():
    """The kernel backend executes the bf16 program too (interpret
    mode = exact Pallas semantics): low-precision VMEM blocks, f32
    scratch accumulate, cast at the fused-epilogue flush."""
    cfg32, g, z, y32, g32 = _f32_reference("dcgan",
                                           backend="pallas-interpret")
    cfg = GanConfig("dcgan", channel_scale=_SCALE,
                    backend="pallas-interpret", dtype="bf16")
    prog = Program.build(cfg, 2, "generator")
    assert all(le.backend == "pallas-interpret"
               for le in prog.spec.layers)
    y = prog.forward(g, z)
    assert y.dtype == jnp.bfloat16
    gate = model_tolerance("dcgan", "bfloat16")
    assert float(jnp.max(jnp.abs(y.astype(jnp.float32) - y32))) < \
        gate["output_atol"]
    grads = jax.grad(lambda p: jnp.sum(
        prog.forward(p, z).astype(jnp.float32) ** 2))(g)
    assert _grad_tree_rel(grads, g32) < gate["grad_rel"]


def test_bf16_pallas_tpu_program_pins_and_round_trips():
    """Acceptance for the hardware backend on a CPU host: the bf16
    TPU program builds resolution-pinned and survives JSON with its
    precision intact."""
    for name in sorted(GAN_MODELS):
        cfg = GanConfig(name, channel_scale=_SCALE,
                        backend="pallas-tpu", dtype="bf16")
        spec = ProgramSpec.build(cfg, 2, "generator")
        assert spec.dtype == "bfloat16"
        assert all(le.backend == "pallas-tpu" and le.source == "pinned"
                   for le in spec.layers)
        again = ProgramSpec.from_json(spec.to_json())
        assert again == spec and again.dtype == "bfloat16"


def test_discriminator_logits_stay_f32():
    cfg = GanConfig("dcgan", channel_scale=_SCALE, backend="polyphase",
                    dtype="bf16")
    _, d = init_gan(cfg, jax.random.PRNGKey(0))
    img = jax.random.normal(jax.random.PRNGKey(2), (2, 64, 64, 3))
    prog = Program.build(cfg, 2, "discriminator")
    logits = prog.forward(d, img)
    assert logits.dtype == jnp.float32      # loss input: full precision
    assert logits.shape == (2,)


def test_mixed_precision_train_step_keeps_f32_state():
    """bf16 storage trains: one adversarial step; params, optimizer
    state, and gradients stay f32 end to end."""
    from repro.train.loop import make_gan_train_step
    cfg = GanConfig("dcgan", channel_scale=_SCALE, backend="polyphase",
                    dtype="bf16")
    step, _ = make_gan_train_step(cfg, batch=2)
    g, d = init_gan(cfg, jax.random.PRNGKey(0))
    z = jax.random.normal(jax.random.PRNGKey(1), (2, cfg.z_dim))
    real = jax.random.normal(jax.random.PRNGKey(2), (2, 64, 64, 3))
    (g2, d2), metrics = step((g, d), {"z": z, "real": real})
    assert all(v.dtype == jnp.float32 for v in g2.values())
    assert all(v.dtype == jnp.float32 for v in d2.values())
    assert np.isfinite(float(metrics["g_loss"]))
    assert np.isfinite(float(metrics["d_loss"]))


def test_precision_is_its_own_tuning_workload():
    """The autotuner keys plans by dtype: a bf16 layer is a different
    workload than the same geometry at f32, so tuned f32 plans never
    leak into low-precision dispatches."""
    cfg32 = GanConfig("dcgan", channel_scale=_SCALE)
    cfgbf = dataclasses.replace(cfg32, dtype="bf16")
    k32 = {k for _, k in ProgramSpec.build(cfg32, 2,
                                           "generator").plan_keys()}
    kbf = {k for _, k in ProgramSpec.build(cfgbf, 2,
                                           "generator").plan_keys()}
    assert k32 and kbf and not (k32 & kbf)
    assert {k.dtype for k in kbf} == {"bfloat16"}
    assert {k.dtype for k in k32} == {"float32"}


# ---------------------------------------------------------------------------
# int8 weight quantization.
# ---------------------------------------------------------------------------

def test_quantize_weight_round_trip():
    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.normal(size=(4, 4, 8, 16)), jnp.float32)
    q, scale = quantize_weight(w)
    assert q.dtype == np.int8 and scale.dtype == np.float32
    assert q.shape == w.shape and scale.shape == (16,)
    assert int(np.abs(q).max()) <= 127
    back = dequantize_weight(q, scale, "float32")
    # per-channel symmetric: error bounded by scale/2 per element
    assert np.max(np.abs(np.asarray(back) - np.asarray(w)) /
                  scale.reshape(1, 1, 1, -1)) <= 0.5 + 1e-6


def test_quantize_weight_zero_channel_and_rank_guard():
    w = jnp.zeros((3, 3, 2, 4), jnp.float32)
    q, scale = quantize_weight(w)
    assert np.all(scale == 1.0) and np.all(q == 0)
    with pytest.raises(ValueError, match="rank"):
        quantize_weight(jnp.zeros((7,), jnp.float32))


def test_validate_quantized_rejects_corrupt_payloads():
    cfg = GanConfig("dcgan", channel_scale=_SCALE)
    g, _ = init_gan(cfg, jax.random.PRNGKey(0))
    spec = quantize_program(ProgramSpec.build(cfg, 2, "generator"), g)
    blob = json.loads(json.dumps(spec.quantized_params))
    validate_quantized(blob)                       # the good one passes
    bad = dict(blob, scheme="int4-groupwise")
    with pytest.raises(ValueError, match="scheme"):
        validate_quantized(bad)
    bad = json.loads(json.dumps(blob))
    first = next(k for k, v in bad["params"].items()
                 if v["kind"] == "int8")
    bad["params"][first]["values"]["data"] = "AAAA"  # truncated payload
    with pytest.raises(ValueError):
        validate_quantized(bad)


def test_quantize_program_wants_covering_params():
    cfg = GanConfig("dcgan", channel_scale=_SCALE)
    g, _ = init_gan(cfg, jax.random.PRNGKey(0))
    spec = ProgramSpec.build(cfg, 2, "generator")
    incomplete = {k: v for k, v in g.items() if k != "t0_w"}
    with pytest.raises(ValueError, match="t0_w"):
        quantize_program(spec, incomplete)


@pytest.mark.parametrize("name", sorted(GAN_MODELS))
def test_int8_forward_gate_every_model(name):
    """The int8-weight export stays within its checked-in forward
    tolerance for every Table-I model (weights dequantized into the
    program's storage dtype at load)."""
    cfg32, g, z, y32, _ = _f32_reference(name)
    spec = quantize_program(
        ProgramSpec.build(cfg32, 2, "generator"), g)
    loaded = ProgramSpec.from_json(json.loads(json.dumps(
        spec.to_json())))
    prog = Program(loaded)
    assert prog.quantized
    params = prog.params
    y = prog.forward(params, z)
    gate = model_tolerance(name, "int8")["output_atol"]
    drift = float(jnp.max(jnp.abs(y.astype(jnp.float32) - y32)))
    assert drift < gate, (drift, gate)
    # serving artifact: bit-stable across replays
    np.testing.assert_array_equal(np.asarray(prog.forward(params, z)),
                                  np.asarray(y))


def test_int8_export_round_trip_and_versioning(tmp_path):
    cfg = GanConfig("dcgan", channel_scale=_SCALE, dtype="bf16")
    g, _ = init_gan(cfg, jax.random.PRNGKey(0))
    spec = quantize_program(ProgramSpec.build(cfg, 2, "generator"), g)
    path = tmp_path / "prog.json"
    spec.save(path)
    doc = json.loads(path.read_text())
    assert doc["version"] == 3
    assert doc["dtype"] == "bfloat16"
    assert doc["quantized_params"]["scheme"].startswith("int8")
    loaded = ProgramSpec.load(path)
    assert loaded == spec
    prog = Program(loaded)
    # weights dequantize into the storage dtype; biases stay raw f32
    params = prog.params
    assert params["t0_w"].dtype == jnp.bfloat16
    assert params["t0_b"].dtype == jnp.float32
    assert "quant=int8" in repr(prog)
    assert "quant=int8" in loaded.describe()


def test_old_program_versions_load_as_f32_unquantized(tmp_path):
    """v1/v2 files predate the precision subsystem: they must load as
    float32 with no quantized payload (forward-compatible fields are
    ignored, not misread)."""
    cfg = GanConfig("dcgan", channel_scale=_SCALE)
    doc = ProgramSpec.build(cfg, 2, "generator").to_json()
    for version in (1, 2):
        old = json.loads(json.dumps(doc))
        old["version"] = version
        if version == 1:
            old.pop("mesh", None)
        # a v1/v2 writer never emitted these fields
        old.pop("dtype", None)
        old.pop("quantized_params", None)
        spec = ProgramSpec.from_json(old)
        assert spec.dtype == "float32"
        assert spec.quantized_params is None


def test_precision_drift_rebuilds_from_config(tmp_path):
    """dtype is part of the geometry signature: a program frozen at
    one storage precision must not serve a config wanting another."""
    cfg_bf = GanConfig("dcgan", channel_scale=_SCALE, dtype="bf16")
    path = tmp_path / "prog.json"
    ProgramSpec.build(cfg_bf, 2, "generator").save(path)
    cfg_f32 = GanConfig("dcgan", channel_scale=_SCALE)
    prog, loaded = load_or_build(path, cfg_f32, 2, "generator")
    assert not loaded
    assert prog.spec.dtype == "float32"


def test_int8_program_serves_planner_less_process(tmp_path):
    """Acceptance: the quantized export serves on a fresh process with
    zero planner measurements and zero extra inputs — the embedded
    weights are the parameters."""
    cfg = GanConfig("dcgan", channel_scale=_SCALE, dtype="bf16")
    g, _ = init_gan(cfg, jax.random.PRNGKey(0))
    spec = quantize_program(ProgramSpec.build(cfg, 2, "generator"), g)
    path = tmp_path / "prog.json"
    spec.save(path)
    code = f"""
import jax, numpy as np, jax.numpy as jnp
from repro.program import Program, ProgramSpec
from repro.tune import Planner, set_planner

planner = set_planner(Planner())      # would record any consult
spec = ProgramSpec.load({str(path)!r})
prog = Program(spec)
assert prog.quantized
z = jax.random.normal(jax.random.PRNGKey(1), (2, 100))
img = prog.apply(prog.params, z)
assert img.shape == (2, 64, 64, 3), img.shape
assert img.dtype == jnp.bfloat16, img.dtype
again = prog.apply(prog.params, z)
assert (np.asarray(img) == np.asarray(again)).all()
assert planner.measurements == 0, planner.measurements
assert planner.lookups == 0, planner.lookups
print("SERVED-INT8")
"""
    root = Path(__file__).resolve().parent.parent
    env = dict(os.environ,
               PYTHONPATH=f"{root / 'src'}:"
                          f"{os.environ.get('PYTHONPATH', '')}",
               JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", code],
                         capture_output=True, text=True,
                         cwd=str(root), env=env)
    assert out.returncode == 0, out.stderr
    assert "SERVED-INT8" in out.stdout


def test_gan_server_serves_quantized_program(tmp_path):
    """The documented int8 deploy flow: export → load → GanServer with
    g_params=None adopts the program's precision and embedded
    weights."""
    from repro.serve.gan import GanServer
    cfg = GanConfig("dcgan", channel_scale=_SCALE, dtype="bf16")
    g, _ = init_gan(cfg, jax.random.PRNGKey(0))
    spec = quantize_program(ProgramSpec.build(cfg, 2, "generator"), g)
    path = tmp_path / "prog.json"
    spec.save(path)
    prog = Program(ProgramSpec.load(path))
    srv = GanServer(GanConfig("dcgan", channel_scale=_SCALE), None,
                    batch_size=2, program=prog)
    assert srv.cfg.dtype == "bfloat16"    # adopted from the program
    imgs = srv.generate(3)
    assert imgs.shape == (3, 64, 64, 3)
    assert srv.samples_buffered == 1
    with pytest.raises(ValueError, match="quantized"):
        GanServer(cfg, None, batch_size=2)
