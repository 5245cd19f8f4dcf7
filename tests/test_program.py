"""`repro.program`: ahead-of-time compiled GAN executables.

Pins the API-redesign contract: bit-parity with the legacy per-call
dispatch threading on every runnable backend, one traced executable per
program (zero per-call re-resolution), JSON round-trip including
tuned-plan export to a planner-less process, and stale/corrupt program
files degrading to fresh resolution.
"""

import json
import os
import re
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
from repro.models.gan import (GanConfig, discriminator_epilogues,
                              generator_epilogues, init_gan)
from repro.program import (PROGRAM_FORMAT_VERSION, Program, ProgramSpec,
                           load_or_build)
from repro.tune import Plan, Planner, set_planner
from repro.tune.zoo import layer_plan_keys


@pytest.fixture(autouse=True)
def _isolated_planner():
    set_planner(None)
    yield
    set_planner(None)


# The concrete backends runnable on the CPU CI host (compiled
# pallas-tpu needs TPU hardware; its resolution path is pinned below).
RUNNABLE = ("polyphase", "zero-insert", "pallas-interpret")


def _legacy_generator_apply(params, z, cfg, policy):
    """The pre-Program per-call threading, verbatim: re-resolves
    config → policy → epilogues at every call site."""
    g_layers, _ = cfg.layers
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
        x = op(x, params[f"t{i}_w"], l.strides, l.paddings,
               policy=policy, bias=params[f"t{i}_b"], epilogue=ep)
    return x


def _legacy_discriminator_apply(params, img, cfg, policy):
    _, d_layers = cfg.layers
    x = img
    for i, (l, ep) in enumerate(zip(d_layers,
                                    discriminator_epilogues(d_layers))):
        x = df_conv(x, params[f"c{i}_w"], l.strides, l.paddings,
                    policy=policy, bias=params[f"c{i}_b"], epilogue=ep)
    return x.reshape(img.shape[0], -1).mean(axis=-1)


# ---------------------------------------------------------------------------
# Bit-parity vs the legacy path.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["polyphase", "zero-insert"])
@pytest.mark.parametrize("name", sorted(GAN_MODELS))
def test_program_matches_legacy_every_model(name, backend):
    """Acceptance: Program.apply is bit-identical to the legacy
    generator_apply threading for every Table-I model."""
    cfg = GanConfig(name=name, channel_scale=0.0625, backend=backend)
    g, _ = init_gan(cfg, jax.random.PRNGKey(0))
    z = jax.random.normal(jax.random.PRNGKey(1), (2, *cfg.input_shape))
    prog = Program.build(cfg, 2, "generator")
    legacy = _legacy_generator_apply(g, z, cfg, cfg.policy)
    np.testing.assert_array_equal(np.asarray(prog.apply(g, z)),
                                  np.asarray(legacy))


def test_program_matches_legacy_pallas_interpret():
    """The kernel backend (interpret mode on CPU): same contract."""
    cfg = GanConfig(name="dcgan", channel_scale=0.03125,
                    backend="pallas-interpret")
    g, d = init_gan(cfg, jax.random.PRNGKey(0))
    z = jax.random.normal(jax.random.PRNGKey(1), (1, cfg.z_dim))
    prog = Program.build(cfg, 1, "generator")
    assert all(le.backend == "pallas-interpret"
               for le in prog.spec.layers)
    img = prog.apply(g, z)
    np.testing.assert_array_equal(
        np.asarray(img),
        np.asarray(_legacy_generator_apply(g, z, cfg, cfg.policy)))
    d_prog = Program.build(cfg, 1, "discriminator")
    np.testing.assert_array_equal(
        np.asarray(d_prog.apply(d, img)),
        np.asarray(_legacy_discriminator_apply(d, img, cfg,
                                               cfg.policy)))


def test_discriminator_program_matches_legacy():
    cfg = GanConfig(name="dcgan", channel_scale=0.0625)
    _, d = init_gan(cfg, jax.random.PRNGKey(0))
    img = jax.random.normal(jax.random.PRNGKey(2), (2, 64, 64, 3))
    prog = Program.build(cfg, 2, "discriminator")
    np.testing.assert_array_equal(
        np.asarray(prog.apply(d, img)),
        np.asarray(_legacy_discriminator_apply(d, img, cfg,
                                               cfg.policy)))


def test_pallas_tpu_program_builds_and_round_trips():
    """A TPU-pinned program can't execute on this host, but its spec
    must build, describe, and survive JSON — that is the shippable
    artifact a TPU box loads."""
    cfg = GanConfig(name="dcgan", channel_scale=0.0625,
                    backend="pallas-tpu")
    spec = ProgramSpec.build(cfg, 8, "generator")
    assert all(le.backend == "pallas-tpu" and le.source == "pinned"
               for le in spec.layers)
    assert ProgramSpec.from_json(spec.to_json()) == spec
    assert "pallas-tpu" in spec.describe()


# ---------------------------------------------------------------------------
# One traced executable per program; zero per-call re-resolution.
# ---------------------------------------------------------------------------

def test_single_trace_per_shape():
    cfg = GanConfig(name="dcgan", channel_scale=0.03125)
    g, _ = init_gan(cfg, jax.random.PRNGKey(0))
    prog = Program.build(cfg, 2, "generator")
    z = jax.random.normal(jax.random.PRNGKey(1), (2, cfg.z_dim))
    for _ in range(3):
        prog.apply(g, z)
    assert prog.traces == 1
    # a new batch shape is a retrace of the same frozen records,
    # not a rebuild — the planning batch doesn't constrain apply
    prog.apply(g, jax.random.normal(jax.random.PRNGKey(2),
                                    (5, cfg.z_dim)))
    assert prog.traces == 2


def test_auto_program_resolves_once_not_per_call():
    """backend='auto' resolution happens at build: the planner is
    consulted once per layer, and repeated apply calls (and retraces)
    never touch it again."""
    planner = set_planner(Planner())
    cfg = GanConfig(name="dcgan", channel_scale=0.03125, backend="auto")
    g, _ = init_gan(cfg, jax.random.PRNGKey(0))
    g_layers, _ = cfg.layers
    prog = Program.build(cfg, 2, "generator")    # lookups, no measuring
    assert planner.lookups == len(g_layers)
    assert planner.measurements == 0
    z = jax.random.normal(jax.random.PRNGKey(1), (2, cfg.z_dim))
    for _ in range(3):
        prog.apply(g, z)
    prog.apply(g, jax.random.normal(jax.random.PRNGKey(2),
                                    (4, cfg.z_dim)))
    assert planner.lookups == len(g_layers)      # unchanged
    assert prog.traces == 2


def test_program_jaxpr_is_resolution_free():
    """The traced computation is pure array ops on the frozen records —
    building the jaxpr works with no planner in the process at all."""
    cfg = GanConfig(name="dcgan", channel_scale=0.03125)
    g, _ = init_gan(cfg, jax.random.PRNGKey(0))
    prog = Program.build(cfg, 2, "generator")
    z = jnp.zeros((2, cfg.z_dim), jnp.float32)
    jaxpr = jax.make_jaxpr(prog.forward)(g, z)
    assert len(jaxpr.jaxpr.eqns) > 0
    from repro.tune import get_planner
    assert get_planner(create=False) is None


# ---------------------------------------------------------------------------
# Differentiability (training path).
# ---------------------------------------------------------------------------

def test_program_forward_is_differentiable():
    cfg = GanConfig(name="dcgan", channel_scale=0.03125,
                    backend="pallas-interpret")
    g, _ = init_gan(cfg, jax.random.PRNGKey(0))
    prog = Program.build(cfg, 1, "generator")
    z = jax.random.normal(jax.random.PRNGKey(1), (1, cfg.z_dim))

    def loss(g):
        return jnp.sum(prog.forward(g, z) ** 2)

    grads = jax.grad(loss)(g)
    assert set(grads) == set(g)
    assert all(np.isfinite(np.asarray(v)).all() for v in grads.values())


def test_make_gan_train_step_builds_programs_once():
    from repro.train.loop import make_gan_train_step
    cfg = GanConfig(name="dcgan", channel_scale=0.03125)
    g, d = init_gan(cfg, jax.random.PRNGKey(0))
    step, (g_prog, d_prog) = make_gan_train_step(cfg, 2, g_lr=1e-3)
    assert g_prog.spec.role == "generator"
    assert d_prog.spec.role == "discriminator"
    batch = {"z": jax.random.normal(jax.random.PRNGKey(1),
                                    (2, cfg.z_dim)),
             "real": jnp.zeros((2, 64, 64, 3), jnp.float32)}
    state, metrics = step((g, d), batch)
    state, metrics = step(state, batch)
    assert np.isfinite(float(metrics["loss"]))
    # the step embeds the programs' forward, not their jitted apply
    assert g_prog.traces == 0


@pytest.mark.parametrize("name", ["dcgan", "3dgan"])
def test_train_step_names_every_layer_in_the_compiled_hlo(name):
    """Each ``LayerExec``'s ops carry its ``layer.<name>`` scope in the
    compiled step's ``op_name`` metadata, forward and backward, and D's
    layers run under both update phases."""
    from repro.train.loop import make_gan_train_step
    cfg = GanConfig(name=name, channel_scale=0.03125)
    step, (g_prog, d_prog) = make_gan_train_step(cfg, 2, g_lr=1e-3)
    state = init_gan(cfg, jax.random.PRNGKey(0))
    image = d_prog.spec.layers[0]
    batch = {"z": jax.ShapeDtypeStruct((2, cfg.z_dim), jnp.float32),
             "real": jax.ShapeDtypeStruct(
                 (2, *image.in_spatial, image.cin), jnp.float32)}
    text = step.lower(state, batch).compile().as_text()
    paths = re.findall(r'op_name="([^"]*)"', text)
    names = ["proj"] + [le.name for le in g_prog.spec.layers
                        + d_prog.spec.layers]
    for layer in names:
        assert any(f"jvp(layer.{layer})" in p for p in paths), layer
        assert any(f"transpose(jvp(layer.{layer}))" in p
                   for p in paths), layer
    for le in d_prog.spec.layers:
        for phase in ("gan.d_update/", "gan.g_update/"):
            assert any(p.startswith(f"jit(train_step)/{phase}")
                       and f"(layer.{le.name})" in p for p in paths), \
                (phase, le.name)
    assert any(p.startswith("jit(train_step)/gan.sgd/") for p in paths)


# ---------------------------------------------------------------------------
# JSON round-trip and export.
# ---------------------------------------------------------------------------

def _tuned_spec(cfg, batch=2):
    """A spec whose plans came from the autotuner: pallas-interpret with
    explicit block shapes on every generator layer."""
    planner = Planner()
    g_layers, _ = cfg.layers
    for _, key in layer_plan_keys(g_layers, batch=batch,
                                  epilogues=generator_epilogues(
                                      g_layers)):
        planner.put(key, Plan(backend="pallas-interpret", blocks=None,
                              measured_us=7.0))
    return ProgramSpec.build(cfg, batch, "generator",
                             policy=DataflowPolicy(backend="auto"),
                             planner=planner)


def test_tuned_spec_json_round_trip():
    cfg = GanConfig(name="dcgan", channel_scale=0.03125)
    spec = _tuned_spec(cfg)
    assert all(le.source == "tuned" and le.measured_us == 7.0
               for le in spec.layers)
    doc = json.loads(json.dumps(spec.to_json()))   # through real JSON
    spec2 = ProgramSpec.from_json(doc)
    assert spec2 == spec
    g, _ = init_gan(cfg, jax.random.PRNGKey(0))
    z = jax.random.normal(jax.random.PRNGKey(1), (2, cfg.z_dim))
    np.testing.assert_array_equal(
        np.asarray(Program(spec).apply(g, z)),
        np.asarray(Program(spec2).apply(g, z)))


def test_tuned_blocks_survive_round_trip(tmp_path):
    """Explicit Pallas tile shapes are part of the exported program."""
    cfg = GanConfig(name="dcgan", channel_scale=0.03125)
    planner = Planner()
    g_layers, _ = cfg.layers
    keys = layer_plan_keys(g_layers, batch=1,
                           epilogues=generator_epilogues(g_layers))
    # g1: 4x4 -> 8x8, stride 2: phase-plane qy=4; cin=32*scale=1? use
    # known-valid divisors from the layer channels
    first = g_layers[0]
    planner.put(keys[0][1], Plan(backend="pallas-interpret",
                                 blocks=(2, first.cin, first.cout)))
    spec = ProgramSpec.build(cfg, 1, "generator",
                             policy=DataflowPolicy(backend="auto"),
                             planner=planner)
    assert spec.layers[0].blocks == (2, first.cin, first.cout)
    path = tmp_path / "prog.json"
    spec.save(path)
    loaded = ProgramSpec.load(path)
    assert loaded == spec
    g, _ = init_gan(cfg, jax.random.PRNGKey(0))
    z = jax.random.normal(jax.random.PRNGKey(1), (1, cfg.z_dim))
    ref = Program.build(cfg, 1, "generator").apply(g, z)
    np.testing.assert_allclose(np.asarray(Program(loaded).apply(g, z)),
                               np.asarray(ref), atol=1e-5, rtol=1e-5)


def test_exported_program_serves_planner_less_process(tmp_path):
    """Acceptance: to_json → from_json → apply on a fresh process with
    no planner measurements — the measurement counter stays 0 and no
    process-wide planner is even created."""
    cfg = GanConfig(name="dcgan", channel_scale=0.03125)
    spec = _tuned_spec(cfg)
    path = tmp_path / "prog.json"
    spec.save(path)
    code = f"""
import jax, numpy as np
from repro.models.gan import GanConfig, init_gan
from repro.program import Program, ProgramSpec
from repro.tune import Planner, get_planner, set_planner

planner = set_planner(Planner())      # would record any consult
spec = ProgramSpec.load({str(path)!r})
cfg = GanConfig(name="dcgan", channel_scale=0.03125)
g, _ = init_gan(cfg, jax.random.PRNGKey(0))
prog = Program(spec)
img = prog.apply(g, jax.random.normal(jax.random.PRNGKey(1),
                                      (2, cfg.z_dim)))
assert img.shape == (2, 64, 64, 3), img.shape
assert all(le.source == "tuned" for le in spec.layers)
assert planner.measurements == 0, planner.measurements
assert planner.lookups == 0, planner.lookups
print("SERVED", planner.measurements, planner.lookups)
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
    assert "SERVED 0 0" in out.stdout


# ---------------------------------------------------------------------------
# Stale / corrupt program files degrade to fresh resolution.
# ---------------------------------------------------------------------------

CFG = dict(name="dcgan", channel_scale=0.03125)


def _assert_rebuilt(path, cfg=None):
    cfg = cfg or GanConfig(**CFG)
    prog, loaded = load_or_build(path, cfg, 2, "generator")
    assert not loaded
    assert len(prog.spec.layers) == len(cfg.layers[0])
    g, _ = init_gan(cfg, jax.random.PRNGKey(0))
    img = prog.apply(g, jax.random.normal(jax.random.PRNGKey(1),
                                          (2, cfg.z_dim)))
    assert img.shape[0] == 2
    return prog


def test_missing_program_file_builds_fresh(tmp_path):
    _assert_rebuilt(tmp_path / "nope.json")


def test_corrupt_program_file_builds_fresh(tmp_path):
    path = tmp_path / "prog.json"
    path.write_text("{not json")
    _assert_rebuilt(path)


def test_wrong_version_builds_fresh(tmp_path):
    cfg = GanConfig(**CFG)
    doc = ProgramSpec.build(cfg, 2, "generator").to_json()
    doc["version"] = PROGRAM_FORMAT_VERSION + 1
    path = tmp_path / "prog.json"
    path.write_text(json.dumps(doc))
    _assert_rebuilt(path)


def test_unknown_backend_builds_fresh(tmp_path):
    cfg = GanConfig(**CFG)
    doc = ProgramSpec.build(cfg, 2, "generator").to_json()
    doc["layers"][0]["backend"] = "systolic-array-9000"
    path = tmp_path / "prog.json"
    path.write_text(json.dumps(doc))
    _assert_rebuilt(path)


def test_stale_blocks_build_fresh(tmp_path):
    cfg = GanConfig(**CFG)
    doc = ProgramSpec.build(cfg, 2, "generator").to_json()
    doc["layers"][0]["backend"] = "pallas-interpret"
    doc["layers"][0]["blocks"] = [3, 7, 11]   # divides nothing
    path = tmp_path / "prog.json"
    path.write_text(json.dumps(doc))
    _assert_rebuilt(path)


def test_geometry_drift_builds_fresh(tmp_path):
    """A program frozen for one channel scale must not serve a config
    built at another — that is workload drift, not a valid program."""
    other = GanConfig(name="dcgan", channel_scale=0.0625)
    path = tmp_path / "prog.json"
    ProgramSpec.build(other, 2, "generator").save(path)
    prog = _assert_rebuilt(path)
    assert prog.spec.channel_scale == 0.03125


def test_corrupt_epilogue_fields_build_fresh(tmp_path):
    """from_json validates hard: a file with an unknown activation or a
    bias layer missing its param name must fail at load (and so degrade
    via load_or_build), not at first trace."""
    cfg = GanConfig(**CFG)
    doc = ProgramSpec.build(cfg, 2, "generator").to_json()
    bad_act = json.loads(json.dumps(doc))
    bad_act["layers"][0]["activation"] = "gelu"
    with pytest.raises(ValueError, match="activation"):
        ProgramSpec.from_json(bad_act)
    bad_bias = json.loads(json.dumps(doc))
    bad_bias["layers"][0]["b_param"] = None
    with pytest.raises(ValueError, match="b_param"):
        ProgramSpec.from_json(bad_bias)
    path = tmp_path / "prog.json"
    path.write_text(json.dumps(bad_act))
    _assert_rebuilt(path)


def test_good_program_file_loads(tmp_path):
    cfg = GanConfig(**CFG)
    spec = ProgramSpec.build(
        cfg, 2, "generator",
        policy=DataflowPolicy(backend="zero-insert"))
    path = tmp_path / "prog.json"
    spec.save(path)
    prog, loaded = load_or_build(path, cfg, 2, "generator")
    assert loaded
    # the file's resolution wins over what the config would pick now
    assert all(le.backend == "zero-insert" for le in prog.spec.layers)


# ---------------------------------------------------------------------------
# CLI.
# ---------------------------------------------------------------------------

def test_cli_describe_export_load(tmp_path):
    root = Path(__file__).resolve().parent.parent
    env = dict(os.environ,
               PYTHONPATH=f"{root / 'src'}:"
                          f"{os.environ.get('PYTHONPATH', '')}",
               JAX_PLATFORMS="cpu")
    path = tmp_path / "prog.json"
    out = subprocess.run(
        [sys.executable, "-m", "repro.program", "dcgan",
         "--channel-scale", "0.0625", "--role", "generator",
         "--export", str(path)],
        capture_output=True, text=True, cwd=str(root), env=env)
    assert out.returncode == 0, out.stderr
    assert "program dcgan/generator" in out.stdout
    assert path.exists()
    out2 = subprocess.run(
        [sys.executable, "-m", "repro.program", "dcgan",
         "--channel-scale", "0.0625", "--load", str(path)],
        capture_output=True, text=True, cwd=str(root), env=env)
    assert out2.returncode == 0, out2.stderr
    assert "program dcgan/generator" in out2.stdout
    assert "rebuilt" not in out2.stdout


# ---------------------------------------------------------------------------
# Guard rails.
# ---------------------------------------------------------------------------

def test_bad_role_raises():
    cfg = GanConfig(**CFG)
    with pytest.raises(ValueError, match="role"):
        ProgramSpec.build(cfg, 2, "critic")


def test_server_rejects_wrong_role_program():
    from repro.serve.gan import GanServer
    cfg = GanConfig(**CFG)
    g, _ = init_gan(cfg, jax.random.PRNGKey(0))
    d_prog = Program.build(cfg, 2, "discriminator")
    with pytest.raises(ValueError, match="generator"):
        GanServer(cfg, g, batch_size=2, program=d_prog)


def test_server_rejects_mismatched_workload_program():
    """A program frozen for a different model (or scaling) of the served
    config fails at construction with a clear error, not as a shape
    mismatch inside the first generate() trace."""
    from repro.serve.gan import GanServer
    cfg = GanConfig(**CFG)
    g, _ = init_gan(cfg, jax.random.PRNGKey(0))
    other = Program.build(GanConfig(name="gpgan", channel_scale=0.03125),
                          2, "generator")
    with pytest.raises(ValueError, match="different workload"):
        GanServer(cfg, g, batch_size=2, program=other)
    scaled = Program.build(GanConfig(name="dcgan", channel_scale=0.0625),
                           2, "generator")
    with pytest.raises(ValueError, match="different workload"):
        GanServer(cfg, g, batch_size=2, program=scaled)


def test_cli_measure_exports_tuned_program(tmp_path):
    """--backend auto --measure tunes plan misses at build, so the
    exported file carries tuned (not heuristic) layer resolutions."""
    from repro.program.__main__ import main
    plans = tmp_path / "plans.json"
    path = tmp_path / "prog.json"
    rc = main(["dcgan", "--channel-scale", "0.03125", "--batch", "2",
               "--role", "generator", "--backend", "auto",
               "--plans", str(plans), "--measure",
               "--export", str(path)])
    assert rc == 0
    spec = ProgramSpec.load(path)
    assert all(le.source == "tuned" for le in spec.layers)
    assert plans.exists()   # measured plans persisted for reuse
