"""Mesh-sharded GAN programs: the frozen ``(data, model)`` mesh and
per-layer sharding in :class:`~repro.program.ProgramSpec`, shard_map
replay parity with single-device execution, sharded serving, and the
data-parallel train step.

Three tiers:

* plain in-process tests (spec round-trip, version gating, the
  footprint heuristic, oversized-mesh degradation on this process's
  single device);
* in-process multi-device tests, skipped unless the process already
  sees >= 8 devices — CI runs this file a second time under
  ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` to light
  these up;
* ``slow`` subprocess tests via ``conftest.run_forced_devices`` for
  the scenarios that need a forced device count regardless of how
  pytest was launched (all-model parity, exported-program serving,
  DP gradient parity).
"""

import numpy as np
import jax
import pytest
from conftest import run_forced_devices

from repro import obs as _obs
from repro.core.dataflow import COUT_SHARD_MIN_BYTES, choose_layer_sharding
from repro.launch.mesh import make_local_mesh
from repro.models.gan import GanConfig, init_gan
from repro.program import Program, ProgramSpec

SCALE = 0.0625


def _cfg(name="dcgan", **kw):
    return GanConfig(name=name, channel_scale=SCALE, **kw)


needs8 = pytest.mark.skipif(
    jax.device_count() < 8,
    reason="needs 8 devices (run under the CI forced-device entry)")


# -- the footprint heuristic ------------------------------------------------

def test_choose_layer_sharding_heuristic():
    # no model axis, or Cout not divisible -> batch-only
    assert choose_layer_sharding((4, 4), 512, 512, 1) == "data"
    assert choose_layer_sharding((4, 4), 512, 511, 2) == "data"
    # 4*4*512*512*4 bytes == the 16 MiB threshold exactly -> shard
    assert 4 * 4 * 512 * 512 * 4 == COUT_SHARD_MIN_BYTES
    assert choose_layer_sharding((4, 4), 512, 512, 2) == "cout"
    # below the footprint threshold the all-gather isn't worth it
    assert choose_layer_sharding((4, 4), 8, 8, 2) == "data"
    # ...unless the threshold is overridden (tests force small configs)
    assert choose_layer_sharding((4, 4), 8, 8, 2, min_bytes=0) == "cout"


# -- spec: frozen mesh + sharding, JSON round-trip, version gating ----------

def test_spec_freezes_mesh_and_layer_sharding():
    spec = ProgramSpec.build(_cfg(), 8, mesh=(4, 2),
                             cout_shard_min_bytes=0)
    assert spec.mesh == (4, 2)
    shardings = [le.sharding for le in spec.layers]
    assert "cout" in shardings          # forced by min_bytes=0
    for le in spec.layers:
        if le.sharding == "cout":
            assert le.cout % 2 == 0
    assert "mesh=4x2" in spec.describe()
    assert "@cout" in spec.describe()


def test_cfg_mesh_is_build_default():
    spec = ProgramSpec.build(_cfg(mesh=(2, 1)), 4)
    assert spec.mesh == (2, 1)
    # explicit mesh=None overrides a config that carries one
    spec = ProgramSpec.build(_cfg(mesh=(2, 1)), 4, mesh=None)
    assert spec.mesh is None


def test_meshed_spec_json_round_trip(tmp_path):
    spec = ProgramSpec.build(_cfg(), 8, mesh=(4, 2),
                             cout_shard_min_bytes=0)
    assert ProgramSpec.from_json(spec.to_json()) == spec
    spec.save(tmp_path / "prog.json")
    assert ProgramSpec.load(tmp_path / "prog.json") == spec


def test_v1_document_loads_single_device():
    """Pre-mesh (version-1) program files still load: mesh defaults to
    None and every layer to batch-only sharding."""
    doc = ProgramSpec.build(_cfg(), 8).to_json()
    doc["version"] = 1
    del doc["mesh"]
    for layer in doc["layers"]:
        del layer["sharding"]
    loaded = ProgramSpec.from_json(doc)
    assert loaded.mesh is None
    assert all(le.sharding == "data" for le in loaded.layers)


def test_mesh_validation_rejects_corrupt_documents():
    spec = ProgramSpec.build(_cfg(), 8, mesh=(4, 2),
                             cout_shard_min_bytes=0)
    doc = spec.to_json()
    bad = dict(doc, mesh=[4])
    with pytest.raises(ValueError, match="mesh"):
        ProgramSpec.from_json(bad)
    # a Cout-sharded layer without a model axis must not load
    bad = dict(doc, mesh=None)
    with pytest.raises(ValueError, match="model axis"):
        ProgramSpec.from_json(bad)
    bad = dict(doc, layers=[dict(doc["layers"][0], sharding="weird")]
               + doc["layers"][1:])
    with pytest.raises(ValueError, match="sharding"):
        ProgramSpec.from_json(bad)


# -- local mesh construction ------------------------------------------------

def test_make_local_mesh_forms():
    n = jax.device_count()
    m = make_local_mesh()
    model = next((f for f in (4, 2) if n % f == 0), 1)
    assert dict(m.shape) == {"data": n // model, "model": model}
    # data-only convenience: pure DP, model axis of 1
    assert dict(make_local_mesh(data=1).shape) == {"data": 1, "model": 1}
    with pytest.raises(ValueError, match="does not divide"):
        make_local_mesh(model=2 * n)
    with pytest.raises(ValueError, match="needs"):
        make_local_mesh(data=n + 1, model=1)


@pytest.mark.slow
def test_make_local_mesh_odd_count_falls_back_to_model_1():
    """The documented no-argument fallback: an odd device count puts
    every device on the data axis (model=1) instead of crashing."""
    run_forced_devices("""
        from repro.launch.mesh import make_local_mesh
        m = make_local_mesh()
        assert dict(m.shape) == {"data": 7, "model": 1}, dict(m.shape)
        assert dict(make_local_mesh(data=7).shape) == \\
            {"data": 7, "model": 1}
        print("PASS")
    """, n_devices=7)


# -- oversized mesh degrades (the 1-device side of the export contract) ----

def test_oversized_mesh_degrades_with_warning(tmp_path):
    """An exported (4,2)-mesh program loaded on a 1-device box warns,
    runs single-device, and produces the same samples."""
    if jax.device_count() >= 8:
        pytest.skip("needs a device-starved process")
    cfg = _cfg()
    spec = ProgramSpec.build(cfg, 8, mesh=(4, 2), cout_shard_min_bytes=0)
    spec.save(tmp_path / "prog.json")
    loaded = ProgramSpec.load(tmp_path / "prog.json")
    before = _obs.counter("program.mesh_degraded").value
    with pytest.warns(RuntimeWarning, match="degrading"):
        prog = Program(loaded)
    assert _obs.counter("program.mesh_degraded").value == before + 1
    assert prog.mesh is None
    assert prog.device_count == 1
    assert prog.input_sharding is None
    assert prog.mesh_str == "1"
    g, _ = init_gan(cfg, jax.random.PRNGKey(0))
    z = jax.random.normal(jax.random.PRNGKey(1), (8, cfg.z_dim))
    ref = Program.build(cfg, 8, mesh=None)
    np.testing.assert_allclose(np.asarray(prog.apply(g, z)),
                               np.asarray(ref.apply(g, z)), atol=1e-6)


# -- in-process multi-device tests (CI forced-device matrix entry) ----------

@needs8
def test_sharded_forward_parity_inprocess():
    cfg = _cfg()
    g, _ = init_gan(cfg, jax.random.PRNGKey(0))
    z = jax.random.normal(jax.random.PRNGKey(1), (8, cfg.z_dim))
    plain = Program.build(cfg, 8, mesh=None)
    before = _obs.counter("program.sharded").value
    sharded = Program.build(cfg, 8, mesh=(4, 2), cout_shard_min_bytes=0)
    assert _obs.counter("program.sharded").value == before + 1
    assert sharded.device_count == 8
    assert sharded.mesh_str == "4x2"
    assert sharded.input_sharding is not None
    np.testing.assert_allclose(np.asarray(sharded.apply(g, z)),
                               np.asarray(plain.apply(g, z)), atol=1e-5)
    # batches must divide over the data axis
    with pytest.raises(ValueError, match="does not divide"):
        sharded.forward(g, z[:6])


@needs8
def test_sharded_server_stream_parity_inprocess():
    from repro.serve.gan import GanServer
    cfg = _cfg()
    g, _ = init_gan(cfg, jax.random.PRNGKey(0))
    ref = GanServer(cfg, g, batch_size=8, seed=7)
    prog = Program.build(cfg, 8, mesh=(4, 2), cout_shard_min_bytes=0,
                         differentiable=False)
    srv = GanServer(cfg, g, batch_size=8, seed=7, program=prog)
    np.testing.assert_allclose(srv.generate(12), ref.generate(12),
                               atol=1e-5)
    with pytest.raises(ValueError, match="data axis"):
        GanServer(cfg, g, batch_size=6, mesh=(4, 2))


# -- subprocess scenarios (forced 8 host CPU devices) -----------------------

@pytest.mark.slow
def test_all_table1_models_sharded_parity():
    """Every Table-I GAN generator produces allclose-identical samples
    sharded over a (4,2) mesh and on a single device (equal params and
    seeds); the dcgan discriminator rides along for the conv path."""
    run_forced_devices("""
        from repro.configs.gans import GAN_MODELS
        from repro.models.gan import GanConfig, init_gan
        from repro.program import Program
        n_cout = 0
        for name in sorted(GAN_MODELS):
            cfg = GanConfig(name=name, channel_scale=0.0625)
            g, d = init_gan(cfg, jax.random.PRNGKey(0))
            z = jax.random.normal(jax.random.PRNGKey(1),
                                  (8, *cfg.input_shape))
            plain = Program.build(cfg, 8, mesh=None)
            sharded = Program.build(cfg, 8, mesh=(4, 2),
                                    cout_shard_min_bytes=0)
            assert sharded.device_count == 8, name
            n_cout += sum(le.sharding == "cout"
                          for le in sharded.spec.layers)
            np.testing.assert_allclose(
                np.asarray(sharded.apply(g, z)),
                np.asarray(plain.apply(g, z)), atol=1e-5,
                err_msg=name)
        assert n_cout > 0, "no layer ever Cout-sharded"
        cfg = GanConfig(name="dcgan", channel_scale=0.0625)
        g, d = init_gan(cfg, jax.random.PRNGKey(0))
        img = Program.build(cfg, 8, mesh=None).apply(
            g, jax.random.normal(jax.random.PRNGKey(1), (8, cfg.z_dim)))
        p_d = Program.build(cfg, 8, "discriminator", mesh=None)
        s_d = Program.build(cfg, 8, "discriminator", mesh=(4, 2),
                            cout_shard_min_bytes=0)
        np.testing.assert_allclose(np.asarray(s_d.apply(d, img)),
                                   np.asarray(p_d.apply(d, img)),
                                   atol=1e-5)
        print("PASS")
    """)


@pytest.mark.slow
def test_exported_meshed_program_serves_identically(tmp_path):
    """The acceptance pin's 8-device side: a (4,2)-mesh program
    exported from this (single-device) process serves the bit-for-bit
    identical sample stream as a plain single-device server on 8
    forced devices."""
    cfg = _cfg()
    spec = ProgramSpec.build(cfg, 8, mesh=(4, 2), cout_shard_min_bytes=0)
    path = tmp_path / "dcgan_g.json"
    spec.save(path)
    run_forced_devices(f"""
        from repro import obs as _obs
        from repro.models.gan import GanConfig, init_gan
        from repro.program import Program, ProgramSpec
        from repro.serve.gan import GanServer
        cfg = GanConfig(name="dcgan", channel_scale=0.0625)
        g, _ = init_gan(cfg, jax.random.PRNGKey(0))
        prog = Program(ProgramSpec.load({str(path)!r}),
                       differentiable=False)
        assert prog.device_count == 8
        assert _obs.counter("program.sharded").value == 1
        srv = GanServer(cfg, g, batch_size=8, seed=7, program=prog)
        ref = GanServer(cfg, g, batch_size=8, seed=7)
        np.testing.assert_allclose(srv.generate(12), ref.generate(12),
                                   atol=1e-5)
        np.testing.assert_allclose(srv.generate(4), ref.generate(4),
                                   atol=1e-5)
        print("PASS")
    """)


@pytest.mark.slow
def test_engine_sharded_stream_parity():
    """The continuous-batching engine on a meshed program: identical
    stream to a plain engine at equal seed/buckets, bucket sizes
    validated against the data axis."""
    run_forced_devices("""
        from repro.models.gan import GanConfig, init_gan
        from repro.program import Program
        from repro.serve.gan_engine import GanEngine
        cfg = GanConfig(name="dcgan", channel_scale=0.0625)
        g, _ = init_gan(cfg, jax.random.PRNGKey(0))
        prog = Program.build(cfg, 8, mesh=(4, 2), cout_shard_min_bytes=0,
                             differentiable=False)
        eng = GanEngine(cfg, g, buckets=(4, 8), seed=3, program=prog)
        ref = GanEngine(cfg, g, buckets=(4, 8), seed=3)
        try:
            for n in (5, 7, 4):
                np.testing.assert_allclose(
                    eng.submit(n).result(30), ref.submit(n).result(30),
                    atol=1e-5)
        finally:
            eng.close(); ref.close()
        try:
            GanEngine(cfg, g, buckets=(2, 8), mesh=(4, 2), warmup=False)
            raise SystemExit("bucket 2 accepted on a (4,2) mesh")
        except ValueError as e:
            assert "divide" in str(e), e
        print("PASS")
    """)


@pytest.mark.slow
def test_dp_train_step_grad_parity():
    """Data-parallel training: the sharded step's losses and updated
    parameters match the single-device step (the shard_map transpose
    psums the weight cotangents — DP gradient reduction with no
    explicit pmean).  Float tolerance is relative: distributed
    reductions reassociate."""
    run_forced_devices("""
        from repro.models.gan import GanConfig, init_gan
        from repro.program import Program
        from repro.train.loop import make_gan_train_step
        cfg = GanConfig(name="dcgan", channel_scale=0.0625)
        gp, dp = init_gan(cfg, jax.random.PRNGKey(0))
        step_p, _ = make_gan_train_step(cfg, 8, mesh=None)
        step_s, (g_prog, _) = make_gan_train_step(cfg, 8, mesh=(4, 2))
        assert step_p.mesh is None and step_p.state_shardings is None
        assert step_s.mesh is not None
        z = jax.random.normal(jax.random.PRNGKey(1), (8, cfg.z_dim))
        z2 = jax.random.normal(jax.random.PRNGKey(2), (8, cfg.z_dim))
        real = jnp.tanh(Program.build(cfg, 8, mesh=None).apply(gp, z2))
        batch = {"z": z, "real": np.asarray(real)}
        g_sh, d_sh = step_s.state_shardings
        state_s = (jax.device_put(gp, g_sh), jax.device_put(dp, d_sh))
        s1, m1 = step_p((gp, dp), batch)
        s2, m2 = step_s(state_s, batch)
        for k in m1:
            np.testing.assert_allclose(float(m1[k]), float(m2[k]),
                                       rtol=1e-4, err_msg=k)
        for a, b in zip(jax.tree_util.tree_leaves(s1),
                        jax.tree_util.tree_leaves(s2)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-5)
        s2, m2 = step_s(s2, batch)    # placement stable across steps
        assert np.isfinite(float(m2["loss"]))
        print("PASS")
    """)
