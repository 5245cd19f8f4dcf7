"""The cross-domain (DiscoGAN) cell: its reference against the program at
a size the CPU holds, a whole run through the harness, the faults that
must come out not correct, the work counts against a hand count, and the
readers of a traced run on the step's own op names."""

import json
import shutil

import jax.numpy as jnp
import pytest

from bench import cells, layers, reference_cycle, trace, work, work_cycle
from bench import run as bench_run
from bench.drivers import cycle_train_steps as cycle
from bench.drivers.train_steps import checked_steps
from bench.tests.conftest import BENCH, ROOT, tiny_config
from bench.tests.test_harness import (  # noqa: F401 (steered)
    SEED, STAMP, broken_step, steered)

LIMITS = json.loads((BENCH / "limits" / "discogan.train.json").read_text())


def tiny_cycle_config() -> dict:
    cfg = tiny_config("discogan")
    cfg["batch"] = 2
    return cfg


@pytest.fixture
def cycle_root(tmp_path):
    """A checkout holding the benchmark's files and one cross-domain cell,
    ``tiny.cycle``, on a 1/32 cut of discogan at batch 2."""
    for d in ("metrics", "traffic"):
        shutil.copytree(BENCH / d, tmp_path / "bench" / d)
    for d in ("configs", "limits"):
        (tmp_path / "bench" / d).mkdir()
    (tmp_path / "bench" / "configs" / "tiny.json").write_text(
        json.dumps(tiny_cycle_config()))
    shutil.copy(BENCH / "limits" / "discogan.train.json",
                tmp_path / "bench" / "limits" / "tiny.cycle.json")
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["configs"] = [{"name": "tiny", "source": "test",
                         "file": "bench/configs/tiny.json", "reduced": [],
                         "why": "test"}]
    bench["workloads"] = [{"name": "tiny.cycle", "config": "tiny",
                           "traffic": "cycle_train", "chips": 1,
                           "why": "test"}]
    for metric in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in metric:
            metric["workloads"] = ["tiny.cycle"]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp_path


@pytest.fixture(scope="module")
def checked():
    """The program's three checked steps on the tiny cut, from one seed."""
    cfg = tiny_cycle_config()
    step, p0, feed = cycle.build(cfg, SEED, 3)
    p3, p1, losses = checked_steps(step, p0, feed, 3)
    return cfg, step, p0, feed, p1, p3, losses


def test_the_reference_follows_the_program(checked):
    cfg, _, p0, feed, p1, p3, losses = checked
    numbers = cycle.reference_numbers(cfg, p0, p1, p3, losses, feed)
    # the losses and the first update agree to f32 rounding; over three
    # steps a ReLU input that the two algorithms' rounding puts on either
    # side of 0 moves a later gradient of a bias with 2 channels at this
    # cut (5.7% of g/ba.t8_b's update here)
    assert numbers["loss_gap"] < 1e-5, numbers
    assert numbers["grad_gap"] < 1e-3, numbers
    assert numbers["update_gap"] < 0.1, numbers
    assert not numbers["_quiet_leaves"]
    # every leaf of the four parameter sets is judged
    g, d = cycle.flat(p0)
    assert {k.split(".")[0] for k in g} == {"ab", "ba"}
    assert {k.split(".")[0] for k in d} == {"a", "b"}
    assert len(g) == 2 * 2 * len(cfg["generator"])


@pytest.mark.parametrize("fault", ["fp8", "half", "unchanged"])
def test_the_planted_faults_are_caught(checked, fault):
    """The control (fp8-e4m3 operands) and the faults, each put in the
    program's place, read over at least one of the cell's limits."""
    import jax
    cfg, step, p0, feed, *_ = checked
    if fault == "unchanged":
        p1 = p3 = p0
        losses = [{k: float(v) for k, v in
                   jax.device_get(step(p0, b)[1]).items() if k != "loss"}
                  for b in feed]
    else:
        kw = ({"rows": cfg["batch"] // 2} if fault == "half" else
              {"operand_dtype": jnp.float8_e4m3fn})
        states, _, losses = reference_cycle.sgd_steps(cfg, p0, feed, **kw)
        p1, p3 = states[0], states[-1]
    numbers = cycle.reference_numbers(cfg, p0, p1, p3, losses, feed)
    assert any(numbers[k] > limit for k, limit in LIMITS.items()), numbers


def test_a_sound_cycle_run_is_correct(cycle_root, steered):  # noqa: F811
    cell = cells.load_cell("tiny.cycle", root=cycle_root)
    line = bench_run.measure(cell, SEED, 1.0, False, STAMP)
    assert line["correct"], line
    assert set(line["metrics"]) == {"train_samples_per_s", "setup_s"}
    assert line["attempted"] > 0 and line["failed"] == 0


@pytest.mark.parametrize("fault", ["unchanged", "half_batch"])
def test_a_broken_cycle_step_is_not_correct(cycle_root, steered,  # noqa: F811
                                            monkeypatch, fault):
    broken_step(monkeypatch, fault)
    cell = cells.load_cell("tiny.cycle", root=cycle_root)
    line = bench_run.measure(cell, SEED, 0.5, False, STAMP)
    assert not line["correct"], line["checks"]


def test_a_program_without_the_image_input_is_refused(monkeypatch):
    from repro.models.gan import GanConfig
    monkeypatch.setattr(GanConfig, "input_kind", property(
        lambda self: "latent"))
    with pytest.raises(ValueError, match="no image-input generator"):
        cycle.build(tiny_cycle_config(), SEED, 1)


# per-layer forward MACs of discogan at published widths, by hand: a conv
# is out^2 x 16 taps x cin x cout; a stride-2 4x4 tconv has 4 useful taps
# per output pixel, out^2 x 4 x cin x cout
E = [32 * 32 * 16 * 3 * 64] + [n * n * 16 * c * 2 * c
                               for n, c in ((16, 64), (8, 128), (4, 256),
                                            (2, 512))]
G = [4 * 4 * 4 * 1024 * 512, 8 * 8 * 4 * 512 * 256, 16 * 16 * 4 * 256 * 128,
     32 * 32 * 4 * 128 * 64, 64 * 64 * 4 * 64 * 3]
D = [32 * 32 * 16 * 3 * 64, 16 * 16 * 16 * 64 * 128,
     8 * 8 * 16 * 128 * 256, 4 * 4 * 16 * 256 * 512, 1 * 16 * 512 * 1]


def test_work_cycle_counts_by_hand():
    cfg = json.loads((BENCH / "configs" / "discogan.json").read_text())
    passes = work_cycle.step_passes(cfg)
    counts = {(lay["name"], kind): n for lay, kind, n in passes}
    assert counts[("e1", "igrad")] == 2 and counts[("e2", "igrad")] == 4
    assert counts[("g5", "fwd")] == 4 and counts[("g5", "wgrad")] == 4
    assert counts[("d1", "igrad")] == 2 and counts[("d3", "igrad")] == 6
    assert counts[("d5", "fwd")] == 6 and counts[("d5", "wgrad")] == 4
    assert len(passes) == 3 * (10 + 5)
    for lay, macs in zip(cfg["generator"] + cfg["discriminator"], E + G + D):
        assert work.layer_macs(lay, 2) == macs, lay["name"]
    g, d = sum(E + G), sum(D)
    assert (g, d) == (274_726_912, 103_817_216)
    # G: 4 fwd, 4 wgrad, 4 igrad but 2 at e1; D: 6, 4, 6 but 2 at d1
    hand = 2 * (12 * g - 2 * E[0]) + 2 * (16 * d - 4 * D[0])
    assert work.flops(cfg, passes) == hand == 9_877_848_064
    assert work_cycle.encoder(cfg) == ("e1", "e2", "e3", "e4", "e5")


def test_the_readers_split_a_traced_run(monkeypatch):
    """Every op of the compiled step given 1 ms: the encoder's share, the
    reconstruction's share and the MFU follow from the op names alone."""
    cfg = tiny_cycle_config()
    text = cycle.step_hlo(cfg)
    monkeypatch.setattr(cycle, "step_hlo", lambda cfg: text)
    paths = layers.op_paths(text)
    ops = {f"{name} (convolution fusion)": 1e-3 for name in paths}
    busy = len(ops) * 1e-3
    summary = trace.Summary(window_s=2 * busy, busy_s=busy, devices=1,
                            op_seconds=ops, class_total={}, gaps=[])
    cell = cells.Cell(name="tiny.cycle", chips=1, cfg=cfg, traffic={},
                      limits={}, benchmark={}, root=ROOT)
    run = cells.Run(cell=cell, peaks={"flops": {"bf16": 197e12},
                                      "hbm_bytes_per_s": 819e9},
                    setup_s=1.0, window_s=9.0, samples=40, attempted=20,
                    failed=0, traced={"samples": 8}, summary=summary)

    def read(metric):
        return cells.reader(cell, metric)(run)

    encoder = sum(1 for p in paths.values()
                  if layers.key(p).split(" ")[0] in work_cycle.encoder(cfg))
    rebuilt = sum(1 for p in paths.values() if "gan.reconstruct" in p)
    assert encoder > 0 and rebuilt > 0
    assert read("encoder_share.cycle_train") == \
        pytest.approx(100 * encoder / len(ops))
    assert read("reconstruct_share.cycle_train") == \
        pytest.approx(100 * rebuilt / len(ops))
    per_pair = work.flops(cfg, work_cycle.step_passes(cfg))
    assert read("mfu.cycle_train") == \
        pytest.approx(100 * per_pair * 8 / (2 * busy) / 197e12)
    run.summary = None
    assert read("mfu.cycle_train") is None
    assert read("encoder_share.cycle_train") is None
