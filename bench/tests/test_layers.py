"""Device time by layer and pass (``bench/layers.py``) and the image
layers' readers, on a small trace recorded on the chip with the program's
layer scopes (``bench/testdata/layers``: the training driver's traced first
20 ms of the tests' tiny dcgan cut, batch 4, on a TPU v5e, with the step's
compiled HLO text from the same process)."""

import gzip
import json

import pytest

from bench import cells, layers, trace, work
from bench.tests.conftest import BENCH, tiny_config

DATA = BENCH / "testdata" / "layers"
PEAKS = {"flops": {"bf16": 197e12}, "hbm_bytes_per_s": 819e9}
CONVS = [f"g{i}" for i in range(1, 5)] + [f"d{i}" for i in range(1, 6)]


@pytest.fixture(scope="module")
def recorded():
    summary = trace.reduce(DATA)
    with gzip.open(DATA / "layers.hlo.txt.gz", "rt") as f:
        text = f.read()
    traced = json.loads((DATA / "run.json").read_text())["traced"]
    return summary, text, traced


def make_run(summary, traced, cfg=None):
    cfg = cfg or tiny_config()
    cell = cells.Cell(name="tiny.train", chips=1, cfg=cfg, traffic={},
                      limits={}, benchmark={}, root=BENCH.parent)
    return cells.Run(cell=cell, peaks=PEAKS, setup_s=1.0, window_s=1.0,
                     samples=4, attempted=1, failed=0, traced=traced,
                     summary=summary)


@pytest.fixture
def run(recorded, monkeypatch):
    summary, text, traced = recorded
    monkeypatch.setattr(layers, "step_hlo", lambda cfg: text)
    return make_run(summary, traced)


@pytest.mark.parametrize("path,want", [
    ("jit(train_step)/gan.d_update/transpose(jvp(layer.d1))/"
     "conv_general_dilated", "d1 bwd"),
    ("jit(train_step)/gan.g_update/transpose(gan.g_update)/jvp(layer.g2)/"
     "dot_general", "g2 bwd"),
    ("jit(train_step)/gan.g_update/jvp(layer.g4)/jit(_take)/gather",
     "g4 fwd"),
    ("jit(train_step)/gan.d_update/jvp(layer.proj)/jvp(layer.d3)/add",
     "d3 fwd"),
    ("jit(train_step)/gan.sgd/sub", layers.UNSCOPED),
    ("", layers.UNSCOPED),
])
def test_key_is_the_last_layer_and_the_pass(path, want):
    assert layers.key(path) == want


def test_op_paths_reads_instruction_names():
    text = (
        '  %fusion.12 = f32[4,8]{1,0} fusion(%p0), kind=kOutput, '
        'calls=%fc.12, metadata={op_name="jit(train_step)/gan.d_update/'
        'jvp(layer.d1)/conv_general_dilated" source_file="x.py"}\n'
        '  ROOT %tuple.3 = (f32[4,8]{1,0}) tuple(%fusion.12)\n'
        '  %copy.4 = f32[4]{0} copy(%p1), metadata={op_name="a\\"b"}\n')
    assert layers.op_paths(text) == {
        "fusion.12": "jit(train_step)/gan.d_update/jvp(layer.d1)/"
                     "conv_general_dilated",
        "copy.4": 'a\\"b'}


def test_layer_seconds_partition_the_op_seconds(recorded):
    summary, text, _ = recorded
    split = layers.split(summary, layers.op_paths(text))
    assert sum(split.values()) == pytest.approx(
        sum(summary.op_seconds.values()) / summary.devices, rel=1e-12)
    for name in CONVS:
        assert split.get(f"{name} fwd", 0) > 0, name
        assert split.get(f"{name} bwd", 0) > 0, name


def test_conv_time_falls_under_layer_scopes(recorded):
    summary, text, _ = recorded
    paths = layers.op_paths(text)
    conv = {op: t for op, t in summary.op_seconds.items()
            if "(convolution" in op}
    scoped = sum(t for op, t in conv.items()
                 if layers.key(paths.get(op.rsplit(" (", 1)[0], ""))
                 != layers.UNSCOPED)
    assert conv and scoped >= 0.95 * sum(conv.values())


def test_the_steps_hlo_names_the_ops_as_the_trace_does(recorded):
    """The short names the reader's own compile gives the step's ops meet
    the trace's, and name the same layer and pass as the ``tf_op`` the
    profiler's export records for them; an op it cannot name has no
    ``tf_op`` either."""
    summary, text, _ = recorded
    paths = layers.op_paths(text)
    exported = {}
    for path in DATA.glob("*.trace.json.gz"):
        with gzip.open(path, "rt") as f:
            for ev in json.load(f)["traceEvents"]:
                args = ev.get("args") or {}
                if ev.get("ph") == "X" and "long_name" in args:
                    short = args["long_name"].split(" = ", 1)[0].lstrip("%")
                    exported[short] = args.get("tf_op", "")
    shorts = {op.rsplit(" (", 1)[0] for op in summary.op_seconds}
    assert shorts <= set(exported)
    for short in shorts:
        if short in paths:
            assert layers.key(paths[short]) == layers.key(exported[short]), \
                short
        else:   # made by XLA without metadata (layout copies)
            assert not exported[short], short
    assert len(shorts & set(paths)) > len(shorts) / 2


def test_readers_read_the_image_layers(run):
    share = cells.reader(run.cell, "image_layers_share.train")(run)
    roof = cells.reader(run.cell, "image_layers_roofline.train")(run)
    assert 0 < share <= 100
    assert 0 < roof <= 100
    split = run.layer_seconds
    image = sum(split.get(f"{n} {p}", 0) for n in ("g4", "d1")
                for p in ("fwd", "bwd"))
    assert share == pytest.approx(100 * image / run.summary.busy_s)


def test_breakdown_rows_longest_first(run):
    rows = layers.breakdown(run, layers.of(run))
    seconds = [t for _, t, _ in rows]
    assert seconds == sorted(seconds, reverse=True)
    by_key = {k: roof for k, _, roof in rows}
    assert by_key[layers.UNSCOPED] is None
    assert all(by_key[f"{n} {p}"] > 0 for n in CONVS for p in ("fwd", "bwd"))


def test_readers_need_a_trace(recorded):
    summary, _, traced = recorded
    for r in (make_run(None, None), make_run(summary, None)):
        assert layers.of(r) is None
        for name in ("image_layers_share.train",
                     "image_layers_roofline.train"):
            assert cells.reader(r.cell, name)(r) is None


def test_a_step_without_layer_scopes_reads_nothing(recorded, monkeypatch):
    summary, text, traced = recorded
    monkeypatch.setattr(layers, "step_hlo",
                        lambda cfg: text.replace("layer.", "L_"))
    r = make_run(summary, traced)
    assert layers.of(r) is None
    assert cells.reader(r.cell, "image_layers_share.train")(r) is None


def test_a_step_that_cannot_be_compiled_reads_nothing(recorded,
                                                      monkeypatch, capsys):
    summary, _, traced = recorded

    def broken(cfg):
        raise RuntimeError("no compiler")

    monkeypatch.setattr(layers, "step_hlo", broken)
    r = make_run(summary, traced)
    assert cells.reader(r.cell, "image_layers_roofline.train")(r) is None
    assert "no compiler" in capsys.readouterr().err


@pytest.mark.parametrize("name", ["dcgan", "3dgan"])
def test_image_and_other_layers_sum_to_the_whole_least(name):
    cfg = json.loads((BENCH / "configs" / f"{name}.json").read_text())
    r = make_run(None, None, cfg)
    image = layers.image_layers(cfg)
    assert image == ("g4", "d1")
    others = {lay["name"] for lay in cfg["generator"] + cfg["discriminator"]
              } - set(image)
    whole = work.least_seconds(cfg, work.step_passes(cfg), cfg["batch"],
                               197e12, 819e9)
    parts = layers.least_seconds(r, image) + layers.least_seconds(r, others)
    assert parts == pytest.approx(whole, rel=1e-12)
    halves = (layers.least_seconds(r, image, "fwd")
              + layers.least_seconds(r, image, "bwd"))
    assert halves == pytest.approx(layers.least_seconds(r, image), rel=1e-12)


def test_step_hlo_names_every_layer():
    """The reader's own compile of the driver's step (here on the CPU)
    names each layer's forward and backward ops."""
    cfg = tiny_config()
    keys = {layers.key(p)
            for p in layers.op_paths(layers.step_hlo(cfg)).values()}
    for name in ["proj"] + CONVS:
        assert {f"{name} fwd", f"{name} bwd"} <= keys, name
