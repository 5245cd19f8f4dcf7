"""Work counts from layer shapes: the hand counts, the program's own
analytical count of useful MACs, and independence from the backend."""

import json

import pytest

from bench import work
from bench.tests.conftest import BENCH


def config(name):
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


@pytest.mark.parametrize("name, gmac", [("dcgan", 0.41), ("3dgan", 3.9)])
def test_forward_macs_match_hand_counts(name, gmac):
    cfg = config(name)
    for role in ("generator", "discriminator"):
        macs = sum(work.layer_macs(layer, cfg["dims"]) for layer in cfg[role])
        assert macs / 1e9 == pytest.approx(gmac, rel=0.03), role


@pytest.mark.parametrize("name, gflop", [("dcgan", 9.0), ("3dgan", 85.6)])
def test_step_flops_are_about_3g_plus_8d(name, gflop):
    cfg = config(name)
    per_sample = work.flops(cfg, work.step_passes(cfg))
    assert per_sample / 1e9 == pytest.approx(gflop, rel=0.03)
    g = sum(work.layer_macs(x, cfg["dims"]) for x in cfg["generator"])
    d = sum(work.layer_macs(x, cfg["dims"]) for x in cfg["discriminator"])
    assert per_sample <= 2 * (3 * g + 8 * d)


def test_tconv_macs_match_consequential_macs_of_every_table1_layer():
    from repro.configs.gans import GAN_MODELS
    seen = 0
    for g_layers, d_layers in GAN_MODELS.values():
        for layer in g_layers + d_layers:
            if not layer.transposed:
                continue
            mine = {"kind": "tconv", "in": layer.in_spatial[0],
                    "k": layer.kernel[0], "s": layer.strides[0],
                    "p": layer.paddings[0], "cin": layer.cin,
                    "cout": layer.cout}
            want = layer.schedule().consequential_macs(layer.cin, layer.cout)
            assert work.layer_macs(mine, len(layer.in_spatial)) == want
            seen += 1
    assert seen >= 20


@pytest.mark.parametrize("name", ["dcgan", "3dgan"])
def test_counts_read_the_same_under_every_backend(name):
    """The layers the program freezes under the zero-insert and the
    polyphase policies give one count: the count reads shapes only."""
    from repro.core.dataflow import DataflowPolicy
    from repro.models.gan import GanConfig
    from repro.program import ProgramSpec
    cfg = config(name)
    counts = set()
    for backend in ("zero-insert", "polyphase"):
        total = 0
        for role in ("generator", "discriminator"):
            spec = ProgramSpec.build(GanConfig(name=name, z_dim=cfg["z_dim"]),
                                     2, role,
                                     policy=DataflowPolicy(backend=backend))
            for le in spec.layers:
                total += work.layer_macs(
                    {"kind": le.kind, "in": le.in_spatial[0],
                     "k": le.kernel[0], "s": le.strides[0],
                     "p": le.paddings[0], "cin": le.cin, "cout": le.cout},
                    len(le.in_spatial))
        counts.add(total)
    stated = sum(work.layer_macs(x, cfg["dims"]) for x in
                 cfg["generator"][1:] + cfg["discriminator"])
    assert counts == {stated}


def test_least_seconds_takes_the_larger_bound_per_pass():
    cfg = config("dcgan")
    passes = work.step_passes(cfg)
    compute_only = work.least_seconds(cfg, passes, 64, 1e12, 1e30)
    bytes_only = work.least_seconds(cfg, passes, 64, 1e30, 1e9)
    both = work.least_seconds(cfg, passes, 64, 1e12, 1e9)
    assert both >= max(compute_only, bytes_only)
    assert both <= compute_only + bytes_only
    assert compute_only == pytest.approx(
        64 * work.flops(cfg, passes) / 1e12)


def test_layer_shapes_chain():
    for name in ("dcgan", "3dgan"):
        work.layer_count_check(config(name))
    bad = config("dcgan")
    bad["generator"][2]["cin"] = 7
    with pytest.raises(ValueError):
        work.layer_count_check(bad)
