"""The harness finds a cell's files by name and drives a whole run; with
the timed path broken underneath, ``correct`` comes out false.

The device check is steered here, in the test: the stamp is handed in
and the peaks table is given the CPU's kind.  The faults are those a
one-chip training cell can have: a step that returns its state
unchanged; half of the batch left out, the mean taken over the rest.
(No cell spans chips, so none can leave out an exchange between them,
and none serves, so none can alter an answer where it is produced.)
"""

import json

import pytest

from bench import cells, device
from bench import run as bench_run

STAMP = {"platform": "cpu", "kind": "cpu", "count": 1}
SEED = 2 ** 31 + 99


@pytest.fixture
def steered(monkeypatch):
    import repro.utils.compile_cache as cc
    monkeypatch.setattr(device, "peaks", lambda kind, table=None: {
        "flops": {"bf16": 197e12}, "hbm_bytes_per_s": 819e9})
    monkeypatch.setattr(cc, "enable_compile_cache", lambda: "off")


def test_files_are_found_by_name(tiny_root):
    cell = cells.load_cell("tiny.train", root=tiny_root)
    assert cell.cfg["name"] == "tiny" and cell.cfg["channel_scale"] < 1
    assert cell.traffic["kind"] == "train_steps"
    assert set(cell.limits) == {"loss_gap", "grad_gap", "update_gap"}
    names = [m["name"] for m in cells.cell_metrics(cell, traced=True)]
    assert "mfu.train" in names
    for name in names:
        assert callable(cells.reader(cell, name))
    other = cells.load_cell("tiny.other", root=tiny_root)
    assert [m["name"] for m in cells.cell_metrics(other, traced=False)] \
        == ["setup_s"]
    assert cells.cell_metrics(other, traced=True) == []
    with pytest.raises(KeyError):
        cells.load_cell("nope", root=tiny_root)


def run_cell(root, name, **traffic):
    cell = cells.load_cell(name, root=root)
    cell.traffic.update(traffic)
    return bench_run.measure(cell, SEED, 1.5, False, STAMP)


def test_a_sound_training_run_is_correct(tiny_root, steered, capsys):
    line = run_cell(tiny_root, "tiny.train")
    assert line["correct"], line
    assert list(line)[-1] == "checks"
    assert set(line["metrics"]) == {"train_samples_per_s", "setup_s"}
    assert line["attempted"] > 0 and line["failed"] == 0
    err = capsys.readouterr().err.strip().splitlines()
    assert all(x.startswith("check ") for x in err[-3:])
    json.dumps(line)


def broken_step(monkeypatch, fault):
    import repro.train.loop as loop
    make = loop.make_gan_train_step

    def make_broken(cfg, batch, **kw):
        step, programs = make(cfg, batch, **kw)

        def broken(state, feed):
            if fault == "unchanged":
                return state, step(state, feed)[1]
            half = {k: v[: v.shape[0] // 2] for k, v in feed.items()}
            return step(state, half)

        return broken, programs

    monkeypatch.setattr(loop, "make_gan_train_step", make_broken)


@pytest.mark.parametrize("fault", ["unchanged", "half_batch"])
def test_a_broken_training_step_is_not_correct(tiny_root, steered,
                                               monkeypatch, fault):
    broken_step(monkeypatch, fault)
    line = run_cell(tiny_root, "tiny.train")
    assert not line["correct"], line["checks"]


def test_the_step_share_of_peak_takes_its_time_from_the_trace(tiny_root):
    from bench import trace, work
    cell = cells.load_cell("tiny.train", root=tiny_root)
    summary = trace.Summary(window_s=0.5, busy_s=0.4, devices=1,
                            op_seconds={}, class_total={}, gaps=[])
    run = cells.Run(cell=cell, peaks={"flops": {"bf16": 1e12}},
                    setup_s=1.0, window_s=9.0, samples=400, attempted=100,
                    failed=0, traced={"samples": 40}, summary=summary)
    mfu = cells.reader(cell, "mfu.train")
    per_sample = work.flops(cell.cfg, work.step_passes(cell.cfg))
    assert mfu(run) == pytest.approx(100 * per_sample * 40 / 0.5 / 1e12)
    run.summary = None
    assert mfu(run) is None


def test_the_host_watch_names_the_longest_step():
    import gc
    from bench.drivers.common import HostWatch
    with HostWatch() as host:
        for i, took in enumerate([0.010, 0.012, 0.250, 0.011]):
            began = 10.0 + i
            if i == 2:
                gc.collect()
            host.mark(began, began + 0.001, began + took)
    report = host.report(1)
    assert report.startswith("host: median step 12.000 ms; longest #2 "
                             "250.000 ms (1.000 dispatching")
    assert host.gc_runs >= 1 and host.gc_s > 0
    assert f"garbage collector {host.gc_runs} runs" in report
    assert HostWatch().report() == "host: no steps"
