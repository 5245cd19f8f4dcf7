"""Find a cell's files by the names in ``BENCHMARK.json``, and hold what a
run measured for the metric readers.

A workload entry names its configuration and its traffic mix.  The
configuration's file is the ``file`` of its ``configs`` entry; the mix
is ``bench/traffic/<traffic>.json``; the limits of the cell's check are
``bench/limits/<workload>.json``; each metric, end to end or per layer,
is read by ``bench/metrics/<metric>.py``.  Nothing here names a model,
a mix or a metric: a later cell adds files and entries, and edits none.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib

__all__ = ["Cell", "Run", "load_cell", "cell_metrics", "reader"]

ROOT = pathlib.Path(__file__).resolve().parents[1]


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    cfg: dict          # the configuration file
    traffic: dict      # the traffic mix's file
    limits: dict       # name -> limit of each number the check compares
    benchmark: dict    # the whole BENCHMARK.json
    root: pathlib.Path


@dataclasses.dataclass
class Run:
    """What one run measured.  Readers take their metric from here."""
    cell: Cell
    peaks: dict
    setup_s: float
    window_s: float                  # the measured window, host clock
    samples: int                     # samples completed in the window
    attempted: int
    failed: int
    counters: dict = dataclasses.field(default_factory=dict)
    traced: dict | None = None       # {"samples"} of the traced part
    summary: object = None           # trace.Summary of a traced run

    @property
    def cfg(self) -> dict:
        return self.cell.cfg

    @property
    def peak_flops(self) -> float:
        return self.peaks["flops"][self.cfg["compute"]]


def _json(path: pathlib.Path) -> dict:
    return json.loads(path.read_text())


def load_cell(name: str, root: pathlib.Path = ROOT) -> Cell:
    """The workload ``name`` with its configuration, traffic and limits,
    each read from the file its name leads to."""
    root = pathlib.Path(root)
    bench = _json(root / "BENCHMARK.json")
    work = {w["name"]: w for w in bench["workloads"]}
    if name not in work:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"({sorted(work)})")
    entry = work[name]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg = _json(root / configs[entry["config"]]["file"])
    traffic = _json(root / "bench" / "traffic" / f"{entry['traffic']}.json")
    limits = _json(root / "bench" / "limits" / f"{name}.json")
    return Cell(name=name, chips=int(entry["chips"]), cfg=cfg,
                traffic=traffic, limits=limits, benchmark=bench, root=root)


def cell_metrics(cell: Cell, traced: bool) -> list[dict]:
    """The metrics a run of ``cell`` reports: its end-to-end metrics, or
    with a trace its per-layer ones."""
    kind = "per_layer" if traced else "end_to_end"
    return [m for m in cell.benchmark[kind]
            if cell.name in m.get("workloads", [cell.name])]


def reader(cell: Cell, metric: str):
    """The ``read(run)`` function of ``bench/metrics/<metric>.py``."""
    path = cell.root / "bench" / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{metric.replace('.', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read
