#!/usr/bin/env python3
"""Run one benchmark cell once, on the chip it is started on.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell is looked up by name in ``BENCHMARK.json``; its configuration,
traffic mix, limits and metric readers are files found by their names
(``bench/cells.py``).  The run stamps the device and stops, printing no
result, when JAX finds no TPU or fewer chips than the cell asks for.
It builds what the cell needs from the seed, warms it, measures for
``--seconds``, checks what the timed path produced against the plain
reference, and prints as its last line one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics`` (the end-to-end
metrics, or with ``--trace 1`` the per-layer ones), ``device``,
``breakdown`` (traced runs) and, last, ``checks``: each number compared
with its limit, which the last lines of standard error repeat.  The
persistent compile cache is ``<checkout>/.jax_cache`` unless
``JAX_COMPILATION_CACHE_DIR`` names another.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import cells, checks, device  # noqa: E402
from bench.compile_log import CompileLog  # noqa: E402
from bench.drivers.common import Ctx, say  # noqa: E402


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def measure(cell: cells.Cell, seed: int, seconds: float, traced: bool,
            stamp: dict, t0: float = T0) -> dict:
    """Run the cell's driver and build the result line."""
    import jax
    from repro.utils.compile_cache import enable_compile_cache

    say(f"compile cache: {enable_compile_cache()}")
    # cache every executable, so that only a cell's first run compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    peaks = device.peaks(stamp["kind"])
    log = CompileLog()
    driver = importlib.import_module(
        f"bench.drivers.{cell.traffic['kind']}")
    ctx = Ctx(cell=cell, seed=seed, seconds=seconds, trace=traced, t0=t0,
              log=log)
    run, numbers = driver.run(ctx)
    run.peaks = peaks
    say(f"compiles over the run: {log.since((0.0, 0, 0, 0))}")

    metrics = {}
    for spec in cells.cell_metrics(cell, traced):
        value = cells.reader(cell, spec["name"])(run)
        if value is not None:
            metrics[spec["name"]] = {"value": value, "unit": spec["unit"]}
    correct, compared = checks.judge(numbers, cell.limits)
    correct = correct and run.failed == 0
    dev = dict(stamp, memory_peak_bytes=run.counters.get("memory_peak_bytes"))
    line = {"correct": correct, "attempted": run.attempted,
            "failed": run.failed, "metrics": metrics, "device": dev}
    if traced and run.summary is not None:
        dev["busy_s"] = run.summary.busy_s
        dev["window_s"] = run.summary.window_s
        line["breakdown"] = {
            "device_ops": run.summary.top_ops(10),
            "idle_gaps": [list(g) for g in run.summary.gaps[:10]]}
    extra = {k: v for k, v in numbers.items() if k.startswith("_")}
    if extra:
        say(f"check details: {extra}")
    line["checks"] = compared
    for name, c in compared.items():
        say(f"check {name}: {c['value']} (limit {c['limit']})")
    return line


def main(argv=None) -> int:
    args = parse(argv)
    cell = cells.load_cell(args.workload)
    try:
        stamp = device.stamp(cell.chips)
        device.peaks(stamp["kind"])
    except device.DeviceError as e:
        say(f"device: {e}")
        return 2
    say(f"device: {stamp}")
    line = measure(cell, args.seed, args.seconds, bool(args.trace), stamp)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
