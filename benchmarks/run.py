"""Benchmark aggregator: one section per paper table/figure + the roofline
table.  Prints ``name,value,derived`` CSV at the end (harness contract)
and writes ``BENCH_dataflow.json`` (GANAX vs zero-insert wall-clock per
Table-I model) so the perf trajectory is recorded across PRs."""

from __future__ import annotations

import json
import pathlib
import sys

# `python benchmarks/run.py` puts benchmarks/ itself on sys.path, not the
# repo root the `benchmarks.*` imports need — add it.
sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))


def _dataflow_json(rows) -> dict:
    """Pivot the micro/<model>/<metric> rows into {model: {metric: value}}.

    Non-finite values (e.g. a NaN speedup when a model has no transposed
    layers) become null — the artifact must stay valid JSON for CI."""
    import math
    out: dict[str, dict[str, float | None]] = {}
    for name, value, _ in rows:
        parts = name.split("/")
        if len(parts) != 3 or parts[0] != "micro":
            continue
        v = float(value)
        out.setdefault(parts[1], {})[parts[2]] = \
            v if math.isfinite(v) else None
    return out


def main() -> None:
    from benchmarks import microbench, paper_figs, roofline, traffic
    rows = []
    rows += paper_figs.run_all()
    micro_rows = microbench.run_all()
    # traffic rows share the micro/<model>/<metric> convention so the
    # pivot below carries them into BENCH_dataflow.json for the gate
    micro_rows += traffic.run_all()
    rows += micro_rows
    rows += roofline.run_all()

    bench = _dataflow_json(micro_rows)
    path = pathlib.Path(__file__).resolve().parent.parent / \
        "BENCH_dataflow.json"
    path.write_text(json.dumps(bench, indent=2, sort_keys=True) + "\n")
    print(f"\nwrote {path}")

    print("\n== CSV ==")
    print("name,us_per_call,derived")
    for name, value, derived in rows:
        print(f"{name},{value},{derived}")


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
