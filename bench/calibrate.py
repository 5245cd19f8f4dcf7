#!/usr/bin/env python3
"""Readings from which a training cell's correctness limits are set, on
the chip.

    python bench/calibrate.py --workload <name> --seeds 12 [--control 3] \
        [--out <file.jsonl>]

In one process, for each seed, the numbers ``correct`` compares for the
program as the configuration states it (``program``); and, for the first
``--control`` seeds, for the program's own bfloat16 storage path
(``bf16``), for the control, the reference with every contraction's
operands rounded to float8 e4m3 (``fp8``), and for the half-batch fault
planted in the reference (``half``: the losses' mean taken over half of
each batch), each put in the program's place.  Each reading is one JSON
line on standard output (and in ``--out``).  The benchmark's own runs
never run this.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import cells, device, inputs  # noqa: E402
from bench.drivers import train_steps  # noqa: E402

SEED0 = 3_000_000_000   # calibration seeds, apart from any run's


def train_readings(cell, seeds, control, kinds=("bf16", "fp8", "half")):
    import jax.numpy as jnp
    from bench import reference
    cfg, traffic = cell.cfg, cell.traffic
    count = traffic["checked_steps"]
    steps = {"program": train_steps.build(cfg, 0, 1)[0]}
    if "bf16" in kinds:
        steps["bf16"] = train_steps.build(cfg, 0, 1, dtype="bfloat16")[0]
    for i, seed in enumerate(seeds):
        p0 = inputs.make_params(cfg, seed)
        feed = inputs.make_batches(cfg, seed, count, cfg["batch"])
        for kind in ["program"] + (list(kinds) if i < control else []):
            t = time.perf_counter()
            if kind in ("half", "fp8"):
                kw = ({"rows": cfg["batch"] // 2} if kind == "half" else
                      {"operand_dtype": jnp.float8_e4m3fn})
                states, _, losses = reference.sgd_steps(cfg, p0, feed,
                                                        **kw)
                p1, p3 = states[0], states[-1]
            else:
                p3, p1, losses = train_steps.checked_steps(
                    steps[kind], p0, feed, count)
            numbers = train_steps.reference_numbers(cfg, p0, p1, p3,
                                                    losses, feed)
            yield {"seed": seed, "kind": kind, "losses": losses,
                   "seconds": time.perf_counter() - t, **numbers}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control", type=int, default=3,
                    help="seeds (the first ones) that also read the "
                         "control and the faults")
    ap.add_argument("--first-seed", type=int, default=SEED0)
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    cell = cells.load_cell(args.workload)
    stamp = device.stamp(cell.chips)
    from repro.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    print(json.dumps({"device": stamp}), flush=True)
    seeds = [args.first_seed + 7919 * i for i in range(args.seeds)]
    rows = train_readings(cell, seeds, args.control)
    out = open(args.out, "a") if args.out else None
    for row in rows:
        line = json.dumps(row)
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
