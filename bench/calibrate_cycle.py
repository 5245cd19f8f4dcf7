#!/usr/bin/env python3
"""Readings from which the cross-domain training cell's correctness limits
are set, on the chip (``bench/calibrate.py`` for the cycle driver).

    python bench/calibrate_cycle.py --workload discogan.train --seeds 10 \
        [--control 3] [--out <file.jsonl>]

In one process, for each seed, the numbers ``correct`` compares for the
program as the configuration states it (``program``); and, for the first
``--control`` seeds, for the control, the reference with every
contraction's operands rounded to float8 e4m3 (``fp8``), for the
half-batch fault planted in the reference (``half``: the losses' mean
taken over half of each batch), and for a step that leaves the state
unchanged (``unchanged``), each put in the program's place.  Each
reading is one JSON line on standard output (and in ``--out``).  The
benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import cells, device, reference_cycle  # noqa: E402
from bench.calibrate import SEED0  # noqa: E402
from bench.drivers import cycle_train_steps as cycle  # noqa: E402
from bench.drivers.train_steps import checked_steps  # noqa: E402

KINDS = ("fp8", "half", "unchanged")


def readings(cell, seeds, control):
    import jax
    import jax.numpy as jnp
    cfg = cell.cfg
    count = cell.traffic["checked_steps"]
    step = cycle.build(cfg, 0, 1)[0]
    for i, seed in enumerate(seeds):
        p0 = cycle.make_params(cfg, seed)
        feed = cycle.make_batches(cfg, seed, count, cfg["batch"])
        for kind in ("program",) + (KINDS if i < control else ()):
            t = time.perf_counter()
            if kind == "program":
                p3, p1, losses = checked_steps(step, p0, feed, count)
            elif kind == "unchanged":
                p1 = p3 = p0
                losses = [{k: float(v) for k, v in
                           jax.device_get(step(p0, b)[1]).items()
                           if k != "loss"} for b in feed]
            else:
                kw = ({"rows": cfg["batch"] // 2} if kind == "half" else
                      {"operand_dtype": jnp.float8_e4m3fn})
                states, _, losses = reference_cycle.sgd_steps(cfg, p0, feed,
                                                              **kw)
                p1, p3 = states[0], states[-1]
            numbers = cycle.reference_numbers(cfg, p0, p1, p3, losses, feed)
            yield {"seed": seed, "kind": kind, "losses": losses,
                   "seconds": time.perf_counter() - t, **numbers}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=10)
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
    out = open(args.out, "a") if args.out else None
    for row in readings(cell, seeds, args.control):
        line = json.dumps(row)
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
