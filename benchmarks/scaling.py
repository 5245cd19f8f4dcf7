"""Data-parallel scaling microbenchmark for mesh-sharded GAN programs.

Times the ahead-of-time compiled generator executable at a fixed
*global* batch under three frozen meshes — single-device, ``(2, 1)``
and ``(4, 1)`` — over forced host CPU devices, and emits

* ``micro/<model>/dp_scaling_{1,2,4}x_us`` — wall-clock per ``apply``
  at global batch 8 on 1/2/4 data-parallel devices.  Only the ``1x``
  row gates (widened: it is the same executable ``program_us`` already
  tracks, plus nothing); the multi-device rows are **informational on
  CPU** — forced host devices share the same cores, so DP "scaling"
  here measures partitioning overhead, not speedup;
* ``micro/<model>/dp_speedup`` — ``1x`` / ``4x`` wall-clock ratio
  (informational; > 1 only on machines with real parallel hardware).

Runs **standalone** (never imported by ``benchmarks/run.py``): it needs
at least four devices, and on the CPU those exist only when
``XLA_FLAGS`` forces them before jax first initializes, which the
aggregator's process cannot do.  The caller sets the platform and the
flag; the script refuses to run on fewer than four devices.  Instead
of returning rows to the aggregator it merges its pivoted rows into
``BENCH_dataflow.json`` itself (CI runs it right after ``run.py``)::

    JAX_PLATFORMS=cpu \
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    PYTHONPATH=src python benchmarks/scaling.py --models dcgan
"""

from __future__ import annotations

import argparse
import json
import math
import pathlib
import sys
import time

import jax

_ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(_ROOT / "src"))

DEFAULT_BATCH = 8
DEFAULT_REPEATS = 30

# (row label, (data, model) mesh); None = plain single-device program.
MESHES = (("1x", None), ("2x", (2, 1)), ("4x", (4, 1)))


def _time_apply(prog, params, z, repeats: int) -> float:
    """Steady-state µs per ``apply`` (first call pays trace+compile and
    is excluded)."""
    out = prog.apply(params, z)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(repeats):
        out = prog.apply(params, z)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / repeats * 1e6


def run_scaling(models=("dcgan",), channel_scale=0.25,
                batch=DEFAULT_BATCH, repeats=DEFAULT_REPEATS, seed=0):
    from repro.models.gan import GanConfig, init_gan
    from repro.program import Program

    need = max(m[0] * m[1] for _, m in MESHES if m is not None)
    if len(jax.devices()) < need:
        raise SystemExit(
            f"dp scaling needs {need} devices, found "
            f"{len(jax.devices())}; on the CPU set XLA_FLAGS="
            f"--xla_force_host_platform_device_count=8 before running")
    rows = []
    print(f"\n== dp scaling: generator program at global batch {batch} "
          f"over {len(jax.devices())} {jax.devices()[0].platform} devices "
          f"(channels×{channel_scale}) ==")
    for name in models:
        cfg = GanConfig(name=name, channel_scale=channel_scale)
        g_params, _ = init_gan(cfg, jax.random.PRNGKey(seed))
        z = jax.random.normal(jax.random.PRNGKey(seed + 1),
                              (batch, cfg.z_dim))
        times = {}
        for label, mesh in MESHES:
            prog = Program.build(cfg, batch, mesh=mesh,
                                 differentiable=False)
            zp = z if prog.input_sharding is None else \
                jax.device_put(z, prog.input_sharding)
            us = _time_apply(prog, g_params, zp, repeats)
            times[label] = us
            gate = "gated wide" if label == "1x" else \
                "informational on CPU"
            rows.append((f"micro/{name}/dp_scaling_{label}_us", us,
                         f"mesh={prog.mesh_str}, {gate}"))
        speedup = times["1x"] / times["4x"] if times["4x"] > 0 \
            else float("inf")
        rows.append((f"micro/{name}/dp_speedup", speedup,
                     "1x/4x wall-clock, informational on CPU"))
        print(f"  {name:8s} 1x={times['1x']:9.1f}us  "
              f"2x={times['2x']:9.1f}us  4x={times['4x']:9.1f}us  "
              f"dp_speedup={speedup:5.2f}x")
    return rows


def merge_into_artifact(rows, path) -> None:
    """Pivot ``micro/<model>/<metric>`` rows and merge them into the
    (possibly already written) ``BENCH_dataflow.json`` — the aggregator
    ran in another process, so this is a read-modify-write, not a
    return value."""
    path = pathlib.Path(path)
    doc = json.loads(path.read_text()) if path.exists() else {}
    for name, value, _ in rows:
        parts = name.split("/")
        if len(parts) != 3 or parts[0] != "micro":
            continue
        v = float(value)
        doc.setdefault(parts[1], {})[parts[2]] = \
            v if math.isfinite(v) else None
    path.write_text(json.dumps(doc, indent=2, sort_keys=True) + "\n")
    print(f"merged {len(rows)} rows into {path}")


def main(argv=None):
    from repro.configs.gans import GAN_MODELS

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--models", nargs="+", default=["dcgan"],
                    choices=sorted(GAN_MODELS))
    ap.add_argument("--batch", type=int, default=DEFAULT_BATCH)
    ap.add_argument("--repeats", type=int, default=DEFAULT_REPEATS)
    ap.add_argument("--channel-scale", type=float, default=0.25)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=str(_ROOT / "BENCH_dataflow.json"))
    args = ap.parse_args(argv)
    rows = run_scaling(models=tuple(args.models), batch=args.batch,
                       channel_scale=args.channel_scale,
                       repeats=args.repeats, seed=args.seed)
    merge_into_artifact(rows, args.out)

    print("\n== CSV ==")
    print("name,us_per_call,derived")
    for name, value, derived in rows:
        print(f"{name},{value},{derived}")
    return rows


if __name__ == "__main__":
    main()
