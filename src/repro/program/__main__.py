"""``python -m repro.program`` — build, describe, export, load, and
account ahead-of-time compiled GAN programs.

Typical use::

    PYTHONPATH=src python -m repro.program dcgan
    PYTHONPATH=src python -m repro.program dcgan --backend auto \
        --plans plans.json --export dcgan-program.json
    PYTHONPATH=src python -m repro.program dcgan --load dcgan-program.json
    PYTHONPATH=src python -m repro.program dcgan --backend auto --stats
    PYTHONPATH=src python -m repro.program dcgan --dtype bf16 \
        --quantize int8 --export dcgan-int8.json

The first form is the CI smoke: resolving the whole spec touches no
arrays and runs no jit — a broken resolution path fails fast and cheap.
The last prints the resolution-counter deltas of the build (plan-cache
hits/misses, pinned/tuned/heuristic provenance, degradations) from the
``repro.obs`` metrics registry — the quickest answer to "did my plan
file actually get used".
"""

from __future__ import annotations

import argparse
import sys

from repro.configs.gans import GAN_MODELS
from repro.core.dataflow import DataflowPolicy, available_backends


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.program",
        description="Build, describe, export/load, and (--stats) "
                    "account the resolution of an ahead-of-time "
                    "compiled GAN program (the supported execution "
                    "API).")
    ap.add_argument("model", choices=sorted(GAN_MODELS))
    ap.add_argument("--role", default="both",
                    choices=("generator", "discriminator", "both"))
    ap.add_argument("--batch", type=int, default=8,
                    help="planning batch (plan keys; apply() accepts "
                         "any batch)")
    ap.add_argument("--channel-scale", type=float, default=1.0)
    ap.add_argument("--mesh", default=None, metavar="DATAxMODEL",
                    help="freeze a (data, model) device mesh into the "
                         "spec, e.g. 4x2 (per-layer sharding chosen by "
                         "the footprint heuristic; the exported file "
                         "degrades to single-device on boxes without "
                         "the devices)")
    ap.add_argument("--dtype", default=None,
                    help="storage precision frozen into the spec: "
                         "float32 (default), bfloat16, or float16 "
                         "(aliases f32/bf16/f16 accepted); "
                         "accumulation is always f32")
    ap.add_argument("--quantize", default=None, choices=("int8",),
                    help="with --export: embed per-channel symmetric "
                         "int8 weights (+ f32 scales) in the program "
                         "file, from a seed-0 init of the model (the "
                         "export-transform demo flow; real deployments "
                         "call repro.quant.quantize_program on trained "
                         "params)")
    ap.add_argument("--backend", default=None,
                    help="policy backend (a registered name, 'pallas', "
                         f"or 'auto'; registered: "
                         f"{', '.join(available_backends())}; default: "
                         "heuristic)")
    ap.add_argument("--plans", default=None, metavar="PATH",
                    help="autotuner plan file consulted by "
                         "--backend auto")
    ap.add_argument("--measure", action="store_true",
                    help="with --backend auto: tune plan misses while "
                         "building (the tuned-program export flow; "
                         "without it, resolution is lookup-only and a "
                         "cold planner exports heuristic layers)")
    ap.add_argument("--export", default=None, metavar="PATH",
                    help="write the (first-role) spec JSON here")
    ap.add_argument("--load", default=None, metavar="PATH",
                    help="load a program file instead of resolving "
                         "(falls back to fresh resolution when "
                         "corrupt/stale)")
    ap.add_argument("--stats", action="store_true",
                    help="after describing, print the resolution "
                         "metrics this invocation produced (plan-cache "
                         "hits/misses, provenance breakdown, "
                         "degradations)")
    args = ap.parse_args(argv)

    from repro import obs
    from repro.models.gan import GanConfig
    from repro.program import Program, ProgramSpec, load_or_build

    counters0 = dict(obs.snapshot()["counters"]) if args.stats else {}

    planner = None
    if args.plans:
        from repro.tune import Planner
        planner = Planner(args.plans)
        if planner.load_error:
            print(f"warning: plan file ignored ({planner.load_error})")
    policy = DataflowPolicy(backend=args.backend) if args.backend \
        else None
    mesh = None
    if args.mesh:
        try:
            data, model = args.mesh.lower().split("x")
            mesh = (int(data), int(model))
        except ValueError:
            ap.error(f"--mesh wants DATAxMODEL (e.g. 4x2), "
                     f"got {args.mesh!r}")
    try:
        cfg = GanConfig(name=args.model,
                        channel_scale=args.channel_scale,
                        backend=args.backend, mesh=mesh,
                        dtype=args.dtype or "float32")
    except ValueError as e:
        ap.error(str(e))
    if args.quantize and not args.export:
        ap.error("--quantize only makes sense with --export")
    roles = (args.role,) if args.role != "both" \
        else ("generator", "discriminator")
    if args.load and args.role == "both":
        # a program file freezes one network; describe that one (a
        # corrupt file keeps the generator default and falls back)
        try:
            roles = (ProgramSpec.load(args.load).role,)
        except Exception:
            roles = ("generator",)

    exported = False
    for role in roles:
        if args.load:
            prog, loaded = load_or_build(
                args.load, cfg, args.batch, role, policy=policy,
                planner=planner, measure=args.measure)
            if not loaded:
                print(f"note: {args.load} unusable for "
                      f"{args.model}/{role}; rebuilt from config")
            spec = prog.spec
        else:
            spec = ProgramSpec.build(cfg, args.batch, role,
                                     policy=policy, planner=planner,
                                     measure=args.measure)
        print(spec.describe())
        if args.export and not exported:
            if args.quantize:
                import jax

                from repro.models.gan import init_gan
                from repro.quant import quantize_program
                g_params, d_params = init_gan(cfg, jax.random.PRNGKey(0))
                params = g_params if spec.role == "generator" \
                    else d_params
                spec = quantize_program(spec, params)
            spec.save(args.export)
            print(f"wrote {args.export}"
                  + (" (int8 weights embedded)" if args.quantize
                     else ""))
            exported = True
        if role != roles[-1]:
            print()
    # a loadable spec is also buildable into a runtime object; keep the
    # smoke honest by exercising the wrap (no trace, no arrays)
    Program(spec)
    if args.stats:
        counters = obs.snapshot()["counters"]
        deltas = {k: v - counters0.get(k, 0)
                  for k, v in sorted(counters.items())
                  if v - counters0.get(k, 0)
                  and (k.startswith("dataflow.resolve")
                       or k.startswith("program."))}
        print("\nresolution stats:")
        for name, v in deltas.items():
            print(f"  {name:36s} {v}")
        if not deltas:
            print("  (none)")
    return 0


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    sys.exit(main())
