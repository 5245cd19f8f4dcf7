"""Wall-clock microbenchmarks (CPU, XLA-compiled): GANAX dataflow vs the
zero-insertion baseline on the paper's layer geometries.

The zero-elimination speedup is algorithmic, so it shows up even on CPU:
the GANAX path executes only consequential MACs.  (Kernel-level VMEM/MXU
effects require real TPU hardware; the interpret-mode Pallas kernel is
validated for correctness in tests/, not timed here.)

Runnable directly with the same knobs the tuner and CI use::

    PYTHONPATH=src python benchmarks/microbench.py \
        --backends polyphase zero-insert --repeats 5 --models dcgan

``--backends`` accepts any registered dataflow backend plus ``auto``
(planner-consulting dispatch — tuned when a plan file is warm, heuristic
otherwise).  The default model pool includes ``3dgan`` so the artifacts
track the volumetric trajectory now that the Pallas kernel covers 3-D;
its wall-clock rows feed the CI regression gate like every other model.
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.gans import GAN_MODELS
from repro.core.dataflow import (DataflowPolicy, Epilogue,
                                 available_backends, tconv,
                                 uop_cache_info)
from repro.core.tconv import tconv_output_shape

DEFAULT_BACKENDS = ("polyphase", "zero-insert")


def _time(fn, *args, iters=5):
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


def bench_dataflows(models=("dcgan", "3dgan"), batch=2, channel_scale=0.25,
                    backends=DEFAULT_BACKENDS, repeats=5):
    """Per-model generator tconv wall-clock for each requested backend.

    Emits ``micro/<model>/<backend>_us`` per backend (dashes become
    underscores), the legacy ``ganax_us`` alias for the polyphase path,
    and ``wallclock_speedup`` (zero-insert / polyphase) when both are in
    the pool — the row names `BENCH_dataflow.json` tracks across PRs."""
    rows = []
    cache0 = uop_cache_info()
    print("\n== microbench: dataflow backends "
          f"{list(backends)} (batch={batch}, channels×{channel_scale}) ==")
    for name in models:
        g_layers, _ = GAN_MODELS[name]
        totals = dict.fromkeys(backends, 0.0)
        for l in g_layers:
            if not l.transposed:
                continue
            cin = max(1, int(l.cin * channel_scale))
            cout = max(1, int(l.cout * channel_scale))
            rng = np.random.default_rng(0)
            x = jnp.asarray(rng.normal(size=(batch, *l.in_spatial, cin)),
                            jnp.float32)
            w = jnp.asarray(rng.normal(
                size=(*l.kernel, cin, cout)), jnp.float32)
            for backend in backends:
                policy = DataflowPolicy(backend=backend)
                f = jax.jit(lambda x, w, l=l, policy=policy: tconv(
                    x, w, l.strides, l.paddings, policy=policy))
                totals[backend] += _time(f, x, w, iters=repeats)
        summary = "  ".join(f"{b}={totals[b]*1e3:7.2f}ms"
                            for b in backends)
        for backend in backends:
            rows.append((f"micro/{name}/{backend.replace('-', '_')}_us",
                         totals[backend] * 1e6, ""))
        if "polyphase" in totals:
            rows.append((f"micro/{name}/ganax_us",
                         totals["polyphase"] * 1e6, "alias of polyphase"))
        if "polyphase" in totals and "zero-insert" in totals:
            speed = totals["zero-insert"] / totals["polyphase"] \
                if totals["polyphase"] else float("nan")
            rows.append((f"micro/{name}/wallclock_speedup", speed,
                         "zero-elimination, measured"))
            summary += f"  speedup={speed:4.2f}x"
        print(f"  {name:8s} {summary}")
    info = uop_cache_info()
    print(f"  μop cache: {info['hits'] - cache0['hits']} hits / "
          f"{info['misses'] - cache0['misses']} misses (this bench)")
    return rows


def bench_fused_epilogue(models=("dcgan", "3dgan"), batch=2,
                         channel_scale=0.25, repeats=5,
                         backend="polyphase"):
    """Fused (in-dispatch) vs unfused (out-of-op ``+ b`` / activation)
    epilogue wall-clock over each model's generator tconv layers.

    Emits ``micro/<model>/fused_us`` / ``unfused_us`` and the
    machine-relative ``fused_speedup`` (unfused / fused — both sides
    from the same run).  ``fused_us`` feeds the CI regression gate; on
    the pure-JAX backend runnable in CI the two formulations compile to
    near-identical fused XLA, so the gated expectation is "no
    regression", with the HBM-round-trip win reserved for real-TPU
    kernel runs."""
    rows = []
    ep = Epilogue(bias=True, activation="relu")
    policy = DataflowPolicy(backend=backend)
    print(f"\n== microbench: fused vs unfused epilogue ({backend}, "
          f"batch={batch}, channels×{channel_scale}) ==")
    for name in models:
        g_layers, _ = GAN_MODELS[name]
        fused_total = unfused_total = 0.0
        for l in g_layers:
            if not l.transposed:
                continue
            cin = max(1, int(l.cin * channel_scale))
            cout = max(1, int(l.cout * channel_scale))
            rng = np.random.default_rng(0)
            x = jnp.asarray(rng.normal(size=(batch, *l.in_spatial, cin)),
                            jnp.float32)
            w = jnp.asarray(rng.normal(size=(*l.kernel, cin, cout)),
                            jnp.float32)
            b = jnp.asarray(rng.normal(size=(cout,)), jnp.float32)
            fused = jax.jit(lambda x, w, b, l=l: tconv(
                x, w, l.strides, l.paddings, policy=policy, bias=b,
                epilogue=ep))
            unfused = jax.jit(lambda x, w, b, l=l: jax.nn.relu(tconv(
                x, w, l.strides, l.paddings, policy=policy) + b))
            fused_total += _time(fused, x, w, b, iters=repeats)
            unfused_total += _time(unfused, x, w, b, iters=repeats)
        speed = unfused_total / fused_total if fused_total \
            else float("nan")
        rows.append((f"micro/{name}/fused_us", fused_total * 1e6, ""))
        rows.append((f"micro/{name}/unfused_us", unfused_total * 1e6, ""))
        rows.append((f"micro/{name}/fused_speedup", speed,
                     "unfused/fused, machine-relative"))
        print(f"  {name:8s} fused={fused_total*1e3:7.2f}ms  "
              f"unfused={unfused_total*1e3:7.2f}ms  "
              f"ratio={speed:4.2f}x")
    return rows


def bench_program(models=("dcgan", "3dgan"), batch=2, channel_scale=0.25,
                  repeats=5, backend="polyphase"):
    """Ahead-of-time compiled Program vs the legacy per-call dispatch
    threading over each model's full generator forward.

    Emits ``micro/<model>/program_us`` (the Program API's jitted
    executable — this row feeds the CI regression gate: the supported
    entry point must not regress), ``generator_apply_us`` (the
    legacy-wrapper path, now itself program-backed), and the
    machine-relative ``program_speedup`` (legacy / program, both sides
    from the same run)."""
    from repro.models.gan import GanConfig, generator_apply, init_gan
    from repro.program import Program

    rows = []
    print(f"\n== microbench: program vs legacy dispatch ({backend}, "
          f"batch={batch}, channels×{channel_scale}) ==")
    for name in models:
        cfg = GanConfig(name=name, channel_scale=channel_scale,
                        backend=backend)
        g_params, _ = init_gan(cfg, jax.random.PRNGKey(0))
        z = jnp.asarray(np.random.default_rng(0).normal(
            size=(batch, *cfg.input_shape)), jnp.float32)
        prog = Program.build(cfg, batch, "generator")
        legacy = jax.jit(lambda p, z, cfg=cfg: generator_apply(p, z, cfg))
        t_prog = _time(prog.apply, g_params, z, iters=repeats)
        t_leg = _time(legacy, g_params, z, iters=repeats)
        speed = t_leg / t_prog if t_prog else float("nan")
        rows.append((f"micro/{name}/program_us", t_prog * 1e6, ""))
        rows.append((f"micro/{name}/generator_apply_us", t_leg * 1e6,
                     "legacy wrapper"))
        rows.append((f"micro/{name}/program_speedup", speed,
                     "legacy/program, machine-relative"))
        print(f"  {name:8s} program={t_prog*1e3:7.2f}ms  "
              f"legacy={t_leg*1e3:7.2f}ms  ratio={speed:4.2f}x")
    return rows


def bench_precision(models=("dcgan", "3dgan"), batch=2,
                    channel_scale=0.25, repeats=5,
                    backend="polyphase"):
    """Storage-precision rows (repro.quant): the bf16 generator
    executable and the int8-weight deploy path, plus analytic HBM
    traffic at each precision.

    Emits per model:

    * ``generator_bf16_us`` — the full generator forward with
      ``dtype="bfloat16"`` storage (f32 accumulation inside the op).
      Gated in CI like ``program_us``: the low-precision path must not
      regress.  On CPU XLA bf16 is usually emulated, so the row tracks
      "does the bf16 program stay runnable and sane", not a memory-BW
      win — that is what the analytic byte rows are for.
    * ``generator_int8_us`` — the int8-weight export served end to end:
      ``quantize_program`` → JSON round-trip → ``Program`` (weights
      dequantized into bf16 storage at load) → forward (informational).
    * ``hbm_bytes_f32`` / ``hbm_bytes_bf16`` / ``hbm_bytes_int8`` —
      analytic per-forward HBM traffic (weights + biases + layer
      in/out activations, batch included): storage itemsize per
      element, except int8 weights at 1 B + one f32 scale per output
      channel, and biases always f32 (the accumulator precision).
      Deterministic (no timing), informational — they document the
      compression the storage dtype buys on a memory-bound forward."""
    import json as _json

    from repro.models.gan import GanConfig, init_gan
    from repro.program import Program
    from repro.program.spec import ProgramSpec
    from repro.quant import quantize_program, storage_itemsize

    rows = []
    print(f"\n== microbench: storage precision ({backend}, "
          f"batch={batch}, channels×{channel_scale}) ==")
    for name in models:
        cfg32 = GanConfig(name=name, channel_scale=channel_scale,
                          backend=backend)
        cfgbf = GanConfig(name=name, channel_scale=channel_scale,
                          backend=backend, dtype="bfloat16")
        g_params, _ = init_gan(cfg32, jax.random.PRNGKey(0))
        z = jnp.asarray(np.random.default_rng(0).normal(
            size=(batch, *cfg32.input_shape)), jnp.float32)

        prog_bf = Program.build(cfgbf, batch, "generator")
        t_bf = _time(prog_bf.apply, g_params, z, iters=repeats)
        rows.append((f"micro/{name}/generator_bf16_us", t_bf * 1e6,
                     "bf16 storage, f32 accumulation; gated"))

        # int8 deploy: export → JSON round-trip → dequantize-at-load
        spec_q = ProgramSpec.from_json(_json.loads(_json.dumps(
            quantize_program(prog_bf.spec, g_params).to_json())))
        prog_q = Program(spec_q)
        t_q = _time(prog_q.apply, prog_q.params, z, iters=repeats)
        rows.append((f"micro/{name}/generator_int8_us", t_q * 1e6,
                     "int8-weight export served (informational)"))

        # analytic HBM traffic per forward at each precision
        g_layers, _ = cfgbf.layers
        for label, wsize, asize, int8 in (("f32", 4, 4, False),
                                          ("bf16", 2, 2, False),
                                          ("int8", 1, 2, True)):
            total = 0
            for l in g_layers:
                taps = int(np.prod(np.asarray(l.kernel)))
                w_el = taps * l.cin * l.cout
                total += w_el * (1 if int8 else wsize)
                if int8:
                    total += 4 * l.cout            # per-channel scales
                total += 4 * l.cout                # bias, always f32
                out_sp = tconv_output_shape(
                    (batch, *l.in_spatial, l.cin),
                    (*l.kernel, l.cin, l.cout), l.strides, l.paddings
                )[1:-1] if l.transposed else l.conv_out_spatial()
                total += batch * asize * (
                    int(np.prod(np.asarray(l.in_spatial))) * l.cin +
                    int(np.prod(np.asarray(out_sp))) * l.cout)
            rows.append((f"micro/{name}/hbm_bytes_{label}", float(total),
                         "analytic per-forward traffic"))
        f32b = rows[-3][1]
        print(f"  {name:8s} bf16={t_bf*1e3:7.2f}ms  int8={t_q*1e3:7.2f}ms"
              f"  bytes f32={f32b/1e6:6.2f}MB"
              f"  bf16={rows[-2][1]/1e6:6.2f}MB"
              f"  int8={rows[-1][1]/1e6:6.2f}MB")
    assert storage_itemsize("bfloat16") == 2   # the asize=2 rows above
    return rows


def bench_obs_overhead(models=("dcgan",), batch=2,
                       channel_scale=0.25, repeats=5,
                       backend="polyphase"):
    """Cost of the obs instrumentation on the ``Program.apply`` hot
    path: the instrumented wrapper vs the raw jitted callable
    (``prog._apply``), timed interleaved so both sides share every noise
    window and reduced with the per-thunk *minimum* — the wrapper delta
    is sub-microsecond on a millisecond-scale op, so a median is still
    noise-dominated on a contended host while the min (noise is
    strictly additive) recovers both sides' intrinsic time.  Only the
    *fastest* program is measured by default: the wrapper delta is a
    fixed per-call cost, so the quickest apply gives the tightest
    relative bound, while on a hundreds-of-ms program the same delta is
    thousands of times smaller than run-to-run drift — that row could
    only ever flake, never inform.

    Emits ``micro/<model>/obs_overhead_pct`` — the **disabled**-tracing
    wrapper cost, clamped at 0 and gated in CI against an absolute cap
    (observability must stay near-free when off) — plus the
    informational ``obs_enabled_overhead_pct`` (tracing on, in-memory
    sink: the price of actually recording spans)."""
    from repro import obs
    from repro.models.gan import GanConfig, init_gan
    from repro.program import Program
    from repro.tune.measure import time_interleaved

    rows = []
    print(f"\n== microbench: obs overhead on program apply ({backend}, "
          f"batch={batch}, channels×{channel_scale}) ==")
    was_enabled, prior_sink = obs.is_enabled(), obs.get_sink()
    rounds = max(repeats * 3, 15)   # min over many rounds: noise floor
    try:
        for name in models:
            cfg = GanConfig(name=name, channel_scale=channel_scale,
                            backend=backend)
            g_params, _ = init_gan(cfg, jax.random.PRNGKey(0))
            z = jnp.asarray(np.random.default_rng(0).normal(
                size=(batch, *cfg.input_shape)), jnp.float32)
            prog = Program.build(cfg, batch, "generator")
            thunks = [lambda: prog.apply(g_params, z),
                      lambda: prog._apply(g_params, z)]
            obs.disable()
            t_off, t_raw = time_interleaved(thunks, warmup=1,
                                            repeats=rounds, reduce="min")
            obs.enable()    # fresh in-memory sink
            t_on, t_raw_on = time_interleaved(thunks, warmup=1,
                                              repeats=rounds,
                                              reduce="min")
            obs.disable()
            off_pct = max(0.0, (t_off / t_raw - 1.0) * 100.0) \
                if t_raw else 0.0
            on_pct = max(0.0, (t_on / t_raw_on - 1.0) * 100.0) \
                if t_raw_on else 0.0
            rows.append((f"micro/{name}/obs_overhead_pct", off_pct,
                         "apply wrapper vs raw callable, tracing off; "
                         "gated: absolute cap"))
            rows.append((f"micro/{name}/obs_enabled_overhead_pct", on_pct,
                         "tracing on, memory sink (informational)"))
            print(f"  {name:8s} raw={t_raw*1e6:8.1f}us  "
                  f"disabled=+{off_pct:4.2f}%  enabled=+{on_pct:4.2f}%")
    finally:
        if was_enabled:
            obs.enable(prior_sink)
        else:
            obs.disable()
    return rows


def bench_kernel_interpret():
    """Sanity timing of the Pallas kernel in interpret mode — both the
    planar and the volumetric (3-D) entry points (correctness path; not
    a perf number)."""
    rng = np.random.default_rng(0)
    policy = DataflowPolicy(backend="pallas-interpret")
    x = jnp.asarray(rng.normal(size=(1, 8, 8, 128)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(4, 4, 128, 128)), jnp.float32)
    t0 = time.perf_counter()
    jax.block_until_ready(tconv(x, w, (2, 2), (1, 1), policy=policy))
    dt = time.perf_counter() - t0
    print(f"\n  pallas-interpret tconv 8x8x128→16x16x128: {dt*1e3:.1f}ms "
          "(correctness path)")
    x3 = jnp.asarray(rng.normal(size=(1, 4, 4, 4, 32)), jnp.float32)
    w3 = jnp.asarray(rng.normal(size=(4, 4, 4, 32, 32)), jnp.float32)
    t0 = time.perf_counter()
    jax.block_until_ready(tconv(x3, w3, (2, 2, 2), (1, 1, 1),
                                policy=policy))
    dt3 = time.perf_counter() - t0
    print(f"  pallas-interpret tconv3d 4³x32→8³x32: {dt3*1e3:.1f}ms "
          "(correctness path)")
    return [("micro/pallas_interpret_us", dt * 1e6, "interpret mode"),
            ("micro/pallas_interpret_3d_us", dt3 * 1e6,
             "interpret mode, volumetric")]


def run_all(models=("dcgan", "3dgan"), batch=2, channel_scale=0.25,
            backends=DEFAULT_BACKENDS, repeats=5):
    rows = bench_dataflows(models, batch, channel_scale,
                           backends=backends, repeats=repeats)
    rows += bench_fused_epilogue(models, batch, channel_scale,
                                 repeats=repeats)
    rows += bench_program(models, batch, channel_scale, repeats=repeats)
    rows += bench_precision(models, batch, channel_scale,
                            repeats=repeats)
    # first model only: the quickest apply bounds the fixed wrapper
    # cost tightest (see bench_obs_overhead)
    rows += bench_obs_overhead(models[:1], batch, channel_scale,
                               repeats=repeats)
    rows += bench_kernel_interpret()
    return rows


def main(argv=None):
    valid = available_backends() + ("auto",)
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--models", nargs="+", default=["dcgan", "3dgan"],
                    choices=sorted(GAN_MODELS))
    ap.add_argument("--backends", nargs="+", default=list(DEFAULT_BACKENDS),
                    choices=sorted(valid),
                    help="dataflow backends to time")
    ap.add_argument("--repeats", type=int, default=5,
                    help="timed iterations per layer (mean reported)")
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--channel-scale", type=float, default=0.25)
    args = ap.parse_args(argv)
    return run_all(models=tuple(args.models), batch=args.batch,
                   channel_scale=args.channel_scale,
                   backends=tuple(args.backends), repeats=args.repeats)


if __name__ == "__main__":
    main()
