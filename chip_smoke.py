#!/usr/bin/env python3
"""Run the GAN train and serve path once on a TPU, through its entry points.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # the sharded path on four chips

One chip: dcgan at published widths (``channel_scale=1.0``, f32, the
default polyphase path) takes three ``make_gan_train_step`` steps at
batch 64; a ``GanEngine`` with its default buckets answers a few
requests and closes; then the generator programs are checked against
each other under ``jax.default_matmul_precision("highest")``: for
dcgan, polyphase against the lax-built zero-insert oracle, and the
Pallas kernel (``pallas-tpu``) against polyphase; for dcgan and 3dgan
at batch 2, polyphase against a float64 NumPy generator on the host,
which shares no code with JAX or the dataflow.  (3dgan's zero-insert
generator is left out: the chip's compiler spends many minutes on it
at highest precision.)

Four chips (``--chips 4``), and nothing else: a dcgan generator
``Program`` on a ``(2, 2)`` mesh, a ``GanEngine`` on that mesh, and a
data-parallel training step on a ``(4, 1)`` mesh, each compared with
its single-device counterpart in the same process, at the default
matmul precision.

Each phase prints one line of its numbers.  These are smoke readings,
not benchmark numbers.  The last line,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``,
is printed only when every phase passed.  The script exits non-zero
when JAX finds no TPU (``JAX_PLATFORMS=cpu`` included) and when any
phase fails.  The persistent compile cache is kept where
``JAX_COMPILATION_CACHE_DIR`` says, or else in ``<checkout>/.jax_cache``.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time
import traceback

import jax
import numpy as np

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

from repro import obs  # noqa: E402
from repro.core.dataflow import DataflowPolicy  # noqa: E402
from repro.models.gan import GanConfig, init_gan  # noqa: E402
from repro.program import Program  # noqa: E402
from repro.serve.gan_engine import GanEngine, ServerClosed  # noqa: E402
from repro.train.loop import make_gan_train_step  # noqa: E402
from repro.utils.compile_cache import enable_compile_cache  # noqa: E402

BATCH = 64              # the published DCGAN minibatch
TRAIN_STEPS = 3
REQUESTS = (1, 8, 64)   # samples per serving request
BATCH_3D = 2            # 3dgan's 64^3 voxel output at a small batch
# f32 generator-output parity, set before any chip run: the model-level
# backend-parity tolerance of tests/test_gan_models.py.  Outputs are
# tanh-bounded, so the bound is absolute.
F32_ATOL = 2e-4
# data-parallel step parity, as tests/test_sharded_gan.py holds it
DP_RTOL, DP_ATOL = 1e-4, 1e-5

HIGHEST = "highest"


class CompileLog:
    """Seconds spent getting executables (compiling, or loading them from
    the persistent cache) and the cache's hits and misses, from JAX's
    monitoring events."""

    def __init__(self):
        self.seconds = 0.0
        self.executables = 0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.executables += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def state(self) -> tuple:
        return self.seconds, self.executables, self.hits, self.misses

    def since(self, before: tuple) -> dict:
        now = self.state()
        return {"compile_s": round(now[0] - before[0], 3),
                "executables": now[1] - before[1],
                "cache_hits": now[2] - before[2],
                "cache_misses": now[3] - before[3]}


class PhaseError(AssertionError):
    pass


def require(cond: bool, what: str) -> None:
    if not cond:
        raise PhaseError(what)


def max_abs_diff(a, b) -> float:
    return float(np.max(np.abs(np.asarray(a, np.float32)
                               - np.asarray(b, np.float32))))


def check_images(x, shape, what: str) -> None:
    x = np.asarray(x)
    require(x.shape == shape, f"{what}: shape {x.shape}, want {shape}")
    require(bool(np.isfinite(x).all()), f"{what}: non-finite values")
    require(float(np.abs(x).max()) <= 1.0,
            f"{what}: values outside tanh's [-1, 1]")


def dcgan(seed: int):
    cfg = GanConfig(name="dcgan", channel_scale=1.0)
    g, d = init_gan(cfg, jax.random.PRNGKey(seed))
    return cfg, g, d


def latent(cfg: GanConfig, batch: int, seed: int):
    return jax.random.normal(jax.random.PRNGKey(seed), (batch, cfg.z_dim))


def generator(cfg: GanConfig, g, z, backend: str | None = None,
              mesh=None):
    """Build a forward-only generator program, run it once on ``z`` and
    return ``(program, output)``."""
    prog = Program.build(cfg, int(z.shape[0]), "generator",
                         policy=DataflowPolicy(backend=backend),
                         differentiable=False, mesh=mesh)
    if prog.input_sharding is not None:
        z = jax.device_put(z, prog.input_sharding)
    return prog, jax.block_until_ready(prog.apply(g, z))


def numpy_tconv(x, w, strides, paddings):
    """Transposed convolution in NumPy: every input pixel scatters
    ``x @ w[tap]`` to ``stride * i + tap``, then the padding is cropped."""
    nd = x.ndim - 2
    in_sp = x.shape[1:1 + nd]
    full = [s * (i - 1) + k for s, i, k in zip(strides, in_sp, w.shape)]
    out = np.zeros((x.shape[0], *full, w.shape[-1]))
    for tap in np.ndindex(*w.shape[:nd]):
        at = tuple(slice(t, t + s * (i - 1) + 1, s)
                   for t, s, i in zip(tap, strides, in_sp))
        out[(slice(None),) + at] += x @ w[tap]
    crop = tuple(slice(p, f - p) for p, f in zip(paddings, full))
    return out[(slice(None),) + crop]


def host_reference(cfg: GanConfig, g, z) -> np.ndarray:
    """The generator in float64 NumPy on the host: a reference that
    shares no code with JAX or with the dataflow under test."""
    layers, _ = cfg.layers
    require(all(layer.transposed for layer in layers),
            f"{cfg.name}: the host reference covers tconv generators only")
    p = {k: np.asarray(v, np.float64) for k, v in g.items()}
    x = np.asarray(z, np.float64) @ p["proj_w"] + p["proj_b"]
    x = np.maximum(x.reshape((len(x), *layers[0].in_spatial,
                              layers[0].cin)), 0.0)
    for i, layer in enumerate(layers):
        x = numpy_tconv(x, p[f"t{i}_w"], layer.strides, layer.paddings)
        x = x + p[f"t{i}_b"]
        x = np.tanh(x) if i == len(layers) - 1 else np.maximum(x, 0.0)
    return x


def kernel_calls(prog: Program, params, x) -> int:
    """Pallas TPU kernel calls in the program's lowered module."""
    return jax.jit(prog.forward).lower(params, x).as_text().count(
        "tpu_custom_call")


def leaves_moved(before, after) -> float:
    return max(max_abs_diff(a, b) for a, b in
               zip(jax.tree.leaves(before), jax.tree.leaves(after)))


# -- phases -------------------------------------------------------------------

def phase_device(chips: int) -> dict:
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise PhaseError(f"no TPU: JAX runs on {dev.platform!r} "
                         f"({dev.device_kind}); this script measures "
                         f"nothing off the chip")
    require(len(devices) >= chips,
            f"{chips} chips asked for, JAX sees {len(devices)}")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices)}


def phase_training(seed: int, log: CompileLog) -> dict:
    cfg, g, d = dcgan(seed)
    step, (g_prog, d_prog) = make_gan_train_step(cfg, BATCH)
    backends = sorted({le.backend for p in (g_prog, d_prog)
                       for le in p.spec.layers})
    real = jax.random.uniform(jax.random.PRNGKey(seed + 2),
                              (BATCH, 64, 64, 3), minval=-1.0, maxval=1.0)
    before = log.state()
    state, losses, seconds = (g, d), [], []
    for i in range(TRAIN_STEPS):
        batch = {"z": latent(cfg, BATCH, seed + 10 + i), "real": real}
        t0 = time.perf_counter()
        state, metrics = step(state, batch)
        metrics = jax.device_get(jax.block_until_ready(metrics))
        jax.block_until_ready(state)
        seconds.append(time.perf_counter() - t0)
        losses.append(float(metrics["loss"]))
    require(all(np.isfinite(losses)), f"non-finite losses {losses}")
    g_moved, d_moved = leaves_moved(g, state[0]), leaves_moved(d, state[1])
    require(g_moved > 0 and d_moved > 0,
            f"parameters did not change (g {g_moved}, d {d_moved})")
    return {"model": "dcgan", "batch": BATCH, "dtype": cfg.dtype,
            "backends": backends, **log.since(before),
            "first_step_s": seconds[0], "step_s": seconds[1:],
            "losses": losses, "g_max_update": g_moved,
            "d_max_update": d_moved}


def phase_serving(seed: int, log: CompileLog) -> dict:
    cfg, g, _ = dcgan(seed)
    before = log.state()
    t0 = time.perf_counter()
    engine = GanEngine(cfg, g, seed=seed)
    start_s = time.perf_counter() - t0
    compiled = log.since(before)
    latency_s = []
    try:
        for n in REQUESTS:
            t0 = time.perf_counter()
            out = engine.submit(n).result(timeout=300)
            latency_s.append(time.perf_counter() - t0)
            check_images(out, (n, 64, 64, 3), f"request of {n}")
    finally:
        engine.close(timeout=60)
    require(not engine._thread.is_alive(), "engine thread still running")
    try:
        engine.submit(1)
    except ServerClosed:
        pass
    else:
        raise PhaseError("a closed engine accepted a request")
    return {"model": "dcgan", "buckets": list(engine.buckets),
            "backends": sorted({le.backend for le in engine.spec.layers}),
            "start_s": start_s, **compiled, "requests": list(REQUESTS),
            "latency_s": latency_s,
            "samples_served": engine.samples_served}


def phase_correctness(seed: int, log: CompileLog) -> dict:
    before = log.state()
    out = {"precision": HIGHEST, "atol": F32_ATOL}
    with jax.default_matmul_precision(HIGHEST):
        cfg, g, _ = dcgan(seed)
        z = latent(cfg, BATCH, seed + 1)
        shape = (BATCH, 64, 64, 3)
        _, poly = generator(cfg, g, z, "polyphase")
        _, zero = generator(cfg, g, z, "zero-insert")
        kprog, kern = generator(cfg, g, z, "pallas-tpu")
        for name, y in (("polyphase", poly), ("zero-insert", zero),
                        ("pallas-tpu", kern)):
            check_images(y, shape, f"dcgan {name}")
        out["dcgan_kernel_calls"] = kernel_calls(kprog, g, z)
        require(out["dcgan_kernel_calls"] >= len(kprog.spec.layers),
                f"pallas-tpu program holds {out['dcgan_kernel_calls']} "
                f"kernel calls for {len(kprog.spec.layers)} layers")
        out["dcgan_max_abs_out"] = float(np.abs(np.asarray(poly)).max())
        out["dcgan_poly_vs_zero"] = max_abs_diff(poly, zero)
        out["dcgan_pallas_vs_poly"] = max_abs_diff(kern, poly)

        rows = 2   # the chip against the host, on the first rows
        out["dcgan_poly_vs_host"] = max_abs_diff(
            poly[:rows], host_reference(cfg, g, z[:rows]))

        cfg3 = GanConfig(name="3dgan", channel_scale=1.0)
        g3, _ = init_gan(cfg3, jax.random.PRNGKey(seed))
        z3 = latent(cfg3, BATCH_3D, seed + 1)
        _, poly3 = generator(cfg3, g3, z3, "polyphase")
        host3 = host_reference(cfg3, g3, z3)
        for name, y in (("polyphase", poly3), ("host", host3)):
            check_images(y, (BATCH_3D, 64, 64, 64, 1), f"3dgan {name}")
        out["3dgan_max_abs_out"] = float(np.abs(np.asarray(poly3)).max())
        out["3dgan_poly_vs_host"] = max_abs_diff(poly3, host3)
    out.update(log.since(before))
    bad = {k: v for k, v in out.items()
           if k.endswith(("_vs_zero", "_vs_poly", "_vs_host"))
           and not v <= F32_ATOL}
    require(not bad, f"over the f32 tolerance {F32_ATOL}: {bad} "
                     f"(all readings: {out})")
    return out


def phase_sharded(seed: int, log: CompileLog) -> dict:
    """The sharded path on four chips against single-device runs, all at
    the default matmul precision: the engine dispatches from its own
    thread, which a precision context does not reach, and the chip's
    compiler takes minutes over a highest-precision training step."""
    degraded = obs.counter("program.mesh_degraded")
    degraded0 = degraded.value
    before = log.state()
    out = {"precision": "default"}
    cfg, g, d = dcgan(seed)
    z = latent(cfg, BATCH, seed + 1)
    prog, sharded = generator(cfg, g, z, mesh=(2, 2))
    _, single = generator(cfg, g, z)
    layouts = [le.sharding for le in prog.spec.layers]
    require(layouts[0] == "cout", f"dcgan g1 not Cout-sharded: {layouts}")
    out["program_devices"] = len(sharded.sharding.device_set)
    require(out["program_devices"] == 4,
            f"(2, 2) program output spans {out['program_devices']} devices")
    out["program_layouts"] = layouts
    out["program_vs_single"] = max_abs_diff(sharded, single)
    require(out["program_vs_single"] <= F32_ATOL,
            f"(2, 2) program vs single device: {out['program_vs_single']}")

    buckets = (2, 4, 8)   # the (2, 2) mesh's data axis divides each
    meshed = GanEngine(cfg, g, buckets=buckets, seed=seed, mesh=(2, 2))
    plain = GanEngine(cfg, g, buckets=buckets, seed=seed, mesh=None)
    diffs = []
    try:
        require(all(p.device_count == 4 for p in meshed.programs.values()),
                "an engine bucket program is not on 4 devices")
        for n in REQUESTS:
            a = meshed.submit(n).result(timeout=300)
            b = plain.submit(n).result(timeout=300)
            check_images(a, (n, 64, 64, 3), f"meshed engine request of {n}")
            diffs.append(max_abs_diff(a, b))
    finally:
        meshed.close(timeout=60)
        plain.close(timeout=60)
    out["engine_vs_single"] = diffs
    require(max(diffs) <= F32_ATOL, f"engine streams differ: {diffs}")

    step_1, _ = make_gan_train_step(cfg, BATCH, mesh=None)
    step_4, _ = make_gan_train_step(cfg, BATCH, mesh=(4, 1))
    g_sh, d_sh = step_4.state_shardings
    s1, s4 = (g, d), (jax.device_put(g, g_sh), jax.device_put(d, d_sh))
    real = jax.random.uniform(jax.random.PRNGKey(seed + 2),
                              (BATCH, 64, 64, 3), minval=-1.0, maxval=1.0)
    losses = []
    for i in range(2):
        batch = {"z": latent(cfg, BATCH, seed + 10 + i), "real": real}
        s1, m1 = step_1(s1, batch)
        s4, m4 = step_4(s4, batch)
        for k in m1:
            np.testing.assert_allclose(float(m4[k]), float(m1[k]),
                                       rtol=DP_RTOL, err_msg=k)
        losses.append((float(m1["loss"]), float(m4["loss"])))
    for a, b in zip(jax.tree.leaves(s1), jax.tree.leaves(s4)):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=DP_RTOL, atol=DP_ATOL)
    out["dp_param_vs_single"] = leaves_moved(s1, s4)
    out["dp_losses_single_vs_4"] = losses
    spans = {len(x.sharding.device_set) for x in jax.tree.leaves(s4)}
    require(spans == {4}, f"DP state spans {spans} devices")
    out["mesh_degraded"] = degraded.value - degraded0
    require(out["mesh_degraded"] == 0, "a program's mesh degraded")
    out.update(log.since(before))
    return out


def report(name: str, numbers: dict) -> None:
    print(f"{name}: " + " ".join(f"{k}={v}" for k, v in numbers.items()),
          flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: train, serve and check on one chip; 4: only "
                         "the sharded path and its single-device "
                         "comparison")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    try:
        device = phase_device(args.chips)
    except PhaseError as e:
        print(f"device: FAILED {e}", file=sys.stderr, flush=True)
        return 1
    device["cache_dir"] = enable_compile_cache()
    report("device", device)
    log = CompileLog()
    phases = [("sharded", phase_sharded)] if args.chips == 4 else [
        ("training", phase_training), ("serving", phase_serving),
        ("correctness", phase_correctness)]
    failed = []
    for name, phase in phases:
        t0 = time.perf_counter()
        try:
            numbers = phase(args.seed, log)
        except Exception as e:  # noqa: BLE001 — reported, and fails the run
            traceback.print_exc()
            print(f"{name}: FAILED {type(e).__name__}: {e}", flush=True)
            failed.append(name)
            continue
        report(name, {**numbers, "phase_s": time.perf_counter() - t0})
    report("compile", {"cache_dir": device["cache_dir"],
                       **log.since((0.0, 0, 0, 0))})
    if failed:
        print(f"FAILED phases: {', '.join(failed)}", file=sys.stderr,
              flush=True)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": device["platform"], "kind": device["kind"],
        "count": device["count"]}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
