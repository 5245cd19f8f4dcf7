"""Cross-domain training (DiscoGAN): the program's jitted step over two
generators and two discriminators, dispatched back to back.

The window is ``train_steps.py``'s, with a sample counted as one pair of
images (one from domain A, one from B).  Set-up builds the step
(``make_gan_train_step``, which the image-input model turns into the
cross-domain step) and the state ``((g_ab, g_ba), (d_a, d_b))`` once,
drives them from the seed through the first ``checked_steps`` steps on
batches whose rows all differ, and hands the same step and state to the
window.  After it, ``bench/reference_cycle.py`` follows the checked
steps; the state is flattened into ``g/ab.<leaf>``, ``g/ba.<leaf>``,
``d/a.<leaf>`` and ``d/b.<leaf>`` so that ``checks.train_numbers``
judges every leaf as it judges a single G and D.

For its readers, a traced run's device time is split by layer and pass
(``bench/layers.py``, from the step's own HLO, which ``step_hlo``
compiles again) and by the ``gan.reconstruct`` scope; ``split`` does it
once per run and prints the per-layer breakdown on standard error.
"""

from __future__ import annotations

import collections
import json
import time
import traceback

from bench import checks, inputs, layers, reference_cycle, work, work_cycle
from bench.cells import Run
from bench.drivers.common import (HostWatch, Profile, annotate,
                                  check_layers, say)
from bench.drivers.train_steps import checked_steps, memory_peak

__all__ = ["DOMAINS", "program_config", "build", "make_params",
           "make_batches", "flat", "reference_numbers", "step_hlo",
           "split", "run"]

# the state's roles in order: ((g_ab, g_ba), (d_a, d_b))
DOMAINS = (("generator", ("ab", "ba")), ("discriminator", ("a", "b")))
RECONSTRUCT = "gan.reconstruct"


def program_config(cfg: dict, **overrides):
    """The program's ``GanConfig``; refuses a program whose generator
    does not take an image (it would be fed latents)."""
    from repro.models.gan import GanConfig
    fields = {"name": cfg["model"], "dtype": cfg["dtype"],
              "channel_scale": cfg.get("channel_scale", 1.0)}
    fields.update(overrides)
    gcfg = GanConfig(**fields)
    if getattr(gcfg, "input_kind", "latent") != "image":
        raise ValueError(f"{cfg['name']}: the program has no image-input "
                         f"generator, so no cross-domain step")
    return gcfg


def build(cfg: dict, seed: int, feed_batches: int, **overrides):
    """``(step, p0, feed)``: the program's step, the weights and the
    batches of a seed."""
    from repro.train.loop import make_gan_train_step
    gcfg = program_config(cfg, **overrides)
    check_layers(gcfg, cfg)
    opt = cfg["optimizer"]
    step, _ = make_gan_train_step(gcfg, cfg["batch"], g_lr=opt["g_lr"],
                                  d_lr=opt["d_lr"])
    return (step, make_params(cfg, seed),
            make_batches(cfg, seed, feed_batches, cfg["batch"]))


def make_params(cfg: dict, seed: int):
    """``((g_ab, g_ba), (d_a, d_b))`` on the device, from one jitted
    call; each set drawn as ``inputs.make_params`` draws one."""
    import jax
    import jax.numpy as jnp

    shapes = {role: inputs.param_shapes(cfg, role)
              for role, _ in DOMAINS}

    @jax.jit
    def init(key):
        out = []
        for role, names in DOMAINS:
            sets = []
            for _ in names:
                key, sub = jax.random.split(key)
                keys = jax.random.split(sub, len(shapes[role]))
                sets.append({
                    name: (scale * jax.random.normal(k, shape, jnp.float32)
                           if scale else jnp.zeros(shape, jnp.float32))
                    for k, (name, (shape, scale))
                    in zip(keys, sorted(shapes[role].items()))})
            out.append(tuple(sets))
        return tuple(out)

    return init(jax.random.PRNGKey(inputs.streams(seed)["params"]))


def make_batches(cfg: dict, seed: int, count: int, batch: int) -> list:
    """``count`` batches ``{"a", "b"}`` on the device, every row of
    either domain different: images uniform in [-1, 1]."""
    import jax
    import jax.numpy as jnp

    shape = (batch,) + tuple(cfg["image"])

    @jax.jit
    def make(key):
        out = []
        for key in jax.random.split(key, count):
            ka, kb = jax.random.split(key)
            out.append({
                "a": jax.random.uniform(ka, shape, jnp.float32, -1.0, 1.0),
                "b": jax.random.uniform(kb, shape, jnp.float32, -1.0, 1.0)})
        return out

    return make(jax.random.PRNGKey(inputs.streams(seed)["batches"]))


def flat(state) -> tuple[dict, dict]:
    """``((g_ab, g_ba), (d_a, d_b))`` as one ``(g, d)`` pair of dicts
    keyed ``"<domain>.<leaf>"``."""
    return tuple({f"{domain}.{name}": leaf
                  for domain, params in zip(names, sets)
                  for name, leaf in params.items()}
                 for (_, names), sets in zip(DOMAINS, state))


def reference_numbers(cfg: dict, p0, p1, p3, losses, feed) -> dict:
    """The reference follows the checked steps; the numbers compared."""
    states, first, ref_losses = reference_cycle.sgd_steps(
        cfg, p0, feed[:len(losses)])
    return checks.train_numbers(cfg, flat(p0), flat(p1), flat(p3), losses,
                                flat(first), flat(states[-1]), ref_losses)


def step_hlo(cfg: dict) -> str:
    """The optimized HLO text of this driver's step for ``cfg``, compiled
    with the metadata in the compile-cache key (see
    ``bench/layers.py``'s ``step_hlo``, which builds the latent step)."""
    import jax
    step, p0, feed = build(cfg, 0, 1)
    lowered = step.lower(p0, feed[0])
    flag = "jax_compilation_cache_include_metadata_in_key"
    before = getattr(jax.config, flag)
    jax.config.update(flag, True)
    try:
        return lowered.compile().as_text()
    finally:
        jax.config.update(flag, before)


def split(run) -> dict | None:
    """``{"layers": {"<layer> <fwd|bwd>": s}, "reconstruct": s}``, device
    seconds of the traced steps, computed once per run and kept on it;
    None when the run was not traced or its step names no layer."""
    if run.summary is None or not run.traced:
        return None
    if not hasattr(run, "cycle_split"):
        run.cycle_split = _split(run)
    return run.cycle_split


def _split(run) -> dict | None:
    began = time.perf_counter()
    try:
        paths = layers.op_paths(step_hlo(run.cfg))
    except Exception:  # noqa: BLE001 — a traced run reports without it
        say(f"layers: the step's HLO could not be read\n"
            f"{traceback.format_exc()}")
        return None
    say(f"layers: the step's HLO read in {time.perf_counter() - began:.3f} s")
    summary = run.summary
    layer_seconds = layers.split(summary, paths)
    if sum(t for k, t in layer_seconds.items() if k != layers.UNSCOPED) <= 0:
        say("layers: the step names no layer")
        return None
    reconstruct = sum(
        t for op, t in summary.op_seconds.items()
        if RECONSTRUCT in paths.get(op.rsplit(" (", 1)[0], "")
    ) / summary.devices
    say(f"layers: {json.dumps(breakdown(run, layer_seconds))}")
    say(f"layers: under {RECONSTRUCT} {reconstruct:.6f} s of "
        f"{summary.busy_s:.6f} s busy")
    return {"layers": layer_seconds, "reconstruct": reconstruct}


def least_seconds(run, names, direction: str | None = None) -> float:
    """The least time of one step's passes of the layers ``names`` (both
    directions, or ``"fwd"`` or ``"bwd"``)."""
    kinds = {"fwd": ("fwd",), "bwd": ("wgrad", "igrad"),
             None: ("fwd", "wgrad", "igrad")}[direction]
    passes = [p for p in work_cycle.step_passes(run.cfg)
              if p[0]["name"] in names and p[1] in kinds]
    return work.least_seconds(run.cfg, passes, run.cfg["batch"],
                              run.peak_flops, run.peaks["hbm_bytes_per_s"])


def breakdown(run, layer_seconds: dict[str, float]) -> list[list]:
    """``[<layer> <pass>, seconds a traced step, roofline %]``, longest
    first; the roofline only for conv layers, else None."""
    steps = run.traced["samples"] / run.cfg["batch"]
    convs = {lay["name"] for lay in run.cfg["generator"]
             + run.cfg["discriminator"]}
    rows = []
    for k, t in sorted(layer_seconds.items(), key=lambda kv: -kv[1]):
        name, _, direction = k.partition(" ")
        roof = (100.0 * least_seconds(run, (name,), direction) * steps / t
                if name in convs and t > 0 else None)
        rows.append([k, t / steps, roof])
    return rows


def run(ctx) -> tuple[Run, dict]:
    import jax

    cfg, traffic = ctx.cell.cfg, ctx.cell.traffic
    batch, count = cfg["batch"], traffic["checked_steps"]
    step, p0, feed = build(cfg, ctx.seed, traffic["feed_batches"])
    state, p1, losses = checked_steps(step, p0, feed, count)
    p3 = state
    say(f"set-up: checked steps' losses {losses}")
    profile = Profile(ctx.trace)
    profile.start()
    compiled = ctx.log.state()

    inflight = collections.deque()
    steps, traced = 0, None
    profile.open_window()
    start = time.perf_counter()
    setup_s = start - ctx.t0
    with HostWatch() as host:
        while True:
            began = time.perf_counter()
            with annotate("bench.step"):
                state, metrics = step(state,
                                      feed[(count + steps) % len(feed)])
            dispatched = time.perf_counter()
            steps += 1
            inflight.append(metrics["loss"])
            if len(inflight) > traffic["in_flight"]:
                with annotate("bench.wait"):
                    inflight.popleft().block_until_ready()
            now = time.perf_counter()
            host.mark(began, dispatched, now)
            if profile.started and now - start >= traffic["trace_seconds"]:
                jax.block_until_ready(state)
                traced = {"samples": steps * batch}
                profile.stop()
            if now - start >= ctx.seconds:
                break
        jax.block_until_ready(state)
    window_s = time.perf_counter() - start
    say(f"window: {steps} steps in {window_s:.6f} s; compiles in the "
        f"window: {ctx.log.since(compiled)}")
    say(host.report())
    summary = profile.reduce()

    peak = memory_peak()
    del state, inflight, metrics
    numbers = reference_numbers(cfg, p0, p1, p3, losses, feed)
    run = Run(cell=ctx.cell, peaks=None, setup_s=setup_s,
              window_s=window_s, samples=steps * batch, attempted=steps,
              failed=0, traced=traced, summary=summary,
              counters={"memory_peak_bytes": peak})
    return run, numbers
