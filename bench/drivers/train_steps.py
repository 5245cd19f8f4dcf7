"""Training: the program's jitted G+D step, dispatched back to back.

Set-up builds the step (``make_gan_train_step``) and the state once,
drives them from the seed through the first ``checked_steps`` steps on
batches whose rows all differ, keeps the parameters before and after
them, and hands the same step and state to the window.  The window
dispatches steps with at most ``in_flight`` unfinished, cycling over the
feed's batches, and ends on ``block_until_ready`` of the last step once
``--seconds`` have passed; standard error then names its longest steps
and what the host did in them (``HostWatch``).  After it, the reference
follows the checked steps from the same parameters and batches.
"""

from __future__ import annotations

import collections
import time

from bench import checks, inputs, reference
from bench.cells import Run
from bench.drivers.common import (HostWatch, Profile, annotate,
                                  check_layers, program_config, say)

__all__ = ["build", "checked_steps", "reference_numbers", "run"]


def build(cfg: dict, seed: int, feed_batches: int, **overrides):
    """``(step, p0, feed)``: the program's step, the weights and the
    batches of a seed."""
    from repro.train.loop import make_gan_train_step
    gcfg = program_config(cfg, **overrides)
    check_layers(gcfg, cfg)
    opt = cfg["optimizer"]
    step, _ = make_gan_train_step(gcfg, cfg["batch"], g_lr=opt["g_lr"],
                                  d_lr=opt["d_lr"])
    return (step, inputs.make_params(cfg, seed),
            inputs.make_batches(cfg, seed, feed_batches, cfg["batch"]))


def checked_steps(step, p0, feed, count: int):
    """Run ``count`` steps from ``p0`` through the window's own call.
    Returns ``(state, p1, losses)``: the state after the last step, the
    parameters after the first, and each step's losses."""
    import jax
    state, p1, metrics = p0, None, []
    for i in range(count):
        state, m = step(state, feed[i])
        metrics.append(m)
        if i == 0:
            p1 = state
    losses = [{k: float(v) for k, v in m.items() if k != "loss"}
              for m in jax.device_get(metrics)]
    return jax.block_until_ready(state), p1, losses


def reference_numbers(cfg: dict, p0, p1, p3, losses, feed) -> dict:
    """The reference follows the checked steps; the numbers compared."""
    states, first, ref_losses = reference.sgd_steps(cfg, p0,
                                                    feed[:len(losses)])
    return checks.train_numbers(cfg, p0, p1, p3, losses, first, states[-1],
                                ref_losses)


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


def memory_peak() -> int | None:
    """Peak bytes in use on the fullest chip, as JAX reports it."""
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.local_devices()]
    peaks = [p for p in peaks if p is not None]
    value = max(peaks) if peaks else None
    say(f"memory: peak_bytes_in_use {value}")
    return value
