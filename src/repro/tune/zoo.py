"""Tuning entry points for the Table-I GAN model zoo.

The per-model layer walk lives in :class:`repro.program.ProgramSpec` —
the zoo derives every plan key from a built spec
(``spec.plan_keys()``), so the tuner keys exactly the fused ops the
programs execute, with no duplicated layer-group/epilogue threading
here.  ``layer_plan_keys`` turns a raw layer topology into plan keys
(the spec-free form); ``warm_gan_plans`` resolves (measuring on miss) a
plan for every layer of a config; ``tune_model_zoo`` drives the whole
zoo and produces the ``BENCH_tune.json`` payload (tuned vs heuristic
wall-clock per model).
"""

from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp

from repro.core.dataflow import DataflowPolicy
from repro.tune.measure import time_interleaved
from repro.tune.planner import Plan, PlanKey, Planner

__all__ = ["layer_plan_keys", "warm_gan_plans", "tune_model_zoo"]


def layer_plan_keys(layers, batch: int, dtype: str = "float32",
                    platform: str | None = None, epilogues=None
                    ) -> list[tuple[str, PlanKey]]:
    """(layer name, PlanKey) per ConvLayer-like entry in ``layers``.

    ``epilogues`` (an optional per-layer :class:`Epilogue` sequence)
    folds the fused bias/activation specs into the keys — warmed plans
    are only found at dispatch when they key the op the model actually
    runs, which since the fused-epilogue refactor includes the
    epilogue."""
    platform = platform or jax.default_backend()
    if epilogues is None:
        epilogues = [None] * len(layers)
    out = []
    for l, ep in zip(layers, epilogues):
        out.append((l.name, PlanKey(
            kind="tconv" if l.transposed else "conv",
            batch=int(batch),
            in_spatial=tuple(l.in_spatial),
            kernel=tuple(l.kernel),
            strides=tuple(l.strides),
            paddings=tuple(l.paddings),
            cin=int(l.cin), cout=int(l.cout),
            dtype=dtype, platform=platform,
            **({} if ep is None else ep.key_fields()))))
    return out


def _zoo_keys(cfg, batch: int, *, generator_only: bool = False,
              dtype: str = "float32") -> list[tuple[str, PlanKey]]:
    """("g/<name>" | "d/<name>", PlanKey) per layer of a ``GanConfig``
    — derived from :class:`~repro.program.ProgramSpec`, the single
    owner of the layer/epilogue walk, so tuner keys and program
    dispatches agree by construction."""
    from repro.program import ProgramSpec
    roles = [("g", "generator")]
    if not generator_only:
        roles.append(("d", "discriminator"))
    out = []
    for prefix, role in roles:
        # the heuristic policy keeps spec construction planner-free —
        # only the geometry/epilogue records matter for the keys
        spec = ProgramSpec.build(cfg, batch, role,
                                 policy=DataflowPolicy(), dtype=dtype)
        out.extend((f"{prefix}/{name}", key)
                   for name, key in spec.plan_keys())
    return out


def warm_gan_plans(cfg, batch: int, planner: Planner | None = None, *,
                   generator_only: bool = False, measure: bool = True,
                   dtype: str = "float32") -> dict[str, Plan]:
    """Resolve a plan for every layer of ``cfg`` (a ``GanConfig``),
    keyed on the fused per-layer epilogues the model dispatches.

    Returns ``{"g/<name>" | "d/<name>": Plan}``.  With a warm plan cache
    (or persisted plan file) this performs zero measurements."""
    if planner is None:
        from repro.tune import get_planner
        planner = get_planner()
    return {name: planner.plan(key, measure=measure)
            for name, key in _zoo_keys(cfg, batch,
                                       generator_only=generator_only,
                                       dtype=dtype)}


def _time_generator_pair(cfg, params, z, policies, *, warmup: int,
                         repeats: int) -> list[float]:
    """Median seconds per call for several policies on the same compiled
    generator, timed with the shared interleaved harness so the
    tuned-vs-heuristic ratio is meaningful on a noisy host."""
    from repro.models.gan import generator_apply

    thunks = []
    for policy in policies:
        @jax.jit
        def run(params, z, policy=policy):
            return generator_apply(params, z, cfg, policy=policy)
        thunks.append(lambda run=run: run(params, z))
    return time_interleaved(thunks, warmup=max(1, warmup),
                            repeats=repeats)


def tune_model_zoo(models: Sequence[str], planner: Planner, *,
                   batch: int = 2, channel_scale: float = 0.25,
                   warmup: int = 1, repeats: int = 3,
                   end_to_end: bool = True, log=print) -> dict:
    """Tune every layer of every model in ``models``; return the
    ``BENCH_tune.json`` payload.

    Per model: every layer geometry is tuned through the planner (shared
    geometries across models hit the plan cache), then — when
    ``end_to_end`` — the full generator forward is timed once with the
    heuristic policy and once with ``backend="auto"`` consulting the
    freshly tuned plans."""
    from repro.models.gan import GanConfig, init_gan

    out: dict[str, dict] = {}
    for name in models:
        cfg = GanConfig(name=name, channel_scale=channel_scale)
        meas0 = planner.measurements
        plans = warm_gan_plans(cfg, batch, planner)
        keys = dict(_zoo_keys(cfg, batch))
        layer_rows = {}
        tuned_us = heur_us = 0.0
        complete = True
        for lname, plan in plans.items():
            heur = planner.heuristic_plan(keys[lname])
            row = {"backend": plan.backend,
                   "blocks": list(plan.blocks) if plan.blocks else None,
                   "source": plan.source,
                   "tuned_us": plan.measured_us,
                   "heuristic_backend": heur.backend}
            layer_rows[lname] = row
            if plan.measured_us is None:
                complete = False
            else:
                tuned_us += plan.measured_us
        row = {"layers": layer_rows,
               "measurements": planner.measurements - meas0,
               "layer_tuned_us_sum": tuned_us if complete else None}
        if end_to_end:
            g_params, _ = init_gan(cfg, jax.random.PRNGKey(0))
            z = jnp.zeros((batch, *cfg.input_shape), jnp.float32)
            # "auto" dispatch consults the *process-wide* planner; point
            # it at the one we just tuned for the timed run
            from repro.tune import get_planner, set_planner
            prev = get_planner(create=False)
            set_planner(planner)
            try:
                heur_s, tuned_s = _time_generator_pair(
                    cfg, g_params, z,
                    [DataflowPolicy(), DataflowPolicy(backend="auto")],
                    warmup=warmup, repeats=max(repeats, 5))
            finally:
                set_planner(prev)
            heur_us, tuned_e2e_us = heur_s * 1e6, tuned_s * 1e6
            row["generator_heuristic_us"] = heur_us
            row["generator_tuned_us"] = tuned_e2e_us
            row["generator_speedup"] = heur_us / tuned_e2e_us \
                if tuned_e2e_us else None
            log(f"  {name:9s} generator: heuristic={heur_us:9.0f}us  "
                f"tuned={tuned_e2e_us:9.0f}us  "
                f"speedup={row['generator_speedup']:.2f}x  "
                f"({row['measurements']} measurements)")
        else:
            log(f"  {name:9s} tuned {len(layer_rows)} layers "
                f"({row['measurements']} measurements)")
        out[name] = row
    return out
