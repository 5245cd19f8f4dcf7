"""Device time per layer and pass of a traced training run, from the names
the program gives its own work.

The program runs each layer under ``jax.named_scope("layer.<name>")``
(``layer.proj`` and ``layer.head`` around the projection and the final
mean) and each part of the G+D step under ``gan.d_update``,
``gan.g_update`` and ``gan.sgd``.  The names reach the ``op_name`` of
every op the step compiles to.  An op belongs to the last ``layer.<name>``
of its path, and to the backward pass when the path holds
``transpose(``; an op under no layer scope is ``(unscoped)``.

The trace reduction (``bench/trace.py``) keys device seconds by each op's
short HLO name, and removes the trace once it is reduced.  So the op paths
are read from the compiled step itself: the same call the driver made,
compiled again, whose HLO text carries each instruction's ``op_name``.
XLA names the instructions of one module alike on every compile, so the
short names meet those of the trace.  An op that XLA made without
metadata (a layout copy, for one) is unscoped.
"""

from __future__ import annotations

import collections
import json
import re
import time
import traceback

from bench import work
from bench.drivers.common import say
from bench.trace import CONV_CATEGORIES

__all__ = ["UNSCOPED", "key", "op_paths", "split", "step_hlo", "of",
           "image_layers", "seconds_of", "least_seconds", "breakdown"]

UNSCOPED = "(unscoped)"
_LAYER = re.compile(r"layer\.(\w+)")
_INSTRUCTION = re.compile(
    r'^\s*(?:ROOT\s+)?%?([^\s=]+) = .*?metadata=\{op_name="((?:[^"\\]|\\.)*)"',
    re.M)
_PASSES = {"fwd": ("fwd",), "bwd": ("wgrad", "igrad")}


def key(op_path: str) -> str:
    """``"<layer> fwd"``, ``"<layer> bwd"`` or ``(unscoped)``."""
    names = _LAYER.findall(op_path)
    if not names:
        return UNSCOPED
    return f"{names[-1]} {'bwd' if 'transpose(' in op_path else 'fwd'}"


def op_paths(hlo_text: str) -> dict[str, str]:
    """Each instruction's short name -> its ``op_name``, from an HLO
    module's text."""
    return dict(_INSTRUCTION.findall(hlo_text))


def _short(op: str) -> str:
    # a Summary's op key is "<short name> (<category>)"
    return op.rsplit(" (", 1)[0]


def split(summary, paths: dict[str, str]) -> dict[str, float]:
    """The device seconds of ``summary.op_seconds`` by layer and pass,
    averaged over the devices like ``Summary.class_seconds``; the values
    sum to the ops' seconds."""
    out: dict[str, float] = collections.Counter()
    for op, seconds in summary.op_seconds.items():
        out[key(paths.get(_short(op), ""))] += seconds / summary.devices
    return dict(out)


def step_hlo(cfg: dict) -> str:
    """The optimized HLO text of the training driver's step for ``cfg``
    (shapes alone decide it, so any seed does).

    JAX's persistent cache keys an executable by its HLO without the
    metadata, so the step the window ran may come from a cache entry that
    code with other names (or none) compiled: the names it carries are
    then that code's.  This compile puts the metadata in the key, so it
    names the ops as this checkout does; its instruction names are those
    of the step the window ran, which differs only in metadata."""
    import jax
    from bench.drivers import train_steps
    step, p0, feed = train_steps.build(cfg, 0, 1)
    lowered = step.lower(p0, feed[0])
    flag = "jax_compilation_cache_include_metadata_in_key"
    before = getattr(jax.config, flag)
    jax.config.update(flag, True)
    try:
        return lowered.compile().as_text()
    finally:
        jax.config.update(flag, before)


def image_layers(cfg: dict) -> tuple[str, str]:
    """G's output layer and D's input layer: the layers that hold the
    image, with its 1 or 3 channels."""
    return cfg["generator"][-1]["name"], cfg["discriminator"][0]["name"]


def seconds_of(layer_seconds: dict[str, float], names) -> float:
    """Device seconds of the layers ``names``, both passes."""
    return sum(t for k, t in layer_seconds.items()
               if k.split(" ")[0] in names)


def least_seconds(run, names, direction: str | None = None) -> float:
    """The least time of one step's passes of the layers ``names`` (both
    directions, or ``"fwd"`` or ``"bwd"``), as ``conv_roofline.train``
    counts it (``bench/work.py``)."""
    kinds = _PASSES[direction] if direction else ("fwd", "wgrad", "igrad")
    passes = [p for p in work.step_passes(run.cfg)
              if p[0]["name"] in names and p[1] in kinds]
    return work.least_seconds(run.cfg, passes, run.cfg["batch"],
                              run.peak_flops, run.peaks["hbm_bytes_per_s"])


def breakdown(run, layer_seconds: dict[str, float]) -> list[list]:
    """``[<layer> <pass>, seconds a traced step, roofline %]``, longest
    first; the roofline only for conv layers, else None."""
    steps = run.traced["samples"] / run.cfg["batch"]
    convs = {lay["name"] for lay in run.cfg["generator"]
             + run.cfg["discriminator"] if lay["kind"] in ("conv", "tconv")}
    rows = []
    for k, t in sorted(layer_seconds.items(), key=lambda kv: -kv[1]):
        name, _, direction = k.partition(" ")
        roof = (100.0 * least_seconds(run, (name,), direction) * steps / t
                if name in convs and t > 0 else None)
        rows.append([k, t / steps, roof])
    return rows


def of(run) -> dict[str, float] | None:
    """The layer split of a traced run's device seconds, computed once per
    run and kept on it; None when the run was not traced, or its step
    names no layer.  Standard error gets the breakdown and what share of
    the time the layer scopes cover."""
    if run.summary is None or not run.traced:
        return None
    if not hasattr(run, "layer_seconds"):
        run.layer_seconds = _compute(run)
    return run.layer_seconds


def _compute(run) -> dict[str, float] | None:
    began = time.perf_counter()
    try:
        paths = op_paths(step_hlo(run.cfg))
    except Exception:  # noqa: BLE001 — a traced run reports without it
        say(f"layers: the step's HLO could not be read\n"
            f"{traceback.format_exc()}")
        return None
    say(f"layers: the step's HLO read in {time.perf_counter() - began:.3f} s")
    summary = run.summary
    layer_seconds = split(summary, paths)
    total = sum(layer_seconds.values())
    scoped = total - layer_seconds.get(UNSCOPED, 0.0)
    if scoped <= 0:
        say("layers: the step names no layer")
        return None
    conv = [(op, t) for op, t in summary.op_seconds.items()
            if any(c in op.rsplit(" (", 1)[-1] for c in CONV_CATEGORIES)]
    conv_scoped = sum(t for op, t in conv
                      if key(paths.get(_short(op), "")) != UNSCOPED)
    conv_share = 100 * conv_scoped / max(sum(t for _, t in conv), 1e-30)
    named = sum(1 for op in summary.op_seconds if _short(op) in paths)
    say(f"layers: {named} of {len(summary.op_seconds)} traced ops named "
        f"by the step's HLO; under a layer scope {100 * scoped / total:.3f}%"
        f" of op time, {100 * scoped / summary.busy_s:.3f}% of busy, "
        f"{conv_share:.3f}% of conv time")
    say(f"layers: {json.dumps(breakdown(run, layer_seconds))}")
    return layer_seconds
