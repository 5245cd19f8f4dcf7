"""Fault-tolerant training loop.

Production posture (1000+ nodes):

* **Checkpoint/restart** — periodic async checkpoints; on any step failure
  the loop restores the latest checkpoint and *replays* from there (the
  data pipeline is a pure function of step, so replay is exact).
* **Preemption** — SIGTERM triggers a synchronous checkpoint then a clean
  exit (the standard TPU-pod eviction contract).
* **Straggler watchdog** — per-step wall time is tracked with an EWMA; a
  step slower than ``straggler_factor ×`` the EWMA fires a callback (on a
  real cluster: report the slow host for replacement / trigger
  data-rebalancing; here: logged + counted, and used by tests).
* **Failure injection** — ``failure_injector(step) -> bool`` lets tests
  and the elastic example kill arbitrary steps deterministically.
"""

from __future__ import annotations

import dataclasses
import signal
import time
from typing import Any, Callable

import jax

from repro import obs as _obs
from repro.program.spec import _UNSET as _MESH_UNSET
from repro.train import checkpoint as ckpt

__all__ = ["LoopConfig", "TrainLoop", "InjectedFailure",
           "make_gan_train_step"]


def make_gan_train_step(cfg, batch: int, *, g_lr: float = 2e-4,
                        d_lr: float | None = None, policy=None,
                        planner=None, measure: bool = False,
                        mesh=_MESH_UNSET):
    """Program-backed adversarial SGD step for a ``GanConfig``.

    Builds the generator and discriminator
    :class:`repro.program.Program` **once** — the whole
    config → policy → epilogue → plan walk happens here, ahead of the
    first trace — and returns ``(train_step, (g_program, d_program))``
    where ``train_step(state, batch)`` is a jitted
    ``((g_params, d_params), {"z", "real"}) → (state, metrics)`` that
    replays the frozen programs every step.  ``measure=True`` tunes
    plan misses at build for an ``auto`` policy (never during the
    loop).

    The model chooses the step.  A latent generator gets the
    adversarial step above; an image-input generator
    (``cfg.input_kind == "image"``, DiscoGAN) gets the cross-domain
    step of :func:`_cross_domain_step`, whose state is
    ``((g_ab, g_ba), (d_a, d_b))`` and whose batch is ``{"a", "b"}``,
    two image batches of unpaired domains.  Both return the metrics
    ``{"g_loss", "d_loss", "loss"}``.

    ``mesh`` (default: ``cfg.mesh``) builds **sharded** programs: the
    programs' forwards run under ``shard_map``, so the batch splits
    over the ``data`` axis and the weight cotangents are ``psum``-med
    across it by the shard_map transpose — data-parallel gradient
    reduction with no explicit ``pmean`` in the loss.  The returned
    step then ``device_put``s each incoming batch array with
    :func:`repro.sharding.rules.batch_sharding` (batch dim over
    ``data``), and exposes ``train_step.state_shardings`` — a
    ``(g, d)`` pair of replicated :func:`~repro.sharding.rules
    .param_shardings` trees (``((g, g), (d, d))`` for the cross-domain
    step) — for placing the initial state and for
    :class:`TrainLoop`'s checkpoint-restore ``state_shardings``.
    Degrades with the programs: too few local devices → a plain
    single-device step.

    **Mixed precision** (``cfg.dtype="bfloat16"``/``"float16"``): the
    programs cast activations and weights to the storage dtype *at
    use* and accumulate in f32 (see ``repro.quant``), so parameters,
    optimizer state, and gradients stay f32 end to end — the
    ``state_shardings`` f32 shape-structs and checkpoints need no
    change, and the step stays numerically stable at low storage
    precision.

    The step names its parts for the device trace: D's and G's
    ``value_and_grad`` run under ``jax.named_scope("gan.d_update")`` and
    ``"gan.g_update"``, the SGD updates under ``"gan.sgd"``, and each
    layer inside them under the programs' ``layer.<name>`` scopes."""
    from repro.program import Program

    d_lr = g_lr if d_lr is None else d_lr
    g_prog = Program.build(cfg, batch, "generator", policy=policy,
                           planner=planner, measure=measure, mesh=mesh)
    d_prog = Program.build(cfg, batch, "discriminator", policy=policy,
                           planner=planner, measure=measure, mesh=mesh)
    cross_domain = g_prog.spec.input_kind == "image"
    make = _cross_domain_step if cross_domain else _adversarial_step
    train_step = make(g_prog, d_prog, g_lr, d_lr)

    if g_prog.mesh is not None:
        from repro.models.gan import (discriminator_specs,
                                      generator_specs)
        from repro.sharding.rules import (Rules, batch_sharding,
                                          param_shardings)
        mesh_obj = g_prog.mesh

        # GAN data-parallel state is fully replicated (the programs'
        # own shard_map in_specs do the Cout splitting where frozen) —
        # a Rules table mapping every param axis to no mesh axis.
        dp_rules = Rules(table={"conv_in": None, "conv_out": None,
                                "mlp": None})

        def _shardings(specs):
            return param_shardings(
                mesh_obj, {k: s.axes for k, s in specs.items()},
                {k: jax.ShapeDtypeStruct(s.shape, "float32")
                 for k, s in specs.items()}, dp_rules)

        inner_step = train_step

        def train_step(state, batch):
            batch = {k: jax.device_put(
                         v, batch_sharding(mesh_obj,
                                           getattr(v, "ndim", 0)))
                     for k, v in batch.items()}
            return inner_step(state, batch)

        g_sh = _shardings(generator_specs(cfg))
        d_sh = _shardings(discriminator_specs(cfg))
        train_step.mesh = mesh_obj
        train_step.state_shardings = ((g_sh, g_sh), (d_sh, d_sh)) \
            if cross_domain else (g_sh, d_sh)
    else:
        train_step.mesh = None
        train_step.state_shardings = None
    return train_step, (g_prog, d_prog)


def _sgd(params, grads, lr):
    with jax.named_scope("gan.sgd"):
        return jax.tree.map(lambda p, g: p - lr * g, params, grads)


def _adversarial_step(g_prog, d_prog, g_lr, d_lr):
    """The latent GAN's step: D on real and G(z), then G through the
    updated D, both with the non-saturating BCE."""
    from repro.models.gan import bce_with_logits

    def losses(g_params, d_params, z, real):
        fake = g_prog.forward(g_params, z)
        d_fake = d_prog.forward(d_params, fake)
        d_real = d_prog.forward(d_params, real)
        d_loss = bce_with_logits(d_real, 1.0) + \
            bce_with_logits(d_fake, 0.0)
        g_loss = bce_with_logits(d_fake, 1.0)
        return g_loss, d_loss

    @jax.jit
    def train_step(state, batch):
        g_params, d_params = state
        z, real = batch["z"], batch["real"]
        with jax.named_scope("gan.d_update"):
            dl, d_grads = jax.value_and_grad(
                lambda d: losses(g_params, d, z, real)[1])(d_params)
        d_new = _sgd(d_params, d_grads, d_lr)
        with jax.named_scope("gan.g_update"):
            gl, g_grads = jax.value_and_grad(
                lambda g: losses(g, d_new, z, real)[0])(g_params)
        g_new = _sgd(g_params, g_grads, g_lr)
        return (g_new, d_new), {"g_loss": gl, "d_loss": dl,
                                "loss": gl + dl}

    return train_step


def _cross_domain_step(g_prog, d_prog, g_lr, d_lr):
    """DiscoGAN's step (Kim et al. 2017, Eq. 1-6) over unpaired domains
    A and B.  One generator and one discriminator program serve both
    directions and both domains, each with its own parameters: the
    state is ``((g_ab, g_ba), (d_a, d_b))`` and a batch ``{"a", "b"}``.

    D's update: ``L_D = L_D_A + L_D_B``, BCE on real and on the
    translated fake in each domain.  Then G's update through the updated
    D: ``L_G = L_GAN_B + L_CONST_A + L_GAN_A + L_CONST_B``, with L_GAN
    the non-saturating BCE of ``D_B(x_AB)`` (``D_A(x_BA)``) against
    real and L_CONST the MSE between ``x_ABA = G_BA(x_AB)`` and ``x_A``
    (``x_BAB`` and ``x_B``).  The translations run under
    ``gan.translate``, the reconstructions and their MSE under
    ``gan.reconstruct``."""
    import jax.numpy as jnp

    from repro.models.gan import bce_with_logits as bce
    gen, disc = g_prog.forward, d_prog.forward

    def translate(g_ab, g_ba, a, b):
        with jax.named_scope("gan.translate"):
            return gen(g_ab, a), gen(g_ba, b)

    def d_loss(ds, a, b, x_ab, x_ba):
        d_a, d_b = ds
        return (bce(disc(d_a, a), 1.0) + bce(disc(d_a, x_ba), 0.0)
                + bce(disc(d_b, b), 1.0) + bce(disc(d_b, x_ab), 0.0))

    def g_loss(gs, ds, a, b):
        (g_ab, g_ba), (d_a, d_b) = gs, ds
        x_ab, x_ba = translate(g_ab, g_ba, a, b)
        with jax.named_scope("gan.reconstruct"):
            const = (jnp.mean(jnp.square(gen(g_ba, x_ab) - a))
                     + jnp.mean(jnp.square(gen(g_ab, x_ba) - b)))
        return bce(disc(d_b, x_ab), 1.0) + bce(disc(d_a, x_ba), 1.0) \
            + const

    @jax.jit
    def train_step(state, batch):
        gs, ds = state
        a, b = batch["a"], batch["b"]
        with jax.named_scope("gan.d_update"):
            fake = translate(*gs, a, b)
            dl, d_grads = jax.value_and_grad(d_loss)(ds, a, b, *fake)
        d_new = _sgd(ds, d_grads, d_lr)
        with jax.named_scope("gan.g_update"):
            gl, g_grads = jax.value_and_grad(g_loss)(gs, d_new, a, b)
        g_new = _sgd(gs, g_grads, g_lr)
        return (g_new, d_new), {"g_loss": gl, "d_loss": dl,
                                "loss": gl + dl}

    return train_step


class InjectedFailure(RuntimeError):
    pass


def _collect_stats() -> dict:
    """External-subsystem stats through the obs registry's collector
    hooks — every dict is a fresh copy (``obs.collect``), so a snapshot
    held across the run never aliases live counter state.  The imports
    force collector registration (each module registers its own on
    import); missing subsystems simply don't report."""
    import repro.core.dataflow  # noqa: F401 — registers dataflow.uop_cache
    import repro.tune           # noqa: F401 — registers tune.planner
    return _obs.collect()


@dataclasses.dataclass
class LoopConfig:
    total_steps: int = 100
    ckpt_dir: str = "/tmp/repro_ckpt"
    ckpt_every: int = 50
    async_ckpt: bool = True
    max_restarts: int = 10
    straggler_factor: float = 3.0
    ewma_alpha: float = 0.2
    log_every: int = 10


class TrainLoop:
    def __init__(self, cfg: LoopConfig, train_step: Callable,
                 batch_fn: Callable[[int], dict], state: Any,
                 state_shardings: Any = None,
                 failure_injector: Callable[[int], bool] | None = None,
                 log_fn: Callable[[str], None] = print):
        self.cfg = cfg
        self.train_step = train_step
        self.batch_fn = batch_fn
        self.state = state
        self.state_shardings = state_shardings
        self.failure_injector = failure_injector
        self.log = log_fn
        self.restarts = 0
        self._last_saved_step: int | None = None
        self.straggler_events: list[int] = []
        self._ewma: float | None = None
        self._preempted = False
        self.metrics_history: list[dict] = []

    # -- signals ------------------------------------------------------------
    def _install_sigterm(self):
        def handler(signum, frame):
            self._preempted = True
        try:
            signal.signal(signal.SIGTERM, handler)
        except ValueError:
            pass  # not on the main thread (tests)

    # -- checkpointing -------------------------------------------------------
    def _save(self, step: int, sync: bool = False):
        if sync or not self.cfg.async_ckpt:
            ckpt.save(self.state, self.cfg.ckpt_dir, step)
        else:
            ckpt.save_async(self.state, self.cfg.ckpt_dir, step)
        self._last_saved_step = step
        _obs.counter("train.checkpoints").inc()
        _obs.event("train.checkpoint", step=step,
                   sync=bool(sync or not self.cfg.async_ckpt))

    def _restore_latest(self) -> int:
        ckpt.wait_pending()
        step = ckpt.latest_step(self.cfg.ckpt_dir)
        if step is None:
            # replay is only exact from the step-0 parameters, not from
            # whatever partially-trained state the failure left behind
            self.state = self._initial_state
            self.log("[loop] no checkpoint found; restarting from step 0")
            return 0
        self.state = ckpt.restore(self.state, self.cfg.ckpt_dir, step,
                                  self.state_shardings)
        self.log(f"[loop] restored checkpoint at step {step}")
        _obs.event("train.restore", step=step)
        return step

    # -- watchdog -----------------------------------------------------------
    def _watch(self, step: int, dt: float):
        if self._ewma is None:
            self._ewma = dt
            return
        if dt > self.cfg.straggler_factor * self._ewma:
            self.straggler_events.append(step)
            _obs.counter("train.stragglers").inc()
            _obs.event("train.straggler", step=step, dt_s=dt,
                       ewma_s=self._ewma)
            self.log(f"[loop] STRAGGLER step {step}: {dt:.3f}s vs "
                     f"EWMA {self._ewma:.3f}s")
        self._ewma = (1 - self.cfg.ewma_alpha) * self._ewma + \
            self.cfg.ewma_alpha * dt

    # -- main ---------------------------------------------------------------
    def run(self, start_step: int = 0) -> Any:
        self._install_sigterm()
        self._stats0 = _collect_stats()
        self._initial_state = self.state  # immutable tree: reference only
        step_us = _obs.histogram("train.step_us")
        step = start_step
        while step < self.cfg.total_steps:
            if self._preempted:
                self.log(f"[loop] SIGTERM: checkpointing at {step}, exiting")
                _obs.event("train.preempt", step=step)
                self._save(step, sync=True)
                self._log_uop_cache()
                return self.state
            try:
                if self.failure_injector and self.failure_injector(step):
                    raise InjectedFailure(f"injected failure at step {step}")
                t0 = time.perf_counter()
                with _obs.trace("train.step", step=step):
                    batch = self.batch_fn(step)
                    self.state, metrics = self.train_step(self.state,
                                                          batch)
                    jax.block_until_ready(
                        jax.tree.leaves(self.state)[0])
                dt = time.perf_counter() - t0
                step_us.observe(dt * 1e6)
                _obs.counter("train.steps").inc()
                self._watch(step, dt)
                if step % self.cfg.log_every == 0:
                    m = {k: float(v) for k, v in metrics.items()
                         if getattr(v, "ndim", 0) == 0}
                    for k, v in m.items():
                        _obs.gauge(f"train.{k}").set(v)
                    self.metrics_history.append({"step": step, **m})
                    self.log(f"[loop] step {step} "
                             f"loss={m.get('total_loss', m.get('loss', -1)):.4f} "
                             f"dt={dt:.3f}s")
                step += 1
                if step % self.cfg.ckpt_every == 0:
                    self._save(step)
            except InjectedFailure as e:
                self.restarts += 1
                _obs.counter("train.failures").inc()
                _obs.event("train.failure", step=step,
                           restart=self.restarts)
                self.log(f"[loop] FAILURE: {e}; restart "
                         f"{self.restarts}/{self.cfg.max_restarts}")
                if self.restarts > self.cfg.max_restarts:
                    raise
                step = self._restore_latest()
        # drain in-flight async saves; *this run* already checkpointed the
        # final step when total_steps is a multiple of ckpt_every (a stale
        # file from an earlier run in the same dir doesn't count)
        ckpt.wait_pending()
        if self._last_saved_step != self.cfg.total_steps:
            self._save(self.cfg.total_steps, sync=True)
        self._log_uop_cache()
        return self.state

    def _log_uop_cache(self):
        """Surface the dataflow μop-cache efficiency over this run:
        replayed/retraced steps should hit the cache, not re-run the
        scheduler.  Both sources are read through ``obs.collect()``
        (consistent copies), never by poking subsystem privates."""
        stats = _collect_stats()
        info = stats.get("dataflow.uop_cache")
        if info is not None:
            base = self._stats0.get("dataflow.uop_cache",
                                    {"hits": 0, "misses": 0})
            hits = info["hits"] - base["hits"]
            misses = info["misses"] - base["misses"]
            if hits or misses:
                self.log(f"[loop] dataflow μop cache: {hits} hits / "
                         f"{misses} misses this run "
                         f"({info['currsize']} geometries cached)")
        tune = stats.get("tune.planner")
        if tune is not None:
            base = self._stats0.get("tune.planner") or \
                {"lookups": 0, "hits": 0, "measurements": 0}
            lookups = tune["lookups"] - base["lookups"]
            if lookups:
                self.log(f"[loop] tune planner: {lookups} lookups / "
                         f"{tune['hits'] - base['hits']} plan hits / "
                         f"{tune['measurements'] - base['measurements']} "
                         f"measurements this run "
                         f"({tune['plans']} plans cached)")
