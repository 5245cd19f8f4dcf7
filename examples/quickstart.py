"""Quickstart: train a tiny DCGAN with the GANAX dataflow on CPU.

This is the **Program API** flow — the supported entry point.  The
config → policy → epilogue → plan walk runs exactly twice
(``make_gan_train_step`` builds the generator and discriminator
programs ahead of the first trace); training replays the frozen
programs under the fault-tolerant ``TrainLoop``, and serving
demonstrates the full build → export → load → serve loop: the trained
generator's program spec is written to JSON, re-loaded as if on a
fresh serving box, and handed to ``GanServer``::

    PYTHONPATH=src python examples/quickstart.py --steps 30

Pick the execution path with ``--backend`` (``polyphase`` by default;
``pallas-interpret`` exercises the kernel semantics, ``zero-insert`` is
the conventional-accelerator baseline, ``auto`` consults the repro.tune
planner — point ``REPRO_TUNE_PLANS`` at a plan file from
``python -m repro.tune`` for measured plans).
"""

import argparse
import pathlib
import tempfile
import time

import jax
import jax.numpy as jnp

from repro.models.gan import GanConfig, init_gan
from repro.program import Program, ProgramSpec
from repro.serve.gan import GanServer
from repro.train.loop import LoopConfig, TrainLoop, make_gan_train_step


def synthetic_reals(key, batch):
    """'Real' data: smooth blobs (enough for a quickstart objective)."""
    k1, k2 = jax.random.split(key)
    xy = jnp.linspace(-1, 1, 64)
    gx, gy = jnp.meshgrid(xy, xy)
    centers = jax.random.uniform(k1, (batch, 2), minval=-0.5, maxval=0.5)
    r = jax.random.uniform(k2, (batch, 1), minval=0.1, maxval=0.4)
    d2 = ((gx[None] - centers[:, :1, None]) ** 2
          + (gy[None] - centers[:, 1:, None]) ** 2)
    img = jnp.exp(-d2 / (2 * r[..., None] ** 2))
    return jnp.tanh(img)[..., None] * jnp.ones((1, 1, 1, 3))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--lr", type=float, default=4e-3)
    ap.add_argument("--channel-scale", type=float, default=0.0625)
    ap.add_argument("--backend", default="polyphase",
                    help="dataflow backend (polyphase | zero-insert | "
                         "pallas | pallas-interpret | auto)")
    args = ap.parse_args()

    cfg = GanConfig(name="dcgan", channel_scale=args.channel_scale,
                    backend=args.backend)
    g_params, d_params = init_gan(cfg, jax.random.PRNGKey(0))

    # One ahead-of-time resolution for the whole run: both networks'
    # programs are frozen here, before anything traces.
    train_step, (g_prog, d_prog) = make_gan_train_step(
        cfg, args.batch, g_lr=args.lr * 5, measure=True)
    print(g_prog.describe())
    print(d_prog.describe())

    def batch_fn(step):
        # pure function of step → exact replay after any restart
        kz, kr = jax.random.split(jax.random.PRNGKey(step))
        return {"z": jax.random.normal(kz, (args.batch, cfg.z_dim)),
                "real": synthetic_reals(kr, args.batch)}

    t0 = time.time()
    with tempfile.TemporaryDirectory() as ckpt_dir:
        loop = TrainLoop(
            LoopConfig(total_steps=args.steps, ckpt_dir=ckpt_dir,
                       ckpt_every=max(10, args.steps // 2), log_every=5),
            train_step, batch_fn, (g_params, d_params))
        g_params, d_params = loop.run()
    print(f"done: {args.steps} adversarial steps through the "
          f"{args.backend} dataflow in {time.time()-t0:.1f}s")

    # Build → export → load → serve: ship the tuned program as data.
    with tempfile.TemporaryDirectory() as d:
        path = pathlib.Path(d) / "generator-program.json"
        g_prog.save(path)
        spec = ProgramSpec.load(path)          # a fresh serving process
        server = GanServer(cfg, g_params, batch_size=args.batch,
                           program=Program(spec, differentiable=False))
        imgs = server.generate(3)
    print(f"served {imgs.shape[0]} samples {imgs.shape[1:]} from the "
          f"exported program in {server.batches_served} batch(es) "
          f"({server.samples_buffered} buffered for the next call)")


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
