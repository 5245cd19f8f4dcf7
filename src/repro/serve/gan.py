"""Batched GAN image-generation service on ahead-of-time compiled
programs.

The serving analogue of `serve.engine.DecodeEngine` for the GAN
workloads.  On construction the server builds (or is handed) one
:class:`repro.program.Program`: the config → policy → epilogue → plan
walk happens exactly once, ahead of the first trace, and the hot path is
the program's single jitted executable — there is no per-request (or
even per-trace) resolution left.  With ``backend="auto"`` the build
**measures** a plan for every generator-layer geometry (zero
measurements when the planner's plan file is already warm); the frozen
per-layer resolutions are exposed via ``server.describe()`` and the
one-line summary in ``repr``.  A program exported from a tuning box
(``ProgramSpec.save``) can be served directly by passing ``program=``.

``generate(n)`` rounds work up to full batches but **discards nothing**:
tail samples beyond ``n`` are carried in a remainder buffer and served
first on the next call, so under ``n % batch_size != 0`` traffic every
generated sample is eventually served.  ``samples_served`` /
``samples_buffered`` / ``samples_discarded`` account for every sample
the generator produced (``samples_discarded`` stays 0 while the buffer
carries remainders; it exists so capacity planning can trust the
invariant ``served + buffered + discarded == batches x batch_size``).

Two ways to drive it:

* **Synchronous** — call ``generate(n)`` from one thread; the call
  blocks until the samples are on the host.
* **Asynchronous** — call ``submit(n)`` (from any number of threads):
  the first ``submit`` hands the server's program, RNG key, and
  remainder buffer to an internal continuous-batching
  :class:`~repro.serve.gan_engine.GanEngine`, and returns a
  :class:`~repro.serve.gan_engine.GanFuture`.  From then on
  ``generate`` delegates to the engine too (``submit(n).result()``), so
  the sample stream stays single-sourced and bit-identical to the
  synchronous one at equal seeds.  Call ``close()`` (or use the server
  as a context manager) to shut the engine down cleanly.

For many concurrent clients, batch-size buckets, and measured
throughput/latency, construct a :class:`~repro.serve.gan_engine
.GanEngine` directly — see ``docs/serving.md``.
"""

from __future__ import annotations

import dataclasses
import itertools
import time

import jax
import numpy as np

from repro import obs as _obs
from repro.core.dataflow import DataflowPolicy
from repro.models.gan import GanConfig
from repro.program import Program, ProgramSpec
from repro.program.spec import _UNSET as _MESH_UNSET
from repro.serve.gan_engine import refuse_image_input

__all__ = ["GanServer"]

# Distinguishes the metrics of multiple servers in one process (same
# model, different seeds/batch sizes) — the label, not the metric name,
# carries the instance identity.
_SERVER_SEQ = itertools.count()

# Batch occupancy is a fraction of batch_size in (0, 1]; latency buckets
# make no sense for it.
_OCCUPANCY_BOUNDS = tuple(i / 10 for i in range(1, 11))


class GanServer:
    def __init__(self, cfg: GanConfig, g_params, batch_size: int = 8,
                 policy: DataflowPolicy | None = None, seed: int = 0,
                 warm_plans: bool = True,
                 program: Program | None = None, mesh=_MESH_UNSET,
                 dtype: str | None = None):
        if int(batch_size) <= 0:
            raise ValueError(f"batch_size must be positive, "
                             f"got {batch_size}")
        if dtype is not None:
            # serving-time storage-precision override (canonicalized by
            # GanConfig; accumulation stays f32 — see repro.quant)
            cfg = dataclasses.replace(cfg, dtype=dtype)
        refuse_image_input(cfg)
        self.cfg = cfg
        if g_params is None:
            # int8-deploy flow: a quantized program carries its own
            # (dequantized-at-load) parameters
            if program is None or not program.quantized:
                raise ValueError("g_params=None needs a quantized "
                                 "program= (int8 export) to serve")
            g_params = program.params
        self.params = g_params
        self.batch_size = int(batch_size)
        self.policy = policy or cfg.policy
        self.key = jax.random.PRNGKey(seed)
        # Accounting lives on the obs registry (one labeled metric set
        # per server instance); the old integer attributes survive as
        # read-only properties over these, so
        # ``served + buffered + discarded == batches × batch_size``
        # is now an invariant of registry state.
        self.server_id = f"{cfg.name}#{next(_SERVER_SEQ)}"
        labels = {"server": self.server_id}
        self._m_batches = _obs.counter("serve.batches", **labels)
        self._m_served = _obs.counter("serve.samples_served", **labels)
        self._m_discarded = _obs.counter("serve.samples_discarded",
                                         **labels)
        self._m_buffered = _obs.gauge("serve.samples_buffered", **labels)
        self._m_request_us = _obs.histogram("serve.request_us", **labels)
        self._m_occupancy = _obs.histogram(
            "serve.batch_occupancy", bounds=_OCCUPANCY_BOUNDS, **labels)
        self._spare: np.ndarray | None = None   # carried tail samples
        self._engine = None     # async façade (created on first submit)
        if program is not None:
            if program.spec.role != "generator":
                raise ValueError(f"GanServer needs a generator program, "
                                 f"got role={program.spec.role!r}")
            if dtype is None and program.spec.dtype != cfg.dtype:
                # adopt the exported program's storage precision unless
                # the caller pinned one explicitly
                cfg = dataclasses.replace(cfg, dtype=program.spec.dtype)
                self.cfg = cfg
            # a mismatched program file must fail here with a clear
            # error, not as a shape mismatch inside the first trace
            # (the heuristic-policy walk below touches no planner)
            expected = ProgramSpec.build(cfg, self.batch_size,
                                         "generator",
                                         policy=DataflowPolicy())
            if program.spec.geometry_signature() != \
                    expected.geometry_signature():
                raise ValueError(
                    f"program {program.spec.model!r} froze a different "
                    f"workload than config {cfg.name!r} builds "
                    f"(topology / z_dim / channel-scale / epilogue / "
                    f"precision drift)")
            self.program = program
        else:
            # measure=warm_plans: an auto policy tunes every layer plan
            # ahead of the first trace (a no-op for concrete policies,
            # and zero measurements when the plan cache is warm)
            self.program = Program.build(
                cfg, self.batch_size, "generator", policy=self.policy,
                measure=warm_plans, differentiable=False, mesh=mesh)
        if self.program.mesh is not None and \
                self.batch_size % self.program.spec.mesh[0]:
            raise ValueError(
                f"batch_size {self.batch_size} does not divide over "
                f"the program's data axis of "
                f"{self.program.spec.mesh[0]} (mesh "
                f"{self.program.mesh_str})")
        # sharded programs want their input batch placed batch-split
        # over the data axis before dispatch (None = single device,
        # including the degraded-mesh case: skip the device_put)
        self._in_sharding = self.program.input_sharding
        self._generate = self.program.apply

    # -- accounting (registry-backed; attribute API preserved) --------------
    # Once the async façade is live, the engine continues the stream:
    # totals are the pre-handoff counts plus the engine's, so the
    # ``served + buffered + discarded == batches × batch_size``
    # invariant spans the handoff.
    @property
    def batches_served(self) -> int:
        eng = self._engine
        return self._m_batches.value + (eng.batches_served if eng else 0)

    @property
    def samples_served(self) -> int:
        eng = self._engine
        return self._m_served.value + (eng.samples_served if eng else 0)

    @property
    def samples_discarded(self) -> int:
        eng = self._engine
        return self._m_discarded.value + \
            (eng.samples_discarded if eng else 0)

    @property
    def samples_buffered(self) -> int:
        if self._engine is not None:
            return self._engine.samples_buffered
        return 0 if self._spare is None else len(self._spare)

    def _set_spare(self, spare: np.ndarray | None) -> None:
        self._spare = spare if spare is not None and len(spare) else None
        self._m_buffered.set(self.samples_buffered)

    def _next_key(self):
        self.key, k = jax.random.split(self.key)
        return k

    # -- async façade -------------------------------------------------------
    def submit(self, n: int):
        """Asynchronous :meth:`generate`: enqueue a request and return
        a :class:`~repro.serve.gan_engine.GanFuture` (thread-safe).

        The first call hands the server's program, RNG key, and
        remainder buffer to an internal single-bucket
        :class:`~repro.serve.gan_engine.GanEngine`; the stream picks up
        exactly where the synchronous calls left off, so mixing
        ``generate`` and ``submit`` never forks or reorders it."""
        return self._ensure_engine().submit(n)

    def close(self, drain: bool = True) -> None:
        """Shut the async engine down (no-op if :meth:`submit` was
        never called).  ``drain=True`` answers queued requests first;
        ``drain=False`` fails unscheduled ones with ``ServerClosed``."""
        if self._engine is not None:
            self._engine.close(drain=drain)

    def __enter__(self) -> "GanServer":
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self.close(drain=exc_type is None)

    def _ensure_engine(self):
        if self._engine is None:
            from repro.serve.gan_engine import GanEngine
            self._engine = GanEngine(
                self.cfg, self.params, buckets=(self.batch_size,),
                policy=self.policy, program=self.program,
                key=self.key, spare=self._spare, warmup=False)
            self._set_spare(None)   # the engine owns the buffer now
        return self._engine

    def generate(self, n: int) -> np.ndarray:
        """Generate ``n`` images (n, *spatial, C) as numpy.  Remainder
        samples from the final batch are buffered for the next call,
        never discarded.  After the first :meth:`submit`, delegates to
        the async engine (same stream, same accounting)."""
        if int(n) <= 0:
            raise ValueError(f"n must be positive, got {n}")
        if self._engine is not None:
            return self._engine.generate(n)
        t0 = time.perf_counter()
        with _obs.trace("serve.generate", server=self.server_id,
                        n=int(n)) as sp:
            outs = []
            remaining = int(n)
            batches = 0
            if self._spare is not None:
                take = min(len(self._spare), remaining)
                outs.append(self._spare[:take])
                self._set_spare(self._spare[take:])
                self._m_served.inc(take)
                remaining -= take
            while remaining > 0:
                z = jax.random.normal(self._next_key(),
                                      (self.batch_size, self.cfg.z_dim))
                if self._in_sharding is not None:
                    z = jax.device_put(z, self._in_sharding)
                img = np.asarray(self._generate(self.params, z))
                self._m_batches.inc()
                batches += 1
                take = min(self.batch_size, remaining)
                self._m_served.inc(take)
                self._m_occupancy.observe(take / self.batch_size)
                remaining -= take
                outs.append(img[:take])
                if take < self.batch_size:
                    self._set_spare(img[take:])
            out = np.concatenate(outs, axis=0)
            sp.set(batches=batches, buffered=self.samples_buffered)
        self._m_request_us.observe((time.perf_counter() - t0) * 1e6)
        return out

    def describe(self) -> str:
        """The server's frozen execution: the program's per-layer
        records (op, geometry, epilogue, resolved backend/blocks,
        provenance)."""
        return self.program.describe()

    def __repr__(self) -> str:
        return (f"GanServer(model={self.cfg.name!r}, "
                f"batch_size={self.batch_size}, "
                f"policy={self.program.spec.summary()}, "
                f"served={self.samples_served}, "
                f"buffered={self.samples_buffered}, "
                f"discarded={self.samples_discarded})")
