"""Continuous-batching async GAN serving engine.

:class:`GanEngine` is the GAN analogue of :class:`~repro.serve.engine
.DecodeEngine`: a thread-safe front-end that turns many concurrent
sample requests into a small number of well-packed device batches.

* **Request queue + scheduler thread.**  ``submit(n)`` is callable from
  any number of producer threads; it enqueues a :class:`GanFuture` and
  returns immediately.  A single scheduler thread owns the device work:
  it drains the queue, coalesces pending demand, advances the RNG
  stream, dispatches compute, and distributes results — so every
  JAX-visible mutation stays single-threaded while the front door is
  concurrent.
* **Ahead-of-time bucket set.**  At construction the engine builds one
  :class:`~repro.program.ProgramSpec` (the config → policy → plan walk
  runs once) and fans it out into one :class:`~repro.program.Program`
  per batch-size bucket (:func:`repro.program.build_bucket_programs`).
  Each coalesced batch runs the smallest bucket that covers pending
  demand (the largest bucket under overload), so serving never traces
  per request: ``programs[b].traces`` stays at 1 per bucket.
* **Transfer/compute overlap.**  Dispatch is asynchronous: the
  scheduler launches batch *k+1* before it blocks on batch *k*'s
  device→host transfer, so the copy of one batch rides under the
  compute of the next (``pipeline_depth`` batches stay in flight).
* **Nothing is discarded.**  Tail samples of a bucket beyond what the
  coalesced requests asked for land in the same remainder buffer the
  synchronous :class:`~repro.serve.gan.GanServer` keeps, and serve the
  next requests first.  The accounting invariant becomes
  ``served + buffered + discarded == generated + initial spare``;
  ``samples_discarded`` stays 0 except when ``close(drain=False)``
  cancels requests whose samples were already in flight.
* **Clean shutdown.**  ``close()`` (or exiting the context manager)
  drains: queued requests are answered, then the scheduler exits.
  ``close(drain=False)`` answers what is already in flight and fails
  the rest with :class:`ServerClosed`.  A scheduler-side exception
  fails every outstanding request with that exception.  In every case
  a ``GanFuture.result()`` returns or raises — it never hangs.

**Determinism.**  The sample stream is defined by ``(seed, the
sequence of batch sizes drawn)``: one key split per batch, exactly like
the synchronous server.  With a single bucket equal to a
``GanServer``'s ``batch_size``, the engine's stream is bit-identical to
``GanServer.generate`` at equal seeds, whatever the request
interleaving — requests are filled FIFO in stream order, and each
future's ``offset`` records its slice's stream position so concurrent
consumers can reassemble the sequential stream (pinned by tests).
With multiple buckets the bucket *choice* depends on instantaneous
queue depth, so the stream is reproducible only for a deterministic
submission schedule.

Metrics (labels ``engine=<id>``): ``engine.requests`` /
``engine.batches`` / ``engine.samples_served`` / ``.samples_discarded``
counters, ``engine.queue_depth`` / ``engine.samples_buffered`` gauges,
``engine.batch_occupancy`` / ``engine.request_us`` histograms (p50/p99
per-request end-to-end latency), plus an ``engine.request`` span per
completed request (via :func:`repro.obs.emit_span` — submit and
completion happen on different threads).  See ``docs/serving.md``.
"""

from __future__ import annotations

import dataclasses
import itertools
import threading
import time
from collections import deque

import jax
import numpy as np

from repro import obs as _obs
from repro.core.dataflow import DataflowPolicy
from repro.models.gan import GanConfig
from repro.program import Program, ProgramSpec, build_bucket_programs
from repro.program.spec import _UNSET as _MESH_UNSET

__all__ = ["GanEngine", "GanFuture", "ServerClosed", "DEFAULT_BUCKETS"]

DEFAULT_BUCKETS = (1, 2, 4, 8)


def refuse_image_input(cfg: GanConfig) -> None:
    """Serving draws latents for a request of ``n`` samples; a generator
    that takes an image (``cfg.input_kind``, DiscoGAN) needs requests
    that carry one, which the engine does not take yet (ROADMAP 2.3)."""
    if cfg.input_kind != "latent":
        raise ValueError(
            f"{cfg.name} is an image-input generator: serving feeds "
            f"latents and takes no per-request images yet (ROADMAP 2.3)")

# Occupancy is assigned/bucket in (0, 1] — latency buckets make no
# sense for it (same bounds the synchronous server uses).
_OCCUPANCY_BOUNDS = tuple(i / 10 for i in range(1, 11))

_ENGINE_SEQ = itertools.count()


class ServerClosed(RuntimeError):
    """The engine was closed before (or while) this request could be
    served; also raised by ``submit`` after ``close``."""


class GanFuture:
    """Handle for one submitted request: blocks in :meth:`result` until
    the engine answers (samples or an error) — never hangs past
    engine shutdown."""

    __slots__ = ("n", "offset", "_chunks", "_filled", "_result",
                 "_error", "_event", "_t0", "_t1", "_t0_us")

    def __init__(self, n: int):
        self.n = int(n)
        #: stream position of this request's first sample (set when the
        #: scheduler allocates it; allocation is FIFO, so sorting
        #: completed futures by offset reassembles the sequential
        #: stream).  None until allocated.
        self.offset: int | None = None
        self._chunks: list[np.ndarray] = []
        self._filled = 0
        self._result: np.ndarray | None = None
        self._error: BaseException | None = None
        self._event = threading.Event()
        self._t0 = time.perf_counter()
        self._t0_us = _obs.now_us()
        self._t1: float | None = None

    # -- engine side (scheduler thread, engine lock held) -------------------
    def _deliver(self, chunk: np.ndarray) -> None:
        self._chunks.append(chunk)
        self._filled += len(chunk)
        if self._filled >= self.n:
            self._result = self._chunks[0] if len(self._chunks) == 1 \
                else np.concatenate(self._chunks, axis=0)
            self._chunks = []
            self._finish()

    def _fail(self, err: BaseException) -> None:
        if not self._event.is_set():
            self._error = err
            self._finish()

    def _finish(self) -> None:
        self._t1 = time.perf_counter()
        self._event.set()

    # -- caller side --------------------------------------------------------
    def done(self) -> bool:
        return self._event.is_set()

    def exception(self, timeout: float | None = None
                  ) -> BaseException | None:
        if not self._event.wait(timeout):
            raise TimeoutError(f"request for {self.n} samples not "
                               f"answered within {timeout}s")
        return self._error

    def result(self, timeout: float | None = None) -> np.ndarray:
        err = self.exception(timeout)
        if err is not None:
            raise err
        return self._result

    @property
    def latency_us(self) -> float | None:
        """Submit→answer wall-clock (None while pending)."""
        if self._t1 is None:
            return None
        return (self._t1 - self._t0) * 1e6


class _Batch:
    """One dispatched bucket: the in-flight device array plus the FIFO
    share list saying which request gets which rows at resolution."""

    __slots__ = ("size", "shares", "assigned", "dev")

    def __init__(self, size: int):
        self.size = size
        self.shares: list[tuple[GanFuture, int]] = []
        self.assigned = 0
        self.dev = None


class GanEngine:
    """Continuous-batching asynchronous server for one GAN generator.

    Parameters mirror :class:`~repro.serve.gan.GanServer` where they
    overlap; the serving-specific ones:

    ``buckets``
        The ahead-of-time compiled batch sizes.  Each scheduled batch
        uses the smallest bucket covering coalesced pending demand
        (the largest bucket when demand exceeds it).
    ``program``
        An exported/tuned generator :class:`~repro.program.Program` to
        serve; its frozen spec seeds every bucket executable.  Built
        from ``cfg`` when omitted (``measure=warm_plans`` for ``auto``
        policies, exactly like the synchronous server).
    ``pipeline_depth``
        How many dispatched batches may be unresolved at once (≥1).
        Depth 1 already overlaps batch *k*'s device→host transfer with
        batch *k+1*'s compute.
    ``max_pending``
        Backpressure: ``submit`` blocks while this many requests are
        queued unallocated (None = unbounded).
    ``warmup``
        Trace every bucket executable at construction (a dummy forward
        per bucket) so no request ever pays compile time.
    ``key`` / ``spare``
        Advanced (used by the ``GanServer`` façade): start the RNG
        stream from an existing key instead of ``seed``, and seed the
        remainder buffer with already-generated samples.
    ``dtype``
        Storage-precision override ("float32"/"bfloat16"/"float16",
        aliases accepted): replaces ``cfg.dtype`` before the program
        build.  When serving an exported ``program=`` without an
        explicit override, the engine adopts the program's precision.
        Pass ``g_params=None`` with a quantized (int8-exported)
        program to serve its embedded weights.
    """

    def __init__(self, cfg: GanConfig, g_params,
                 buckets=DEFAULT_BUCKETS, *,
                 policy: DataflowPolicy | None = None, seed: int = 0,
                 warm_plans: bool = True, program: Program | None = None,
                 pipeline_depth: int = 1, max_pending: int | None = None,
                 warmup: bool = True, key=None,
                 spare: np.ndarray | None = None, mesh=_MESH_UNSET,
                 dtype: str | None = None):
        if dtype is not None:
            # serving-time storage-precision override (canonicalized by
            # GanConfig; accumulation stays f32 — see repro.quant)
            cfg = dataclasses.replace(cfg, dtype=dtype)
        refuse_image_input(cfg)
        if g_params is None:
            # int8-deploy flow: a quantized program carries its own
            # (dequantized-at-load) parameters
            if program is None or not program.quantized:
                raise ValueError("g_params=None needs a quantized "
                                 "program= (int8 export) to serve")
            g_params = program.params
        self.cfg = cfg
        self.params = g_params
        self.buckets = tuple(sorted({int(b) for b in buckets}))
        if not self.buckets or self.buckets[0] <= 0:
            raise ValueError(f"buckets must be positive ints, got "
                             f"{tuple(buckets)}")
        if int(pipeline_depth) < 1:
            raise ValueError(f"pipeline_depth must be >= 1, got "
                             f"{pipeline_depth}")
        if max_pending is not None and int(max_pending) < 1:
            raise ValueError(f"max_pending must be >= 1 or None, got "
                             f"{max_pending}")
        self.policy = policy or cfg.policy
        self.pipeline_depth = int(pipeline_depth)
        self.max_pending = None if max_pending is None \
            else int(max_pending)
        self.key = key if key is not None else jax.random.PRNGKey(seed)

        if program is not None:
            if program.spec.role != "generator":
                raise ValueError(f"GanEngine needs a generator program, "
                                 f"got role={program.spec.role!r}")
            if dtype is None and program.spec.dtype != cfg.dtype:
                # adopt the exported program's storage precision unless
                # the caller pinned one explicitly
                cfg = dataclasses.replace(cfg, dtype=program.spec.dtype)
                self.cfg = cfg
            expected = ProgramSpec.build(cfg, self.buckets[-1],
                                         "generator",
                                         policy=DataflowPolicy())
            if program.spec.geometry_signature() != \
                    expected.geometry_signature():
                raise ValueError(
                    f"program {program.spec.model!r} froze a different "
                    f"workload than config {cfg.name!r} builds "
                    f"(topology / z_dim / channel-scale / epilogue / "
                    f"precision drift)")
            spec = program.spec
        else:
            spec = ProgramSpec.build(cfg, self.buckets[-1], "generator",
                                     policy=self.policy,
                                     measure=warm_plans, mesh=mesh)
        self.spec = spec
        self.programs = build_bucket_programs(spec, self.buckets)
        # all bucket programs share the spec (and the local device
        # count), so one probe answers for the whole set: the batch
        # placement to device_put with (None when unsharded — including
        # the degraded-mesh case) and the span-attr mesh identity
        probe = self.programs[self.buckets[0]]
        self._in_sharding = probe.input_sharding
        self._devices = probe.device_count
        self._mesh_str = probe.mesh_str
        if probe.mesh is not None:
            bad = [b for b in self.buckets if b % spec.mesh[0]]
            if bad:
                raise ValueError(
                    f"buckets {bad} do not divide over the program's "
                    f"data axis of {spec.mesh[0]} (mesh "
                    f"{probe.mesh_str})")

        self.engine_id = f"{cfg.name}#{next(_ENGINE_SEQ)}"
        labels = {"engine": self.engine_id}
        self._m_requests = _obs.counter("engine.requests", **labels)
        self._m_batches = _obs.counter("engine.batches", **labels)
        self._m_generated = _obs.counter("engine.samples_generated",
                                         **labels)
        self._m_served = _obs.counter("engine.samples_served", **labels)
        self._m_discarded = _obs.counter("engine.samples_discarded",
                                         **labels)
        self._m_queue = _obs.gauge("engine.queue_depth", **labels)
        self._m_buffered = _obs.gauge("engine.samples_buffered", **labels)
        self._m_request_us = _obs.histogram("engine.request_us", **labels)
        self._m_occupancy = _obs.histogram(
            "engine.batch_occupancy", bounds=_OCCUPANCY_BOUNDS, **labels)

        # Shared state (producers ↔ scheduler): the queue, closed flag,
        # and futures' delivery all mutate under this lock.  The RNG
        # key, dispatch deque, and spare buffer are scheduler-thread
        # only.
        self._lock = threading.Lock()
        self._cv = threading.Condition(self._lock)
        self._queue: deque[GanFuture] = deque()
        self._closed = False
        self._drain = True
        self._alloc_pos = 0
        self._dispatched: deque[_Batch] = deque()
        self._spare: np.ndarray | None = None
        self.initial_spare = 0
        if spare is not None and len(spare):
            self._spare = np.asarray(spare)
            self.initial_spare = len(self._spare)
            self._m_buffered.set(self.initial_spare)

        if warmup:
            z0 = np.zeros((1, cfg.z_dim), np.float32)
            for b, prog in self.programs.items():
                z = np.broadcast_to(z0, (b, cfg.z_dim))
                if self._in_sharding is not None:
                    z = jax.device_put(z, self._in_sharding)
                jax.block_until_ready(prog.apply(g_params, z))

        self._thread = threading.Thread(
            target=self._run, name=f"gan-engine-{self.engine_id}",
            daemon=True)
        self._thread.start()

    # -- producer API -------------------------------------------------------
    def submit(self, n: int, timeout: float | None = None) -> GanFuture:
        """Enqueue a request for ``n`` samples (thread-safe, returns
        immediately once admitted).  Blocks while ``max_pending``
        requests are already waiting; raises :class:`ServerClosed` once
        the engine is closed."""
        if int(n) <= 0:
            raise ValueError(f"n must be positive, got {n}")
        fut = GanFuture(n)
        with self._cv:
            while (not self._closed and self.max_pending is not None
                   and len(self._queue) >= self.max_pending):
                if not self._cv.wait(timeout):
                    raise TimeoutError(
                        f"queue full ({self.max_pending} pending) for "
                        f"{timeout}s")
            if self._closed:
                raise ServerClosed(f"engine {self.engine_id} is closed")
            self._queue.append(fut)
            self._m_requests.inc()
            self._m_queue.set(len(self._queue))
            self._cv.notify_all()
        return fut

    def generate(self, n: int, timeout: float | None = None
                 ) -> np.ndarray:
        """Synchronous convenience: ``submit(n).result()``."""
        return self.submit(n).result(timeout)

    def close(self, drain: bool = True,
              timeout: float | None = None) -> None:
        """Stop the engine.  ``drain=True`` (default) answers every
        queued request first; ``drain=False`` answers only requests
        whose samples are already dispatched and fails the rest with
        :class:`ServerClosed`.  Idempotent; safe from any thread."""
        with self._cv:
            if not self._closed:
                self._closed = True
                self._drain = bool(drain)
            self._cv.notify_all()
        if self._thread is not threading.current_thread():
            self._thread.join(timeout)

    def __enter__(self) -> "GanEngine":
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        # an exception escaping the block must not hang on a full drain
        self.close(drain=exc_type is None)

    # -- accounting ---------------------------------------------------------
    @property
    def batches_served(self) -> int:
        return self._m_batches.value

    @property
    def samples_generated(self) -> int:
        return self._m_generated.value

    @property
    def samples_served(self) -> int:
        return self._m_served.value

    @property
    def samples_discarded(self) -> int:
        return self._m_discarded.value

    @property
    def samples_buffered(self) -> int:
        return 0 if self._spare is None else len(self._spare)

    @property
    def queue_depth(self) -> int:
        with self._lock:
            return len(self._queue)

    def describe(self) -> str:
        return self.spec.describe()

    def __repr__(self) -> str:
        return (f"GanEngine(model={self.cfg.name!r}, "
                f"buckets={self.buckets}, "
                f"policy={self.spec.summary()}, "
                f"served={self.samples_served}, "
                f"buffered={self.samples_buffered}, "
                f"discarded={self.samples_discarded}, "
                f"closed={self._closed})")

    # -- scheduler (single thread) ------------------------------------------
    def _run(self) -> None:
        try:
            self._loop()
        except BaseException as e:   # noqa: BLE001 — must answer futures
            self._fail_outstanding(e)
        finally:
            with self._cv:
                self._closed = True
                self._cv.notify_all()

    def _loop(self) -> None:
        while True:
            action = self._next_action()
            if action == "stop":
                break
            if isinstance(action, _Batch):
                self._dispatch(action)
                # overlap: block on the oldest transfer only once a
                # newer batch's compute is already in flight
                while len(self._dispatched) > self.pipeline_depth:
                    self._resolve(self._dispatched.popleft())
            else:   # "flush": no new demand — settle what's in flight
                while self._dispatched:
                    self._resolve(self._dispatched.popleft())
        # shutdown (non-drain close): requests that would need further
        # compute fail now — so their shares in still-unresolved
        # batches count as discarded — then in-flight batches settle,
        # answering every fully-dispatched request.
        with self._cv:
            for fut in list(self._queue):
                if fut.n - fut._filled - self._promised(fut) > 0:
                    fut._fail(ServerClosed(
                        f"engine {self.engine_id} closed before this "
                        f"request was scheduled"))
                    self._queue.remove(fut)
            self._m_queue.set(len(self._queue))
        while self._dispatched:
            self._resolve(self._dispatched.popleft())

    def _next_action(self):
        """Wait for work; serve the spare buffer; return the next batch
        to dispatch, ``"flush"`` to settle in-flight transfers, or
        ``"stop"``."""
        with self._cv:
            while True:
                self._serve_spare_locked()
                demand = self._fill_inflight_locked()
                if demand > 0:
                    if self._closed and not self._drain:
                        return "stop"
                    return self._make_batch_locked(demand)
                if self._dispatched:
                    return "flush"
                if self._closed:
                    return "stop"
                self._cv.wait()

    def _demand_locked(self) -> int:
        return sum(f.n - f._filled - self._promised(f)
                   for f in self._queue)

    def _promised(self, fut: GanFuture) -> int:
        # samples already assigned to `fut` in unresolved batches
        return sum(c for b in self._dispatched
                   for f, c in b.shares if f is fut)

    def _serve_spare_locked(self) -> None:
        """Drain the remainder buffer into the head of the queue (no
        compute; completes small requests instantly)."""
        while self._spare is not None and len(self._spare) and \
                self._queue:
            fut = self._queue[0]
            need = fut.n - fut._filled - self._promised(fut)
            if need <= 0:
                break
            take = min(need, len(self._spare))
            self._allocate_locked(fut, take)
            self._deliver_locked(fut, self._spare[:take])
            self._spare = self._spare[take:]
            if not len(self._spare):
                self._spare = None
        self._m_buffered.set(self.samples_buffered)

    def _fill_inflight_locked(self) -> int:
        """Assign unclaimed tail capacity of dispatched batches to
        queued demand; returns the demand still uncovered."""
        for b in self._dispatched:
            for fut in list(self._queue):
                free = b.size - b.assigned
                if free <= 0:
                    break
                need = fut.n - fut._filled - self._promised(fut)
                if need <= 0:
                    continue
                take = min(free, need)
                self._allocate_locked(fut, take)
                b.shares.append((fut, take))
                b.assigned += take
        return self._demand_locked()

    def _make_batch_locked(self, demand: int) -> _Batch:
        """Coalesce queued demand into the smallest covering bucket
        (largest under overload) and pre-assign its rows FIFO."""
        size = next((b for b in self.buckets if b >= demand),
                    self.buckets[-1])
        batch = _Batch(size)
        for fut in list(self._queue):
            free = size - batch.assigned
            if free <= 0:
                break
            need = fut.n - fut._filled - self._promised(fut)
            if need <= 0:
                continue
            take = min(free, need)
            self._allocate_locked(fut, take)
            batch.shares.append((fut, take))
            batch.assigned += take
        return batch

    def _allocate_locked(self, fut: GanFuture, take: int) -> None:
        if fut.offset is None:
            fut.offset = self._alloc_pos
        self._alloc_pos += take

    def _deliver_locked(self, fut: GanFuture, chunk: np.ndarray) -> None:
        fut._deliver(chunk)
        self._m_served.inc(len(chunk))
        if fut.done():
            if self._queue and self._queue[0] is fut:
                self._queue.popleft()
            else:                       # filled out of head position
                self._queue.remove(fut)
            self._m_queue.set(len(self._queue))
            if fut.latency_us is not None:
                self._m_request_us.observe(fut.latency_us)
            _obs.emit_span("engine.request", fut._t0_us,
                           engine=self.engine_id, n=fut.n,
                           offset=fut.offset, devices=self._devices,
                           mesh=self._mesh_str)
            self._cv.notify_all()       # backpressure: queue slot freed

    def _next_key(self):
        self.key, k = jax.random.split(self.key)
        return k

    def _dispatch(self, batch: _Batch) -> None:
        z = jax.random.normal(self._next_key(),
                              (batch.size, self.cfg.z_dim))
        if self._in_sharding is not None:
            z = jax.device_put(z, self._in_sharding)
        # async dispatch: returns a device future, does not block
        batch.dev = self.programs[batch.size].apply(self.params, z)
        self._m_generated.inc(batch.size)
        self._dispatched.append(batch)

    def _resolve(self, batch: _Batch) -> None:
        """Block on the batch's device→host transfer, then distribute
        rows to its shares in FIFO stream order; the unclaimed tail
        joins the remainder buffer."""
        out = np.asarray(batch.dev)
        batch.dev = None
        self._m_batches.inc()
        self._m_occupancy.observe(batch.assigned / batch.size)
        with self._cv:
            pos = 0
            for fut, count in batch.shares:
                chunk = out[pos:pos + count]
                pos += count
                if fut._event.is_set():   # cancelled mid-flight
                    self._m_discarded.inc(count)
                    continue
                self._deliver_locked(fut, chunk)
            if pos < batch.size:
                tail = out[pos:]
                self._spare = tail if self._spare is None \
                    else np.concatenate([self._spare, tail], axis=0)
                self._m_buffered.set(len(self._spare))

    def _fail_outstanding(self, err: BaseException) -> None:
        with self._cv:
            self._closed = True
            # nothing from an unresolved batch was delivered, so the
            # whole batch (shares and tail alike) is lost compute
            self._m_discarded.inc(sum(b.size for b in self._dispatched))
            self._dispatched.clear()
            for fut in self._queue:
                fut._fail(err)
            self._queue.clear()
            self._m_queue.set(0)
            self._cv.notify_all()
