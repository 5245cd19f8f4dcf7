"""The executable form of a :class:`~repro.program.ProgramSpec`.

A :class:`Program` binds a frozen spec to **one** jitted callable:
``program.apply(params, x)`` traces the whole network once (per input
shape/dtype) and replays the compiled executable afterwards — no
per-call config → policy → plan threading anywhere on the hot path.
The per-layer policies are concrete pinned backends (the spec resolved
them ahead of time), so tracing never touches the autotuning planner:
an exported program serves on a planner-less process with zero
measurements.
"""

from __future__ import annotations

import logging
import warnings

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro import obs as _obs
from repro.compat import shard_map as _shard_map
from repro.core.dataflow import DataflowPolicy
from repro.core.dataflow import conv as df_conv
from repro.core.dataflow import tconv as df_tconv
from repro.launch.mesh import make_local_mesh
from repro.program.spec import _UNSET as _SPEC_UNSET
from repro.program.spec import ProgramSpec

__all__ = ["Program", "build_bucket_programs", "load_or_build"]

log = logging.getLogger(__name__)


class Program:
    """One GAN network as an ahead-of-time compiled executable.

    ``forward`` is the traceable (unjitted) computation — use it inside
    a larger ``jit`` (a train step, a loss);  ``apply`` is the jitted
    standalone entry point serving uses.  ``traces`` counts actual
    traces of ``apply`` — the executable-reuse contract is testable:
    repeated same-shape calls keep it at 1.

    A spec with a frozen ``mesh`` makes the program **sharded**:
    ``forward``/``apply`` wrap the layer replay in one
    ``shard_map`` over a ``("data", "model")`` mesh — the batch splits
    over ``data`` (weights replicated: the shard_map transpose psums
    their cotangents, so data-parallel gradient reduction is automatic
    when the forward is differentiated), and ``"cout"``-sharded layers
    run on a local Cout shard of their weights followed by a tiled
    ``all_gather``.  When the local process has fewer devices than the
    spec's mesh needs, the program **degrades to single-device with a
    warning** (``self.mesh is None``, ``program.mesh_degraded``
    counter) — the exported file serves anywhere, just unsharded.
    """

    def __init__(self, spec: ProgramSpec, *, differentiable: bool = True):
        from repro.quant.precision import storage_dtype
        self.spec = spec
        self.differentiable = bool(differentiable)
        self._policies = tuple(
            DataflowPolicy(backend=le.backend,
                           differentiable=self.differentiable)
            for le in spec.layers)
        # the storage precision every activation/weight is cast to at
        # use (f32 = no-op); params may stay f32 in the caller's
        # optimizer — the cast is inside the trace, so gradients flow
        # back to the parameter dtype (mixed-precision training)
        self._storage = storage_dtype(spec.dtype)
        self._dequantized = None
        self.traces = 0
        self.mesh = None
        if spec.mesh is not None:
            need = spec.mesh[0] * spec.mesh[1]
            have = len(jax.devices())
            if need > have:
                warnings.warn(
                    f"program {spec.model}/{spec.role} wants a "
                    f"{spec.mesh[0]}x{spec.mesh[1]} mesh ({need} "
                    f"devices) but only {have} available; degrading "
                    f"to single-device execution", RuntimeWarning,
                    stacklevel=2)
                _obs.counter("program.mesh_degraded").inc()
            else:
                self.mesh = make_local_mesh(data=spec.mesh[0],
                                            model=spec.mesh[1])
                _obs.counter("program.sharded").inc()
        # parameter layouts for the sharded path: Cout-sharded layers
        # split their weight's last (Cout) axis and bias over "model";
        # everything else (incl. a latent generator's projection)
        # replicates
        self._param_pspecs = {}
        if self.mesh is not None:
            for le in spec.layers:
                if le.sharding != "cout":
                    continue
                self._param_pspecs[le.w_param] = \
                    P(*((None,) * (le.nd + 1) + ("model",)))
                if le.bias:
                    self._param_pspecs[le.b_param] = P("model")

        def _traced(params, x):
            # Runs once per input shape (trace time, not per call) —
            # cheap enough to always count, visible in ``--stats``.
            self.traces += 1
            _obs.counter("program.traces").inc()
            if self.traces > 1:
                _obs.counter("program.retraces").inc()
            return self.forward(params, x)
        self._apply = jax.jit(_traced)

    @classmethod
    def build(cls, cfg, batch: int, role: str = "generator", *,
              policy: DataflowPolicy | None = None, planner=None,
              measure: bool = False, dtype: str | None = None,
              differentiable: bool = True, mesh=_SPEC_UNSET,
              cout_shard_min_bytes: int | None = None) -> "Program":
        """:meth:`ProgramSpec.build` + wrap — the one-call form."""
        spec = ProgramSpec.build(cfg, batch, role, policy=policy,
                                 planner=planner, measure=measure,
                                 dtype=dtype, mesh=mesh,
                                 cout_shard_min_bytes=cout_shard_min_bytes)
        return cls(spec, differentiable=differentiable)

    # -- embedded (quantized) parameters ------------------------------------
    @property
    def quantized(self) -> bool:
        """True when the spec carries an embedded int8 weight payload
        (an exported quantized program)."""
        return self.spec.quantized_params is not None

    @property
    def params(self):
        """The spec's embedded int8 payload dequantized into the
        storage dtype (weights → ``spec.dtype``, biases → f32),
        materialized once per Program and deterministic across loads —
        the tree callers hand straight to :meth:`apply` /
        ``GanServer``.  ``None`` for ordinary programs, whose params
        live with the caller."""
        if self.spec.quantized_params is None:
            return None
        if self._dequantized is None:
            from repro.quant.weights import dequantize_params
            self._dequantized = dequantize_params(
                self.spec.quantized_params, self.spec.dtype)
        return self._dequantized

    # -- sharding queries ---------------------------------------------------
    @property
    def input_sharding(self) -> NamedSharding | None:
        """How callers should place input batches: batch dim split over
        the ``data`` axis (``None`` for unsharded / degraded programs —
        callers skip the ``device_put``)."""
        if self.mesh is None:
            return None
        return NamedSharding(self.mesh, P("data"))

    @property
    def device_count(self) -> int:
        """Devices this program actually executes on (1 when unsharded
        or degraded)."""
        return self.mesh.devices.size if self.mesh is not None else 1

    @property
    def mesh_str(self) -> str:
        """``"4x2"``-style label of the *active* mesh (``"1"`` when
        unsharded or degraded) — the span-attr form."""
        if self.mesh is None:
            return "1"
        return f"{self.spec.mesh[0]}x{self.spec.mesh[1]}"

    # -- execution ----------------------------------------------------------
    def forward(self, params, x):
        """Replay the frozen layer records (traceable; donate to ``jit``
        via :meth:`apply` or embed in a caller's trace).  On a sharded
        program this *is* the ``shard_map``-wrapped computation, so
        embedding it in a caller's ``jit`` (e.g. the train step)
        inherits the spec's layouts."""
        if self.mesh is None:
            return self._replay(params, x)
        data_dim = self.spec.mesh[0]
        if x.shape[0] % data_dim:
            raise ValueError(
                f"batch {x.shape[0]} does not divide over the data "
                f"axis of {data_dim} (program "
                f"{self.spec.model}/{self.spec.role} mesh "
                f"{self.mesh_str})")
        pspecs = {k: self._param_pspecs.get(k, P()) for k in params}
        fn = _shard_map(self._replay, mesh=self.mesh,
                        in_specs=(pspecs, P("data")),
                        out_specs=P("data"))
        return fn(params, x)

    def _replay(self, params, x):
        """The per-device layer replay (the whole computation when
        unsharded; the shard-local body under ``shard_map`` when not).
        Inside shard_map, ``x`` is the local batch shard and
        ``"cout"``-layers' params are local Cout shards.

        The spec's storage precision is applied here: inputs and
        weights are cast to ``spec.dtype`` at use, the projection
        contracts with an f32 accumulator (``preferred_element_type``,
        matching the conv backends' f32 scratch), and biases stay f32
        into the fused epilogues.  Bit-identical to the historic path
        for f32 specs.

        Each layer's work runs under ``jax.named_scope("layer.<name>")``
        (``layer.proj`` for a latent generator's projection, which an
        image-input generator does not have, ``layer.head``
        for the discriminator's mean), so every op it compiles to
        carries the layer's name in its HLO ``op_name`` and in the
        device trace, whichever backend the spec froze."""
        spec = self.spec
        sd = self._storage
        sharded = self.mesh is not None
        x = x.astype(sd)
        if spec.input_kind == "latent":
            first = spec.layers[0]
            with jax.named_scope("layer.proj"):
                x = jnp.dot(x, params["proj_w"].astype(sd),
                            preferred_element_type=jnp.float32)
                x = x + params["proj_b"].astype(jnp.float32)
                x = x.reshape((x.shape[0],) + first.in_spatial
                              + (first.cin,))
                x = jax.nn.relu(x).astype(sd)
        batch = x.shape[0]
        for le, policy in zip(spec.layers, self._policies):
            op = df_tconv if le.kind == "tconv" else df_conv
            with jax.named_scope(f"layer.{le.name}"):
                w = params[le.w_param].astype(sd)
                b = params[le.b_param] if le.bias else None
                x = op(x, w, le.strides, le.paddings, policy=policy,
                       blocks=le.blocks, bias=b, epilogue=le.epilogue)
                if sharded and le.sharding == "cout":
                    # each device computed cout/model output channels
                    # (epilogue included — bias was sharded alongside);
                    # restore full Cout for the next layer.  No halo:
                    # Cout is a pure output dimension.
                    x = jax.lax.all_gather(x, "model", axis=x.ndim - 1,
                                           tiled=True)
        if spec.role == "discriminator":
            # logits reduce in f32 (a bf16 mean over every pixel would
            # lose the signal) and *stay* f32 — losses are always
            # computed at full precision
            with jax.named_scope("layer.head"):
                x = x.reshape(batch, -1).mean(axis=-1, dtype=jnp.float32)
        return x

    def apply(self, params, x):
        """The jitted executable: one trace per input shape, then the
        cached computation — serving's hot path.

        The disabled-tracing path is a single boolean check away from
        the raw jitted callable (the microbench gate pins its cost on
        ``program_us`` under 2%); with tracing on, each call gets a
        ``program.apply`` span whose ``traced`` attr flags the calls
        that paid trace+compile time."""
        if not _obs.is_enabled():
            return self._apply(params, x)
        traces_before = self.traces
        with _obs.trace("program.apply", model=self.spec.model,
                        role=self.spec.role, batch=int(x.shape[0]),
                        devices=self.device_count,
                        mesh=self.mesh_str) as sp:
            out = self._apply(params, x)
            sp.set(traced=self.traces > traces_before)
        return out

    # -- passthroughs -------------------------------------------------------
    def describe(self) -> str:
        return self.spec.describe()

    def save(self, path) -> None:
        self.spec.save(path)

    def __repr__(self) -> str:
        quant = ", quant=int8" if self.quantized else ""
        return (f"Program({self.spec.model}/{self.spec.role}, "
                f"{len(self.spec.layers)} layers, "
                f"{self.spec.summary()}, dtype={self.spec.dtype}"
                f"{quant}, traces={self.traces})")


def build_bucket_programs(spec: ProgramSpec, buckets, *,
                          differentiable: bool = False
                          ) -> dict[int, "Program"]:
    """One :class:`Program` per batch-size bucket, all from **one**
    frozen spec.

    The continuous-batching serving engine
    (:class:`repro.serve.gan_engine.GanEngine`) coalesces requests into
    a small set of batch-size buckets.  Resolution (the config → policy
    → plan walk) happened once when ``spec`` was built; this helper
    only fans the frozen records out into one jitted executable per
    bucket, so each bucket traces exactly once — ``programs[b].traces``
    stays at 1 however many requests ride that bucket (pinned by the
    engine tests) and the ``program.retraces`` counter never fires on
    the serving path.

    ``buckets`` is deduplicated and sorted ascending; every bucket must
    be a positive int.
    """
    sizes = sorted({int(b) for b in buckets})
    if not sizes or sizes[0] <= 0:
        raise ValueError(f"buckets must be positive ints, got "
                         f"{tuple(buckets)}")
    return {b: Program(spec, differentiable=differentiable)
            for b in sizes}


def load_or_build(path, cfg, batch: int, role: str = "generator", *,
                  policy: DataflowPolicy | None = None, planner=None,
                  measure: bool = False, dtype: str | None = None,
                  differentiable: bool = True,
                  mesh=_SPEC_UNSET) -> tuple[Program, bool]:
    """Load an exported program file, falling back to fresh resolution.

    Returns ``(program, loaded)``.  ``loaded=False`` means the file was
    missing, corrupt, version-skewed, named unknown backends/stale
    blocks, or froze a different workload than ``cfg`` builds now
    (topology / channel-scale / epilogue / storage-precision drift —
    the requested ``dtype`` defaults to ``cfg.dtype``, so a file at
    the wrong precision degrades too) — in every such case the
    program is rebuilt from ``cfg`` exactly as :meth:`Program.build`
    would, so a bad file degrades the optimization, never the service.

    The mesh is deliberately **not** part of the workload identity: a
    file exported with a mesh loads fine on a config without one (and
    vice versa) — it is the file's frozen sharding decision that wins,
    degrading to single-device if this process lacks the devices.
    ``mesh`` only shapes the *fallback* rebuild."""
    fresh = ProgramSpec.build(cfg, batch, role, policy=policy,
                              planner=planner, measure=False,
                              dtype=dtype, mesh=mesh)
    try:
        spec = ProgramSpec.load(path)
        if spec.geometry_signature() != fresh.geometry_signature():
            raise ValueError("program file froze a different workload "
                             "than this config builds")
    except Exception as e:   # corrupt/stale file → fresh resolution
        log.warning("ignoring program file %s (%s: %s); rebuilding from "
                    "config", path, type(e).__name__, e)
        if measure:   # the fallback still honors the warmup request
            fresh = ProgramSpec.build(cfg, batch, role, policy=policy,
                                      planner=planner, measure=True,
                                      dtype=dtype, mesh=mesh)
        return Program(fresh, differentiable=differentiable), False
    return Program(spec, differentiable=differentiable), True
