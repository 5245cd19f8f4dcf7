"""Declarative, ahead-of-time-resolved GAN execution specs.

GANAX's core move is ahead-of-time specialization: a deconv layer's
access patterns are static, so the accelerator compiles one microprogram
per output-row pattern *once* and then executes flat-out.  This module
lifts that principle to the model level.  :meth:`ProgramSpec.build`
walks a :class:`~repro.models.gan.GanConfig`'s layers **once** and
freezes a tuple of :class:`LayerExec` records — op kind, geometry,
fused epilogue, the resolved concrete backend + Pallas block shapes,
and the resolution's provenance (``pinned`` / ``tuned`` /
``heuristic``).  Nothing is re-resolved per call: the runtime
(:class:`repro.program.Program`) replays the frozen records.

Specs round-trip through JSON (:meth:`ProgramSpec.to_json` /
:meth:`ProgramSpec.from_json`), so a program tuned on a measurement box
can be exported and loaded on a serving box with **zero** planner
measurements — the serving process never needs a planner at all.
``from_json`` validates hard (version, backends, ranks, block shapes):
a stale or corrupt file raises ``ValueError`` so loaders can fall back
to fresh resolution (see :func:`repro.program.load_or_build`).
"""

from __future__ import annotations

import dataclasses
import json
import os

import jax

from repro import obs as _obs
from repro.core.dataflow import (SHARDINGS, DataflowPolicy, Epilogue,
                                 available_backends, backend_supports,
                                 blocks_valid, resolve_execution)

__all__ = ["LayerExec", "ProgramSpec", "PROGRAM_FORMAT_VERSION",
           "SUPPORTED_PROGRAM_VERSIONS", "ROLES", "INPUT_KINDS"]

# Version 2 added the mesh/sharding fields; version 3 added the
# optional embedded int8 weight payload (``quantized_params``, written
# by :func:`repro.quant.weights.quantize_program`).  Older documents
# still load: v1 defaults to single-device, v1/v2 to float32 storage
# with no quantized payload — see ``from_json``.
PROGRAM_FORMAT_VERSION = 3
SUPPORTED_PROGRAM_VERSIONS = (1, 2, 3)

ROLES = ("generator", "discriminator")

# A program's input signature: a latent of ``z_dim`` (projected onto the
# first layer's input) or an image of the first layer's input shape.  A
# discriminator always takes an image.
INPUT_KINDS = ("latent", "image")

# ``build(mesh=...)``'s "not passed" sentinel: None is a meaningful
# value (force single-device even if cfg carries a mesh).
_UNSET = object()


@dataclasses.dataclass(frozen=True)
class LayerExec:
    """One frozen layer execution record of a compiled GAN program.

    The geometry fields mirror :class:`~repro.core.analytical.ConvLayer`;
    ``w_param`` / ``b_param`` name the entries of the params dict the
    runtime reads; ``backend`` / ``blocks`` are the *concrete* resolved
    execution path (never ``"auto"`` or a preference form); ``source``
    records where that resolution came from (``pinned`` / ``tuned`` /
    ``heuristic``) and ``measured_us`` the winning plan's wall-clock
    when it was tuned.

    ``sharding`` is the layer's frozen mesh layout (one of
    :data:`repro.core.dataflow.SHARDINGS`): ``"data"`` = batch split
    over the ``data`` axis with replicated weights, ``"cout"`` =
    weights additionally sharded on Cout over the ``model`` axis (the
    local output is all-gathered back to full Cout).  Meaningful only
    when the owning :class:`ProgramSpec` carries a mesh; always
    ``"data"`` otherwise.
    """

    name: str
    kind: str                       # "tconv" | "conv"
    in_spatial: tuple[int, ...]
    kernel: tuple[int, ...]
    strides: tuple[int, ...]
    paddings: tuple[int, ...]
    cin: int
    cout: int
    w_param: str
    b_param: str | None
    bias: bool
    activation: str
    leaky_slope: float
    backend: str
    blocks: tuple[int, ...] | None
    source: str                     # "pinned" | "tuned" | "heuristic"
    measured_us: float | None = None
    sharding: str = "data"          # "data" | "cout"

    def __post_init__(self):
        if self.kind not in ("tconv", "conv"):
            raise ValueError(f"unknown layer kind {self.kind!r}")
        if self.source not in ("pinned", "tuned", "heuristic"):
            raise ValueError(f"unknown resolution source {self.source!r}")
        if self.sharding not in SHARDINGS:
            raise ValueError(f"unknown layer sharding "
                             f"{self.sharding!r}; one of {SHARDINGS}")
        # constructing the epilogue validates activation/leaky_slope —
        # a corrupt program file must fail here, not at first trace
        Epilogue(bias=self.bias, activation=self.activation,
                 leaky_slope=self.leaky_slope)
        if self.bias and self.b_param is None:
            raise ValueError(f"layer {self.name!r} has bias=True but "
                             f"no b_param")

    @property
    def nd(self) -> int:
        return len(self.in_spatial)

    @property
    def epilogue(self) -> Epilogue:
        return Epilogue(bias=self.bias, activation=self.activation,
                        leaky_slope=self.leaky_slope)

    def plan_key(self, batch: int, dtype: str, platform: str):
        """The autotuner :class:`~repro.tune.PlanKey` of this layer —
        the single source the tuner's zoo entry points key plans on."""
        from repro.tune.planner import PlanKey
        return PlanKey(kind=self.kind, batch=int(batch),
                       in_spatial=self.in_spatial, kernel=self.kernel,
                       strides=self.strides, paddings=self.paddings,
                       cin=self.cin, cout=self.cout, dtype=dtype,
                       platform=platform, **self.epilogue.key_fields())

    def geometry_signature(self) -> tuple:
        """The layer's workload identity (everything but the resolved
        execution) — what a program file must match to serve a config."""
        return (self.name, self.kind, self.in_spatial, self.kernel,
                self.strides, self.paddings, self.cin, self.cout,
                self.bias, self.activation, self.leaky_slope)

    def describe(self) -> str:
        sp = "x".join(map(str, self.in_spatial))
        k = "x".join(map(str, self.kernel))
        s = "x".join(map(str, self.strides))
        exec_ = self.backend
        if self.blocks:
            exec_ += f"[{'x'.join(map(str, self.blocks))}]"
        us = "" if self.measured_us is None \
            else f"  {self.measured_us:.0f}us"
        shard = "" if self.sharding == "data" else f"  @{self.sharding}"
        return (f"{self.name}: {self.kind} {sp} k{k} s{s} "
                f"{self.cin}->{self.cout}  ep[{self.epilogue.describe()}]"
                f"  -> {exec_}{shard}  ({self.source}{us})")

    def to_json(self) -> dict:
        d = {f.name: getattr(self, f.name)
             for f in dataclasses.fields(self)}
        d["blocks"] = list(self.blocks) if self.blocks else None
        for f in ("in_spatial", "kernel", "strides", "paddings"):
            d[f] = list(d[f])
        return d

    @classmethod
    def from_json(cls, d: dict) -> "LayerExec":
        names = {f.name for f in dataclasses.fields(cls)}
        # measured_us and sharding are optional on input: version-1
        # documents predate sharding and default to "data"
        if not (names - {"measured_us", "sharding"} <= set(d) <= names):
            raise ValueError(f"bad layer fields: {sorted(d)}")
        d = dict(d)
        for f in ("in_spatial", "kernel", "strides", "paddings"):
            d[f] = tuple(int(v) for v in d[f])
        for f in ("cin", "cout"):
            d[f] = int(d[f])
        if d.get("blocks") is not None:
            d["blocks"] = tuple(int(v) for v in d["blocks"])
        le = cls(**d)
        # the epilogue/kind/source checks ran in __post_init__; now the
        # executable part: the backend must exist, run this rank, and
        # (for the kernel backends) accept the recorded tile shapes
        if le.backend not in available_backends():
            raise ValueError(f"unknown backend {le.backend!r} in layer "
                             f"{le.name!r}")
        if not backend_supports(le.backend, le.nd):
            raise ValueError(f"backend {le.backend!r} does not support "
                             f"{le.nd}-D layer {le.name!r}")
        if le.blocks is not None:
            if not le.backend.startswith("pallas"):
                raise ValueError(f"layer {le.name!r} carries blocks on "
                                 f"non-kernel backend {le.backend!r}")
            if not blocks_valid(le.kind, le.in_spatial, le.kernel,
                                le.strides, le.paddings, le.cin, le.cout,
                                le.blocks):
                raise ValueError(f"stale blocks {le.blocks} for layer "
                                 f"{le.name!r}")
        return le


@dataclasses.dataclass(frozen=True)
class ProgramSpec:
    """A frozen, fully resolved execution plan for one GAN network.

    ``batch`` is the *planning* batch — the batch size the autotuner
    plans were keyed on; the runtime accepts any batch (a new batch
    shape is just a retrace of the same frozen records).  ``platform``
    records where the spec was resolved (provenance — a pinned program
    executes its recorded backends wherever it loads).
    ``requested_backend`` preserves the policy form the spec was built
    from (``None`` = heuristic), purely for display.

    ``mesh`` freezes the device layout the program was resolved for:
    ``(data, model)`` device counts over the ``("data", "model")`` axes
    of :func:`repro.launch.mesh.make_local_mesh`, or ``None`` for a
    single-device program.  The mesh is a property of the *program*,
    not the call site — an exported meshed spec serves identically on
    any box with enough devices, and degrades (with a warning) to
    single-device where there aren't.  It is provenance-like but
    executable, so it is excluded from :meth:`geometry_signature`: a
    meshed program still serves the same workload.

    ``dtype`` is the **storage** precision (one of
    :data:`repro.quant.SUPPORTED_STORAGE_DTYPES`; accumulation is
    always f32 — see :mod:`repro.quant`).  Unlike the mesh it *is*
    part of the geometry signature: a bf16 program computes a
    different function than the f32 one, so a file at the wrong
    precision must not serve a config.  ``quantized_params`` is the
    optional embedded int8 weight payload of an exported quantized
    program (the v3 JSON form; see
    :func:`repro.quant.weights.quantize_program`) — ``None`` for
    ordinary specs, whose params live with the caller.

    ``input_kind`` is the input signature (:data:`INPUT_KINDS`): a
    ``"latent"`` generator takes ``(B, z_dim)`` through its projection;
    an ``"image"`` generator (an encoder-decoder) and every
    discriminator take ``(B, *layers[0].in_spatial, layers[0].cin)``
    (:attr:`input_shape`).  ``None`` picks the role's historic default
    (latent generator, image discriminator), which is also how a file
    written before the field reads.
    """

    model: str
    role: str                       # "generator" | "discriminator"
    batch: int
    z_dim: int | None               # latent generator programs only
    channel_scale: float
    dtype: str
    platform: str
    requested_backend: str | None
    layers: tuple[LayerExec, ...]
    mesh: tuple[int, int] | None = None
    quantized_params: dict | None = None
    input_kind: str | None = None

    def __post_init__(self):
        from repro.quant.precision import canonical_dtype
        if self.role not in ROLES:
            raise ValueError(f"unknown program role {self.role!r}; "
                             f"one of {ROLES}")
        if self.input_kind is None:
            object.__setattr__(self, "input_kind",
                               "latent" if self.role == "generator"
                               else "image")
        if self.input_kind not in INPUT_KINDS:
            raise ValueError(f"unknown program input {self.input_kind!r}; "
                             f"one of {INPUT_KINDS}")
        if self.input_kind == "latent" and (self.role != "generator"
                                            or self.z_dim is None):
            raise ValueError(f"a latent input needs a generator with a "
                             f"z_dim (role={self.role!r}, "
                             f"z_dim={self.z_dim!r})")
        # canonicalize ("bf16" → "bfloat16") and reject non-storage
        # dtypes before they leak into plan keys or serialized files
        object.__setattr__(self, "dtype", canonical_dtype(self.dtype))
        if not self.layers:
            raise ValueError("a program needs at least one layer")
        if self.mesh is not None:
            if (len(self.mesh) != 2
                    or any(not isinstance(v, int) or v < 1
                           for v in self.mesh)):
                raise ValueError(f"mesh must be two positive ints "
                                 f"(data, model), got {self.mesh!r}")
        if self.quantized_params is not None:
            # hard-validates scheme/records/payload sizes — a corrupt
            # quantized file must raise at load (where loaders degrade
            # to fresh resolution), never at first trace
            from repro.quant.weights import validate_quantized
            validate_quantized(self.quantized_params)
        model_dim = self.mesh[1] if self.mesh else 1
        for le in self.layers:
            if le.sharding == "cout":
                if model_dim <= 1:
                    raise ValueError(
                        f"layer {le.name!r} is Cout-sharded but the "
                        f"program mesh {self.mesh!r} has no model axis")
                if le.cout % model_dim:
                    raise ValueError(
                        f"layer {le.name!r} cout={le.cout} does not "
                        f"divide over model axis of {model_dim}")

    # -- construction -------------------------------------------------------
    @classmethod
    def build(cls, cfg, batch: int, role: str = "generator", *,
              policy: DataflowPolicy | None = None, planner=None,
              measure: bool = False, dtype: str | None = None,
              mesh=_UNSET, cout_shard_min_bytes: int | None = None
              ) -> "ProgramSpec":
        """Walk ``cfg``'s layers once and freeze every resolution.

        ``policy`` defaults to ``cfg.policy``.  With
        ``backend="auto"`` each layer consults the autotuning planner
        (``planner`` or the process-wide one); ``measure=True``
        additionally tunes plan misses — the ahead-of-time analogue of
        the old per-call warmup, and the only place measurement belongs.

        ``dtype`` is the storage precision (default: ``cfg.dtype``,
        float32 for configs without the field).  It enters every
        layer's plan key — each precision is its own tuning workload —
        and the sharding footprint heuristic (half-width weights clear
        the Cout threshold half as often).

        ``mesh`` freezes a ``(data, model)`` device layout into the
        spec (default: ``cfg.mesh``; pass ``None`` explicitly to force
        single-device).  Each layer's sharding is chosen by the
        footprint heuristic in
        :func:`repro.core.dataflow.choose_layer_sharding`
        (``cout_shard_min_bytes`` overrides its threshold — tests use
        ``0`` to force Cout sharding on small configs).
        """
        from repro.models.gan import (discriminator_epilogues,
                                      generator_epilogues)
        from repro.quant.precision import canonical_dtype
        if role not in ROLES:
            raise ValueError(f"unknown program role {role!r}; "
                             f"one of {ROLES}")
        policy = policy or cfg.policy
        dtype = canonical_dtype(
            getattr(cfg, "dtype", "float32") if dtype is None else dtype)
        if mesh is _UNSET:
            mesh = getattr(cfg, "mesh", None)
        if mesh is not None:
            mesh = (int(mesh[0]), int(mesh[1]))
        mesh_model = mesh[1] if mesh else 1
        g_layers, d_layers = cfg.layers
        if role == "generator":
            layers, prefix = g_layers, "t"
            epilogues = generator_epilogues(g_layers)
        else:
            layers, prefix = d_layers, "c"
            epilogues = discriminator_epilogues(d_layers)
        input_kind = cfg.input_kind if role == "generator" else "image"
        records = []
        with _obs.trace("program.build", model=cfg.name, role=role,
                        batch=int(batch), measure=bool(measure),
                        layers=len(layers), input=input_kind):
            for i, (l, ep) in enumerate(zip(layers, epilogues)):
                kind = "tconv" if l.transposed else "conv"
                res = resolve_execution(
                    policy, kind, l.in_spatial, l.kernel, l.strides,
                    l.paddings, l.cin, l.cout, batch=batch, dtype=dtype,
                    epilogue=ep, planner=planner, measure=measure,
                    mesh_model=mesh_model,
                    cout_shard_min_bytes=cout_shard_min_bytes)
                records.append(LayerExec(
                    name=l.name, kind=kind,
                    in_spatial=tuple(l.in_spatial),
                    kernel=tuple(l.kernel),
                    strides=tuple(l.strides), paddings=tuple(l.paddings),
                    cin=int(l.cin), cout=int(l.cout),
                    w_param=f"{prefix}{i}_w",
                    b_param=f"{prefix}{i}_b" if ep.bias else None,
                    bias=ep.bias, activation=ep.activation,
                    leaky_slope=ep.leaky_slope,
                    backend=res.backend, blocks=res.blocks,
                    source=res.source, measured_us=res.measured_us,
                    sharding=res.sharding))
        _obs.counter("program.builds").inc()
        return cls(model=cfg.name, role=role, batch=int(batch),
                   z_dim=int(cfg.z_dim) if input_kind == "latent" else None,
                   channel_scale=float(cfg.channel_scale), dtype=dtype,
                   platform=jax.default_backend(),
                   requested_backend=policy.backend,
                   layers=tuple(records), mesh=mesh,
                   input_kind=input_kind)

    # -- queries ------------------------------------------------------------
    @property
    def input_shape(self) -> tuple[int, ...]:
        """The input per sample: ``(z_dim,)`` for a latent, else the
        first layer's ``(*in_spatial, cin)``."""
        if self.input_kind == "latent":
            return (self.z_dim,)
        first = self.layers[0]
        return first.in_spatial + (first.cin,)

    def plan_keys(self) -> list[tuple[str, object]]:
        """(layer name, :class:`~repro.tune.PlanKey`) per layer — what
        the tuner's zoo entry points iterate instead of re-deriving
        layer groups themselves."""
        return [(le.name, le.plan_key(self.batch, self.dtype,
                                      self.platform))
                for le in self.layers]

    def geometry_signature(self) -> tuple:
        """The whole network's workload identity: a loaded spec whose
        signature differs from a freshly built one is stale (topology,
        scaling, or **storage-precision** drift) and must not serve.
        The storage dtype is part of the identity — a bf16 program
        computes a different function than the f32 one — while the
        mesh and the quantized payload are not (they change where/how
        the same function runs, not what it computes... up to the
        checked-in quantization tolerance)."""
        return (self.model, self.role, self.input_kind, self.z_dim,
                self.dtype, tuple(le.geometry_signature()
                                  for le in self.layers))

    def summary(self) -> str:
        """One-line resolution summary (the repr-sized form of
        :meth:`describe`)."""
        if self.requested_backend == "auto":
            per_layer = ", ".join(
                f"{le.name}->{le.backend}"
                + (f"[{'x'.join(map(str, le.blocks))}]" if le.blocks
                   else "")
                for le in self.layers)
            return f"auto({per_layer})"
        backends = sorted({le.backend for le in self.layers})
        return backends[0] if len(backends) == 1 \
            else f"mixed({', '.join(backends)})"

    def describe(self) -> str:
        """The human-readable program listing: header plus one line per
        frozen layer record."""
        mesh = "" if self.mesh is None else \
            f"mesh={self.mesh[0]}x{self.mesh[1]}  "
        quant = "" if self.quantized_params is None else "quant=int8  "
        head = (f"program {self.model}/{self.role}  "
                f"input={self.input_kind}  "
                f"batch={self.batch}  dtype={self.dtype}  {quant}"
                f"platform={self.platform}  {mesh}"
                f"policy={self.requested_backend or 'heuristic'}  "
                f"({len(self.layers)} layers)")
        return "\n".join([head] + [f"  {le.describe()}"
                                   for le in self.layers])

    # -- persistence --------------------------------------------------------
    def to_json(self) -> dict:
        doc = {
            "version": PROGRAM_FORMAT_VERSION,
            "model": self.model, "role": self.role, "batch": self.batch,
            "input_kind": self.input_kind,
            "z_dim": self.z_dim, "channel_scale": self.channel_scale,
            "dtype": self.dtype, "platform": self.platform,
            "requested_backend": self.requested_backend,
            "layers": [le.to_json() for le in self.layers],
            "mesh": list(self.mesh) if self.mesh else None,
        }
        if self.quantized_params is not None:
            doc["quantized_params"] = self.quantized_params
        return doc

    @classmethod
    def from_json(cls, doc: dict) -> "ProgramSpec":
        if not isinstance(doc, dict):
            raise ValueError(f"program doc must be a dict, got "
                             f"{type(doc).__name__}")
        version = doc.get("version")
        if version not in SUPPORTED_PROGRAM_VERSIONS:
            raise ValueError(f"unsupported program version "
                             f"{version!r} "
                             f"(want one of {SUPPORTED_PROGRAM_VERSIONS})")
        layers = doc.get("layers")
        if not isinstance(layers, list) or not layers:
            raise ValueError("program doc has no 'layers' list")
        # version-gated defaults: v1 documents predate the mesh fields
        # and mean a single-device program; v1/v2 predate the storage-
        # precision and quantization fields and mean plain float32
        mesh = doc.get("mesh") if version >= 2 else None
        if mesh is not None:
            if not isinstance(mesh, (list, tuple)) or len(mesh) != 2:
                raise ValueError(f"program mesh must be [data, model], "
                                 f"got {mesh!r}")
            mesh = (int(mesh[0]), int(mesh[1]))
        dtype = str(doc.get("dtype", "float32")) if version >= 3 \
            else "float32"
        quantized = doc.get("quantized_params") if version >= 3 else None
        z_dim = doc.get("z_dim")
        # the input signature is optional: a file without it was written
        # before image-input generators and holds the role's default
        input_kind = doc.get("input_kind")
        return cls(model=str(doc["model"]), role=str(doc["role"]),
                   batch=int(doc["batch"]),
                   z_dim=None if z_dim is None else int(z_dim),
                   channel_scale=float(doc.get("channel_scale", 1.0)),
                   dtype=dtype,
                   platform=str(doc.get("platform", "cpu")),
                   requested_backend=doc.get("requested_backend"),
                   layers=tuple(LayerExec.from_json(d) for d in layers),
                   mesh=mesh, quantized_params=quantized,
                   input_kind=None if input_kind is None
                   else str(input_kind))

    def save(self, path) -> None:
        """Atomically write the spec's JSON document to ``path``."""
        path = os.fspath(path)
        tmp = f"{path}.tmp.{os.getpid()}"
        parent = os.path.dirname(os.path.abspath(path))
        os.makedirs(parent, exist_ok=True)
        with open(tmp, "w") as f:
            json.dump(self.to_json(), f, indent=2, sort_keys=True)
            f.write("\n")
        os.replace(tmp, path)

    @classmethod
    def load(cls, path) -> "ProgramSpec":
        """Read + validate a spec JSON file (raises on corrupt/stale —
        use :func:`repro.program.load_or_build` for the degrading
        form)."""
        with open(path) as f:
            return cls.from_json(json.load(f))
