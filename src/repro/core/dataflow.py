"""Unified dataflow dispatch for the GANAX (transposed-)convolution ops.

This module is the single entry point to every executable dataflow in the
repo.  It owns three things:

1. **Backend registry** — the four execution paths (``pallas-tpu``,
   ``pallas-interpret``, ``polyphase``, ``zero-insert``) are registered
   :class:`Backend` objects; callers select them with one
   :class:`DataflowPolicy` value (auto-selection by platform/ndim, an
   explicit override, or a per-call escape hatch) instead of the old
   scattered ``use_pallas``/``force_pallas`` booleans.

2. **μop compilation cache** — the static "μop compilation" stage
   (``PhaseSchedule`` construction, tap tables, per-phase weight-gather
   indices, the uniform padding plan) is pure geometry and used to be
   recomputed on every trace.  :func:`compile_uops` /
   :func:`compile_conv_uops` hoist it behind an LRU cache keyed on
   ``(in_spatial, kernel, strides, paddings)`` returning frozen numpy
   artifacts, so repeated layers and re-traces (train step, serve engine,
   benchmark sweeps) pay the scheduler once.

3. **Custom VJP** — on the kernel backends, :func:`tconv` /
   :func:`conv` carry a ``jax.custom_vjp`` exploiting the conv/tconv
   adjoint duality: the input-cotangent of a stride-``s`` transposed
   conv is a stride-``s`` plain conv with channel-swapped kernel (and
   vice versa), so the input-gradient re-enters the *same* unified
   kernel with a derived schedule (the weight gradient is a dense
   tap-indexed contraction with no inserted zeros, computed on the XLA
   path — see ``_tconv_wgrad``).  This makes the Pallas kernel (which
   has no autodiff rule) trainable, and keeps zero-skipping in both the
   forward and backward passes.  The pure-JAX backends keep XLA's
   native autodiff, which is already fused (and, for polyphase,
   already zero-skipping — the backward of a phase conv is a phase
   conv, that of the packed form two stride-1 convs).  One exception
   on polyphase: a strided conv with a lane-sparse Cin carries a
   ``jax.custom_jvp`` whose input gradient is the packed tconv
   (``_conv_packed_dx``); it too differentiates to any order.

4. **Fused epilogue** — an :class:`Epilogue` (bias add + activation)
   is a first-class argument of :func:`tconv` / :func:`conv`.  On the
   kernel backends it executes inside the Pallas accumulator flush, so
   the raw accumulator never round-trips through HBM just to have two
   elementwise ops applied; the pure-JAX backends apply the identical
   epilogue after the op (XLA fuses it natively), keeping all four
   backends bit-comparable.  Fused configs stay trainable: the fused
   custom VJP recovers the activation derivative from the *saved
   output* (``relu``/``leaky_relu``/``tanh`` are all invertible-slope
   activations) and reduces the pre-activation cotangent into the bias
   gradient, so no pre-activation tensor is ever materialized.

Geometry semantics are PyTorch ``ConvTranspose`` / correlation-conv
throughout (channels-last ``x``, ``(K..., Cin, Cout)`` weights), matching
``core.tconv`` and ``core.scheduler``.
"""

from __future__ import annotations

import dataclasses
import functools
import warnings
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.custom_derivatives import SymbolicZero

from repro import obs as _obs
from repro.core.scheduler import PhaseSchedule, make_schedule
from repro.core.tconv import tconv_ganax, tconv_taps, tconv_zero_insert

__all__ = [
    "Backend",
    "DataflowPolicy",
    "Epilogue",
    "Resolution",
    "ACTIVATIONS",
    "SHARDINGS",
    "COUT_SHARD_MIN_BYTES",
    "choose_layer_sharding",
    "pallas_kernel_supported",
    "backend_supports",
    "blocks_valid",
    "resolve_execution",
    "CompiledUops",
    "ConvUops",
    "register_backend",
    "available_backends",
    "compile_uops",
    "compile_conv_uops",
    "uop_cache_info",
    "uop_cache_clear",
    "tconv",
    "conv",
    "SecondOrderNotImplemented",
]


# ---------------------------------------------------------------------------
# Fused epilogue spec.
# ---------------------------------------------------------------------------

ACTIVATIONS = ("none", "relu", "leaky_relu", "tanh")


@dataclasses.dataclass(frozen=True)
class Epilogue:
    """Per-layer epilogue fused into the unified (t)conv op.

    ``bias`` adds a per-output-channel bias vector (supplied as the
    ``bias=`` argument of :func:`tconv` / :func:`conv`); ``activation``
    is applied after it.  On the kernel backends both run inside the
    Pallas accumulator flush; the pure-JAX backends apply :meth:`apply`
    after the op, so every backend computes the same function.

    The spec is hashable (safe as a static jit / ``custom_vjp`` nondiff
    argument and as part of an autotuner plan key).  ``leaky_slope`` is
    canonicalized to the default for non-leaky activations so two specs
    that compute the same function compare (and hash) equal.
    """

    bias: bool = False
    activation: str = "none"
    leaky_slope: float = 0.2

    def __post_init__(self):
        if self.activation not in ACTIVATIONS:
            raise ValueError(f"unknown epilogue activation "
                             f"{self.activation!r}; one of {ACTIVATIONS}")
        slope = 0.2 if self.activation != "leaky_relu" \
            else float(self.leaky_slope)
        if not slope >= 0:
            # grad_from_output recovers the leaky derivative from the
            # output's sign, which requires a sign-preserving slope
            raise ValueError(f"leaky_slope must be >= 0, got {slope}")
        object.__setattr__(self, "leaky_slope", slope)

    @property
    def is_identity(self) -> bool:
        return not self.bias and self.activation == "none"

    def apply(self, y: jax.Array, bias: jax.Array | None = None
              ) -> jax.Array:
        """Reference (pure-JAX) application — the function the kernel
        backends fuse into their flush step.

        Computed in float32 and cast back to ``y.dtype``, mirroring the
        kernels' f32-accumulator flush: with low-precision storage the
        bias add and activation never run in the narrow type (a bf16
        ``y + f32 bias`` would otherwise also silently promote the
        layer output to f32).  Bit-neutral for f32 inputs."""
        dt = y.dtype
        y = y.astype(jnp.float32)
        if self.bias:
            y = y + bias.astype(jnp.float32)
        if self.activation == "relu":
            y = jax.nn.relu(y)
        elif self.activation == "leaky_relu":
            y = jax.nn.leaky_relu(y, self.leaky_slope)
        elif self.activation == "tanh":
            y = jnp.tanh(y)
        return y.astype(dt)

    def grad_from_output(self, y: jax.Array) -> jax.Array:
        """The activation derivative recovered from the saved *output*
        ``y = act(z)`` — every supported activation is sign-preserving
        (relu/leaky) or smoothly invertible (tanh: act' = 1 - y²), so
        the fused VJP never needs the pre-activation tensor."""
        if self.activation == "relu":
            return (y > 0).astype(y.dtype)
        if self.activation == "leaky_relu":
            return jnp.where(y > 0, jnp.ones_like(y),
                             jnp.asarray(self.leaky_slope, y.dtype))
        if self.activation == "tanh":
            return 1.0 - jnp.square(y)
        return jnp.ones_like(y)

    def key_fields(self) -> dict:
        """The epilogue's contribution to an autotuner plan key."""
        return {"bias": self.bias, "activation": self.activation,
                "leaky_slope": self.leaky_slope}

    def describe(self) -> str:
        parts = []
        if self.activation != "none":
            parts.append(self.activation
                         if self.activation != "leaky_relu"
                         else f"leaky_relu({self.leaky_slope:g})")
        if self.bias:
            parts.append("bias")
        return "+".join(parts) or "none"


_IDENTITY_EPILOGUE = Epilogue()


def _canonical_epilogue(epilogue: Epilogue | None,
                        bias: jax.Array | None, w: jax.Array
                        ) -> Epilogue:
    """Validate the (epilogue, bias) pair of one dispatch; a bare
    ``bias=`` array with no epilogue means a plain fused bias add."""
    if epilogue is None:
        epilogue = Epilogue(bias=True) if bias is not None \
            else _IDENTITY_EPILOGUE
    if epilogue.bias and bias is None:
        raise ValueError("epilogue.bias=True but no bias= array passed")
    if not epilogue.bias and bias is not None:
        raise ValueError("bias= passed but epilogue.bias=False")
    if bias is not None and tuple(bias.shape) != (w.shape[-1],):
        raise ValueError(f"bias must have shape (cout,)=({w.shape[-1]},), "
                         f"got {tuple(bias.shape)}")
    return epilogue


# ---------------------------------------------------------------------------
# μop compilation cache (frozen static artifacts, keyed on geometry).
# ---------------------------------------------------------------------------

def _frozen(a: np.ndarray) -> np.ndarray:
    a = np.ascontiguousarray(a)
    a.setflags(write=False)
    return a


@dataclasses.dataclass(frozen=True)
class CompiledUops:
    """Frozen static schedule artifacts for one tconv geometry.

    ``schedule`` serves every backend; the remaining fields are the
    kernel-ready "local μop buffer" contents for the 2-D and 3-D Pallas
    paths (``None`` for other ranks): flattened tap tables, per-phase
    weight-gather indices, and the uniform input padding plan.
    ``tap_dz`` is the depth offset table of the volumetric kernel and is
    ``None`` for 2-D geometries.
    """

    schedule: PhaseSchedule
    # -- Pallas prep (2-D / 3-D) --------------------------------------------
    n_taps: np.ndarray | None       # (P,)
    tap_dy: np.ndarray | None       # (P, T)
    tap_dx: np.ndarray | None       # (P, T)
    k_idx: np.ndarray | None        # (P, T) flattened kernel tap index
    valid: np.ndarray | None        # (P, T) tap-validity mask
    pad: tuple[tuple[int, int], ...] | None   # per-spatial-dim input padding
    q_sizes: tuple[int, ...] | None           # phase-plane grid (ceil(out/s))
    tap_dz: np.ndarray | None = None          # (P, T), 3-D only


@dataclasses.dataclass(frozen=True)
class ConvUops:
    """Frozen single-phase (SIMD-mode) tables for a plain strided conv.
    ``tap_dz`` is ``None`` for 2-D geometries."""

    out_sizes: tuple[int, ...]
    n_taps: np.ndarray              # (1,)
    tap_dy: np.ndarray              # (1, prod(kernel))
    tap_dx: np.ndarray              # (1, prod(kernel))
    pad: tuple[tuple[int, int], ...]
    tap_dz: np.ndarray | None = None    # (1, prod(kernel)), 3-D only


@functools.lru_cache(maxsize=512)
def compile_uops(in_spatial: tuple[int, ...], kernel: tuple[int, ...],
                 strides: tuple[int, ...], paddings: tuple[int, ...]
                 ) -> CompiledUops:
    """Run the static μop compilation once per layer geometry."""
    sched = make_schedule(in_spatial, kernel, strides, paddings)
    nd = sched.n_dims
    if nd not in (2, 3):
        return CompiledUops(schedule=sched, n_taps=None, tap_dy=None,
                            tap_dx=None, k_idx=None, valid=None, pad=None,
                            q_sizes=None)
    tables = sched.tap_tables()
    tap_off = tables["tap_dx"]          # (P, T, nd)
    tap_k = tables["tap_k"]             # (P, T, nd)
    n_taps = tables["n_taps"]           # (P,)
    t_max = tap_off.shape[1]

    # Row-major flattened kernel tap index over all spatial dims.
    k_idx = tap_k[..., 0]
    for d in range(1, nd):
        k_idx = k_idx * kernel[d] + tap_k[..., d]             # (P, T)
    valid = np.arange(t_max)[None, :] < n_taps[:, None]
    k_idx = np.where(valid, k_idx, 0)

    # Uniform padding, extended so every (offset + q) window slice stays
    # in bounds (the kernel walks phase planes with unit window stride).
    q_sizes = tuple(-(-o // s) for o, s in zip(sched.out_sizes, strides))
    upad = sched.uniform_padding()
    pad = []
    for d in range(nd):
        lo, hi = upad[d]
        need = int(tap_off[..., d].max()) + (q_sizes[d] - 1) + 1
        extent = in_spatial[d] + lo + hi
        pad.append((lo, hi + max(0, need - extent)))
    offs = {f"tap_d{ax}": _frozen(tap_off[..., d])
            for d, ax in enumerate("zyx"[-nd:])}
    return CompiledUops(
        schedule=sched,
        n_taps=_frozen(n_taps),
        k_idx=_frozen(k_idx.astype(np.int32)),
        valid=_frozen(valid),
        pad=tuple(pad),
        q_sizes=q_sizes,
        **offs,
    )


@functools.lru_cache(maxsize=512)
def compile_conv_uops(in_spatial: tuple[int, ...],
                      kernel: tuple[int, ...], strides: tuple[int, ...],
                      paddings: tuple[int, ...]) -> ConvUops:
    """Single-phase tap tables for a 2-D/3-D plain conv (the paper's SIMD
    mode: one microprogram whose taps are the full kernel)."""
    nd = len(in_spatial)
    if not pallas_kernel_supported(nd):
        raise ValueError(f"conv μop tables exist only for the kernel's "
                         f"spatial ranks (2-D/3-D), got {nd}-D")
    out_sizes = tuple((i + 2 * p - k) // s + 1
                      for i, k, s, p in zip(in_spatial, kernel, strides,
                                            paddings))
    t_max = int(np.prod(kernel))
    taps = np.stack([np.asarray(u, np.int32)
                     for u in np.ndindex(*kernel)])       # (T, nd)
    pad = tuple(
        (p, max(0, (k - 1) + (q - 1) * s + 1 - (i + p)))
        for i, k, s, p, q in zip(in_spatial, kernel, strides, paddings,
                                 out_sizes))
    offs = {f"tap_d{ax}": _frozen(taps[None, :, d])
            for d, ax in enumerate("zyx"[-nd:])}
    return ConvUops(out_sizes=out_sizes,
                    n_taps=_frozen(np.asarray([t_max], np.int32)),
                    pad=pad, **offs)


def uop_cache_info() -> dict[str, int]:
    """Aggregate hit/miss counters over both μop caches."""
    a, b = compile_uops.cache_info(), compile_conv_uops.cache_info()
    return {"hits": a.hits + b.hits, "misses": a.misses + b.misses,
            "currsize": a.currsize + b.currsize}


def uop_cache_clear() -> None:
    compile_uops.cache_clear()
    compile_conv_uops.cache_clear()


# Observers (the train loop's end-of-run stats, ``obs.collect``) read
# the μop-cache efficiency through the obs registry instead of poking
# this module's privates.
_obs.register_collector("dataflow.uop_cache", uop_cache_info)


# ---------------------------------------------------------------------------
# Backend registry.
# ---------------------------------------------------------------------------

def _any_rank(nd: int) -> bool:
    return True


@dataclasses.dataclass(frozen=True)
class Backend:
    """One executable dataflow: a tconv and a conv implementation.

    ``tconv`` / ``conv`` take ``(x, w, strides, paddings)`` (plus the
    resolved ``interpret`` flag for kernel backends) and return the output;
    ``supports`` gates dispatch on the spatial rank.
    """

    name: str
    tconv: Callable[..., jax.Array]
    conv: Callable[..., jax.Array]
    supports: Callable[[int], bool] = _any_rank


_BACKENDS: dict[str, Backend] = {}


def register_backend(backend: Backend) -> None:
    _BACKENDS[backend.name] = backend


def available_backends() -> tuple[str, ...]:
    return tuple(sorted(_BACKENDS))


def backend_supports(name: str, nd: int) -> bool:
    """True when registered backend ``name`` executes ``nd``-spatial ops
    (used by the autotuner's candidate enumerator and plan validation)."""
    b = _BACKENDS.get(name)
    return b is not None and b.supports(nd)


def pallas_kernel_supported(nd: int) -> bool:
    """Spatial ranks the Pallas kernel implements (single source of
    truth for both dispatch and the ops-level guards): planar (2-D) and
    volumetric (3-D) layers."""
    return nd in (2, 3)


def _conv_dense(x, w, strides, paddings):
    from repro.kernels.ref import conv_ref
    return conv_ref(x, w, strides, paddings)


# The MXU's and the vector unit's lane width.
_LANES = 128


def _packs_phases(channels: int, strides: Sequence[int]) -> bool:
    """Whether a strided transposed conv with ``channels`` output
    channels takes the packed form.  The phase convs read all of x once
    per phase, and with few output channels each makes few MACs per
    element of x it reads, so they are bound by those reads; the packed
    form reads x once.  Taken for a lane-sparse output (even its phases
    packed into channels fill no lane tile) with 8 phases or more: a 3-D
    stride-2 layer.  A stride-1 layer has one phase and nothing to save.
    A 2-D stride-2 layer (4 phases) gains too, but keeps the phase convs
    until the benchmark's least-bytes count can measure it (PERF.md,
    Open question 9).  A conv's input gradient is the transposed conv
    whose output channels are the conv's input channels, so the conv
    asks about its Cin."""
    phases = int(np.prod(strides))
    return phases >= 8 and channels * phases < _LANES


def _tconv_polyphase(x, w, strides, paddings):
    nd = x.ndim - 2
    u = compile_uops(x.shape[1:1 + nd], w.shape[:nd], tuple(strides),
                     tuple(paddings))
    if _packs_phases(w.shape[-1], strides):
        _obs.counter("dataflow.tconv.taps").inc()
        return tconv_taps(x, w, strides, paddings, schedule=u.schedule)
    return tconv_ganax(x, w, strides, paddings, schedule=u.schedule)


def _crop_adjoint(dx_full, in_spatial, paddings):
    """The input cotangent of a conv from its uncropped adjoint: a conv
    with padding p reads input positions [-p, s·(Q-1)+K-1-p], so its
    adjoint is the tconv with padding 0 shifted by p, cropped to [0, I)
    with zero cotangent past the stride tail."""
    slc = [slice(None)]
    pad = [(0, 0)]
    for d, (i_d, p) in enumerate(zip(in_spatial, paddings)):
        slc.append(slice(p, p + i_d))
        pad.append((0, max(0, i_d - (dx_full.shape[1 + d] - p))))
    slc.append(slice(None))
    pad.append((0, 0))
    return jnp.pad(dx_full[tuple(slc)], pad)


def _conv_polyphase(x, w, strides, paddings):
    if _packs_phases(w.shape[-2], strides):
        _obs.counter("dataflow.conv.dx_taps").inc()
        return _conv_packed_dx(tuple(strides), tuple(paddings), x, w)
    return _conv_dense(x, w, strides, paddings)


@functools.partial(jax.custom_jvp, nondiff_argnums=(0, 1))
def _conv_packed_dx(strides, paddings, x, w):
    """A strided conv whose input gradient is the packed tconv
    (:func:`repro.core.tconv.tconv_taps`): one stride-1 conv into
    ``prod(s)·Cin`` phase channels and an interleave, where autodiff of
    the strided conv would zero-insert the cotangent and write ``Cin``
    lane-sparse channels.  The forward pass and the weight gradient are
    the plain conv's.  A JVP, not a VJP: its x-tangent map is the
    transpose of the packed input gradient, so reverse mode transposes it
    back into the packed form, and derivatives of any order, forward mode
    included, stay native."""
    return _conv_dense(x, w, strides, paddings)


@functools.partial(_conv_packed_dx.defjvp, symbolic_zeros=True)
def _conv_packed_dx_jvp(strides, paddings, primals, tangents):
    x, w = primals
    x_dot, w_dot = tangents
    y = _conv_dense(x, w, strides, paddings)
    nd = x.ndim - 2
    terms = []
    if not isinstance(x_dot, SymbolicZero):
        u = compile_uops(y.shape[1:1 + nd], w.shape[:nd], strides, (0,) * nd)

        def packed_dx(g):
            dx_full = tconv_taps(g, _swap_io(w), strides, (0,) * nd,
                                 schedule=u.schedule)
            return _crop_adjoint(dx_full, x.shape[1:1 + nd], paddings)

        conv_x, = jax.linear_transpose(
            packed_dx, jax.ShapeDtypeStruct(y.shape, y.dtype))(x_dot)
        terms.append(conv_x)
    if not isinstance(w_dot, SymbolicZero):
        terms.append(_conv_dense(x, w_dot, strides, paddings))
    # JAX calls no rule when every tangent is zero, so terms is not empty
    return y, functools.reduce(jnp.add, terms)


def _pallas(interpret: bool, transposed: bool):
    def fn(x, w, strides, paddings, blocks=None, epilogue=None, bias=None):
        from repro.kernels.ops import ganax_conv, ganax_conv_transpose
        op = ganax_conv_transpose if transposed else ganax_conv
        return op(x, w, strides, paddings, interpret=interpret,
                  blocks=blocks, epilogue=epilogue, bias=bias)
    return fn


register_backend(Backend(
    name="zero-insert", tconv=tconv_zero_insert, conv=_conv_dense))
register_backend(Backend(
    name="polyphase", tconv=_tconv_polyphase, conv=_conv_polyphase))
register_backend(Backend(
    name="pallas-interpret", tconv=_pallas(True, True),
    conv=_pallas(True, False), supports=pallas_kernel_supported))
register_backend(Backend(
    name="pallas-tpu", tconv=_pallas(False, True),
    conv=_pallas(False, False), supports=pallas_kernel_supported))


# ---------------------------------------------------------------------------
# Policy.
# ---------------------------------------------------------------------------

def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


@dataclasses.dataclass(frozen=True)
class DataflowPolicy:
    """How to pick an execution path for the unified (t)conv ops.

    ``backend``:
      * ``None`` (heuristic) — polyphase on every platform and rank.
        The pure-JAX polyphase path compiles on the TPU for every
        Table-I layer, forward and backward, in f32 and bf16; the
        Pallas kernel does not yet (strided convs, bf16 tap offsets and
        3dgan g4's VMEM footprint are refused by the chip's compiler),
        so it is reached only by pinning it or through ``"pallas"``.
      * ``"auto"`` — measurement-driven: at dispatch time the op consults
        the autotuning :class:`repro.tune.Planner` for a plan keyed on
        (layer geometry, dtype, platform); a hit executes the measured
        best backend *and* its tuned Pallas block shapes, a miss falls
        back to the ``None`` heuristic.  The planner never measures at
        dispatch (dispatch may be inside a ``jit`` trace) — plans come
        from ``python -m repro.tune``, ``GanServer`` construction
        warmup, or an explicit ``Planner.plan`` call, persisted via the
        planner's JSON plan file.  Resolution order is therefore
        *pinned > auto(planned) > heuristic*.
      * ``"pallas"`` — the unified kernel, interpret off-TPU, with a
        polyphase fallback for ranks the kernel doesn't support (the
        legacy ``use_pallas=True`` behavior).
      * ``"pallas-tpu"`` / ``"pallas-interpret"`` / ``"polyphase"`` /
        ``"zero-insert"`` — that registered backend exactly (strict:
        unsupported rank raises).

    ``interpret`` requests the Pallas kernel in interpret (``True``) or
    compiled (``False``) mode regardless of platform; with an auto or
    ``"pallas"`` backend it implies the kernel, keeping the polyphase
    fallback for ranks the kernel doesn't support.  Combined with an
    explicitly pinned backend it must agree — a contradiction (e.g.
    ``backend="pallas-tpu", interpret=True``) raises.
    ``differentiable=True`` (default) guarantees gradients on every
    backend: the kernel backends — which have no autodiff rule — get the
    custom VJP; the pure-JAX backends keep XLA's native (already fused,
    and for polyphase already zero-skipping) autodiff.
    ``differentiable=False`` drops that guarantee for the kernel
    backends (forward-only serving/benchmark escape hatch).

    The policy is hashable, so it is safe as a static jit argument and as
    part of a ``custom_vjp`` nondiff argument.
    """

    backend: str | None = None
    interpret: bool | None = None
    differentiable: bool = True

    @classmethod
    def from_legacy(cls, dataflow: str = "ganax",
                    use_pallas: bool = False) -> "DataflowPolicy":
        """Interpret the historic ``GanConfig`` flag pair.  This is the
        only place the legacy booleans are given meaning.

        Deprecated: ``GanConfig(backend=...)`` (any registered backend
        name, ``"pallas"``, or ``"auto"``) is the supported knob; the
        legacy pair survives only for old configs and warns when set to
        a non-default value."""
        if dataflow != "ganax" or use_pallas:
            warnings.warn(
                "the legacy GanConfig dataflow=/use_pallas= fields are "
                "deprecated; select the execution path with "
                "GanConfig(backend=...) (a registered backend name, "
                "'pallas', or 'auto') instead",
                DeprecationWarning, stacklevel=3)
        if dataflow == "zero_insert":
            return cls(backend="zero-insert")
        if dataflow != "ganax":
            raise ValueError(f"unknown dataflow {dataflow!r}")
        return cls(backend="pallas") if use_pallas else \
            cls(backend="polyphase")

    def resolve(self, nd: int) -> str:
        """Pick the concrete backend name for an ``nd``-spatial op.

        Geometry-free resolution: ``backend="auto"`` reports the
        heuristic choice here (the planner needs full layer geometry,
        which only the dispatch functions have)."""
        name = self.backend
        if name == "auto":
            if self.interpret is not None:
                raise ValueError(
                    "interpret cannot be combined with backend='auto': "
                    "the planner owns the kernel-variant choice")
            name = None
        if self.interpret is not None and name is None:
            # an interpret request implies the Pallas kernel (with the
            # usual rank fallback), not whatever auto would pick
            name = "pallas"
        if name is None:
            name = "polyphase"
        elif name == "pallas":
            if pallas_kernel_supported(nd):
                name = "pallas-tpu" if _on_tpu() else "pallas-interpret"
            else:
                name = "polyphase"
        if self.interpret is not None:
            if self.backend in (None, "pallas"):
                # preference forms: the interpret request picks the
                # kernel variant (rank fallback to polyphase untouched)
                if name.startswith("pallas"):
                    name = ("pallas-interpret" if self.interpret
                            else "pallas-tpu")
            else:
                # explicit names are strict: a pinned backend that
                # disagrees with the interpret request is a
                # contradiction, not an override
                expected = ("pallas-interpret" if self.interpret
                            else "pallas-tpu")
                if name != expected:
                    raise ValueError(
                        f"interpret={self.interpret} contradicts "
                        f"backend={self.backend!r}")
        if name not in _BACKENDS:
            raise ValueError(f"unknown dataflow backend {name!r}; "
                             f"available: {available_backends()}")
        if not _BACKENDS[name].supports(nd):
            raise ValueError(f"backend {name!r} does not support "
                             f"{nd}-D spatial inputs")
        return name


# ---------------------------------------------------------------------------
# Unified ops + custom VJP.
# ---------------------------------------------------------------------------

class SecondOrderNotImplemented(NotImplementedError):
    pass


_SECOND_ORDER_MSG = (
    "second-order (and forward-mode) autodiff through the unified GANAX "
    "(t)conv op is not implemented on the kernel backends: their "
    "jax.custom_vjp defines a single backward pass, so grad-of-grad "
    "(hessian, etc.) would need derivatives of the Pallas kernel itself. "
    "Differentiate through a pure-JAX backend instead — "
    "DataflowPolicy(backend='polyphase') or 'zero-insert' keep XLA's "
    "native autodiff, which supports arbitrary-order derivatives.")


@functools.partial(jax.custom_jvp, nondiff_argnums=(0, 1, 2, 3, 4))
def _kernel_first_order(fn, strides, paddings, blocks, epilogue, x, w,
                        bias):
    """Run a kernel backend's op.  Kernel backends have no JVP rule, so
    this gives them one that raises: a JVP reaching the kernel means the
    custom VJP's single backward pass is itself being differentiated
    (grad-of-grad) or forward-mode is being applied.  Without it that
    surfaces as a bare NotImplementedError from deep inside pallas_call;
    raise the actionable error instead."""
    return fn(x, w, strides, paddings, blocks=blocks, epilogue=epilogue,
              bias=bias)


@_kernel_first_order.defjvp
def _kernel_first_order_jvp(fn, strides, paddings, blocks, epilogue,
                            primals, tangents):
    raise SecondOrderNotImplemented(_SECOND_ORDER_MSG)


def _run(backend: str, transposed: bool, x, w, strides, paddings,
         blocks=None, epilogue: Epilogue | None = None, bias=None):
    ep = epilogue or _IDENTITY_EPILOGUE
    b = _BACKENDS[backend]
    fn = b.tconv if transposed else b.conv
    if backend.startswith("pallas"):
        return _kernel_first_order(fn, strides, paddings, blocks,
                                   None if ep.is_identity else ep, x, w,
                                   bias)
    if blocks is not None:
        raise ValueError(f"blocks={blocks!r} only applies to the Pallas "
                         f"kernel backends, not {backend!r}")
    y = fn(x, w, strides, paddings)
    # Pure-JAX backends: the same epilogue, applied after the op — XLA
    # fuses it natively and keeps native autodiff through it.
    return y if ep.is_identity else ep.apply(y, bias)


@jax.custom_vjp
def _first_order_only(x):
    """Identity marking the custom-VJP cotangents: differentiating it
    (i.e. taking a second derivative of the unified op) raises instead of
    producing silently wrong higher-order terms."""
    return x


def _foo_fwd(x):
    return x, None


def _foo_bwd(_, g):
    raise SecondOrderNotImplemented(_SECOND_ORDER_MSG)


_first_order_only.defvjp(_foo_fwd, _foo_bwd)


def _swap_io(w: jax.Array) -> jax.Array:
    """(K..., Cin, Cout) → (K..., Cout, Cin): the adjoint's kernel."""
    return jnp.swapaxes(w, -1, -2)


def _flat_sp(a: jax.Array) -> jax.Array:
    """(N, *spatial, C) → (N, prod(spatial), C)."""
    return a.reshape(a.shape[0], -1, a.shape[-1])


def _tconv_wgrad(x, g, kernel, strides, paddings):
    """dL/dw for ``y = tconv(x, w)``:  dw[u,ci,co] = Σ_{n,i} x[n,i,ci] ·
    g[n, s·i + u - p, co] — a dense tap-indexed contraction with no
    inserted zeros (every product is a consequential MAC)."""
    nd = x.ndim - 2
    in_sp = x.shape[1:1 + nd]
    gp = jnp.pad(g, ((0, 0),) + tuple((p, p) for p in paddings) + ((0, 0),))
    xf = _flat_sp(x)
    rows = []
    for u in np.ndindex(*kernel):
        slc = (slice(None),) + tuple(
            slice(u[d], u[d] + strides[d] * (in_sp[d] - 1) + 1, strides[d])
            for d in range(nd)) + (slice(None),)
        rows.append(jnp.einsum("nsc,nso->co", xf, _flat_sp(gp[slc]),
                               preferred_element_type=jnp.float32))
    return jnp.stack(rows).reshape(tuple(kernel) + rows[0].shape)


def _conv_wgrad(x, g, kernel, strides, paddings):
    """dL/dw for ``y = conv(x, w)``:  dw[t,ci,co] = Σ_{n,q}
    x[n, s·q + t - p, ci] · g[n,q,co]."""
    nd = x.ndim - 2
    q_sp = g.shape[1:1 + nd]
    in_sp = x.shape[1:1 + nd]
    pad = []
    for d in range(nd):
        hi = strides[d] * (q_sp[d] - 1) + kernel[d] - 1 - paddings[d] \
            - (in_sp[d] - 1)
        pad.append((paddings[d], max(0, hi)))
    xp = jnp.pad(x, ((0, 0),) + tuple(pad) + ((0, 0),))
    gf = _flat_sp(g)
    rows = []
    for t in np.ndindex(*kernel):
        slc = (slice(None),) + tuple(
            slice(t[d], t[d] + strides[d] * (q_sp[d] - 1) + 1, strides[d])
            for d in range(nd)) + (slice(None),)
        rows.append(jnp.einsum("nsc,nso->co", _flat_sp(xp[slc]), gf,
                               preferred_element_type=jnp.float32))
    return jnp.stack(rows).reshape(tuple(kernel) + rows[0].shape)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3))
def _tconv_diff(backend, strides, paddings, blocks, x, w):
    return _run(backend, True, x, w, strides, paddings, blocks)


def _tconv_fwd(backend, strides, paddings, blocks, x, w):
    return _run(backend, True, x, w, strides, paddings, blocks), (x, w)


def _tconv_bwd(backend, strides, paddings, blocks, res, g):
    x, w = res
    # Adjoint duality: tconv(·, w) is the adjoint of conv(·, swap(w)), so
    # dx is a plain conv — same stride/padding, same backend, derived
    # (single-phase) schedule; zero-skipping is preserved because no
    # zero-inserted tensor is ever formed.  Tuned blocks describe the
    # *forward* geometry (the adjoint has its own phase-plane/channel
    # extents), so the backward pass uses the heuristic defaults.
    dx = _run(backend, False, g, _swap_io(w), strides, paddings)
    dw = _tconv_wgrad(x, g, w.shape[:x.ndim - 2], strides, paddings)
    return (_first_order_only(dx.astype(x.dtype)),
            _first_order_only(dw.astype(w.dtype)))


_tconv_diff.defvjp(_tconv_fwd, _tconv_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3))
def _conv_diff(backend, strides, paddings, blocks, x, w):
    return _run(backend, False, x, w, strides, paddings, blocks)


def _conv_fwd(backend, strides, paddings, blocks, x, w):
    return _run(backend, False, x, w, strides, paddings, blocks), (x, w)


def _conv_dx(backend, strides, paddings, x, w, g):
    """Input-cotangent of ``y = conv(x, w)``: a transposed conv (the
    multi-phase MIMD path), uncropped and then cropped by
    :func:`_crop_adjoint`."""
    nd = x.ndim - 2
    dx_full = _run(backend, True, g, _swap_io(w), strides, (0,) * nd)
    return _crop_adjoint(dx_full, x.shape[1:1 + nd], paddings)


def _conv_bwd(backend, strides, paddings, blocks, res, g):
    x, w = res
    dx = _conv_dx(backend, strides, paddings, x, w, g)
    dw = _conv_wgrad(x, g, w.shape[:x.ndim - 2], strides, paddings)
    return (_first_order_only(dx.astype(x.dtype)),
            _first_order_only(dw.astype(w.dtype)))


_conv_diff.defvjp(_conv_fwd, _conv_bwd)


# -- fused-epilogue variants -------------------------------------------------
#
# ``y = act(op(x, w) + b)`` on a kernel backend.  The forward runs the
# epilogue inside the Pallas flush; the backward recovers the activation
# derivative from the saved *output* (see ``Epilogue.grad_from_output``),
# folds it into the cotangent once, and then reuses the identity-epilogue
# machinery: dx re-enters the unified kernel through the adjoint duality,
# dw is the dense tap-indexed contraction, and db is a plain reduction of
# the pre-activation cotangent over every non-channel axis.

def _epilogue_cotangent(epilogue: Epilogue, y, g):
    return g if epilogue.activation == "none" \
        else g * epilogue.grad_from_output(y)


def _bias_grad(g_pre, bias):
    # f32 accumulation: the reduction spans batch x spatial elements,
    # far too many to sum in a 8/10-bit mantissa when g_pre is stored
    # low-precision (no-op for f32 cotangents)
    axes = tuple(range(g_pre.ndim - 1))
    return jnp.sum(g_pre, axis=axes,
                   dtype=jnp.float32).astype(bias.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3, 4))
def _tconv_ep_diff(backend, strides, paddings, blocks, epilogue, x, w, b):
    return _run(backend, True, x, w, strides, paddings, blocks,
                epilogue, b)


def _tconv_ep_fwd(backend, strides, paddings, blocks, epilogue, x, w, b):
    y = _run(backend, True, x, w, strides, paddings, blocks, epilogue, b)
    return y, (x, w, b, y)


def _tconv_ep_bwd(backend, strides, paddings, blocks, epilogue, res, g):
    x, w, b, y = res
    g_pre = _epilogue_cotangent(epilogue, y, g)
    dx = _run(backend, False, g_pre, _swap_io(w), strides, paddings)
    dw = _tconv_wgrad(x, g_pre, w.shape[:x.ndim - 2], strides, paddings)
    db = None if b is None else _first_order_only(_bias_grad(g_pre, b))
    return (_first_order_only(dx.astype(x.dtype)),
            _first_order_only(dw.astype(w.dtype)), db)


_tconv_ep_diff.defvjp(_tconv_ep_fwd, _tconv_ep_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3, 4))
def _conv_ep_diff(backend, strides, paddings, blocks, epilogue, x, w, b):
    return _run(backend, False, x, w, strides, paddings, blocks,
                epilogue, b)


def _conv_ep_fwd(backend, strides, paddings, blocks, epilogue, x, w, b):
    y = _run(backend, False, x, w, strides, paddings, blocks, epilogue, b)
    return y, (x, w, b, y)


def _conv_ep_bwd(backend, strides, paddings, blocks, epilogue, res, g):
    x, w, b, y = res
    # the shared _conv_bwd derivation, with the pre-activation cotangent
    # in place of g
    g_pre = _epilogue_cotangent(epilogue, y, g)
    dx = _conv_dx(backend, strides, paddings, x, w, g_pre)
    dw = _conv_wgrad(x, g_pre, w.shape[:x.ndim - 2], strides, paddings)
    db = None if b is None else _first_order_only(_bias_grad(g_pre, b))
    return (_first_order_only(dx.astype(x.dtype)),
            _first_order_only(dw.astype(w.dtype)), db)


_conv_ep_diff.defvjp(_conv_ep_fwd, _conv_ep_bwd)


@dataclasses.dataclass(frozen=True)
class Resolution:
    """One layer's fully resolved execution: the concrete backend, its
    Pallas tile shapes (``None`` = heuristic defaults or a pure-JAX
    backend), the provenance of that choice, and — when the layer is
    resolved for a device mesh — how it is laid out across it.

    ``source`` is one of ``"pinned"`` (the policy named a backend or the
    kernel preference explicitly), ``"tuned"`` (a measured autotuner
    plan), or ``"heuristic"`` (the platform default, including auto-plan
    misses).  ``sharding`` is one of :data:`SHARDINGS`: ``"data"``
    (batch split over the ``data`` mesh axis, weights replicated — the
    serving-throughput layout) or ``"cout"`` (weights and bias
    additionally sharded on Cout over the ``model`` axis; the layer's
    local output is all-gathered back to full Cout, no halo exchange
    needed because Cout is a pure output dimension).  This is the data
    form of dispatch — what :class:`repro.program.ProgramSpec` freezes
    ahead of time."""

    backend: str
    blocks: tuple[int, ...] | None = None
    source: str = "heuristic"
    measured_us: float | None = None
    sharding: str = "data"


# Per-layer mesh layouts a resolution can freeze (see Resolution).
SHARDINGS = ("data", "cout")

# The footprint heuristic's default threshold: a layer whose weight
# tensor is at least this many bytes goes Cout-model-parallel on a
# mesh with model > 1 (the big 3D-GAN tconvs: g1 is 4³·512·256·4B
# ≈ 34 MiB; the small 2-D generator tails stay data-parallel where an
# all-gather would cost more than the weight traffic it saves).
COUT_SHARD_MIN_BYTES = 16 * 1024 * 1024


def choose_layer_sharding(kernel: Sequence[int], cin: int, cout: int,
                          mesh_model: int, *,
                          min_bytes: int | None = None,
                          itemsize: int = 4) -> str:
    """The footprint heuristic picking one of :data:`SHARDINGS` for a
    layer resolved against a mesh with ``mesh_model`` devices on the
    ``model`` axis.

    ``"cout"`` (weights sharded on Cout, no halo exchange) is chosen
    only when the model axis is real (> 1), Cout divides it evenly, and
    the weight footprint ``prod(kernel)·cin·cout·itemsize`` reaches
    ``min_bytes`` (default :data:`COUT_SHARD_MIN_BYTES`) — the layers
    that outgrow a single device's memory/bandwidth.  ``itemsize`` is
    the *storage* dtype's (a bf16 program's weights are half the f32
    footprint, so fewer of its layers clear the sharding threshold).
    Everything else (including every layer of a mesh-less program) is
    ``"data"``."""
    if mesh_model <= 1 or cout % mesh_model != 0:
        return "data"
    threshold = COUT_SHARD_MIN_BYTES if min_bytes is None \
        else int(min_bytes)
    weight_bytes = int(np.prod(tuple(kernel))) * int(cin) * int(cout) \
        * int(itemsize)
    return "cout" if weight_bytes >= threshold else "data"


def blocks_valid(kind: str, in_spatial: Sequence[int],
                 kernel: Sequence[int], strides: Sequence[int],
                 paddings: Sequence[int], cin: int, cout: int,
                 blocks: Sequence[int]) -> bool:
    """True when ``blocks`` divides this geometry's kernel extents —
    a stale plan (or program) entry must degrade, never raise from
    inside a trace.  ``kind`` is ``"tconv"`` or ``"conv"``."""
    from repro.kernels.ops import resolve_blocks
    in_spatial, kernel = tuple(in_spatial), tuple(kernel)
    strides, paddings = tuple(strides), tuple(paddings)
    if not pallas_kernel_supported(len(in_spatial)):
        return False
    if kind == "conv":
        u = compile_conv_uops(in_spatial, kernel, strides, paddings)
        q_lead = u.out_sizes[:-1]
    else:
        u = compile_uops(in_spatial, kernel, strides, paddings)
        q_lead = u.q_sizes[:-1]
    try:
        resolve_blocks(tuple(blocks), q_lead, int(cin), int(cout))
    except ValueError:
        return False
    return True


def resolve_execution(policy: DataflowPolicy, kind: str,
                      in_spatial: Sequence[int], kernel: Sequence[int],
                      strides: Sequence[int], paddings: Sequence[int],
                      cin: int, cout: int, *, batch: int = 1,
                      dtype="float32", epilogue: Epilogue | None = None,
                      planner=None, measure: bool = False,
                      mesh_model: int = 1,
                      cout_shard_min_bytes: int | None = None
                      ) -> Resolution:
    """Resolve one layer's execution path **as data** — the single
    resolution routine behind both the per-call dispatch and the
    ahead-of-time :mod:`repro.program` builder.

    For a non-``auto`` policy this is just ``policy.resolve`` plus
    provenance.  ``backend="auto"`` consults the autotuning planner
    (``planner`` or the process-wide one) with the full layer geometry;
    a hit yields the measured backend + tuned Pallas blocks, with stale
    plans — unknown backend, unsupported rank, blocks that no longer
    divide the geometry — degrading to the heuristic rather than
    raising.  ``measure=True`` additionally tunes plan misses (never do
    this from dispatch: it may run inside a ``jit`` trace, where timing
    is meaningless — ahead-of-time builders only).

    ``mesh_model > 1`` resolves the layer against a device mesh with
    that many devices on the ``model`` axis: :func:`choose_layer_sharding`
    picks the layout (overridable threshold via
    ``cout_shard_min_bytes``), and tuned Pallas blocks that do not
    divide the *local* Cout shard of a ``"cout"`` layer are dropped
    (reason counter ``dataflow.resolve.shard_blocks``) — the kernel
    executes per-device on ``cout / mesh_model`` channels."""
    with _obs.trace("dataflow.resolve", kind=kind) as sp:
        res, reasons = _resolve_execution(
            policy, kind, in_spatial, kernel, strides, paddings, cin,
            cout, batch=batch, dtype=dtype, epilogue=epilogue,
            planner=planner, measure=measure)
        sharding = choose_layer_sharding(
            kernel, cin, cout, mesh_model,
            min_bytes=cout_shard_min_bytes,
            itemsize=np.dtype(str(dtype)).itemsize)
        if sharding != res.sharding:
            res = dataclasses.replace(res, sharding=sharding)
        if sharding == "cout" and res.blocks is not None and \
                not blocks_valid(kind, in_spatial, kernel, strides,
                                 paddings, cin, cout // mesh_model,
                                 res.blocks):
            res = dataclasses.replace(res, blocks=None)
            reasons.append("shard_blocks")
        sp.set(backend=res.backend, source=res.source)
    _obs.counter("dataflow.resolve").inc()
    _obs.counter(f"dataflow.resolve.{res.source}").inc()
    for reason in reasons:
        _obs.counter(f"dataflow.resolve.{reason}").inc()
    return res


def _resolve_execution(policy, kind, in_spatial, kernel, strides,
                       paddings, cin, cout, *, batch, dtype, epilogue,
                       planner, measure
                       ) -> tuple[Resolution, list[str]]:
    """Uninstrumented :func:`resolve_execution` body; the second return
    value lists the plan-cache outcomes (``plan_hit``/``plan_miss``/
    ``plan_measured``) and degradations (``stale_plan``/
    ``stale_blocks``) that explain the provenance."""
    nd = len(in_spatial)
    if policy.backend != "auto":
        source = "heuristic" if policy.backend is None \
            and policy.interpret is None else "pinned"
        return Resolution(policy.resolve(nd), None, source), []
    policy.resolve(nd)  # validates the interpret combination
    from repro.tune import get_planner
    from repro.tune.planner import PlanKey
    if planner is None:
        planner = get_planner()
    ep = epilogue or _IDENTITY_EPILOGUE
    key = PlanKey(kind=kind, batch=int(batch),
                  in_spatial=tuple(int(d) for d in in_spatial),
                  kernel=tuple(int(d) for d in kernel),
                  strides=tuple(int(s) for s in strides),
                  paddings=tuple(int(p) for p in paddings),
                  cin=int(cin), cout=int(cout),
                  dtype=str(jnp.dtype(dtype)),
                  platform=jax.default_backend(),
                  **ep.key_fields())
    # Plan-cache outcome classification must not issue extra planner
    # calls — test_program pins exact ``planner.lookups`` counts — so
    # hit/miss is inferred from the measurement delta / lookup result.
    if measure:
        measured_before = planner.measurements
        plan = planner.plan(key, measure=True)
        reasons = ["plan_measured" if planner.measurements
                   > measured_before else "plan_hit"]
    else:
        plan = planner.lookup(key)
        reasons = ["plan_hit" if plan is not None else "plan_miss"]
    if plan is not None and plan.backend in _BACKENDS and \
            _BACKENDS[plan.backend].supports(nd):
        blocks = plan.blocks if plan.backend.startswith("pallas") else None
        if blocks is not None and not blocks_valid(
                kind, key.in_spatial, key.kernel, key.strides,
                key.paddings, cin, cout, blocks):
            blocks = None   # stale blocks (geometry drift): keep the
            # planned backend, fall back to its default tile shapes
            reasons.append("stale_blocks")
        source = "tuned" if plan.source == "measured" else "heuristic"
        return Resolution(plan.backend, blocks, source,
                          plan.measured_us), reasons
    if plan is not None:
        reasons.append("stale_plan")    # unknown backend / bad rank
    heuristic = dataclasses.replace(policy, backend=None).resolve(nd)
    return Resolution(heuristic, None, "heuristic"), reasons


def _planned_dispatch(policy: DataflowPolicy, transposed: bool, x, w,
                      strides, paddings,
                      epilogue: Epilogue | None = None
                      ) -> tuple[str, tuple | None]:
    """Resolve (backend, blocks) for one dispatch — the per-call form of
    :func:`resolve_execution` (lookup only, never measures)."""
    nd = x.ndim - 2
    if policy.backend != "auto":
        return policy.resolve(nd), None
    res = resolve_execution(
        policy, "tconv" if transposed else "conv",
        tuple(int(d) for d in x.shape[1:1 + nd]),
        tuple(int(d) for d in w.shape[:nd]), strides, paddings,
        int(w.shape[-2]), int(w.shape[-1]), batch=int(x.shape[0]),
        dtype=x.dtype, epilogue=epilogue)
    return res.backend, res.blocks


def tconv(x: jax.Array, w: jax.Array, strides: Sequence[int],
          paddings: Sequence[int],
          policy: DataflowPolicy | None = None,
          blocks: Sequence[int] | None = None,
          bias: jax.Array | None = None,
          epilogue: Epilogue | None = None) -> jax.Array:
    """Transposed convolution through the unified GANAX dispatch.

    x: (N, *spatial, Cin) channels-last; w: (K..., Cin, Cout).
    ``blocks`` pins the Pallas kernel tile shapes — the
    (block_qy, block_cin, block_cout) triple for 2-D layers, the
    (block_qz, block_qy, block_cin, block_cout) quadruple for volumetric
    ones — the per-call escape hatch the autotuner measures through;
    with ``backend="auto"`` the planner's tuned blocks are used instead.

    ``epilogue`` fuses a bias add (``bias``: a (Cout,) vector, required
    iff ``epilogue.bias``) and activation into the op — inside the
    Pallas accumulator flush on the kernel backends, applied post-op on
    the pure-JAX ones; a bare ``bias=`` with no epilogue means a plain
    fused bias add.  Fused configs stay differentiable (the fused
    custom VJP differentiates through the epilogue).
    """
    return _dispatch(True, x, w, strides, paddings, policy, blocks,
                     bias, epilogue)


def conv(x: jax.Array, w: jax.Array, strides: Sequence[int],
         paddings: Sequence[int],
         policy: DataflowPolicy | None = None,
         blocks: Sequence[int] | None = None,
         bias: jax.Array | None = None,
         epilogue: Epilogue | None = None) -> jax.Array:
    """Plain (strided) convolution through the same dispatch — the paper's
    SIMD mode; on kernel backends it is the degenerate single-phase case
    of the very same Pallas kernel.  ``bias``/``epilogue`` as in
    :func:`tconv`."""
    return _dispatch(False, x, w, strides, paddings, policy, blocks,
                     bias, epilogue)


def _dispatch(transposed: bool, x, w, strides, paddings, policy, blocks,
              bias, epilogue) -> jax.Array:
    policy = policy or DataflowPolicy()
    strides, paddings = tuple(strides), tuple(paddings)
    epilogue = _canonical_epilogue(epilogue, bias, w)
    if blocks is not None:
        backend = policy.resolve(x.ndim - 2)
    else:
        backend, blocks = _planned_dispatch(policy, transposed, x, w,
                                            strides, paddings, epilogue)
    blocks = tuple(blocks) if blocks is not None else None
    if policy.differentiable and backend.startswith("pallas"):
        if epilogue.is_identity:
            op = _tconv_diff if transposed else _conv_diff
            return op(backend, strides, paddings, blocks, x, w)
        op = _tconv_ep_diff if transposed else _conv_ep_diff
        return op(backend, strides, paddings, blocks, epilogue, x, w,
                  bias)
    return _run(backend, transposed, x, w, strides, paddings, blocks,
                epilogue, bias)
