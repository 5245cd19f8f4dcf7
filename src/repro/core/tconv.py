"""GANAX transposed convolution: polyphase ("row-reorganized") dataflow.

Three executable forms are provided for N-D transposed convolution
(channels-last layout, PyTorch ``ConvTranspose`` geometry semantics):

* :func:`tconv_zero_insert` — the conventional-accelerator baseline the
  paper compares against: materialize the zero-inserted input and run a
  dense convolution over it.  Every inserted zero costs a MAC, exactly like
  running the layer on an unmodified EYERISS.

* :func:`tconv_ganax` — the paper's dataflow: output/filter rows are
  regrouped by zero-pattern (= polyphase decomposition, see
  ``core/scheduler.py``) so only consequential MACs are executed, each phase
  being a dense, fully-regular convolution (SIMD inside a phase, distinct
  microprograms across phases = MIMD-SIMD).

* :func:`tconv_taps` — every phase in one convolution: taps written
  ``t = s·j + r`` (phase ``r``, shift ``j``), one stride-1 conv over a
  window of ``ceil(k / s)`` shifts with the phases packed into its
  ``prod(s)·Cout`` output channels, then the phases interleaved.  Still
  GANAX zero-skipping, the same consequential MACs grouped into one
  conv instead of one per phase (a kernel whose size is not a multiple
  of the stride is padded with zero taps; no zoo layer has one).  It
  reads x once where the phase convs read it once per phase, which is
  what bounds a phase conv with few output channels.  The polyphase
  backend (``core/dataflow.py``) takes it for a lane-sparse output
  layer with 8 phases or more (3dgan g4), and for the input gradient
  of a conv with such an input (3dgan d1).

All produce bit-comparable results (up to dtype accumulation order) and
match ``jax.lax.conv_transpose``.
"""

from __future__ import annotations

import string
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core.scheduler import PhaseSchedule, make_schedule

__all__ = [
    "tconv_zero_insert",
    "tconv_ganax",
    "tconv_taps",
    "tconv_output_shape",
    "interleave_phases",
]


def _spatial_dims(x: jax.Array) -> int:
    # (N, *spatial, C)
    return x.ndim - 2


def _dim_numbers(nd: int):
    """Channels-last dimension numbers for an nd-spatial conv."""
    letters = "".join(c for c in string.ascii_uppercase if c not in "NCIO")
    sp = letters[:nd]                         # e.g. "AB"
    lhs = "N" + sp + "C"
    rhs = sp + "IO"
    out = "N" + sp + "C"
    return lax.conv_dimension_numbers((0,) * (nd + 2), (0,) * (nd + 2),
                                      (lhs, rhs, out))


def accum_conv(lhs: jax.Array, rhs: jax.Array, *, window_strides,
               padding, dimension_numbers,
               preferred_element_type=jnp.float32) -> jax.Array:
    """``conv_general_dilated`` with true f32 accumulation at any
    storage precision.

    Low-precision (bf16/f16) operands are **upcast** to the accumulator
    dtype instead of passing narrow inputs with
    ``preferred_element_type`` — the result is bit-identical (narrow
    floats are exactly representable in f32), but unlike the
    mixed-dtype form the conv's *transpose* is defined, so native
    autodiff through the pure-JAX backends works at every storage
    precision.  f32 operands pass through untouched."""
    if preferred_element_type is not None:
        acc = jnp.dtype(preferred_element_type)
        lhs, rhs = lhs.astype(acc), rhs.astype(acc)
    return lax.conv_general_dilated(
        lhs, rhs, window_strides=window_strides, padding=padding,
        dimension_numbers=dimension_numbers,
        preferred_element_type=preferred_element_type)


def tconv_output_shape(x_shape: Sequence[int], w_shape: Sequence[int],
                       strides: Sequence[int], paddings: Sequence[int]
                       ) -> tuple[int, ...]:
    """(N, *spatial_out, C_out) for channels-last x and (K..., C_in, C_out) w."""
    nd = len(x_shape) - 2
    sched = make_schedule(x_shape[1:1 + nd], w_shape[:nd], strides, paddings)
    return (x_shape[0], *sched.out_sizes, w_shape[-1])


# ---------------------------------------------------------------------------
# Baseline dataflow: explicit zero insertion + dense convolution.
# ---------------------------------------------------------------------------

def zero_insert(x: jax.Array, strides: Sequence[int]) -> jax.Array:
    """Materialize the zero-expanded input (size ``s*(n-1)+1`` per dim)."""
    nd = _spatial_dims(x)
    strides = tuple(strides)
    out_sp = tuple(s * (n - 1) + 1
                   for n, s in zip(x.shape[1:1 + nd], strides))
    out = jnp.zeros((x.shape[0], *out_sp, x.shape[-1]), x.dtype)
    idx = (slice(None),) + tuple(slice(None, None, s) for s in strides) + (
        slice(None),)
    return out.at[idx].set(x)


def tconv_zero_insert(x: jax.Array, w: jax.Array, strides: Sequence[int],
                      paddings: Sequence[int],
                      preferred_element_type=jnp.float32) -> jax.Array:
    """Transposed conv via the conventional dataflow (baseline).

    Args:
      x: (N, *spatial, C_in), channels last.
      w: (*kernel, C_in, C_out).
      strides/paddings: per-spatial-dim ints, PyTorch ``ConvTranspose``
        semantics (padding is the forward-conv padding being transposed).
    """
    nd = _spatial_dims(x)
    strides = tuple(strides)
    paddings = tuple(paddings)
    kernel = w.shape[:nd]
    expanded = zero_insert(x, strides)
    # Correlate with the *flipped* kernel; pad by (k - 1 - p) per side.
    w_flipped = jnp.flip(w, axis=tuple(range(nd)))
    pads = tuple((k - 1 - p, k - 1 - p) for k, p in zip(kernel, paddings))
    return accum_conv(
        expanded, w_flipped, window_strides=(1,) * nd, padding=pads,
        dimension_numbers=_dim_numbers(nd),
        preferred_element_type=preferred_element_type,
    ).astype(x.dtype)


# ---------------------------------------------------------------------------
# GANAX dataflow: polyphase decomposition (output/filter row reorganization).
# ---------------------------------------------------------------------------

def _phase_conv(x: jax.Array, w: jax.Array, sched: PhaseSchedule,
                flat_phase: int, preferred_element_type) -> jax.Array:
    """Dense sub-convolution for one phase (one GANAX microprogram)."""
    nd = sched.n_dims
    pds = sched.phase_dims(flat_phase)
    # Gather this phase's kernel taps, reversed so XLA's correlation
    # (out[q] = Σ_t rhs[t]·lhs[q + t - pad_lo]) realizes
    # out[q] = Σ_t w[tap_t]·x[q + offset - t].
    w_sub = w
    for d, pd in enumerate(pds):
        taps = np.asarray(pd.taps[::-1], dtype=np.int32)
        w_sub = jnp.take(w_sub, taps, axis=d)
    pads = []
    for d, pd in enumerate(pds):
        n, m = pd.n_taps, pd.offset
        in_size = sched.in_sizes[d]
        pad_lo = n - 1 - m
        pad_hi = pd.out_size - in_size + m
        pads.append((pad_lo, pad_hi))
    return accum_conv(
        x, w_sub, window_strides=(1,) * nd, padding=tuple(pads),
        dimension_numbers=_dim_numbers(nd),
        preferred_element_type=preferred_element_type)


def interleave_phases(phase_outs: dict[tuple[int, ...], jax.Array],
                      sched: PhaseSchedule) -> jax.Array:
    """Scatter phase planes into the full output (the "row reorganization"
    permutation applied in reverse).

    Phase planes are zero-padded to a common ``ceil(out/s)`` grid, stacked,
    and interleaved with a reshape — a pure layout transformation (XLA
    transpose), no arithmetic.
    """
    nd = sched.n_dims
    strides = sched.strides
    q_sizes = tuple(-(-o // s) for o, s in zip(sched.out_sizes, strides))
    # Build an array indexed [phase_0, ..., phase_{nd-1}, N, q_0, ..., q_{nd-1}, C]
    first = next(iter(phase_outs.values()))
    n, c = first.shape[0], first.shape[-1]
    dtype = first.dtype
    planes = np.empty(tuple(strides), dtype=object)
    for phases, out in phase_outs.items():
        pad = [(0, 0)]
        for d in range(nd):
            pad.append((0, q_sizes[d] - out.shape[1 + d]))
        pad.append((0, 0))
        planes[phases] = jnp.pad(out, pad)
    stacked = jnp.stack([planes[idx] for idx in np.ndindex(*strides)])
    stacked = stacked.reshape(tuple(strides) + (n, *q_sizes, c))
    # target order: (N, q_0, phase_0, q_1, phase_1, ..., C)
    perm = [nd]  # N
    for d in range(nd):
        perm.extend([nd + 1 + d, d])
    perm.append(2 * nd + 1)  # C
    inter = jnp.transpose(stacked, perm)
    full = inter.reshape((n,) + tuple(q * s for q, s in zip(q_sizes, strides))
                         + (c,))
    slc = (slice(None),) + tuple(slice(0, o) for o in sched.out_sizes) + (
        slice(None),)
    return full[slc]


def tconv_ganax(x: jax.Array, w: jax.Array, strides: Sequence[int],
                paddings: Sequence[int],
                preferred_element_type=jnp.float32,
                schedule: PhaseSchedule | None = None) -> jax.Array:
    """Transposed conv via the GANAX dataflow (pure-JAX reference).

    Executes only consequential MACs: one dense sub-convolution per output
    phase, then a zero-arithmetic interleave.  Stride 1 degenerates to a
    single plain convolution (paper's SIMD mode / discriminator path).
    """
    nd = _spatial_dims(x)
    strides = tuple(strides)
    paddings = tuple(paddings)
    sched = schedule or make_schedule(x.shape[1:1 + nd], w.shape[:nd],
                                      strides, paddings)
    outs = {}
    for flat in sched.phase_order:  # longest-microprogram-first
        phases = sched.phase_tuple(flat)
        pds = sched.phase_dims(flat)
        if any(pd.n_taps == 0 for pd in pds):
            # no consequential taps: this phase's outputs are all zero
            # (possible when kernel < stride)
            outs[phases] = jnp.zeros(
                (x.shape[0],) + tuple(pd.out_size for pd in pds)
                + (w.shape[-1],), x.dtype)
            continue
        outs[phases] = _phase_conv(x, w, sched, flat,
                                   preferred_element_type).astype(x.dtype)
    if sched.n_phases == 1:
        return outs[(0,) * nd]
    return interleave_phases(outs, sched)


def tconv_taps(x: jax.Array, w: jax.Array, strides: Sequence[int],
               paddings: Sequence[int],
               preferred_element_type=jnp.float32,
               schedule: PhaseSchedule | None = None) -> jax.Array:
    """Transposed conv via the GANAX dataflow with every phase in one conv.

    Write each kernel tap as ``t = s·j + r``: ``r`` is the output phase
    the tap lands on, ``j`` its shift within that phase.  One stride-1
    convolution over a window of ``J = ceil(k / s)`` shifts, with the
    phases packed into its ``prod(s)·Cout`` output channels, computes
    ``Z[n, u, (r, o)] = Σ_{j, c} x[n, u − j, c] · w[s·j + r, c, o]`` for
    every phase at once (x read once, not once per phase); the phases
    are then interleaved (``s·u + r``) and the padding cropped.  The
    same MACs as :func:`tconv_ganax` where ``kernel % stride == 0``;
    otherwise the kernel is padded with zero taps up to ``J·s``.
    """
    nd = _spatial_dims(x)
    strides = tuple(strides)
    sched = schedule or make_schedule(x.shape[1:1 + nd], w.shape[:nd],
                                      strides, tuple(paddings))
    n, in_sp, (cin, cout) = x.shape[0], x.shape[1:1 + nd], w.shape[-2:]
    shifts = tuple(-(-k // s) for k, s in zip(w.shape[:nd], strides))
    # (*k, Cin, Cout) → (J_0, s_0, J_1, s_1, …, Cin, Cout) → (J…, Cin,
    # (s…, Cout)), flipped over J: the conv correlates
    w = jnp.pad(w, [(0, j * s - k) for j, s, k in
                    zip(shifts, strides, w.shape[:nd])] + [(0, 0)] * 2)
    w = w.reshape(sum(zip(shifts, strides), ()) + (cin, cout))
    w = jnp.transpose(w, (*range(0, 2 * nd, 2), 2 * nd,
                          *range(1, 2 * nd, 2), 2 * nd + 1))
    w = jnp.flip(w.reshape(shifts + (cin, int(np.prod(strides)) * cout)),
                 tuple(range(nd)))
    with jax.named_scope("tconv.taps"):
        z = accum_conv(x, w, window_strides=(1,) * nd,
                       padding=tuple((j - 1, j - 1) for j in shifts),
                       dimension_numbers=_dim_numbers(nd),
                       preferred_element_type=preferred_element_type)
    with jax.named_scope("tconv.interleave"):
        # (N, U…, s…, Cout) → (N, U_0, s_0, U_1, s_1, …, Cout): output
        # position s·u + r of the uncropped transposed conv
        u_sp = tuple(i + j - 1 for i, j in zip(in_sp, shifts))
        z = z.reshape((n, *u_sp, *strides, cout))
        z = jnp.transpose(z, (0, *(a for d in range(nd)
                                   for a in (1 + d, 1 + nd + d)),
                              1 + 2 * nd))
        z = z.reshape((n, *(u * s for u, s in zip(u_sp, strides)), cout))
        out = lax.pad(z, jnp.zeros((), z.dtype),
                      [(0, 0, 0), *((-p, o + p - u * s, 0) for p, o, u, s
                                    in zip(paddings, sched.out_sizes,
                                           u_sp, strides)),
                       (0, 0, 0)])
    return out.astype(x.dtype)
