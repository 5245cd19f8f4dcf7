"""Opt-in bridge from the obs tracer to the JAX/XLA profiler.

``obs.profile(outdir)`` wraps ``jax.profiler.start_trace`` /
``stop_trace`` around a code region (the resulting TensorBoard/Perfetto
dump shows the *device*-side timeline) inside an ``obs.profile`` span.
While obs tracing is enabled, every obs span (this one included) is
also a ``jax.profiler.TraceAnnotation`` of its name, so the program's
spans appear in the profile's host plane on the device trace's clock,
beside the device ops they dispatched.

It degrades to host-side-only behavior when the profiler is
unavailable (no jax, or a backend without profiling support): the obs
span still records, the device trace is skipped with an error attr —
observability must never take the workload down.
"""

from __future__ import annotations

import contextlib

from repro.obs import tracer as _tracer

__all__ = ["profile"]


@contextlib.contextmanager
def profile(outdir):
    """Context manager: capture a JAX profiler trace of the region into
    ``outdir`` (viewable in TensorBoard / Perfetto) inside an
    ``obs.profile`` span; with obs enabled, the obs spans opened in the
    region are in the profile's host plane too."""
    started = False
    err = None
    try:
        import jax
        jax.profiler.start_trace(str(outdir))
        started = True
    except Exception as e:    # no jax / unsupported backend
        err = f"{type(e).__name__}: {e}"
    span = _tracer.trace("obs.profile", outdir=str(outdir),
                         device_trace=started)
    if err is not None:
        span.attrs["error"] = err
    with span:
        try:
            yield
        finally:
            if started:
                import jax
                jax.profiler.stop_trace()
