"""The JAX APIs this repo uses that have moved between releases.

Every call site goes through this module, so a JAX upgrade is a
one-file change.  Written for the installed JAX (0.9):

* ``tpu_compiler_params`` — ``pltpu.CompilerParams``.
* ``shard_map`` — ``jax.shard_map`` with ``check_vma``.
* ``lower_as_mlir`` — ``pl.lower_as_mlir``: the Mosaic/TPU MLIR of a
  kernel call, for inspection from a host without a chip.
* ``CUSTOM_VJP_CALL`` — the jaxpr primitive name of a
  ``jax.custom_vjp`` call (``custom_vjp_call_jaxpr`` before 0.7).
"""

from __future__ import annotations

from typing import Sequence

import jax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["tpu_compiler_params", "shard_map", "lower_as_mlir",
           "CUSTOM_VJP_CALL"]

CUSTOM_VJP_CALL = "custom_vjp_call"


def tpu_compiler_params(*, dimension_semantics: Sequence[str]):
    """Pallas-TPU compiler params for a grid's dimension semantics."""
    return pltpu.CompilerParams(
        dimension_semantics=tuple(dimension_semantics))


def shard_map(f, *, mesh, in_specs, out_specs, check: bool = False):
    """``jax.shard_map`` with its replication (VMA) check toggled."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check)


def lower_as_mlir(f, *args) -> str:
    """Lower ``f(*args)`` for the real TPU target and return the MLIR text
    (works from a CPU host: the kernel must *lower*, not run)."""
    return str(pl.lower_as_mlir(f, *args))
