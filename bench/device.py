"""The device a run measures: its stamp and its peaks.

A run that finds no TPU, or fewer chips than its cell asks for, or a
device kind that the peaks table does not hold, stops here: nothing is
measured off the chip and no peak is guessed.
"""

from __future__ import annotations

import json
import pathlib

__all__ = ["DeviceError", "stamp", "peaks"]

PEAKS_FILE = pathlib.Path(__file__).resolve().parent / "peaks.json"


class DeviceError(RuntimeError):
    pass


def stamp(chips: int, devices=None) -> dict:
    """``{"platform", "kind", "count"}`` of the devices JAX sees (or of
    ``devices``); raises :class:`DeviceError` off the TPU or short of
    ``chips``."""
    if devices is None:
        import jax
        devices = jax.devices()
    first = devices[0]
    if first.platform != "tpu":
        raise DeviceError(f"no TPU: JAX runs on {first.platform!r} "
                          f"({first.device_kind}); nothing is measured "
                          f"off the chip")
    if len(devices) < chips:
        raise DeviceError(f"the cell asks for {chips} chips, JAX sees "
                          f"{len(devices)}")
    return {"platform": first.platform, "kind": first.device_kind,
            "count": len(devices)}


def peaks(kind: str, table: dict | None = None) -> dict:
    """The peaks of ``kind`` from ``peaks.json``; an unknown kind is an
    error, never a default."""
    if table is None:
        table = json.loads(PEAKS_FILE.read_text())
    try:
        return table["devices"][kind]
    except KeyError:
        raise DeviceError(f"device kind {kind!r} is not in the peaks "
                          f"table ({sorted(table['devices'])})") from None
