"""Reduce one profiler trace to the numbers the per-layer metrics read.

The profiler writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``
and, beside it, ``<host>.trace.json.gz``, its own export of the same
events with each XLA op's ``hlo_category``.  The reduction reads the
op intervals and the host's annotations from the ``.xplane.pb`` and the
categories from the export:

* device busy time: the union of the intervals in which an XLA op ran,
  per device, inside the traced window, averaged over the devices;
* device time per op, and per class of op: an op is of the conv class
  when its category is a convolution (XLA on the TPU lowers a dot to a
  convolution too) or it is a Pallas kernel (``tpu_custom_call``);
* idle gaps: the stretches of the window in which no op ran, each named
  by the ``bench.*`` annotation the host was in at the gap's middle.

The traced window is the span of the ``bench.window`` annotation when
the trace holds one, else the span of the device ops.
"""

from __future__ import annotations

import bisect
import collections
import dataclasses
import gzip
import json
import pathlib

__all__ = ["Summary", "reduce", "find_run"]

WINDOW = "bench.window"
PREFIX = "bench."
CONV_CATEGORIES = ("convolution",)
KERNEL_MARK = "tpu_custom_call"


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float                 # averaged over the devices
    devices: int
    op_seconds: dict[str, float]  # "op (category)" -> device seconds
    class_total: dict[str, float]  # "conv" / "other" -> device seconds
    gaps: list[tuple[str, float]]  # (host annotation, seconds), longest first

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def class_seconds(self, cls: str) -> float:
        """Device seconds of one op class, averaged over the devices like
        ``busy_s``."""
        return self.class_total.get(cls, 0.0) / self.devices

    def top_ops(self, n: int = 10) -> list[list]:
        ranked = sorted(self.op_seconds.items(), key=lambda kv: -kv[1])
        return [[op, t / self.devices] for op, t in ranked[:n]]


def find_run(log_dir) -> pathlib.Path:
    """The newest profile run directory under a ``start_trace`` dir."""
    runs = sorted(pathlib.Path(log_dir).glob("plugins/profile/*"))
    if not runs:
        raise FileNotFoundError(f"no profile under {log_dir}")
    return runs[-1]


def _short(long_name: str) -> str:
    head = long_name.split(" = ", 1)[0]
    return head.lstrip("%")


def _categories(run: pathlib.Path) -> dict[str, str]:
    """An op's full HLO text -> its ``hlo_category``, from the profiler's
    export (keyed by the full text: short names repeat across the
    executables of one trace)."""
    out: dict[str, str] = {}
    for path in run.glob("*.trace.json.gz"):
        with gzip.open(path, "rt") as f:
            events = json.load(f).get("traceEvents", [])
        for ev in events:
            args = ev.get("args") or {}
            if ev.get("ph") == "X" and "hlo_category" in args:
                out[args.get("long_name", ev.get("name", ""))] = \
                    args["hlo_category"]
    return out


def _union(intervals: list[tuple[float, float]]) -> list[list[float]]:
    merged: list[list[float]] = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return merged


def _clip(intervals, lo: float, hi: float) -> list[list[float]]:
    return [[max(a, lo), min(b, hi)] for a, b in intervals
            if b > lo and a < hi]


def reduce(run_dir) -> Summary:
    """Reduce the profile run in ``run_dir`` (a ``plugins/profile/<time>``
    directory, or the ``start_trace`` directory above it)."""
    from jax.profiler import ProfileData

    run = pathlib.Path(run_dir)
    if not list(run.glob("*.xplane.pb")):
        run = find_run(run)
    categories = _categories(run)
    device_ops: dict[str, list[tuple[float, float, str]]] = \
        collections.defaultdict(list)
    host: list[tuple[float, float, str]] = []
    for path in run.glob("*.xplane.pb"):
        data = ProfileData.from_file(str(path))
        for plane in data.planes:
            if plane.name.startswith("/device:TPU:"):
                for line in plane.lines:
                    if line.name != "XLA Ops":
                        continue
                    for ev in line.events:
                        start = ev.start_ns * 1e-9
                        device_ops[plane.name].append(
                            (start, start + ev.duration_ns * 1e-9, ev.name))
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    for ev in line.events:
                        if ev.name.startswith(PREFIX):
                            start = ev.start_ns * 1e-9
                            host.append((start,
                                         start + ev.duration_ns * 1e-9,
                                         ev.name))
    if not device_ops:
        raise ValueError(f"no TPU ops in the trace under {run}")

    windows = [(a, b) for a, b, name in host if name == WINDOW]
    if windows:
        lo, hi = min(a for a, _ in windows), max(b for _, b in windows)
    else:
        lo = min(a for ops in device_ops.values() for a, _, _ in ops)
        hi = max(b for ops in device_ops.values() for _, b, _ in ops)

    op_seconds: dict[str, float] = collections.Counter()
    class_total: dict[str, float] = collections.Counter()
    busy, idle = 0.0, []
    for ops in device_ops.values():
        for a, b, name in ops:
            a, b = max(a, lo), min(b, hi)
            if b > a:
                category = categories.get(name, "unknown")
                conv = (any(c in category for c in CONV_CATEGORIES)
                        or KERNEL_MARK in name)
                op_seconds[f"{_short(name)} ({category})"] += b - a
                class_total["conv" if conv else "other"] += b - a
        merged = _clip(_union([(a, b) for a, b, _ in ops]), lo, hi)
        busy += sum(b - a for a, b in merged)
        edges = [lo] + [x for ab in merged for x in ab] + [hi]
        idle += [(edges[i], edges[i + 1])
                 for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]

    inner = sorted((a, b, n) for a, b, n in host if n != WINDOW)
    starts = [a for a, _, _ in inner]

    def doing(t: float) -> str:
        # the latest-started annotation still open at t
        i = bisect.bisect_right(starts, t)
        for a, b, name in reversed(inner[max(0, i - 64):i]):
            if b >= t:
                return name
        return "host.other"

    gaps = sorted(((doing((a + b) / 2), b - a) for a, b in idle),
                  key=lambda g: -g[1])
    return Summary(window_s=hi - lo, busy_s=busy / len(device_ops),
                   devices=len(device_ops), op_seconds=dict(op_seconds),
                   class_total=dict(class_total), gaps=gaps)
