"""What the drivers share: the program's view of a configuration, the
host annotations, and a profiler over part of the window."""

from __future__ import annotations

import dataclasses
import gc
import resource
import shutil
import sys
import tempfile
import time

__all__ = ["Ctx", "program_config", "check_layers", "annotate", "Profile",
           "HostWatch", "say"]


@dataclasses.dataclass
class Ctx:
    cell: object         # cells.Cell
    seed: int
    seconds: float
    trace: bool
    t0: float            # process start, on time.perf_counter()
    log: object          # compile_log.CompileLog


def say(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


def annotate(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)


def program_config(cfg: dict, **overrides):
    """The program's ``GanConfig`` for a configuration file."""
    from repro.models.gan import GanConfig
    fields = {"name": cfg["model"], "z_dim": cfg["z_dim"],
              "dtype": cfg["dtype"],
              "channel_scale": cfg.get("channel_scale", 1.0)}
    fields.update(overrides)
    return GanConfig(**fields)


def check_layers(gcfg, cfg: dict) -> None:
    """Refuse to measure a program whose layers are not the ones the
    configuration file states (the work counts read the file)."""
    from bench.work import layer_count_check
    layer_count_check(cfg)
    g_layers, d_layers = gcfg.layers
    dims = cfg["dims"]
    stated = [lay for lay in cfg["generator"] if lay["kind"] != "dense"] \
        + cfg["discriminator"]
    for mine, theirs in zip(stated, list(g_layers) + list(d_layers)):
        got = {"in": theirs.in_spatial, "k": theirs.kernel,
               "s": theirs.strides, "p": theirs.paddings,
               "cin": theirs.cin, "cout": theirs.cout,
               "kind": "tconv" if theirs.transposed else "conv"}
        want = {"in": (mine["in"],) * dims, "k": (mine["k"],) * dims,
                "s": (mine["s"],) * dims, "p": (mine["p"],) * dims,
                "cin": mine["cin"], "cout": mine["cout"],
                "kind": mine["kind"]}
        if {k: tuple(v) if isinstance(v, tuple) else v
                for k, v in got.items()} != want:
            raise ValueError(f"{cfg['name']} {mine['name']}: the program "
                             f"builds {got}, the configuration states "
                             f"{want}")
    if len(stated) != len(g_layers) + len(d_layers):
        raise ValueError(f"{cfg['name']}: layer count differs from the "
                         f"program's")


class Profile:
    """A profiler trace of the window (``bench.window``), or of its first
    part, reduced after the window; the Python tracer stays off."""

    def __init__(self, on: bool):
        self.on = on
        self.dir = tempfile.mkdtemp(prefix="bench-trace-") if on else None
        self.started = self.window = None
        self.summary = None

    def start(self) -> None:
        if self.on:
            import jax
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            self.started = True

    def open_window(self) -> None:
        if self.started:
            self.window = annotate("bench.window")
            self.window.__enter__()

    def close_window(self) -> None:
        if self.window is not None:
            self.window.__exit__(None, None, None)
            self.window = None

    def stop(self) -> None:
        if self.started:
            import jax
            self.close_window()
            jax.profiler.stop_trace()
            self.started = False

    def reduce(self):
        """Reduce the stopped trace (after the window) and remove it."""
        if self.dir:
            from bench import trace
            t = time.perf_counter()
            try:
                self.summary = trace.reduce(self.dir)
            finally:
                shutil.rmtree(self.dir, ignore_errors=True)
                self.dir = None
            say(f"trace: reduced in {time.perf_counter() - t:.3f} s")
        return self.summary


class HostWatch:
    """What the host did in each step of the window, to tell a stall of
    the host from one of the device: when the step began, when its
    dispatch returned and when it was done, with the loop thread's
    involuntary context switches and the garbage collector's seconds so
    far.  ``report`` names the longest steps."""

    def __init__(self):
        self.marks = []
        self.gc_s, self.gc_runs, self._gc_t = 0.0, 0, None

    def _gc(self, phase, info):
        if phase == "start":
            self._gc_t = time.perf_counter()
        elif self._gc_t is not None:
            self.gc_s += time.perf_counter() - self._gc_t
            self.gc_runs += 1

    def __enter__(self):
        gc.callbacks.append(self._gc)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self._gc)

    def mark(self, began: float, dispatched: float, done: float) -> None:
        switches = resource.getrusage(resource.RUSAGE_THREAD).ru_nivcsw
        self.marks.append((began, dispatched, done, switches, self.gc_s))

    def report(self, n: int = 3) -> str:
        if not self.marks:
            return "host: no steps"
        rows, prev = [], self.marks[0]
        for i, mark in enumerate(self.marks):
            began, dispatched, done, switches, gc_s = mark
            rows.append((done - began, dispatched - began,
                         switches - prev[3], gc_s - prev[4], i))
            prev = mark
        took = sorted(r[0] for r in rows)
        longest = "; ".join(
            f"#{i} {t * 1e3:.3f} ms ({d * 1e3:.3f} dispatching, {sw} "
            f"involuntary switches, {g * 1e3:.3f} ms collecting)"
            for t, d, sw, g, i in sorted(rows, reverse=True)[:n])
        return (f"host: median step {took[len(took) // 2] * 1e3:.3f} ms; "
                f"longest {longest}; garbage collector {self.gc_runs} runs "
                f"{self.gc_s:.6f} s; involuntary switches "
                f"{self.marks[-1][3] - self.marks[0][3]}")
