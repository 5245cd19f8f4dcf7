"""repro.obs: span tracer semantics (nesting, jit interaction, the
disabled no-op pin), metrics registry (histogram percentiles vs a numpy
reference, labels, collectors), export round-trips, and the
instrumentation acceptance paths (serve spans/latency, program --stats).
"""

import bisect
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.obs.metrics import Histogram, Registry

REPO = Path(__file__).resolve().parent.parent


def _cli_env() -> dict:
    env = dict(os.environ, PYTHONPATH="src", JAX_PLATFORMS="cpu")
    env.pop("REPRO_OBS", None)      # the CLIs under test run untraced
    return env


@pytest.fixture(autouse=True)
def _tracing_off():
    """Every test starts and ends with tracing disabled (the process
    default); sinks created mid-test are dropped, never flushed."""
    obs.disable()
    yield
    obs.disable()


# ---------------------------------------------------------------------------
# Span tracer.
# ---------------------------------------------------------------------------

def test_span_nesting_depth_and_close_order():
    sink = obs.enable()
    with obs.trace("outer", a=1):
        with obs.trace("inner"):
            pass
        with obs.trace("inner2"):
            pass
    spans = sink.spans()
    # spans are emitted as they close: children before the parent
    assert [s["name"] for s in spans] == ["inner", "inner2", "outer"]
    assert {s["name"]: s["depth"] for s in spans} == \
        {"outer": 0, "inner": 1, "inner2": 1}
    inner, inner2, outer = spans
    assert outer["attrs"] == {"a": 1}
    assert outer["ts_us"] <= inner["ts_us"]
    assert inner["ts_us"] <= inner2["ts_us"]
    assert outer["dur_us"] >= inner["dur_us"] + inner2["dur_us"] - 1e-3
    assert obs.tracer.current_depth() == 0          # stack fully popped


def test_span_mid_attrs_error_attr_and_decorator():
    sink = obs.enable()
    with obs.trace("s") as sp:
        sp.set(found=3)
    assert sink.spans("s")[0]["attrs"] == {"found": 3}

    with pytest.raises(ValueError):
        with obs.trace("boom"):
            raise ValueError("x")
    assert sink.spans("boom")[0]["attrs"]["error"] == "ValueError"

    @obs.trace("deco", kind="fn")
    def g(v):
        return v + 1

    assert g(1) == 2
    assert sink.spans("deco")[0]["attrs"] == {"kind": "fn"}
    obs.disable()
    assert g(2) == 3                                # inert when disabled
    assert len(sink.spans("deco")) == 1


def test_span_inside_jit_fires_once_at_trace_time():
    """A span in a jitted function records trace time exactly once —
    it can never fire inside the compiled computation."""
    sink = obs.enable()

    @jax.jit
    def f(x):
        with obs.trace("jit.body"):
            return x * 2.0

    for i in range(4):
        f(jnp.float32(i)).block_until_ready()
    assert len(sink.spans("jit.body")) == 1


def test_disabled_is_a_no_op_and_jaxpr_identical():
    sink = obs.enable()
    obs.disable()
    with obs.trace("x", a=1) as sp:
        sp.set(b=2)
    obs.event("y", n=3)
    assert len(sink) == 0                           # zero sink writes
    assert not obs.is_enabled()

    # spans are host-side only: the traced computation is identical
    # with tracing on or off
    def f(x):
        with obs.trace("span.inside", k="v"):
            return jnp.sin(x) + 1.0

    x = jnp.arange(4.0)
    jaxpr_off = str(jax.make_jaxpr(f)(x))
    obs.enable()
    jaxpr_on = str(jax.make_jaxpr(f)(x))
    assert jaxpr_on == jaxpr_off


# ---------------------------------------------------------------------------
# Metrics.
# ---------------------------------------------------------------------------

def test_counter_gauge_labels_and_snapshot():
    reg = Registry()
    reg.counter("hits").inc()
    reg.counter("hits").inc(2)                      # get-or-create
    assert reg.counter("hits").value == 3
    assert reg.counter("hits", server="a") is not reg.counter("hits")
    reg.counter("hits", server="a").inc(5)
    reg.gauge("depth").set(7)
    snap = reg.snapshot()
    assert snap["counters"] == {"hits": 3, "hits{server=a}": 5}
    assert snap["gauges"] == {"depth": 7.0}
    with pytest.raises(ValueError):
        reg.counter("hits").inc(-1)
    with pytest.raises(TypeError):
        reg.gauge("hits")                           # kind collision


def test_histogram_percentiles_vs_numpy():
    rng = np.random.default_rng(7)
    data = rng.uniform(10.0, 1e5, size=4000)
    h = Histogram("lat_us")
    for v in data:
        h.observe(v)
    assert h.count == len(data)
    assert np.isclose(h.sum, data.sum())
    for p in (0, 50, 90, 99, 100):
        ref = float(np.percentile(data, p))
        got = h.percentile(p)
        # error bounded by the containing bucket's width
        i = bisect.bisect_left(h.bounds, ref)
        lo = data.min() if i == 0 else h.bounds[i - 1]
        hi = data.max() if i == len(h.bounds) else h.bounds[i]
        assert abs(got - ref) <= (hi - lo), (p, got, ref)
        assert data.min() <= got <= data.max()
    assert set(h.percentiles()) == {"p50", "p90", "p99"}


def test_histogram_edge_cases():
    h = Histogram("h", bounds=(1.0, 2.0, 4.0))
    assert np.isnan(h.percentile(50))               # empty
    h.observe(3.0)
    assert h.percentile(50) == 3.0                  # single → clamped
    h.observe(100.0)                                # overflow bucket
    assert h.count == 2
    assert 4.0 < h.percentile(100) <= 100.0         # clamped to max
    with pytest.raises(ValueError):
        Histogram("bad", bounds=(2.0, 1.0))
    with pytest.raises(ValueError):
        h.percentile(101)


def test_collectors_return_copies():
    reg = Registry()
    live = {"hits": 1}
    reg.register_collector("src", lambda: live)
    reg.register_collector("dead", lambda: None)    # source not alive
    out = reg.collect()
    assert out == {"src": {"hits": 1}}
    out["src"]["hits"] = 99                         # mutate the copy
    assert live["hits"] == 1                        # original untouched


def test_process_collectors_registered():
    import repro.core.dataflow  # noqa: F401
    import repro.tune  # noqa: F401
    stats = obs.collect()
    assert "dataflow.uop_cache" in stats
    assert {"hits", "misses"} <= set(stats["dataflow.uop_cache"])


# ---------------------------------------------------------------------------
# Export round-trips and the CLI.
# ---------------------------------------------------------------------------

def test_jsonl_trace_event_roundtrip(tmp_path):
    sink = obs.enable()
    with obs.trace("a", k="v"):
        obs.event("e", n=1)
    obs.counter("c", model="dcgan").inc(4)
    obs.histogram("h").observe(12.5)
    obs.flush_metrics()
    records = list(sink.records)

    back = obs.from_trace_events(obs.to_trace_events(records))
    want = [r for r in records if r["type"] in ("span", "event")]
    got = [r for r in back if r["type"] in ("span", "event")]
    assert got == want                              # lossless
    # the flush carries the whole (process-wide) registry; pick out the
    # metrics this test created
    c = next(r for r in back if r.get("kind") == "counter"
             and r["name"] == "c")
    assert c["value"] >= 4 and c["labels"] == {"model": "dcgan"}
    hist = next(r for r in back if r.get("kind") == "histogram"
                and r["name"] == "h")
    assert hist["count"] >= 1

    jl, te = tmp_path / "t.jsonl", tmp_path / "t.trace.json"
    obs.write_jsonl(records, jl)
    obs.write_trace_events(records, te)
    assert obs.read_records(jl) == records          # format sniffing
    doc = json.loads(te.read_text())
    assert all("ph" in e for e in doc["traceEvents"])
    assert [r for r in obs.read_records(te) if r["type"] == "span"] \
        == [r for r in records if r["type"] == "span"]

    text = obs.summarize(records)
    assert "a" in text and "c{model=dcgan}" in text and "p50" in text


def test_jsonl_sink_live_file_and_env_opt_in(tmp_path):
    path = tmp_path / "run.jsonl"
    obs.enable(str(path))
    with obs.trace("s"):
        pass
    obs.flush_metrics()
    obs.disable()
    records = obs.read_records(path)
    assert records[0]["type"] == "header"
    assert any(r["type"] == "span" and r["name"] == "s"
               for r in records)


def test_obs_cli_summarize_and_convert(tmp_path):
    src = tmp_path / "run.jsonl"
    obs.write_jsonl([{"type": "span", "name": "x", "ts_us": 1.0,
                      "dur_us": 5.0, "tid": 0, "depth": 0,
                      "attrs": {}}], src)
    out = tmp_path / "out.trace.json"
    r = subprocess.run(
        [sys.executable, "-m", "repro.obs", str(src),
         "--perfetto", str(out)],
        capture_output=True, text=True, cwd=str(REPO), env=_cli_env())
    assert r.returncode == 0, r.stderr
    assert "1 spans" in r.stdout
    assert json.loads(out.read_text())["traceEvents"]


# ---------------------------------------------------------------------------
# Instrumentation acceptance.
# ---------------------------------------------------------------------------

def test_serve_generate_spans_and_latency():
    from repro.models.gan import GanConfig, init_gan
    from repro.serve.gan import GanServer

    cfg = GanConfig(name="dcgan", channel_scale=0.03125)
    g, _ = init_gan(cfg, jax.random.PRNGKey(0))
    srv = GanServer(cfg, g, batch_size=2)
    sink = obs.enable()
    srv.generate(3)
    srv.generate(1)
    obs.disable()

    reqs = sink.spans("serve.generate")
    assert [s["attrs"]["n"] for s in reqs] == [3, 1]
    assert reqs[0]["attrs"]["batches"] == 2
    assert reqs[1]["attrs"]["batches"] == 0          # all from buffer
    # the traced call nests the program span under the request span
    apply_spans = sink.spans("program.apply")
    assert apply_spans and apply_spans[0]["attrs"]["traced"] is True
    first, span = reqs[0], apply_spans[0]
    assert span["depth"] > first["depth"]
    assert first["ts_us"] <= span["ts_us"]
    assert span["ts_us"] + span["dur_us"] <= \
        first["ts_us"] + first["dur_us"]
    # each layer is named in the executable, not by a host span
    prog = srv.program
    x = jnp.zeros((2, cfg.z_dim), jnp.float32)
    text = jax.jit(prog.forward).lower(g, x).compile().as_text()
    for layer in ["proj"] + [le.name for le in prog.spec.layers]:
        assert f'op_name="jit(forward)/layer.{layer}/' in text, layer

    # registry-backed accounting: attribute API + invariant intact
    assert srv.samples_served + srv.samples_buffered + \
        srv.samples_discarded == srv.batches_served * 2
    lat = srv._m_request_us
    assert lat.count == 2 and lat.percentile(99) >= lat.percentile(50)
    snap = obs.snapshot()
    key = f"serve.samples_served{{server={srv.server_id}}}"
    assert snap["counters"][key] == srv.samples_served


def test_spans_land_in_the_profiler_host_plane(tmp_path):
    from jax.profiler import ProfileData
    obs.enable()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with obs.trace("probe.outer"):
            with obs.trace("probe.inner"):
                jnp.ones(3).block_until_ready()
    finally:
        jax.profiler.stop_trace()
        obs.disable()
    with obs.trace("probe.disabled"):
        pass
    found = {}
    for path in tmp_path.glob("plugins/profile/*/*.xplane.pb"):
        for plane in ProfileData.from_file(str(path)).planes:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("probe."):
                        found[ev.name] = (plane.name, ev.start_ns,
                                          ev.duration_ns)
    assert set(found) == {"probe.outer", "probe.inner"}
    assert all(plane.startswith("/host:") for plane, _, _ in found.values())
    (_, o0, od), (_, i0, idur) = found["probe.outer"], found["probe.inner"]
    assert o0 <= i0 and i0 + idur <= o0 + od


def test_obs_leaves_jax_unimported():
    r = subprocess.run(
        [sys.executable, "-c",
         "import sys; from repro import obs; obs.enable()\n"
         "with obs.trace('x'): pass\n"
         "assert 'jax' not in sys.modules"],
        capture_output=True, text=True, cwd=str(REPO), env=_cli_env())
    assert r.returncode == 0, r.stderr


def test_resolution_counters_and_program_stats_flag():
    from repro.models.gan import GanConfig
    from repro.program import ProgramSpec

    before = obs.snapshot()["counters"]
    cfg = GanConfig(name="dcgan", channel_scale=0.03125)
    spec = ProgramSpec.build(cfg, 2, "generator")
    after = obs.snapshot()["counters"]

    def delta(name):
        return after.get(name, 0) - before.get(name, 0)

    assert delta("dataflow.resolve") == len(spec.layers)
    assert delta("program.builds") == 1
    by_source = sum(delta(f"dataflow.resolve.{s}")
                    for s in ("pinned", "tuned", "heuristic"))
    assert by_source == len(spec.layers)

    r = subprocess.run(
        [sys.executable, "-m", "repro.program", "dcgan",
         "--role", "generator", "--stats"],
        capture_output=True, text=True, cwd=str(REPO), env=_cli_env())
    assert r.returncode == 0, r.stderr
    assert "resolution stats:" in r.stdout
    assert "dataflow.resolve" in r.stdout
