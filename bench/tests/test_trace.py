"""The trace reduction, on a small trace recorded on the chip
(``bench/testdata``: the training driver's traced first 20 ms of the
tests' tiny dcgan cut, batch 4, on a TPU v5e, with the benchmark's host
annotations; the host's dispatch, not the device, sets its pace)."""

import pytest

from bench import trace
from bench.tests.conftest import BENCH


@pytest.fixture(scope="module")
def summary():
    return trace.reduce(BENCH / "testdata")


def test_window_is_the_bench_window_annotation(summary):
    assert summary.devices == 1
    assert 0.015 < summary.window_s < 0.03


def test_busy_and_idle_partition_the_window(summary):
    assert 0 < summary.busy_s < summary.window_s
    idle = sum(seconds for _, seconds in summary.gaps)
    assert summary.busy_s + idle == pytest.approx(summary.window_s, rel=1e-9)
    assert 0.5 < summary.idle_share < 1.0


def test_ops_are_timed_and_classed_by_the_trace_categories(summary):
    assert summary.class_seconds("conv") > 0
    assert summary.class_seconds("other") > 0
    total = sum(summary.op_seconds.values())
    assert summary.class_seconds("conv") + summary.class_seconds("other") \
        == pytest.approx(total)
    assert any("(convolution fusion)" in op for op in summary.op_seconds)
    assert not any("(unknown)" in op for op in summary.op_seconds)
    top = summary.top_ops(10)
    assert len(top) == 10
    assert [t for _, t in top] == sorted((t for _, t in top), reverse=True)


def test_gaps_are_named_by_the_host_annotation(summary):
    names = {name for name, _ in summary.gaps}
    assert "bench.step" in names
    assert names <= {"bench.step", "bench.wait", "host.other"}
    seconds = [s for _, s in summary.gaps]
    assert seconds == sorted(seconds, reverse=True)


def test_union_and_clip():
    merged = trace._union([(3, 4), (0, 1), (0.5, 2), (2, 2.5)])
    assert merged == [[0, 2.5], [3, 4]]
    assert trace._clip(merged, 1, 3.5) == [[1, 2.5], [3, 3.5]]


def test_a_directory_without_a_trace_is_an_error(tmp_path):
    with pytest.raises(FileNotFoundError):
        trace.reduce(tmp_path)
