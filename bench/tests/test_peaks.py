"""The device stamp and the peaks table: no run measures off the chip,
and no peak is guessed."""

import types

import pytest

from bench import device


def fake(platform, kind):
    return types.SimpleNamespace(platform=platform, device_kind=kind)


def test_the_cpu_is_refused():
    with pytest.raises(device.DeviceError, match="no TPU"):
        device.stamp(1, [fake("cpu", "cpu")])


def test_the_cpu_this_process_runs_on_is_refused():
    with pytest.raises(device.DeviceError):
        device.stamp(1)


def test_too_few_chips_are_refused():
    with pytest.raises(device.DeviceError, match="4 chips"):
        device.stamp(4, [fake("tpu", "TPU v5 lite")])


def test_a_tpu_is_stamped():
    assert device.stamp(1, [fake("tpu", "TPU v5 lite")] * 4) == {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 4}


def test_unknown_kind_is_an_error():
    with pytest.raises(device.DeviceError, match="not in the peaks"):
        device.peaks("TPU v9 imaginary")


def test_v5e_peaks_are_the_published_ones():
    p = device.peaks("TPU v5 lite")
    assert p["flops"]["bf16"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9


def test_run_exits_nonzero_without_a_tpu(capsys):
    from bench import run
    assert run.main(["--workload", "dcgan.train", "--seed", "1",
                     "--seconds", "1"]) == 2
    out = capsys.readouterr()
    assert out.out == ""
    assert "no TPU" in out.err
