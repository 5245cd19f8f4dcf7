"""The control, at a size a test run can hold: the reference computed one
step below the configuration's contraction precision (float8 e4m3
operands, the configuration contracting in bf16), put in the program's
place, fails the training cell's limits, which the program as
configured passes.  (The faults are in ``test_harness.py``; on the chip,
at the cells' own sizes, ``bench/calibrate.py`` reads the same
numbers.)"""

import json

import pytest

from bench import calibrate, cells, checks
from bench.tests.conftest import BENCH


@pytest.fixture(scope="module")
def readings(tmp_path_factory):
    from bench.tests.conftest import tiny_config
    cell = cells.Cell(name="tiny.train", chips=1, cfg=tiny_config(),
                      traffic=json.loads((BENCH / "traffic" /
                                          "train.json").read_text()),
                      limits={}, benchmark={}, root=BENCH.parent)
    return {row["kind"]: row
            for row in calibrate.train_readings(cell, [2 ** 31 + 5], 1,
                                                     ("fp8",))}


LIMITS = json.loads((BENCH / "limits" / "dcgan.train.json").read_text())


def test_the_program_passes(readings):
    assert checks.judge(readings["program"], LIMITS)[0], readings["program"]


def test_the_control_fails(readings):
    assert not checks.judge(readings["fp8"], LIMITS)[0], readings["fp8"]

