"""The control fails the limits that sound runs pass.

At a size a test run holds (the CPU-sized copy of the one-node cell,
whose apply runs in float32), the reference computing the apply one
precision lower (bfloat16) is put in the program's place on three
seeds: each time at least one number exceeds its limit, while the
program's own numbers on the same segments stay within theirs.
"""
import os
import sys
import time

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [REPO, os.path.join(REPO, "src")]

from perf import control, harness  # noqa: E402
from perf.reference import check  # noqa: E402
from perf.tests import tinycell  # noqa: E402


@pytest.fixture(scope="module")
def setup(tmp_path_factory):
    root = tinycell.make(tmp_path_factory.mktemp("bench"))
    cell, devs = harness.open_cell("tiny1.ground", root, False)
    _, program = harness.build(cell, devs)
    ref = harness.reference_setup(cell, ["float32", "bfloat16"])
    return cell, program, ref


@pytest.mark.parametrize("seed", [11, 12, 13])
def test_control_fails_a_limit(setup, seed):
    cell, program, ref = setup
    m = harness.measure(cell, program, seed, 0.3, False, time.perf_counter())
    prog, ctl = harness.reference_readings(
        cell, ref, m["samples"], "float32", control.LOWER["float32"])
    prog, ctl = check.worst(prog), check.worst(ctl)
    limits = cell["workload"]["limits"]
    assert all(prog[k] <= limits[k] for k in limits), prog
    assert any(ctl[k] > limits[k] for k in limits), ctl
